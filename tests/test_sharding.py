"""Logical-axis rules + multi-device equivalence (8 host devices, subprocess)."""
import os
import subprocess
import sys
import textwrap


from repro.sharding import DECODE_RULES, TRAIN_RULES, logical_to_spec


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_divisible_dims_shard():
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = logical_to_spec(("vocab", "embed"), (102400, 2048), TRAIN_RULES, mesh)
    assert spec == __import__("jax").sharding.PartitionSpec("model", "data")


def test_non_divisible_dims_replicate():
    mesh = FakeMesh({"data": 16, "model": 16})
    # hymba: 25 heads don't divide 16 -> replicated (trailing Nones trimmed)
    spec = logical_to_spec(("embed", "heads", "head_dim"), (1600, 25, 64), TRAIN_RULES, mesh)
    assert len(spec) < 2 or spec[1] is None


def test_axis_never_used_twice():
    mesh = FakeMesh({"data": 16, "model": 16})
    # decode rules put ('data','model') on embed and vocab: second one drops
    spec = logical_to_spec(("vocab", "embed"), (256000, 12288), DECODE_RULES, mesh)
    used = [a for part in spec if part for a in (part if isinstance(part, tuple) else (part,))]
    assert len(used) == len(set(used))


def test_missing_mesh_axes_filtered():
    mesh = FakeMesh({"data": 4, "model": 2})  # no 'pod'
    spec = logical_to_spec(("batch", "seq"), (32, 128), TRAIN_RULES, mesh)
    assert spec == __import__("jax").sharding.PartitionSpec("data")


_DISTRIBUTED_DRIVER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from repro.configs import get_smoke
    from repro.models.model import build
    from repro.models import moe as moe_mod
    from repro.sharding import AxisCtx, TRAIN_RULES, DECODE_RULES, init_params, tree_shardings
    import dataclasses

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)

    # --- MoE: shard_map EP vs pure-local path (no-drop capacity) ---
    cfg = get_smoke("qwen3-moe-30b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    specs = moe_mod.moe_specs(cfg, layers=1)
    params = init_params(specs, jax.random.PRNGKey(1))
    params_l = jax.tree.map(lambda x: x[0], params)
    x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)).astype(np.float32) * 0.3, jnp.bfloat16)
    y_local, aux_l = jax.jit(lambda p, x: moe_mod.apply_moe(p, x, cfg, AxisCtx()))(params_l, x)
    ctx = AxisCtx(dict(TRAIN_RULES), mesh)
    y_dist, aux_d = jax.jit(lambda p, x: moe_mod.apply_moe(p, x, cfg, ctx))(params_l, x)
    d = np.abs(np.asarray(y_local, np.float32) - np.asarray(y_dist, np.float32)).max()
    assert d < 0.05, f"moe mismatch {d}"
    print("MOE_OK", d)

    # --- decode on mesh (incl. shard_map cache update) vs single-device ---
    cfg2 = get_smoke("granite-8b")
    model = build(cfg2)
    p2 = init_params(model.param_specs(), jax.random.PRNGKey(2))
    cache = init_params(model.cache_specs(4, 16), jax.random.PRNGKey(0))
    toks = rng.integers(0, cfg2.vocab, (4, 1)).astype(np.int32)
    lg_local, nc_local = jax.jit(lambda p,c,t: model.decode_step(p,c,t,jnp.int32(3), AxisCtx()))(p2, cache, toks)
    ctx2 = AxisCtx(dict(DECODE_RULES), mesh)
    lg_dist, nc_dist = jax.jit(lambda p,c,t: model.decode_step(p,c,t,jnp.int32(3), ctx2))(p2, cache, toks)
    d2 = np.abs(np.asarray(lg_local, np.float32) - np.asarray(lg_dist, np.float32)).max()
    ck = np.abs(np.asarray(nc_local["k"], np.float32) - np.asarray(nc_dist["k"], np.float32)).max()
    assert d2 < 0.05 and ck < 1e-6, f"decode mismatch {d2} {ck}"
    print("DECODE_OK", d2, ck)

    # --- train step on mesh: loss matches single-device ---
    from repro.train.step import make_train_step
    from repro.train.optimizer import init_state
    batch = {"tokens": rng.integers(0, cfg2.vocab, (4, 16)).astype(np.int32),
             "labels": rng.integers(0, cfg2.vocab, (4, 16)).astype(np.int32)}
    st = init_state(p2)
    _, m_local = jax.jit(make_train_step(cfg2, AxisCtx()))(st, batch)
    st2 = init_state(p2)
    _, m_dist = jax.jit(make_train_step(cfg2, AxisCtx(dict(TRAIN_RULES), mesh)))(st2, batch)
    dl = abs(float(m_local["loss"]) - float(m_dist["loss"]))
    assert dl < 0.02, f"train loss mismatch {dl}"
    print("TRAIN_OK", dl)
""")


def test_distributed_equivalence_8dev():
    """shard_map MoE, sharded-cache decode and distributed train_step match
    their single-device counterparts on an 8-device host mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _DISTRIBUTED_DRIVER],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "MOE_OK" in res.stdout and "DECODE_OK" in res.stdout and "TRAIN_OK" in res.stdout
