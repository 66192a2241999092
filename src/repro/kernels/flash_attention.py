"""Pallas TPU kernel: fused flash attention (beyond-paper LM-side optimization).

WHY (from the dry-run roofline, EXPERIMENTS.md §Perf): the pure-JAX chunked
attention materializes every (bq, bk) score block to HBM at fusion
boundaries — measured as the dominant memory-term contributor for the
train/prefill cells (arithmetic intensity of the score ops ~26 flop/byte vs
the v5e machine balance of ~240).  Fusing QK^T -> online-softmax -> PV into
one kernel keeps scores in VMEM; traffic drops to Q/K/V/O once each.

Grid: (batch*q_heads, Sq/bq, Sk/bk) — TPU iterates the minor-most (kv) axis
sequentially, so the online-softmax state (m, l, acc) lives in VMEM scratch
across kv steps; the output block is written once on the last kv step.
GQA is expressed in the k/v index_maps (q head -> kv head).

Validated against ``repro.kernels.ref.flash_attention_ref`` in interpret
mode (tests/test_kernels.py); on TPU it lowers to Mosaic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU scratch memories (interpret mode accepts them too)
_SCRATCH = lambda shape: pltpu.VMEM(shape, jnp.float32)

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
    if causal:
        iq = pl.program_id(1)
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ()))
    )
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_fused(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,  # (B, Sk, Hkv, hd)
    *,
    causal: bool = True,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, "pad sequences to block multiples"
    nq, nk = sq // bq, sk // bk

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)

    def kv_head(bh):  # flat q-head id -> flat kv-head id
        return (bh // h) * hkv + (bh % h) // g

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (kv_head(bh), ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (kv_head(bh), ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[_SCRATCH((bq,)), _SCRATCH((bq,)), _SCRATCH((bq, hd))],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
