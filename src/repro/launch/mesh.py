"""Production mesh definitions.

Axes are ``Auto``: the model code places arrays with
``with_sharding_constraint``, which jax 0.9 accepts only on Auto axes
(``jax.make_mesh`` defaults to Explicit).

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 1
    for s in shape:
        ndev *= s
    devices = jax.devices()[:ndev]
    if len(devices) < ndev:
        raise RuntimeError(
            f"mesh {shape} needs {ndev} devices, have {len(jax.devices())} "
            "(dry-runs must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax)"
        )
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    devices = jax.devices()[: data * model]
    return jax.make_mesh((data, model), ("data", "model"), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
