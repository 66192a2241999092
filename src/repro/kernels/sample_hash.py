"""Pallas TPU kernel: bulk audit-sample hashing.

Shelby's internal audits (§4.1) hash 1 KiB samples at high frequency: every
SP answers per-epoch challenges and every auditor re-hashes received samples
to verify Merkle proofs.  At production scale that is millions of 1 KiB
digests per epoch per SP — a bandwidth-bound bulk op worth a kernel.

TPU adaptation (DESIGN.md §3): TPUs have no SHA engine and byte-gather is
slow, so the *bulk* path uses an xxhash32-style word mixer over uint32 lanes
(protocol-grade SHA-256 stays on the coordination layer).  Leaves live on
the vector lanes: the wrapper lays the words out as (W, 8, L/8), so word i
of a block's leaves is one (8, lanes) slab of whole tiles, and the kernel
mixes slab after slab into an (8, lanes) accumulator — pure VPU work, with
no lane extracts and nothing held per word beyond one slab.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_LEAVES = 1024  # 8 sublanes x 128 lanes

_P1 = 2654435761
_P2 = 2246822519
_P3 = 3266489917
_P4 = 668265263


def _rotl(x, r):
    return (x << r) | (x >> (32 - r))


def _kernel(w_ref, o_ref, *, words: int, seed: int):
    # w_ref: (W, 8, BL/8) — word i of every leaf in the block; o_ref: (8, BL/8)
    acc = jnp.full(o_ref.shape, jnp.uint32(seed + _P4), jnp.uint32)
    for i in range(words):
        acc = acc + w_ref[i] * jnp.uint32(_P2)
        acc = _rotl(acc, 13) * jnp.uint32(_P1)
    acc = acc ^ (acc >> 15)
    acc = acc * jnp.uint32(_P2)
    acc = acc ^ (acc >> 13)
    acc = acc * jnp.uint32(_P3)
    acc = acc ^ (acc >> 16)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("seed", "block_leaves", "interpret"))
def sample_hash(
    words: jax.Array,
    *,
    seed: int = 0,
    block_leaves: int = DEFAULT_BLOCK_LEAVES,
    interpret: bool = False,
) -> jax.Array:
    """words: (L, W) uint32 -> (L,) uint32 digests.

    ``block_leaves`` is a multiple of 8 x 128 on a TPU, so each block is
    whole (8, 128) tiles.
    """
    leaves, w = words.shape
    pad = -leaves % block_leaves
    words = jnp.pad(words.astype(jnp.uint32), ((0, pad), (0, 0)))
    cols = words.shape[0] // 8
    lanes = block_leaves // 8
    # leaf l -> [:, l // cols, l % cols]: a row-major reshape back restores order
    tiled = words.T.reshape(w, 8, cols)
    out = pl.pallas_call(
        functools.partial(_kernel, words=w, seed=seed),
        grid=(cols // lanes,),
        in_specs=[pl.BlockSpec((w, 8, lanes), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((8, lanes), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, cols), jnp.uint32),
        interpret=interpret,
    )(tiled)
    return out.reshape(-1)[:leaves]
