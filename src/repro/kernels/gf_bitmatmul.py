"""Pallas TPU kernel: GF(2^8) matrix multiply as a GF(2) bit-matrix product on the MXU.

Multiplying by a constant ``a`` in GF(2^8) is linear over GF(2): bit ``p``
of ``a*b`` is the XOR over ``q`` of ``M_a[p, q] * b_q``, where ``M_a[p, q]``
is bit ``p`` of ``a * x^q``.  So

    C (M, N) = A (M, K)  (x)  B (K, N)      over GF(2^8)

is one integer matmul of 0/1 values followed by a parity:

    C_bits (8M, N) = (A_bits (8M, 8K) @ B_bits (8K, N)) & 1

with rows ordered bit-plane major (row ``p*M + i`` is bit ``p`` of row ``i``;
column ``q*K + j`` of ``A_bits`` is bit ``q`` of row ``j`` of B).  ``A_bits``
is built once per call from A with ``xtime`` steps; each (K, BN) tile of B is
unpacked into its 8K bit-rows in VMEM, multiplied on the MXU as int8 with
int32 accumulation (a sum of at most 8K ones, exact), and the 8M result
bit-rows are packed back into M bytes.

This is the kernel for wide coefficient matrices (the DAS square's
(k, k) = (128, 128) parity matrix): ``gf_matmul``'s VPU form unrolls
8*M*K xtime steps at trace time, while here M and K only set the MXU
operands' sizes.  ``kernels/ops.py`` chooses between the two by shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.gf import POLY

DEFAULT_BLOCK_N = 512
_RED = POLY & 0xFF
_ALIGN = 8  # M and K are padded to a multiple of this (zero rows add nothing)


def _xtime(v):
    """Multiply int32 byte values by x in GF(2^8)."""
    return ((v << 1) & 0xFF) ^ (((v >> 7) & 1) * _RED)


def bit_matrix(a: jax.Array) -> jax.Array:
    """A (M, K) uint8 -> A_bits (8M, 8K) int8 of 0/1, bit-plane major on both axes."""
    m, k = a.shape
    v = a.astype(jnp.int32)
    powers = []  # a * x^q for q = 0..7
    for _ in range(8):
        powers.append(v)
        v = _xtime(v)
    prod = jnp.stack(powers)  # (q, M, K)
    bits = jnp.stack([(prod >> p) & 1 for p in range(8)])  # (p, q, M, K)
    return bits.transpose(0, 2, 1, 3).reshape(8 * m, 8 * k).astype(jnp.int8)


def _kernel(a_ref, b_ref, o_ref, *, m: int):
    b = b_ref[...].astype(jnp.int32)  # (K, BN)
    b_bits = jnp.concatenate([(b >> q) & 1 for q in range(8)], axis=0)  # (8K, BN)
    acc = jnp.dot(a_ref[...], b_bits.astype(jnp.int8),
                  preferred_element_type=jnp.int32)  # (8M, BN)
    out = acc[0:m] & 1
    for p in range(1, 8):
        out = out | ((acc[p * m:(p + 1) * m] & 1) << p)
    o_ref[...] = out.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gf_bitmatmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """C = A (x) B over GF(2^8).  a: (M, K) uint8, b: (K, N) uint8 -> (M, N).

    M and K are padded to a multiple of 8 and N to a multiple of block_n
    internally.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    m_pad, k_pad, n_pad = -m % _ALIGN, -k % _ALIGN, -n % block_n
    a = jnp.pad(a, ((0, m_pad), (0, k_pad)))
    b = jnp.pad(b, ((0, k_pad), (0, n_pad)))
    mp, kp = a.shape
    a_bits = bit_matrix(a)
    out = pl.pallas_call(
        functools.partial(_kernel, m=mp),
        grid=(b.shape[1] // block_n,),
        in_specs=[
            pl.BlockSpec((8 * mp, 8 * kp), lambda i: (0, 0)),
            pl.BlockSpec((kp, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((mp, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((mp, b.shape[1]), jnp.uint8),
        interpret=interpret,
    )(a_bits, b)
    return out[:m, :n]
