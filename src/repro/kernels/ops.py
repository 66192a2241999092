"""Jit'd public wrappers around the Pallas kernels, and the one place that
decides whether GF(2^8) decode runs on the host or on the device, and on
which of the two GF kernels.

On CPU (the test suite) the kernels execute with ``interpret=True``; on a
TPU they compile to Mosaic.  ``repro.core``/``repro.storage`` call only
these wrappers, never `pallas_call` directly.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import numpy as np

from repro.kernels import gf_bitmatmul as _gb
from repro.kernels import gf_matmul as _gf
from repro.kernels import ref as _ref
from repro.kernels import sample_hash as _sh
from repro.spans import span

# fixed, so that every run from this checkout finds what earlier runs cached
_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# gf_matmul traffic since the last reset: device -> [kernel calls, bytes of B]
_GF_TRAFFIC: dict[jax.Device, list[int]] = {}


@functools.lru_cache(None)
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins where it is set; otherwise the cache
    lives in ``.jax_cache/`` at the root of the checkout.  Every compile is
    kept, however short: a kernel compiles in one or two seconds.  Call it
    before the first compile of the process.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


# From this many coefficients on, an (M, K) matrix goes to the bit-matrix
# kernel: its (8M, 8K) operand then covers at least one 128 x 128 MXU tile,
# while the VPU kernel's body, which unrolls 8 xtime steps per coefficient,
# already takes seconds to trace (16 x 16).
_BIT_MATRIX_MIN_COEFFS = 256


def uses_bit_matrix(m: int, k: int) -> bool:
    """Whether an (M, K) coefficient matrix runs on the bit-matrix kernel
    (``gf_bitmatmul``, the MXU) rather than ``gf_matmul`` (the VPU): the one
    place that chooses between them, by shape alone."""
    return m * k >= _BIT_MATRIX_MIN_COEFFS


def gf_matmul(a, b, *, device: jax.Device | None = None, block_n: int | None = None):
    """GF(2^8) matmul via a Pallas kernel (interpret-mode off-TPU), the
    kernel chosen by :func:`uses_bit_matrix`.

    Runs on ``device`` (JAX's first device when None) and counts the call
    and the bytes of ``b`` against it (see :func:`gf_traffic`).
    """
    device = device or jax.devices()[0]
    a, b = jax.device_put((a, b), device)
    kwargs = {} if block_n is None else {"block_n": block_n}
    kernel = _gb.gf_bitmatmul if uses_bit_matrix(*a.shape) else _gf.gf_matmul
    out = kernel(a, b, interpret=device.platform != "tpu", **kwargs)
    traffic = _GF_TRAFFIC.setdefault(device, [0, 0])
    traffic[0] += 1
    traffic[1] += b.nbytes
    return out


def gf_matmul_np(a: np.ndarray, b: np.ndarray, *, device: jax.Device | None = None) -> np.ndarray:
    """numpy-in/numpy-out convenience for the storage data path.

    Its span covers the transfer to the device, the dispatch, the wait and
    the copy back to the host."""
    with span("shelby.gf.call"):
        return np.asarray(gf_matmul(np.asarray(a, np.uint8), np.asarray(b, np.uint8),
                                    device=device))


def gf_traffic() -> dict[jax.Device, tuple[int, int]]:
    """(kernel calls, bytes of B) per device since the last reset."""
    return {dev: (calls, nbytes) for dev, (calls, nbytes) in _GF_TRAFFIC.items()}


def reset_gf_traffic() -> None:
    _GF_TRAFFIC.clear()


def gf_compilations() -> int:
    """Distinct GF matmul programs compiled in this process (one per shape)."""
    return _gf.gf_matmul._cache_size() + _gb.gf_bitmatmul._cache_size()


def resolve_decode_matmul(choice: str = "auto", device: jax.Device | None = None):
    """The GF matmul the Clay decode uses: the one place that chooses it.

    * ``"numpy"``  -> ``None``: the pure-numpy GF(2^8) path.
    * ``"pallas"`` -> :func:`gf_matmul_np`, bound to ``device`` when one is
      given (interpret mode off-TPU, which is slow: only force it to
      exercise the kernel).
    * ``"auto"``   -> pallas when ``device`` (or, without one, JAX's default
      backend) is a TPU, numpy otherwise.
    """
    if choice == "auto":
        platform = device.platform if device is not None else jax.default_backend()
        choice = "pallas" if platform == "tpu" else "numpy"
    if choice == "numpy":
        return None
    if choice == "pallas":
        if device is None:
            return gf_matmul_np
        return functools.partial(gf_matmul_np, device=device)
    raise ValueError(f"decode_matmul must be auto|numpy|pallas, got {choice!r}")


def gf_matmul_ref(a, b):
    return _ref.gf_matmul_ref(a, b)


def sample_hash(words, *, seed: int = 0):
    """Bulk sample digests via the Pallas kernel (interpret-mode off-TPU)."""
    return _sh.sample_hash(words, seed=seed, interpret=not _on_tpu())


def sample_hash_ref(words, seed: int = 0):
    return _ref.sample_hash_ref(words, seed)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512, bk: int = 512):
    """Fused flash attention via the Pallas kernel (interpret-mode off-TPU)."""
    from repro.kernels import flash_attention as _fa

    return _fa.flash_attention_fused(q, k, v, causal=causal, bq=bq, bk=bk,
                                     interpret=not _on_tpu())


def flash_attention_ref(q, k, v, causal: bool = True):
    return _ref.flash_attention_ref(q, k, v, causal)
