"""Jitted public wrappers for the PQ-ADC Pallas kernels."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import tune
from ..common import default_interpret, pad_to
from .kernel import (
    make_adc_lookup_call,
    make_adc_lookup_quant_call,
    make_adc_sym_call,
    make_adc_sym_quant_call,
)

__all__ = [
    "adc_sym_cdist",
    "adc_lookup",
    "adc_sym_cdist_quant",
    "adc_lookup_quant",
    "quantize_lut",
]


def _tuned(op: str, param: str, value: Optional[int], K: int,
           interpret: bool, default: int) -> int:
    if value is not None:
        return value
    backend = "pallas_interpret" if interpret else "pallas"
    return tune.tuned(op, param, length=K, window=None, measure=None,
                      backend=backend, default=default)


@functools.partial(jax.jit, static_argnames=("dtype",))
def quantize_lut(lut: jnp.ndarray, dtype: str = "int8"
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-subspace affine quantization of an ADC table.

    ``lut (M, K, K)`` (or ``(M, K)`` query tables) -> ``(q, scale, zero)``
    with ``q`` int8 (symmetric-range, per-subspace affine
    ``v ~ q * scale_m + zero_m``) or bfloat16 (``scale=1``, ``zero=0``).
    ``scale``/``zero`` are ``(M, 1)`` f32, ready for the quantized
    kernels' affine-after-contraction accumulation.
    """
    lut = jnp.asarray(lut, jnp.float32)
    M = lut.shape[0]
    if dtype in ("bf16", "bfloat16"):
        return (lut.astype(jnp.bfloat16), jnp.ones((M, 1), jnp.float32),
                jnp.zeros((M, 1), jnp.float32))
    if dtype != "int8":
        raise ValueError(f"unsupported LUT quantization dtype: {dtype!r}")
    flat = lut.reshape(M, -1)
    lo = flat.min(axis=1, keepdims=True)
    hi = flat.max(axis=1, keepdims=True)
    zero = (hi + lo) * 0.5
    scale = jnp.maximum(hi - lo, 1e-12) / 254.0
    q = jnp.clip(jnp.round((flat - zero) / scale), -127, 127)
    return q.astype(jnp.int8).reshape(lut.shape), scale, zero


@functools.partial(jax.jit, static_argnames=("block_a", "block_b", "interpret"))
def adc_sym_cdist(codes_a: jnp.ndarray, codes_b: jnp.ndarray,
                  lut: jnp.ndarray, block_a: Optional[int] = None,
                  block_b: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Symmetric PQ distance matrix via one-hot MXU contractions.

    ``codes_a (Na, M)``, ``codes_b (Nb, M)`` int32; ``lut (M, K, K)``.
    ``block_a``/``block_b`` default to the tuned launch geometry.
    """
    if interpret is None:
        interpret = default_interpret()
    nA, M = codes_a.shape
    nB = codes_b.shape[0]
    K = lut.shape[-1]
    block_a = _tuned("adc_sym", "block_a", block_a, K, interpret, 128)
    block_b = _tuned("adc_sym", "block_b", block_b, K, interpret, 128)
    block_a = min(block_a, max(8, nA))
    block_b = min(block_b, max(8, nB))
    a = pad_to(codes_a.astype(jnp.int32), block_a, axis=0, value=0)
    b = pad_to(codes_b.astype(jnp.int32), block_b, axis=0, value=0)
    call = make_adc_sym_call(a.shape[0], b.shape[0], M, K,
                             block_a, block_b, interpret)
    return call(a, b, lut.astype(jnp.float32))[:nA, :nB]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def adc_lookup(codes: jnp.ndarray, qlut: jnp.ndarray,
               block: Optional[int] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Asymmetric scan: ``codes (N, M)``, ``qlut (M, K)`` -> ``(N,)``, or
    a batch of scans, each against its own table: ``codes (B, N, M)``,
    ``qlut (B, M, K)`` -> ``(B, N)``."""
    if interpret is None:
        interpret = default_interpret()
    batched = codes.ndim == 3
    c = codes if batched else codes[None]
    q = qlut if batched else qlut[None]
    b, n, M = c.shape
    K = q.shape[-1]
    block = _tuned("adc_lookup", "block", block, K, interpret, 256)
    block = min(block, max(8, n))
    c = pad_to(c.astype(jnp.int32), block, axis=1, value=0)
    call = make_adc_lookup_call(b, c.shape[1], M, K, block, interpret)
    d = call(c, q.astype(jnp.float32))[:, :n, 0]
    return d if batched else d[0]


@functools.partial(jax.jit, static_argnames=("block_a", "block_b", "interpret"))
def adc_sym_cdist_quant(codes_a: jnp.ndarray, codes_b: jnp.ndarray,
                        qlut: jnp.ndarray, scale: jnp.ndarray,
                        zero: jnp.ndarray, block_a: Optional[int] = None,
                        block_b: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Symmetric ADC over a quantized table from :func:`quantize_lut`:
    ``qlut (M, K, K)`` int8/bf16 plus ``scale``/``zero (M, 1)``."""
    if interpret is None:
        interpret = default_interpret()
    nA, M = codes_a.shape
    nB = codes_b.shape[0]
    K = qlut.shape[-1]
    block_a = _tuned("adc_sym", "block_a", block_a, K, interpret, 128)
    block_b = _tuned("adc_sym", "block_b", block_b, K, interpret, 128)
    block_a = min(block_a, max(8, nA))
    block_b = min(block_b, max(8, nB))
    a = pad_to(codes_a.astype(jnp.int32), block_a, axis=0, value=0)
    b = pad_to(codes_b.astype(jnp.int32), block_b, axis=0, value=0)
    call = make_adc_sym_quant_call(a.shape[0], b.shape[0], M, K,
                                   block_a, block_b, interpret)
    return call(a, b, qlut, scale.astype(jnp.float32),
                zero.astype(jnp.float32))[:nA, :nB]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def adc_lookup_quant(codes: jnp.ndarray, qlut: jnp.ndarray,
                     scale: jnp.ndarray, zero: jnp.ndarray,
                     block: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Asymmetric scan over a quantized query table: ``qlut (M, K)``
    int8/bf16 plus ``scale``/``zero (M, 1)`` -> ``(N,)``."""
    if interpret is None:
        interpret = default_interpret()
    n, M = codes.shape
    K = qlut.shape[-1]
    block = _tuned("adc_lookup", "block", block, K, interpret, 256)
    block = min(block, max(8, n))
    c = pad_to(codes.astype(jnp.int32), block, axis=0, value=0)
    call = make_adc_lookup_quant_call(c.shape[0], M, K, block, interpret)
    return call(c, qlut, scale.astype(jnp.float32),
                zero.astype(jnp.float32))[:n, 0]
