"""Pure-jnp oracle for the PQ-ADC kernels (plain gathers, no one-hot)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "adc_sym_cdist_ref",
    "adc_lookup_ref",
    "adc_sym_cdist_quant_ref",
    "adc_lookup_quant_ref",
]


def _dequant(qlut: jnp.ndarray, scale: jnp.ndarray,
             zero: jnp.ndarray) -> jnp.ndarray:
    """Per-subspace affine dequantization back to f32: the quant kernels
    are numerically this table through the f32 oracle."""
    shape = (qlut.shape[0],) + (1,) * (qlut.ndim - 1)
    return (qlut.astype(jnp.float32) * scale.reshape(shape)
            + zero.reshape(shape))


@jax.jit
def adc_sym_cdist_ref(codes_a: jnp.ndarray, codes_b: jnp.ndarray,
                      lut: jnp.ndarray) -> jnp.ndarray:
    def per_sub(am, bm, lut_m):
        return lut_m[am[:, None], bm[None, :]]
    d2 = jnp.sum(jax.vmap(per_sub, in_axes=(1, 1, 0))(
        codes_a.astype(jnp.int32), codes_b.astype(jnp.int32),
        lut.astype(jnp.float32)), axis=0)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


@jax.jit
def adc_lookup_ref(codes: jnp.ndarray, qlut: jnp.ndarray) -> jnp.ndarray:
    if codes.ndim == 3:  # a batch of scans, each against its own table
        return jax.vmap(adc_lookup_ref)(codes, qlut)
    m_idx = jnp.arange(qlut.shape[0])
    d2 = jnp.sum(qlut[m_idx[None, :], codes.astype(jnp.int32)], axis=-1)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


@jax.jit
def adc_sym_cdist_quant_ref(codes_a: jnp.ndarray, codes_b: jnp.ndarray,
                            qlut: jnp.ndarray, scale: jnp.ndarray,
                            zero: jnp.ndarray) -> jnp.ndarray:
    return adc_sym_cdist_ref(codes_a, codes_b, _dequant(qlut, scale, zero))


@jax.jit
def adc_lookup_quant_ref(codes: jnp.ndarray, qlut: jnp.ndarray,
                         scale: jnp.ndarray,
                         zero: jnp.ndarray) -> jnp.ndarray:
    return adc_lookup_ref(codes, _dequant(qlut, scale, zero))
