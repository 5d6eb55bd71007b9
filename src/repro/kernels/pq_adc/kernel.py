"""PQ asymmetric/symmetric distance-computation (ADC) Pallas kernels.

The distance scan over PQ codes is a gather+reduce; TPU gathers are slow, so
lookups are rewritten as one-hot contractions that land on the MXU:

  * symmetric cdist:  d2[i, j] = sum_m LUT[m, a_i^m, b_j^m]
        per subspace:  onehot(a^m) @ LUT[m] @ onehot(b^m)^T   (two matmuls)
  * asymmetric scan:  d2[n] = sum_m QLUT[m, c_n^m]
        per subspace:  onehot(c^m) @ QLUT[m]                  (one matvec)

K (=256 by default) is MXU-lane aligned, so the one-hot matrices tile
perfectly.  LUT/QLUT live fully in VMEM (M*K*K*4 bytes = 1 MiB for M=4,
K=256); code tiles stream through the grid.

Quantized LUT variants (``*_quant_kernel``) take the table as int8 or
bfloat16 with per-subspace affine parameters ``scale``/``zero`` — the
resident LUT shrinks 4x (int8) or 2x (bf16).  Because each one-hot
contraction *selects* exactly one table entry per subspace, the affine
map commutes with the contraction: the kernels accumulate
``scale_m * contraction + zero_m`` per subspace, which equals running
the f32 kernel on the dequantized table (up to the quantization error
itself — see :func:`repro.kernels.pq_adc.ops.quantize_lut`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "make_adc_sym_call",
    "make_adc_lookup_call",
    "make_adc_sym_quant_call",
    "make_adc_lookup_quant_call",
]


# f32 tables select through the MXU at full f32 precision: the default
# single bf16 pass would round every selected LUT entry to 8 mantissa bits
# (measured on a TPU v5e: 1e-3 relative error against the gather
# reference).  The quantized tables hold int8 / bf16 values, which one
# bf16 pass selects exactly.
_EXACT = jax.lax.Precision.HIGHEST


def _one_hot(codes_col: jnp.ndarray, K: int) -> jnp.ndarray:
    """``codes_col (B,)`` int32 -> ``(B, K)`` float32 one-hot (iota compare)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (codes_col.shape[0], K), 1)
    return (iota == codes_col[:, None]).astype(jnp.float32)


def adc_sym_kernel(a_ref, b_ref, lut_ref, o_ref, *, n_sub: int, K: int):
    """``a_ref (bA, M)``, ``b_ref (bB, M)``, ``lut_ref (M, K, K)`` ->
    ``o_ref (bA, bB)`` = sqrt(sum_m LUT[m, a^m, b^m])."""
    a = a_ref[...]
    b = b_ref[...]
    acc = jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
    for m in range(n_sub):  # static unroll: M is small
        a_oh = _one_hot(a[:, m], K)                    # (bA, K)
        b_oh = _one_hot(b[:, m], K)                    # (bB, K)
        mid = jax.lax.dot_general(
            a_oh, lut_ref[m], (((1,), (0,)), ((), ())),
            precision=_EXACT,
            preferred_element_type=jnp.float32)        # (bA, K)
        acc += jax.lax.dot_general(
            mid, b_oh, (((1,), (1,)), ((), ())),
            precision=_EXACT,
            preferred_element_type=jnp.float32)        # (bA, bB)
    o_ref[...] = jnp.sqrt(jnp.maximum(acc, 0.0))


def adc_lookup_kernel(c_ref, qlut_ref, o_ref, *, n_sub: int, K: int):
    """``c_ref (B, M)``, ``qlut_ref (M, K)`` -> ``o_ref (B, 1)`` distances."""
    c = c_ref[...]
    acc = jnp.zeros((c.shape[0], 1), jnp.float32)
    for m in range(n_sub):
        oh = _one_hot(c[:, m], K)                      # (B, K)
        acc += jax.lax.dot_general(
            oh, qlut_ref[m][:, None], (((1,), (0,)), ((), ())),
            precision=_EXACT,
            preferred_element_type=jnp.float32)        # (B, 1)
    o_ref[...] = jnp.sqrt(jnp.maximum(acc, 0.0))


def adc_sym_quant_kernel(a_ref, b_ref, qlut_ref, sc_ref, zp_ref, o_ref, *,
                         n_sub: int, K: int):
    """Quantized-LUT symmetric ADC: ``qlut_ref (M, K, K)`` int8/bf16 with
    per-subspace affine ``sc_ref``/``zp_ref (M, 1)`` f32 ->
    ``o_ref (bA, bB)``.  The affine is applied *after* each subspace
    contraction (the one-hot selection commutes with it)."""
    a = a_ref[...]
    b = b_ref[...]
    acc = jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
    for m in range(n_sub):  # static unroll: M is small
        a_oh = _one_hot(a[:, m], K)
        b_oh = _one_hot(b[:, m], K)
        mid = jax.lax.dot_general(
            a_oh, qlut_ref[m].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        sel = jax.lax.dot_general(
            mid, b_oh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc += sc_ref[m, 0] * sel + zp_ref[m, 0]
    o_ref[...] = jnp.sqrt(jnp.maximum(acc, 0.0))


def adc_lookup_quant_kernel(c_ref, qlut_ref, sc_ref, zp_ref, o_ref, *,
                            n_sub: int, K: int):
    """Quantized-LUT asymmetric scan: ``qlut_ref (M, K)`` int8/bf16 plus
    ``sc_ref``/``zp_ref (M, 1)`` f32 -> ``o_ref (B, 1)``."""
    c = c_ref[...]
    acc = jnp.zeros((c.shape[0], 1), jnp.float32)
    for m in range(n_sub):
        oh = _one_hot(c[:, m], K)
        sel = jax.lax.dot_general(
            oh, qlut_ref[m].astype(jnp.float32)[:, None],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc += sc_ref[m, 0] * sel + zp_ref[m, 0]
    o_ref[...] = jnp.sqrt(jnp.maximum(acc, 0.0))


def make_adc_sym_call(nA: int, nB: int, n_sub: int, K: int,
                      block_a: int, block_b: int, interpret: bool):
    kernel = functools.partial(adc_sym_kernel, n_sub=n_sub, K=K)
    return pl.pallas_call(
        kernel,
        grid=(nA // block_a, nB // block_b),
        in_specs=[
            pl.BlockSpec((block_a, n_sub), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, n_sub), lambda i, j: (j, 0)),
            pl.BlockSpec((n_sub, K, K), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, block_b), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nA, nB), jnp.float32),
        interpret=interpret,
    )


def make_adc_lookup_call(b: int, n: int, n_sub: int, K: int, block: int,
                         interpret: bool):
    """``b`` scans, each of ``n`` code rows against its own table: codes
    ``(b, n, M)`` and tables ``(b, M, K)`` -> ``(b, n, 1)``.  The scans
    are the grid's leading axis."""
    kernel = functools.partial(adc_lookup_kernel, n_sub=n_sub, K=K)
    return pl.pallas_call(
        kernel,
        grid=(b, n // block),
        in_specs=[
            pl.BlockSpec((None, block, n_sub), lambda q, i: (q, i, 0)),
            pl.BlockSpec((None, n_sub, K), lambda q, i: (q, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block, 1), lambda q, i: (q, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, 1), jnp.float32),
        interpret=interpret,
    )


def make_adc_sym_quant_call(nA: int, nB: int, n_sub: int, K: int,
                            block_a: int, block_b: int, interpret: bool):
    kernel = functools.partial(adc_sym_quant_kernel, n_sub=n_sub, K=K)
    return pl.pallas_call(
        kernel,
        grid=(nA // block_a, nB // block_b),
        in_specs=[
            pl.BlockSpec((block_a, n_sub), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, n_sub), lambda i, j: (j, 0)),
            pl.BlockSpec((n_sub, K, K), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((n_sub, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((n_sub, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, block_b), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nA, nB), jnp.float32),
        interpret=interpret,
    )


def make_adc_lookup_quant_call(n: int, n_sub: int, K: int, block: int,
                               interpret: bool):
    kernel = functools.partial(adc_lookup_quant_kernel, n_sub=n_sub, K=K)
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, n_sub), lambda i: (i, 0)),
            pl.BlockSpec((n_sub, K), lambda i: (0, 0)),
            pl.BlockSpec((n_sub, 1), lambda i: (0, 0)),
            pl.BlockSpec((n_sub, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )
