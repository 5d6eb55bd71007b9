"""DTW lower bounds: Keogh envelopes, LB_Keogh (reversed), LB_Kim, cascade.

All bounds are for *squared* DTW cost, matching :mod:`repro.core.dtw`.

The paper reverses the query/data role of LB_Keogh: envelopes are built once
around the *codebook centroids* at training time, so encoding a fresh series
costs only O(D/M) per bound evaluation.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["keogh_envelope", "lb_keogh", "lb_kim", "lb_cascade", "lb_lut"]


def _shift(x: jnp.ndarray, offset: int, fill: float) -> jnp.ndarray:
    """``x[..., i + offset]`` with out-of-range slots reading ``fill``."""
    if offset == 0:
        return x
    pad = jnp.full(x.shape[:-1] + (abs(offset),), fill, x.dtype)
    if offset > 0:
        return jnp.concatenate([x[..., offset:], pad], axis=-1)
    return jnp.concatenate([pad, x[..., :offset]], axis=-1)


def _rolling_extreme(x: jnp.ndarray, w: int, combine, fill: float
                     ) -> jnp.ndarray:
    """``combine`` over the truncated window ``x[max(0, i-w) .. min(L-1,
    i+w)]`` via doubling: O(L log w) time, O(L) memory.

    The series is padded with ``w`` identity elements (``fill``) per side
    so every centered window is full width ``2w+1``; forward windows
    ``g[s] = combine(pad[s .. s+p-1])`` for the largest power of two
    ``p <= 2w+1`` are built in log2(p) shifted-combine steps, and each
    centered window is the combine of the two (overlapping) ``p``-windows
    that cover it.
    """
    width = 2 * w + 1
    p = 1 << (width.bit_length() - 1)       # largest power of two <= width
    L = x.shape[-1]
    pad = jnp.full(x.shape[:-1] + (w,), fill, x.dtype)
    g = jnp.concatenate([pad, x, pad], axis=-1)
    step = 1
    while step < p:
        g = combine(g, _shift(g, step, fill))
        step *= 2
    # window i spans pad[i .. i+width-1]; its two covering p-windows start
    # at i and i + width - p (p > width/2, so together they cover it all)
    return combine(g[..., :L], g[..., width - p:width - p + L])


@functools.partial(jax.jit, static_argnames=("window",))
def keogh_envelope(x: jnp.ndarray, window: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Upper/lower Keogh envelope: rolling max/min over ``|shift| <= window``.

    ``x`` may be ``(L,)`` or batched ``(..., L)``.  Returns ``(U, L)`` with
    the same shape as ``x``.  Rolling extrema are computed by log-depth
    shifted combines — O(L log window) time and O(L) memory, so a full-width
    envelope (``window >= L``) no longer materializes an O(L^2) shift stack.
    The effective window is clamped to ``L - 1``: shifts beyond the series
    length never contribute.
    """
    x = jnp.asarray(x, jnp.float32)
    L = x.shape[-1]
    w = max(0, min(int(window), L - 1))
    if w == 0:
        return x, x
    upper = _rolling_extreme(x, w, jnp.maximum, -jnp.inf)
    lower = _rolling_extreme(x, w, jnp.minimum, jnp.inf)
    return upper, lower


def lb_keogh(q: jnp.ndarray, upper: jnp.ndarray, lower: jnp.ndarray) -> jnp.ndarray:
    """LB_Keogh(q, c) given c's envelope — a lower bound on squared DTW(q, c).

    Broadcasts: ``q (..., L)`` against envelopes ``(..., L)``.
    """
    above = jnp.where(q > upper, (q - upper) ** 2, 0.0)
    below = jnp.where(q < lower, (lower - q) ** 2, 0.0)
    return jnp.sum(above + below, axis=-1)


def lb_kim(q: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Simplified LB_Kim: first and last points are always aligned by DTW,
    so their squared differences lower-bound the squared DTW cost.

    The end points are read as static slices: an integer index lowers to a
    dynamic slice, which a Pallas TPU kernel cannot hold."""
    kim = (q[..., :1] - c[..., :1]) ** 2 + (q[..., -1:] - c[..., -1:]) ** 2
    return kim[..., 0]


def lb_cascade(q: jnp.ndarray, centroids: jnp.ndarray,
               upper: jnp.ndarray, lower: jnp.ndarray) -> jnp.ndarray:
    """Cascading bound used for the filter-then-refine encoder.

    ``q (L,)`` vs ``centroids (K, L)`` with envelopes ``(K, L)`` each.
    Returns the *tightest available* cheap bound per centroid:
    ``max(LB_Kim, reversed LB_Keogh)`` — both are valid lower bounds, so the
    max is too.
    """
    kim = lb_kim(q[None, :], centroids)
    keogh = lb_keogh(q[None, :], upper, lower)
    return jnp.maximum(kim, keogh)


def lb_lut(q_segs: jnp.ndarray, centroids: jnp.ndarray,
           upper: jnp.ndarray, lower: jnp.ndarray) -> jnp.ndarray:
    """Cascaded lower-bound table for the asymmetric query LUT.

    ``q_segs (..., M, S)`` vs ``centroids (M, K, S)`` with envelopes
    ``(M, K, S)`` -> ``(..., M, K)``; every entry lower-bounds the
    corresponding squared subspace distance in ``pq.query_lut``, so
    code-wise sums of this table lower-bound the asymmetric ADC distance.
    """
    kim = lb_kim(q_segs[..., None, :], centroids)
    keogh = lb_keogh(q_segs[..., None, :], upper, lower)
    return jnp.maximum(kim, keogh)
