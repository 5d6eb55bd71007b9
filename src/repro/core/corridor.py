"""Per-pair adaptive alignment corridors — FastDTW-style coarse projection.

The static Sakoe-Chiba band sweeps ``~window + 1`` register lanes for
every pair even when the true alignment path hugs the diagonal.  This
module bounds the corridor *per pair* from a cheap coarse pass:

1. **PAA downsample** both series by ``factor`` (edge-padded means), so
   the coarse grid is ``Lc = ceil(L / factor)`` cells per side;
2. **banded DTW on the coarse grid**, forward *and* backward, via the
   core anti-diagonal sweep with full tables — ``O((L/factor)^2)`` work;
3. **on-path envelope**: a coarse cell lies on a (near-)optimal path iff
   ``F[i,j] + G[i,j] - cost(i,j) <= opt * (1+rtol) + atol``; per coarse
   anti-diagonal the on-path cells give a ``[lo_c, hi_c]`` range
   (dilated across neighbouring diagonals, since a diagonal move skips
   one);
4. **projection** back to the fine grid with a safety ``radius``,
   intersected with the static band and closed so the envelope satisfies
   the structural invariants the band-compressed kernel needs:
   ``lo`` non-decreasing with per-diagonal drift <= 1 (so the register
   base shifts stay lane rotates), ``lo(0) = 0``, ``lo(2L-2) = L-1``,
   and ``lo <= hi`` everywhere (every diagonal keeps at least one live
   cell, so the DP remains connected).

**Exactness contract.**  The corridor is always a *subset* of the static
band, so the adaptive cost is an upper bound on the static banded cost:
``adaptive >= static``, with equality — bit-identical floats, same
sweep order — whenever the corridor contains the static band's optimal
path.  :func:`certify_adaptive` checks this cheaply at the corridor
boundary: if re-sweeping with the corridor dilated by one cell does not
change the cost, the optimum has converged inside the corridor.  Pairs
that fail the check fall back to a documented *approximate* result
(still a valid banded alignment cost, just over a narrower corridor) —
which is why ``band="adaptive"`` is capability-gated out of the
certified LB cascade, mirroring how measures gate pruning.

The coarse pass always uses plain DTW geometry: the corridor is a
*search-space heuristic*, and a DTW coarse path is a good corridor
predictor for every registered measure; the fine sweep itself runs the
requested measure.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .dtw import _diag_sweep

__all__ = [
    "build_corridor",
    "static_band",
    "clip_to_width",
    "corridor_width",
    "certify_adaptive",
    "corridor_sweep",
]

# on-path tolerance for the coarse through-cost test (f32 accumulation
# order differs between the forward and backward tables)
_RTOL = 1e-4
_ATOL = 1e-5


def _eff_window(length: int, window: Optional[int]) -> int:
    w = length - 1 if window is None else int(window)
    return max(0, min(w, length - 1))


def paa(X: jnp.ndarray, factor: int) -> jnp.ndarray:
    """Piecewise-aggregate downsample ``(N, L) -> (N, ceil(L/factor))``.

    The tail segment is edge-padded so every coarse cell is a mean of
    ``factor`` values.
    """
    n, L = X.shape
    Lc = -(-L // factor)
    pad = Lc * factor - L
    if pad:
        X = jnp.concatenate([X, jnp.repeat(X[:, -1:], pad, axis=1)], axis=1)
    return X.reshape(n, Lc, factor).mean(axis=2)


def static_band(length: int, window: Optional[int]
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The static Sakoe-Chiba envelope as ``(lo, hi)`` int32 ``(2L-1,)``
    arrays — the widest corridor any adaptive envelope is clipped to."""
    L = length
    w = _eff_window(length, window)
    d = jnp.arange(2 * L - 1, dtype=jnp.int32)
    lo = jnp.maximum(jnp.maximum(0, d - (L - 1)), -((w - d) // 2))
    hi = jnp.minimum(jnp.minimum(L - 1, d), (d + w) // 2)
    return lo, hi


@functools.partial(jax.jit, static_argnames=("window", "factor", "radius"))
def build_corridor(A: jnp.ndarray, B: jnp.ndarray,
                   window: Optional[int] = None, *, factor: int = 8,
                   radius: int = 2) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-pair corridor envelopes for zipped batches ``A, B (N, L)``.

    Returns ``(lo, hi)`` int32 arrays of shape ``(N, 2L-1)`` satisfying
    the structural invariants in the module header.  Pure ``jnp`` — safe
    to call inside a jitted caller (``factor``/``radius``/``window`` are
    static).
    """
    N, L = A.shape
    A = A.astype(jnp.float32)
    B = B.astype(jnp.float32)
    w = _eff_window(L, window)
    lo_s, hi_s = static_band(L, w)
    Lc = -(-L // factor)
    if Lc < 4:
        # coarse grid too small to say anything: fall back to the static
        # band (adaptive == static, trivially certified)
        return (jnp.broadcast_to(lo_s, (N, 2 * L - 1)),
                jnp.broadcast_to(hi_s, (N, 2 * L - 1)))

    Ac = paa(A, factor)
    Bc = paa(B, factor)
    wc = min(Lc - 1, w // factor + 2)

    sweep = jax.vmap(
        lambda a, b: _diag_sweep(a, b, wc, return_table=True)[1])
    F = sweep(Ac, Bc)                       # (N, 2Lc-1, Lc): T[i, d-i]
    G = sweep(Ac[:, ::-1], Bc[:, ::-1])[:, ::-1, ::-1]  # cost-to-go

    i_c = jnp.arange(Lc, dtype=jnp.int32)
    d_c = jnp.arange(2 * Lc - 1, dtype=jnp.int32)
    j_mat = d_c[:, None] - i_c[None, :]     # (2Lc-1, Lc)
    cost = (Ac[:, None, :]
            - jnp.take(Bc, jnp.clip(j_mat, 0, Lc - 1), axis=1)) ** 2
    opt = F[:, -1:, -1:]
    through = F + G - cost
    on = ((j_mat >= 0) & (j_mat < Lc)
          & jnp.isfinite(F) & jnp.isfinite(G)
          & (through <= opt * (1.0 + _RTOL) + _ATOL))

    lo_c = jnp.min(jnp.where(on, i_c, Lc), axis=2)      # (N, 2Lc-1)
    hi_c = jnp.max(jnp.where(on, i_c, -1), axis=2)
    # a diagonal move skips one anti-diagonal: cover skipped diagonals
    # from their neighbours
    lo_p = jnp.pad(lo_c, ((0, 0), (1, 1)), constant_values=Lc)
    hi_p = jnp.pad(hi_c, ((0, 0), (1, 1)), constant_values=-1)
    lo_c = jnp.minimum(jnp.minimum(lo_p[:, :-2], lo_p[:, 1:-1]),
                       lo_p[:, 2:])
    hi_c = jnp.maximum(jnp.maximum(hi_p[:, :-2], hi_p[:, 1:-1]),
                       hi_p[:, 2:])

    # project: fine diagonal d intersects the blocks of coarse diagonals
    # floor(d/f)-1 and floor(d/f) only (block span 2f-2 < 2f)
    d_f = jnp.arange(2 * L - 1, dtype=jnp.int32)
    dc0 = jnp.clip(d_f // factor, 0, 2 * Lc - 2)
    dc1 = jnp.maximum(dc0 - 1, 0)
    lo_raw = (factor * jnp.minimum(lo_c[:, dc1], lo_c[:, dc0]) - radius)
    hi_raw = (factor * jnp.maximum(hi_c[:, dc1], hi_c[:, dc0])
              + factor - 1 + radius)

    # structural closure of lo: clamp to feasible cells, enforce
    # "reachable from the left" (lo(d) <= lo(d') + d - d' for d' < d) via
    # a running min of lo - d, then monotonicity via a reverse running
    # min.  Both only *lower* lo, so corridor containment is preserved;
    # the final max with the static band lo (itself non-decreasing with
    # drift <= 1) keeps both invariants and pins lo(0)=0, lo(2L-2)=L-1.
    feas_hi = jnp.minimum(d_f, L - 1)
    lo0 = jnp.minimum(lo_raw, feas_hi)
    lo1 = d_f + jax.lax.cummin(lo0 - d_f, axis=1)
    lo2 = jax.lax.cummin(lo1, axis=1, reverse=True)
    lo = jnp.maximum(lo2, lo_s)
    hi = jnp.maximum(jnp.minimum(hi_raw, hi_s), lo)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def clip_to_width(lo: jnp.ndarray, hi: jnp.ndarray,
                  width: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cap the corridor at the (static) register ``width``.  A clipped
    pair may lose containment of the optimal path — exactly what
    :func:`certify_adaptive` detects."""
    return lo, jnp.minimum(hi, lo + width - 1)


def corridor_width(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Per-pair maximum live cells on any diagonal — the register width
    the pair actually needs."""
    return jnp.max(hi - lo + 1, axis=-1)


def dilate(lo: jnp.ndarray, hi: jnp.ndarray, length: int,
           window: Optional[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Widen the corridor by one cell per side, re-clipped to the static
    band (preserves every structural invariant)."""
    lo_s, hi_s = static_band(length, window)
    return jnp.maximum(lo - 1, lo_s), jnp.minimum(hi + 1, hi_s)


def corridor_sweep(A: jnp.ndarray, B: jnp.ndarray, lo: jnp.ndarray,
                   hi: jnp.ndarray, *, window: Optional[int], width: int,
                   measure=None) -> jnp.ndarray:
    """Adaptive band-compressed sweep on the pure-JAX route:
    ``A, B (N, L)`` with corridors ``(N, 2L-1)`` -> ``(N, 1)`` costs."""
    from ..kernels.dtw_band.kernel import wavefront_compressed
    L = A.shape[1]
    return wavefront_compressed(
        A.astype(jnp.float32), jnp.flip(B.astype(jnp.float32), axis=1),
        length=L,
        window=_eff_window(L, window), width=width, measure=measure,
        corridor=(lo, hi))


@functools.partial(jax.jit, static_argnames=("window", "width", "measure"))
def certify_adaptive(A: jnp.ndarray, B: jnp.ndarray, lo: jnp.ndarray,
                     hi: jnp.ndarray, *, window: Optional[int], width: int,
                     measure=None) -> jnp.ndarray:
    """Corridor-boundary convergence check, per pair -> bool ``(N,)``.

    Re-sweeps with the corridor dilated by one cell (still inside the
    static band): if the cost is unchanged the optimum has converged
    inside the corridor and the adaptive result equals the static-band
    result bit-for-bit whenever the corridor contains the static optimal
    path.  Cost: one extra sweep at ``width + 2`` registers."""
    L = A.shape[1]
    base = corridor_sweep(A, B, lo, hi, window=window, width=width,
                          measure=measure)
    lo_d, hi_d = dilate(lo, hi, L, window)
    wide = corridor_sweep(A, B, lo_d, hi_d, window=window, width=width + 2,
                          measure=measure)
    return (base == wide)[:, 0]
