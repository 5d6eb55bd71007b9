"""Banded-DTW wavefront Pallas kernels.

Two generations of the same anti-diagonal sweep live here:

``dtw_band_kernel`` (full-width, legacy)
    The two live diagonals are ``(block, L)`` registers and the Sakoe-Chiba
    band is only a *mask*: every wavefront step still pays for all ``L``
    lanes, so at the paper's default ``w = 0.1*L`` roughly ``L/(w+1) ~ 5-10x``
    of the VPU work is thrown away.  Kept as the benchmark baseline.

``dtw_band_compressed_kernel`` (band-compressed)
    The registers hold only the *feasible* cells of each diagonal.  On
    anti-diagonal ``d`` the valid rows are ``i in [lo(d), hi(d)]`` with

        lo(d) = max(0, d - (L-1), ceil((d-w)/2))
        hi(d) = min(L-1, d,        floor((d+w)/2))

    so at most ``w + 1`` cells are live; the register width is
    ``W = min(L, roundup(min(w, L-1) + 1, lane))`` — per-step cost scales
    with the band, not the series length.  Sequential depth stays ``2L-1``.

    Compressed-coordinate recurrence: slot ``t`` on diagonal ``d`` is cell
    ``i = lo(d) + t``.  Its predecessors sit at slots shifted by the *base
    drift* between consecutive diagonals:

        (i,   j-1) on d-1  ->  t + s1,      s1 = lo(d) - lo(d-1)   in {0, 1}
        (i-1, j  ) on d-1  ->  t + s1 - 1
        (i-1, j-1) on d-2  ->  t + s2,      s2 = lo(d) - lo(d-2) - 1
                                                                in {-1, 0, 1}

    All shifts are lane rotates selected by the (scalar) drift — no gathers.

TPU notes (both kernels):
  * every kernel takes the second operand already *reversed* along time
    (``b_rev = b[:, ::-1]``, one XLA op in the ops layer) — Mosaic has no
    in-kernel ``rev``;
  * the diagonal gather ``b[d - i]`` is then a window of a lane-padded copy
    of ``b_rev``, read as a lane rotate by the scalar diagonal offset
    (``pltpu.roll``) plus a static prefix slice — no dynamic slices, no
    scatter/gather ops;
  * the band geometry is integer arithmetic on the loop counter, so shapes
    never depend on data.

Measure-generic: the band-compressed sweep takes a static
:class:`repro.core.measures.MeasureSpec` whose per-move costs are inlined
into the wavefront step, so one kernel body serves DTW, WDTW, ERP and MSM
(plus anything registered later).  ERP-style virtual first rows/columns
are prefix sums of gap costs, sliced per diagonal exactly like the series
values.  The legacy full-width kernel stays DTW-only.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import measures
from ...core.dispatch import effective_window
from ...core.measures import MeasureArg
from ..common import CompiledRouteUnsupported, carry_full, cdiv

__all__ = [
    "dtw_band_kernel",
    "dtw_band_compressed_kernel",
    "dtw_band_cdist_kernel",
    "dtw_band_adaptive_kernel",
    "make_dtw_band_call",
    "make_dtw_band_cdist_call",
    "band_width",
    "wavefront_compressed",
]

_NEG_SAFE_INF = 3.0e38  # finite stand-in for +inf (avoids inf-inf NaNs)
_LANES = 128            # TPU vreg lane count: rotates run on whole lane tiles


def _pad_lanes(x: jnp.ndarray, left: int = 0,
               min_width: int = 0) -> jnp.ndarray:
    """Zero-pad ``x (rows, n)`` with ``left`` lanes in front and enough
    behind to reach a multiple of 128 lanes that is at least
    ``min_width``."""
    rows, n = x.shape
    total = cdiv(max(left + n, min_width), _LANES) * _LANES
    parts = [jnp.zeros((rows, left), x.dtype)] if left else []
    parts.append(x)
    if total > left + n:
        parts.append(jnp.zeros((rows, total - left - n), x.dtype))
    return jnp.concatenate(parts, axis=1)


def _window(padded: jnp.ndarray, base, width: int) -> jnp.ndarray:
    """``padded[:, base:base + width]`` for a scalar ``base`` (the caller
    guarantees ``base + width <= padded.shape[1]``): a lane rotate that
    brings ``base`` to lane 0, then a static prefix slice — what Mosaic
    lowers in place of a dynamic slice."""
    total = padded.shape[1]
    return pltpu.roll(padded, (total - base) % total, axis=1)[:, :width]


def band_width(length: int, window: Optional[int], lane: int = 8) -> int:
    """Compressed register width: band cells padded up to a lane multiple,
    capped at ``length`` (beyond which compression cannot help).

    Contract: when ``min(window, length-1) + 1`` is already a lane
    multiple the width is exactly that cell count — no extra lane of
    padding is ever added on an aligned band.
    """
    w = length if window is None else int(window)
    need = min(w, length - 1) + 1
    if need % lane == 0:            # aligned band: width == cell count
        return min(length, need)
    return min(length, -(-need // lane) * lane)


# ---------------------------------------------------------------------------
# Full-width kernel (legacy / benchmark baseline)
# ---------------------------------------------------------------------------

def dtw_band_kernel(a_ref, b_rev_ref, o_ref, *, length: int, window: int,
                    block: int):
    """Kernel body: ``a_ref (block, L)``, ``b_rev_ref (block, L)`` (time
    reversed) -> ``o_ref (block, 1)`` squared banded DTW costs."""
    L = length
    a = a_ref[...].astype(jnp.float32)
    b_rev = b_rev_ref[...].astype(jnp.float32)

    idx = jax.lax.broadcasted_iota(jnp.int32, (block, L), 1)
    # b_big[:, L + t] == b_rev[:, t]; diagonal d needs v[i] = b[d - i]
    #   = b_rev[i + L - 1 - d] = b_big[:, i + 2L - 1 - d].
    b_big = _pad_lanes(b_rev, left=L, min_width=3 * L)

    inf = jnp.float32(_NEG_SAFE_INF)

    def step(d, carry):
        prev1, prev2 = carry
        j = d - idx
        valid = (j >= 0) & (j < L) & (jnp.abs(idx - j) <= window)
        v = _window(b_big, 2 * L - 1 - d, L)
        cost = (a - v) ** 2

        shift1 = jnp.where(idx == 0, inf, jnp.roll(prev1, 1, axis=1))
        shift2 = jnp.where(idx == 0, inf, jnp.roll(prev2, 1, axis=1))
        best = jnp.minimum(jnp.minimum(shift2, prev1), shift1)
        best = jnp.where((idx == 0) & (d == 0), 0.0, best)
        diag = jnp.where(valid, cost + best, inf)
        # clamp so accumulating inf + cost never overflows to inf*2
        diag = jnp.minimum(diag, inf)
        return diag, prev1

    init = carry_full((block, L), _NEG_SAFE_INF)
    last, _ = jax.lax.fori_loop(0, 2 * L - 1, step, (init, init))
    o_ref[...] = last[:, L - 1:L]


# ---------------------------------------------------------------------------
# Band-compressed kernel
# ---------------------------------------------------------------------------

def _prefix_sum(x: jnp.ndarray, length: int,
                reverse: bool = False) -> jnp.ndarray:
    """Inclusive prefix sum along axis 1 (suffix sum with ``reverse``) —
    log-depth shifted adds (rolls + masks only, so it lowers inside a
    Pallas kernel body; no cumsum primitive).  The suffix sum of a
    reversed row performs the mirrored additions of the prefix sum of the
    row, so it equals the reversed prefix sum bit for bit."""
    t = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < length:
        if reverse:
            moved = jnp.where(t < length - shift,
                              jnp.roll(x, -shift, axis=1), 0.0)
        else:
            moved = jnp.where(t >= shift, jnp.roll(x, shift, axis=1), 0.0)
        x = x + moved
        shift *= 2
    return x


def wavefront_compressed(a: jnp.ndarray, b_rev: jnp.ndarray, *, length: int,
                         window: int, width: int,
                         measure: MeasureArg = None,
                         corridor=None) -> jnp.ndarray:
    """Band-compressed anti-diagonal sweep over zipped pair *arrays*.

    ``a (rows, L)`` vs ``b (rows, L)``, the latter passed time-reversed as
    ``b_rev = b[:, ::-1]`` -> ``(rows, 1)`` banded elastic cost under
    ``measure`` (squared banded DTW by default).  This is the
    in-register DP shared by :func:`dtw_band_compressed_kernel`, the fused
    LB-cascade refine and the fused pre-align+encode kernel (which calls it
    on segment x centroid pairs it has just built in VMEM) — everything
    stays ``(rows, width)`` with ``width ~ window + 1``.

    The measure spec is static: its per-move costs are inlined into the
    step, and ERP-style measures additionally thread their virtual first
    row/column (prefix sums of gap costs, sliced per diagonal exactly like
    the series values) through the same sweep.

    ``corridor`` switches the sweep to *per-pair adaptive bands*: a pair of
    ``(rows, 2L-1)`` int32 arrays ``(lo_arr, hi_arr)`` giving each pair's
    feasible cell range on every anti-diagonal (see
    :mod:`repro.core.corridor` for the builder and the structural
    invariants: ``lo`` non-decreasing with per-diagonal drift <= 1,
    ``lo(0) = 0``, ``lo(2L-2) = L-1``, ``lo <= hi``).  Registers stay
    ``(rows, width)``; the per-row base offsets turn the value windows into
    ``take_along_axis`` gathers and the predecessor shifts into per-row
    rotate-selects — no shapes depend on data.  With ``corridor=None`` the
    static Sakoe-Chiba geometry is traced exactly as before.  The per-row
    gathers do not lower on the TPU; the compiled routes refuse a corridor
    (:class:`repro.kernels.common.CompiledRouteUnsupported`).
    """
    spec = measures.resolve(measure)
    L, w, W = length, window, width
    rows = a.shape[0]
    adaptive = corridor is not None
    if adaptive:
        lo_arr, hi_arr = corridor
        lo_arr = lo_arr.astype(jnp.int32)
        hi_arr = hi_arr.astype(jnp.int32)

    inf = jnp.float32(_NEG_SAFE_INF)
    t = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)

    # Lane-padded copies so the per-diagonal windows are rotate + slice:
    #   a cells:  a[lo + t]              -> window of a_pad at lo
    #   b cells:  b[d - lo - t]
    #           = b_rev[L-1-d+lo + t]    -> window of b_rev_pad at L-1-d+lo
    # (0 <= lo <= L-1 and 0 <= L-1-d+lo <= L-1 for every feasible diagonal,
    # so every window ends inside the L + W padded lanes.)
    def padded(x):
        return _pad_lanes(x, min_width=L + W)

    a_pad = padded(a)
    b_rev_pad = padded(b_rev)

    if spec.uses_neighbors:
        # a_{i-1} / b_{j-1} values (sentinel = element 0 at the borders,
        # where the corresponding move reads an inf predecessor anyway);
        # reversed, b_{j-1} is b_rev shifted one lane left
        a_prev = jnp.concatenate([a[:, :1], a[:, :-1]], axis=1)
        b_prev_rev = jnp.concatenate([b_rev[:, 1:], b_rev[:, -1:]], axis=1)
        a_prev_pad = padded(a_prev)
        b_prev_rev_pad = padded(b_prev_rev)
    if spec.uses_gap_border:
        # virtual first column/row: T[i, -1] = ga[i], T[-1, j] = gb[j];
        # gb reversed is the suffix sum of b_rev's gap costs
        ga = _prefix_sum(measures.gap_costs(spec, a), L)
        gb_rev = _prefix_sum(measures.gap_costs(spec, b_rev), L,
                             reverse=True)
        zero = jnp.zeros((rows, 1), jnp.float32)
        ga_prev = jnp.concatenate([zero, ga[:, :-1]], axis=1)
        gb_prev_rev = jnp.concatenate([gb_rev[:, 1:], zero], axis=1)
        ga_pad = padded(ga)
        ga_prev_pad = padded(ga_prev)
        gb_rev_pad = padded(gb_rev)
        gb_prev_rev_pad = padded(gb_prev_rev)

    def lo_of(d):
        # max(0, d - (L-1), ceil((d - w) / 2)); jnp // is floor division.
        return jnp.maximum(jnp.maximum(0, d - (L - 1)), -((w - d) // 2))

    def read(reg, s):
        """``reg[t + s]`` for scalar shift ``s`` in {-1, 0, 1}; out-of-range
        slots read the +inf sentinel (lane rotate + edge mask, gather-free)."""
        left = jnp.where(t == W - 1, inf, jnp.roll(reg, -1, axis=1))
        right = jnp.where(t == 0, inf, jnp.roll(reg, 1, axis=1))
        return jnp.where(s == 0, reg, jnp.where(s > 0, left, right))

    def step(d, carry):
        prev1, prev2 = carry  # compressed diagonals d-1 / d-2, inf-masked
        if adaptive:
            def band_at(arr, dd):
                return jax.lax.dynamic_slice_in_dim(
                    arr, jnp.maximum(dd, 0), 1, axis=1)

            lo = band_at(lo_arr, d)                      # (rows, 1)
            hi = band_at(hi_arr, d)
            s1 = lo - band_at(lo_arr, d - 1)             # in {0, 1}
            s2 = lo - band_at(lo_arr, d - 2) - 1         # in {-1, 0, 1}

            def fetch(arr, base):
                return jnp.take_along_axis(arr, base + t, axis=1)
        else:
            lo = lo_of(d)
            hi = jnp.minimum(jnp.minimum(L - 1, d), (d + w) // 2)
            s1 = lo - lo_of(d - 1)
            s2 = lo - lo_of(d - 2) - 1

            def fetch(arr, base):
                return _window(arr, base, W)
        off_b = L - 1 - d + lo

        av = fetch(a_pad, lo)
        bv = fetch(b_rev_pad, off_b)
        i_arr = lo + t
        xp = fetch(a_prev_pad, lo) if spec.uses_neighbors else None
        yp = fetch(b_prev_rev_pad, off_b) if spec.uses_neighbors else None
        dd = jnp.abs(2 * i_arr - d) if spec.uses_position else None
        c_d, c_v, c_h = measures.move_costs(spec, av, bv, xp, yp, dd, L)

        # Predecessor slots (see module header): horiz (i, j-1) at t + s1
        # on d-1, vert (i-1, j) at t + s1 - 1 on d-1, diag (i-1, j-1) at
        # t + s2 on d-2.  In adaptive mode s1/s2 are (rows, 1) columns and
        # the rotate-select in ``read`` broadcasts per row.
        pred_h = read(prev1, s1)
        pred_v = read(prev1, s1 - 1)
        pred_d = read(prev2, s2)
        is_i0 = i_arr == 0
        is_j0 = (d - i_arr) == 0
        if spec.uses_gap_border:
            ga_v = fetch(ga_pad, lo)
            gap_v = fetch(ga_prev_pad, lo)
            gb_v = fetch(gb_rev_pad, off_b)
            gbp_v = fetch(gb_prev_rev_pad, off_b)
            pred_d = jnp.where(is_i0, gbp_v, jnp.where(is_j0, gap_v, pred_d))
            pred_d = jnp.where(is_i0 & is_j0, 0.0, pred_d)
            pred_v = jnp.where(is_i0, gb_v, pred_v)
            pred_h = jnp.where(is_j0, ga_v, pred_h)
        else:
            # Base case: cell (0, 0) starts from 0 via the diagonal move.
            pred_d = jnp.where(is_i0 & is_j0, 0.0, pred_d)
        if c_v is c_d and c_h is c_d:   # shared-cost family (DTW, WDTW)
            cell = c_d + jnp.minimum(jnp.minimum(pred_d, pred_h), pred_v)
        else:
            cell = jnp.minimum(jnp.minimum(pred_d + c_d, pred_v + c_v),
                               pred_h + c_h)
        diag = jnp.where(t <= hi - lo, cell, inf)
        diag = jnp.minimum(diag, inf)
        return diag, prev1

    init = carry_full((rows, W), _NEG_SAFE_INF)
    last, _ = jax.lax.fori_loop(0, 2 * L - 1, step, (init, init))
    # Diagonal 2L-2 has lo = L-1: cell (L-1, L-1) sits in slot 0.
    return last[:, 0:1]


def dtw_band_compressed_kernel(a_ref, b_rev_ref, o_ref, *, length: int,
                               window: int, block: int, width: int,
                               measure: MeasureArg = None):
    """Kernel body: ``a_ref (block, L)`` and ``b_rev_ref (block, L)`` (time
    reversed) -> ``o_ref (block, 1)``.

    Registers are ``(block, width)`` — only the feasible band cells of each
    anti-diagonal are materialized.
    """
    a = a_ref[...].astype(jnp.float32)
    b_rev = b_rev_ref[...].astype(jnp.float32)
    o_ref[...] = wavefront_compressed(a, b_rev, length=length, window=window,
                                      width=width, measure=measure)


def dtw_band_cdist_kernel(a_ref, b_rev_ref, o_ref, *, length: int,
                          window: int, block_a: int, block_b: int,
                          width: int, measure: MeasureArg = None):
    """All-pairs tile: ``a_ref (block_a, L)`` x ``b_rev_ref (block_b, L)``
    (time reversed) -> ``o_ref (block_a, block_b)``.

    One band-compressed sweep per B row, broadcast against the A tile; each
    sweep's ``(block_a, 1)`` column is selected into the lane-dense output
    tile, which is stored once.
    """
    a = a_ref[...].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_a, block_b), 1)

    def one_row(j, out):
        b_rev = jnp.broadcast_to(
            b_rev_ref[pl.ds(j, 1), :].astype(jnp.float32), (block_a, length))
        d = wavefront_compressed(a, b_rev, length=length, window=window,
                                 width=width, measure=measure)
        return jnp.where(col == j, d, out)

    o_ref[...] = jax.lax.fori_loop(0, block_b, one_row,
                                   carry_full((block_a, block_b), 0.0))


def dtw_band_adaptive_kernel(a_ref, b_rev_ref, lo_ref, hi_ref, o_ref, *,
                             length: int, window: int, block: int,
                             width: int, measure: MeasureArg = None):
    """Adaptive-corridor kernel body: ``a_ref (block, L)``, ``b_rev_ref
    (block, L)`` (time reversed) plus per-pair corridor envelopes
    ``lo_ref``/``hi_ref (block, 2L-1)`` int32 -> ``o_ref (block, 1)``.

    Same band-compressed registers as the static kernel, but the live cell
    range of every anti-diagonal comes from the pair's own corridor (built
    by :mod:`repro.core.corridor`), so ``width`` can be far below the
    static ``window + 1`` when alignment paths hug the diagonal.
    """
    a = a_ref[...].astype(jnp.float32)
    b_rev = b_rev_ref[...].astype(jnp.float32)
    o_ref[...] = wavefront_compressed(
        a, b_rev, length=length, window=window, width=width, measure=measure,
        corridor=(lo_ref[...], hi_ref[...]))


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------

def make_dtw_band_call(n_pairs: int, length: int, window: Optional[int],
                       block: int, interpret: bool, mode: str = "compressed",
                       lane: int = 8, measure: MeasureArg = None,
                       width: Optional[int] = None):
    """Build the pallas_call for ``(n_pairs, L)`` zipped pair batches, the
    second operand time reversed.

    ``n_pairs`` must already be padded to a multiple of ``block``.
    ``mode`` selects the band-compressed sweep (default), the legacy
    full-width sweep (DTW-only benchmark baseline), or the
    adaptive-corridor sweep (``mode="adaptive"``, which adds two
    ``(n_pairs, 2L-1)`` int32 corridor operands and requires an explicit
    register ``width`` — normally the tuned adaptive width, see
    :mod:`repro.kernels.tune`).  The adaptive sweep's per-row gathers do
    not lower on the TPU, so it is refused unless ``interpret``.
    """
    spec = measures.resolve(measure)
    w = effective_window(length, window)
    grid = (n_pairs // block,)
    in_specs = [
        pl.BlockSpec((block, length), lambda i: (i, 0)),
        pl.BlockSpec((block, length), lambda i: (i, 0)),
    ]
    if mode == "full":
        if spec.name != "dtw":
            raise ValueError(
                "mode='full' is the legacy DTW-only benchmark baseline; "
                f"measure {spec.name!r} requires mode='compressed'")
        kernel = functools.partial(dtw_band_kernel, length=length, window=w,
                                   block=block)
    elif mode == "compressed":
        if width is None:
            width = band_width(length, w, lane)
        kernel = functools.partial(dtw_band_compressed_kernel, length=length,
                                   window=w, block=block, width=width,
                                   measure=spec)
    elif mode == "adaptive":
        if width is None:
            raise ValueError("mode='adaptive' needs an explicit width "
                             "(the corridor cap)")
        if not interpret:
            raise CompiledRouteUnsupported("dtw_band with an adaptive "
                                           "corridor")
        kernel = functools.partial(dtw_band_adaptive_kernel, length=length,
                                   window=w, block=block, width=width,
                                   measure=spec)
        in_specs += [
            pl.BlockSpec((block, 2 * length - 1), lambda i: (i, 0)),
            pl.BlockSpec((block, 2 * length - 1), lambda i: (i, 0)),
        ]
    else:
        raise ValueError(f"unknown dtw_band mode: {mode!r}")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pairs, 1), jnp.float32),
        interpret=interpret,
    )


def make_dtw_band_cdist_call(n_a: int, n_b: int, length: int,
                             window: Optional[int], block_a: int,
                             interpret: bool, lane: int = 8,
                             measure: MeasureArg = None):
    """All-pairs call on a 2-D grid: ``A (n_a, L) x B_rev (n_b, L) -> (n_a,
    n_b)`` with B passed time reversed.

    Each grid step sweeps ``block_a`` rows of A against ``block_b =
    min(n_b, 128)`` rows of B, one B row at a time broadcast inside the
    kernel, so the N*M cross-product is never materialized in HBM and the
    output tile is lane dense.  ``n_a`` must be padded to a multiple of
    ``block_a`` and ``n_b`` to a multiple of ``block_b``.
    """
    w = effective_window(length, window)
    block_b = min(n_b, _LANES)
    if n_b % block_b:
        raise ValueError(f"n_b={n_b} must be a multiple of {block_b}")
    kernel = functools.partial(dtw_band_cdist_kernel, length=length,
                               window=w, block_a=block_a, block_b=block_b,
                               width=band_width(length, w, lane),
                               measure=measures.resolve(measure))
    return pl.pallas_call(
        kernel,
        grid=(n_a // block_a, n_b // block_b),
        in_specs=[
            pl.BlockSpec((block_a, length), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, length), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, block_b), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_a, n_b), jnp.float32),
        interpret=interpret,
    )
