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

The all-pairs kernel (``dtw_band_cdist_kernel``) turns the compressed
register on its side: band slots on sublanes, one B row per lane, a few A
rows stacked as independent chains.  Its windows are dynamic sublane
slices of time-major VMEM buffers and its predecessor shifts sublane
rotates, so a launch's work follows its real rows and band cells.

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
_SUBLANES = 8           # TPU vreg sublane count
# VMEM the all-pairs kernel may spend on A rows broadcast across the lanes
_A_LANES_BYTES = 4 * 1024 * 1024


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


def _cdist_streams(spec, x: jnp.ndarray, reversed_: bool) -> jnp.ndarray:
    """The series one side of the all-pairs sweep reads per diagonal,
    ``(n_streams, rows, L)``: the values, then their predecessors
    (``uses_neighbors``), then the virtual border prefix sums and their
    predecessors (``uses_gap_border``), each built exactly as
    :func:`wavefront_compressed` builds it.  ``reversed_`` marks the B side,
    passed time reversed."""
    rows, L = x.shape
    streams = [x]
    if spec.uses_neighbors:
        streams.append(
            jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1) if reversed_
            else jnp.concatenate([x[:, :1], x[:, :-1]], axis=1))
    if spec.uses_gap_border:
        g = _prefix_sum(measures.gap_costs(spec, x), L, reverse=reversed_)
        zero = jnp.zeros((rows, 1), jnp.float32)
        streams += [g, jnp.concatenate([g[:, 1:], zero], axis=1) if reversed_
                    else jnp.concatenate([zero, g[:, :-1]], axis=1)]
    return jnp.stack(streams)


def dtw_band_cdist_kernel(a_ref, b_ref, o_ref, a_lanes, *, length: int,
                          window: int, width: int,
                          measure: MeasureArg = None):
    """All-pairs tile: ``chains`` rows of A against ``n_b`` (a lane tile)
    rows of B, one anti-diagonal sweep for all of them.

    ``a_ref (1, n_streams, L_pad, chains)`` holds the A rows' streams (see
    :func:`_cdist_streams`) time major; ``b_ref (n_streams, L_pad, n_b)``
    the B rows' streams, time reversed and time major, one B row per lane;
    ``o_ref (1, chains, n_b)``; ``a_lanes (n_streams, chains, L_pad, n_b)``
    is VMEM scratch that holds each A row broadcast across the lanes.

    Band slots sit on sublanes and B rows on lanes: the register is
    ``(chains * width, n_b)``, ``width`` slots per A row stacked row above
    row, so the A rows run as independent chains through one sweep.  Slot
    ``t`` of diagonal ``d`` is cell ``i = lo(d) + t`` (module header); its
    values are the dynamic sublane windows ``a[lo + t]`` and ``b_rev[L-1-d
    + lo + t]`` of the time-major buffers, and the predecessor shifts are
    sublane rotates whose wrap into the next row's slots is masked off.
    The per-cell recurrence is :func:`wavefront_compressed`'s.
    """
    spec = measures.resolve(measure)
    L, w, W = length, window, width
    chains = a_ref.shape[3]
    n_b = b_ref.shape[2]
    rows = chains * W
    for k in range(a_ref.shape[1]):
        for c in range(chains):
            a_lanes[k, c] = jnp.broadcast_to(
                a_ref[0, k, :, c:c + 1], a_lanes.shape[2:])

    inf = jnp.float32(_NEG_SAFE_INF)
    t = jnp.concatenate(
        [jax.lax.broadcasted_iota(jnp.int32, (W, n_b), 0)] * chains, axis=0)

    def a_win(k, lo):
        return jnp.concatenate([a_lanes[k, c, pl.ds(lo, W), :]
                                for c in range(chains)], axis=0)

    def b_win(k, off):
        return jnp.concatenate([b_ref[k, pl.ds(off, W), :]] * chains, axis=0)

    def lo_of(d):
        # max(0, d - (L-1), ceil((d - w) / 2)); jnp // is floor division.
        return jnp.maximum(jnp.maximum(0, d - (L - 1)), -((w - d) // 2))

    def read(reg, s):
        """``reg[t + s]`` for scalar shift ``s`` in {-1, 0, 1}; slots out of
        the row's band read the +inf sentinel (sublane rotate + mask)."""
        left = jnp.where(t == W - 1, inf, pltpu.roll(reg, rows - 1, 0))
        right = jnp.where(t == 0, inf, pltpu.roll(reg, 1, 0))
        return jnp.where(s == 0, reg, jnp.where(s > 0, left, right))

    gap = 1 + spec.uses_neighbors     # stream index of the border sums

    def step(d, carry):
        prev1, prev2 = carry  # compressed diagonals d-1 / d-2, inf-masked
        lo = lo_of(d)
        hi = jnp.minimum(jnp.minimum(L - 1, d), (d + w) // 2)
        s1 = lo - lo_of(d - 1)
        s2 = lo - lo_of(d - 2) - 1
        off_b = L - 1 - d + lo

        av = a_win(0, lo)
        bv = b_win(0, off_b)
        i_arr = lo + t
        xp = a_win(1, lo) if spec.uses_neighbors else None
        yp = b_win(1, off_b) if spec.uses_neighbors else None
        dd = jnp.abs(2 * i_arr - d) if spec.uses_position else None
        c_d, c_v, c_h = measures.move_costs(spec, av, bv, xp, yp, dd, L)

        pred_h = read(prev1, s1)
        pred_v = read(prev1, s1 - 1)
        pred_d = read(prev2, s2)
        is_i0 = i_arr == 0
        is_j0 = (d - i_arr) == 0
        if spec.uses_gap_border:
            ga_v = a_win(gap, lo)
            gap_v = a_win(gap + 1, lo)
            gb_v = b_win(gap, off_b)
            gbp_v = b_win(gap + 1, off_b)
            pred_d = jnp.where(is_i0, gbp_v, jnp.where(is_j0, gap_v, pred_d))
            pred_d = jnp.where(is_i0 & is_j0, 0.0, pred_d)
            pred_v = jnp.where(is_i0, gb_v, pred_v)
            pred_h = jnp.where(is_j0, ga_v, pred_h)
        else:
            pred_d = jnp.where(is_i0 & is_j0, 0.0, pred_d)
        if c_v is c_d and c_h is c_d:   # shared-cost family (DTW, WDTW)
            cell = c_d + jnp.minimum(jnp.minimum(pred_d, pred_h), pred_v)
        else:
            cell = jnp.minimum(jnp.minimum(pred_d + c_d, pred_v + c_v),
                               pred_h + c_h)
        diag = jnp.where(t <= hi - lo, cell, inf)
        diag = jnp.minimum(diag, inf)
        return diag, prev1

    init = carry_full((rows, n_b), _NEG_SAFE_INF)
    last, _ = jax.lax.fori_loop(0, 2 * L - 1, step, (init, init))
    # Diagonal 2L-2 has lo = L-1: cell (L-1, L-1) sits in each row's slot 0.
    for c in range(chains):
        o_ref[0, c:c + 1, :] = last[c * W:c * W + 1, :]


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
                             window: Optional[int], block: int,
                             interpret: bool, measure: MeasureArg = None):
    """All-pairs call: ``A (n_a, L) x B_rev (n_b, L) -> (n_a, n_b)``, B
    passed time reversed; the returned function lays both sides out time
    major and runs :func:`dtw_band_cdist_kernel` on a 2-D grid.

    The band register is ``width`` sublanes per A row (the band's cells
    rounded up to 8) by 128 B rows on lanes.  A grid step runs ``chains``
    A rows as independent chains: as many as fit ``block`` register
    sublanes, never more than ``n_a`` and never more than the VMEM that
    broadcasting them across the lanes may take.  So a launch's work
    scales with its real rows and band cells; only B is padded, to whole
    lane tiles, and the ``n_a x n_b`` cross-product never reaches HBM.
    """
    spec = measures.resolve(measure)
    w = effective_window(length, window)
    width = cdiv(min(w, length - 1) + 1, _SUBLANES) * _SUBLANES
    l_pad = cdiv(length + width - 1, _SUBLANES) * _SUBLANES
    n_streams = 1 + spec.uses_neighbors + 2 * spec.uses_gap_border
    chains = max(1, min(n_a, block // width,
                        _A_LANES_BYTES // (n_streams * l_pad * _LANES * 4)))
    n_blocks = cdiv(n_a, chains)
    b_tiles = cdiv(n_b, _LANES)
    kernel = functools.partial(dtw_band_cdist_kernel, length=length,
                               window=w, width=width, measure=spec)
    call = pl.pallas_call(
        kernel,
        grid=(n_blocks, b_tiles),
        in_specs=[
            pl.BlockSpec((1, n_streams, l_pad, chains),
                         lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((n_streams, l_pad, _LANES), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, chains, _LANES), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, chains, b_tiles * _LANES),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_streams, chains, l_pad, _LANES),
                                   jnp.float32)],
        interpret=interpret,
    )

    def run(A: jnp.ndarray, B_rev: jnp.ndarray) -> jnp.ndarray:
        a = _cdist_streams(spec, A, reversed_=False)
        a = jnp.pad(a, ((0, 0), (0, n_blocks * chains - n_a),
                        (0, l_pad - length)))
        a = a.reshape(n_streams, n_blocks, chains, l_pad).transpose(1, 0, 3, 2)
        b = _cdist_streams(spec, B_rev, reversed_=True)
        b = jnp.pad(b, ((0, 0), (0, b_tiles * _LANES - n_b),
                        (0, l_pad - length))).transpose(0, 2, 1)
        out = call(a, b).reshape(n_blocks * chains, b_tiles * _LANES)
        return out[:n_a, :n_b]

    return run
