"""Jitted public wrappers for the banded elastic-measure Pallas kernels."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core import measures
from .. import tune
from ..common import default_interpret, pad_to
from .kernel import MeasureArg, make_dtw_band_call, make_dtw_band_cdist_call

__all__ = ["dtw_band", "dtw_band_cdist"]


def _default_lane() -> int:
    """Lane multiple for the compressed register width: full 128-lane tiles
    on real TPU hardware, small tiles under interpret/CPU so tests stay
    cheap and the band compression is visible at short lengths."""
    return 128 if jax.default_backend() == "tpu" else 8


def _backend_name(interpret: bool) -> str:
    return "pallas_interpret" if interpret else "pallas"


def _tuned_block(op: str, block: Optional[int], *, length: int,
                 window: Optional[int], measure: MeasureArg,
                 interpret: bool) -> int:
    """``block=None`` consults the tuning table (a trace-time Python
    resolution — the result is a static launch parameter), falling back
    to the backend's builtin block; an explicit block always wins."""
    if block is not None:
        return block
    backend = _backend_name(interpret)
    return tune.tuned(op, "block", length=length, window=window,
                      measure=measures.resolve(measure).name,
                      backend=backend,
                      default=tune.default_block(op, backend))


@functools.partial(jax.jit,
                   static_argnames=("window", "block", "interpret", "mode",
                                    "lane", "measure", "width"))
def dtw_band(A: jnp.ndarray, B: jnp.ndarray, window: Optional[int] = None,
             block: Optional[int] = None, interpret: Optional[bool] = None,
             mode: str = "compressed",
             lane: Optional[int] = None,
             measure: MeasureArg = None,
             corridor: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
             width: Optional[int] = None) -> jnp.ndarray:
    """Banded elastic cost over zipped pairs: ``A (N, L)``, ``B (N, L)`` ->
    ``(N,)`` (squared banded DTW under the default measure).

    ``mode="compressed"`` (default) runs the band-compressed wavefront whose
    per-step cost scales with the Sakoe-Chiba band; ``mode="full"`` runs the
    legacy full-width sweep (kept as the DTW-only benchmark baseline).
    ``measure`` selects any registered elastic measure (static).

    ``corridor=(lo, hi)`` (``(N, 2L-1)`` int32 envelopes from
    :mod:`repro.core.corridor`) switches to the adaptive per-pair band
    sweep; ``width`` caps its registers (default: the tuned adaptive
    width for this geometry).  ``block=None`` consults the
    :mod:`repro.kernels.tune` table for the launch block.
    """
    if interpret is None:
        interpret = default_interpret()
    if lane is None:
        lane = _default_lane()
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    n, L = A.shape
    if corridor is not None:
        mode = "adaptive"
        if width is None:
            width = tune.adaptive_width(
                L, window, lane, measure=measures.resolve(measure).name,
                backend=_backend_name(interpret))
    block = _tuned_block("dtw_band", block, length=L, window=window,
                         measure=measure, interpret=interpret)
    Ap = pad_to(A, block, axis=0)
    Bp = pad_to(jnp.flip(B, axis=1), block, axis=0)   # kernels take B reversed
    call = make_dtw_band_call(Ap.shape[0], L, window, block, interpret,
                              mode=mode, lane=lane, measure=measure,
                              width=width)
    if corridor is not None:
        lo, hi = corridor
        out = call(Ap, Bp, pad_to(lo.astype(jnp.int32), block, axis=0),
                   pad_to(hi.astype(jnp.int32), block, axis=0))
    else:
        out = call(Ap, Bp)
    return out[:n, 0]


@functools.partial(jax.jit,
                   static_argnames=("window", "block", "interpret", "measure"))
def dtw_band_cdist(A: jnp.ndarray, B: jnp.ndarray,
                   window: Optional[int] = None, block: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   measure: MeasureArg = None) -> jnp.ndarray:
    """All-pairs banded elastic cost: ``A (N, L)``, ``B (M, L)`` -> ``(N, M)``.

    One anti-diagonal sweep per grid step covers a few A rows against 128
    B rows: band slots on sublanes, B rows on lanes, the A rows as
    independent register chains (see :func:`make_dtw_band_cdist_call`).
    The N*M cross-product is never materialized.  ``block=None`` consults
    the tuning table for the register height in sublanes, which sets how
    many A rows share a grid step.
    """
    if interpret is None:
        interpret = default_interpret()
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    N, L = A.shape
    M = B.shape[0]
    block = _tuned_block("dtw_band_cdist", block, length=L, window=window,
                         measure=measure, interpret=interpret)
    call = make_dtw_band_cdist_call(N, M, L, window, block, interpret,
                                    measure=measure)
    return call(A, jnp.flip(B, axis=1))    # kernels take B reversed
