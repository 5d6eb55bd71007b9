"""Jitted public wrapper for the fused LB-cascade filter-and-refine kernel."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core import measures
from ...core.measures import MeasureArg
from .. import tune
from ..common import default_interpret, pad_to
from .kernel import make_lb_refine_call

__all__ = ["lb_refine"]


def _default_lane() -> int:
    """Compressed-width lane multiple: full 128-lane tiles on real TPU
    hardware, small tiles under interpret/CPU so tests stay cheap."""
    return 128 if jax.default_backend() == "tpu" else 8


@functools.partial(jax.jit,
                   static_argnames=("window", "block", "interpret", "lane",
                                    "measure", "width"))
def lb_refine(A: jnp.ndarray, B: jnp.ndarray, upper: jnp.ndarray,
              lower: jnp.ndarray, thresh: jnp.ndarray,
              window: Optional[int] = None, block: Optional[int] = None,
              interpret: Optional[bool] = None,
              lane: Optional[int] = None,
              measure: MeasureArg = None,
              corridor: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              width: Optional[int] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cascaded bound + conditional banded-DTW refine over zipped pairs.

    ``A (N, L)`` queries, ``B (N, L)`` candidates, ``upper``/``lower``
    ``(N, L)`` Keogh envelopes of ``A`` (built with the *same* effective
    window as the DTW band, clamped to ``L - 1``), ``thresh (N,)``.
    Returns ``(d (N,), refined (N,) bool)`` where ``d`` is the exact
    squared banded DTW when ``lb < thresh`` (refined) and the lower bound
    ``max(LB_Kim, LB_Keogh)`` otherwise.

    ``corridor=(lo, hi)`` (``(N, 2L-1)`` int32 per-pair envelopes)
    switches the refine sweep to the adaptive band — the refined value
    becomes the corridor-restricted cost (>= the static cost; see
    :mod:`repro.core.corridor` for the exactness contract).
    ``block=None`` consults the tuning table.
    """
    if interpret is None:
        interpret = default_interpret()
    if lane is None:
        lane = _default_lane()
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    n, L = A.shape
    backend = "pallas_interpret" if interpret else "pallas"
    if block is None:
        block = tune.tuned("lb_refine", "block", length=L, window=window,
                           measure=measures.resolve(measure).name,
                           backend=backend,
                           default=tune.default_block("lb_refine", backend))
    adaptive = corridor is not None
    if adaptive and width is None:
        width = tune.adaptive_width(L, window, lane,
                                    measure=measures.resolve(measure).name,
                                    backend=backend)
    Ap = pad_to(A, block, axis=0)
    Bp = pad_to(B, block, axis=0)
    Brp = jnp.flip(Bp, axis=1)        # the refine sweep takes B reversed
    Up = pad_to(jnp.asarray(upper, jnp.float32), block, axis=0)
    Lp = pad_to(jnp.asarray(lower, jnp.float32), block, axis=0)
    # padded rows never refine: their threshold is -inf
    Tp = pad_to(jnp.asarray(thresh, jnp.float32).reshape(-1, 1), block,
                axis=0, value=-jnp.inf)
    call = make_lb_refine_call(Ap.shape[0], L, window, block, interpret,
                               lane=lane, measure=measure,
                               adaptive=adaptive, width=width)
    if adaptive:
        lo, hi = corridor
        d, flag = call(Ap, Bp, Brp, Up, Lp, Tp,
                       pad_to(lo.astype(jnp.int32), block, axis=0),
                       pad_to(hi.astype(jnp.int32), block, axis=0))
    else:
        d, flag = call(Ap, Bp, Brp, Up, Lp, Tp)
    return d[:n, 0], flag[:n, 0].astype(bool)
