"""Pure-jnp implementations of the LB-cascade filter-and-refine kernel.

Two flavors with the same contract as :func:`..ops.lb_refine`:

* :func:`lb_refine_ref` — the test oracle.  Delegates the refine to the
  core wavefront DTW (itself validated against an O(L^2) numpy DP oracle
  in tests/conftest.py), fully independent of the kernel's compressed DP.
* :func:`lb_refine_jax` — the dispatch layer's ``"jax"`` route.  Same
  bound math, but the refine runs the band-compressed anti-diagonal sweep
  (:func:`...kernels.dtw_band.kernel.wavefront_compressed` — plain jnp,
  no Pallas) vectorized over the whole batch, so per-step cost scales
  with the Sakoe-Chiba band rather than the series length.

Both compute the exact distance for every pair and select — the pruning
(tile-level wavefront skip) is a Pallas-route optimization, not a
semantic difference.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.dispatch import effective_window
from ...core.dtw import dtw_batch
from ...core.lb import lb_keogh, lb_kim
from ...core.measures import MeasureArg
from ..dtw_band.kernel import band_width, wavefront_compressed

__all__ = ["lb_refine_ref", "lb_refine_jax", "cascade_bound_ref"]


def cascade_bound_ref(A: jnp.ndarray, B: jnp.ndarray, upper: jnp.ndarray,
                      lower: jnp.ndarray) -> jnp.ndarray:
    """``max(LB_Kim(a, b), LB_Keogh(b, env(a)))`` per zipped pair."""
    return jnp.maximum(lb_kim(A, B), lb_keogh(B, upper, lower))


@jax.jit
def _select(lb, d, thresh):
    surv = lb < thresh
    return jnp.where(surv, d, lb), surv


def lb_refine_ref(A: jnp.ndarray, B: jnp.ndarray, upper: jnp.ndarray,
                  lower: jnp.ndarray, thresh: jnp.ndarray,
                  window: Optional[int] = None,
                  measure: MeasureArg = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    lb = cascade_bound_ref(A, B, jnp.asarray(upper, jnp.float32),
                           jnp.asarray(lower, jnp.float32))
    d = dtw_batch(A, B, window, measure)
    return _select(lb, d, jnp.asarray(thresh, jnp.float32))


@functools.partial(jax.jit, static_argnames=("window", "measure", "width"))
def lb_refine_jax(A: jnp.ndarray, B: jnp.ndarray, upper: jnp.ndarray,
                  lower: jnp.ndarray, thresh: jnp.ndarray,
                  window: Optional[int] = None,
                  measure: MeasureArg = None,
                  corridor: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                  width: Optional[int] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    L = A.shape[-1]
    w = effective_window(L, window)
    lb = cascade_bound_ref(A, B, jnp.asarray(upper, jnp.float32),
                           jnp.asarray(lower, jnp.float32))
    if width is None:
        width = band_width(L, w)
    d = wavefront_compressed(A, jnp.flip(B, axis=1), length=L, window=w,
                             width=width, measure=measure,
                             corridor=corridor)[:, 0]
    return _select(lb, d, jnp.asarray(thresh, jnp.float32))
