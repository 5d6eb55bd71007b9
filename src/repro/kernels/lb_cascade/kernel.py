"""Fused LB-cascade filter + banded-DTW refine Pallas kernel.

The paper's cascading lower bounds (§3.2) make elastic search viable by
skipping most exact DTW evaluations; "Exact Indexing for Massive Time
Series Databases under Time Warping Distance" is the database-scale version
of the same idea.  On TPU the pruning decision cannot change any shape, so
the cascade is expressed as a *tile-level* skip instead of a per-candidate
branch: for each ``(block, L)`` tile of zipped (query, candidate) pairs the
kernel

  1. evaluates ``LB_Kim`` (first/last aligned points) and the reversed
     ``LB_Keogh`` (candidate against the query's precomputed envelope) —
     a handful of VPU ops per pair;
  2. compares ``lb = max(kim, keogh)`` against the per-pair threshold
     (the caller's current k-th best verified distance);
  3. runs the band-compressed DTW wavefront shared with
     :mod:`..dtw_band.kernel` **only if any pair in the tile survives**
     (a scalar ``lax.cond`` — a fully pruned tile costs O(L) bound math
     instead of the O(L * width) wavefront sweep).

Outputs per pair: a distance that is the *exact* squared banded DTW when
``lb < thresh`` and the (valid lower-bound) ``lb`` otherwise, plus the
refined mask.  Callers that order candidates by ascending bound (the
two-phase batched search in :mod:`repro.core.lb_search`) concentrate the
survivors in few tiles, so late tiles skip the wavefront entirely.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.dispatch import effective_window
from ...core.lb import lb_keogh, lb_kim
from ...core.measures import MeasureArg
from ..common import CompiledRouteUnsupported
from ..dtw_band.kernel import band_width, wavefront_compressed

__all__ = [
    "lb_cascade_kernel",
    "lb_cascade_adaptive_kernel",
    "make_lb_refine_call",
]


def lb_cascade_kernel(a_ref, b_ref, br_ref, u_ref, l_ref, t_ref, d_ref,
                      f_ref, *, length: int, window: int, block: int,
                      width: int, measure: MeasureArg = None):
    """``a_ref (block, L)`` queries, ``b_ref (block, L)`` candidates and
    ``br_ref`` the same candidates time reversed (the bounds read ``b``,
    the sweep reads ``b_rev``), ``u_ref``/``l_ref (block, L)`` query
    envelopes, ``t_ref (block, 1)`` thresholds -> ``d_ref (block, 1)``
    distances, ``f_ref (block, 1)`` refined flags (int32 0/1)."""
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    up = u_ref[...].astype(jnp.float32)
    lo = l_ref[...].astype(jnp.float32)
    thresh = t_ref[...].astype(jnp.float32)            # (block, 1)

    # shared bound definitions (the filter must agree with the caller's
    # phase-1 ledger, which uses the same core.lb helpers)
    lb = jnp.maximum(lb_kim(a, b), lb_keogh(b, up, lo))[:, None]
    surv = lb < thresh                                 # (block, 1)

    def refine(_):
        return wavefront_compressed(a, br_ref[...].astype(jnp.float32),
                                    length=length, window=window,
                                    width=width, measure=measure)

    def skip(_):
        return jnp.zeros((block, 1), jnp.float32)

    d = jax.lax.cond(jnp.any(surv), refine, skip, 0)
    d_ref[...] = jnp.where(surv, d, lb)
    f_ref[...] = surv.astype(jnp.int32)


def lb_cascade_adaptive_kernel(a_ref, b_ref, br_ref, u_ref, l_ref, t_ref,
                               lo_ref, hi_ref, d_ref, f_ref, *, length: int,
                               window: int, block: int, width: int,
                               measure: MeasureArg = None):
    """Adaptive-corridor cascade tile: the static kernel plus per-pair
    corridor envelopes ``lo_ref``/``hi_ref (block, 2L-1)`` int32 feeding
    the refine sweep.  The bound math is unchanged (``lb`` stays a valid
    lower bound of the *static*-band distance); the refined distance is
    the corridor-restricted cost — an upper bound of the static cost, so
    the overall result is the documented approximate ``band="adaptive"``
    contract, not the certified-exact cascade."""
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    up = u_ref[...].astype(jnp.float32)
    lo = l_ref[...].astype(jnp.float32)
    thresh = t_ref[...].astype(jnp.float32)            # (block, 1)

    lb = jnp.maximum(lb_kim(a, b), lb_keogh(b, up, lo))[:, None]
    surv = lb < thresh                                 # (block, 1)

    def refine(_):
        return wavefront_compressed(a, br_ref[...].astype(jnp.float32),
                                    length=length, window=window,
                                    width=width, measure=measure,
                                    corridor=(lo_ref[...], hi_ref[...]))

    def skip(_):
        return jnp.zeros((block, 1), jnp.float32)

    d = jax.lax.cond(jnp.any(surv), refine, skip, 0)
    d_ref[...] = jnp.where(surv, d, lb)
    f_ref[...] = surv.astype(jnp.int32)


def make_lb_refine_call(n_pairs: int, length: int, window: Optional[int],
                        block: int, interpret: bool, lane: int = 8,
                        measure: MeasureArg = None, adaptive: bool = False,
                        width: Optional[int] = None):
    """Build the pallas_call over ``(n_pairs, L)`` zipped pair batches.

    ``n_pairs`` must already be padded to a multiple of ``block``.
    Operands: queries, candidates, candidates time reversed, the two
    envelopes and the ``(n_pairs, 1)`` thresholds.  ``adaptive=True`` adds
    two ``(n_pairs, 2L-1)`` int32 corridor operands and requires an
    explicit register ``width``; its per-row gathers do not lower on the
    TPU, so it is refused unless ``interpret``.
    """
    w = effective_window(length, window)
    if width is None:
        width = band_width(length, w, lane)
    row_spec = pl.BlockSpec((block, length), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block, 1), lambda i: (i, 0))
    in_specs = [row_spec, row_spec, row_spec, row_spec, row_spec, out_spec]
    if adaptive:
        if not interpret:
            raise CompiledRouteUnsupported(
                "lb_refine with an adaptive corridor")
        kernel = functools.partial(lb_cascade_adaptive_kernel, length=length,
                                   window=w, block=block, width=width,
                                   measure=measure)
        cor_spec = pl.BlockSpec((block, 2 * length - 1), lambda i: (i, 0))
        in_specs += [cor_spec, cor_spec]
    else:
        kernel = functools.partial(lb_cascade_kernel, length=length,
                                   window=w, block=block, width=width,
                                   measure=measure)
    return pl.pallas_call(
        kernel,
        grid=(n_pairs // block,),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((n_pairs, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n_pairs, 1), jnp.int32)],
        interpret=interpret,
    )
