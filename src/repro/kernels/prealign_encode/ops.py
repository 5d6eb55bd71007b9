"""Jitted public wrapper for the fused prealign+encode Pallas kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.dispatch import effective_window
from ...core.measures import MeasureArg
from ..common import default_interpret, pad_to
from ..dtw_band.kernel import band_width
from .kernel import make_prealign_encode_call
from .ref import check_geometry

__all__ = ["prealign_encode"]


def _default_lane() -> int:
    """Compressed-width lane multiple: full 128-lane tiles on real TPU
    hardware, small tiles under interpret/CPU so tests stay cheap."""
    return 128 if jax.default_backend() == "tpu" else 8


@functools.partial(jax.jit, static_argnames=("level", "tail", "window",
                                             "block", "interpret", "lane",
                                             "measure"))
def prealign_encode(X: jnp.ndarray, centroids: jnp.ndarray, level: int,
                    tail: int, window: Optional[int] = None,
                    block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    lane: Optional[int] = None,
                    measure: MeasureArg = None) -> jnp.ndarray:
    """Fused MODWT prealign + DTW-1NN encode: ``X (N, D)`` -> ``(N, M)``.

    ``centroids (M, K, S)`` with ``S = D // M + tail``; ``window`` is the
    Sakoe-Chiba band over the *subsequence* length (``None`` = unbanded).
    Codes match ``modwt.prealign`` + exact ``pq.encode``.
    ``block=None`` consults the :mod:`repro.kernels.tune` table.
    """
    if interpret is None:
        interpret = default_interpret()
    if lane is None:
        lane = _default_lane()
    X = jnp.asarray(X, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    N, D = X.shape
    M, K, S = centroids.shape
    check_geometry(D, centroids, tail)
    w = effective_window(S, window)
    if block is None:
        from ...core import measures as _measures
        from .. import tune
        block = tune.tuned(
            "prealign_encode", "block", length=S, window=window,
            measure=_measures.resolve(measure).name,
            backend="pallas_interpret" if interpret else "pallas",
            default=8)
    block = min(block, max(1, N))
    Xp = pad_to(X, block, axis=0)
    lin = jnp.linspace(0.0, 1.0, S, dtype=jnp.float32)[None, :]
    call = make_prealign_encode_call(
        Xp.shape[0], D, M, K, S, level, tail, w, block,
        band_width(S, w, lane), interpret, measure=measure)
    # the kernel's wavefront takes the centroids time reversed
    return call(Xp, jnp.flip(centroids, axis=2), lin)[:N]
