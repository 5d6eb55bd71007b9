"""DBA k-means — the codebook learner of the paper's training phase.

Assignment is one all-pairs launch through the elastic dispatch layer
(`dispatch.elastic_cdist`; on TPU the Pallas kernel that sweeps a few
series at once against 128 centroids on lanes) under any registered
elastic measure; the update step runs one
or more DBA iterations per round, where each series contributes only to its
assigned centroid (scatter-add by cluster id, so the cost per round is N
backtracks, not N*K).  The DBA barycenter update itself always averages
along *DTW* alignment paths — for non-DTW measures it is the standard
averaging heuristic (centroids are representatives; assignment and every
LUT/search distance use the configured measure).

A Euclidean variant (`euclidean_kmeans`) backs the PQ_ED baseline.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .dispatch import elastic_cdist
from .dtw import euclidean_sq
from .dba import alignment_path
from .measures import MeasureArg

__all__ = ["KMeansResult", "dba_kmeans", "euclidean_kmeans"]


class KMeansResult(NamedTuple):
    centroids: jnp.ndarray   # (K, L)
    assignment: jnp.ndarray  # (N,)
    inertia: jnp.ndarray     # scalar: sum of within-cluster squared DTW


def _init_centroids(key: jax.Array, X: jnp.ndarray, k: int) -> jnp.ndarray:
    n = X.shape[0]
    if n >= k:
        idx = jax.random.choice(key, n, (k,), replace=False)
    else:  # codebook larger than data: sample with replacement + jitter
        idx = jax.random.choice(key, n, (k,), replace=True)
    return X[idx]


@functools.partial(jax.jit, static_argnames=("window",))
def _dba_assigned_update(C: jnp.ndarray, X: jnp.ndarray, assign: jnp.ndarray,
                         window: Optional[int]) -> jnp.ndarray:
    """Scatter-add DBA update: every series aligns to its assigned centroid."""
    K, L = C.shape

    def per_series(x, a):
        i_cells, j_cells, active = alignment_path(C[a], x, window)
        w = active.astype(jnp.float32)
        return i_cells, x[j_cells] * w, w

    i_cells, vals, w = jax.vmap(per_series)(X, assign)  # (N, 2L-1) each
    rows = jnp.broadcast_to(assign[:, None], i_cells.shape)
    assoc = jnp.zeros((K, L), jnp.float32).at[rows, i_cells].add(vals)
    count = jnp.zeros((K, L), jnp.float32).at[rows, i_cells].add(w)
    return jnp.where(count > 0, assoc / jnp.maximum(count, 1e-9), C)


def dba_kmeans(key: jax.Array, X: jnp.ndarray, k: int, iters: int = 10,
               dba_iters: int = 2, window: Optional[int] = None,
               measure: MeasureArg = None) -> KMeansResult:
    """DBA k-means over ``X (N, L)`` with ``k`` clusters.

    Python-level outer loop (iters is small) over jitted assignment/update
    steps; fully deterministic given ``key``.  ``measure`` selects the
    assignment/inertia distance (DTW by default); the DBA update remains
    DTW-alignment averaging (see module docstring).
    """
    X = jnp.asarray(X, jnp.float32)
    C = _init_centroids(key, X, k)
    assign = jnp.zeros((X.shape[0],), jnp.int32)
    for _ in range(iters):
        d = elastic_cdist(X, C, window, measure=measure)   # (N, K)
        assign = jnp.argmin(d, axis=1)
        for _ in range(dba_iters):
            C = _dba_assigned_update(C, X, assign, window)
    d = elastic_cdist(X, C, window, measure=measure)
    assign = jnp.argmin(d, axis=1)
    inertia = jnp.sum(jnp.min(d, axis=1))
    return KMeansResult(C, assign, inertia)


def euclidean_kmeans(key: jax.Array, X: jnp.ndarray, k: int,
                     iters: int = 20) -> KMeansResult:
    """Plain Lloyd k-means (squared Euclidean) for the PQ_ED baseline."""
    X = jnp.asarray(X, jnp.float32)
    C = _init_centroids(key, X, k)

    @jax.jit
    def step(C):
        d = euclidean_sq(X, C)
        assign = jnp.argmin(d, axis=1)
        oh = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # (N, K)
        count = oh.sum(0)[:, None]
        mean = (oh.T @ X) / jnp.maximum(count, 1e-9)
        return jnp.where(count > 0, mean, C), assign, d

    assign = jnp.zeros((X.shape[0],), jnp.int32)
    d = None
    for _ in range(iters):
        C, assign, d = step(C)
    inertia = jnp.sum(jnp.min(d, axis=1))
    return KMeansResult(C, assign, inertia)
