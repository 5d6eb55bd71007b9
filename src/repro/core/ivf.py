"""IVF-PQDTW: inverted-file index for million-scale elastic search.

Paper §4.1: "To handle million-scale search, a search system with inverted
indexing was developed in the original PQ paper" — this is that system,
adapted to DTW.  A coarse DBA-k-means quantizer over *whole* series routes
each database series to one of ``n_lists`` inverted lists; queries compute
``n_lists`` coarse DTW distances, probe the ``n_probe`` nearest lists, and
evaluate the PQDTW asymmetric distance only for candidates in those lists.

DTW adaptation notes (vs IVFADC): the Euclidean residual trick (encode
``x - c``) is unsound under warping — subtracting unaligned series destroys
shape — so lists share one global PQ codebook over raw series and the coarse
stage is used purely for pruning.  Search cost per query drops from
O(N·M) table look-ups to O(n_lists·D²w) coarse DTWs + O(cap·M) look-ups,
with ``cap`` a static candidate budget (TPU-friendly shapes).

The fine stage is *segment-searchable*: :func:`fine_rank` operates on bare
list-layout arrays (codes / ids / list_start / list_len [+ optional
tombstone mask]) instead of a whole :class:`IVFPQIndex`, so the streaming
segmented index (:mod:`repro.index`) ranks each sealed segment with exactly
the same code path as the monolithic index.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dispatch import adc_lookup, elastic_cdist, two_level_coarse
from .kmeans import dba_kmeans
from .lb import lb_lut
from .measures import MeasureArg
from .pq import (PQCodebook, PQConfig, encode, fit, query_lut_batch,
                 segment)

__all__ = ["IVFPQIndex", "TwoLevelCoarse", "build_index", "build_lists",
           "build_two_level", "coarse_assign", "coarse_dists", "fine_rank",
           "search", "search_batch", "validate_n_probe",
           "validate_codebook"]


def validate_codebook(cb: PQCodebook, cfg: PQConfig, D: int) -> None:
    """Reject a pre-trained codebook whose geometry disagrees with ``cfg``
    for series of length ``D`` — e.g. a codebook trained without
    pre-alignment paired with a ``use_prealign=True`` config (or with a
    different ``snap_tail``).  Catches the mismatch at build/restore time
    with a clear message instead of a shape error inside encode."""
    want = cfg.subseq_len(D)
    if cb.n_sub != cfg.n_sub or cb.subseq_len != want:
        raise ValueError(
            f"codebook geometry (n_sub={cb.n_sub}, subseq_len="
            f"{cb.subseq_len}) does not match config (n_sub={cfg.n_sub}, "
            f"subseq_len={want} for D={D}) — check the prealign settings "
            f"(use_prealign/tail_frac/snap_tail) the codebook was trained "
            f"with")


class IVFPQIndex(NamedTuple):
    coarse: jnp.ndarray       # (n_lists, D) DBA centroids of whole series
    cb: PQCodebook            # shared PQ codebook (paper §3.1)
    codes: jnp.ndarray        # (N, M) PQ codes, list-sorted order
    ids: jnp.ndarray          # (N,) original indices, list-sorted
    list_start: jnp.ndarray   # (n_lists,) offset of each list in codes/ids
    list_len: jnp.ndarray     # (n_lists,)
    max_list: int             # python int: longest list (static shapes)
    coarse_window: int        # python int: Sakoe-Chiba band the inverted
                              # lists were assigned with — the search-time
                              # default, so probe ranking matches the
                              # build-time metric

    @property
    def n_lists(self) -> int:
        return self.coarse.shape[0]


def coarse_assign(X: jnp.ndarray, coarse: jnp.ndarray,
                  window: Optional[int],
                  measure: MeasureArg = None) -> jnp.ndarray:
    """Route series ``X (N, D)`` to their nearest coarse centroid (banded
    elastic distance through the dispatch layer) -> ``(N,)`` int32 list
    ids."""
    return jnp.argmin(elastic_cdist(X, coarse, window, measure=measure),
                      axis=1).astype(jnp.int32)


class TwoLevelCoarse(NamedTuple):
    """Hierarchical coarse quantizer: a k-means clustering *of the coarse
    centroids themselves*, so queries rank ``n_top`` top cells and fan
    out only to the probed cells' children instead of evaluating all
    ``n_lists`` centroids (the per-query coarse bottleneck once
    ``n_lists`` reaches tens of thousands).  A pytree of arrays —
    replicable across a device mesh alongside the flat centroids."""
    top: jnp.ndarray          # (n_top, D) centroids of the coarse centroids
    child_idx: jnp.ndarray    # (n_top, max_children) int32 into coarse
    child_valid: jnp.ndarray  # (n_top, max_children) bool padding mask

    @property
    def n_top(self) -> int:
        return self.top.shape[0]

    @property
    def max_children(self) -> int:
        return self.child_idx.shape[1]


def build_two_level(key: jax.Array, coarse: jnp.ndarray, n_top: int,
                    window: Optional[int], measure: MeasureArg = None,
                    iters: int = 8) -> TwoLevelCoarse:
    """Cluster the ``(n_lists, D)`` coarse centroids into ``n_top`` top
    cells (same elastic DBA k-means as the bottom level) and tabulate each
    cell's children as a static padded table."""
    coarse = jnp.asarray(coarse, jnp.float32)
    n_lists = coarse.shape[0]
    if not 1 <= n_top <= n_lists:
        raise ValueError(
            f"n_top={n_top} out of range: must satisfy 1 <= n_top <= "
            f"n_lists={n_lists}")
    res = dba_kmeans(key, coarse, n_top, iters=iters, dba_iters=1,
                     window=window, measure=measure)
    assign = np.asarray(res.assignment)
    order, start, length, max_children = build_lists(assign, n_top)
    max_children = max(1, max_children)
    child_idx = np.zeros((n_top, max_children), np.int32)
    child_valid = np.zeros((n_top, max_children), bool)
    for t in range(n_top):
        kids = order[start[t]:start[t] + length[t]]
        child_idx[t, :len(kids)] = kids
        child_valid[t, :len(kids)] = True
    return TwoLevelCoarse(top=res.centroids,
                          child_idx=jnp.asarray(child_idx),
                          child_valid=jnp.asarray(child_valid))


def coarse_dists(Q: jnp.ndarray, coarse: jnp.ndarray,
                 window: Optional[int], measure: MeasureArg = None,
                 two_level: Optional[TwoLevelCoarse] = None,
                 n_probe_top: Optional[int] = None) -> jnp.ndarray:
    """Coarse distance rows ``(Nq, n_lists)`` for the probe stage: the
    flat all-pairs cdist, or — when a :class:`TwoLevelCoarse` is given —
    the hierarchical fan-out (``+inf`` outside the ``n_probe_top``
    nearest top cells' children).  Shared by the monolithic
    :func:`search_batch`, the streaming index, and the sharded planner,
    so every plan ranks probes with identical numbers."""
    if two_level is None:
        return elastic_cdist(Q, coarse, window, measure=measure)
    if n_probe_top is None:
        raise ValueError("two_level coarse search requires n_probe_top")
    return two_level_coarse(Q, two_level.top, coarse, two_level.child_idx,
                            two_level.child_valid, window,
                            n_probe_top=n_probe_top, measure=measure)


def build_lists(assign: np.ndarray, n_lists: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """List-sorted layout from a coarse assignment (host-side).

    Returns ``(order, list_start, list_len, max_list)``: stable sort
    permutation into list order plus the per-list offsets/lengths.
    """
    assign = np.asarray(assign)
    order = np.argsort(assign, kind="stable")
    sorted_assign = assign[order]
    start = np.searchsorted(sorted_assign, np.arange(n_lists)).astype(np.int32)
    length = (np.searchsorted(sorted_assign, np.arange(n_lists), "right")
              - start).astype(np.int32)
    max_list = int(length.max()) if assign.size else 0
    return order, start, length, max_list


def build_index(key: jax.Array, X: jnp.ndarray, cfg: PQConfig,
                n_lists: int, coarse_iters: int = 8,
                coarse_window_frac: float = 0.1, *,
                coarse: Optional[jnp.ndarray] = None,
                cb: Optional[PQCodebook] = None) -> IVFPQIndex:
    """Train coarse + fine quantizers and populate the inverted lists.

    Pass pre-trained ``coarse`` centroids and/or a ``cb`` codebook to skip
    the corresponding training stage — the path the streaming index uses to
    rebuild an equivalent monolithic index from a shared quantizer.
    """
    X = jnp.asarray(X, jnp.float32)
    N, D = X.shape
    kc, kf = jax.random.split(key)
    w = max(1, int(round(coarse_window_frac * D)))
    spec = cfg.measure()
    if coarse is None:
        res = dba_kmeans(kc, X, n_lists, iters=coarse_iters, dba_iters=1,
                         window=w, measure=spec)
        coarse_cents, assign = res.centroids, np.asarray(res.assignment)
    else:
        coarse_cents = jnp.asarray(coarse, jnp.float32)
        if coarse_cents.shape[0] != n_lists:
            raise ValueError(
                f"pre-trained coarse quantizer has {coarse_cents.shape[0]} "
                f"centroids but n_lists={n_lists}")
        assign = np.asarray(coarse_assign(X, coarse_cents, w, spec))

    if cb is None:
        cb = fit(kf, X, cfg)
    else:
        validate_codebook(cb, cfg, D)
    codes = np.asarray(encode(X, cb, cfg))

    order, start, length, max_list = build_lists(assign, n_lists)
    return IVFPQIndex(
        coarse=coarse_cents,
        cb=cb,
        codes=jnp.asarray(codes[order]),
        ids=jnp.asarray(order.astype(np.int32)),
        list_start=jnp.asarray(start),
        list_len=jnp.asarray(length),
        max_list=max_list,
        coarse_window=w)


def _candidates(list_start: jnp.ndarray, list_len: jnp.ndarray,
                max_list: int, probe_lists: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static-shape candidate slots for each query's ``n_probe`` lists.

    ``probe_lists (Nq, P)`` -> (slots (Nq, P*max_list) int32 into
    codes/ids, valid mask).
    """
    offs = jnp.arange(max_list)
    start = list_start[probe_lists]                # (Nq, P)
    length = list_len[probe_lists]
    slots = start[..., None] + offs                # (Nq, P, max_list)
    valid = offs < length[..., None]
    slots = jnp.where(valid, slots, 0)
    nq = probe_lists.shape[0]
    return slots.reshape(nq, -1), valid.reshape(nq, -1)


def fine_rank(codes: jnp.ndarray, ids: jnp.ndarray,
              list_start: jnp.ndarray, list_len: jnp.ndarray, max_list: int,
              dc: jnp.ndarray, qlut: jnp.ndarray, n_probe: int, topk: int,
              live: Optional[jnp.ndarray] = None,
              lb_qlut: Optional[jnp.ndarray] = None,
              lb_budget: Optional[int] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rank one list-sorted shard against a batch of queries.

    ``dc (Nq, n_lists)`` coarse distances, ``qlut (Nq, M, K)`` asymmetric
    tables; ``live`` is an optional ``(N,)`` tombstone mask (False =
    deleted).  Returns ``(distances (Nq, topk), ids (Nq, topk))`` with
    ``inf`` / ``-1`` filling invalid slots, so shard results can be merged
    by a plain top-k.

    ``lb_qlut (Nq, M, K)`` (see :func:`repro.core.lb.lb_lut`) enables the
    cascaded LB pre-filter: candidates are first ranked by their cheap
    lower-bound ADC sum and only the ``lb_budget`` most promising proceed
    to the exact ADC scan.  The bound never exceeds the true asymmetric
    distance, so with ``lb_budget == cap`` results are identical to the
    unfiltered path; smaller budgets trade recall for scan work.

    Both sums are scored by :func:`repro.core.dispatch.adc_lookup`, one
    launch for the batch's padded candidate blocks: on the chip the
    one-hot MXU kernel, elsewhere its plain gather reference.
    """
    neg_dc, probes = jax.lax.top_k(-dc, n_probe)            # (Nq, P)
    slots, valid = _candidates(list_start, list_len, max_list, probes)
    # Hierarchical coarse stage (:func:`coarse_dists` with a two-level
    # quantizer) leaves unprobed lists at dc == +inf; if n_probe exceeds
    # the finite fan-out, top_k pads with such lists — their rows were
    # never coarse-ranked and must not become candidates.  Flat coarse
    # distances are always finite, so this is a no-op there.
    valid = valid & jnp.repeat(jnp.isfinite(neg_dc), max_list, axis=1)
    if live is not None:
        valid = valid & live[slots]
    cand_codes = codes[slots]                               # (Nq, cap, M)
    if lb_qlut is not None and lb_budget is not None \
            and lb_budget < slots.shape[1]:
        lb_d = jnp.where(valid, adc_lookup(cand_codes, lb_qlut), jnp.inf)
        _, keep = jax.lax.top_k(-lb_d, lb_budget)
        slots = jnp.take_along_axis(slots, keep, axis=1)
        valid = jnp.take_along_axis(valid, keep, axis=1)
        cand_codes = jnp.take_along_axis(cand_codes, keep[..., None], axis=1)
    d = jnp.where(valid, adc_lookup(cand_codes, qlut), jnp.inf)
    neg, best = jax.lax.top_k(-d, topk)
    out_ids = ids[jnp.take_along_axis(slots, best, axis=1)]
    return -neg, jnp.where(jnp.isfinite(neg), out_ids, -1)


def _fine_stage(index: IVFPQIndex, dc: jnp.ndarray, qlut: jnp.ndarray,
                n_probe: int, topk: int,
                lb_qlut: Optional[jnp.ndarray] = None,
                lb_budget: Optional[int] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return fine_rank(index.codes, index.ids, index.list_start,
                     index.list_len, index.max_list, dc, qlut, n_probe, topk,
                     lb_qlut=lb_qlut, lb_budget=lb_budget)


def validate_n_probe(n_probe: int, n_lists: int) -> None:
    """Shared probe-budget check (monolithic and streaming indexes)."""
    if not 1 <= n_probe <= n_lists:
        raise ValueError(
            f"n_probe={n_probe} out of range: must satisfy "
            f"1 <= n_probe <= n_lists={n_lists}")


def _validate_probe(n_lists: int, max_list: int, n_probe: int,
                    topk: int, lb_budget: Optional[int] = None) -> None:
    """Static-shape sanity for the probe/rank stage — a clear ``ValueError``
    instead of an XLA shape error deep inside ``top_k``."""
    validate_n_probe(n_probe, n_lists)
    cap = n_probe * max_list
    if not 1 <= topk <= cap:
        raise ValueError(
            f"topk={topk} out of range: must satisfy 1 <= topk <= "
            f"n_probe*max_list={cap} (n_probe={n_probe}, "
            f"max_list={max_list}); raise n_probe or shrink topk")
    if lb_budget is not None and not topk <= lb_budget <= cap:
        raise ValueError(
            f"lb_budget={lb_budget} out of range: must satisfy topk="
            f"{topk} <= lb_budget <= n_probe*max_list={cap}")


def search(index: IVFPQIndex, q: jnp.ndarray, cfg: PQConfig, *,
           n_probe: int, topk: int = 1,
           coarse_window: Optional[int] = None,
           lb_budget: Optional[int] = None
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single query ``q (D,)`` -> (distances (topk,), ids (topk,)).

    Coarse stage: banded DTW to all list centroids; fine stage: asymmetric
    PQDTW over the probed lists' candidates only.
    """
    d, ids = search_batch(index, q[None, :], cfg, n_probe=n_probe,
                          topk=topk, coarse_window=coarse_window,
                          lb_budget=lb_budget)
    return d[0], ids[0]


def search_batch(index: IVFPQIndex, Q: jnp.ndarray, cfg: PQConfig, *,
                 n_probe: int, topk: int = 1,
                 coarse_window: Optional[int] = None,
                 lb_budget: Optional[int] = None,
                 two_level: Optional[TwoLevelCoarse] = None,
                 n_probe_top: Optional[int] = None):
    """Batched search over queries ``Q (Nq, D)``.

    The coarse DTW stage, the asymmetric query tables and the fine ADC
    scan are each computed for the whole batch in dispatch-layer launches
    (Pallas kernels on TPU).

    ``coarse_window`` defaults to ``index.coarse_window`` — the band the
    inverted lists were assigned with at build time — so probe ranking
    always matches the list-assignment metric unless explicitly overridden.
    ``lb_budget`` enables the cascaded LB pre-filter in the fine stage
    (see :func:`fine_rank`): candidates beyond the budget are discarded on
    their envelope lower bound before the exact ADC scan.  The budget is
    capability-gated: for measures without a sound Keogh cascade it is
    ignored (exact full scan) instead of pruning unsoundly.

    ``two_level`` + ``n_probe_top`` switch the coarse stage to the
    hierarchical quantizer (:func:`build_two_level`): probe ranking is
    restricted to the children of each query's ``n_probe_top`` nearest
    top cells.  With ``n_probe_top == two_level.n_top`` the results match
    the flat coarse stage; smaller fan-outs trade coarse recall for an
    ``O(n_top + n_probe_top * max_children)`` coarse cost.
    """
    _validate_probe(index.n_lists, index.max_list, n_probe, topk, lb_budget)
    Q = jnp.asarray(Q, jnp.float32)
    D = Q.shape[-1]
    spec = cfg.measure()
    w = coarse_window if coarse_window is not None else index.coarse_window
    dc = coarse_dists(Q, index.coarse, w, measure=spec,
                      two_level=two_level,
                      n_probe_top=n_probe_top)             # (Nq, n_lists)
    q_segs = segment(Q, cfg)                                # (Nq, M, S)
    qluts = query_lut_batch(q_segs, index.cb, cfg.window(D),
                            not cfg.is_elastic, spec)       # (Nq, M, K)
    if lb_budget is not None and spec is not None and not spec.has_keogh_lb:
        # The envelope bound table is only a lower bound for measures with
        # a sound Keogh cascade; fall back to the exact full scan rather
        # than an unsound prune.
        lb_budget = None
    if lb_budget is not None and lb_budget < n_probe * index.max_list:
        lb_luts = lb_lut(q_segs, index.cb.centroids, index.cb.env_upper,
                         index.cb.env_lower)                # (Nq, M, K)
        return _fine_stage(index, dc, qluts, n_probe, topk, lb_luts,
                           lb_budget)
    return _fine_stage(index, dc, qluts, n_probe, topk)
