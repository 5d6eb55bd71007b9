"""StreamingIndex — LSM-style lifecycle over IVF-PQDTW shards.

Write path (host-side, numpy): ``insert`` fills the fixed-capacity
:class:`~repro.index.segments.HotBuffer`; a full buffer auto-``flush``\\ es
into a :class:`~repro.index.segments.SealedSegment` — PQ codes against the
*shared* codebook, list-sorted under the *shared* coarse quantizer.  Both
quantizers are trained once (``bootstrap``) and never change afterwards,
which is what makes segments mergeable: ``compact`` concatenates live rows
and re-balances the inverted lists without touching a single code.

Read path (device-side, jitted): one coarse-DTW launch + one query-LUT
launch for the whole batch (shared by every segment), then a per-segment
fine stage (:func:`repro.core.ivf.fine_rank`) and an exact LB-cascade
filter-and-refine scan of the hot buffer, merged with a final
``lax.top_k``.  All shapes are
static: flush-born segments share one compiled fine stage, the hot scan is
always ``(Nq, capacity)``, and tombstones are masks, not re-layouts.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.dtw import euclidean_sq
from ..core.ivf import (TwoLevelCoarse, build_two_level, coarse_assign,
                        coarse_dists, fine_rank, validate_codebook,
                        validate_n_probe)
from ..core.lb_search import filtered_topk
from ..core.kmeans import dba_kmeans
from ..core.pq import (PQCodebook, PQConfig, encode, fit, memory_cost,
                       query_lut_batch, segment)
from .segments import HotBuffer, SealedSegment, seal

__all__ = ["IndexConfig", "StreamingIndex"]


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Lifecycle hyper-parameters around a :class:`PQConfig`.

    ``n_shards`` is the data-partition count of the sealed layout: every
    segment is sealed shard-major for ``n_shards`` devices
    (:mod:`repro.index.placement`), which the list-sharded planner
    (:func:`repro.index.planner.search_sharded`) maps 1:1 onto the search
    mesh.  ``n_shards == 1`` is the historical replicated layout.

    ``n_top_lists > 0`` enables the hierarchical (two-level) coarse
    quantizer: queries rank ``n_top_lists`` top cells and fan out to the
    children of their ``n_probe_top`` nearest — an ``O(n_top +
    fan_out)`` coarse stage instead of ``O(n_lists)``.  With
    ``n_probe_top == n_top_lists`` results match the flat stage exactly.

    ``band="adaptive"`` switches the hot-buffer elastic scan to per-pair
    alignment corridors (:mod:`repro.core.corridor`): narrower registers,
    faster sweeps, documented *approximate* results — the certified-exact
    LB cascade applies to the default ``"static"`` band only.

    >>> from repro.core.pq import PQConfig
    >>> cfg = IndexConfig(PQConfig(n_sub=2, codebook_size=4), n_lists=4)
    >>> cfg.coarse_window(48)
    5
    >>> IndexConfig(PQConfig(), n_lists=4, n_probe_top=2)
    Traceback (most recent call last):
        ...
    ValueError: n_probe_top=2 requires a two-level coarse quantizer (set n_top_lists > 0)
    """
    pq: PQConfig
    n_lists: int = 8
    hot_capacity: int = 128
    coarse_iters: int = 8
    coarse_window_frac: float = 0.1
    n_shards: int = 1
    n_top_lists: int = 0
    n_probe_top: int = 0
    band: str = "static"

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"n_shards={self.n_shards} must be >= 1")
        if self.band not in ("static", "adaptive"):
            raise ValueError(f"band={self.band!r} must be 'static' or "
                             f"'adaptive'")
        if self.n_top_lists:
            if not 1 <= self.n_top_lists <= self.n_lists:
                raise ValueError(
                    f"n_top_lists={self.n_top_lists} out of range: must "
                    f"satisfy 1 <= n_top_lists <= n_lists={self.n_lists}")
            if not 1 <= self.n_probe_top <= self.n_top_lists:
                raise ValueError(
                    f"n_probe_top={self.n_probe_top} out of range: must "
                    f"satisfy 1 <= n_probe_top <= n_top_lists="
                    f"{self.n_top_lists}")
        elif self.n_probe_top:
            raise ValueError(
                f"n_probe_top={self.n_probe_top} requires a two-level "
                f"coarse quantizer (set n_top_lists > 0)")

    def coarse_window(self, D: int) -> int:
        return max(1, int(round(self.coarse_window_frac * D)))


# ---------------------------------------------------------------------------
# Pure search math (shared by StreamingIndex.search and the sharded planner)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_list", "n_probe", "k"))
def _rank_segment(codes, ids, live, list_start, list_len, dc, qluts, *,
                  max_list: int, n_probe: int, k: int):
    """Fine stage of a query batch over one sealed segment -> ``(Nq, k)``
    d, ids.

    Jitted per *shape*, not per segment: every flush-born segment (same
    padded rows, same ``max_list`` = hot capacity) reuses one compiled
    fine stage regardless of how many segments exist."""
    with jax.named_scope("index.search.fine"):
        return fine_rank(codes, ids, list_start, list_len, max_list, dc,
                         qluts, n_probe, k, live=live)


@functools.partial(jax.jit, static_argnames=("window", "k", "euclidean",
                                             "measure", "with_stats",
                                             "band"))
def _scan_hot(data, ids, live, Q, q_valid=None, *, window: int, k: int,
              euclidean: bool, measure=None, with_stats: bool = False,
              band: str = "static"):
    """Exact scan of the hot buffer -> ``(Nq, k)`` d, ids.

    The configured elastic measure under PQDTW-style metrics, squared
    Euclidean under the PQ_ED
    baseline — matching the metric the sealed segments' LUTs encode, so
    hot and sealed distances stay order-compatible in the merge.  The
    elastic path runs the LB-cascade filter-and-refine top-k
    (:func:`repro.core.lb_search.filtered_topk`): every (query, hot row)
    pair is bounded cheaply and only candidates the cascade cannot exclude
    reach the exact banded wavefront — same distances, fewer sweeps.
    Measures without the pruning capabilities take its exact dense
    fallback automatically.  ``q_valid`` is the optional query padding
    mask of the sharded planner — masked rows produce ``inf``/``-1`` and
    never claim LB-cascade refine work.

    ``with_stats=True`` (static, obs-enabled callers only) additionally
    returns the LB-cascade pruning telemetry dict of
    :func:`repro.core.lb_search.filtered_topk`; the default path compiles
    the exact pre-telemetry graph, so obs-off results stay bit-identical.
    """
    with jax.named_scope("index.search.hot"):
        if euclidean:
            d2 = euclidean_sq(Q, data)
            dh = jnp.sqrt(jnp.maximum(d2, 0.0))
            dh = jnp.where(live[None, :], dh, jnp.inf)           # (Nq, cap)
            if q_valid is not None:
                dh = jnp.where(q_valid[:, None], dh, jnp.inf)
            neg, idx = jax.lax.top_k(-dh, k)
            out_ids = jnp.where(jnp.isfinite(neg), ids[idx], -1)
            if with_stats:
                # no elastic cascade under the PQ_ED baseline: report an empty
                # telemetry record rather than a fake 0% pruning rate
                zero = jnp.zeros((), jnp.int32)
                return -neg, out_ids, {"n_bounded": zero, "n_refined": zero,
                                       "n_waves": zero,
                                       "refined_per_wave": zero[None]}
            return -neg, out_ids
        d2, idx, st = filtered_topk(Q, data, window, k, valid=live,
                                    measure=measure, q_valid=q_valid,
                                    with_stats=with_stats, band=band)
        dh = jnp.sqrt(jnp.maximum(d2, 0.0))
        out_ids = jnp.where(idx >= 0, ids[jnp.maximum(idx, 0)], -1)
        if with_stats:
            return dh, out_ids, st
        return dh, out_ids


@functools.partial(jax.jit, static_argnames=("topk",))
def _merge_topk(parts_d: Tuple[jnp.ndarray, ...],
                parts_i: Tuple[jnp.ndarray, ...], *, topk: int):
    with jax.named_scope("index.search.merge"):
        all_d = jnp.concatenate(parts_d, axis=1)
        all_i = jnp.concatenate(parts_i, axis=1)
        missing = topk - all_d.shape[1]
        if missing > 0:
            Nq = all_d.shape[0]
            all_d = jnp.concatenate(
                [all_d, jnp.full((Nq, missing), jnp.inf)], 1)
            all_i = jnp.concatenate(
                [all_i, jnp.full((Nq, missing), -1, all_i.dtype)], 1)
        neg, best = jax.lax.top_k(-all_d, topk)
        return -neg, jnp.take_along_axis(all_i, best, axis=1)


def search_impl(coarse: jnp.ndarray, cb: PQCodebook,
                segs: Tuple[SealedSegment, ...],
                hot: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]],
                Q: jnp.ndarray, *, icfg: IndexConfig, n_probe: int,
                topk: int, dim: int,
                two_level: Optional[TwoLevelCoarse] = None,
                q_valid: Optional[jnp.ndarray] = None,
                with_stats: bool = False):
    """Fan ``Q (Nq, D)`` out over every segment and merge top-k.

    ``segs`` is a (possibly empty) tuple of sealed segments; ``hot`` is
    ``(data (cap, D), ids (cap,), live (cap,))`` or None when the buffer is
    empty.  Returns ``(distances, ids)`` of shape ``(Nq, topk)``, distance
    ``inf`` / id ``-1`` where fewer than ``topk`` live rows exist.  Sealed
    rows are ranked by asymmetric PQDTW, hot rows by exact banded DTW —
    both in sqrt space, so the merge is order-compatible.

    ``two_level`` switches the coarse stage to the hierarchical quantizer
    with the config's ``n_probe_top`` fan-out; ``q_valid (Nq,)`` marks
    padding rows of a sharded query batch (results for masked rows are
    arbitrary — the caller slices them off — but they are excluded from
    LB-cascade refine work and pruning statistics).

    ``with_stats=True`` returns ``(distances, ids, stats)`` where
    ``stats`` is the hot-scan LB-cascade telemetry dict (device scalars;
    ``None`` when the hot buffer is empty) — the obs-enabled entry point
    (:meth:`StreamingIndex.search`) pulls it to host and feeds the
    registry.  The flag threads a *static* argument into the jitted hot
    scan, so the default path compiles the exact pre-telemetry graph.

    Pipeline stages run inside :func:`repro.obs.span` blocks (coarse, lut,
    fine, hot, merge) with device work fenced into its span when obs is
    enabled; disabled spans are shared no-ops — no fences, no syncs, no
    timing.  When this function is itself traced (the query-sharded
    planner's ``shard_map``), the spans time the trace — once per
    compilation — and the fences no-op on tracers.

    Deliberately NOT one enclosing jit: the pieces (coarse cdist, query
    LUTs, per-segment fine stage, hot scan, final merge) are jitted
    separately, so growing the segment count only recompiles the tiny
    concat/top-k merge instead of the whole search graph — no query-latency
    spike every time a flush adds a segment.
    """
    Q = jnp.asarray(Q, jnp.float32)
    parts_d, parts_i = [], []
    hot_stats = None

    spec = icfg.pq.measure()
    if segs:
        w = icfg.coarse_window(dim)
        with obs.span("index.search.coarse") as sp:
            dc = sp.fence(coarse_dists(
                Q, coarse, w, measure=spec, two_level=two_level,
                n_probe_top=icfg.n_probe_top if two_level is not None
                else None))                                  # (Nq, n_lists)
        with obs.span("index.search.lut") as sp:
            qluts = sp.fence(query_lut_batch(
                segment(Q, icfg.pq), cb, icfg.pq.window(dim),
                not icfg.pq.is_elastic, spec))                # (Nq, M, K)
        with obs.span("index.search.fine") as sp:
            for sg in segs:
                k = min(topk, n_probe * sg.max_list)
                if k < 1:
                    continue
                d, i = _rank_segment(sg.codes, sg.ids, sg.live,
                                     sg.list_start, sg.list_len, dc, qluts,
                                     max_list=sg.max_list, n_probe=n_probe,
                                     k=k)
                parts_d.append(d)
                parts_i.append(i)
            sp.fence(parts_d)

    if hot is not None:
        data, ids, live = hot
        with obs.span("index.search.hot") as sp:
            out = _scan_hot(data, ids, live, Q, q_valid,
                            window=icfg.coarse_window(dim),
                            k=min(topk, data.shape[0]),
                            euclidean=not icfg.pq.is_elastic,
                            measure=spec, with_stats=with_stats,
                            band=icfg.band)
            if with_stats:
                d, i, hot_stats = out
            else:
                d, i = out
            sp.fence((d, i))
        parts_d.append(d)
        parts_i.append(i)

    if not parts_d:
        Nq = Q.shape[0]
        empty = (jnp.full((Nq, topk), jnp.inf),
                 jnp.full((Nq, topk), -1, jnp.int32))
        return empty + (None,) if with_stats else empty

    with obs.span("index.search.merge") as sp:
        d, i = sp.fence(_merge_topk(tuple(parts_d), tuple(parts_i),
                                    topk=topk))
    if with_stats:
        return d, i, hot_stats
    return d, i


# ---------------------------------------------------------------------------
# The lifecycle object
# ---------------------------------------------------------------------------

class StreamingIndex:
    """Incrementally maintained IVF-PQDTW index (see module docstring).

    Construct with :meth:`bootstrap` (trains the shared quantizers on a
    sample) or :meth:`from_parts` (pre-trained quantizers / restore path).

    The full write/read lifecycle in one example (tiny shapes so it runs
    as a doctest):

    >>> import jax, numpy as np
    >>> from repro.core.pq import PQConfig
    >>> cfg = IndexConfig(
    ...     PQConfig(n_sub=2, codebook_size=4, use_prealign=False,
    ...              kmeans_iters=1, dba_iters=1),
    ...     n_lists=2, hot_capacity=4, coarse_iters=2)
    >>> X = np.sin(np.arange(12 * 16, dtype=np.float32)).reshape(12, 16)
    >>> idx = StreamingIndex.bootstrap(jax.random.PRNGKey(0), X, cfg)
    >>> ids = idx.insert(X[:6])            # fills hot_capacity=4 -> 1 seal
    >>> [int(i) for i in ids[:3]], len(idx.segments)
    ([0, 1, 2], 1)
    >>> idx.delete([1])                    # tombstone by external id
    1
    >>> dist, out = idx.search(X[:2], n_probe=2, topk=1)
    >>> out.shape                          # (Nq, topk) external ids
    (2, 1)
    >>> bool(np.isfinite(np.asarray(dist)).all())
    True
    >>> idx.flush(); idx.compact()         # seal the tail, drop dead rows
    >>> len(idx.segments), idx.n_live()
    (1, 5)
    """

    def __init__(self, cfg: IndexConfig, coarse: jnp.ndarray,
                 cb: PQCodebook, dim: int,
                 two_level: Optional[TwoLevelCoarse] = None):
        if coarse.shape[0] != cfg.n_lists:
            raise ValueError(
                f"coarse quantizer has {coarse.shape[0]} centroids, "
                f"config says n_lists={cfg.n_lists}")
        if cfg.hot_capacity < 1:
            raise ValueError(
                f"hot_capacity={cfg.hot_capacity} must be >= 1 (inserts "
                f"stage in the hot buffer before sealing)")
        # the prealign geometry (use_prealign/tail) must match the codebook:
        # every seal re-encodes through it, so a drifted config would write
        # segments of the wrong static length into immutable shards
        validate_codebook(cb, cfg.pq, int(dim))
        self.cfg = cfg
        self.coarse = jnp.asarray(coarse, jnp.float32)
        self.cb = cb
        self.dim = int(dim)
        # hierarchical coarse quantizer: derived deterministically from the
        # (frozen) coarse centroids when the config asks for one, unless a
        # pre-built table is handed in (the snapshot-restore path)
        if two_level is None and cfg.n_top_lists:
            two_level = build_two_level(
                jax.random.PRNGKey(0), self.coarse, cfg.n_top_lists,
                cfg.coarse_window(self.dim), measure=cfg.pq.measure(),
                iters=cfg.coarse_iters)
        self.two_level = two_level
        self.hot = HotBuffer(cfg.hot_capacity, dim)
        self.segments: List[SealedSegment] = []
        # host-side mirrors of each segment's id array (immutable) and live
        # mask (updated alongside tombstone()), so the delete/accounting
        # paths never download device arrays
        self._seg_ids: List[np.ndarray] = []
        self._seg_live: List[np.ndarray] = []
        # every id physically resident anywhere (tombstoned rows included —
        # they occupy slots until flush/compact drops them), for O(batch)
        # collision checks on explicit-id inserts
        self._resident: set = set()
        # device copy of the hot buffer, rebuilt only after a mutation
        self._hot_device: Optional[Tuple] = None
        self.next_id = 0

    # -- construction -------------------------------------------------------

    @classmethod
    def bootstrap(cls, key: jax.Array, X_train: np.ndarray,
                  cfg: IndexConfig) -> "StreamingIndex":
        """Train the shared coarse + PQ quantizers on ``X_train`` and return
        an *empty* index (the sample is not inserted)."""
        X_train = jnp.asarray(X_train, jnp.float32)
        D = X_train.shape[-1]
        kc, kf = jax.random.split(key)
        res = dba_kmeans(kc, X_train, cfg.n_lists, iters=cfg.coarse_iters,
                         dba_iters=1, window=cfg.coarse_window(D),
                         measure=cfg.pq.measure())
        cb = fit(kf, X_train, cfg.pq)
        return cls(cfg, res.centroids, cb, D)

    @classmethod
    def from_parts(cls, cfg: IndexConfig, coarse: jnp.ndarray,
                   cb: PQCodebook, dim: int,
                   two_level: Optional[TwoLevelCoarse] = None
                   ) -> "StreamingIndex":
        return cls(cfg, coarse, cb, dim, two_level=two_level)

    # -- write path ---------------------------------------------------------

    def insert(self, X: np.ndarray, ids: Optional[Sequence[int]] = None
               ) -> np.ndarray:
        """Add series ``X (n, D)``; returns their external ids.  Flushes
        automatically whenever the hot buffer fills."""
        X = np.asarray(X, np.float32)
        if X.ndim != 2 or X.shape[1] != self.dim:
            raise ValueError(
                f"expected (n, {self.dim}) series, got {X.shape}")
        n = X.shape[0]
        if ids is None:
            out = np.arange(self.next_id, self.next_id + n, dtype=np.int32)
            self.next_id += n
        else:
            out = np.asarray(ids, np.int32)
            if len(out) != n:
                raise ValueError(f"{n} series but {len(out)} ids")
            if n and int(out.min()) < 0:
                raise ValueError(
                    "external ids must be >= 0 (-1 is the reserved "
                    "empty-slot / no-result sentinel)")
            if len(np.unique(out)) != n:
                raise ValueError("duplicate ids within one insert batch")
            # one row per external id: reject ids still resident anywhere
            # (tombstoned rows occupy slots until flush/compact drops them)
            clash = self._resident.intersection(out.tolist())
            if clash:
                raise ValueError(
                    f"ids already resident in the index: "
                    f"{sorted(clash)[:8]}")
            self.next_id = max(self.next_id, int(out.max(initial=-1)) + 1)
        self._resident.update(out.tolist())
        self._hot_device = None
        with obs.span("index.insert"):
            i = 0
            while i < n:
                i += self.hot.append(X[i:], out[i:])
                if self.hot.space == 0:
                    self.flush()
        if obs.enabled():
            obs.counter("index_inserted_total", persistent=True).inc(n)
            self._update_obs_gauges()
        return out

    def delete(self, ids: Sequence[int]) -> int:
        """Tombstone by external id; returns how many rows were hit."""
        dead = np.asarray(ids, np.int32)
        hit = self.hot.tombstone(dead)
        if hit:
            self._hot_device = None
        for s, sg in enumerate(self.segments):
            mask = np.isin(self._seg_ids[s], dead) & self._seg_live[s]
            if mask.any():
                self.segments[s] = sg.tombstone(mask)
                self._seg_live[s] = self._seg_live[s] & ~mask
                hit += int(mask.sum())
        if obs.enabled():
            obs.counter("index_deleted_total", persistent=True).inc(hit)
            self._update_obs_gauges()
        return hit

    def flush(self) -> None:
        """Seal the hot buffer's live rows into a new sealed segment."""
        with obs.span("index.flush"):
            dropped = self.hot.ids[(self.hot.ids >= 0) & ~self.hot.live]
            rows, ids = self.hot.take_live()
            self._resident.difference_update(dropped.tolist())
            self._hot_device = None
            if len(ids) == 0:
                return
            Xj = jnp.asarray(rows)
            with obs.span("index.flush.encode") as sp:
                codes = np.asarray(sp.fence(encode(Xj, self.cb,
                                                   self.cfg.pq)))
            with obs.span("index.flush.assign") as sp:
                assign = np.asarray(sp.fence(coarse_assign(
                    Xj, self.coarse, self.cfg.coarse_window(self.dim),
                    self.cfg.pq.measure())))
            cap = self.cfg.hot_capacity
            with obs.span("index.flush.seal") as sp:
                # shard_round = ceil(cap / n_shards): every flush-born
                # segment gets the same shard_cap regardless of list skew,
                # so they all share one compiled fine-stage / planner shape
                seg = seal(codes, ids, assign, self.cfg.n_lists, rows=cap,
                           max_list=cap, n_shards=self.cfg.n_shards,
                           shard_round=-(-cap // self.cfg.n_shards))
                self._add_segment(sp.fence(seg))
        if obs.enabled():
            obs.counter("index_sealed_rows_total",
                        persistent=True).inc(len(ids))
            self._update_obs_gauges()

    def compact(self) -> None:
        """Merge every sealed segment into one: tombstoned and padding rows
        are dropped, inverted lists re-balanced, and the fine stage's
        candidate width shrinks from the flush-time worst case (the full
        segment capacity) back to the true longest merged list."""
        if not self.segments:
            return
        with obs.span("index.compact"):
            codes, ids, assign = [], [], []
            for s, sg in enumerate(self.segments):
                live = self._seg_live[s]
                dead = self._seg_ids[s][~live]
                self._resident.difference_update(dead[dead >= 0].tolist())
                codes.append(np.asarray(sg.codes)[live])
                ids.append(self._seg_ids[s][live])
                assign.append(np.asarray(sg.assign)[live])
            codes = np.concatenate(codes)
            ids = np.concatenate(ids)
            assign = np.concatenate(assign)
            self.segments, self._seg_ids, self._seg_live = [], [], []
            if len(ids):
                self._add_segment(seal(codes, ids, assign, self.cfg.n_lists,
                                       rows=len(ids),
                                       n_shards=self.cfg.n_shards))
        if obs.enabled():
            obs.counter("index_compactions_total", persistent=True).inc()
            self._update_obs_gauges()

    # -- read path ----------------------------------------------------------

    def search(self, Q: np.ndarray, *, n_probe: int, topk: int = 1
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Top-``topk`` live neighbors of ``Q (Nq, D)`` -> (dist, ids).

        With obs enabled (:func:`repro.obs.enabled`) the search runs under
        stage spans and records LB-cascade pruning telemetry — the stats
        transfer is a deliberate device sync, which is why the disabled
        path never requests stats (``with_stats`` is static: the obs-off
        compiled graph, and therefore the results, are bit-identical to an
        uninstrumented build).
        """
        Q = self._validate(Q, n_probe, topk)
        if not obs.enabled():
            return search_impl(self.coarse, self.cb, tuple(self.segments),
                               self._hot_arrays(), Q,
                               icfg=self.cfg, n_probe=n_probe, topk=topk,
                               dim=self.dim, two_level=self.two_level)
        with obs.span("index.search") as sp:
            d, ids, hot_stats = search_impl(
                self.coarse, self.cb, tuple(self.segments),
                self._hot_arrays(), Q, icfg=self.cfg, n_probe=n_probe,
                topk=topk, dim=self.dim, two_level=self.two_level,
                with_stats=True)
            sp.fence((d, ids))
        self._record_search_obs(Q.shape[0], hot_stats)
        return d, ids

    def _record_search_obs(self, n_queries: int, hot_stats) -> None:
        """Feed one search's counters into the obs registry (obs on)."""
        obs.counter("index_searches_total", persistent=True).inc()
        obs.counter("index_queries_total",
                    persistent=True).inc(int(n_queries))
        if hot_stats is not None:
            bounded = int(hot_stats["n_bounded"])
            refined = int(hot_stats["n_refined"])
            if bounded:
                obs.counter("lb_candidates_bounded_total",
                            persistent=True).inc(bounded)
                obs.counter("lb_candidates_refined_total",
                            persistent=True).inc(refined)
                obs.counter("lb_candidates_pruned_total",
                            persistent=True).inc(bounded - refined)
                obs.counter("lb_refine_waves_total", persistent=True).inc(
                    int(hot_stats["n_waves"]))
                obs.histogram("lb_pruning_rate",
                              buckets=tuple(i / 10 for i in range(1, 11)),
                              persistent=True).record(
                    1.0 - refined / bounded)
        self._update_obs_gauges()

    def _update_obs_gauges(self) -> None:
        """Refresh the lifecycle gauges (host-side mirrors only — no
        device transfers)."""
        cap = self.cfg.hot_capacity
        obs.gauge("hot_fill", persistent=True).set(self.hot.count)
        obs.gauge("hot_occupancy", persistent=True).set(
            self.hot.count / cap)
        obs.gauge("n_segments", persistent=True).set(self.n_segments)
        sealed_resident = sum(int((ids >= 0).sum())
                              for ids in self._seg_ids)
        sealed_live = sum(int(live.sum()) for live in self._seg_live)
        resident = sealed_resident + self.hot.count
        live = sealed_live + self.hot.n_live()
        obs.gauge("sealed_rows", persistent=True).set(sealed_resident)
        obs.gauge("tombstone_fraction", persistent=True).set(
            (resident - live) / resident if resident else 0.0)

    def _validate(self, Q, n_probe: int, topk: int) -> jnp.ndarray:
        Q = jnp.asarray(Q, jnp.float32)
        if Q.ndim != 2 or Q.shape[1] != self.dim:
            raise ValueError(
                f"expected (n, {self.dim}) queries, got {Q.shape}")
        validate_n_probe(n_probe, self.cfg.n_lists)
        if topk < 1:
            raise ValueError(f"topk={topk} must be >= 1")
        return Q

    def _add_segment(self, seg: SealedSegment,
                     host_ids: Optional[np.ndarray] = None,
                     host_live: Optional[np.ndarray] = None) -> None:
        self.segments.append(seg)
        self._seg_ids.append(np.asarray(seg.ids) if host_ids is None
                             else np.asarray(host_ids))
        self._seg_live.append(np.asarray(seg.live) if host_live is None
                              else np.asarray(host_live))
        ids = self._seg_ids[-1]
        self._resident.update(ids[ids >= 0].tolist())

    def _hot_arrays(self):
        if self.hot.count == 0:
            return None
        if self._hot_device is None:      # invalidated on any hot mutation
            self._hot_device = (jnp.asarray(self.hot.data),
                                jnp.asarray(self.hot.ids),
                                jnp.asarray(self.hot.live))
        return self._hot_device

    # -- accounting ---------------------------------------------------------

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    def n_live(self) -> int:
        return self.hot.n_live() + sum(
            int(live.sum()) for live in self._seg_live)

    def live_ids(self) -> np.ndarray:
        out = [self.hot.ids[self.hot.live]]
        out += [ids[live] for ids, live in zip(self._seg_ids,
                                               self._seg_live)]
        return np.sort(np.concatenate(out))

    def memory_cost(self) -> dict:
        """§3.4 accounting extended with the lifecycle-layer overheads."""
        rows = sum(sg.rows for sg in self.segments)
        return memory_cost(self.cfg.pq, self.dim, rows,
                           n_segments=self.n_segments,
                           n_lists=self.cfg.n_lists,
                           hot_capacity=self.cfg.hot_capacity,
                           n_devices=self.cfg.n_shards)

    def stats(self) -> dict:
        return dict(n_segments=self.n_segments, n_live=self.n_live(),
                    hot_fill=self.hot.count, next_id=self.next_id,
                    sealed_rows=sum(sg.rows for sg in self.segments),
                    max_lists=[sg.max_list for sg in self.segments])
