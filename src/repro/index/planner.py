"""Sharded query planner: scale search out over a device mesh.

Two partitioning strategies over the 1-D ``search`` axis of
:func:`repro.launch.mesh.make_search_mesh`:

* ``"queries"`` — the index (coarse centroids, codebook, sealed segments,
  hot buffer) is *replicated*; the query batch is padded to a multiple of
  the mesh size and sharded.  Each device runs the identical single-device
  plan (:func:`repro.index.streaming.search_impl`) on its query block —
  top-k over queries is embarrassingly parallel, so the only collective is
  the implicit output gather.  Padding rows carry a ``q_valid`` mask down
  the whole plan, so they are excluded from LB-cascade refine work and
  pruning statistics instead of burning wavefront sweeps.  Right when the
  index fits on every device and the query stream is wide.

* ``"lists"`` — the *data* is partitioned: sealed segments are laid out
  shard-major (:func:`repro.index.segments.seal` with ``n_shards`` equal
  to the mesh size, lists placed by :mod:`repro.index.placement`), and
  each device scans only its locally-placed inverted lists.  The query
  batch, coarse distances and query LUTs are replicated; every device
  ranks its local lists with the existing fine-stage kernels, scans a
  striped slice of the hot buffer, merges a device-local top-k, and the
  partial ``(topk, ids)`` tiles fan in with a device-resident
  ``all_gather`` + masked merge — no host round-trip.  Because every
  candidate row is scanned by exactly one device and the final merge
  re-ranks the union of all partials, results match the single-device
  plan exactly.  Right when the sealed codes outgrow one device's memory:
  per-device bytes shrink ~linearly with the mesh (see
  ``repro.core.pq.memory_cost`` ``max_device_bytes``).

On CPU (or any single-device runtime) both strategies degenerate to a
1-device mesh whose ``shard_map`` is bit-identical to the plain path, so
the planner is exercised by the tier-1 suite without TPU hardware.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from ..core.ivf import coarse_dists
from ..core.pq import query_lut_batch, segment
from ..launch.mesh import make_search_mesh, validate_search_mesh
from .streaming import (StreamingIndex, _merge_topk, _rank_segment,
                        _scan_hot, search_impl)

__all__ = ["search_sharded"]

_PARTITIONS = ("auto", "queries", "lists")


def _pad_queries(Q: jnp.ndarray, n_dev: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pad ``Q`` to a multiple of ``n_dev`` rows; returns
    ``(Q_padded, q_valid, Nq)`` where ``q_valid`` masks the real rows."""
    Nq = Q.shape[0]
    pad = (-Nq) % n_dev
    if pad:
        Q = jnp.concatenate([Q, jnp.zeros((pad, Q.shape[1]), Q.dtype)], 0)
    q_valid = jnp.arange(Nq + pad) < Nq
    return Q, q_valid, Nq


def _search_query_sharded(index: StreamingIndex, Q: jnp.ndarray,
                          mesh: Mesh, n_probe: int, topk: int
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n_dev = mesh.shape["search"]
    Q, q_valid, Nq = _pad_queries(Q, n_dev)

    plan = (index.coarse, index.cb, tuple(index.segments),
            index._hot_arrays(), index.two_level)

    def per_device(plan, Qb, qv):
        coarse, cb, segs, hot, two_level = plan
        return search_impl(coarse, cb, segs, hot, Qb, icfg=index.cfg,
                           n_probe=n_probe, topk=topk, dim=index.dim,
                           two_level=two_level, q_valid=qv)

    # check_vma=False: jax has no varying-axes rule for pallas_call, and the
    # out_specs fully describe the (embarrassingly parallel) output layout.
    with obs.span("sharded.execute") as sp:
        d, ids = sp.fence(jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P("search", None), P("search")),
            out_specs=(P("search", None), P("search", None)),
            check_vma=False)(plan, Q, q_valid))
    return d[:Nq], ids[:Nq]


def _search_list_sharded(index: StreamingIndex, Q: jnp.ndarray,
                         mesh: Mesh, n_probe: int, topk: int
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n_dev = mesh.shape["search"]
    icfg = index.cfg
    validate_search_mesh(mesh, icfg.n_shards)
    for sg in index.segments:
        if sg.n_shards != n_dev:
            raise ValueError(
                f"list-sharded search on a {n_dev}-device mesh needs every "
                f"segment sealed with n_shards={n_dev}, found a segment "
                f"with n_shards={sg.n_shards} — set "
                f"IndexConfig(n_shards={n_dev}) and compact() (or flush "
                f"new data) to re-seal the layout")

    Q = jnp.asarray(Q, jnp.float32)
    Nq = Q.shape[0]
    segs = tuple(index.segments)
    hot = index._hot_arrays()
    if not segs and hot is None:
        return (jnp.full((Nq, topk), jnp.inf),
                jnp.full((Nq, topk), -1, jnp.int32))

    spec = icfg.pq.measure()
    w = icfg.coarse_window(index.dim)
    # Replicated stages: the coarse ranking and the per-query LUTs are
    # tiny relative to the sealed codes, so they are computed once for the
    # full batch and broadcast — every device probes with identical
    # numbers, which is what makes the fan-in merge exact.
    with obs.span("sharded.coarse") as sp:
        dc = sp.fence(coarse_dists(
            Q, index.coarse, w, measure=spec,
            two_level=index.two_level,
            n_probe_top=icfg.n_probe_top if index.two_level
            is not None else None))                          # (Nq, n_lists)
    with obs.span("sharded.lut") as sp:
        qluts = sp.fence(query_lut_batch(
            segment(Q, icfg.pq), index.cb, icfg.pq.window(index.dim),
            not icfg.pq.is_elastic, spec))                   # (Nq, M, K)

    views = tuple(sg.shard_views() for sg in segs)
    metas = tuple((sg.max_list, min(topk, n_probe * sg.max_list))
                  for sg in segs)

    def per_device(dc, qluts, Qb, hot, views):
        # spans inside this function run under the shard_map trace: they
        # time tracing (once per compilation) and bridge the stage names
        # into device profiles via TraceAnnotation — per-call wall time
        # lives in the host-level "sharded.execute" span around the launch
        parts_d, parts_i = [], []
        with obs.span("sharded.device_scan"):
            for (codes, ids, live, loc_start, loc_len), (max_list, k) \
                    in zip(views, metas):
                if k < 1:
                    continue
                # leading shard axis is sliced to 1 by shard_map: [0] is
                # this device's block; loc_start/loc_len address rows
                # inside it, lists placed elsewhere have local length 0
                d, i = _rank_segment(codes[0], ids[0], live[0],
                                     loc_start[0], loc_len[0], dc, qluts,
                                     max_list=max_list, n_probe=n_probe,
                                     k=k)
                parts_d.append(d)
                parts_i.append(i)
            if hot is not None:
                data, h_ids, h_live = hot
                cap = data.shape[0]
                # stripe the (replicated) hot buffer: row r belongs to
                # device r % n_dev, so every live row is scanned by
                # exactly one device
                mine = (jnp.arange(cap) % n_dev
                        ) == jax.lax.axis_index("search")
                d, i = _scan_hot(data, h_ids, h_live & mine, Qb,
                                 window=w, k=min(topk, cap),
                                 euclidean=not icfg.pq.is_elastic,
                                 measure=spec)
                parts_d.append(d)
                parts_i.append(i)
        with obs.span("sharded.fanin_merge"):
            if parts_d:
                d_loc, i_loc = _merge_topk(tuple(parts_d), tuple(parts_i),
                                           topk=topk)
            else:
                d_loc = jnp.full((Qb.shape[0], topk), jnp.inf)
                i_loc = jnp.full((Qb.shape[0], topk), -1, jnp.int32)
            # device-resident fan-in: gather every device's partial top-k
            # and re-rank the union — the merged result is replicated, no
            # host round-trip.  Empty partial slots carry +inf / -1 and
            # lose to any real candidate, so padded lanes never surface.
            g_d = jax.lax.all_gather(d_loc, "search")  # (n_dev, Nq, topk)
            g_i = jax.lax.all_gather(i_loc, "search")
            all_d = jnp.moveaxis(g_d, 0, 1).reshape(
                Qb.shape[0], n_dev * topk)
            all_i = jnp.moveaxis(g_i, 0, 1).reshape(
                Qb.shape[0], n_dev * topk)
            neg, best = jax.lax.top_k(-all_d, topk)
            return -neg, jnp.take_along_axis(all_i, best, axis=1)

    view_spec = (P("search", None, None), P("search", None),
                 P("search", None), P("search", None), P("search", None))
    with obs.span("sharded.execute") as sp:
        d, ids = sp.fence(jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(), P(), P(), tuple(view_spec for _ in views)),
            out_specs=(P(None, None), P(None, None)),
            check_vma=False)(dc, qluts, Q, hot, views))
    return d, ids


def search_sharded(index: StreamingIndex, Q: np.ndarray, *,
                   n_probe: int, topk: int = 1,
                   mesh: Optional[Mesh] = None,
                   partition: str = "auto"
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-device :meth:`StreamingIndex.search` -> ``(dist, ids)``.

    ``partition`` selects the strategy (module docstring): ``"queries"``
    replicates the index and shards the batch, ``"lists"`` partitions the
    sealed inverted lists across the mesh (requires segments sealed with
    ``n_shards`` equal to the mesh size) and fans the per-device partial
    top-k back in with a device-resident ``all_gather`` merge.  ``"auto"``
    picks ``"lists"`` when the index layout matches the mesh
    (``cfg.n_shards == n_devices > 1``) and ``"queries"`` otherwise.

    Results match the single-device path under either strategy — same
    kernels, same distances; candidate sets are identical, only the merge
    order of exact distance ties can differ.

    On a single-device host ``"auto"`` degrades to ``"queries"`` and the
    call is a drop-in for :meth:`StreamingIndex.search`:

    >>> import jax, numpy as np
    >>> from repro.core.pq import PQConfig
    >>> from repro.index.streaming import IndexConfig, StreamingIndex
    >>> cfg = IndexConfig(
    ...     PQConfig(n_sub=2, codebook_size=4, use_prealign=False,
    ...              kmeans_iters=1, dba_iters=1),
    ...     n_lists=2, hot_capacity=4, coarse_iters=2)
    >>> X = np.sin(np.arange(8 * 16, dtype=np.float32)).reshape(8, 16)
    >>> idx = StreamingIndex.bootstrap(jax.random.PRNGKey(0), X, cfg)
    >>> _ = idx.insert(X)
    >>> dist, ids = search_sharded(idx, X[:2], n_probe=2, topk=1)
    >>> ids.shape, int(ids[0, 0])
    ((2, 1), 0)
    """
    if partition not in _PARTITIONS:
        raise ValueError(
            f"partition={partition!r} must be one of {_PARTITIONS}")
    Q = index._validate(Q, n_probe, topk)
    mesh = mesh if mesh is not None else make_search_mesh()
    n_dev = mesh.shape["search"]
    if partition == "auto":
        partition = ("lists" if n_dev > 1 and index.cfg.n_shards == n_dev
                     else "queries")
    with obs.span("sharded.search"):
        if obs.enabled():
            obs.counter("sharded_searches_total", persistent=True,
                        partition=partition).inc()
        if partition == "lists":
            return _search_list_sharded(index, Q, mesh, n_probe, topk)
        return _search_query_sharded(index, Q, mesh, n_probe, topk)
