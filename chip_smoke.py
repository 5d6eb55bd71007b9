#!/usr/bin/env python3
"""Smoke run of the streaming IVF-PQDTW index on a TPU.

Drives the served path once through the entry points a user calls --
``StreamingIndex.bootstrap``, then ``IndexServer`` insert / compact /
search -- at archive scale: z-normalised random walks of length 256 (the
Hydra whole-matching setting), with every elastic op on the compiled
Pallas route.  It then checks what came out against the plain references:
the same published view searched on the ``jax`` route, codes encoded on
both routes, and raw DTW pairs against a numpy DP written here.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # list-sharded search on four chips only

Exits non-zero, printing no result line, when JAX finds no TPU or any
phase fails.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

LENGTH = 256  # series length
N_SERIES = 131072  # 2**17 random walks
N_TRAIN = 8192  # bootstrap sample
N_HOT = 1000  # rows left in the hot buffer after compaction
N_QUERIES = 64
NOISE = 0.1  # query = dataset series + N(0, NOISE^2)
N_PROBE, TOPK = 8, 10
N_ENCODE = 1024  # series encoded on both routes
N_PAIRS, PAIR_WINDOW = 16, 25  # raw pairs checked against the numpy DP
RTOL = 1e-5
INSERT_BATCH = 4096

# elastic ops of the dispatch ledger (repro.core.dispatch.totals)
ELASTIC_OPS = (
    "elastic_pairwise",
    "elastic_pairwise_adaptive",
    "elastic_cdist",
    "lb_refine",
    "lb_refine_adaptive",
    "prealign_encode",
    "two_level_coarse",
)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


class Phases:
    """Wall time of each phase, printed as it ends."""

    def __init__(self):
        self.seconds = {}

    def run(self, name, fn, *args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.seconds[name] = time.perf_counter() - t0
        log(f"[phase] {name}: {self.seconds[name]:.3f} s")
        return out


def require_tpu(count):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found -- JAX's first device is "
            f"{d0.platform!r} ({d0.device_kind}); this script runs only on "
            f"a TPU and has no CPU fallback"
        )
    if len(devices) < count:
        raise SystemExit(
            f"chip_smoke: needs {count} TPU chips, JAX sees {len(devices)}"
        )
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}


def numpy_dtw(a, b, window):
    """Squared DTW with a Sakoe-Chiba band, O(L * window) numpy DP."""
    n = len(a)
    prev = np.full(n + 1, np.inf)
    prev[0] = 0.0
    for i in range(1, n + 1):
        cur = np.full(n + 1, np.inf)
        for j in range(max(1, i - window), min(n, i + window) + 1):
            cost = (float(a[i - 1]) - float(b[j - 1])) ** 2
            cur[j] = cost + min(prev[j - 1], prev[j], cur[j - 1])
        prev = cur
    return prev[n]


def make_data(seed):
    from repro.data.timeseries import random_walks

    X = random_walks(N_SERIES, LENGTH, seed=seed)
    hot = random_walks(N_HOT, LENGTH, seed=seed + 1)
    rng = np.random.default_rng(seed + 2)
    src = rng.choice(N_SERIES, N_QUERIES, replace=False)
    Q = (X[src] + NOISE * rng.standard_normal((N_QUERIES, LENGTH))).astype(
        np.float32
    )
    return X, hot, Q, src


def index_config(n_shards):
    from repro.core.pq import PQConfig
    from repro.index import IndexConfig

    return IndexConfig(
        PQConfig(n_sub=16, codebook_size=256),
        n_lists=256,
        hot_capacity=4096,
        n_shards=n_shards,
    )


def bootstrap(X, cfg, seed):
    import jax

    from repro.index import StreamingIndex

    index = StreamingIndex.bootstrap(jax.random.PRNGKey(seed), X[:N_TRAIN], cfg)
    jax.block_until_ready((index.coarse, index.cb))
    return index


def ingest(srv, X):
    for i in range(0, len(X), INSERT_BATCH):
        srv.insert(X[i : i + INSERT_BATCH]).result()


def search(srv, Q):
    import jax

    r = srv.submit_search(Q).result()
    jax.block_until_ready((r.dist, r.ids))
    return r


def log_sizes(cfg):
    log(
        f"sizes: N={N_SERIES} series x L={LENGTH} float32 "
        f"({N_SERIES * LENGTH * 4 / 1e6:.1f} MB raw), bootstrap sample "
        f"{N_TRAIN}, {N_HOT} more rows left in the hot buffer after "
        f"compaction, {N_QUERIES} queries (dataset series + N(0, {NOISE}^2)), "
        f"n_probe={N_PROBE}, topk={TOPK}"
    )
    log(
        f"config: n_sub={cfg.pq.n_sub}, codebook_size={cfg.pq.codebook_size}, "
        f"window_frac={cfg.pq.window_frac}, use_prealign={cfg.pq.use_prealign}, "
        f"n_lists={cfg.n_lists}, hot_capacity={cfg.hot_capacity}, "
        f"n_shards={cfg.n_shards}; cut to fit the time limit: nothing"
    )


def build_served(phases, X, hot, Q, cfg, seed):
    """Bootstrap, serve N inserts, compact, fill the hot buffer, search."""
    from repro.serve_index import IndexServer, ServeConfig

    index = phases.run("bootstrap", bootstrap, X, cfg, seed)
    srv = IndexServer(index, ServeConfig(n_probe=N_PROBE, topk=TOPK)).start()
    try:
        phases.run("ingest", ingest, srv, X)
        phases.run("compact", lambda: srv.compact().result())
        phases.run("hot_insert", lambda: srv.insert(hot).result())
        r = phases.run("search_cold", search, srv, Q)
        r = phases.run("search_warm", search, srv, Q)
        view = srv.view
    finally:
        srv.stop()
    check(r.version == view.version, "search answered from the published view")
    return index, view, np.asarray(r.dist), np.asarray(r.ids)


def run_one_chip(args):
    import jax
    import jax.numpy as jnp

    from repro.core import dispatch
    from repro.core.pq import encode

    phases = Phases()
    cfg = index_config(1)
    log_sizes(cfg)
    X, hot, Q, src = phases.run("data", make_data, args.seed)
    index, view, dist, ids = build_served(phases, X, hot, Q, cfg, args.seed)
    check(
        dist.shape == (N_QUERIES, TOPK) and np.isfinite(dist).all(),
        f"search returned finite ({N_QUERIES}, {TOPK}) distances",
    )
    check((ids >= 0).all(), "every query has topk live neighbours")
    recall = float(np.mean([s in row for s, row in zip(src, ids)]))
    log(f"  source series in the top {TOPK}: {recall:.3f} of queries")

    pq = cfg.pq
    D = LENGTH
    Xe = jnp.asarray(X[:N_ENCODE])
    geometry = dict(level=pq.wavelet_level, tail=pq.tail(D), window=pq.window(D))
    rng = np.random.default_rng(args.seed + 3)
    pa = X[rng.choice(N_SERIES, N_PAIRS, replace=False)]
    pb = X[rng.choice(N_SERIES, N_PAIRS, replace=False)]

    def chip_route():
        codes = np.asarray(encode(Xe, index.cb, pq))
        fused = np.asarray(dispatch.prealign_encode(Xe, index.cb.centroids, **geometry))
        pairs = np.asarray(dispatch.elastic_pairwise(pa, pb, PAIR_WINDOW))
        return codes, fused, pairs

    codes, fused, pairs = phases.run("chip_route_checks", chip_route)

    ledger = {f"{op}/{route}": n for (op, route), n in sorted(dispatch.totals.items())}
    log(f"dispatch.totals: {json.dumps(ledger)}")
    elastic = {
        (op, route)
        for (op, route) in dispatch.totals
        if op.split("[")[0] in ELASTIC_OPS
    }
    off_route = sorted(f"{op}/{route}" for op, route in elastic if route != "pallas")
    check(not off_route, f"every elastic op on 'pallas' (off route: {off_route})")
    seen = {op for op, _ in elastic}
    for op in ("elastic_cdist", "elastic_pairwise", "lb_refine", "prealign_encode"):
        check(op in seen, f"{op} ran on the chip")

    def reference_route():
        jax.clear_caches()
        with dispatch.use_backend("jax"):
            d, i = view.search(jnp.asarray(Q), n_probe=N_PROBE, topk=TOPK)
            codes = np.asarray(encode(Xe, index.cb, pq))
            fused = np.asarray(
                dispatch.prealign_encode(Xe, index.cb.centroids, **geometry)
            )
        return np.asarray(d), np.asarray(i), codes, fused

    d_ref, ids_ref, codes_ref, fused_ref = phases.run("jax_reference", reference_route)
    check(np.array_equal(ids, ids_ref), "search ids identical to the jax route")
    rel = float(np.max(np.abs(dist - d_ref) / np.maximum(np.abs(d_ref), 1e-30)))
    log(f"  search distances: max relative difference {rel:.3e}")
    check(
        np.allclose(dist, d_ref, rtol=RTOL, atol=0.0),
        f"search distances within rtol {RTOL} of the jax route",
    )
    check(
        np.array_equal(codes, codes_ref),
        f"codes of {N_ENCODE} series identical on both routes",
    )
    check(
        np.array_equal(fused, fused_ref),
        f"fused prealign_encode codes of {N_ENCODE} series identical on both routes",
    )

    want = phases.run(
        "numpy_dp",
        lambda: np.array([numpy_dtw(a, b, PAIR_WINDOW) for a, b in zip(pa, pb)]),
    )
    rel = float(np.max(np.abs(pairs - want) / want))
    log(
        f"  {N_PAIRS} raw pairs (L={LENGTH}, w={PAIR_WINDOW}) vs numpy DP: "
        f"max relative difference {rel:.3e}"
    )
    check(
        np.allclose(pairs, want, rtol=RTOL, atol=0.0),
        f"DTW pairs within rtol {RTOL}",
    )

    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"phase seconds: {json.dumps(phases.seconds)}")


def run_four_chips(args):
    import jax

    from repro.index import search_sharded
    from repro.launch.mesh import make_search_mesh

    phases = Phases()
    cfg = index_config(4)
    log_sizes(cfg)
    X, hot, Q, _ = phases.run("data", make_data, args.seed)
    index = phases.run("bootstrap", bootstrap, X, cfg, args.seed)
    phases.run("ingest", index.insert, X)
    phases.run("compact", index.compact)
    phases.run("hot_insert", index.insert, hot)

    mesh = make_search_mesh(4)
    (seg,) = index.segments
    live = np.asarray(seg.live).reshape(seg.n_shards, seg.shard_cap).sum(axis=1)
    for dev, rows in zip(mesh.devices.flat, live):
        log(f"  {dev}: {int(rows)} sealed rows ({rows / live.sum():.3f} of the index)")
    check(
        live.min() > 0 and live.max() <= 0.5 * live.sum(),
        "sealed rows spread over all four chips",
    )

    def sharded():
        d, i = search_sharded(
            index, Q, n_probe=N_PROBE, topk=TOPK, mesh=mesh, partition="lists"
        )
        return np.asarray(d), np.asarray(i)

    phases.run("sharded_search_cold", sharded)
    d4, i4 = phases.run("sharded_search_warm", sharded)

    def direct():
        d, i = index.search(Q, n_probe=N_PROBE, topk=TOPK)
        return np.asarray(d), np.asarray(i)

    d1, i1 = phases.run("direct_search", direct)
    check(np.array_equal(i4, i1), "list-sharded ids identical to one-device search")
    check(
        np.allclose(d4, d1, rtol=RTOL, atol=0.0),
        f"list-sharded distances within rtol {RTOL} of one-device search",
    )
    stats = [d.memory_stats() or {} for d in jax.devices()[:4]]
    log(f"peak_bytes_in_use per chip: {[s.get('peak_bytes_in_use') for s in stats]}")
    log(f"phase seconds: {json.dumps(phases.seconds)}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: list-sharded search on a four-chip mesh against one-device "
        "search, and nothing else",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # the smoke run measures the defaults: no pinned tuning table, no
    # forced elastic backend
    for var in ("REPRO_TUNE", "REPRO_ELASTIC_BACKEND"):
        if os.environ.pop(var, None) is not None:
            log(f"note: ignoring ${var} for this run")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: the repro package is not under {SRC}")
    sys.path.insert(0, SRC)

    device = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {json.dumps(device)}")
    if args.chips == 4:
        run_four_chips(args)
    else:
        run_one_chip(args)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
