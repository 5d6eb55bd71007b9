"""Serving core: coalesced microbatching, concurrent ingest, admission
control.  The acceptance test here is the headline guarantee of
``docs/serving.md``: searches issued during a background
insert/seal/compact storm are *bit-identical* to searching the quiesced
snapshot they ran against, on both the jax and Pallas-interpret backends.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import dispatch
from repro.core.dispatch import use_backend
from repro.core.pq import PQConfig
from repro.data.timeseries import cbf
from repro.index import IndexConfig, StreamingIndex
from repro.serve_index import (SHED_POLICIES, Backpressure, IndexServer,
                               ServeConfig)
from repro.serve_index.coalescer import _DEPTH_BUCKETS, QueryCoalescer
from repro.serve_index.server import SearchResult


def _config(n_lists=4, hot_capacity=12):
    pq = PQConfig(n_sub=4, codebook_size=8, use_prealign=False,
                  kmeans_iters=2, dba_iters=1)
    return IndexConfig(pq=pq, n_lists=n_lists, hot_capacity=hot_capacity,
                       coarse_iters=3)


@pytest.fixture(scope="module")
def data():
    X, _ = cbf(n_per_class=12, length=48, seed=0)    # 36 series
    Q, _ = cbf(n_per_class=2, length=48, seed=7)     # 6 queries
    return X.astype(np.float32), Q.astype(np.float32)


@pytest.fixture(scope="module")
def booted(data):
    X, _ = data
    return StreamingIndex.bootstrap(jax.random.PRNGKey(0), X, _config())


def _fresh(booted):
    return StreamingIndex.from_parts(booted.cfg, booted.coarse, booted.cb,
                                     booted.dim)


@pytest.fixture
def obs_on():
    prev = obs.enabled()
    obs.enable()
    yield
    if not prev:
        obs.disable()


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

class TestServeConfig:
    def test_bucket_for(self):
        cfg = ServeConfig()
        assert [cfg.bucket_for(n) for n in (1, 2, 3, 5, 64)] == \
            [1, 2, 4, 8, 64]
        with pytest.raises(ValueError):
            cfg.bucket_for(65)
        with pytest.raises(ValueError):
            cfg.bucket_for(0)

    def test_validation(self):
        with pytest.raises(ValueError):
            ServeConfig(q_buckets=(4, 2))          # not increasing
        with pytest.raises(ValueError):
            ServeConfig(shed_policy="drop_tables")
        with pytest.raises(ValueError):
            ServeConfig(queue_bound=0)
        with pytest.raises(ValueError):
            ServeConfig(coalesce_window_s=-1.0)
        assert set(SHED_POLICIES) == {"shed_inserts", "shed_all", "block"}


# ---------------------------------------------------------------------------
# acceptance: bit-identical searches under a concurrent write storm
# ---------------------------------------------------------------------------

class TestConcurrentBitIdentity:
    @pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
    def test_search_during_storm_bit_identical(self, data, booted, backend):
        """Client threads search while the writer seals/compacts/deletes.
        Every result is re-derived afterwards by searching the retained
        (now quiesced) snapshot it reported running against — distances
        and ids must match bit-for-bit."""
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:24])
        views = {}
        results = []
        res_lock = threading.Lock()
        cfg = ServeConfig(n_probe=4, topk=3, coalesce_window_s=0.001)

        def searcher(seed):
            rng = np.random.default_rng(seed)
            for _ in range(5):
                rows = rng.integers(0, len(Q), size=int(rng.integers(1, 4)))
                q = Q[rows]
                r = srv.submit_search(q).result(timeout=120)
                with res_lock:
                    results.append((q, r))

        with use_backend(backend):
            with IndexServer(idx, cfg, on_publish=lambda v:
                             views.setdefault(v.version, v)) as srv:
                views[srv.view.version] = srv.view
                threads = [threading.Thread(target=searcher, args=(s,))
                           for s in range(3)]
                for t in threads:
                    t.start()
                # the storm: grow, tombstone, seal, grow, merge, tombstone
                storm = [srv.insert(X[24:]), srv.delete([1, 5, 17]),
                         srv.flush(), srv.insert(X[:6] + 0.25),
                         srv.compact(), srv.delete([2])]
                for f in storm:
                    f.result(timeout=120)
                for t in threads:
                    t.join()
                srv.quiesce(timeout=120)

            assert len(results) == 15
            assert len(views) >= 2                # storm really swapped views
            for q, r in results:
                view = views[r.version]
                d_ref, i_ref = view.search(jnp.asarray(q), n_probe=4, topk=3)
                np.testing.assert_array_equal(np.asarray(r.ids),
                                              np.asarray(i_ref))
                np.testing.assert_array_equal(np.asarray(r.dist),
                                              np.asarray(d_ref))

    def test_completed_write_is_visible(self, data, booted):
        """insert(...).result() resolving implies the rows are searchable:
        futures resolve only after the snapshot swap."""
        X, _ = data
        idx = _fresh(booted)
        with IndexServer(idx, ServeConfig(n_probe=4, topk=1,
                                          coalesce_window_s=0.0)) as srv:
            ids = srv.insert(X[:10]).result(timeout=120)
            d, nn = srv.search(X[:3], timeout=120)
            assert set(np.asarray(nn)[:, 0].tolist()) <= set(ids.tolist())
            hits = srv.delete(ids[:2]).result(timeout=120)
            assert hits == 2
            _, nn2 = srv.search(X[:3], timeout=120)
            assert not set(np.asarray(nn2)[:, 0]) & set(ids[:2].tolist())

    def test_view_is_immune_to_later_writes(self, data, booted):
        """A captured view keeps answering identically after the hot
        buffer it copied has been mutated and sealed (the double-buffer
        property)."""
        X, Q = data
        idx = _fresh(booted)
        with IndexServer(idx, ServeConfig(n_probe=4, topk=2,
                                          coalesce_window_s=0.0)) as srv:
            srv.insert(X[:8]).result(timeout=120)     # hot-only state
            view = srv.view
            d0, i0 = view.search(jnp.asarray(Q), n_probe=4, topk=2)
            srv.insert(X[8:30]).result(timeout=120)   # mutates + seals hot
            srv.compact().result(timeout=120)
            d1, i1 = view.search(jnp.asarray(Q), n_probe=4, topk=2)
            np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
            np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


# ---------------------------------------------------------------------------
# admission control / backpressure
# ---------------------------------------------------------------------------

class TestBackpressure:
    def _wedged(self, booted, **kw):
        """Server whose writer never drains (not started): the bounded
        queue fills deterministically."""
        srv = IndexServer(_fresh(booted), ServeConfig(**kw))
        srv._started = True
        return srv

    def test_shed_inserts_full_queue(self, booted, obs_on):
        srv = self._wedged(booted, queue_bound=2, shed_policy="shed_inserts")
        X = np.zeros((1, booted.dim), np.float32)
        srv.flush(), srv.flush()                  # maintenance fills queue
        assert srv.pressure() == 1.0
        before = obs.counter("serving_shed_total", persistent=True,
                             op="insert").value
        with pytest.raises(Backpressure):
            srv.insert(X)
        assert obs.counter("serving_shed_total", persistent=True,
                           op="insert").value == before + 1

    def test_shed_inserts_admits_deletes(self, booted):
        srv = self._wedged(booted, queue_bound=2, shed_policy="shed_inserts")
        srv.flush()                               # 1 of 2 slots used
        fut = srv.delete([0])                     # admitted, no shed
        assert not fut.done()
        assert srv._wq.qsize() == 2

    def test_shed_all_sheds_deletes_too(self, booted, obs_on):
        srv = self._wedged(booted, queue_bound=1, shed_policy="shed_all")
        srv.flush()
        before = obs.counter("serving_shed_total", persistent=True,
                             op="delete").value
        with pytest.raises(Backpressure):
            srv.delete([0])
        assert obs.counter("serving_shed_total", persistent=True,
                           op="delete").value == before + 1

    def test_block_policy_blocks_until_drained(self, booted):
        srv = self._wedged(booted, queue_bound=1, shed_policy="block")
        srv.flush()                               # queue full
        X = np.zeros((1, booted.dim), np.float32)
        t = threading.Thread(target=lambda: srv.insert(X), daemon=True)
        t.start()
        t.join(timeout=0.2)
        assert t.is_alive()                       # blocked, not shed
        srv._wq.get()                             # writer-side drain
        t.join(timeout=10)
        assert not t.is_alive()

    def test_rejects_writes_when_not_running(self, booted):
        srv = IndexServer(_fresh(booted), ServeConfig())
        with pytest.raises(RuntimeError):
            srv.insert(np.zeros((1, booted.dim), np.float32))

    def test_search_validates_shape(self, booted):
        srv = IndexServer(_fresh(booted), ServeConfig())
        srv._started = True
        with pytest.raises(ValueError):
            srv.submit_search(np.zeros((2, booted.dim + 1), np.float32))
        with pytest.raises(ValueError):
            srv.submit_search(np.zeros((0, booted.dim), np.float32))


# ---------------------------------------------------------------------------
# coalescer: bucketing, windowing, compiled-shape reuse
# ---------------------------------------------------------------------------

class TestCoalescer:
    def test_concurrent_requests_coalesce_into_one_bucket(self, data,
                                                          booted, obs_on):
        """Three 1-query requests inside one window launch as a single
        padded bucket-4 batch against one snapshot."""
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:16])
        cfg = ServeConfig(n_probe=2, topk=1, coalesce_window_s=0.25)
        with IndexServer(idx, cfg) as srv:
            before = obs.counter("serving_batches_total", persistent=True,
                                 bucket="4").value
            futs = [srv.submit_search(Q[i:i + 1]) for i in range(3)]
            rs = [f.result(timeout=120) for f in futs]
            after = obs.counter("serving_batches_total", persistent=True,
                                bucket="4").value
        assert after == before + 1
        assert len({r.version for r in rs}) == 1  # one snapshot, one launch
        for i, r in enumerate(rs):
            assert r.dist.shape == (1, 1) and r.ids.shape == (1, 1)

    def test_oversized_request_is_chunked(self, data, booted):
        """Requests wider than the largest bucket split into chunks whose
        re-concatenated rows match the direct index search bit-for-bit."""
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:20])
        idx.flush()
        d_direct, i_direct = idx.search(Q, n_probe=2, topk=2)
        cfg = ServeConfig(n_probe=2, topk=2, coalesce_window_s=0.0,
                          q_buckets=(1, 2, 4))
        with IndexServer(idx, cfg) as srv:
            r = srv.submit_search(Q).result(timeout=120)   # 6 > max bucket 4
        assert r.dist.shape == (6, 2)
        np.testing.assert_array_equal(np.asarray(r.ids), np.asarray(i_direct))
        np.testing.assert_array_equal(np.asarray(r.dist),
                                      np.asarray(d_direct))

    @staticmethod
    def _assert_warm_buckets_pin_compiles(data, booted):
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:20])
        idx.flush()                               # freeze the segment set
        cfg = ServeConfig(n_probe=2, topk=1, coalesce_window_s=0.0)
        with IndexServer(idx, cfg) as srv:
            for n in (1, 2, 4):                   # warm each bucket
                srv.submit_search(Q[:n]).result(timeout=120)
            # the steady-state per-call signature: eager dispatch wrappers
            # (the coarse cdist) count once per *call*, jitted stages only
            # at *trace* time — so one more warm search isolates the
            # eager-only delta
            base = dict(dispatch.totals)
            srv.submit_search(Q[:2]).result(timeout=120)
            per_call = {k: v - base.get(k, 0)
                        for k, v in dispatch.totals.items()
                        if v != base.get(k, 0)}
            before = dict(dispatch.totals)
            rng = np.random.default_rng(0)
            rounds = 6
            for _ in range(rounds):
                n = int(rng.choice([1, 2, 3, 4]))   # 3 pads into bucket 4
                srv.submit_search(Q[:n]).result(timeout=120)
            want = dict(before)
            for key, v in per_call.items():
                want[key] = want.get(key, 0) + rounds * v
            # any re-trace of a jitted stage would bump its counter past
            # the eager-only expectation
            assert dict(dispatch.totals) == want

    def test_warm_buckets_trigger_no_new_compilations(self, data, booted):
        """After one warmup pass over the traffic's buckets, steady-state
        mixed-size traffic adds zero trace-time dispatch counts: the
        finite bucket family really does pin the compiled executables."""
        self._assert_warm_buckets_pin_compiles(data, booted)

    def test_warm_buckets_pin_compiles_with_adc_kernel(self, data, booted):
        """The same steady state with every dispatched op, the fine stage's
        one-hot ADC kernel among them, on the Pallas kernel bodies."""
        route = ("adc_lookup", "pallas_interpret")
        jax.clear_caches()   # routes are chosen when a stage is traced
        try:
            before = dispatch.totals.get(route, 0)
            with dispatch.use_backend("pallas_interpret"):
                self._assert_warm_buckets_pin_compiles(data, booted)
            # one trace of the fine stage per warmed bucket, none after
            assert dispatch.totals.get(route, 0) == before + 3
        finally:
            jax.clear_caches()

    def test_graceful_stop_answers_queued_requests(self, data, booted):
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:12])
        cfg = ServeConfig(n_probe=2, topk=1, coalesce_window_s=0.2)
        srv = IndexServer(idx, cfg).start()
        futs = [srv.submit_search(Q[:2]) for _ in range(3)]
        srv.stop()                                # drains before exiting
        for f in futs:
            r = f.result(timeout=5)
            assert r.ids.shape == (2, 1)


# ---------------------------------------------------------------------------
# serving telemetry
# ---------------------------------------------------------------------------

class TestServingObs:
    def test_serving_metrics_populate(self, data, booted, obs_on):
        X, Q = data
        idx = _fresh(booted)
        with IndexServer(idx, ServeConfig(n_probe=2, topk=1,
                                          coalesce_window_s=0.0)) as srv:
            srv.insert(X[:16]).result(timeout=120)
            srv.search(Q[:2], timeout=120)
        assert obs.counter("serving_queries_total",
                           persistent=True).value >= 2
        assert obs.counter("serving_view_swaps_total",
                           persistent=True).value >= 1
        assert obs.gauge("serving_view_version",
                         persistent=True).value >= 1
        assert obs.histogram("serving_snapshot_swap_seconds",
                             persistent=True).count >= 1

    def test_serving_spans_recorded(self, data, booted, obs_on):
        from repro.obs import export
        X, Q = data
        idx = _fresh(booted)
        with IndexServer(idx, ServeConfig(n_probe=2, topk=1,
                                          coalesce_window_s=0.0)) as srv:
            srv.insert(X[:16]).result(timeout=120)
            srv.search(Q[:2], timeout=120)
        snap = export.snapshot()
        stages = {h["labels"].get("stage") for h in snap["histograms"]
                  if h["name"] == "stage_seconds"}
        assert {"serving.apply", "serving.snapshot_swap",
                "serving.batch_search"} <= stages


# ---------------------------------------------------------------------------
# request-scoped tracing: ids, phase times, the completion watcher and the
# fence switch
# ---------------------------------------------------------------------------

def _samples(name, **labels):
    return list(obs.histogram(name, persistent=True, **labels).samples)


def _new(name, before):
    return _samples(name)[len(before):]


def _obs_free_snapshot():
    """Every metric except the dispatch routing mirror, which counts eager
    dispatches whether obs is on or off."""
    from repro.obs import export
    snap = export.snapshot()
    return {k: [m for m in snap[k] if m["name"] != "dispatch_total"]
            for k in ("counters", "gauges", "histograms")}


def _thread_names():
    return {t.name for t in threading.enumerate()}


def _serve(idx, queries, cfg):
    with IndexServer(idx, cfg) as srv:
        return [srv.submit_search(q).result(timeout=120) for q in queries]


class _SlowBatches:
    """A ``run_batch`` stand-in that spends ``seconds`` in the call."""

    def __init__(self, seconds):
        self.seconds = seconds

    def __call__(self, Qp, q_valid, n_real):
        time.sleep(self.seconds)
        return SearchResult(jnp.zeros((Qp.shape[0], 1)),
                            jnp.zeros((Qp.shape[0], 1), jnp.int32), 0)


def _coalesced(run_batch, n, window=0.0):
    """Submit ``n`` one-row requests one after the other through a bare
    coalescer; returns the results."""
    co = QueryCoalescer(run_batch, ServeConfig(coalesce_window_s=window))
    co.start()
    try:
        return [co.submit(np.zeros((1, 4), np.float32)).result(timeout=60)
                for _ in range(n)]
    finally:
        co.stop()


class TestRequestTracing:
    def test_obs_off_serving_starts_no_watcher_and_records_nothing(
            self, data, booted, monkeypatch):
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:16])
        cfg = ServeConfig(n_probe=2, topk=1, coalesce_window_s=0.0)
        _serve(idx, [Q[:2]], cfg)                 # compile outside the check

        def forbid(x):
            raise AssertionError("obs-off serving must not sync through obs")
        monkeypatch.setattr("repro.obs.spans._block", forbid)
        assert not obs.enabled()
        before = _obs_free_snapshot()
        seen = set()
        with IndexServer(idx, cfg) as srv:
            for i in range(3):
                srv.submit_search(Q[i:i + 2]).result(timeout=120)
                seen |= _thread_names()
        assert "repro-serve-watcher" not in seen
        assert _obs_free_snapshot() == before

    @pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
    def test_unfenced_coalescer_never_blocks_and_matches_obs_off(
            self, data, booted, backend, monkeypatch):
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:20])
        idx.flush()                               # sealed rows + hot rows
        cfg = ServeConfig(n_probe=2, topk=2, coalesce_window_s=0.0)
        queries = [Q[:1], Q[1:4], Q[2:6]]
        callers = []

        def record(x):
            callers.append(threading.current_thread().name)
            return jax.block_until_ready(x)
        with use_backend(backend):
            off = _serve(idx, queries, cfg)
            monkeypatch.setattr("repro.obs.spans._block", record)
            with obs.override(True, fence=False):
                on = _serve(idx, queries, cfg)
        assert "repro-serve-coalescer" not in callers
        assert "repro-serve-watcher" in callers   # the watcher did wait
        for a, b in zip(off, on):
            assert np.asarray(a.dist).tobytes() == np.asarray(b.dist).tobytes()
            assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))

    @pytest.mark.parametrize("fence", [True, False])
    def test_wait_excludes_and_request_includes_the_search(self, fence):
        """The coalesce wait ends when the batch is handed to ``run_batch``;
        the request time runs until the answer is ready, so a 50 ms search
        lies inside every request time and outside every wait."""
        waits = _samples("serving_coalesce_wait_seconds")
        reqs = _samples("serving_request_seconds")
        with obs.override(True, fence=fence):
            _coalesced(_SlowBatches(0.05), 4)
        new_w = _new("serving_coalesce_wait_seconds", waits)
        new_r = _new("serving_request_seconds", reqs)
        assert len(new_w) == len(new_r) == 4
        assert all(w < 0.05 for w in new_w)
        assert all(r >= 0.05 for r in new_r)
        assert all(r >= w for w, r in zip(new_w, new_r))

    def test_dispatch_and_inflight_split_the_batch(self):
        """Unfenced, the host time inside ``run_batch`` is the dispatch and
        the rest until ready is in flight; each batch gives one sample of
        each, and the in-flight depth is sampled at each dispatch."""
        names = ("serving_batch_dispatch_seconds",
                 "serving_batch_inflight_seconds")
        before = {n: _samples(n) for n in names}
        h = obs.histogram("serving_inflight_depth", persistent=True,
                          buckets=_DEPTH_BUCKETS)
        depth = len(h.samples)
        with obs.override(True, fence=False):
            _coalesced(_SlowBatches(0.02), 3)
        dispatch = _new(names[0], before[names[0]])
        inflight = _new(names[1], before[names[1]])
        assert len(dispatch) == len(inflight) == 3
        assert all(d >= 0.02 for d in dispatch)
        assert all(t >= 0.0 for t in inflight)
        assert len(h.samples) == depth + 3
        assert all(s >= 1 for s in h.samples[depth:])
        assert obs.gauge("serving_batches_in_flight",
                         persistent=True).value == 0

    def test_n_requests_n_samples_and_busy_idle_grow(self, data, booted):
        X, Q = data
        idx = _fresh(booted)
        idx.insert(X[:16])
        names = ("serving_coalesce_wait_seconds", "serving_request_seconds")
        before = {n: _samples(n) for n in names}
        busy = obs.counter("serving_coalescer_busy_seconds",
                           persistent=True).value
        idle = obs.counter("serving_coalescer_idle_seconds",
                           persistent=True).value
        with obs.override(True, fence=False):
            _serve(idx, [Q[i:i + 1] for i in range(5)],
                   ServeConfig(n_probe=2, topk=1, coalesce_window_s=0.0))
        for n in names:
            assert len(_new(n, before[n])) == 5
        assert obs.counter("serving_coalescer_busy_seconds",
                           persistent=True).value > busy
        assert obs.counter("serving_coalescer_idle_seconds",
                           persistent=True).value > idle

    def test_concurrent_clients_lose_no_sample(self):
        """More client threads than cores and a short switch interval: every
        request still gives one wait and one request sample, the batches
        account for every row, and the watcher drains to 0 in flight."""
        import os
        import sys
        n_threads = (os.cpu_count() or 2) + 2
        per_thread = max(2, 128 // n_threads)
        names = ("serving_coalesce_wait_seconds", "serving_request_seconds")
        before = {n: _samples(n) for n in names}
        rows = obs.histogram("serving_batch_queries", persistent=True,
                             q_buckets="1,2,4,8,16,32,64",
                             buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0))
        rows_before = rows.sum
        co = QueryCoalescer(_SlowBatches(0.001),
                            ServeConfig(coalesce_window_s=0.0005))
        errors = []

        def client():
            try:
                for _ in range(per_thread):
                    co.submit(np.zeros((1, 4), np.float32)).result(
                        timeout=60)
            except Exception as e:            # noqa: BLE001 - reported
                errors.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            with obs.override(True, fence=False):
                co.start()
                threads = [threading.Thread(target=client)
                           for _ in range(n_threads)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
                co.stop()
        finally:
            sys.setswitchinterval(old)
        assert not errors and not any(t.is_alive() for t in threads)
        n = n_threads * per_thread
        for name in names:
            assert len(_new(name, before[name])) == n, name
        assert rows.sum - rows_before == n
        assert obs.gauge("serving_batches_in_flight",
                         persistent=True).value == 0

    def test_coalescer_phases_are_spans(self, obs_on):
        counts = {s: len(_samples("stage_seconds", stage=s))
                  for s in ("serving.coalesce", "serving.batch_search",
                            "serving.deliver")}
        _coalesced(_SlowBatches(0.0), 2)
        for s, n in counts.items():
            assert len(_samples("stage_seconds", stage=s)) >= n + 2, s

    def test_batch_annotation_carries_ids(self, tmp_path):
        """The profiler keeps the launch annotation's name and its keyword
        arguments apart, so trace stages still match by name."""
        import glob
        from jax.profiler import ProfileData
        with obs.override(True, fence=False):
            jax.profiler.start_trace(str(tmp_path))
            try:
                _coalesced(_SlowBatches(0.0), 2)
            finally:
                jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        events = [e for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for e in line.events
                  if e.name == "serving.batch_search"]
        assert len(events) == 2
        args = [{k: v for k, v in e.stats} for e in events]
        assert {"batch_id", "n_real", "bucket", "version", "first_request",
                "oldest_wait_ms"} <= set(args[0])
        assert args[1]["batch_id"] == args[0]["batch_id"] + 1
        assert args[1]["first_request"] == args[0]["first_request"] + 1
        assert [a["n_real"] for a in args] == [1, 1]

    def test_write_wait_visible_and_flush_subspans(self, data, booted,
                                                   obs_on):
        X, _ = data
        idx = _fresh(booted)
        names = ("serving_write_wait_seconds", "serving_write_visible_seconds")
        before = {n: _samples(n) for n in names}
        subs = {s: len(_samples("stage_seconds", stage=s))
                for s in ("index.flush.encode", "index.flush.assign",
                          "index.flush.seal")}
        with IndexServer(idx, ServeConfig(n_probe=2, topk=1)) as srv:
            futs = [srv.insert(X[:8]), srv.insert(X[8:16]), srv.flush()]
            for f in futs:
                f.result(timeout=120)
            srv.quiesce(timeout=120)              # a barrier is not a write
        wait = _new(names[0], before[names[0]])
        visible = _new(names[1], before[names[1]])
        assert len(wait) == len(visible) == 3
        assert all(v >= w >= 0 for w, v in zip(wait, visible))
        for s, n in subs.items():
            assert len(_samples("stage_seconds", stage=s)) > n, s
