"""Observability layer: registry math vs numpy oracles, span
nesting/re-entrancy, the zero-overhead disabled contract (bit-identical
search results + no device syncs), export round-trips, and the dispatch
routing mirror."""

import json
import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import dispatch
from repro.core.dispatch import use_backend
from repro.core.lb_search import filtered_topk
from repro.core.pq import PQConfig
from repro.data.timeseries import random_walks
from repro.index import IndexConfig, StreamingIndex, search_sharded
from repro.obs.registry import Registry


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test here starts with obs disabled (the contract under test),
    then the session's state is restored — a CI run with REPRO_OBS=1 must
    keep recording spans in the test files that sort after this one."""
    prev = obs.enabled()
    obs.disable()
    yield
    if prev:
        obs.enable()
    else:
        obs.disable()


# ---------------------------------------------------------------------------
# registry: buckets + percentiles vs numpy
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_percentile_matches_numpy(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 10, 101):
            samples = rng.exponential(0.01, size=n).tolist()
            for p in (0.0, 25.0, 50.0, 90.0, 95.0, 99.0, 100.0):
                assert obs.percentile(samples, p) == pytest.approx(
                    float(np.percentile(samples, p)), rel=1e-12)

    def test_histogram_percentiles_match_numpy(self):
        reg = Registry()
        h = reg.histogram("t")
        samples = np.random.default_rng(1).exponential(0.01, 257)
        for v in samples:
            h.record(v)
        for p in (50.0, 95.0, 99.0):
            assert h.percentile(p) == pytest.approx(
                float(np.percentile(samples, p)), rel=1e-12)

    def test_bucket_boundaries_le_semantics(self):
        reg = Registry()
        h = reg.histogram("t", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 2.0, 4.0, 5.0):  # bounds land IN bucket
            h.record(v)
        assert h.bucket_counts == [2, 2, 1, 1]     # [-1] = +Inf overflow
        assert h.cumulative_counts() == [2, 4, 5, 6]
        assert h.cumulative_counts()[-1] == h.count

    def test_bucket_counts_match_numpy_histogram(self):
        bounds = obs.exp_buckets(1e-4, 2.0, 20)
        reg = Registry()
        h = reg.histogram("t", buckets=bounds)
        samples = np.random.default_rng(2).exponential(0.01, 500)
        for v in samples:
            h.record(v)
        # np.histogram uses right-open bins; with no sample exactly on a
        # bound (probability zero for continuous draws) both agree
        expect, _ = np.histogram(samples,
                                 bins=[0.0] + list(bounds) + [np.inf])
        assert h.bucket_counts == expect.tolist()

    def test_exp_buckets(self):
        assert obs.exp_buckets(1.0, 2.0, 4) == (1.0, 2.0, 4.0, 8.0)
        with pytest.raises(ValueError):
            obs.exp_buckets(0.0, 2.0, 4)
        with pytest.raises(ValueError):
            obs.exp_buckets(1.0, 1.0, 4)

    def test_sum_min_max(self):
        reg = Registry()
        h = reg.histogram("t", buckets=(1.0,))
        for v in (0.25, 0.5, 3.0):
            h.record(v)
        assert h.count == 3
        assert h.sum == pytest.approx(3.75)
        assert (h.min, h.max) == (0.25, 3.0)
        assert not h.samples_capped

    def test_conflicting_buckets_rejected(self):
        reg = Registry()
        reg.histogram("t", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="already exists"):
            reg.histogram("t", buckets=(1.0, 3.0))


class TestRegistry:
    def test_get_or_create_by_name_and_labels(self):
        reg = Registry()
        a = reg.counter("c", op="x")
        assert reg.counter("c", op="x") is a
        assert reg.counter("c", op="y") is not a

    def test_reset_keeps_persistent(self):
        reg = Registry()
        reg.counter("scratch").inc()
        keep = reg.counter("keep", persistent=True)
        keep.inc(5)
        reg.reset()
        assert reg.counter("keep", persistent=True) is keep
        assert keep.value == 5
        assert reg.counter("scratch").value == 0    # recreated fresh
        reg.reset(include_persistent=True)
        assert reg.counter("keep", persistent=True) is not keep


# ---------------------------------------------------------------------------
# spans: nesting, re-entrancy, exception safety, disabled no-ops
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_and_stack(self):
        with obs.override(True):
            assert obs.current_spans() == ()
            with obs.span("outer"):
                with obs.span("inner"):
                    assert obs.current_spans() == ("outer", "inner")
                assert obs.current_spans() == ("outer",)
            assert obs.current_spans() == ()
        h = obs.histogram("stage_seconds", persistent=True, stage="inner")
        assert h.count >= 1

    def test_reentrancy_same_name(self):
        with obs.override(True):
            before = obs.histogram("stage_seconds", persistent=True,
                                   stage="re").count
            with obs.span("re"):
                with obs.span("re"):
                    assert obs.current_spans() == ("re", "re")
            after = obs.histogram("stage_seconds", persistent=True,
                                  stage="re").count
        assert after == before + 2

    def test_exception_still_records_and_pops(self):
        with obs.override(True):
            before = obs.histogram("stage_seconds", persistent=True,
                                   stage="boom").count
            with pytest.raises(RuntimeError):
                with obs.span("boom"):
                    raise RuntimeError("x")
            assert obs.current_spans() == ()
            after = obs.histogram("stage_seconds", persistent=True,
                                  stage="boom").count
        assert after == before + 1

    def test_disabled_span_is_shared_noop(self):
        s1, s2 = obs.span("a"), obs.span("b")
        assert s1 is s2                       # one immutable null object
        with s1 as sp:
            assert obs.current_spans() == ()
            assert sp.fence(123) == 123

    def test_fence_blocks_only_when_enabled(self, monkeypatch):
        calls = []
        monkeypatch.setattr("repro.obs.spans._block",
                            lambda x: calls.append(1) or x)
        x = jax.numpy.ones(3)
        assert obs.fence(x) is x
        assert calls == []                    # disabled: never blocks
        with obs.override(True):
            obs.fence(x)
        assert calls == [1]

    def test_fence_skips_tracers(self, monkeypatch):
        calls = []
        monkeypatch.setattr("repro.obs.spans._block",
                            lambda x: calls.append(1) or x)
        with obs.override(True):
            @jax.jit
            def f(x):
                return obs.fence(x * 2)       # tracer: must not block
            f(jax.numpy.ones(3))
        assert calls == []

    def test_env_var_parsing(self):
        assert obs.ENV_VAR == "REPRO_OBS"
        assert not obs.enabled()              # suite runs with obs off

    def test_unfenced_keeps_spans_and_drops_fences(self, monkeypatch):
        calls = []
        monkeypatch.setattr("repro.obs.spans._block",
                            lambda x: calls.append(1) or x)
        x = jax.numpy.ones(3)
        before = obs.histogram("stage_seconds", persistent=True,
                               stage="unfenced").count
        with obs.override(True, fence=False):
            assert obs.enabled() and not obs.fencing()
            with obs.span("unfenced") as sp:
                assert sp.fence(x) is x
            assert obs.fence(x) is x
            obs.wait(x)                       # observers still may wait
        assert calls == [1]
        assert obs.histogram("stage_seconds", persistent=True,
                             stage="unfenced").count == before + 1

    @pytest.mark.parametrize("value,fenced", [("0", False), ("false", False),
                                              ("1", True), (None, True)])
    def test_fence_env_var(self, monkeypatch, value, fenced):
        assert obs.FENCE_ENV_VAR == "REPRO_OBS_FENCE"
        if value is None:
            monkeypatch.delenv(obs.FENCE_ENV_VAR, raising=False)
        else:
            monkeypatch.setenv(obs.FENCE_ENV_VAR, value)
        with obs.override(False):             # restores both switches
            obs.enable()
            assert obs.fencing() is fenced
            obs.enable(fence=not fenced)      # the argument wins
            assert obs.fencing() is (not fenced)
        assert not obs.fencing()              # disabled never fences

    def test_span_annotation_arguments(self):
        with obs.span("noop", a=1) as sp:     # disabled: shared no-op
            sp.annotate(b=2)
        with obs.override(True):
            with obs.span("annotated", batch_id=3) as sp:
                sp.annotate(version=7)
                assert obs.current_spans() == ("annotated",)


# ---------------------------------------------------------------------------
# zero-overhead contract: search results bit-identical with obs on/off
# ---------------------------------------------------------------------------

def _small_index():
    cfg = IndexConfig(
        pq=PQConfig(n_sub=4, codebook_size=8, kmeans_iters=2, dba_iters=1),
        n_lists=4, hot_capacity=16, coarse_iters=2)
    idx = StreamingIndex.bootstrap(
        jax.random.PRNGKey(0), random_walks(48, 32, seed=0), cfg)
    idx.insert(random_walks(40, 32, seed=1))   # sealed segments + hot rows
    idx.delete([1, 2])
    return idx


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
class TestBitIdentical:
    def test_search_identical_on_off(self, backend):
        with use_backend(backend):
            idx = _small_index()
            Q = random_walks(5, 32, seed=9)
            d_off, i_off = idx.search(Q, n_probe=2, topk=3)
            with obs.override(True):
                d_on, i_on = idx.search(Q, n_probe=2, topk=3)
        assert np.asarray(d_off).tobytes() == np.asarray(d_on).tobytes()
        assert np.array_equal(np.asarray(i_off), np.asarray(i_on))

    def test_search_sharded_identical_on_off(self, backend):
        with use_backend(backend):
            idx = _small_index()
            Q = random_walks(5, 32, seed=9)
            d_off, i_off = search_sharded(idx, Q, n_probe=2, topk=3)
            with obs.override(True):
                d_on, i_on = search_sharded(idx, Q, n_probe=2, topk=3)
        assert np.asarray(d_off).tobytes() == np.asarray(d_on).tobytes()
        assert np.array_equal(np.asarray(i_off), np.asarray(i_on))

    def test_search_identical_unfenced(self, backend):
        with use_backend(backend):
            idx = _small_index()
            Q = random_walks(5, 32, seed=9)
            d_off, i_off = idx.search(Q, n_probe=2, topk=3)
            with obs.override(True, fence=False):
                d_on, i_on = idx.search(Q, n_probe=2, topk=3)
        assert np.asarray(d_off).tobytes() == np.asarray(d_on).tobytes()
        assert np.array_equal(np.asarray(i_off), np.asarray(i_on))

    def test_unfenced_search_never_fences(self, backend, monkeypatch):
        def forbid(x):
            raise AssertionError("unfenced search must not block_until_ready"
                                 " through the obs layer")
        with use_backend(backend):
            idx = _small_index()
            monkeypatch.setattr("repro.obs.spans._block", forbid)
            with obs.override(True, fence=False):
                idx.search(random_walks(3, 32, seed=9), n_probe=2, topk=3)
                idx.flush()

    def test_disabled_search_never_fences(self, backend, monkeypatch):
        def forbid(x):
            raise AssertionError("obs-off search must not block_until_ready"
                                 " through the obs layer")
        monkeypatch.setattr("repro.obs.spans._block", forbid)
        with use_backend(backend):
            idx = _small_index()
            idx.search(random_walks(3, 32, seed=9), n_probe=2, topk=3)


def test_search_stages_name_their_device_work():
    """The jitted stage bodies carry ``index.search.<stage>`` scopes, so a
    profile can split device time by stage without fences."""
    from repro.index.streaming import search_impl
    idx = _small_index()
    Q = jax.numpy.asarray(random_walks(2, 32, seed=9))
    hlo = jax.jit(lambda q: search_impl(
        idx.coarse, idx.cb, tuple(idx.segments), idx._hot_arrays(), q,
        icfg=idx.cfg, n_probe=2, topk=3, dim=idx.dim)).lower(Q).as_text(
            debug_info=True)
    for stage in ("lut", "fine", "hot", "merge"):
        assert f"index.search.{stage}" in hlo, stage


class TestFilteredTopkStats:
    def test_with_stats_same_results(self):
        Q = random_walks(4, 32, seed=0)
        X = random_walks(30, 32, seed=1)
        d0, i0, n_ref = filtered_topk(Q, X, 4, 3)
        d1, i1, st = filtered_topk(Q, X, 4, 3, with_stats=True)
        assert np.array_equal(np.asarray(d0), np.asarray(d1))
        assert np.array_equal(np.asarray(i0), np.asarray(i1))
        assert int(st["n_refined"]) == int(n_ref)
        assert int(st["n_bounded"]) == 4 * 30
        assert int(st["n_refined"]) <= int(st["n_bounded"])
        waves = np.asarray(st["refined_per_wave"])
        assert int(waves.sum()) == int(st["n_refined"])
        assert int(st["n_waves"]) <= waves.shape[0]

    def test_dense_fallback_stats(self):
        # msm has no Keogh cascade: dense path refines every valid pair
        Q = random_walks(3, 32, seed=0)
        X = random_walks(10, 32, seed=1)
        d, i, st = filtered_topk(Q, X, 4, 2, measure="msm",
                                 with_stats=True)
        assert int(st["n_refined"]) == 30
        assert int(st["n_waves"]) == 1


# ---------------------------------------------------------------------------
# instrumentation lands in the registry
# ---------------------------------------------------------------------------

class TestInstrumentation:
    def test_search_spans_and_pruning_counters(self):
        idx = _small_index()
        with obs.override(True):
            before = obs.counter("index_searches_total",
                                 persistent=True).value
            idx.search(random_walks(3, 32, seed=9), n_probe=2, topk=3)
        assert obs.counter("index_searches_total",
                           persistent=True).value == before + 1
        for stage in ("index.search", "index.search.coarse",
                      "index.search.lut", "index.search.fine",
                      "index.search.hot", "index.search.merge"):
            h = obs.histogram("stage_seconds", persistent=True, stage=stage)
            assert h.count >= 1, stage
        bounded = obs.counter("lb_candidates_bounded_total",
                              persistent=True).value
        refined = obs.counter("lb_candidates_refined_total",
                              persistent=True).value
        pruned = obs.counter("lb_candidates_pruned_total",
                             persistent=True).value
        assert bounded == refined + pruned
        assert bounded > 0

    def test_lifecycle_gauges(self):
        idx = _small_index()
        with obs.override(True):
            idx.insert(random_walks(3, 32, seed=5))
        stats = idx.stats()
        assert obs.gauge("hot_fill", persistent=True).value \
            == stats["hot_fill"]
        assert obs.gauge("n_segments", persistent=True).value \
            == stats["n_segments"]
        occ = obs.gauge("hot_occupancy", persistent=True).value
        assert 0.0 <= occ <= 1.0

    def test_dispatch_mirror_counts_routes(self):
        before = obs.counter("dispatch_total", persistent=True,
                             op="elastic_cdist", backend="jax",
                             kind="trace", measure="dtw").value
        with use_backend("jax"):
            dispatch.elastic_cdist(random_walks(2, 16, seed=0),
                                   random_walks(3, 16, seed=1), 2)
        after = obs.counter("dispatch_total", persistent=True,
                            op="elastic_cdist", backend="jax",
                            kind="trace", measure="dtw").value
        assert after == before + 1


# ---------------------------------------------------------------------------
# export: JSON snapshot round-trip + Prometheus text format
# ---------------------------------------------------------------------------

class TestExport:
    def _populated(self):
        reg = Registry()
        reg.counter("hits", op="scan").inc(3)
        reg.gauge("fill").set(0.5)
        h = reg.histogram("lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 2.0):
            h.record(v)
        return reg

    def test_snapshot_round_trip(self):
        reg = self._populated()
        snap = json.loads(obs.to_json(reg))
        assert snap["counters"] == [
            {"name": "hits", "labels": {"op": "scan"}, "value": 3}]
        assert snap["gauges"][0]["value"] == 0.5
        (h,) = snap["histograms"]
        assert h["count"] == 3
        assert h["buckets"] == {"le": [0.1, 1.0], "counts": [1, 1, 1]}
        assert h["p50"] == pytest.approx(0.5)
        assert h["min"] == 0.05 and h["max"] == 2.0

    def test_snapshot_include_samples(self):
        snap = obs.snapshot(self._populated(), include_samples=True)
        assert snap["histograms"][0]["samples"] == [0.05, 0.5, 2.0]

    def test_prometheus_format(self):
        text = obs.to_prometheus(self._populated())
        assert '# TYPE repro_hits counter' in text
        assert 'repro_hits{op="scan"} 3' in text
        assert '# TYPE repro_lat histogram' in text
        assert 'repro_lat_bucket{le="0.1"} 1' in text
        assert 'repro_lat_bucket{le="1.0"} 2' in text
        assert 'repro_lat_bucket{le="+Inf"} 3' in text
        assert 'repro_lat_count 3' in text
        assert text.endswith("\n")

    def test_write_snapshot_and_report(self, tmp_path):
        path = str(tmp_path / "sub" / "snap.json")
        obs.write_snapshot(path, self._populated())
        with open(path) as f:
            snap = json.load(f)
        text = obs.render(snap, title="t")
        assert "obs_enabled" in text
        assert "hits" in text

    def test_check_stages(self):
        reg = Registry()
        reg.histogram("stage_seconds", stage="a").record(0.1)
        snap = obs.snapshot(reg)
        snap["obs_enabled"] = True
        ok, msg = obs.check_stages(snap, ["a"])
        assert ok and msg is None
        ok, msg = obs.check_stages(snap, ["a", "ghost"])
        assert not ok and "ghost" in msg
        snap["obs_enabled"] = False
        ok, msg = obs.check_stages(snap, ["a"])
        assert not ok and "disabled" in msg

    def test_prometheus_inf_gauge(self):
        reg = Registry()
        reg.gauge("g").set(math.inf)
        assert "repro_g +Inf" in obs.to_prometheus(reg)
