"""Measure registry + measure-generic engine: oracles, backends, gating."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch, measures
from repro.core.dtw import dtw_batch, euclidean_sq
from repro.core.lb_search import filtered_topk
from repro.core.measures import MeasureSpec, get_measure, resolve

ALL_MEASURES = ("dtw", "wdtw:g=0.1", "erp:g=0.3", "msm:c=0.5")
NON_DTW = ("wdtw:g=0.1", "erp:g=0.3", "msm:c=0.5")


# ---------------------------------------------------------------------------
# numpy DP oracle (textbook recurrences, O(L^2), independent of the sweeps)
# ---------------------------------------------------------------------------

def measure_reference(a, b, spec: MeasureSpec, window=None) -> float:
    n, m = len(a), len(b)
    w = max(n, m) if window is None else int(window)
    p = dict(spec.params)
    T = np.full((n + 1, m + 1), np.inf)
    T[0, 0] = 0.0
    if spec.name == "erp":
        for i in range(1, n + 1):
            T[i, 0] = T[i - 1, 0] + abs(a[i - 1] - p["g"])
        for j in range(1, m + 1):
            T[0, j] = T[0, j - 1] + abs(b[j - 1] - p["g"])
    for i in range(1, n + 1):
        for j in range(1, m + 1):
            if abs((i - 1) - (j - 1)) > w:
                continue
            x, y = float(a[i - 1]), float(b[j - 1])
            if spec.name == "dtw":
                cd = cv = ch = (x - y) ** 2
            elif spec.name == "wdtw":
                wt = 2.0 / (1.0 + np.exp(
                    -p["g"] * (abs((i - 1) - (j - 1)) - 0.5 * n)))
                cd = cv = ch = wt * (x - y) ** 2
            elif spec.name == "erp":
                cd, cv, ch = abs(x - y), abs(x - p["g"]), abs(y - p["g"])
            elif spec.name == "msm":
                c = p["c"]

                def C(new, prev, other):
                    if prev <= new <= other or prev >= new >= other:
                        return c
                    return c + min(abs(new - prev), abs(new - other))

                cd = abs(x - y)
                cv = C(x, float(a[i - 2]), y) if i >= 2 else 0.0
                ch = C(y, float(b[j - 2]), x) if j >= 2 else 0.0
            else:  # pragma: no cover
                raise ValueError(spec.name)
            T[i, j] = min(T[i - 1, j - 1] + cd, T[i - 1, j] + cv,
                          T[i, j - 1] + ch)
    return float(T[n, m])


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_ships_required_measures():
    for name in ("dtw", "wdtw", "erp", "msm"):
        assert name in measures.available()
    rows = measures.registry_rows()
    assert {r["name"] for r in rows} >= {"dtw", "wdtw", "erp", "msm"}
    dtw_row = next(r for r in rows if r["name"] == "dtw")
    assert dtw_row["has_keogh_lb"] and dtw_row["euclid_is_upper_bound"]


def test_resolve_forms_and_errors():
    assert resolve(None).name == "dtw"
    spec = resolve("erp:g=1.5")
    assert spec.name == "erp" and spec.param("g") == 1.5
    assert resolve(spec) is spec
    assert resolve("msm").param("c") == 0.5          # default
    with pytest.raises(ValueError, match="unknown elastic measure"):
        resolve("frechet")
    with pytest.raises(ValueError, match="no parameter"):
        get_measure("erp", gamma=1.0)


def test_spec_is_static_jit_key():
    """Equal-by-value specs must share a jit cache entry (hashable, eq)."""
    a = get_measure("erp", g=0.25)
    b = get_measure("erp", g=0.25)
    c = get_measure("erp", g=0.5)
    assert a == b and hash(a) == hash(b) and a != c
    assert a.to_manifest() == {"name": "erp", "params": {"g": 0.25}}


def test_register_custom_measure_flows_through_engine():
    """A user-registered measure runs the whole dispatch path unchanged."""
    if "sqed" not in measures.available():
        def step(params, x, y, xp, yp, dd, length):
            c = (x - y) ** 2 + params["bias"]
            return c, c, c
        measures.register_measure("sqed", step=step,
                                  defaults=(("bias", 0.0),),
                                  doc="test-only: dtw + constant bias")
    spec = get_measure("sqed", bias=0.0)
    rng = np.random.default_rng(0)
    A = rng.standard_normal((4, 12)).astype(np.float32)
    B = rng.standard_normal((4, 12)).astype(np.float32)
    with dispatch.use_backend("jax"):
        want = np.asarray(dispatch.elastic_pairwise(A, B, 3))
        got = np.asarray(dispatch.elastic_pairwise(A, B, 3, measure=spec))
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# recurrence correctness: both backends vs the numpy oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("measure", ALL_MEASURES)
@pytest.mark.parametrize("n,L,window", [(3, 8, None), (5, 16, 2), (4, 24, 5),
                                        (2, 1, None)])
def test_sweep_matches_oracle(measure, n, L, window):
    spec = resolve(measure)
    rng = np.random.default_rng(n * 31 + L)
    A = rng.standard_normal((n, L)).astype(np.float32)
    B = rng.standard_normal((n, L)).astype(np.float32)
    got = np.asarray(dtw_batch(A, B, window, spec))
    want = np.array([measure_reference(A[i], B[i], spec, window)
                     for i in range(n)])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("measure", ALL_MEASURES)
@pytest.mark.parametrize("n,m,L,window", [(4, 6, 12, None), (7, 5, 16, 3),
                                          (35, 130, 18, 2), (1, 256, 64, 0),
                                          (130, 3, 18, None)])
def test_dispatch_cdist_backends_agree_per_measure(measure, n, m, L, window):
    """Acceptance: elastic_cdist agrees between jax and pallas_interpret
    for every registered measure."""
    rng = np.random.default_rng(n * 13 + m)
    A = rng.standard_normal((n, L)).astype(np.float32)
    B = rng.standard_normal((m, L)).astype(np.float32)
    with dispatch.use_backend("jax"):
        want = np.asarray(dispatch.elastic_cdist(A, B, window,
                                                 measure=measure))
    with dispatch.use_backend("pallas_interpret"):
        got = np.asarray(dispatch.elastic_cdist(A, B, window,
                                                measure=measure))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("measure", ALL_MEASURES)
def test_dispatch_pairwise_backends_agree_per_measure(measure):
    rng = np.random.default_rng(7)
    A = rng.standard_normal((9, 20)).astype(np.float32)
    B = rng.standard_normal((9, 20)).astype(np.float32)
    with dispatch.use_backend("jax"):
        want = np.asarray(dispatch.elastic_pairwise(A, B, 4,
                                                    measure=measure))
    with dispatch.use_backend("pallas_interpret"):
        got = np.asarray(dispatch.elastic_pairwise(A, B, 4,
                                                   measure=measure))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# limiting-case equivalences
# ---------------------------------------------------------------------------

def test_wdtw_flat_weight_equals_dtw():
    """g = 0 makes the logistic weight flat 1, so wdtw == dtw exactly."""
    rng = np.random.default_rng(2)
    A = rng.standard_normal((6, 18)).astype(np.float32)
    B = rng.standard_normal((6, 18)).astype(np.float32)
    for window in (None, 3):
        flat = np.asarray(dtw_batch(A, B, window, get_measure("wdtw", g=0.0)))
        plain = np.asarray(dtw_batch(A, B, window))
        np.testing.assert_allclose(flat, plain, rtol=1e-5, atol=1e-5)


def test_erp_dtw_lockstep_limits():
    """The two lock-step limits that tie erp and dtw together: a huge gap
    penalty makes every ERP gap unaffordable (-> Manhattan, the L1
    lock-step), and window=0 restricts both DPs to the diagonal (ERP ->
    Manhattan again, DTW -> squared Euclidean)."""
    rng = np.random.default_rng(3)
    A = rng.standard_normal((5, 14)).astype(np.float32)
    B = rng.standard_normal((5, 14)).astype(np.float32)
    manhattan = np.abs(A - B).sum(1)
    big_g = np.asarray(dtw_batch(A, B, None, get_measure("erp", g=1e6)))
    np.testing.assert_allclose(big_g, manhattan, rtol=1e-4, atol=1e-3)
    banded = np.asarray(dtw_batch(A, B, 0, get_measure("erp", g=0.0)))
    np.testing.assert_allclose(banded, manhattan, rtol=1e-5, atol=1e-5)
    dtw0 = np.asarray(dtw_batch(A, B, 0))
    np.testing.assert_allclose(dtw0, ((A - B) ** 2).sum(1), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# capability gating
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
@pytest.mark.parametrize("measure", ALL_MEASURES)
def test_filtered_topk_exact_per_measure(backend, measure):
    """Acceptance: filtered_topk returns exactly the dense-cdist top-k for
    every measure — via pruning when capabilities allow it (dtw), via the
    gated dense fallback otherwise."""
    spec = resolve(measure)
    rng = np.random.default_rng(11)
    X = np.cumsum(rng.standard_normal((30, 16)), 1).astype(np.float32)
    Q = np.cumsum(rng.standard_normal((4, 16)), 1).astype(np.float32)
    with dispatch.use_backend(backend):
        d, idx, n_ref = filtered_topk(Q, X, 3, 2, measure=spec)
        dense = np.asarray(dispatch.elastic_cdist(Q, X, 3, measure=spec))
    want = np.sort(dense, axis=1)[:, :2]
    np.testing.assert_allclose(np.asarray(d), want, rtol=1e-5, atol=1e-5)
    if spec.can_prune:
        assert int(n_ref) <= Q.shape[0] * X.shape[0]
    else:
        assert int(n_ref) == Q.shape[0] * X.shape[0]   # dense fallback


def test_filtered_topk_dense_fallback_respects_valid_mask():
    rng = np.random.default_rng(5)
    X = rng.standard_normal((12, 10)).astype(np.float32)
    Q = rng.standard_normal((3, 10)).astype(np.float32)
    valid = np.ones(12, bool)
    valid[::2] = False
    with dispatch.use_backend("jax"):
        d, idx, n_ref = filtered_topk(Q, X, 2, 2, valid=jnp.asarray(valid),
                                      measure="msm")
    assert int(n_ref) == 3 * int(valid.sum())
    assert set(np.asarray(idx).ravel().tolist()) <= set(
        np.flatnonzero(valid).tolist())


def test_lb_refine_rejects_uncascaded_measures():
    rng = np.random.default_rng(6)
    A = rng.standard_normal((4, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="no sound Keogh"):
        dispatch.lb_refine(A, A, A, A, np.zeros(4, np.float32), 2,
                           measure="erp")


def test_full_width_kernel_is_dtw_only():
    from repro.kernels.dtw_band.ops import dtw_band
    rng = np.random.default_rng(8)
    A = rng.standard_normal((4, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="DTW-only"):
        dtw_band(A, A, 2, interpret=True, mode="full", measure="msm")


def test_euclid_upper_bound_flags_are_sound():
    """Where the flag is set, squared ED must dominate the measure (the
    threshold-seed soundness filtered_topk relies on)."""
    rng = np.random.default_rng(9)
    A = rng.standard_normal((8, 12)).astype(np.float32)
    B = rng.standard_normal((8, 12)).astype(np.float32)
    ed = np.asarray(euclidean_sq(A, B)).diagonal()
    for measure in ALL_MEASURES:
        spec = resolve(measure)
        if not spec.euclid_is_upper_bound:
            continue
        d = np.asarray(dtw_batch(A, B, None, spec))
        assert (d <= ed + 1e-4 + 1e-5 * np.abs(ed)).all(), spec.label


# ---------------------------------------------------------------------------
# PQ end-to-end + routing per measure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("measure", ALL_MEASURES)
def test_pq_fit_encode_classify_per_measure(measure):
    """Acceptance: a full pq fit -> encode -> 1NN classification run
    completes for every registered measure, with codes agreeing across
    backends."""
    from repro.core.knn import knn_classify_sym
    from repro.core.pq import PQConfig, encode, fit
    from repro.data.timeseries import trace_like
    spec = resolve(measure)
    Xtr, ytr = trace_like(n_per_class=5, length=32, seed=0)
    Xte, _ = trace_like(n_per_class=2, length=32, seed=3)
    cfg = PQConfig(n_sub=4, codebook_size=4, metric=spec.name,
                   measure_params=spec.params, kmeans_iters=2, dba_iters=1)
    key = jax.random.PRNGKey(0)
    with dispatch.use_backend("jax"):
        cb = fit(key, jnp.asarray(Xtr), cfg)
        codes_j = np.asarray(encode(jnp.asarray(Xtr), cb, cfg))
        pred = knn_classify_sym(jnp.asarray(codes_j), jnp.asarray(ytr),
                                jnp.asarray(Xte), cb, cfg)
    assert pred.shape == (len(Xte),)
    with dispatch.use_backend("pallas_interpret"):
        codes_p = np.asarray(encode(jnp.asarray(Xtr), cb, cfg))
    np.testing.assert_array_equal(codes_p, codes_j)
    assert codes_j.min() >= 0 and codes_j.max() < cfg.codebook_size


@pytest.mark.parametrize("measure", NON_DTW)
def test_fused_prealign_encode_per_measure(measure):
    """The fused prealign+encode path is measure-generic: identical codes
    on both backends, and non-cascade measures force the full-scan (fused)
    route even without exact_encode."""
    from repro.core.pq import PQConfig, encode, fit, uses_fused_prealign
    spec = resolve(measure)
    rng = np.random.default_rng(4)
    X = jnp.asarray(rng.standard_normal((10, 32)).astype(np.float32))
    cfg = PQConfig(n_sub=4, codebook_size=4, metric=spec.name,
                   measure_params=spec.params, use_prealign=True,
                   wavelet_level=2, kmeans_iters=2, dba_iters=1)
    assert cfg.full_scan_encode()        # capability-gated off the LB filter
    assert uses_fused_prealign(cfg)
    with dispatch.use_backend("jax"):
        cb = fit(jax.random.PRNGKey(1), X, cfg)
        dispatch.reset_stats()
        codes_j = np.asarray(encode(X, cb, cfg))
        assert dispatch.stats.get(
            (f"prealign_encode[{spec.name}]", "jax"), 0) == 1
    with dispatch.use_backend("pallas_interpret"):
        codes_p = np.asarray(encode(X, cb, cfg))
    np.testing.assert_array_equal(codes_j, codes_p)


def test_per_measure_routing_counters():
    """The dispatch ledger records op[measure] alongside the bare op."""
    rng = np.random.default_rng(10)
    A = rng.standard_normal((4, 8)).astype(np.float32)
    jax.clear_caches()
    dispatch.reset_stats()
    with dispatch.use_backend("pallas_interpret"):
        dispatch.elastic_pairwise(A, A, 2, measure="msm")
    assert dispatch.stats.get(("elastic_pairwise", "pallas_interpret")) == 1
    assert dispatch.stats.get(
        ("elastic_pairwise[msm]", "pallas_interpret")) == 1
    assert dispatch.totals.get(
        ("elastic_pairwise[msm]", "pallas_interpret"), 0) >= 1


# ---------------------------------------------------------------------------
# IVF + streaming index per measure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("measure", ["msm:c=0.5", "erp:g=0.2"])
def test_ivf_search_and_lb_budget_gate(measure):
    from repro.core import ivf
    from repro.core.pq import PQConfig
    spec = resolve(measure)
    rng = np.random.default_rng(12)
    X = rng.standard_normal((24, 32)).astype(np.float32)
    cfg = PQConfig(n_sub=2, codebook_size=4, metric=spec.name,
                   measure_params=spec.params, kmeans_iters=2, dba_iters=1)
    with dispatch.use_backend("jax"):
        index = ivf.build_index(jax.random.PRNGKey(2), X, cfg, n_lists=3)
        d0, i0 = ivf.search_batch(index, X[:4], cfg, n_probe=3, topk=3)
        # lb_budget must be ignored (not unsoundly applied) for measures
        # without a Keogh cascade: results identical to the exact path
        d1, i1 = ivf.search_batch(index, X[:4], cfg, n_probe=3, topk=3,
                                  lb_budget=3)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_streaming_snapshot_roundtrips_measure(tmp_path, backend):
    """Acceptance: a streaming-index snapshot round-trips the measure
    config, and a tampered measure record is a hard error on restore."""
    from repro.core.pq import PQConfig
    from repro.data.timeseries import random_walks
    from repro.index import (IndexConfig, StreamingIndex, restore_snapshot,
                             save_snapshot)
    from repro.index.snapshot import MANIFEST
    cfg = IndexConfig(
        pq=PQConfig(n_sub=4, codebook_size=8, metric="erp",
                    measure_params=(("g", 0.25),), use_prealign=False,
                    kmeans_iters=2, dba_iters=1),
        n_lists=4, hot_capacity=16, coarse_iters=2)
    with dispatch.use_backend(backend):
        index = StreamingIndex.bootstrap(
            jax.random.PRNGKey(0), random_walks(24, 48, seed=0), cfg)
        index.insert(random_walks(20, 48, seed=1))
        Q = random_walks(3, 48, seed=9)
        d1, n1 = index.search(Q, n_probe=2, topk=3)
        snapdir = str(tmp_path / backend)
        save_snapshot(snapdir, index)
        restored = restore_snapshot(snapdir)
        assert restored.cfg.pq.metric == "erp"
        assert restored.cfg.pq.measure_params == (("g", 0.25),)
        assert restored.cfg.pq.measure() == cfg.pq.measure()
        d2, n2 = restored.search(Q, n_probe=2, topk=3)
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)
    # tamper: flip the measure record -> hard error, not silent reinterpret
    snap = next(p for p in sorted(os.listdir(snapdir))
                if p.startswith("snap_"))
    mpath = os.path.join(snapdir, snap, MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["measure"] = {"name": "msm", "params": {"c": 0.5}}
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="does not match"):
        restore_snapshot(snapdir)


def test_pqconfig_validates_and_normalizes_measure():
    from repro.core.pq import PQConfig
    cfg = PQConfig(metric="msm", measure_params={"c": 0.1})
    assert cfg.measure_params == (("c", 0.1),)
    assert cfg.measure().param("c") == 0.1
    assert dataclasses.replace(cfg).measure_params == (("c", 0.1),)
    with pytest.raises(ValueError, match="unknown elastic measure"):
        PQConfig(metric="nope")
    assert PQConfig(metric="euclidean").measure() is None


# ---------------------------------------------------------------------------
# window-default contract
# ---------------------------------------------------------------------------

def test_effective_window_contract():
    from repro.core.dispatch import effective_window
    assert effective_window(16, None) == 15
    assert effective_window(16, 100) == 15
    assert effective_window(16, 3) == 3
    assert effective_window(16, 0) == 0
    assert effective_window(1, None) == 0
