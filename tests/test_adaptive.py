"""Adaptive alignment corridors, the kernel autotuner, and the quantized
ADC LUT path (the perf-opt PR's three new surfaces).

Exactness contract under test: when a pair's corridor contains the
static-band optimal path, ``band="adaptive"`` results are *bit-identical*
to the static band on both the jax and pallas_interpret routes; when the
corridor is too tight the adaptive result is the documented approximate
upper bound (>= static, still certifiable as such).
"""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax._src.pallas.mosaic.error_handling import MosaicError

from repro.core import corridor as corr
from repro.core import dispatch
from repro.core.lb import keogh_envelope
from repro.core.lb_search import filtered_topk
from repro.kernels.common import CompiledRouteUnsupported
from repro.kernels import tune

from conftest import dtw_reference


def _warped_pairs(n, L, seed=0, drift=3):
    """Locally-warped pairs: B is A with small random time warps, so the
    true alignment path hugs the diagonal within a few cells — the shape
    adaptive corridors exploit."""
    rng = np.random.default_rng(seed)
    A = np.cumsum(rng.normal(size=(n, L)), axis=1).astype(np.float32)
    B = np.empty_like(A)
    for i in range(n):
        # piecewise-smooth monotone warp within +/- drift cells
        steps = rng.integers(-1, 2, size=L).astype(np.float64)
        off = np.clip(np.cumsum(steps), -drift, drift)
        idx = np.clip(np.arange(L) + off, 0, L - 1)
        B[i] = A[i, idx.astype(np.int64)]
    return jnp.asarray(A), jnp.asarray(B + rng.normal(
        scale=0.05, size=B.shape).astype(np.float32))


# -- corridor construction ---------------------------------------------------

def test_corridor_invariants():
    A, B = _warped_pairs(6, 96, seed=1)
    L = 96
    lo, hi = corr.build_corridor(A, B, 9)
    lo = np.asarray(lo)
    hi = np.asarray(hi)
    lo_s, hi_s = map(np.asarray, corr.static_band(L, 9))
    assert lo.shape == (6, 2 * L - 1)
    # endpoints pinned, monotone lo with drift <= 1, inside the static band
    assert (lo[:, 0] == 0).all() and (lo[:, -1] == L - 1).all()
    d = np.diff(lo, axis=1)
    assert ((d >= 0) & (d <= 1)).all()
    assert (lo >= lo_s[None]).all() and (hi <= hi_s[None]).all()
    assert (hi >= lo).all()


def test_corridor_narrower_than_static_band_on_warped_data():
    # window_frac ~ 0.1 at L=512: the static band is ~52 cells per
    # diagonal while the projected corridor stays near the coarse path
    A, B = _warped_pairs(4, 512, seed=2)
    w = 51
    lo, hi = corr.build_corridor(A, B, w)
    lo_s, hi_s = corr.static_band(512, w)
    static_cells = float(jnp.sum(hi_s - lo_s + 1))
    adaptive_cells = float(jnp.mean(jnp.sum(hi - lo + 1, axis=1)))
    assert adaptive_cells < 0.8 * static_cells
    # and the adaptive *register* (what the kernel actually allocates)
    # is narrower than the static compressed register
    from repro.kernels.dtw_band.kernel import band_width
    assert tune.adaptive_width(512, w) < band_width(512, w, 8)


# -- adaptive exactness ------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_adaptive_bit_identical_when_corridor_contains_path(backend):
    A, B = _warped_pairs(8, 64, seed=3, drift=2)
    w = 6
    with dispatch.use_backend(backend):
        ds = dispatch.elastic_pairwise(A, B, w)
        da = dispatch.elastic_pairwise(A, B, w, band="adaptive")
    ok = np.asarray(corr.certify_adaptive(
        A, B, *corr.build_corridor(A, B, w), window=w,
        width=tune.adaptive_width(64, w)))
    assert ok.all()                      # corridors converged on this data
    np.testing.assert_array_equal(np.asarray(da), np.asarray(ds))


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_adaptive_matches_numpy_oracle(backend):
    A, B = _warped_pairs(4, 48, seed=4, drift=2)
    w = 5
    with dispatch.use_backend(backend):
        da = np.asarray(dispatch.elastic_pairwise(A, B, w, band="adaptive"))
    ref = np.array([dtw_reference(np.asarray(A[i]), np.asarray(B[i]), w)
                    for i in range(4)])
    # certified pairs are exactly the static distance
    ok = np.asarray(corr.certify_adaptive(
        A, B, *corr.build_corridor(A, B, w), window=w,
        width=tune.adaptive_width(48, w)))
    np.testing.assert_allclose(da[ok], ref[ok], rtol=1e-5, atol=1e-5)
    # uncertified pairs (if any) are valid upper bounds
    assert (da >= ref - 1e-4).all()


def test_adaptive_violation_is_upper_bound_not_crash():
    # anti-correlated pairs: the optimal path wanders the whole band, so a
    # tight corridor (tiny width cap) must clip it
    rng = np.random.default_rng(5)
    A = jnp.asarray(np.cumsum(rng.normal(size=(6, 64)), axis=1),
                    jnp.float32)
    B = jnp.asarray(np.cumsum(rng.normal(size=(6, 64)), axis=1),
                    jnp.float32)
    w = 16
    lo, hi = corr.build_corridor(A, B, w, factor=4, radius=0)
    lo, hi = corr.clip_to_width(lo, hi, 8)
    with dispatch.use_backend("jax"):
        ds = np.asarray(dispatch.elastic_pairwise(A, B, w))
        da = np.asarray(dispatch.elastic_pairwise(
            A, B, w, band="adaptive", corridor=(lo, hi), width=8))
    assert (da >= ds - 1e-4).all()
    cert = np.asarray(corr.certify_adaptive(A, B, lo, hi, window=w,
                                            width=8))
    # wherever certification failed the result may exceed static; wherever
    # it held the result is exact
    np.testing.assert_allclose(da[cert], ds[cert], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_lb_refine_adaptive_refines_and_bounds(backend):
    A, B = _warped_pairs(8, 64, seed=6, drift=2)
    w = 6
    up, lo_env = keogh_envelope(A, w)
    th = jnp.full((8,), jnp.inf, jnp.float32)
    with dispatch.use_backend(backend):
        ds, rs = dispatch.lb_refine(A, B, up, lo_env, th, w)
        da, ra = dispatch.lb_refine(A, B, up, lo_env, th, w,
                                    band="adaptive")
    assert np.asarray(rs).all() and np.asarray(ra).all()
    assert (np.asarray(da) >= np.asarray(ds) - 1e-4).all()
    # warped data: corridors converge, results bit-identical
    ok = np.asarray(corr.certify_adaptive(
        A, B, *corr.build_corridor(A, B, w), window=w,
        width=tune.adaptive_width(64, w)))
    np.testing.assert_array_equal(np.asarray(da)[ok], np.asarray(ds)[ok])


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_filtered_topk_adaptive_top1_agrees_on_warped_data(backend):
    Q, X = _warped_pairs(4, 64, seed=7, drift=2)
    X = jnp.concatenate([X, X[::-1] + 5.0], axis=0)   # 8 candidates
    with dispatch.use_backend(backend):
        d_s, i_s, _ = filtered_topk(Q, X, 6, 1)
        d_a, i_a, _ = filtered_topk(Q, X, 6, 1, band="adaptive")
    np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_a))
    assert (np.asarray(d_a) >= np.asarray(d_s) - 1e-4).all()


def test_filtered_topk_rejects_unknown_band():
    Q, X = _warped_pairs(2, 32, seed=8)
    with pytest.raises(ValueError, match="band"):
        filtered_topk(Q, X, 4, 1, band="wavy")


def test_dispatch_rejects_unknown_band():
    A, B = _warped_pairs(2, 32, seed=9)
    with pytest.raises(ValueError, match="band"):
        dispatch.elastic_pairwise(A, B, 4, band="wavy")
    up, lo_env = keogh_envelope(A, 4)
    with pytest.raises(ValueError, match="band"):
        dispatch.lb_refine(A, B, up, lo_env, jnp.zeros((2,)), 4,
                           band="wavy")


# -- streaming index adaptive band -------------------------------------------

def test_streaming_index_adaptive_band_smoke():
    from repro.core.pq import PQConfig
    from repro.index.streaming import IndexConfig, StreamingIndex

    rng = np.random.default_rng(10)
    D = 32
    X = np.cumsum(rng.normal(size=(24, D)), axis=1).astype(np.float32)
    cfgs = {}
    for band in ("static", "adaptive"):
        icfg = IndexConfig(PQConfig(n_sub=2, codebook_size=4,
                                    kmeans_iters=2, dba_iters=1),
                           n_lists=2, hot_capacity=64, band=band)
        idx = StreamingIndex.bootstrap(jax.random.PRNGKey(0), X[:16], icfg)
        idx.insert(X[16:], ids=np.arange(16, 24))
        d, ids = idx.search(X[16:20], n_probe=2, topk=1)
        cfgs[band] = np.asarray(ids)
    # hot rows are exact self-matches under both bands
    np.testing.assert_array_equal(cfgs["static"], cfgs["adaptive"])


def test_index_config_rejects_bad_band():
    from repro.core.pq import PQConfig
    from repro.index.streaming import IndexConfig
    with pytest.raises(ValueError, match="band"):
        IndexConfig(PQConfig(), n_lists=2, band="diagonal")


# -- quantized ADC LUT path --------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
@pytest.mark.parametrize("dtype", ["int8", "bfloat16"])
def test_adc_cdist_quant_within_tolerance(backend, dtype):
    rng = np.random.default_rng(11)
    M, K = 4, 16
    lut = jnp.asarray(rng.normal(size=(M, K, K)).astype(np.float32) ** 2)
    codes = jnp.asarray(rng.integers(0, K, size=(12, M)), jnp.int32)
    with dispatch.use_backend(backend):
        Df = np.asarray(dispatch.adc_cdist(codes, codes, lut))
        Dq = np.asarray(dispatch.adc_cdist(codes, codes, lut,
                                           lut_dtype=dtype))
    scale = np.abs(Df).max() + 1e-6
    assert np.abs(Dq - Df).max() / scale < 0.02


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
@pytest.mark.parametrize("dtype", ["int8", "bfloat16"])
def test_adc_lookup_quant_within_tolerance(backend, dtype):
    rng = np.random.default_rng(12)
    M, K = 4, 16
    qlut = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32) ** 2)
    codes = jnp.asarray(rng.integers(0, K, size=(12, M)), jnp.int32)
    with dispatch.use_backend(backend):
        vf = np.asarray(dispatch.adc_lookup(codes, qlut))
        vq = np.asarray(dispatch.adc_lookup(codes, qlut, lut_dtype=dtype))
    scale = np.abs(vf).max() + 1e-6
    assert np.abs(vq - vf).max() / scale < 0.02


def test_pq_cdist_sym_quant_route():
    from repro.core.pq import cdist_sym
    codes = jnp.array([[0, 1], [1, 0]], jnp.int32)
    lut = jnp.stack([1.0 - jnp.eye(2)] * 2)
    with dispatch.use_backend("jax"):
        Df = np.asarray(cdist_sym(codes, codes, lut))
        Dq = np.asarray(cdist_sym(codes, codes, lut, lut_dtype="int8"))
    np.testing.assert_allclose(Dq, Df, atol=0.02)


def test_quantize_lut_roundtrip():
    from repro.kernels.pq_adc.ops import quantize_lut
    from repro.kernels.pq_adc.ref import _dequant
    rng = np.random.default_rng(13)
    lut = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32) * 7)
    q, sc, zp = quantize_lut(lut, dtype="int8")
    assert q.dtype == jnp.int8
    back = np.asarray(_dequant(q, sc, zp))
    err = np.abs(back - np.asarray(lut)).max()
    rng_span = float(lut.max() - lut.min())
    assert err <= rng_span / 254 + 1e-5


def test_adc_cdist_rejects_unknown_lut_dtype():
    codes = jnp.zeros((2, 2), jnp.int32)
    lut = jnp.zeros((2, 4, 4))
    with pytest.raises(ValueError, match="dtype"):
        dispatch.adc_cdist(codes, codes, lut, lut_dtype="fp4")


# -- autotuner ---------------------------------------------------------------

def test_tune_off_returns_defaults(monkeypatch):
    monkeypatch.setenv(tune.ENV, "off")
    tune.reset()
    assert tune.tuned("dtw_band", "block", length=128, window=12,
                      default=7) == 7


def test_tune_pinned_table_is_deterministic(tmp_path, monkeypatch):
    key = tune.table_key("dtw_band", length=128, window=12, measure="dtw",
                         backend="pallas_interpret")
    table = {key: {"block": 16}}
    path = tmp_path / "pinned.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv(tune.ENV, str(path))
    tune.reset()
    for _ in range(3):
        assert tune.tuned("dtw_band", "block", length=128, window=12,
                          measure="dtw", backend="pallas_interpret",
                          default=8) == 16
    # a geometry the table does not pin falls back to the default
    assert tune.tuned("dtw_band", "block", length=4096, window=400,
                      measure="dtw", backend="pallas_interpret",
                      default=8) == 8


def test_tune_auto_benchmarks_and_memoizes(tmp_path, monkeypatch):
    monkeypatch.setenv(tune.ENV, "auto")
    monkeypatch.setenv(tune.GRID_ENV, "minimal")
    monkeypatch.setenv(tune.OUT_ENV, str(tmp_path))
    tune.reset()
    got = tune.tuned("dtw_band", "block", length=32, window=3,
                     measure="dtw", backend="pallas_interpret", default=8)
    assert got == 8                      # minimal grid = (default,)
    out = tmp_path / "tuning.json"
    assert out.exists()
    saved = json.loads(out.read_text())
    key = tune.table_key("dtw_band", length=32, window=3, measure="dtw",
                         backend="pallas_interpret")
    assert saved[key]["block"] == 8
    # second call hits the memo (and must not re-write a different value)
    assert tune.tuned("dtw_band", "block", length=32, window=3,
                      measure="dtw", backend="pallas_interpret",
                      default=8) == 8


def test_tuned_is_noop_inside_trace(tmp_path, monkeypatch):
    # block resolution happens at trace time; mid-trace the tuner must
    # fall back to defaults instead of launching benchmark kernels
    monkeypatch.setenv(tune.ENV, "auto")
    monkeypatch.setenv(tune.GRID_ENV, "minimal")
    monkeypatch.setenv(tune.OUT_ENV, str(tmp_path))
    tune.reset()
    seen = []

    def no_measure(*args, **kwargs):
        raise AssertionError("benchmarked inside a trace")

    monkeypatch.setattr(tune, "_measure", no_measure)
    assert tune._trace_clean()

    @jax.jit
    def f(x):
        assert not tune._trace_clean()
        seen.append(tune.tuned("dtw_band", "block", length=64, window=6,
                               default=8))
        return x

    f(jnp.zeros(3))
    assert seen == [8]
    assert not (tmp_path / "tuning.json").exists()


def _failing_measure(err):
    """Every candidate but the smallest block fails with ``err``."""
    def measure(op, params, **kwargs):
        if params["block"] != 8:
            raise err
        return 1.0
    return measure


@pytest.mark.parametrize("err", [
    CompiledRouteUnsupported("a test kernel"),
    MosaicError("Mosaic failed to compile TPU kernel"),
], ids=["no_compiled_kernel", "mosaic_refused"])
def test_tune_skips_candidates_that_do_not_compile(tmp_path, monkeypatch,
                                                   err):
    monkeypatch.setenv(tune.ENV, "auto")
    monkeypatch.delenv(tune.GRID_ENV, raising=False)
    monkeypatch.setenv(tune.OUT_ENV, str(tmp_path))
    tune.reset()
    monkeypatch.setattr(tune, "_measure", _failing_measure(err))
    assert tune.tuned("dtw_band", "block", length=64, window=6,
                      default=32) == 8


def test_tune_reraises_errors_other_than_compile_errors(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv(tune.ENV, "auto")
    monkeypatch.delenv(tune.GRID_ENV, raising=False)
    monkeypatch.setenv(tune.OUT_ENV, str(tmp_path))
    tune.reset()
    monkeypatch.setattr(tune, "_measure",
                        _failing_measure(TypeError("a bug, not a compile")))
    with pytest.raises(TypeError, match="a bug"):
        tune.tuned("dtw_band", "block", length=64, window=6, default=32)


def test_adaptive_width_is_lane_aligned_and_capped():
    from repro.kernels.dtw_band.kernel import band_width
    for L, w in ((128, 12), (512, 51), (64, 63)):
        aw = tune.adaptive_width(L, w)
        assert aw % 8 == 0
        assert aw <= band_width(L, w, 8)


def test_band_width_exact_when_lane_aligned():
    from repro.kernels.dtw_band.kernel import band_width
    # aligned band: width == cell count, no extra padding lane
    assert band_width(128, 15, 8) == 16          # need 16 -> exactly 16
    assert band_width(128, 31, 8) == 32          # need 32 -> exactly 32
    # unaligned band rounds up to the next lane multiple
    assert band_width(128, 12, 8) == 16          # need 13 -> 16
    assert band_width(128, 16, 8) == 24          # need 17 -> 24
    # capped at the series length
    assert band_width(64, 1000, 8) == 64
