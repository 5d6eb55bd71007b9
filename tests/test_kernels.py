"""Pallas kernels (interpret mode) vs pure-jnp oracles — shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dtw_band.ops import dtw_band, dtw_band_cdist
from repro.kernels.dtw_band.ref import dtw_band_ref, dtw_band_cdist_ref
from repro.kernels.pq_adc.ops import adc_lookup, adc_sym_cdist
from repro.kernels.pq_adc.ref import adc_lookup_ref, adc_sym_cdist_ref
from repro.kernels.pq_attn.ops import (build_qlut, encode_keys,
                                       pq_attn_decode)
from repro.kernels.pq_attn.ref import pq_attn_decode_ref, reconstruct_keys
from repro.kernels.prealign_encode.ops import prealign_encode
from repro.kernels.prealign_encode.ref import prealign_encode_ref


# ---------------------------------------------------------------------------
# dtw_band
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,L", [(1, 8), (5, 16), (8, 32), (13, 64), (32, 24)])
@pytest.mark.parametrize("window", [None, 2, 5])
def test_dtw_band_matches_ref(n, L, window):
    rng = np.random.default_rng(n * 131 + L)
    A = rng.standard_normal((n, L)).astype(np.float32)
    B = rng.standard_normal((n, L)).astype(np.float32)
    got = np.asarray(dtw_band(A, B, window, interpret=True))
    want = np.asarray(dtw_band_ref(A, B, window))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.float16])
def test_dtw_band_dtypes(dtype):
    rng = np.random.default_rng(0)
    A = rng.standard_normal((4, 16)).astype(dtype)
    B = rng.standard_normal((4, 16)).astype(dtype)
    got = np.asarray(dtw_band(A, B, 3, interpret=True))
    want = np.asarray(dtw_band_ref(A.astype(np.float32),
                                   B.astype(np.float32), 3))
    rtol = 1e-5 if dtype != np.float16 else 2e-2
    np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-2)


@pytest.mark.parametrize("L,window", [(18, 0), (18, 2), (18, None),
                                      (64, 0), (64, 2), (64, None)])
@pytest.mark.parametrize("m", [3, 130, 256])
@pytest.mark.parametrize("n", [1, 3, 35, 130])
def test_dtw_band_cdist_matches_ref(n, m, L, window):
    """Serving-like shapes: a single query up to more than one A block,
    fewer B rows than a lane tile up to two tiles, a zero band up to the
    full one."""
    rng = np.random.default_rng(n * 7 + m * 3 + L)
    A = rng.standard_normal((n, L)).astype(np.float32)
    B = rng.standard_normal((m, L)).astype(np.float32)
    got = np.asarray(dtw_band_cdist(A, B, window, interpret=True))
    want = np.asarray(dtw_band_cdist_ref(A, B, window))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dtw_band_odd_batch_padding():
    """Batch not divisible by block must round-trip through padding."""
    rng = np.random.default_rng(4)
    A = rng.standard_normal((7, 12)).astype(np.float32)
    B = rng.standard_normal((7, 12)).astype(np.float32)
    got = np.asarray(dtw_band(A, B, None, block=8, interpret=True))
    want = np.asarray(dtw_band_ref(A, B, None))
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ---------------------------------------------------------------------------
# dtw_band: band-compressed vs full-width sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,L", [(1, 8), (5, 16), (7, 32), (13, 64), (3, 2)])
@pytest.mark.parametrize("window", [None, 1, 3, 100])  # 100 >= every L
def test_dtw_band_compressed_matches_ref(n, L, window):
    rng = np.random.default_rng(n * 311 + L)
    A = rng.standard_normal((n, L)).astype(np.float32)
    B = rng.standard_normal((n, L)).astype(np.float32)
    got = np.asarray(dtw_band(A, B, window, interpret=True,
                              mode="compressed"))
    want = np.asarray(dtw_band_ref(A, B, window))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dtw_band_modes_agree():
    """Full-width and band-compressed sweeps are the same DP."""
    rng = np.random.default_rng(8)
    A = rng.standard_normal((6, 40)).astype(np.float32)
    B = rng.standard_normal((6, 40)).astype(np.float32)
    full = np.asarray(dtw_band(A, B, 4, interpret=True, mode="full"))
    comp = np.asarray(dtw_band(A, B, 4, interpret=True, mode="compressed"))
    np.testing.assert_allclose(comp, full, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block", [4, 24, 128])
def test_dtw_band_cdist_no_materialize_grid(block):
    """2-D grid cdist vs reference, odd shapes: ``block`` register
    sublanes give one chain per grid step, a few (A rows padded to a
    whole step), or every A row at once, depending on the band."""
    rng = np.random.default_rng(12)
    A = rng.standard_normal((11, 24)).astype(np.float32)
    B = rng.standard_normal((5, 24)).astype(np.float32)
    for window in (None, 2, 50):
        got = np.asarray(dtw_band_cdist(A, B, window, block=block,
                                        interpret=True))
        want = np.asarray(dtw_band_cdist_ref(A, B, window))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m", [3, 128, 130, 256])
def test_dtw_band_cdist_pads_b_to_lane_tiles(m):
    """B rows sit on lanes: they are padded to whole 128-row tiles and the
    padded output columns are sliced off."""
    rng = np.random.default_rng(13)
    A = rng.standard_normal((3, 10)).astype(np.float32)
    B = rng.standard_normal((m, 10)).astype(np.float32)
    got = np.asarray(dtw_band_cdist(A, B, 2, block=16, interpret=True))
    assert got.shape == (3, m)
    np.testing.assert_allclose(got, np.asarray(dtw_band_cdist_ref(A, B, 2)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["dtw_band", "lb_refine"])
def test_adaptive_corridor_refused_on_compiled_route(op):
    """The adaptive sweep's per-row gathers have no TPU lowering: asking
    for it with interpret=False fails at trace time with a named error
    instead of taking another route."""
    from repro.core import corridor as corr
    from repro.kernels.common import CompiledRouteUnsupported
    from repro.kernels.lb_cascade.ops import lb_refine
    rng = np.random.default_rng(14)
    A = rng.standard_normal((8, 16)).astype(np.float32)
    B = rng.standard_normal((8, 16)).astype(np.float32)
    band = corr.static_band(16, 3)
    lo, hi = (jnp.broadcast_to(x, (8, 31)) for x in band)
    with pytest.raises(CompiledRouteUnsupported, match="adaptive corridor"):
        if op == "dtw_band":
            dtw_band(A, B, 3, interpret=False, corridor=(lo, hi), width=8)
        else:
            lb_refine(A, B, A, A, np.zeros(8, np.float32), 3,
                      interpret=False, corridor=(lo, hi), width=8)


# ---------------------------------------------------------------------------
# pq_adc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("na,nb,M,K", [(4, 4, 2, 8), (17, 9, 4, 16),
                                       (64, 64, 8, 256), (3, 130, 5, 32)])
def test_adc_sym_matches_ref(na, nb, M, K):
    rng = np.random.default_rng(na * 7 + nb)
    lut = np.abs(rng.standard_normal((M, K, K))).astype(np.float32)
    lut = lut + lut.transpose(0, 2, 1)
    a = rng.integers(0, K, (na, M)).astype(np.int32)
    b = rng.integers(0, K, (nb, M)).astype(np.int32)
    got = np.asarray(adc_sym_cdist(a, b, lut, block_a=8, block_b=8,
                                   interpret=True))
    want = np.asarray(adc_sym_cdist_ref(a, b, lut))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,M,K", [(5, 3, 8), (100, 7, 256), (257, 4, 64)])
def test_adc_lookup_matches_ref(n, M, K):
    rng = np.random.default_rng(n)
    qlut = np.abs(rng.standard_normal((M, K))).astype(np.float32)
    codes = rng.integers(0, K, (n, M)).astype(np.int32)
    got = np.asarray(adc_lookup(codes, qlut, block=32, interpret=True))
    want = np.asarray(adc_lookup_ref(codes, qlut))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,n,M,K", [(1, 5, 3, 8), (3, 100, 7, 256), (4, 257, 16, 256)])
def test_adc_lookup_batched_matches_ref(b, n, M, K):
    """A batch of scans, each against its own table, in one launch: the
    batch is the grid's leading axis, every scan equals its own launch."""
    rng = np.random.default_rng(b * n)
    qlut = np.abs(rng.standard_normal((b, M, K))).astype(np.float32)
    codes = rng.integers(0, K, (b, n, M)).astype(np.int32)
    got = np.asarray(adc_lookup(codes, qlut, block=32, interpret=True))
    want = np.asarray(adc_lookup_ref(codes, qlut))
    assert got.shape == want.shape == (b, n)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for q in range(b):
        np.testing.assert_array_equal(
            got[q], np.asarray(adc_lookup(codes[q], qlut[q], block=32,
                                          interpret=True)))
        np.testing.assert_array_equal(want[q], np.asarray(
            adc_lookup_ref(codes[q], qlut[q])))


def test_adc_sym_consistent_with_core_pq():
    """Kernel output must equal the core library's symmetric distance."""
    from repro.core.pq import cdist_sym
    rng = np.random.default_rng(11)
    M, K = 4, 16
    lut = np.abs(rng.standard_normal((M, K, K))).astype(np.float32)
    for m in range(M):
        np.fill_diagonal(lut[m], 0.0)
    codes = rng.integers(0, K, (12, M)).astype(np.int32)
    got = np.asarray(adc_sym_cdist(codes, codes, lut, interpret=True))
    want = np.asarray(cdist_sym(jnp.asarray(codes), jnp.asarray(codes),
                                jnp.asarray(lut)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# pq_attn
# ---------------------------------------------------------------------------

def _attn_setup(S, G, H, M, K, Ds, seed=0):
    rng = np.random.default_rng(seed)
    D = M * Ds
    q = rng.standard_normal((H, D)).astype(np.float32)
    k_books = rng.standard_normal((G, M, K, Ds)).astype(np.float32)
    k_codes = rng.integers(0, K, (S, G, M)).astype(np.int32)
    v = rng.standard_normal((S, G, D)).astype(np.float32)
    return q, k_codes, k_books, v


@pytest.mark.parametrize("S,G,H,M,K,Ds",
                         [(16, 1, 1, 2, 4, 4),
                          (64, 2, 4, 4, 16, 8),
                          (100, 2, 8, 2, 32, 16),
                          (256, 4, 8, 8, 64, 8)])
def test_pq_attn_matches_ref(S, G, H, M, K, Ds):
    q, k_codes, k_books, v = _attn_setup(S, G, H, M, K, Ds, seed=S)
    got = np.asarray(pq_attn_decode(q, k_codes, k_books, v, block_s=32,
                                    interpret=True))
    want = np.asarray(pq_attn_decode_ref(q, k_codes, k_books, v))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_pq_attn_valid_len_masking():
    q, k_codes, k_books, v = _attn_setup(64, 2, 4, 4, 16, 8, seed=1)
    got = np.asarray(pq_attn_decode(q, k_codes, k_books, v, valid_len=40,
                                    block_s=16, interpret=True))
    want = np.asarray(pq_attn_decode_ref(q, k_codes, k_books, v,
                                         valid_len=40))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # masked tail must actually change the answer vs full length
    full = np.asarray(pq_attn_decode_ref(q, k_codes, k_books, v))
    assert not np.allclose(want, full, atol=1e-4)


def test_pq_attn_exact_when_codes_reconstruct_exactly():
    """If every key IS a codeword, PQ attention == exact attention."""
    rng = np.random.default_rng(5)
    S, G, H, M, K, Ds = 32, 1, 2, 2, 8, 8
    D = M * Ds
    k_books = rng.standard_normal((G, M, K, Ds)).astype(np.float32)
    k_codes = rng.integers(0, K, (S, G, M)).astype(np.int32)
    keys = np.asarray(reconstruct_keys(jnp.asarray(k_codes),
                                       jnp.asarray(k_books)))  # (S, G, D)
    q = rng.standard_normal((H, D)).astype(np.float32)
    v = rng.standard_normal((S, G, D)).astype(np.float32)
    # exact attention with the reconstructed keys
    scores = np.einsum("hd,sd->hs", q, keys[:, 0]) / np.sqrt(D)
    p = np.exp(scores - scores.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    want = p @ v[:, 0]
    got = np.asarray(pq_attn_decode(q, k_codes, k_books, v, block_s=8,
                                    interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_encode_keys_roundtrip():
    """encode_keys must pick the true nearest codeword."""
    rng = np.random.default_rng(7)
    G, M, K, Ds, S = 2, 3, 16, 4, 20
    k_books = rng.standard_normal((G, M, K, Ds)).astype(np.float32)
    codes = rng.integers(0, K, (S, G, M)).astype(np.int32)
    keys = np.asarray(reconstruct_keys(jnp.asarray(codes),
                                       jnp.asarray(k_books)))
    got = np.asarray(encode_keys(jnp.asarray(keys).reshape(S, G, M * Ds),
                                 jnp.asarray(k_books)))
    assert (got == codes).all()


# ---------------------------------------------------------------------------
# prealign_encode (fused MODWT prealign + DTW-1NN encode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,L,M,K,level,tail", [(7, 32, 4, 5, 2, 2),
                                                (12, 64, 4, 8, 3, 3),
                                                (3, 48, 3, 6, 1, 0),
                                                (5, 40, 2, 4, 3, 5),
                                                (1, 24, 4, 3, 2, 1)])
@pytest.mark.parametrize("window", [None, 2])
def test_prealign_encode_fused_matches_ref(n, L, M, K, level, tail, window):
    """Fused kernel codes == modwt.prealign + exact DTW-1NN reference."""
    rng = np.random.default_rng(n * 101 + L + (0 if window is None else window))
    S = L // M + tail
    X = rng.standard_normal((n, L)).astype(np.float32)
    C = rng.standard_normal((M, K, S)).astype(np.float32)
    got = np.asarray(prealign_encode(X, C, level, tail, window, block=4,
                                     interpret=True))
    want = np.asarray(prealign_encode_ref(X, C, level, tail, window))
    np.testing.assert_array_equal(got, want)


def test_prealign_encode_matches_two_step_library_path():
    """Fused kernel == modwt.prealign + pq.encode (exact) on trained
    centroids, and the geometry check rejects mismatched codebooks."""
    import jax as _jax
    from repro.core import pq as pqm
    from repro.core.modwt import prealign as modwt_prealign
    from repro.core.pq import PQConfig
    rng = np.random.default_rng(0)
    X = rng.standard_normal((10, 48)).astype(np.float32)
    cfg = PQConfig(n_sub=4, codebook_size=4, use_prealign=True,
                   wavelet_level=2, tail_frac=0.25, kmeans_iters=2,
                   dba_iters=1, exact_encode=True, fused_encode=False)
    cb = pqm.fit(_jax.random.PRNGKey(0), X, cfg)
    two_step = np.asarray(pqm.encode(X, cb, cfg))       # prealign + encode
    tail, w = cfg.tail(48), cfg.window(48)
    fused = np.asarray(prealign_encode(X, cb.centroids, cfg.wavelet_level,
                                       tail, w, interpret=True))
    np.testing.assert_array_equal(fused, two_step)
    # sanity: the segments the kernel never materializes match modwt
    segs = np.asarray(modwt_prealign(X, cfg.n_sub, cfg.wavelet_level, tail))
    assert segs.shape == (10, 4, cb.subseq_len)
    with pytest.raises(ValueError, match="geometry"):
        prealign_encode(X, cb.centroids, cfg.wavelet_level, tail + 1, w,
                        interpret=True)


# ---------------------------------------------------------------------------
# lb_cascade (fused LB filter + conditional banded-DTW refine)
# ---------------------------------------------------------------------------

from repro.core.lb import keogh_envelope
from repro.kernels.lb_cascade.ops import lb_refine as lb_refine_kernel
from repro.kernels.lb_cascade.ref import cascade_bound_ref, lb_refine_ref


def _lb_setup(n, L, window, seed):
    rng = np.random.default_rng(seed)
    A = np.cumsum(rng.standard_normal((n, L)), 1).astype(np.float32)
    B = np.cumsum(rng.standard_normal((n, L)), 1).astype(np.float32)
    w_env = L - 1 if window is None else min(window, L - 1)
    up, lo = keogh_envelope(A, w_env)
    return A, B, np.asarray(up), np.asarray(lo)


@pytest.mark.parametrize("n,L", [(1, 8), (7, 16), (13, 32), (32, 24)])
@pytest.mark.parametrize("window", [None, 2, 5])
def test_lb_cascade_matches_ref(n, L, window):
    """Mixed thresholds: some tiles refine, some are fully pruned.  The
    threshold sits halfway between the two middle bounds, so no pair's
    bound equals it and an ulp of summation-order difference between the
    kernel's bound and the reference's cannot flip a comparison."""
    A, B, up, lo = _lb_setup(n, L, window, n * 37 + L)
    lb = np.sort(np.asarray(cascade_bound_ref(A, B, up, lo)))
    k = n // 2
    if n > 1:
        assert lb[k - 1] < lb[k]
    mid = (lb[k - 1] + lb[k]) / 2 if n > 1 else lb[0] + 1.0
    thresh = np.full(n, mid, np.float32)
    got_d, got_f = lb_refine_kernel(A, B, up, lo, thresh, window, block=4,
                                    interpret=True)
    want_d, want_f = lb_refine_ref(A, B, up, lo, thresh, window)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-5, atol=1e-5)


def test_lb_cascade_threshold_extremes():
    """+inf threshold refines everything (== exact banded DTW); -inf
    refines nothing (returns the cascade bound)."""
    A, B, up, lo = _lb_setup(9, 20, 3, 5)
    inf = np.full(9, np.inf, np.float32)
    d, f = lb_refine_kernel(A, B, up, lo, inf, 3, interpret=True)
    np.testing.assert_allclose(np.asarray(d),
                               np.asarray(dtw_band_ref(A, B, 3)),
                               rtol=1e-5, atol=1e-5)
    assert np.asarray(f).all()
    d, f = lb_refine_kernel(A, B, up, lo, -inf, 3, interpret=True)
    np.testing.assert_allclose(np.asarray(d),
                               np.asarray(cascade_bound_ref(A, B, up, lo)),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(f).any()


def test_lb_cascade_odd_batch_padding():
    """Pair count not divisible by block round-trips through padding (the
    padded rows run with a -inf threshold and are sliced off)."""
    A, B, up, lo = _lb_setup(7, 12, 2, 11)
    thresh = np.full(7, np.inf, np.float32)
    d, f = lb_refine_kernel(A, B, up, lo, thresh, 2, block=8, interpret=True)
    assert d.shape == (7,) and f.shape == (7,)
    np.testing.assert_allclose(np.asarray(d),
                               np.asarray(dtw_band_ref(A, B, 2)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

from repro.core import dispatch


@pytest.fixture
def fresh_dispatch():
    """Clear jit caches + counters so routing is observable at trace time."""
    jax.clear_caches()
    dispatch.reset_stats()
    yield dispatch
    dispatch.set_backend(None)


def _route_count(op, route="pallas_interpret"):
    return dispatch.stats.get((op, route), 0)


def test_dispatch_backend_selection(fresh_dispatch):
    with dispatch.use_backend("jax"):
        assert dispatch.get_backend() == "jax"
        with dispatch.use_backend("pallas_interpret"):
            assert dispatch.get_backend() == "pallas_interpret"
        assert dispatch.get_backend() == "jax"
    with pytest.raises(ValueError):
        dispatch.set_backend("cuda")


@pytest.mark.parametrize("n,L,window", [(3, 8, None), (7, 16, 2),
                                        (8, 24, 30), (13, 32, 3)])
def test_dispatch_pairwise_backends_agree(fresh_dispatch, n, L, window):
    rng = np.random.default_rng(n * 17 + L)
    A = rng.standard_normal((n, L)).astype(np.float32)
    B = rng.standard_normal((n, L)).astype(np.float32)
    with dispatch.use_backend("jax"):
        want = np.asarray(dispatch.elastic_pairwise(A, B, window))
    with dispatch.use_backend("pallas_interpret"):
        got = np.asarray(dispatch.elastic_pairwise(A, B, window))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n,m,L,window", [(4, 6, 12, None), (9, 5, 16, 2),
                                          (6, 6, 20, 40), (1, 256, 18, 2),
                                          (35, 130, 64, 0),
                                          (130, 3, 18, None)])
def test_dispatch_cdist_backends_agree(fresh_dispatch, n, m, L, window):
    rng = np.random.default_rng(n * 13 + m)
    A = rng.standard_normal((n, L)).astype(np.float32)
    B = rng.standard_normal((m, L)).astype(np.float32)
    with dispatch.use_backend("jax"):
        want = np.asarray(dispatch.elastic_cdist(A, B, window))
    with dispatch.use_backend("pallas_interpret"):
        got = np.asarray(dispatch.elastic_cdist(A, B, window))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_dispatch_adc_backends_agree(fresh_dispatch):
    rng = np.random.default_rng(2)
    M, K = 3, 16
    lut = np.abs(rng.standard_normal((M, K, K))).astype(np.float32)
    codes_a = rng.integers(0, K, (10, M)).astype(np.int32)
    codes_b = rng.integers(0, K, (7, M)).astype(np.int32)
    qlut = np.abs(rng.standard_normal((M, K))).astype(np.float32)
    with dispatch.use_backend("jax"):
        want_c = np.asarray(dispatch.adc_cdist(codes_a, codes_b, lut))
        want_l = np.asarray(dispatch.adc_lookup(codes_a, qlut))
    with dispatch.use_backend("pallas_interpret"):
        got_c = np.asarray(dispatch.adc_cdist(codes_a, codes_b, lut))
        got_l = np.asarray(dispatch.adc_lookup(codes_a, qlut))
    np.testing.assert_allclose(got_c, want_c, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-5, atol=1e-4)


def _toy_corpus(n=20, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


def _toy_cfg():
    from repro.core.pq import PQConfig
    return PQConfig(n_sub=2, codebook_size=4, kmeans_iters=2, dba_iters=1)


def test_encode_and_fit_route_through_dispatch(fresh_dispatch):
    """PQ training + encoding must execute on the Pallas route, and agree
    with the pure-JAX route to <= 1e-4."""
    from repro.core.pq import encode_with_stats, fit
    X = _toy_corpus()
    cfg = _toy_cfg()
    key = jax.random.PRNGKey(0)
    with dispatch.use_backend("jax"):
        cb = fit(key, X, cfg)
        codes_j, _ = encode_with_stats(X, cb, cfg)
    jax.clear_caches()
    dispatch.reset_stats()
    with dispatch.use_backend("pallas_interpret"):
        cb_p = fit(key, X, cfg)
        codes_p, _ = encode_with_stats(X, cb_p, cfg)
        assert _route_count("elastic_cdist") > 0       # k-means + LUT build
        assert _route_count("elastic_pairwise") > 0    # encode refinement
    np.testing.assert_allclose(np.asarray(cb_p.lut), np.asarray(cb.lut),
                               rtol=1e-5, atol=1e-4)
    assert (np.asarray(codes_p) == np.asarray(codes_j)).all()


def test_query_and_sym_route_through_dispatch(fresh_dispatch):
    from repro.core.pq import cdist_asym, cdist_sym, encode, fit
    X = _toy_corpus(seed=3)
    cfg = _toy_cfg()
    with dispatch.use_backend("jax"):
        cb = fit(jax.random.PRNGKey(1), X, cfg)
        codes = encode(X, cb, cfg)
        want_sym = np.asarray(cdist_sym(codes, codes, cb.lut))
        want_asym = np.asarray(cdist_asym(X[:3], codes, cb, cfg))
    jax.clear_caches()
    dispatch.reset_stats()
    with dispatch.use_backend("pallas_interpret"):
        got_sym = np.asarray(cdist_sym(codes, codes, cb.lut))
        got_asym = np.asarray(cdist_asym(X[:3], codes, cb, cfg))
        assert _route_count("adc_cdist") > 0           # MXU ADC kernel
        assert _route_count("elastic_cdist") > 0       # query LUT build
    np.testing.assert_allclose(got_sym, want_sym, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got_asym, want_asym, rtol=1e-5, atol=1e-4)


def test_ivf_search_routes_through_dispatch(fresh_dispatch):
    from repro.core import ivf
    X = _toy_corpus(n=24, seed=5)
    cfg = _toy_cfg()
    with dispatch.use_backend("jax"):
        index = ivf.build_index(jax.random.PRNGKey(2), X, cfg, n_lists=3)
        want_d, want_i = ivf.search_batch(index, X[:4], cfg, n_probe=2,
                                          topk=3)
    jax.clear_caches()
    dispatch.reset_stats()
    with dispatch.use_backend("pallas_interpret"):
        got_d, got_i = ivf.search_batch(index, X[:4], cfg, n_probe=2,
                                        topk=3)
        assert _route_count("elastic_cdist") > 0       # coarse + query LUTs
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-5, atol=1e-4)
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


def test_coarse_assign_and_query_luts_backends_agree(fresh_dispatch):
    """The all-pairs kernel's two serving callers: flush-time coarse
    assignment gives the same list ids, and the query LUTs the same
    tables, as the ``jax`` route."""
    from repro.core.ivf import coarse_assign
    from repro.core.pq import PQConfig, fit, query_lut_batch, segment
    rng = np.random.default_rng(21)
    X = np.cumsum(rng.standard_normal((40, 64)), 1).astype(np.float32)
    cfg = PQConfig(n_sub=4, codebook_size=8, kmeans_iters=1, dba_iters=1)
    with dispatch.use_backend("jax"):
        cb = fit(jax.random.PRNGKey(4), X, cfg)
        q_segs = segment(X[:5], cfg)
        want_ids = np.asarray(coarse_assign(X, X[::5], 6))
        want_lut = np.asarray(query_lut_batch(q_segs, cb, cfg.window(64),
                                              measure=cfg.measure()))
    jax.clear_caches()
    dispatch.reset_stats()
    with dispatch.use_backend("pallas_interpret"):
        got_ids = np.asarray(coarse_assign(X, X[::5], 6))
        got_lut = np.asarray(query_lut_batch(q_segs, cb, cfg.window(64),
                                             measure=cfg.measure()))
        assert _route_count("elastic_cdist") > 0
    assert (got_ids == want_ids).all()
    np.testing.assert_allclose(got_lut, want_lut, rtol=1e-5, atol=1e-5)


def test_knn_exact_routes_through_dispatch(fresh_dispatch):
    from repro.core.knn import nn_dtw_exact
    X = _toy_corpus(n=16, seed=7)
    Q = _toy_corpus(n=5, seed=8)
    labels = jnp.arange(16) % 3
    with dispatch.use_backend("jax"):
        want = np.asarray(nn_dtw_exact(X, labels, Q, window=3))
    jax.clear_caches()
    dispatch.reset_stats()
    with dispatch.use_backend("pallas_interpret"):
        got = np.asarray(nn_dtw_exact(X, labels, Q, window=3))
        assert _route_count("elastic_cdist") > 0
    assert (got == want).all()


def test_prealign_encode_backends_agree(fresh_dispatch):
    """dispatch.prealign_encode: identical codes on jax / pallas_interpret,
    and the routing counters record both routes."""
    rng = np.random.default_rng(21)
    L, M, K, level, tail, window = 40, 4, 6, 2, 2, 3
    X = rng.standard_normal((9, L)).astype(np.float32)
    C = rng.standard_normal((M, K, L // M + tail)).astype(np.float32)
    with dispatch.use_backend("jax"):
        want = np.asarray(dispatch.prealign_encode(
            X, C, level=level, tail=tail, window=window))
    with dispatch.use_backend("pallas_interpret"):
        got = np.asarray(dispatch.prealign_encode(
            X, C, level=level, tail=tail, window=window))
    np.testing.assert_array_equal(got, want)
    assert _route_count("prealign_encode", "jax") == 1
    assert _route_count("prealign_encode") == 1


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_fused_encode_routes_through_dispatch(fresh_dispatch, backend):
    """pq.encode with an exact prealigned config must take the fused
    prealign_encode dispatch route and agree with the two-step path."""
    import dataclasses
    from repro.core.pq import PQConfig, encode, fit, uses_fused_prealign
    X = _toy_corpus(n=14, d=32, seed=9)
    cfg = dataclasses.replace(_toy_cfg(), use_prealign=True,
                              wavelet_level=2, tail_frac=0.25,
                              exact_encode=True)
    assert uses_fused_prealign(cfg)
    with dispatch.use_backend(backend):
        jax.clear_caches()
        cb = fit(jax.random.PRNGKey(3), X, cfg)
        dispatch.reset_stats()
        fused = np.asarray(encode(X, cb, cfg))
        assert _route_count("prealign_encode", backend) == 1
        two_step = np.asarray(encode(
            X, cb, dataclasses.replace(cfg, fused_encode=False)))
    np.testing.assert_array_equal(fused, two_step)


def test_dispatch_lb_refine_backends_agree(fresh_dispatch):
    A, B, up, lo = _lb_setup(11, 16, 3, 2)
    lb = np.asarray(cascade_bound_ref(A, B, up, lo))
    thresh = np.full(11, float(np.median(lb)), np.float32)
    with dispatch.use_backend("jax"):
        want_d, want_f = dispatch.lb_refine(A, B, up, lo, thresh, 3)
    with dispatch.use_backend("pallas_interpret"):
        got_d, got_f = dispatch.lb_refine(A, B, up, lo, thresh, 3)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-5, atol=1e-4)
    assert _route_count("lb_refine", "jax") == 1
    assert _route_count("lb_refine") == 1


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_filtered_topk_routes_through_dispatch(fresh_dispatch, backend):
    """The batched filter-and-refine search must run its refines through
    dispatch.lb_refine and return the exact banded-DTW top-k."""
    from repro.core.lb_search import filtered_topk
    rng = np.random.default_rng(3)
    X = np.cumsum(rng.standard_normal((40, 24)), 1).astype(np.float32)
    Q = np.cumsum(rng.standard_normal((5, 24)), 1).astype(np.float32)
    with dispatch.use_backend(backend):
        jax.clear_caches()
        dispatch.reset_stats()
        d, idx, n_ref = filtered_topk(Q, X, 3, 2)
        assert _route_count("lb_refine", backend) > 0
        dense = np.asarray(dispatch.elastic_cdist(Q, X, 3))
    want = np.sort(dense, axis=1)[:, :2]
    np.testing.assert_allclose(np.asarray(d), want, rtol=1e-5, atol=1e-5)
    assert 0 < int(n_ref) <= Q.shape[0] * X.shape[0]


def test_dispatch_totals_survive_reset(fresh_dispatch):
    """`totals` is the process-lifetime ledger the CI routing gate reads:
    reset_stats() must not clear it."""
    rng = np.random.default_rng(1)
    A = rng.standard_normal((4, 8)).astype(np.float32)
    with dispatch.use_backend("pallas_interpret"):
        dispatch.elastic_pairwise(A, A, 2)
    before = dispatch.totals.get(("elastic_pairwise", "pallas_interpret"), 0)
    assert before > 0
    dispatch.reset_stats()
    assert not dispatch.stats
    assert dispatch.totals.get(("elastic_pairwise", "pallas_interpret"),
                               0) == before


def test_build_qlut_algebra():
    """qlut gathers must equal dot products with reconstructed keys."""
    rng = np.random.default_rng(9)
    G, R, M, K, Ds = 2, 3, 4, 8, 4
    H, D = G * R, M * Ds
    q = rng.standard_normal((H, D)).astype(np.float32)
    books = rng.standard_normal((G, M, K, Ds)).astype(np.float32)
    qlut = np.asarray(build_qlut(jnp.asarray(q), jnp.asarray(books)))
    codes = rng.integers(0, K, (5, G, M)).astype(np.int32)
    keys = np.asarray(reconstruct_keys(jnp.asarray(codes),
                                       jnp.asarray(books)))
    for s in range(5):
        for h in range(H):
            g = h // R
            want = float(q[h] @ keys[s, g])
            got = sum(qlut[h, m, codes[s, g, m]] for m in range(M))
            assert got == pytest.approx(want, rel=1e-4, abs=1e-4)
