"""IVF-PQDTW: recall vs exhaustive search, candidate-slot correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ivf import build_index, search, search_batch
from repro.core.pq import PQConfig, cdist_asym
from repro.data.timeseries import cbf


@pytest.fixture(scope="module")
def setup():
    X, y = cbf(n_per_class=20, length=64, seed=0)
    Q, _ = cbf(n_per_class=4, length=64, seed=9)
    cfg = PQConfig(n_sub=4, codebook_size=16, use_prealign=False,
                   kmeans_iters=3, dba_iters=1)
    index = build_index(jax.random.PRNGKey(0), jnp.asarray(X), cfg,
                        n_lists=6, coarse_iters=4)
    return X, Q, cfg, index


class TestIndexStructure:
    def test_lists_partition_the_database(self, setup):
        X, _, _, index = setup
        ids = np.sort(np.asarray(index.ids))
        np.testing.assert_array_equal(ids, np.arange(len(X)))
        assert int(index.list_len.sum()) == len(X)
        # starts consistent with lengths
        start = np.asarray(index.list_start)
        length = np.asarray(index.list_len)
        for i in range(1, len(start)):
            assert start[i] == start[i - 1] + length[i - 1]

    def test_full_probe_equals_exhaustive_pq(self, setup):
        """Probing every list must reproduce exhaustive asymmetric PQDTW."""
        X, Q, cfg, index = setup
        d_ex = np.asarray(cdist_asym(jnp.asarray(Q), index.codes, index.cb,
                                     cfg))
        ids_ex = np.asarray(index.ids)[d_ex.argmin(1)]
        d, ids = search_batch(index, jnp.asarray(Q), cfg,
                              n_probe=index.n_lists, topk=1)
        np.testing.assert_array_equal(np.asarray(ids)[:, 0], ids_ex)
        np.testing.assert_allclose(np.asarray(d)[:, 0], d_ex.min(1),
                                   rtol=1e-5, atol=1e-5)


class TestRecall:
    def test_recall_monotone_in_probes(self, setup):
        X, Q, cfg, index = setup
        d_ex = np.asarray(cdist_asym(jnp.asarray(Q), index.codes, index.cb,
                                     cfg))
        truth = np.asarray(index.ids)[d_ex.argmin(1)]
        recalls = []
        for p in (1, 3, index.n_lists):
            _, ids = search_batch(index, jnp.asarray(Q), cfg,
                                  n_probe=p, topk=1)
            recalls.append(float((np.asarray(ids)[:, 0] == truth).mean()))
        assert recalls[-1] == 1.0
        assert recalls[0] <= recalls[1] + 1e-9 <= recalls[2] + 2e-9
        assert recalls[1] >= 0.5      # CBF clusters are easy: few probes win

    def test_topk_sorted(self, setup):
        _, Q, cfg, index = setup
        d, ids = search(index, jnp.asarray(Q[0]), cfg, n_probe=3, topk=5)
        dd = np.asarray(d)
        assert (np.diff(dd) >= -1e-6).all()
        assert len(np.unique(np.asarray(ids))) == 5


class TestValidation:
    """Bad probe/topk budgets fail with a clear ValueError, not an XLA
    shape error deep inside top_k."""

    def test_n_probe_out_of_range_raises(self, setup):
        _, Q, cfg, index = setup
        for bad in (0, -1, index.n_lists + 1):
            with pytest.raises(ValueError, match="n_probe"):
                search_batch(index, jnp.asarray(Q), cfg, n_probe=bad)

    def test_topk_exceeds_candidate_budget_raises(self, setup):
        _, Q, cfg, index = setup
        cap = 1 * index.max_list
        with pytest.raises(ValueError, match="topk"):
            search(index, jnp.asarray(Q[0]), cfg, n_probe=1, topk=cap + 1)
        with pytest.raises(ValueError, match="topk"):
            search_batch(index, jnp.asarray(Q), cfg, n_probe=2, topk=0)


class TestCoarseWindow:
    """The band the lists were assigned with is stored on the index and is
    the search-time default (regression: search used to hardcode 0.1*D
    regardless of ``coarse_window_frac``)."""

    def test_coarse_window_stored(self, setup):
        X, _, cfg, index = setup
        D = X.shape[1]
        assert index.coarse_window == max(1, int(round(0.1 * D)))
        wide = build_index(jax.random.PRNGKey(3), jnp.asarray(X), cfg,
                           n_lists=4, coarse_iters=2,
                           coarse_window_frac=0.4)
        assert wide.coarse_window == max(1, int(round(0.4 * D)))

    def test_search_defaults_to_build_window(self, setup):
        X, Q, cfg, _ = setup
        index = build_index(jax.random.PRNGKey(3), jnp.asarray(X), cfg,
                            n_lists=4, coarse_iters=2,
                            coarse_window_frac=0.4)
        d0, i0 = search_batch(index, jnp.asarray(Q), cfg, n_probe=2, topk=3)
        d1, i1 = search_batch(index, jnp.asarray(Q), cfg, n_probe=2, topk=3,
                              coarse_window=index.coarse_window)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(d0), np.asarray(d1))


class TestLBPrefilter:
    """Cascaded lower-bound pre-filter ahead of the exact ADC gather."""

    def test_full_budget_identical(self, setup):
        X, Q, cfg, index = setup
        cap = 3 * index.max_list
        d0, i0 = search_batch(index, jnp.asarray(Q), cfg, n_probe=3, topk=4)
        d1, i1 = search_batch(index, jnp.asarray(Q), cfg, n_probe=3, topk=4,
                              lb_budget=cap)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(d0), np.asarray(d1))

    def test_lb_lut_lower_bounds_query_lut(self, setup):
        from repro.core.lb import lb_lut
        from repro.core.pq import query_lut_batch, segment
        X, Q, cfg, index = setup
        D = Q.shape[1]
        q_segs = segment(jnp.asarray(Q), cfg)
        qluts = np.asarray(query_lut_batch(q_segs, index.cb, cfg.window(D),
                                           cfg.metric != "dtw"))
        lbs = np.asarray(lb_lut(q_segs, index.cb.centroids,
                                index.cb.env_upper, index.cb.env_lower))
        assert (lbs <= qluts + 1e-4).all()

    def test_small_budget_still_returns_topk(self, setup):
        X, Q, cfg, index = setup
        d, ids = search_batch(index, jnp.asarray(Q), cfg, n_probe=3, topk=2,
                              lb_budget=8)
        dd = np.asarray(d)
        assert (np.diff(dd, axis=1) >= -1e-6).all()
        assert (np.asarray(ids) >= 0).all()

    def test_budget_validation(self, setup):
        X, Q, cfg, index = setup
        cap = 2 * index.max_list
        with pytest.raises(ValueError, match="lb_budget"):
            search_batch(index, jnp.asarray(Q), cfg, n_probe=2, topk=3,
                         lb_budget=2)
        with pytest.raises(ValueError, match="lb_budget"):
            search_batch(index, jnp.asarray(Q), cfg, n_probe=2, topk=3,
                         lb_budget=cap + 1)


class TestPretrainedQuantizers:
    def test_build_index_with_shared_quantizers_matches(self, setup):
        """Re-building from the trained coarse/cb must reproduce the same
        inverted-list layout (the streaming-index equivalence path)."""
        X, _, cfg, index = setup
        rebuilt = build_index(jax.random.PRNGKey(42), jnp.asarray(X), cfg,
                              n_lists=index.n_lists, coarse=index.coarse,
                              cb=index.cb)
        np.testing.assert_array_equal(np.asarray(rebuilt.codes),
                                      np.asarray(index.codes))
        np.testing.assert_array_equal(np.asarray(rebuilt.ids),
                                      np.asarray(index.ids))
        np.testing.assert_array_equal(np.asarray(rebuilt.list_len),
                                      np.asarray(index.list_len))
        assert rebuilt.max_list == index.max_list

    def test_build_index_coarse_shape_mismatch_raises(self, setup):
        X, _, cfg, index = setup
        with pytest.raises(ValueError, match="centroids"):
            build_index(jax.random.PRNGKey(0), jnp.asarray(X), cfg,
                        n_lists=index.n_lists + 1, coarse=index.coarse)


# ---------------------------------------------------------------------------
# fine stage: the one-hot ADC kernel against the plain routes
# ---------------------------------------------------------------------------

def _gather_fine_rank(codes, ids, start, length, max_list, dc, qlut, n_probe,
                      topk, live=None, lb_qlut=None, lb_budget=None):
    """The fine stage written out with the per-(slot, subspace) gather
    formula ``sqrt(sum_m qlut[m, codes[slot, m]])`` over the padded slots,
    in the order :func:`fine_rank` ranks them."""
    probes = np.argsort(dc, kind="stable")[:n_probe]
    slots = (start[probes][:, None] + np.arange(max_list)[None, :]).ravel()
    valid = (np.arange(max_list)[None, :] < length[probes][:, None]).ravel()
    valid &= np.repeat(np.isfinite(dc[probes]), max_list)
    slots = np.where(valid, slots, 0)
    if live is not None:
        valid &= live[slots]

    def adc(table):
        d2 = table[np.arange(table.shape[0])[None, :], codes[slots]].sum(-1)
        return np.where(valid, np.sqrt(np.maximum(d2, 0.0)), np.inf)

    keep = np.arange(len(slots))
    if lb_budget is not None and lb_budget < len(slots):
        keep = np.argsort(adc(lb_qlut), kind="stable")[:lb_budget]
    d = adc(qlut)[keep]
    best = np.argsort(d, kind="stable")[:topk]
    out_d = d[best]
    return out_d, np.where(np.isfinite(out_d), ids[slots[keep][best]], -1)


# (rows, n_lists, max_list over the longest list, n_probe, topk, options)
FINE_CASES = {
    "padded": (300, 6, 0, 3, 10, {}),
    "tombstones": (300, 6, 0, 3, 10, {"dead": 0.4}),
    "lists_shorter_than_topk": (20, 8, 8, 2, 10, {}),
    "two_level_inf": (300, 6, 0, 5, 10, {"finite": 2}),
    "lb_budget": (300, 6, 0, 3, 10, {"lb_budget": 40}),
    "flush_width": (120, 6, 256, 3, 10, {"dead": 0.2}),
}


@pytest.mark.parametrize("case", sorted(FINE_CASES))
def test_fine_rank_kernel_matches_jax_route_and_gather(case):
    """The fine stage scores the batch's padded candidate blocks with the
    one-hot ADC kernel (``dispatch.adc_lookup``); ids equal the plain ``jax``
    route's and the gather formula's, distances agree to 1e-6 relative.
    ``flush_width`` pads every list to a flush-born segment's hot capacity
    rather than to its longest list."""
    from repro.core import dispatch
    from repro.core.ivf import build_lists, fine_rank

    n, n_lists, width, n_probe, topk, opt = FINE_CASES[case]
    M, K, Nq = 8, 256, 3
    rng = np.random.default_rng(sorted(FINE_CASES).index(case))
    assign = rng.integers(0, n_lists, n)
    order, start, length, max_list = build_lists(assign, n_lists)
    max_list = max(max_list, width)
    codes = rng.integers(0, K, (n, M)).astype(np.int32)
    ids = order.astype(np.int32)
    live = rng.random(n) >= opt.get("dead", 0.0) if "dead" in opt else None
    dc = rng.random((Nq, n_lists)).astype(np.float32)
    if "finite" in opt:   # a two-level coarse stage: unprobed lists at +inf
        for q in range(Nq):
            dc[q, rng.permutation(n_lists)[opt["finite"]:]] = np.inf
    qlut = rng.random((Nq, M, K)).astype(np.float32)
    lb_qlut = qlut * rng.random((Nq, M, K)).astype(np.float32)
    budget = opt.get("lb_budget")

    def run(backend):
        with dispatch.use_backend(backend):
            before = dispatch.totals.get(("adc_lookup", backend), 0)
            d, i = fine_rank(
                jnp.asarray(codes), jnp.asarray(ids), jnp.asarray(start),
                jnp.asarray(length), max_list, jnp.asarray(dc),
                jnp.asarray(qlut), n_probe, topk,
                live=None if live is None else jnp.asarray(live),
                lb_qlut=jnp.asarray(lb_qlut) if budget else None,
                lb_budget=budget)
            # one launch per scoring sum for the whole batch
            assert dispatch.totals.get(("adc_lookup", backend), 0) \
                == before + (2 if budget else 1)
        return np.asarray(d), np.asarray(i)

    d_k, i_k = run("pallas_interpret")
    d_j, i_j = run("jax")
    for q in range(Nq):
        d_g, i_g = _gather_fine_rank(
            codes, ids, start, length, max_list, dc[q].astype(np.float64),
            qlut[q].astype(np.float64), n_probe, topk, live=live,
            lb_qlut=lb_qlut[q].astype(np.float64), lb_budget=budget)
        np.testing.assert_array_equal(i_k[q], i_g)
        np.testing.assert_allclose(d_k[q], d_g, rtol=1e-6)
    np.testing.assert_array_equal(i_k, i_j)
    np.testing.assert_allclose(d_k, d_j, rtol=1e-6)
    if case == "lists_shorter_than_topk":
        assert (i_k == -1).any() and np.isinf(d_k[i_k == -1]).all()
    if live is not None:   # no deleted row is ever returned
        assert not np.isin(i_k, ids[~live]).any()
