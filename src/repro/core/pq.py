"""PQDTW — the paper's product quantizer for time series under DTW.

Training (Alg. 1): segment -> per-subspace DBA k-means -> pre-compute the
M x K x K symmetric DTW LUT and the Keogh envelope of every centroid.

Encoding (Alg. 2): per subspace, DTW-1NN against the K centroids.  The
paper's cascading-lower-bound early abandoning is replaced by its TPU-native
equivalent: a vectorized LB filter (max(LB_Kim, reversed LB_Keogh) for all K
at once) followed by exact banded DTW on the top-T most promising centroids
(static T -> static shapes).  ``exact=True`` disables the filter.

Distances (§3.3): symmetric = M LUT gathers + sum; asymmetric = one fresh
M x K DTW table per query, then gathers.  §4.2's clustering refinement
replaces the 0 distance of identical codes by the Keogh lower bound.

Every exact-DTW evaluation and the symmetric code-distance matrix route
through :mod:`repro.core.dispatch`, so the Pallas kernels are the default
execution engine on TPU (pure-JAX wavefront elsewhere).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import measures as measures_mod
from .dtw import euclidean_sq
from .dispatch import (adc_cdist, elastic_cdist, elastic_pairwise,
                       prealign_encode)
from .lb import keogh_envelope, lb_keogh, lb_kim
from .kmeans import dba_kmeans, euclidean_kmeans
from .measures import MeasureSpec
from .modwt import prealign, fixed_segments

__all__ = ["PQConfig", "PQCodebook", "segment", "fit", "encode",
           "encode_with_stats", "query_lut", "query_lut_batch", "cdist_sym",
           "cdist_asym", "cdist_sym_refined", "memory_cost",
           "uses_fused_prealign"]


@dataclasses.dataclass(frozen=True)
class PQConfig:
    """Hyper-parameters of the product quantizer (paper §3 + §5).

    ``metric`` selects the subspace distance: any registered elastic
    measure name ("dtw", "wdtw", "erp", "msm", ...) or "euclidean" (the
    PQ_ED baseline).  ``measure_params`` carries the measure's static
    hyper-parameters (e.g. ``{"g": 1.0}`` for erp) — normalized to a
    sorted tuple of pairs so the config stays hashable and JSON-safe.

    >>> cfg = PQConfig(n_sub=2, codebook_size=4, use_prealign=False)
    >>> cfg.is_elastic
    True
    >>> cfg.subseq_len(8), cfg.tail(8), cfg.window(8)
    (4, 1, 1)
    """
    n_sub: int = 8              # M: number of subspaces
    codebook_size: int = 256    # K
    window_frac: float = 0.1    # Sakoe-Chiba band, fraction of subseq length
    metric: str = "dtw"         # elastic measure name or "euclidean"
    measure_params: Tuple[Tuple[str, float], ...] = ()
    use_prealign: bool = True   # MODWT pre-alignment (§3.5)
    wavelet_level: int = 3      # J
    tail_frac: float = 0.15     # t, fraction of D/M
    snap_tail: Optional[int] = None  # explicit t in samples (overrides
                                     # tail_frac; 0 = fixed splits)
    kmeans_iters: int = 8
    dba_iters: int = 2
    refine_frac: float = 0.125  # T/K for filter-then-refine encoding
    exact_encode: bool = False  # disable the LB filter
    fused_encode: bool = True   # exact prealigned encodes take the fused
                                # MODWT+encode dispatch path (one kernel)

    def __post_init__(self):
        params = tuple(sorted((str(k), float(v)) for k, v in
                              dict(self.measure_params or ()).items()))
        object.__setattr__(self, "measure_params", params)
        if self.metric != "euclidean":
            measures_mod.get_measure(self.metric, **dict(params))  # validate

    @property
    def is_elastic(self) -> bool:
        return self.metric != "euclidean"

    def measure(self) -> Optional[MeasureSpec]:
        """The elastic measure spec, or None under the euclidean baseline."""
        if not self.is_elastic:
            return None
        return measures_mod.get_measure(self.metric,
                                        **dict(self.measure_params))

    def subseq_len(self, D: int) -> int:
        base = D // self.n_sub
        return base + self.tail(D) if (self.use_prealign and self.is_elastic) else base

    def tail(self, D: int) -> int:
        if self.snap_tail is not None:
            return int(self.snap_tail)
        return max(1, int(round(self.tail_frac * (D // self.n_sub))))

    def window(self, D: int) -> Optional[int]:
        if not self.is_elastic:
            return None
        return max(1, int(round(self.window_frac * self.subseq_len(D))))

    def refine_t(self) -> int:
        return max(1, int(round(self.refine_frac * self.codebook_size)))

    def full_scan_encode(self) -> bool:
        """True when encoding is an exact full scan of every centroid:
        explicitly requested, a refine budget covering the whole codebook,
        or a measure without a sound LB cascade (the filter-then-refine
        shortcut would prune incorrectly, so it is capability-gated off).
        """
        if self.exact_encode or self.refine_t() >= self.codebook_size:
            return True
        spec = self.measure()
        return spec is not None and not spec.has_keogh_lb


class PQCodebook(NamedTuple):
    """Trained quantizer state (a pytree — jit/shard friendly).

    >>> import jax.numpy as jnp
    >>> cb = PQCodebook(jnp.zeros((2, 4, 5)), jnp.zeros((2, 4, 4)),
    ...                 jnp.zeros((2, 4, 5)), jnp.zeros((2, 4, 5)))
    >>> cb.n_sub, cb.codebook_size, cb.subseq_len
    (2, 4, 5)
    """
    centroids: jnp.ndarray   # (M, K, S) float32
    lut: jnp.ndarray         # (M, K, K) squared elastic distance
    env_upper: jnp.ndarray   # (M, K, S)
    env_lower: jnp.ndarray   # (M, K, S)

    @property
    def n_sub(self) -> int:
        return self.centroids.shape[0]

    @property
    def codebook_size(self) -> int:
        return self.centroids.shape[1]

    @property
    def subseq_len(self) -> int:
        return self.centroids.shape[2]


# ---------------------------------------------------------------------------
# Segmentation
# ---------------------------------------------------------------------------

def segment(X: jnp.ndarray, cfg: PQConfig) -> jnp.ndarray:
    """``X (N, D)`` -> ``(N, M, S)`` subsequences (pre-aligned or fixed).

    >>> import jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, use_prealign=False)
    >>> segment(jnp.zeros((3, 8)), cfg).shape
    (3, 2, 4)
    """
    D = X.shape[-1]
    if cfg.use_prealign and cfg.is_elastic:
        return prealign(X, cfg.n_sub, cfg.wavelet_level, cfg.tail(D))
    return fixed_segments(X, cfg.n_sub)


# ---------------------------------------------------------------------------
# Training (Algorithm 1)
# ---------------------------------------------------------------------------

def fit(key: jax.Array, X: jnp.ndarray, cfg: PQConfig) -> PQCodebook:
    """Learn the codebook, LUT and envelopes from training series ``X (N, D)``.

    >>> import jax, jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, codebook_size=2, use_prealign=False,
    ...                kmeans_iters=1, dba_iters=1)
    >>> X = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 10.0
    >>> cb = fit(jax.random.PRNGKey(0), X, cfg)
    >>> cb.centroids.shape, cb.lut.shape
    ((2, 2, 4), (2, 2, 2))
    """
    X = jnp.asarray(X, jnp.float32)
    D = X.shape[-1]
    segs = segment(X, cfg)                       # (N, M, S)
    window = cfg.window(D)
    keys = jax.random.split(key, cfg.n_sub)

    spec = cfg.measure()
    cents, luts, uppers, lowers = [], [], [], []
    for m in range(cfg.n_sub):
        sub = segs[:, m, :]
        if cfg.is_elastic:
            res = dba_kmeans(keys[m], sub, cfg.codebook_size,
                             iters=cfg.kmeans_iters, dba_iters=cfg.dba_iters,
                             window=window, measure=spec)
            lut = elastic_cdist(res.centroids, res.centroids, window,
                                measure=spec)
        else:
            res = euclidean_kmeans(keys[m], sub, cfg.codebook_size,
                                   iters=cfg.kmeans_iters)
            lut = euclidean_sq(res.centroids, res.centroids)
        up, lo = keogh_envelope(res.centroids, window or 1)
        cents.append(res.centroids)
        luts.append(lut)
        uppers.append(up)
        lowers.append(lo)

    return PQCodebook(jnp.stack(cents), jnp.stack(luts),
                      jnp.stack(uppers), jnp.stack(lowers))


# ---------------------------------------------------------------------------
# Encoding (Algorithm 2) — vectorized filter-then-refine
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "refine_t",
                                             "full_scan", "measure"))
def _encode_segs(segs: jnp.ndarray, cb: PQCodebook, window: Optional[int],
                 refine_t: int, full_scan: bool,
                 measure: Optional[MeasureSpec]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``segs (N, M, S)`` -> codes ``(N, M)`` int32 + soundness flags.

    ``measure=None`` selects the euclidean baseline.  All exact elastic
    refinements across the whole (series x subspace x
    candidate) set are flattened into ONE zipped-pair batch through the
    dispatch layer, so the Pallas wavefront kernel sees a single large
    launch instead of N*M tiny ones.  The LB filter-then-refine shortcut
    only runs for measures with a sound Keogh cascade; ``full_scan`` (see
    ``PQConfig.full_scan_encode``) covers the rest.
    """
    N, M, S = segs.shape
    K = cb.codebook_size

    if measure is None:
        d = jnp.sum((segs[:, :, None, :] - cb.centroids[None]) ** 2, -1)
        return jnp.argmin(d, -1).astype(jnp.int32), jnp.ones((N, M), bool)

    if full_scan:
        # Full scan: per-subspace all-pairs launches — the cdist kernel
        # broadcasts centroids per tile, so nothing of size N*K*S is ever
        # materialized.
        d = jnp.stack([elastic_cdist(segs[:, m], cb.centroids[m], window,
                                     measure=measure)
                       for m in range(M)], axis=1)           # (N, M, K)
        return jnp.argmin(d, -1).astype(jnp.int32), jnp.ones((N, M), bool)

    lbs = jnp.maximum(
        lb_kim(segs[:, :, None, :], cb.centroids[None]),
        lb_keogh(segs[:, :, None, :], cb.env_upper[None],
                 cb.env_lower[None]))                        # (N, M, K)
    _, cand = jax.lax.top_k(-lbs, refine_t)                  # T most promising
    T = refine_t

    qs = jnp.broadcast_to(segs[:, :, None, :], (N, M, T, S))
    cs = cb.centroids[jnp.arange(M)[None, :, None], cand]    # (N, M, T, S)
    d = elastic_pairwise(qs.reshape(-1, S), cs.reshape(-1, S),
                         window, measure=measure).reshape(N, M, T)
    best = jnp.argmin(d, -1)                                 # (N, M)
    codes = jnp.take_along_axis(
        cand, best[..., None], -1)[..., 0].astype(jnp.int32)
    # Soundness certificate: the true NN is inside the candidate set iff
    # best refined distance <= every excluded centroid's lower bound; the
    # excluded minimum is simply the (T+1)-th smallest bound.
    best_d = jnp.take_along_axis(d, best[..., None], -1)[..., 0]
    neg, _ = jax.lax.top_k(-lbs, refine_t + 1)
    return codes, best_d <= -neg[..., -1]


def uses_fused_prealign(cfg: PQConfig) -> bool:
    """True when :func:`encode` takes the fused prealign+encode dispatch
    path: an elastic metric, pre-alignment on, and an exact (full-scan)
    encode — the LB filter-then-refine route still needs materialized
    segments and envelopes, so it stays on the two-step.

    >>> uses_fused_prealign(PQConfig())            # LB filter: two-step
    False
    >>> uses_fused_prealign(PQConfig(exact_encode=True))
    True
    """
    return (cfg.fused_encode and cfg.use_prealign and cfg.is_elastic
            and cfg.full_scan_encode())


def encode(X: jnp.ndarray, cb: PQCodebook, cfg: PQConfig) -> jnp.ndarray:
    """Encode raw series ``X (N, D)`` to PQ codes ``(N, M)``.

    >>> import jax, jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, codebook_size=2, use_prealign=False,
    ...                kmeans_iters=1, dba_iters=1)
    >>> X = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 10.0
    >>> cb = fit(jax.random.PRNGKey(0), X, cfg)
    >>> codes = encode(X, cb, cfg)
    >>> codes.shape, str(codes.dtype)
    ((4, 2), 'int32')
    """
    codes, _ = encode_with_stats(X, cb, cfg)
    return codes


def encode_with_stats(X: jnp.ndarray, cb: PQCodebook, cfg: PQConfig
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Encode + per-code soundness flags (True = certified exact-NN code).

    >>> import jax, jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, codebook_size=2, use_prealign=False,
    ...                kmeans_iters=1, dba_iters=1)
    >>> X = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 10.0
    >>> cb = fit(jax.random.PRNGKey(0), X, cfg)
    >>> codes, sound = encode_with_stats(X, cb, cfg)
    >>> sound.shape, str(sound.dtype)
    ((4, 2), 'bool')
    """
    X = jnp.asarray(X, jnp.float32)
    D = X.shape[-1]
    if uses_fused_prealign(cfg):
        codes = prealign_encode(X, cb.centroids, level=cfg.wavelet_level,
                                tail=cfg.tail(D), window=cfg.window(D),
                                measure=cfg.measure())
        return codes, jnp.ones(codes.shape, bool)   # full scan: always exact
    segs = segment(X, cfg)
    return _encode_segs(segs, cb, cfg.window(D), cfg.refine_t(),
                        cfg.full_scan_encode(), cfg.measure())


# ---------------------------------------------------------------------------
# Distances (§3.3)
# ---------------------------------------------------------------------------

def cdist_sym(codes_a: jnp.ndarray, codes_b: jnp.ndarray,
              lut: jnp.ndarray, *, lut_dtype: str = "float32") -> jnp.ndarray:
    """Symmetric PQ distance matrix: ``(Na, M) x (Nb, M) -> (Na, Nb)``.

    Routed through the dispatch layer: one-hot MXU contractions on the
    Pallas ADC kernel, plain LUT gathers on the pure-JAX route; sqrt of the
    summed squared subspace costs either way.  ``lut_dtype`` selects the
    resident-table precision (``"float32"`` exact, ``"int8"`` /
    ``"bfloat16"`` quantized — see :func:`repro.core.dispatch.adc_cdist`).

    >>> import jax.numpy as jnp
    >>> codes = jnp.array([[0, 1], [1, 0]], jnp.int32)
    >>> lut = jnp.stack([1.0 - jnp.eye(2)] * 2)    # (M=2, K=2, K=2)
    >>> [round(float(x), 3) for x in cdist_sym(codes, codes, lut).ravel()]
    [0.0, 1.414, 1.414, 0.0]
    """
    return adc_cdist(codes_a, codes_b, lut, lut_dtype=lut_dtype)


@functools.partial(jax.jit, static_argnames=("window", "euclidean",
                                             "measure"))
def query_lut(q_segs: jnp.ndarray, cb: PQCodebook, window: Optional[int],
              euclidean: bool = False,
              measure: Optional[MeasureSpec] = None) -> jnp.ndarray:
    """Asymmetric query table: ``q_segs (M, S)`` -> ``(M, K)`` subspace
    distances under the configured measure.

    >>> import jax, jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, codebook_size=2, use_prealign=False,
    ...                kmeans_iters=1, dba_iters=1)
    >>> X = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 10.0
    >>> cb = fit(jax.random.PRNGKey(0), X, cfg)
    >>> q_segs = segment(X, cfg)[0]                # one query's segments
    >>> query_lut(q_segs, cb, cfg.window(8), measure=cfg.measure()).shape
    (2, 2)
    """
    return query_lut_batch(q_segs[None], cb, window, euclidean, measure)[0]


@functools.partial(jax.jit, static_argnames=("window", "euclidean",
                                             "measure"))
def query_lut_batch(q_segs: jnp.ndarray, cb: PQCodebook,
                    window: Optional[int],
                    euclidean: bool = False,
                    measure: Optional[MeasureSpec] = None) -> jnp.ndarray:
    """Batched asymmetric tables: ``q_segs (Nq, M, S)`` -> ``(Nq, M, K)``.

    One all-pairs dispatch launch per subspace; on the compiled route the
    kernel sweeps a few query segments at once against 128 codewords on
    lanes, its register only the band's slots deep, so the Nq x K
    cross-product of series is never materialized.

    >>> import jax, jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, codebook_size=2, use_prealign=False,
    ...                kmeans_iters=1, dba_iters=1)
    >>> X = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 10.0
    >>> cb = fit(jax.random.PRNGKey(0), X, cfg)
    >>> query_lut_batch(segment(X, cfg), cb, cfg.window(8),
    ...                 measure=cfg.measure()).shape
    (4, 2, 2)
    """
    Nq, M, S = q_segs.shape
    # every search plan builds its query tables here: the scope names this
    # device work as the search's LUT stage in a profile
    with jax.named_scope("index.search.lut"):
        if euclidean:
            return jnp.sum(
                (q_segs[:, :, None, :] - cb.centroids[None]) ** 2, -1)
        return jnp.stack([elastic_cdist(q_segs[:, m], cb.centroids[m],
                                        window, measure=measure)
                          for m in range(M)], axis=1)


@jax.jit
def _adc_gather(qlut: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """``qlut (M, K)``, ``codes (N, M)`` -> distances ``(N,)``."""
    m_idx = jnp.arange(qlut.shape[0])
    d2 = jnp.sum(qlut[m_idx[None, :], codes], axis=-1)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def cdist_asym(Q: jnp.ndarray, codes: jnp.ndarray, cb: PQCodebook,
               cfg: PQConfig) -> jnp.ndarray:
    """Asymmetric distances: raw queries ``Q (Nq, D)`` vs codes ``(N, M)``.

    >>> import jax, jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, codebook_size=2, use_prealign=False,
    ...                kmeans_iters=1, dba_iters=1)
    >>> X = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 10.0
    >>> cb = fit(jax.random.PRNGKey(0), X, cfg)
    >>> cdist_asym(X[:3], encode(X, cb, cfg), cb, cfg).shape
    (3, 4)
    """
    Q = jnp.asarray(Q, jnp.float32)
    D = Q.shape[-1]
    q_segs = segment(Q, cfg)                     # (Nq, M, S)
    luts = query_lut_batch(q_segs, cb, cfg.window(D), not cfg.is_elastic,
                           cfg.measure())
    return jax.vmap(lambda ql: _adc_gather(ql, codes))(luts)


@jax.jit
def cdist_sym_refined(codes_a: jnp.ndarray, segs_a: jnp.ndarray,
                      codes_b: jnp.ndarray, segs_b: jnp.ndarray,
                      cb: PQCodebook) -> jnp.ndarray:
    """§4.2 clustering distance: symmetric PQ, but where two series share a
    code in subspace m (LUT says 0), substitute the Keogh lower bound
    ``max(lb(a^m, env(code)), lb(b^m, env(code)))`` — guaranteed between 0
    and the true subspace DTW.

    >>> import jax, jax.numpy as jnp
    >>> cfg = PQConfig(n_sub=2, codebook_size=2, use_prealign=False,
    ...                kmeans_iters=1, dba_iters=1)
    >>> X = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 10.0
    >>> cb = fit(jax.random.PRNGKey(0), X, cfg)
    >>> codes, segs = encode(X, cb, cfg), segment(X, cfg)
    >>> cdist_sym_refined(codes, segs, codes, segs, cb).shape
    (4, 4)
    """
    def per_sub(am, sa, bm, sb, lut_m, up_m, lo_m):
        base = lut_m[am[:, None], bm[None, :]]                  # (Na, Nb)
        lb_a = lb_keogh(sa[:, None, :], up_m[bm][None, :, :],   # a vs b's code
                        lo_m[bm][None, :, :])
        lb_b = lb_keogh(sb[None, :, :], up_m[am][:, None, :],   # b vs a's code
                        lo_m[am][:, None, :])
        fallback = jnp.maximum(lb_a, lb_b)
        same = am[:, None] == bm[None, :]
        return jnp.where(same, fallback, base)

    d2 = jnp.sum(jax.vmap(per_sub, in_axes=(1, 1, 1, 1, 0, 0, 0))(
        codes_a, segs_a, codes_b, segs_b,
        cb.lut, cb.env_upper, cb.env_lower), 0)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


# ---------------------------------------------------------------------------
# Memory accounting (§3.4)
# ---------------------------------------------------------------------------

def memory_cost(cfg: PQConfig, D: int, n_series: int, *,
                n_segments: int = 0, n_lists: int = 0,
                hot_capacity: int = 0, n_devices: int = 1) -> dict:
    """Bytes for raw data vs PQ representation + auxiliary structures.

    With the segmented-index keywords, the estimate also covers the
    streaming lifecycle layer (:mod:`repro.index`): per-entry id/tombstone/
    assignment sidecars, per-segment inverted-list offset tables, and the
    raw float32 hot-segment buffer — so ``compaction`` gains (fewer
    segments, no dead padding) are visible in the same accounting that
    §3.4 uses for the quantizer itself.

    ``n_devices > 1`` additionally splits the segmented estimate into
    per-device accounting for the list-sharded layout: the quantizers,
    inverted-list tables and hot buffer are *replicated* on every device
    (``replicated_bytes``) while the sealed codes and their sidecars are
    *partitioned* across the mesh (``partitioned_bytes``), so the
    per-device high-water mark is

        ``max_device_bytes = replicated + ceil(partitioned / n_devices)``

    — the partitioned share shrinks ~linearly with the mesh (up to the
    one-list placement slack of :mod:`repro.index.placement`).

    >>> cost = memory_cost(PQConfig(), 128, 1000)
    >>> cost["raw_bytes"], cost["code_bytes"]
    (512000, 8000)
    >>> cost["compression"]
    64.0
    """
    S = cfg.subseq_len(D)
    M, K = cfg.n_sub, cfg.codebook_size
    code_bits = max(1, int(np.ceil(np.log2(K))))
    raw = 4 * D * n_series
    codes = int(np.ceil(code_bits / 8)) * M * n_series
    codebook = 4 * M * K * S
    lut = 4 * M * K * K
    envelopes = 2 * 4 * M * K * S
    out = dict(raw_bytes=raw, code_bytes=codes, codebook_bytes=codebook,
               lut_bytes=lut, envelope_bytes=envelopes,
               aux_bytes=codebook + lut + envelopes,
               compression=raw / max(codes, 1))
    if n_segments or hot_capacity:
        # sealed sidecars: int32 id + int32 coarse assignment + bool live
        sidecar = (4 + 4 + 1) * n_series
        # per-segment inverted-list tables: int32 start + len per list
        # (+ int32 placement under the sharded layout — counted replicated)
        lists = 2 * 4 * n_lists * n_segments
        # hot segment: raw float32 buffer + id/live sidecars at capacity
        hot = (4 * D + 4 + 1) * hot_capacity
        out.update(sidecar_bytes=sidecar, list_bytes=lists, hot_bytes=hot,
                   index_bytes=codes + sidecar + lists + hot,
                   total_bytes=codes + sidecar + lists + hot
                   + out["aux_bytes"])
        if n_devices > 1:
            # coarse centroids ride along with every device's probe stage
            coarse = 4 * n_lists * D
            replicated = out["aux_bytes"] + coarse + lists + hot
            partitioned = codes + sidecar
            out.update(
                n_devices=n_devices,
                coarse_bytes=coarse,
                replicated_bytes=replicated,
                partitioned_bytes=partitioned,
                max_device_bytes=replicated + -(-partitioned // n_devices))
    return out
