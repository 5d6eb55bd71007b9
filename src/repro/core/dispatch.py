"""Unified execution-engine dispatch for every elastic-distance hot path.

Every elastic / ADC consumer in the library (PQ encoding, query LUTs, DBA
k-means assignment, IVF coarse search, exact NN search, symmetric code
distances, LB-filtered search) funnels through the entry points here
instead of calling a
specific implementation, so the Pallas kernels are the *default engine* on
TPU rather than a dead benchmark artifact:

    elastic_pairwise(A, B, window)   zipped pairs          -> (N,)
    elastic_cdist(A, B, window)      all pairs             -> (N, M)
    adc_cdist(codes_a, codes_b, lut) symmetric ADC         -> (Na, Nb)
    adc_lookup(codes, qlut)          asymmetric scan       -> (N,)
    prealign_encode(X, centroids)    fused MODWT prealign
                                     + elastic-1NN encode  -> (N, M) codes
    lb_refine(A, B, up, lo, thresh)  fused LB cascade +
                                     conditional DTW refine -> (N,), (N,)
    two_level_coarse(Q, top, coarse, child_idx, child_valid)
                                     hierarchical coarse
                                     rank + child fan-out  -> (Nq, n_lists)

Measures: the elastic entry points take a ``measure`` argument (name,
``"name:param=value"`` string, or :class:`repro.core.measures.MeasureSpec`;
``None`` = DTW) that is threaded as a *static* parameter down to the shared
wavefront recurrence — one implementation per op regardless of measure.
``lb_refine`` additionally validates that the measure supports the Keogh
cascade (only capability-gated callers should reach it).

Window contract (shared by knn / lb / lb_search / ivf / kernels):
``window=None`` means *unbanded*, which is exactly a Sakoe-Chiba band of
``L - 1`` — shifts beyond the series length are infeasible, so
:func:`effective_window` clamps every materialized window to
``[0, L - 1]``.  Use it whenever a concrete integer window is needed
(envelope construction, band geometry); never materialize ``L`` itself.

Backends (resolved once per call site at trace time):

    "pallas"           Pallas kernels; compiled on TPU, interpret elsewhere
    "pallas_interpret" Pallas kernels, interpret mode forced (CI / debug)
    "jax"              pure-JAX lax.scan wavefront + gather ADC (reference)
    "auto"             "pallas" on TPU, "jax" otherwise

Selection order: :func:`set_backend` override > ``$REPRO_ELASTIC_BACKEND`` >
``"auto"``.  The :data:`stats` counters record which route every op took;
they are incremented at *trace* time (a jitted caller that hits its cache
does not re-count), which is exactly what tests need to assert that a code
path really executes through the dispatch layer.  Measure-parameterized
ops are double-counted: once under the bare op name and once under
``"op[measure]"``, so the routing ledger shows per-measure coverage.
:data:`totals` is the
same ledger but process-lifetime — :func:`reset_stats` leaves it alone, so
a CI run can dump it at session end and fail the build if an op silently
fell back to the ``"jax"`` route (see ``scripts/check_routing.py``).

The kernel modules are imported lazily (first dispatch) so that they may
themselves import :mod:`repro.core` submodules — e.g. the measure registry
— without creating an import cycle through this module.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.registry import REGISTRY as _obs_registry
from . import measures
from .dtw import dtw_batch, dtw_cdist
from .measures import MeasureArg, MeasureSpec

__all__ = [
    "BACKENDS", "ENV_VAR", "get_backend", "set_backend", "use_backend",
    "elastic_pairwise", "elastic_cdist", "adc_cdist", "adc_lookup",
    "prealign_encode", "lb_refine", "two_level_coarse", "stats", "totals",
    "reset_stats", "effective_window",
]

ENV_VAR = "REPRO_ELASTIC_BACKEND"
BACKENDS = ("auto", "pallas", "pallas_interpret", "jax")

_override: Optional[str] = None

# (op, resolved backend) -> number of dispatches (trace-time, see module
# doc); measure-parameterized ops are also ledgered as "op[measure]"
stats: Dict[Tuple[str, str], int] = {}

# same ledger, but never cleared by reset_stats: the process-lifetime record
# a CI routing gate can assert on after the whole test session
totals: Dict[Tuple[str, str], int] = {}


def effective_window(length: int, window: Optional[int]) -> int:
    """The library-wide ``window=None`` contract (see module docstring):
    ``None`` -> unbanded -> ``length - 1``; everything clamped to
    ``[0, length - 1]``.

    >>> effective_window(128, None)
    127
    >>> effective_window(128, 12)
    12
    >>> effective_window(128, 500)
    127
    """
    w = length - 1 if window is None else int(window)
    return max(0, min(w, length - 1))


def _check(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown elastic backend {name!r}; expected one of {BACKENDS}")
    return name


def get_backend() -> str:
    """Resolved backend name: ``"pallas"``, ``"pallas_interpret"`` or
    ``"jax"`` (``"auto"`` is resolved against the runtime platform).

    >>> from repro.core import dispatch
    >>> with dispatch.use_backend("jax"):
    ...     dispatch.get_backend()
    'jax'
    >>> dispatch.get_backend() in ("pallas", "pallas_interpret", "jax")
    True
    """
    name = _override if _override is not None else _check(
        os.environ.get(ENV_VAR, "auto"))
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jax"
    return name


def set_backend(name: Optional[str]) -> None:
    """Process-wide override (``None`` restores env/auto selection).

    Callers that were already traced keep their route — pair with
    ``jax.clear_caches()`` to force re-dispatch.

    >>> from repro.core import dispatch
    >>> dispatch.set_backend("pallas_interpret")
    >>> dispatch.get_backend()
    'pallas_interpret'
    >>> dispatch.set_backend(None)          # back to env/auto selection
    """
    global _override
    _override = _check(name) if name is not None else None


@contextmanager
def use_backend(name: str):
    """Scoped :func:`set_backend` (tests, benchmarks).

    >>> from repro.core import dispatch
    >>> prev = dispatch.get_backend()
    >>> with dispatch.use_backend("jax"):
    ...     dispatch.get_backend()
    'jax'
    >>> dispatch.get_backend() == prev      # restored on exit
    True
    """
    global _override
    prev = _override
    _override = _check(name)
    try:
        yield
    finally:
        _override = prev


def reset_stats() -> None:
    """Clear the per-test :data:`stats` ledger; the process-lifetime
    :data:`totals` ledger (the CI routing gate's input) is untouched.

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> with dispatch.use_backend("jax"):
    ...     _ = dispatch.elastic_pairwise(jnp.zeros((1, 4)),
    ...                                   jnp.ones((1, 4)), window=1)
    >>> dispatch.stats[("elastic_pairwise", "jax")] >= 1
    True
    >>> dispatch.reset_stats()
    >>> ("elastic_pairwise", "jax") in dispatch.stats
    False
    >>> ("elastic_pairwise", "jax") in dispatch.totals
    True
    """
    stats.clear()


def _count(op: str, route: str,
           measure: Optional[MeasureSpec] = None) -> None:
    keys = [(op, route)]
    if measure is not None:
        keys.append((f"{op}[{measure.name}]", route))
    for key in keys:
        stats[key] = stats.get(key, 0) + 1
        totals[key] = totals.get(key, 0) + 1
    # Mirror into the observability registry (repro.obs): same trace-time
    # semantics as `totals` — the kind="trace" label keeps the distinction
    # from run-time span metrics explicit in every export — and persistent,
    # so obs.reset() cannot erase the routing ledger mid-session.  A plain
    # host-side counter bump: cheap enough to run whether obs is enabled
    # or not, which keeps the exported routing coverage complete even for
    # sessions that never turn spans on.
    labels = {"op": op, "backend": route, "kind": "trace"}
    if measure is not None:
        labels["measure"] = measure.name
    _obs_registry.counter("dispatch_total", persistent=True, **labels).inc()


def _interpret_flag(backend: str) -> Optional[bool]:
    # "pallas" defers to default_interpret() (compiled on TPU); forced True
    # under "pallas_interpret" so CI exercises the kernel bodies on CPU.
    return True if backend == "pallas_interpret" else None


def _adaptive_geometry(L: int, window: Optional[int], backend: str,
                       spec: MeasureSpec, width: Optional[int],
                       factor: int, radius: int):
    """Shared adaptive-band resolution: the tuned register width cap for
    this geometry (static, trace-time) plus the corridor clipper."""
    from ..kernels import tune
    from . import corridor as corr
    if width is None:
        lane = 128 if jax.default_backend() == "tpu" else 8
        width = tune.adaptive_width(L, window, lane, measure=spec.name,
                                    backend=backend, factor=factor,
                                    radius=radius)
    return corr, width


def elastic_pairwise(A: jnp.ndarray, B: jnp.ndarray,
                     window: Optional[int] = None, *,
                     block: Optional[int] = None,
                     measure: MeasureArg = None,
                     band: str = "static",
                     corridor: Optional[Tuple[jnp.ndarray,
                                              jnp.ndarray]] = None,
                     corridor_factor: int = 8, corridor_radius: int = 2,
                     width: Optional[int] = None) -> jnp.ndarray:
    """Elastic cost over zipped pairs: ``(N, L) x (N, L) -> (N,)``.

    ``band="adaptive"`` sweeps each pair's own corridor envelope (built
    here from a coarse PAA pass unless ``corridor=(lo, hi)`` is given —
    see :mod:`repro.core.corridor`).  The adaptive result is bit-identical
    to the static band whenever the corridor contains the static optimal
    path (checkable via ``corridor.certify_adaptive``) and a documented
    *approximate* upper bound otherwise; it is ledgered separately as
    ``elastic_pairwise_adaptive``.  ``block=None`` consults the
    :mod:`repro.kernels.tune` table for the launch block.

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> with dispatch.use_backend("jax"):
    ...     d = dispatch.elastic_pairwise(jnp.zeros((2, 8)),
    ...                                   jnp.ones((2, 8)), window=2)
    >>> d.shape
    (2,)
    >>> [float(x) for x in d]           # 8 unit squared diffs per pair
    [8.0, 8.0]
    """
    from ..kernels.dtw_band.ops import dtw_band
    spec = measures.resolve(measure)
    backend = get_backend()
    if band == "static":
        _count("elastic_pairwise", backend, spec)
        if backend == "jax":
            return dtw_batch(A, B, window, spec)
        return dtw_band(A, B, window, block=block,
                        interpret=_interpret_flag(backend), measure=spec)
    if band != "adaptive":
        raise ValueError(f"unknown band mode {band!r}; "
                         "expected 'static' or 'adaptive'")
    _count("elastic_pairwise_adaptive", backend, spec)
    L = A.shape[-1]
    corr, width = _adaptive_geometry(L, window, backend, spec, width,
                                     corridor_factor, corridor_radius)
    if corridor is None:
        corridor = corr.build_corridor(A, B, window, factor=corridor_factor,
                                       radius=corridor_radius)
    lo, hi = corr.clip_to_width(*corridor, width)
    if backend == "jax":
        return corr.corridor_sweep(A, B, lo, hi, window=window, width=width,
                                   measure=spec)[:, 0]
    return dtw_band(A, B, window, block=block,
                    interpret=_interpret_flag(backend), measure=spec,
                    corridor=(lo, hi), width=width)


def elastic_cdist(A: jnp.ndarray, B: jnp.ndarray,
                  window: Optional[int] = None, *,
                  block: Optional[int] = None,
                  measure: MeasureArg = None) -> jnp.ndarray:
    """All-pairs elastic cost: ``(N, L) x (M, L) -> (N, M)``.

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> with dispatch.use_backend("jax"):
    ...     D = dispatch.elastic_cdist(jnp.zeros((2, 8)),
    ...                                jnp.ones((3, 8)), window=2)
    >>> D.shape
    (2, 3)
    >>> float(D[0, 0])
    8.0
    """
    from ..kernels.dtw_band.ops import dtw_band_cdist
    spec = measures.resolve(measure)
    backend = get_backend()
    _count("elastic_cdist", backend, spec)
    if backend == "jax":
        return dtw_cdist(A, B, window, measure=spec)
    return dtw_band_cdist(A, B, window, block=block,
                          interpret=_interpret_flag(backend), measure=spec)


def adc_cdist(codes_a: jnp.ndarray, codes_b: jnp.ndarray,
              lut: jnp.ndarray, *,
              lut_dtype: str = "float32") -> jnp.ndarray:
    """Symmetric PQ distance matrix ``sqrt(sum_m LUT[m, a^m, b^m])``:
    one-hot MXU contractions on the Pallas route, plain gathers on "jax".
    Measure-generic by construction — the LUT already encodes whichever
    measure built it (paper §3.3).

    ``lut_dtype`` selects the resident-table precision: ``"float32"``
    (exact), or the quantized LUT path — ``"int8"`` (per-subspace affine,
    4x smaller VMEM table) / ``"bfloat16"`` (2x).  The quantized route is
    ledgered as ``adc_cdist_quant`` and matches f32 within the
    per-subspace quantization step (see
    :func:`repro.kernels.pq_adc.ops.quantize_lut`).

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> codes = jnp.array([[0, 1], [1, 0]], jnp.int32)
    >>> lut = jnp.stack([1.0 - jnp.eye(2)] * 2)   # (M=2, K=2, K=2)
    >>> with dispatch.use_backend("jax"):
    ...     D = dispatch.adc_cdist(codes, codes, lut)
    >>> [round(float(x), 3) for x in D.ravel()]   # sqrt(0), sqrt(2), ...
    [0.0, 1.414, 1.414, 0.0]
    >>> with dispatch.use_backend("jax"):
    ...     Dq = dispatch.adc_cdist(codes, codes, lut, lut_dtype="int8")
    >>> [round(float(x), 2) for x in Dq.ravel()]
    [0.0, 1.41, 1.41, 0.0]
    """
    from ..kernels.pq_adc.ops import adc_sym_cdist as _adc_sym_pallas
    from ..kernels.pq_adc.ops import adc_sym_cdist_quant, quantize_lut
    from ..kernels.pq_adc.ref import adc_sym_cdist_quant_ref, adc_sym_cdist_ref
    backend = get_backend()
    if lut_dtype != "float32":
        _count("adc_cdist_quant", backend)
        q, scale, zero = quantize_lut(lut, lut_dtype)
        if backend == "jax":
            return adc_sym_cdist_quant_ref(codes_a, codes_b, q, scale, zero)
        return adc_sym_cdist_quant(codes_a, codes_b, q, scale, zero,
                                   interpret=_interpret_flag(backend))
    _count("adc_cdist", backend)
    if backend == "jax":
        return adc_sym_cdist_ref(codes_a, codes_b, lut)
    return _adc_sym_pallas(codes_a, codes_b, lut,
                           interpret=_interpret_flag(backend))


def adc_lookup(codes: jnp.ndarray, qlut: jnp.ndarray, *,
               lut_dtype: str = "float32") -> jnp.ndarray:
    """Asymmetric ADC scan: ``codes (N, M)``, ``qlut (M, K)`` -> ``(N,)``.

    Returns ``sqrt(sum_m qlut[m, codes[n, m]])`` per row.  A batch of
    scans, each against its own table, is one launch: ``codes (B, N, M)``,
    ``qlut (B, M, K)`` -> ``(B, N)`` (float32 tables only).  ``lut_dtype``
    mirrors :func:`adc_cdist`: ``"int8"`` / ``"bfloat16"`` run the
    quantized query-table kernel (ledgered ``adc_lookup_quant``).

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> codes = jnp.array([[0, 0], [1, 1]], jnp.int32)
    >>> qlut = jnp.array([[0.0, 2.0], [0.0, 2.0]])
    >>> with dispatch.use_backend("jax"):
    ...     d = dispatch.adc_lookup(codes, qlut)
    >>> [float(x) for x in d]
    [0.0, 2.0]
    """
    from ..kernels.pq_adc.ops import adc_lookup as _adc_lookup_pallas
    from ..kernels.pq_adc.ops import adc_lookup_quant, quantize_lut
    from ..kernels.pq_adc.ref import adc_lookup_quant_ref, adc_lookup_ref
    backend = get_backend()
    if lut_dtype != "float32":
        if codes.ndim != 2:
            raise ValueError("a quantized query table scans one (N, M) "
                             "block of codes, not a batch")
        _count("adc_lookup_quant", backend)
        q, scale, zero = quantize_lut(qlut, lut_dtype)
        if backend == "jax":
            return adc_lookup_quant_ref(codes, q, scale, zero)
        return adc_lookup_quant(codes, q, scale, zero,
                                interpret=_interpret_flag(backend))
    _count("adc_lookup", backend)
    if backend == "jax":
        return adc_lookup_ref(codes, qlut)
    return _adc_lookup_pallas(codes, qlut,
                              interpret=_interpret_flag(backend))


def prealign_encode(X: jnp.ndarray, centroids: jnp.ndarray, *, level: int,
                    tail: int, window: Optional[int] = None,
                    block: Optional[int] = None,
                    measure: MeasureArg = None) -> jnp.ndarray:
    """Fused MODWT prealign + exact elastic-1NN encode: ``X (N, D)`` against
    ``centroids (M, K, S)`` -> codes ``(N, M)`` int32.

    The Pallas route performs the whole §3.5 pipeline (scale recursion,
    change-point snap, segment re-interpolation, nearest-centroid scan) in
    one pass per batch tile — the ``(N, M, S)`` segment tensor never
    reaches HBM.  The ``"jax"`` route is the two-step reference.  The
    1-NN scan runs under ``measure`` (DTW by default).

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> cents = jnp.stack([jnp.zeros((2, 5)), jnp.ones((2, 5))], axis=1)
    >>> cents.shape                                # (M=2, K=2, S=5)
    (2, 2, 5)
    >>> with dispatch.use_backend("jax"):
    ...     codes = dispatch.prealign_encode(jnp.zeros((2, 8)), cents,
    ...                                      level=1, tail=1, window=2)
    >>> codes.shape, str(codes.dtype)
    ((2, 2), 'int32')
    >>> bool((codes == 0).all())                   # zeros snap to centroid 0
    True
    """
    from ..kernels.prealign_encode.ops import (
        prealign_encode as _prealign_encode_pallas)
    from ..kernels.prealign_encode.ref import prealign_encode_ref
    spec = measures.resolve(measure)
    backend = get_backend()
    _count("prealign_encode", backend, spec)
    if backend == "jax":
        return prealign_encode_ref(X, centroids, level, tail, window,
                                   measure=spec)
    return _prealign_encode_pallas(X, centroids, level, tail, window,
                                   block=block,
                                   interpret=_interpret_flag(backend),
                                   measure=spec)


def lb_refine(A: jnp.ndarray, B: jnp.ndarray, upper: jnp.ndarray,
              lower: jnp.ndarray, thresh: jnp.ndarray,
              window: Optional[int] = None, *,
              block: Optional[int] = None,
              measure: MeasureArg = None,
              band: str = "static",
              corridor: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              corridor_factor: int = 8, corridor_radius: int = 2,
              width: Optional[int] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused cascade bound + conditional banded refine over zipped
    pairs: ``A (N, L)`` queries, ``B (N, L)`` candidates, ``upper``/
    ``lower (N, L)`` Keogh envelopes of ``A``, ``thresh (N,)``.

    Returns ``(d (N,), refined (N,) bool)``: ``d`` is the exact banded
    elastic cost where ``max(LB_Kim, LB_Keogh) < thresh`` and the (valid)
    lower bound elsewhere.  On the Pallas route a pair tile whose bounds
    all exceed their thresholds skips the wavefront sweep entirely.

    Only sound for measures with ``has_keogh_lb`` (a hard error otherwise
    — capability-gated callers such as ``lb_search.filtered_topk`` fall
    back to the exact dense path before reaching here).

    ``band="adaptive"`` refines inside each pair's own corridor envelope
    (built here unless ``corridor=(lo, hi)`` is given).  The bound math
    is unchanged — ``lb`` stays a valid lower bound of the static-band
    distance — but the refined value is the corridor-restricted cost, an
    *upper* bound of the static cost, so the adaptive cascade is the
    documented approximate contract (ledgered as ``lb_refine_adaptive``)
    and is excluded from the certified-exact LB cascade guarantees.

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> A, B = jnp.zeros((2, 8)), jnp.ones((2, 8))
    >>> env = jnp.zeros((2, 8))                    # degenerate envelopes
    >>> with dispatch.use_backend("jax"):
    ...     d, refined = dispatch.lb_refine(A, B, env, env,
    ...                                     jnp.array([100.0, 0.0]),
    ...                                     window=2)
    >>> [bool(r) for r in refined]                 # row 1 pruned by bound
    [True, False]
    >>> float(d[0])                                # exact where refined
    8.0
    """
    from ..kernels.lb_cascade.ops import lb_refine as _lb_refine_pallas
    from ..kernels.lb_cascade.ref import lb_refine_jax
    spec = measures.resolve(measure)
    if not spec.has_keogh_lb:
        raise ValueError(
            f"measure {spec.name!r} has no sound Keogh/Kim lower bound; "
            "lb_refine would prune incorrectly — use the exact dense path")
    backend = get_backend()
    if band == "static":
        _count("lb_refine", backend, spec)
        if backend == "jax":
            return lb_refine_jax(A, B, upper, lower, thresh, window,
                                 measure=spec)
        return _lb_refine_pallas(A, B, upper, lower, thresh, window,
                                 block=block,
                                 interpret=_interpret_flag(backend),
                                 measure=spec)
    if band != "adaptive":
        raise ValueError(f"unknown band mode {band!r}; "
                         "expected 'static' or 'adaptive'")
    _count("lb_refine_adaptive", backend, spec)
    L = A.shape[-1]
    corr, width = _adaptive_geometry(L, window, backend, spec, width,
                                     corridor_factor, corridor_radius)
    if corridor is None:
        corridor = corr.build_corridor(A, B, window, factor=corridor_factor,
                                       radius=corridor_radius)
    lo, hi = corr.clip_to_width(*corridor, width)
    if backend == "jax":
        return lb_refine_jax(A, B, upper, lower, thresh, window,
                             measure=spec, corridor=(lo, hi), width=width)
    return _lb_refine_pallas(A, B, upper, lower, thresh, window,
                             block=block,
                             interpret=_interpret_flag(backend),
                             measure=spec, corridor=(lo, hi), width=width)


def two_level_coarse(Q: jnp.ndarray, top: jnp.ndarray, coarse: jnp.ndarray,
                     child_idx: jnp.ndarray, child_valid: jnp.ndarray,
                     window: Optional[int] = None, *, n_probe_top: int,
                     block: Optional[int] = None,
                     measure: MeasureArg = None) -> jnp.ndarray:
    """Hierarchical (two-level) coarse stage for large ``n_lists``.

    ``Q (Nq, D)`` queries are first ranked against the ``top (n_top, D)``
    cluster-the-centroids quantizer (one all-pairs kernel launch); only
    the children of each query's ``n_probe_top`` nearest top cells —
    ``child_idx`` / ``child_valid (n_top, max_children)`` indexing into
    ``coarse (n_lists, D)`` — are then evaluated exactly, as one *zipped
    pairs* launch over the ``Nq * n_probe_top * max_children`` gathered
    (query, centroid) pairs.  Returns the ``(Nq, n_lists)`` coarse
    distance row with ``+inf`` for lists outside the fan-out, which the
    downstream probe ``top_k`` consumes unchanged.

    Per-query cost is ``O(n_top + n_probe_top * max_children)`` elastic
    evaluations instead of ``O(n_lists)``; with ``n_probe_top == n_top``
    every list is visited and the result matches the flat coarse cdist.
    Both heavy stages route through the same kernel paths as
    :func:`elastic_cdist` / :func:`elastic_pairwise`; the op is ledgered
    separately so the routing gate can prove the hierarchical stage ran.

    >>> import jax.numpy as jnp
    >>> from repro.core import dispatch
    >>> coarse = jnp.arange(4, dtype=jnp.float32)[:, None] * jnp.ones(8)
    >>> top = jnp.array([[0.5] * 8, [2.5] * 8])    # parents of {0,1}, {2,3}
    >>> child_idx = jnp.array([[0, 1], [2, 3]], jnp.int32)
    >>> child_valid = jnp.ones((2, 2), bool)
    >>> with dispatch.use_backend("jax"):
    ...     dc = dispatch.two_level_coarse(jnp.zeros((1, 8)), top, coarse,
    ...                                    child_idx, child_valid,
    ...                                    n_probe_top=1)
    >>> dc.shape
    (1, 4)
    >>> [bool(jnp.isfinite(x)) for x in dc[0]]     # only top cell 0 fans out
    [True, True, False, False]
    >>> float(dc[0, 0])
    0.0
    """
    n_top, C = child_idx.shape
    if not 1 <= n_probe_top <= n_top:
        raise ValueError(
            f"n_probe_top={n_probe_top} out of range: must satisfy "
            f"1 <= n_probe_top <= n_top={n_top}")
    spec = measures.resolve(measure)
    _count("two_level_coarse", get_backend(), spec)
    Q = jnp.asarray(Q, jnp.float32)
    Nq = Q.shape[0]
    n_lists = coarse.shape[0]
    dc_top = elastic_cdist(Q, top, window, block=block, measure=spec)
    _, tops = jax.lax.top_k(-dc_top, n_probe_top)          # (Nq, P)
    cand = child_idx[tops].reshape(Nq, n_probe_top * C)    # (Nq, P*C)
    cvalid = child_valid[tops].reshape(Nq, n_probe_top * C)
    cents = coarse[cand.reshape(-1)]                       # (Nq*P*C, D)
    qq = jnp.repeat(Q, n_probe_top * C, axis=0)
    d = elastic_pairwise(qq, cents, window, block=block, measure=spec)
    d = jnp.where(cvalid.reshape(-1), d,
                  jnp.inf).reshape(Nq, n_probe_top * C)
    dc = jnp.full((Nq, n_lists), jnp.inf, jnp.float32)
    # scatter-min: a list reachable through two probed tops keeps one
    # (identical) distance; masked padding lanes are +inf no-ops
    return dc.at[jnp.arange(Nq)[:, None], cand].min(d)
