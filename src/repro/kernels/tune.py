"""Kernel launch autotuner: measured block sizes per launch geometry.

Every ``make_*_call`` builder in this package takes ``block`` /
``block_a`` / ``block_b`` sizes that until now were hardcoded defaults.
This module replaces the hardcoding with a *tuning table* keyed by

    (op, L bucket, window bucket, measure, backend)

consulted by the ops wrappers whenever the caller passes ``block=None``
(the new default throughout :mod:`repro.core.dispatch`).  An explicit
``block`` always wins — that is also how the tuner's own measurement
runs bypass the table.

``REPRO_TUNE`` selects the mode:

``off`` (default)
    No table: every lookup returns the builtin default.  CI's
    recompile gate and the test suite run here — launch geometry is
    byte-stable.
``auto``
    First use of an (op, geometry) key benchmarks the candidate grid,
    memoizes the winner in-process and persists it to a JSON table under
    ``experiments/tune/`` (override the directory with
    ``REPRO_TUNE_OUT``).  ``REPRO_TUNE_GRID=minimal`` shrinks every
    candidate grid to the single builtin default — the bench-smoke CI
    leg uses this so the auto path is exercised without making warm-path
    compile counts data-dependent.
``<path>``
    A pinned table: lookups are read-only from the JSON file at
    ``<path>`` (deterministic; missing keys fall back to the default).

Measurement runs never trigger inside an active JAX trace (the resolved
block is a *static* argument, so resolution happens at trace time): if
the trace state is not clean the lookup silently returns the memoized or
default value instead of benchmarking.  A candidate that fails to compile
(see :func:`_is_compile_error`) is skipped; any other error propagates.

The table also carries the adaptive-corridor register width
(``op="adaptive_width"``): the width cap for ``band="adaptive"`` sweeps
derives from the corridor geometry bucket (projection factor + safety
radius), *not* from the worst-case static band — see
:func:`adaptive_width`.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Tuple

ENV = "REPRO_TUNE"
GRID_ENV = "REPRO_TUNE_GRID"
OUT_ENV = "REPRO_TUNE_OUT"

_DEFAULT_OUT = os.path.join("experiments", "tune")
_TABLE_NAME = "tuning.json"

# candidate grids per op; "minimal" mode collapses each to (default,)
# (row blocks are multiples of 8: a TPU tile has 8 sublanes)
_GRIDS: Dict[str, Dict[str, Tuple[int, ...]]] = {
    "dtw_band": {"block": (8, 64, 256)},
    "dtw_band_cdist": {"block": (64, 128, 256)},
    "lb_refine": {"block": (8, 64, 256)},
    "adc_sym": {"block_a": (64, 128), "block_b": (64, 128)},
    "adc_lookup": {"block": (128, 256, 512)},
}

# Builtin blocks of the wavefront kernels on the compiled route, used when
# no table entry applies.  One anti-diagonal step on a single (8, 128)
# vreg is latency-bound; wider blocks run independent vreg chains side by
# side.  dtw_band and lb_refine count rows; dtw_band_cdist counts register
# sublanes, band slots times A rows, so the A rows it runs as chains adapt
# to the band (4 at L=256, w=26; 16 at S=18, w=2).  Measured on a TPU v5e
# in one run of the serving geometries (PERF.md, Findings: 64 x 256
# lists, 16 x 64 x 256 codewords, 4096 x 256 rows): dtw_band_cdist at
# block 128 ran 10.9M pairs/s at L=256, w=26 (bucket 64; 1.51 ms a
# launch), 488M pairs/s at S=18, w=2 (0.54 ms for the 16 LUT launches)
# and 11.0M pairs/s at 4096 x 256, L=256 (94.9 ms); blocks 64 and 256
# were 1.25-1.4x slower, and 8 or 32 (one chain at L=256) 2.3x at L=256.
# dtw_band at L=18, w=2 ran 2.1M pairs/s at block 8 and 7.3M pairs/s at
# block 256.  lb_refine skips the sweep per tile, so its block stays
# smaller.  Interpret mode keeps 8.
_COMPILED_BLOCK: Dict[str, int] = {
    "dtw_band": 256,
    "dtw_band_cdist": 128,
    "lb_refine": 64,
}

_memo: Dict[str, Dict[str, int]] = {}
_pinned: Dict[str, Dict[str, Dict[str, int]]] = {}


def mode() -> str:
    return os.environ.get(ENV, "off") or "off"


def _bucket(n: int) -> int:
    """Next power of two >= n — geometry keys bucket L and window+1 so
    nearby shapes share one tuning entry."""
    b = 1
    while b < n:
        b *= 2
    return b


def table_key(op: str, *, length: int, window: Optional[int],
              measure: Optional[str], backend: str) -> str:
    w = length if window is None else int(window)
    return (f"{op}|L{_bucket(max(1, length))}"
            f"|w{_bucket(min(w, length - 1) + 1)}"
            f"|{measure or 'dtw'}|{backend}")


def _out_path() -> str:
    return os.path.join(os.environ.get(OUT_ENV, _DEFAULT_OUT), _TABLE_NAME)


def _load(path: str) -> Dict[str, Dict[str, int]]:
    if path not in _pinned:
        try:
            with open(path, encoding="utf-8") as f:
                _pinned[path] = json.load(f)
        except (OSError, ValueError):
            _pinned[path] = {}
    return _pinned[path]


def _persist(path: str, key: str, entry: Dict[str, int]) -> None:
    table = dict(_load(path))
    table[key] = entry
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(table, f, indent=2, sort_keys=True)
        f.write("\n")
    _pinned[path] = table


def _trace_clean() -> bool:
    """True outside any JAX trace (jit, vmap, shard_map, ...)."""
    import jax
    return jax.core.trace_ctx.is_top_level()


def _is_compile_error(err: Exception) -> bool:
    """True for the errors that mean "this candidate does not compile
    here" — the only ones a measurement run may skip: Mosaic refusing the
    kernel (an unsupported op, or more VMEM than a kernel may use), a
    variant with no compiled kernel, and XLA running out of memory."""
    import jax
    from jax._src.pallas.mosaic.error_handling import MosaicError
    from .common import CompiledRouteUnsupported
    if isinstance(err, (MosaicError, CompiledRouteUnsupported)):
        return True
    return (isinstance(err, jax.errors.JaxRuntimeError)
            and str(err).startswith("RESOURCE_EXHAUSTED"))


def _candidates(op: str, defaults: Dict[str, int]
                ) -> Tuple[Dict[str, int], ...]:
    grid = _GRIDS.get(op)
    if grid is None or os.environ.get(GRID_ENV) == "minimal":
        return (dict(defaults),)
    params = sorted(grid)
    combos = [{}]
    for p in params:
        combos = [dict(c, **{p: v}) for c in combos for v in grid[p]]
    return tuple(dict(defaults, **c) for c in combos)


def _time_once(fn) -> float:
    fn()                              # warmup: compile outside the clock
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _measure(op: str, params: Dict[str, int], *, length: int,
             window: Optional[int], measure: Optional[str],
             backend: str) -> Optional[float]:
    """One candidate micro-benchmark; None when the op has no runner."""
    import numpy as np
    rng = np.random.default_rng(0)
    interpret = None if backend == "pallas" else backend == "pallas_interpret"
    if backend == "jax":
        return None
    if op in ("dtw_band", "dtw_band_cdist"):
        from .dtw_band.ops import dtw_band, dtw_band_cdist
        n = 32
        A = rng.standard_normal((n, length)).astype(np.float32)
        B = rng.standard_normal((n, length)).astype(np.float32)
        if op == "dtw_band":
            def fn():
                dtw_band(A, B, window, measure=measure,
                         interpret=interpret, **params).block_until_ready()
        else:
            # a lane tile of B rows, as the coarse stage and LUTs launch
            B = rng.standard_normal((128, length)).astype(np.float32)

            def fn():
                dtw_band_cdist(A, B, window, measure=measure,
                               interpret=interpret,
                               **params).block_until_ready()
        return _time_once(fn)
    if op == "lb_refine":
        from .lb_cascade.ops import lb_refine
        n = 32
        A = rng.standard_normal((n, length)).astype(np.float32)
        B = rng.standard_normal((n, length)).astype(np.float32)
        upper, lower = B + 0.5, B - 0.5
        thresh = np.full((n,), np.inf, np.float32)

        def fn():
            lb_refine(A, B, upper, lower, thresh, window, measure=measure,
                      interpret=interpret, **params)[0].block_until_ready()
        return _time_once(fn)
    if op in ("adc_sym", "adc_lookup"):
        from .pq_adc.ops import adc_sym_cdist, adc_lookup
        n_sub, K = 8, max(4, min(length, 256))
        codes = rng.integers(0, K, (256, n_sub)).astype(np.int32)
        if op == "adc_sym":
            lut = rng.standard_normal((n_sub, K, K)).astype(np.float32)

            def fn():
                adc_sym_cdist(codes, codes, lut, interpret=interpret,
                              **params).block_until_ready()
        else:
            qlut = rng.standard_normal((n_sub, K)).astype(np.float32)

            def fn():
                # repro: ignore[RS101] tuner wall-clock timing; trace-clean
                adc_lookup(codes, qlut, interpret=interpret,
                           **params).block_until_ready()
        return _time_once(fn)
    return None


def _resolve_entry(op: str, defaults: Dict[str, int], *, length: int,
                   window: Optional[int], measure: Optional[str],
                   backend: str) -> Dict[str, int]:
    key = table_key(op, length=length, window=window, measure=measure,
                    backend=backend)
    m = mode()
    if m == "off":
        return defaults
    if m != "auto":                   # pinned table path
        return _load(m).get(key, defaults)
    if key in _memo:
        return _memo[key]
    table = _load(_out_path())
    if key in table:
        _memo[key] = table[key]
        return table[key]
    if not _trace_clean():            # never benchmark mid-trace
        return defaults
    best, best_t = dict(defaults), float("inf")
    for cand in _candidates(op, defaults):
        try:
            t = _measure(op, cand, length=length, window=window,
                         measure=measure, backend=backend)
        except Exception as err:
            if not _is_compile_error(err):
                raise
            continue
        if t is not None and t < best_t:
            best, best_t = cand, t
    _memo[key] = best
    _persist(_out_path(), key, best)
    return best


def tuned(op: str, param: str, *, length: int, window: Optional[int] = None,
          measure: Optional[str] = None, backend: str = "pallas",
          default: int = 8) -> int:
    """Resolve one launch parameter for ``op`` at the given geometry.

    Returns ``default`` in ``off`` mode (and for any key the table does
    not cover); otherwise the pinned or measured winner.
    """
    entry = _resolve_entry(op, {param: default}, length=length,
                           window=window, measure=measure, backend=backend)
    return int(entry.get(param, default))


def default_block(op: str, backend: str) -> int:
    """Builtin launch block of ``op`` on ``backend`` (no table entry)."""
    return _COMPILED_BLOCK.get(op, 8) if backend == "pallas" else 8


def adaptive_width(length: int, window: Optional[int], lane: int = 8, *,
                   measure: Optional[str] = None, backend: str = "pallas",
                   factor: int = 8, radius: int = 2) -> int:
    """Register width cap for ``band="adaptive"`` sweeps.

    The default derives from the *corridor geometry* — projected coarse
    cells span at most ``~2*factor`` fine rows per diagonal, plus the
    block tail and the safety radius — rather than the worst-case static
    band, and is never wider than the static register.  The tuning table
    can override it per bucket (``op="adaptive_width"``)."""
    from .dtw_band.kernel import band_width
    need = 3 * factor + 2 * radius + 2
    default = min(band_width(length, window, lane),
                  max(lane, -(-need // lane) * lane))
    return tuned("adaptive_width", "width", length=length, window=window,
                 measure=measure, backend=backend, default=default)


def reset() -> None:
    """Drop every in-process memo and cached table (tests)."""
    _memo.clear()
    _pinned.clear()
