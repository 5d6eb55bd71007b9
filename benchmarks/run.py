"""Benchmark entry point: one module per paper table/figure + the roofline
aggregation.  ``python -m benchmarks.run [--full] [--only NAME]``."""

from __future__ import annotations

import argparse
import time

from . import (common, dtw_kernel_bench, fig5a_scaling, fig5b_params,
               fig5c_prealign, index_scaling, ivf_scaling, lb_cascade,
               memory_cost, pqkv_bench, roofline, serving_qps,
               table1_accuracy)

SUITES = {
    "dtw_kernel": dtw_kernel_bench.run,
    "fig5a": fig5a_scaling.run,
    "fig5b": fig5b_params.run,
    "fig5c": fig5c_prealign.run,
    "table1": table1_accuracy.run,
    "memory": memory_cost.run,
    "ivf": ivf_scaling.run,
    "index": index_scaling.run,
    "lb_cascade": lb_cascade.run,
    "pqkv": pqkv_bench.run,
    "serving": serving_qps.run,
    "roofline": roofline.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: quick sizes (further shrunk where a "
                         "suite supports it), 1 repetition per point")
    ap.add_argument("--only", choices=tuple(SUITES), default=None)
    ap.add_argument("--measure", default=None,
                    help="elastic measure for the measure-aware suites "
                         "(lb_cascade, ivf, index): a registry name or "
                         "'name:param=value', e.g. msm or erp:g=0.5")
    ap.add_argument("--device", choices=("tpu", "gpu"), default=None,
                    help="opt-in real-hardware leg: verify JAX actually "
                         "runs on this backend and record results as "
                         "experiments/bench/hw_<device>_*.json; the "
                         "committed BENCH_* summaries (CPU/interpret "
                         "baselines) are never touched")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke and args.full:
        ap.error("--smoke and --full are mutually exclusive")
    if args.smoke:
        common.set_smoke(True)
    if args.device:
        common.set_device(args.device)
    if args.measure:
        from repro.core import measures as _measures
        _measures.resolve(args.measure)   # fail fast on unknown names
        common.set_measure(args.measure)

    names = (args.only,) if args.only else tuple(SUITES)
    for name in names:
        print(f"== {name} ==", flush=True)
        t0 = time.time()
        SUITES[name](quick=not args.full)
        print(f"== {name} done in {time.time() - t0:.1f}s ==\n", flush=True)


if __name__ == "__main__":
    main()
