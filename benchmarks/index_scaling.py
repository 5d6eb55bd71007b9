"""Streaming index lifecycle costs: insert throughput, query latency as a
function of sealed-segment count, the cost + payoff of compaction, and the
device-scaling axis of the sharded planner (replicated vs list-sharded
layout on 1/2/4 devices: simulated CPU devices in subprocesses, or the
real devices in this process on an accelerator)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax

from repro.core.pq import PQConfig
from repro.data.timeseries import random_walks
from repro.index import IndexConfig, StreamingIndex

from . import common
from .common import Bench, timeit

def _device_leg(n_dev: int, D: int, n_lists: int, cap: int,
                n_seg: int) -> dict:
    """One device-scaling leg: the same index searched directly and by
    both sharded plans on a mesh of the first ``n_dev`` devices."""
    import numpy as np
    from repro.index import search_sharded
    from repro.launch.mesh import make_search_mesh

    mesh = make_search_mesh(n_dev)
    cfg = IndexConfig(
        pq=PQConfig(n_sub=4, codebook_size=32, use_prealign=False,
                    **common.measure_config_fields(),
                    kmeans_iters=3, dba_iters=1),
        n_lists=n_lists, hot_capacity=cap, coarse_iters=4, n_shards=n_dev)
    index = StreamingIndex.bootstrap(
        jax.random.PRNGKey(0), random_walks(2 * cap, D, seed=0), cfg)
    index.insert(random_walks(n_seg * cap, D, seed=2))
    index.compact()                   # one merged, placement-balanced shard
    Q = random_walks(16, D, seed=99)
    lat, lat_p99 = dict(), dict()
    t = timeit(lambda: index.search(Q, n_probe=4, topk=3), repeats=3)
    lat["direct"], lat_p99["direct"] = t["median_s"], t["p99_s"]
    for part in ("queries", "lists"):
        t = timeit(lambda: search_sharded(index, Q, n_probe=4, topk=3,
                                          mesh=mesh, partition=part),
                   repeats=3)
        lat[part], lat_p99[part] = t["median_s"], t["p99_s"]
    sg = index.segments[0]
    mc = index.memory_cost()
    return dict(
        n_devices=n_dev, latency_s=lat, latency_p99_s=lat_p99,
        live_rows=index.n_live(),
        shard_cap=sg.shard_cap, max_list=int(np.asarray(sg.list_len).max()),
        code_bytes=mc["code_bytes"],
        max_device_bytes=mc.get("max_device_bytes", mc["total_bytes"]),
        replicated_bytes=mc.get("replicated_bytes", 0),
        partitioned_bytes=mc.get("partitioned_bytes",
                                 mc["code_bytes"] + mc["sidecar_bytes"]))


# On the CPU each leg runs in a subprocess: XLA fixes the host device count
# at first init, so each simulated mesh size needs a fresh process.  It
# prints one JSON marker line the parent collects into the shared Bench.
_CPU_LEG = r"""
import json
from benchmarks import common
from benchmarks.index_scaling import _device_leg
common.set_measure({measure!r})
print("LEG:" + json.dumps(_device_leg({n_dev}, {D}, {n_lists}, {cap},
                                      {n_seg})))
"""


def _cpu_leg(n_dev: int, D: int, n_lists: int, cap: int,
             n_seg: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}")
    code = _CPU_LEG.format(measure=common.MEASURE, n_dev=n_dev, D=D,
                           n_lists=n_lists, cap=cap, n_seg=n_seg)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    if res.returncode != 0:
        raise RuntimeError(
            f"device leg n_dev={n_dev} failed:\n{res.stderr[-2000:]}")
    return json.loads(next(ln for ln in res.stdout.splitlines()
                           if ln.startswith("LEG:"))[4:])


def _make_index(D: int, n_lists: int, hot_capacity: int,
                train_n: int) -> StreamingIndex:
    cfg = IndexConfig(
        pq=PQConfig(n_sub=4, codebook_size=32, use_prealign=False,
                    **common.measure_config_fields(),
                    kmeans_iters=3, dba_iters=1),
        n_lists=n_lists, hot_capacity=hot_capacity, coarse_iters=4)
    sample = random_walks(train_n, D, seed=0)
    return StreamingIndex.bootstrap(jax.random.PRNGKey(0), sample, cfg)


def run(quick: bool = True) -> Bench:
    b = Bench("index_scaling")
    D, n_lists, cap = (96, 8, 64) if quick else (256, 32, 256)
    n_segments_sweep = (1, 2, 4, 8) if quick else (1, 2, 4, 8, 16)
    Q = random_walks(16, D, seed=99)

    # --- insert throughput: amortized over fills + seals --------------------
    index = _make_index(D, n_lists, cap, train_n=2 * cap)
    stream = random_walks(4 * cap, D, seed=1)
    index.insert(stream[:cap])          # warm up the encode/assign jits
    t0 = time.perf_counter()
    index.insert(stream[cap:])
    t_ins = time.perf_counter() - t0
    b.add(op="insert", series=3 * cap,
          throughput_per_s=3 * cap / t_ins, total_s=t_ins)

    # --- query latency vs segment count -------------------------------------
    for n_seg in n_segments_sweep:
        index = _make_index(D, n_lists, cap, train_n=2 * cap)
        index.insert(random_walks(n_seg * cap, D, seed=2))
        assert index.n_segments == n_seg
        t = timeit(lambda: index.search(Q, n_probe=4, topk=3), repeats=3)
        b.add(op="search", n_segments=n_seg, rows=n_seg * cap,
              latency_s=t["median_s"], latency_p50_s=t["p50_s"],
              latency_p99_s=t["p99_s"])

    # --- compaction: cost of the merge, payoff on query latency -------------
    t0 = time.perf_counter()
    index.compact()
    t_cmp = time.perf_counter() - t0
    t = timeit(lambda: index.search(Q, n_probe=4, topk=3), repeats=3)
    b.add(op="compact", merged_rows=index.segments[0].rows,
          max_list=index.segments[0].max_list, compact_s=t_cmp,
          post_compact_latency_s=t["median_s"],
          post_compact_latency_p99_s=t["p99_s"])

    # --- device scaling: replicated vs list-sharded layout ------------------
    # On the CPU the devices are simulated and share one CPU, so wall-clock
    # speedup is not the point there; what the rows pin down is the
    # *structure* of the scale-out: per-device occupancy (hence sealed-code
    # HBM) shrinking ~linearly with the mesh, and the cost of the
    # all_gather fan-in merge relative to the query-sharded plan doing
    # identical kernel work.  On an accelerator the legs run in this
    # process over the real devices (a child process could not reach a
    # chip this process holds).
    n_seg_dev = 4
    on_chip = jax.default_backend() != "cpu"
    for n_dev in (1, 2, 4):
        if on_chip:
            if n_dev > len(jax.devices()):
                continue
            leg = _device_leg(n_dev, D, n_lists, cap, n_seg_dev)
        else:
            leg = _cpu_leg(n_dev, D, n_lists, cap, n_seg_dev)
        lat = leg["latency_s"]
        # the placement guarantee, on the physically sealed layout:
        # per-device rows <= perfect split + one list's worth
        assert leg["shard_cap"] <= (-(-leg["live_rows"] // n_dev)
                                    + leg["max_list"]), leg
        if n_dev > 1:
            # per-device partitioned share shrinks ~linearly with the mesh
            share = leg["max_device_bytes"] - leg["replicated_bytes"]
            assert share <= leg["partitioned_bytes"] / n_dev + 1, leg
        b.add(op="device_scaling", n_devices=n_dev,
              rows=leg["live_rows"], shard_cap=leg["shard_cap"],
              latency_direct_s=lat["direct"],
              latency_query_sharded_s=lat["queries"],
              latency_list_sharded_s=lat["lists"],
              latency_list_sharded_p99_s=leg["latency_p99_s"]["lists"],
              fanin_overhead_s=lat["lists"] - lat["queries"],
              per_device_speedup=lat["direct"] / lat["lists"],
              max_device_bytes=leg["max_device_bytes"],
              partitioned_bytes=leg["partitioned_bytes"])

    b.save(headline={
        "quick": quick, "measure": common.MEASURE,
        "config": dict(D=D, n_lists=n_lists, hot_capacity=cap),
        "insert_throughput_per_s": next(
            (r["throughput_per_s"] for r in b.rows if r["op"] == "insert"),
            None),
        "max_device_bytes_by_mesh": {
            str(r["n_devices"]): r["max_device_bytes"]
            for r in b.rows if r["op"] == "device_scaling"}})
    return b


if __name__ == "__main__":
    run(quick=True)
