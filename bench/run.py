#!/usr/bin/env python3
"""Benchmark of the served IVF-PQDTW index: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``: the deployment) and a
traffic mix (``bench/traffic/<traffic>.json``).  The run builds the
deployment from the seed, warms the shapes the traffic reaches, drives
the traffic for ``--seconds``, checks a sample of the answers against the
plain reference (``bench/check.py``), and prints one JSON line.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
it turns the program's ``obs`` spans on, profiles part of the window and
reports the cell's per-layer metrics (``bench/layers/<metric>.py``).

It runs only on a TPU and exits non-zero, printing no result, elsewhere.
``--rehearse`` runs a tiny copy of the cell on whatever JAX finds (the
CPU here), and its result names that device.  ``--sweep r1,r2,...``
offers the cell's open-loop stream at each rate in turn and prints the
latency and backlog at each (the knee search); ``--control`` also judges
the control (the reference in bfloat16 in the program's place).
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
RUNS = os.path.join(BENCH, ".runs")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny copy of the cell on any device (CPU rehearsal)")
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates to offer, one window each")
    ap.add_argument("--sweep-stream", default="",
                    help="the stream whose rate --sweep sets (default: the open-loop one)")
    ap.add_argument("--control", action="store_true",
                    help="also judge the bfloat16 reference in the program's place")
    return ap.parse_args(argv)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    traffic_file = os.path.join(BENCH, "traffic", name + ".json")
    if name in cells:
        cell = cells[name]
    elif os.path.exists(traffic_file):
        # a traffic mix not (yet) listed as a cell: it runs on one chip with
        # the configuration it names, and reports only the metrics that
        # list no cells
        with open(traffic_file) as f:
            cell = {"name": name, "config": json.load(f)["config"], "traffic": name, "chips": 1}
    else:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        cfg = merge(cfg, cfg.get("rehearse", {}))
        traffic = merge(traffic, traffic.get("rehearse", {}))
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [])
             or ("workloads" not in m and any(e["name"] == m["moves"] for e in e2e))]
    return bench, cell, cfg, traffic, e2e, layer


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def setup_jax(rehearse: bool, chips: int):
    """Import JAX, insist on the chip, and keep the compile cache in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: the program under test is not under {src}")
    sys.path.insert(0, src)
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if not rehearse and d0.platform != "tpu":
        raise SystemExit(f"bench: JAX's first device is {d0.platform!r} "
                         f"({d0.device_kind}), not a TPU; no CPU fallback")
    if not rehearse and len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(BENCH, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax, {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}


class Compiles:
    """Counts executables built or loaded (``jax.monitoring``), by phase."""

    def __init__(self):
        self.phase = "setup"
        self.n = {"setup": 0, "window": 0, "after": 0}
        self.seconds = {"setup": 0.0, "window": 0.0, "after": 0.0}
        self.in_window = {}  # program name -> count

    def __call__(self, event, duration, fun_name="", **kw):
        from jax._src import dispatch

        if event == dispatch.BACKEND_COMPILE_EVENT:
            self.n[self.phase] += 1
            self.seconds[self.phase] += duration
            if self.phase == "window":
                self.in_window[fun_name] = self.in_window.get(fun_name, 0) + 1


class Run:
    """What a per-layer reader may read: client records, the program's obs
    registry over the window, the trace reduction and the compile count."""

    def __init__(self, g, traffic, streams, registry, trace, compiles, peak):
        self.g = g
        self.traffic = traffic
        self.streams = streams
        self.registry = registry
        self.trace = trace
        self.compiles = compiles
        self.peak = peak

    def samples(self, name, **labels):
        out = []
        for h in self.registry.histograms():
            if h.name == name and all(h.labels.get(k) == v for k, v in labels.items()):
                out += h.samples
        return out

    def counter(self, name):
        return sum(c.value for c in self.registry.counters() if c.name == name)

    def stage(self, name):
        return self.trace.get("stages", {}).get(name) if self.trace else None


COMPILES = Compiles()


def read_layer(name: str, run: Run):
    path = os.path.join(BENCH, "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_layer_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def warm_writes(dep, writer, buckets, per_request, seconds):
    """Set-up for cells with writers: the schedule's own writes until the
    hot buffer has sealed once and a retention delete has landed, then a
    search at every bucket (the compacted base, a flush-born segment, the
    hot scan), then the final merge at every segment count the window can
    reach.  The window's writes come at a fixed rate, so that count is
    known: it stays within one compaction period, and nothing compiles."""
    cap = dep.cfg["ivf"]["hot_capacity"]
    spec = writer.spec
    while writer.rows_inserted < cap + spec["batch_rows"] or writer.n_ops < spec.get("delete_every", 0):
        op = writer.submit(dep.srv, writer.next_op())
        if op.shed:
            raise SystemExit("set-up write was shed")
        op.future.result()
    dep.warm(buckets, per_request)
    rows = spec["rate_ops_per_s"] * seconds * spec["batch_rows"]
    flushes = (writer.rows_inserted % cap + int(rows)) // cap + 1
    dep.warm_merges(buckets, range(2, 2 + len(dep.srv.view.segments) + flushes + 1))


def main(argv=None):
    args = parse(argv)
    bench, cell, cfg, tspec, e2e, layers = load_cell(args.workload, args.rehearse)
    jax, device = setup_jax(args.rehearse, cell["chips"])
    import jax.monitoring

    from bench import loadgen
    from bench.deploy import Deployment
    from repro import obs

    compiles = COMPILES
    jax.monitoring.register_event_duration_secs_listener(compiles)

    dep = Deployment(cfg, args.seed)
    log(f"set-up: deployment built at {time.time() - T_START:.1f} s ({dep.timings}); "
        f"segments (rows, longest list): {[(int(sg.codes.shape[0]), sg.max_list) for sg in dep.srv.view.segments]}")
    wspec = next((s for s in tspec["streams"] if s["kind"] == "writers"), None)
    writer = None
    if wspec is not None:
        dep.batch_rows = wspec["batch_rows"]
        writer = loadgen.Writer(wspec, dep.log, dep.batch, dep.n_ids,
                                resident=range(dep.n_ids))
    per_request = min(s.get("queries_per_request", 64) for s in tspec["streams"]
                      if s["kind"] != "writers")
    if writer is not None:
        warm_writes(dep, writer, tspec["warm_buckets"], per_request, args.seconds)
    else:
        dep.warm(tspec["warm_buckets"], per_request)
    log(f"set-up: warmed at {time.time() - T_START:.1f} s ({dep.timings})")
    pool, src = dep.query_pool(tspec["query_pool"], cfg["data"]["query_noise"])

    if args.sweep:
        return sweep(args, dep, tspec, pool, src, writer)

    traffic = loadgen.Traffic(tspec, args.seed, pool, src, dep.rows_of,
                              cfg["data"]["query_noise"])
    trace_dir = os.path.join(RUNS, "trace-" + args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.REGISTRY.reset(include_persistent=True)
        obs.enable()
    setup_s = time.time() - T_START
    compiles.phase = "window"

    def during(tr):
        if not args.trace:
            return
        t = tspec["trace"]
        time.sleep(max(0.0, tr.t0 + t["start_s"] - time.perf_counter()))
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(t["seconds"])
        jax.profiler.stop_trace()

    stuck = traffic.run(dep.srv, args.seconds, writer=writer, on_start=during)
    compiles.phase = "after"
    if args.trace:
        obs.disable()
    final = dep.srv.quiesce(timeout=loadgen.GRACE_S)
    dep.log.close(final)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    dep.srv.stop()
    dep.srv = None
    gc.collect()

    streams = {st.name: st for st in traffic.streams}
    attempted, failed = loadgen.counts(traffic)

    # -- correctness --------------------------------------------------------
    numbers = judge(args, dep, traffic, tspec, stuck)
    limits = tspec["check"]["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)

    # -- metrics ------------------------------------------------------------
    metrics = {}
    result = {}
    if args.trace:
        from bench import trace as tr

        ev = tr.load(trace_dir)
        red = tr.reduce(ev, tspec["trace"]["stages"])
        if os.environ.get("BENCH_KEEP_TRACE_EXCERPT"):
            with open(os.environ["BENCH_KEEP_TRACE_EXCERPT"], "w") as f:
                json.dump({"events": tr.excerpt(ev), "stages": tspec["trace"]["stages"]}, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
        run = Run(dep.g, traffic, streams, obs.REGISTRY, red, compiles.n["window"],
                  peaks.get(device["kind"]))
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = red["breakdown"]
            log(f"trace: {json.dumps({k: v for k, v in red.items() if k != 'breakdown'})}")
        for m in layers:
            v = read_layer(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            if m["name"] == "setup_s":
                v = setup_s
            else:
                stat = tspec["end_to_end"][m["name"]]
                v = loadgen.STATS[stat["stat"]](streams[stat["stream"]], traffic, stat.get("q"))
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"compiles: {json.dumps(compiles.n)} taking {json.dumps(compiles.seconds)} s, in the window {json.dumps(compiles.in_window)}; "
        f"setup_s {setup_s}")
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    if "control" in numbers:
        out["control"] = numbers["control"]
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return out


def judge(args, dep, traffic, tspec, stuck):
    """The numbers compared: a sample of answered requests against the
    reference, and the write guarantees over every request."""
    from bench import check

    ck = tspec["check"]
    rng = np.random.default_rng([args.seed, 13])
    answered, never = [], 0
    for st in traffic.streams:
        for r in st.requests:
            if r.done is None:
                never += 1
            else:
                answered += [(r, j) for j in range(len(r.q))]
    never += len(stuck)
    pick = rng.choice(len(answered), min(ck["sample_queries"], len(answered)), replace=False)
    samples = [
        {"q": r.q[j], "version": r.version, "dist": r.dist[j], "ids": r.ids[j],
         "src": int(r.src[j])}
        for r, j in (answered[i] for i in sorted(pick))
    ]
    ops = dep.log.ops
    n_ids = 1 + max(int(o.ids.max()) for o in ops if o.kind == "insert")
    replay = check.Replay(ops, dep.cfg["ivf"]["hot_capacity"], n_ids)
    ever = np.flatnonzero(replay.inserted < len(ops))
    sample_rows = np.sort(rng.choice(ever, min(ck["sample_rows"], len(ever)), replace=False))
    t0 = time.perf_counter()
    numbers = check.compare(dep.g, dep.quant, dep.rows_of, samples, replay,
                            dep.log.prefix, sample_rows)
    numbers["never"] = never
    # a search sent after a write was acknowledged answers from a version
    # that holds it
    acks = sorted((o.t_ack, i) for i, o in enumerate(ops) if o.t_ack is not None)
    ack_t = np.array([a for a, _ in acks])
    need = np.maximum.accumulate(np.array([i + 1 for _, i in acks])) if acks else np.zeros(0)
    stale = 0
    for st in traffic.streams:
        for r in st.requests:
            if r.done is None or not len(ack_t):
                continue
            k = np.searchsorted(ack_t, r.sent, side="left")
            if k and dep.log.prefix.get(r.version, 0) < need[k - 1]:
                stale += 1
    numbers["stale"] = stale
    hit = np.mean([s["src"] in s["ids"].tolist() for s in samples])
    log(f"checked {numbers['checked']} answers in {time.perf_counter() - t0:.1f} s; "
        f"source series in the top {dep.g.topk}: {hit:.3f} of them")
    if args.control:
        sub = check.control_substitute(dep.g, dep.quant, dep.rows_of, replay)
        c = check.compare(dep.g, dep.quant, dep.rows_of, samples, replay,
                          dep.log.prefix, sample_rows, substitute=sub)
        numbers["control"] = c
        log(f"control: {json.dumps(c)}")
    return numbers


def sweep(args, dep, tspec, pool, src, writer):
    """Offer one stream at each rate in turn, the others as the cell has
    them; print latency, backlog and compiles of each window."""
    from bench import loadgen

    name = args.sweep_stream or next(s["name"] for s in tspec["streams"]
                                     if s["kind"] == "open_loop")
    per_request = min(s.get("queries_per_request", 64) for s in tspec["streams"]
                      if s["kind"] != "writers")
    for rate in [float(x) for x in args.sweep.split(",")]:
        streams = [dict(s, **{"rate_ops_per_s" if s["kind"] == "writers" else "rate_per_s": rate})
                   if s["name"] == name else s for s in tspec["streams"]]
        tr = loadgen.Traffic(dict(tspec, streams=streams), args.seed, pool, src,
                             dep.rows_of, dep.cfg["data"]["query_noise"])
        n0 = COMPILES.n["setup"]
        tr.run(dep.srv, args.seconds, writer=writer)
        row = {"stream": name, "rate": rate, "compiles": COMPILES.n["setup"] - n0}
        for st in tr.streams:
            if st.kind == "writers":
                lat = [(o.t_ack - tr.t0 - o.due) * 1e3 for o in st.writes if o.t_ack is not None]
                done = sum(o.t_ack is not None and o.t_ack <= tr.t_end for o in st.writes)
                offered = len(st.writes)
            else:
                lat = [(r.done - tr.t0 - r.due) * 1e3 for r in st.requests if r.done is not None]
                done = sum(r.done is not None and r.done <= tr.t_end for r in st.requests)
                offered = len(st.requests)
            half = len(lat) // 2
            row[st.name] = {
                "done_in_window": done, "offered": offered,
                "p50_ms": float(np.percentile(lat, 50)) if lat else None,
                "p99_ms": loadgen.p99(lat) if lat else None,
                "p50_first_half_ms": float(np.median(lat[:half])) if half else None,
                "p50_second_half_ms": float(np.median(lat[half:])) if half else None,
            }
        log(f"sweep: {json.dumps(row)}")
        if writer is not None:
            # start the next window from a compacted index, as the cell does
            dep.write(loadgen.Op("compact"))
            dep.warm(tspec["warm_buckets"], per_request)
    dep.srv.stop()
    return None


if __name__ == "__main__":
    result = main()
    for t in threading.enumerate():
        if t is not threading.current_thread() and not t.daemon:
            t.join(timeout=5)
    if result is not None:
        print(json.dumps(result, default=lambda o: o.item()), flush=True)
