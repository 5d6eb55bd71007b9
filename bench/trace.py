"""Reduce a profiler trace to busy time, stage device time and a breakdown.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
interval lists; ``reduce`` works on those lists alone, so it can be
checked on a small recorded excerpt (``bench/tests/data``).

* Busy time is the union of the device-op intervals on the TPU planes,
  averaged over the chips that ran anything.
* A stage's device time is the busy time that falls inside that stage's
  host span (``obs`` spans are ``TraceAnnotation``\\ s on the trace's
  clock, and the traced run fences each stage's device work into its
  span).  Attributing by stage keeps the reading the same whether a stage
  runs a Pallas kernel or XLA's own loop.
* The breakdown lists the device ops that took most time and the idle
  time of the device by the ``obs`` span open on the host meanwhile.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OP_LINE = "XLA Ops"


def op_name(name: str) -> str:
    """``%fusion.6 = f32[...] fusion(...)`` -> ``fusion``: an HLO op's kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def host_name(name: str) -> str:
    """A host event's name without its arguments."""
    return name.split("(", 1)[0].split(" ", 1)[0]


def load(trace_dir: str) -> dict:
    """Interval lists from the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"device": {plane: [[name, start_ns, end_ns], ...]},
    "host": [[name, start_ns, end_ns, line], ...]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return {"device": {}, "host": []}
    pd = ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OP_LINE] or lines
            device[plane.name] = [
                [op_name(e.name), e.start_ns, e.start_ns + e.duration_ns]
                for ln in ops
                for e in ln.events
                if e.duration_ns > 0
            ]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host += [
                    [host_name(e.name), e.start_ns, e.start_ns + e.duration_ns, ln.name]
                    for e in ln.events
                    if e.duration_ns > 0
                ]
    return {"device": device, "host": host}


def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def intersect(a, b) -> float:
    """Total overlap of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def innermost(points, events):
    """Name of the shortest event open at each of the sorted ``points``
    (``None`` where none is open), by one sweep."""
    import heapq

    evs = sorted(events, key=lambda h: h[1])
    active, out, k = [], [], 0
    for p in points:
        while k < len(evs) and evs[k][1] <= p:
            heapq.heappush(active, (evs[k][2], k))
            k += 1
        while active and active[0][0] <= p:
            heapq.heappop(active)
        out.append(min((evs[j] for _, j in active), key=lambda h: h[2] - h[1])[0]
                   if active else None)
    return out


def reduce(ev: dict, stages, span_prefixes=("index.", "serving.", "bench.")) -> dict:
    """Busy and idle over the ``bench.window`` span, device time inside
    each stage's spans, and the breakdown.  Times in seconds."""
    win = [h for h in ev["host"] if h[0] == WINDOW_SPAN]
    if not win or not any(ev["device"].values()):
        return {}
    lo, hi = win[0][1], win[0][2]
    spans = [h for h in ev["host"] if h[0].startswith(span_prefixes) and h[0] != WINDOW_SPAN]
    busy, per_plane = [], []
    op_time = defaultdict(float)
    for ops in ev["device"].values():
        if not ops:
            continue
        for name, s, e in ops:
            if e > lo and s < hi:
                op_time[name] += min(e, hi) - max(s, lo)
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        per_plane.append(merged)
    busy = per_plane[0] if len(per_plane) == 1 else union([iv for m in per_plane for iv in m])
    n_chips = len(per_plane)
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(length(m) for m in per_plane) / n_chips / 1e9,
        "stages": {},
    }
    for st in stages:
        mine = union(clip([(s, e) for n, s, e, _ in spans if n == st], lo, hi))
        count = sum(1 for n, s, e, _ in spans if n == st and s >= lo and e <= hi)
        out["stages"][st] = {
            "device_s": intersect(busy, mine) / n_chips / 1e9,
            "span_s": length(mine) / 1e9,
            "count": count,
        }
    # idle gaps, each put down to the innermost program or bench span open
    # at its middle, else to the innermost other host event open then
    gaps = [(s, e) for s, e in zip(([lo] + [x for iv in busy for x in iv] + [hi])[0::2],
                                    ([lo] + [x for iv in busy for x in iv] + [hi])[1::2]) if e > s]
    mids = [(s + e) / 2 for s, e in gaps]
    others = [h for h in ev["host"] if not h[0].startswith(span_prefixes)]
    first = innermost(mids, spans)
    second = innermost(mids, others)
    idle = defaultdict(float)
    for (s, e), a, b in zip(gaps, first, second):
        label = a or ("host: " + b if b else "no span open")
        idle[label] += (e - s) / 1e9
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    out["breakdown"] = {
        "device_ops": [[n, t / n_chips / 1e9] for n, t in top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()), key=lambda kv: -kv[1])[:10],
    }
    return out


def excerpt(ev: dict, seconds: float = 0.5) -> dict:
    """The first ``seconds`` of the traced window, every event clipped to it
    (for the reduction's own test)."""
    (win,) = [h for h in ev["host"] if h[0] == WINDOW_SPAN][:1] or [None]
    if win is None:
        return {"device": {}, "host": []}
    lo = win[1]
    hi = min(win[2], lo + int(seconds * 1e9))
    dev = {p: [[n, max(s, lo), min(e, hi)] for n, s, e in ops if e > lo and s < hi]
           for p, ops in ev["device"].items()}
    host = [[n, max(s, lo), min(e, hi), ln] for n, s, e, ln in ev["host"]
            if e > lo and s < hi and n != WINDOW_SPAN]
    return {"device": dev, "host": host + [[WINDOW_SPAN, lo, hi, win[3]]]}
