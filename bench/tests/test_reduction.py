"""The trace reduction and the work counts.

Run with ``python -m pytest bench/tests``.  The recorded excerpt
(``data/excerpt_bulk.json``) is the first half second of the traced
window of a ``--trace 1`` run of ``hydra-rw256.bulk`` on a TPU v5e: its
device ops, the program's spans and the host events named after the
Python functions ``time`` and ``numpy``, as ``bench.trace.load`` reads
them.
"""

import json
import os

import pytest

from bench import trace, work
from bench.check import Geometry

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("L,w,cells", [(256, 26, 12866), (128, 13, 3274), (18, 2, 84), (4, 9, 16)])
def test_band_cells(L, w, cells):
    assert work.band_cells(L, w) == cells
    brute = sum(1 for i in range(L) for j in range(L) if abs(i - j) <= w)
    assert brute == cells


def test_union_and_intersect():
    a = trace.union([(0, 10), (5, 12), (20, 30), (30, 31), (40, 40)])
    assert a == [[0, 12], [20, 31]]
    assert trace.length(a) == 23
    b = trace.union([(11, 21), (25, 26)])
    assert trace.intersect(a, b) == 1 + 1 + 1


def _sweep_busy(intervals, lo, hi):
    """Busy time by a sweep over endpoints, independent of ``union``."""
    pts = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            pts += [(s, 1), (e, -1)]
    pts.sort()
    depth, last, busy = 0, None, 0.0
    for t, d in pts:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def _recorded():
    with open(os.path.join(HERE, "data", "excerpt_bulk.json")) as f:
        return json.load(f)


def test_reduce_on_recorded_trace():
    rec = _recorded()
    ev, stages = rec["events"], rec["stages"]
    red = trace.reduce(ev, stages)
    (win,) = [h for h in ev["host"] if h[0] == trace.WINDOW_SPAN]
    lo, hi = win[1], win[2]
    ops = [(s, e) for plane in ev["device"].values() for _, s, e in plane]
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert red["busy_s"] == pytest.approx(_sweep_busy(ops, lo, hi) / 1e9)
    assert 0 < red["busy_s"] <= red["window_s"]
    busy = trace.union(trace.clip(ops, lo, hi))
    for st in stages:
        spans = [(s, e) for n, s, e, _ in ev["host"] if n == st]
        # device time of a stage: busy time inside its spans, by brute force
        want = sum(
            max(0, min(e, e2) - max(s, s2))
            for s, e in busy
            for s2, e2 in trace.union(trace.clip(spans, lo, hi))
        )
        got = red["stages"][st]["device_s"]
        assert got == pytest.approx(want / 1e9)
        assert got <= red["busy_s"] + 1e-12
    idle = sum(v for _, v in red["breakdown"]["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9


def test_stage_attribution_synthetic():
    ev = {
        "device": {"/device:TPU:0": [["a", 10, 20], ["b", 15, 30], ["c", 50, 60]]},
        "host": [
            ["bench.window", 0, 100, "python"],
            ["index.search.coarse", 5, 25, "t1"],
            ["index.search.hot", 45, 70, "t1"],
            ["serving.batch_search", 0, 80, "t1"],
        ],
    }
    red = trace.reduce(ev, ["index.search.coarse", "index.search.hot"])
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["stages"]["index.search.coarse"]["device_s"] == pytest.approx(15e-9)
    assert red["stages"]["index.search.hot"]["device_s"] == pytest.approx(10e-9)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # each gap goes to the innermost span open at its middle: 0-10 (at 5)
    # to the coarse stage, 30-50 (at 40) to the batch, 60-100 (at 80) to none
    assert gaps["index.search.coarse"] == pytest.approx(10e-9)
    assert gaps["serving.batch_search"] == pytest.approx(20e-9)
    assert gaps["no span open"] == pytest.approx(40e-9)
    assert red["breakdown"]["device_ops"][0][0] == "b"


def test_roofline_share_bounds():
    g = Geometry(
        {
            "data": {"length": 256},
            "pq": {"n_sub": 16, "codebook_size": 256, "tail_frac": 0.15, "window_frac": 0.1,
                   "wavelet_level": 3, "refine_frac": 0.125},
            "ivf": {"coarse_window_frac": 0.1, "n_lists": 256},
            "serving": {"n_probe": 8, "topk": 10},
        }
    )
    ops, nbytes = work.coarse_stage(g, 64)
    assert ops == 64 * 256 * 12866 * 5
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share, bound = work.roofline_share(ops, nbytes, 0.038, peak)
    assert bound == "compute"
    assert 0 < share < 100
