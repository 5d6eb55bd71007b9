"""Share of the roofline reached by the device work inside ``index.flush``
(coarse assignment and the encoder's exact refinement), in percent of
the bound the chip's published peaks set (``bench/peaks.json``)."""

from bench import work


def read(run):
    st = run.stage("index.flush")
    spans = run.samples("stage_seconds", stage="index.flush")
    rows = run.counter("index_sealed_rows_total")
    if not st or not st["count"] or st["device_s"] <= 0 or not spans or run.peak is None:
        return None
    ops, nbytes = work.flush(run.g, rows / len(spans))
    share, _ = work.roofline_share(ops * st["count"], nbytes * st["count"], st["device_s"], run.peak)
    return share
