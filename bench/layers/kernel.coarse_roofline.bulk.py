"""Share of the roofline reached by the coarse stage (``n_lists`` banded
DTW distances per query at the series length) on the device, in percent
of the bound the chip's published peaks set (``bench/peaks.json``)."""

from bench import work


def read(run):
    st = run.stage("index.search.coarse")
    if not st or not st["count"] or st["device_s"] <= 0 or run.peak is None:
        return None
    batches = run.counter("serving_batches_total")
    per_batch = run.counter("serving_queries_total") / batches if batches else 0
    ops, nbytes = work.coarse_stage(run.g, per_batch)
    share, _ = work.roofline_share(ops * st["count"], nbytes * st["count"], st["device_s"], run.peak)
    return share
