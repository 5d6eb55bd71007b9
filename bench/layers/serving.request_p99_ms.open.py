"""99th percentile of a request's time inside the program, from its submit
to its answer being ready on the device (``serving_request_seconds``,
recorded per request by the serving completion watcher).  Nothing where
the program does not record it."""

from bench.loadgen import p99


def read(run):
    t = run.samples("serving_request_seconds")
    return p99(t) * 1e3 if t else None
