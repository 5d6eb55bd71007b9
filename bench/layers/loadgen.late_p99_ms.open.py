"""99th percentile of how late the load generator sent a request after
it was due: a starved generator must not read as a fast server."""

from bench.loadgen import p99


def read(run):
    st = run.streams.get("lookups")
    if st is None or not st.requests:
        return None
    t0 = run.traffic.t0
    return p99([(r.sent - t0 - r.due) * 1e3 for r in st.requests if r.sent])
