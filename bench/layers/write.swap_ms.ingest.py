"""Mean host time of one snapshot publish (``serving_snapshot_swap_seconds``:
the copy of the hot buffer to the device and the swap of the view), which
every acknowledged write waits for."""


def read(run):
    t = run.samples("serving_snapshot_swap_seconds")
    return sum(t) / len(t) * 1e3 if t else None
