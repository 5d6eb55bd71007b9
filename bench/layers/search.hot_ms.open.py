"""Mean host time per search batch of the exact LB-cascade scan of the
hot buffer, from the program's fenced ``index.search.hot`` span."""


def read(run):
    t = run.samples("stage_seconds", stage="index.search.hot")
    return sum(t) / len(t) * 1e3 if t else None
