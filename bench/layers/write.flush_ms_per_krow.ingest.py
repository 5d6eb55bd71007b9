"""Host time of ``index.flush`` (coarse assignment, encoding, sealing)
per 1000 rows sealed, from the program's spans and counters."""


def read(run):
    t = run.samples("stage_seconds", stage="index.flush")
    rows = run.counter("index_sealed_rows_total")
    return sum(t) * 1e3 / (rows / 1e3) if t and rows else None
