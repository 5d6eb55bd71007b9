"""Median wait of a query in the coalescer before its batch launched
(``serving_coalesce_wait_seconds``, recorded per query by the program)."""

import numpy as np


def read(run):
    w = run.samples("serving_coalesce_wait_seconds")
    return float(np.median(w)) * 1e3 if w else None
