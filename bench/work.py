"""Work of each stage, counted from the cell's shapes alone.

DTW is counted as real band cells times 5 float32 operations per cell
(subtract, multiply, two minimums, add).  Padding rows and padded band
cells do not count, and neither do the lower bounds, the pre-alignment
or the top-k: a share computed from these counts is a lower bound of the
true one.
"""

from __future__ import annotations

OPS_PER_CELL = 5
F32 = 4


def band_cells(L: int, w: int) -> int:
    """Cells of an ``L x L`` DP table with ``|i - j| <= w``."""
    w = min(w, L - 1)
    return L * (2 * w + 1) - w * (w + 1)


def coarse_stage(g, n_queries: int) -> tuple:
    """``(ops, bytes)`` of one coarse stage: ``n_queries x n_lists`` DTW pairs
    at the series length, reading the queries and the centroids and
    writing the distance rows."""
    ops = n_queries * g.n_lists * band_cells(g.L, g.wc) * OPS_PER_CELL
    nbytes = F32 * (n_queries * g.L + g.n_lists * g.L + n_queries * g.n_lists)
    return ops, nbytes


def flush(g, rows: int) -> tuple:
    """``(ops, bytes)`` of sealing ``rows`` rows: coarse assignment
    (``rows x n_lists`` pairs at the series length) and the encoder's exact
    refinement (``rows x M x T`` pairs at the subspace geometry)."""
    ops = rows * (
        g.n_lists * band_cells(g.L, g.wc) + g.M * g.T * band_cells(g.S, g.w)
    ) * OPS_PER_CELL
    nbytes = F32 * (rows * g.L + g.n_lists * g.L + g.M * g.K * g.S) + 4 * rows * g.M
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float, peak: dict):
    """Percent of the roofline bound achieved, and which bound it is."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
