"""The comparison that decides ``correct``.

After the window, a sample of the answered requests (drawn from the seed)
is checked against the plain reference (``bench/reference.py``) and a
replay of the write log:

* the replay gives, for the view version each answer names, which rows
  are live, which sit in the hot buffer and which are sealed;
* a returned hot row must carry its exact banded DTW distance; a returned
  sealed row must sit in a list the query probes and carry its ADC
  distance over the codes the paper's encoder gives it;
* no live hot row, and no row of a sampled set of sealed rows, that ranks
  better than the answer's last neighbour may be missing;
* a returned row must be live in that version, listed once, in order;
* in cells with writers, a search sent after a write was acknowledged
  must answer from a version that holds the write.

Numbers compared (each beside its limit from the traffic file):
``dist_gap`` (largest relative gap of a returned distance from the
reference), ``missed``, ``bad``, ``stale`` and ``never`` (counts).
Near-ties are resolved in the program's favour within ``TIE``: a code,
list or probe that is within ``TIE`` of the best is admissible.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import reference as R

TIE = 1e-5  # relative width of a near-tie between two candidates
BIG = np.iinfo(np.int64).max


class Replay:
    """Per-row op indices from the write log: when each row was inserted,
    sealed into a segment, and deleted.  A row is in that state at a
    version whose prefix of applied ops exceeds the index."""

    def __init__(self, ops, capacity: int, n_ids: int):
        self.inserted = np.full(n_ids, BIG, np.int64)
        self.sealed = np.full(n_ids, BIG, np.int64)
        self.deleted = np.full(n_ids, BIG, np.int64)
        hot: list = []  # ids in slot order, tombstoned ones included
        for i, op in enumerate(ops):
            if op.kind == "insert":
                for x in op.ids.tolist():
                    self.inserted[x] = i
                    hot.append(x)
                    if len(hot) == capacity:  # a full buffer seals at once
                        for y in hot:
                            if self.deleted[y] == BIG:
                                self.sealed[y] = i
                        hot = []
            elif op.kind == "delete":
                for x in op.ids.tolist():
                    if self.inserted[x] < i and self.deleted[x] == BIG:
                        self.deleted[x] = i
            elif op.kind == "flush":
                for y in hot:
                    if self.deleted[y] == BIG:
                        self.sealed[y] = i
                hot = []

    def live(self, p: int) -> np.ndarray:
        return (self.inserted < p) & (self.deleted >= p)

    def hot_live(self, p: int) -> np.ndarray:
        return np.flatnonzero(self.live(p) & (self.sealed >= p))

    def is_sealed(self, p: int) -> np.ndarray:
        return self.sealed < p


class Geometry:
    """The sizes the program derives from its configuration, worked out
    again from the configuration's own numbers."""

    def __init__(self, cfg: dict):
        d, pq, ivf = cfg["data"], cfg["pq"], cfg["ivf"]
        self.L = d["length"]
        self.M = pq["n_sub"]
        self.K = pq["codebook_size"]
        self.tail = max(1, int(round(pq["tail_frac"] * (self.L // self.M))))
        self.S = self.L // self.M + self.tail
        self.w = max(1, int(round(pq["window_frac"] * self.S)))
        self.wc = max(1, int(round(ivf["coarse_window_frac"] * self.L)))
        self.level = pq["wavelet_level"]
        self.T = max(1, int(round(pq["refine_frac"] * self.K)))
        self.n_lists = ivf["n_lists"]
        self.n_probe = cfg["serving"]["n_probe"]
        self.topk = cfg["serving"]["topk"]


def _codes(g: Geometry, X, quant, dtype):
    """Admissible codes ``(N, M, K)`` and per-code squared distances of
    ``X``'s segments under the paper's filter-then-refine encoder: the
    ``T`` centroids of least lower bound, then the DTW-nearest of them."""
    segs = R.prealign(jnp.asarray(X), n_sub=g.M, level=g.level, tail=g.tail)
    lb = np.asarray(R.lower_bounds(segs, quant["cents"], quant["upper"], quant["lower"]))
    d = np.stack(
        [R.dtw_cdist(segs[:, m], quant["cents"][m], g.w, dtype) for m in range(g.M)],
        axis=1,
    )  # (N, M, K)
    lbt = np.sort(lb, axis=-1)[..., g.T - 1 : g.T]
    sure = lb < lbt * (1 - TIE) - 1e-12
    maybe = lb <= lbt * (1 + TIE) + 1e-12
    m_sure = np.where(sure, d, np.inf).min(-1, keepdims=True)
    m_maybe = np.where(maybe, d, np.inf).min(-1, keepdims=True)
    best = np.where(np.isfinite(m_sure), m_sure, m_maybe)
    adm = maybe & (d <= best * (1 + TIE) + 1e-12)
    if not adm.any(-1).all():
        raise RuntimeError("a segment has no admissible code")
    return adm


def _lists(coarse_d):
    """Admissible lists of rows: within ``TIE`` of the nearest."""
    best = coarse_d.min(-1, keepdims=True)
    return coarse_d <= best * (1 + TIE) + 1e-12


def compare(g: Geometry, quant, rows_of, samples, replay, prefix, sample_rows,
            substitute=None):
    """Check ``samples`` (dicts with ``q``, ``version``, ``dist``, ``ids``).

    ``substitute(samples, Q, hot_sets, prefixes)`` may replace the answers before they are
    judged (the control puts a lower-precision reference in the program's
    place).  Returns the numbers compared.
    """
    Q = np.stack([s["q"] for s in samples]).astype(np.float32)
    n = len(samples)
    qc = R.dtw_cdist(Q, quant["coarse"], g.wc)  # (n, n_lists) squared
    qsegs = R.prealign(jnp.asarray(Q), n_sub=g.M, level=g.level, tail=g.tail)
    qlut = np.stack(
        [R.dtw_cdist(qsegs[:, m], quant["cents"][m], g.w) for m in range(g.M)], axis=1
    )  # (n, M, K)
    srt = np.sort(qc, -1)[:, g.n_probe - 1 : g.n_probe]
    probe_sure = qc < srt * (1 - TIE)
    probe_maybe = qc <= srt * (1 + TIE)

    # rows whose codes and lists the check needs
    ps = [prefix[s["version"]] for s in samples]
    need = set(sample_rows.tolist())
    hot_sets = []
    for s, p in zip(samples, ps):
        hot_sets.append(replay.hot_live(p))
        sealed = replay.is_sealed(p)
        need.update(x for x in s["ids"].tolist() if 0 <= x < len(sealed) and sealed[x])
    need = np.array(sorted(need), np.int64)
    Xn = rows_of(need)
    adm_codes = _codes(g, Xn, quant, jnp.float32)
    adm_lists = _lists(R.dtw_cdist(Xn, quant["coarse"], g.wc))
    where = {int(x): i for i, x in enumerate(need)}

    # exact DTW of every (query, live hot row) pair
    pa = np.concatenate([np.full(len(h), i) for i, h in enumerate(hot_sets)])
    pb = np.concatenate(hot_sets)
    hot_d = np.sqrt(np.maximum(R.dtw_pairs(Q[pa], rows_of(pb), g.wc), 0.0))
    hot_ref = dict(zip(zip(pa.tolist(), pb.tolist()), hot_d.tolist()))

    if substitute is not None:
        samples = substitute(samples, Q, hot_sets, ps)

    srows = np.array([where[x] for x in sample_rows.tolist()], np.int64)
    gap = 0.0
    missed = bad = 0
    for i, (s, p) in enumerate(zip(samples, ps)):
        d, ids = np.asarray(s["dist"], np.float64), np.asarray(s["ids"])
        live = replay.live(p)
        sealed = replay.is_sealed(p)
        # ADC interval of every needed row over its admissible codes
        lo = np.sqrt(np.maximum(np.where(adm_codes, qlut[i], np.inf).min(-1).sum(-1), 0.0))
        hi = np.sqrt(np.maximum(np.where(adm_codes, qlut[i], -np.inf).max(-1).sum(-1), 0.0))
        if np.any(np.diff(d) < -TIE * np.abs(d[1:])) or len(set(ids.tolist())) != len(ids):
            bad += 1
        for dr, x in zip(d.tolist(), ids.tolist()):
            if x < 0 or x >= len(live) or not live[x]:
                bad += 1
            elif not sealed[x]:
                r = hot_ref[(i, x)]
                gap = max(gap, abs(dr - r) / max(r, 1e-30))
            elif not (adm_lists[where[x]] & probe_maybe[i]).any():
                bad += 1
            else:
                j = where[x]
                gap = max(gap, max(0.0, lo[j] - dr, dr - hi[j]) / max(lo[j], 1e-30))
        tau = d[-1] * (1 - TIE)
        got = np.isin(hot_sets[i], ids)
        hd = np.array([hot_ref[(i, x)] for x in hot_sets[i].tolist()])
        missed += int(np.sum(~got & (hd < tau)))
        # sampled sealed rows whose every admissible list is surely probed
        x = sample_rows
        cand = live[x] & sealed[x] & ~np.isin(x, ids)
        cand &= ~(adm_lists[srows] & ~probe_sure[i]).any(-1)
        missed += int(np.sum(cand & (hi[srows] < tau)))
    return {"dist_gap": float(gap), "missed": missed, "bad": bad, "checked": n}


def control_substitute(g: Geometry, quant, rows_of, replay):
    """The control: the reference in bfloat16, put in the program's place.
    Each returned row keeps its id and takes the distance the bfloat16
    reference gives it; the answer is re-sorted."""
    bf = jnp.bfloat16

    def sub(samples, Q, hot_sets, ps):
        out = []
        qsegs = R.prealign(jnp.asarray(Q), n_sub=g.M, level=g.level, tail=g.tail)
        qlut = np.stack(
            [R.dtw_cdist(qsegs[:, m], quant["cents"][m], g.w, bf) for m in range(g.M)], axis=1
        )
        for i, (s, p) in enumerate(zip(samples, ps)):
            ids = np.asarray(s["ids"])
            keep = ids >= 0
            x = ids[keep]
            X = rows_of(x)
            sealed = replay.is_sealed(p)[x]
            d = np.sqrt(np.maximum(R.dtw_pairs(np.repeat(Q[i : i + 1], len(x), 0), X, g.wc, bf), 0.0))
            if sealed.any():
                adm = _codes(g, X[sealed], quant, bf)
                first = adm.argmax(-1)  # (n, M) one admissible code each
                lut = qlut[i][np.arange(g.M)[None, :], first]
                d[sealed] = np.sqrt(np.maximum(lut.sum(-1), 0.0))
            order = np.argsort(d, kind="stable")
            out.append(dict(s, dist=d[order], ids=x[order]))
        return out

    return sub
