"""Traffic kinds and the client-side records they leave.

A traffic file (``bench/traffic/<name>.json``) lists streams; each names
one of the kinds below, so a new mix is a data file.  All kinds run from
the seed on host threads against one ``IndexServer``:

* ``open_loop``   independent users: single- or multi-query requests due
  at a fixed rate, the count fixed by rate x seconds and the due times
  drawn uniformly over the window (a Poisson process conditioned on its
  count).  A request's latency runs from when it was due to when its
  answer is on the host.
* ``closed_loop`` callers that each wait for their answer before the
  next request.
* ``writers``     writes due at a fixed rate (drawn as for ``open_loop``): inserts of fresh series,
  every ``delete_every``-th op a delete of the oldest resident ids
  (retention).  A write's latency runs from when it was due to its
  acknowledgement, which the server gives after the publish that makes
  it visible.

Answers resolve as device arrays that may not be ready yet: one FIFO
waiter per stream blocks on them in launch order and copies them to the
host, and that instant ends the request.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

GRACE_S = 60.0  # how long answers due in the window are waited for after it


@dataclass
class Op:
    """One write submitted through the server, in queue order."""

    kind: str  # insert | delete | compact
    ids: Optional[np.ndarray] = None
    rows: Optional[np.ndarray] = None
    t_submit: float = 0.0
    t_ack: Optional[float] = None
    future: object = None
    shed: bool = False
    error: Optional[str] = None
    due: float = 0.0  # seconds after the window opened (window writes)


def _acked(op: Op, fut) -> None:
    """Runs on the server's writer thread right after the publish."""
    op.t_ack = time.perf_counter()
    if fut.exception() is not None:
        op.error = repr(fut.exception())
        op.t_ack = None


class OpLog:
    """Every write goes through :meth:`submit`, under one lock, so the log's
    order is the server's queue order.  ``on_publish`` (the server's hook)
    records how many logged ops each published version holds."""

    def __init__(self):
        self.lock = threading.Lock()
        self.ops: List[Op] = []
        self.applied = 0
        self.prefix = {0: 0}  # view version -> ops applied in it

    def submit(self, srv, op: Op) -> Op:
        from repro.serve_index import Backpressure

        with self.lock:
            op.t_submit = time.perf_counter()
            try:
                if op.kind == "insert":
                    op.future = srv.insert(op.rows, op.ids)
                elif op.kind == "delete":
                    op.future = srv.delete(op.ids)
                elif op.kind == "compact":
                    op.future = srv.compact()
                else:
                    raise ValueError(f"unknown write kind {op.kind!r}")
            except Backpressure:
                op.shed = True
                return op
            self.ops.append(op)
        op.future.add_done_callback(lambda f, op=op: _acked(op, f))
        return op

    def on_publish(self, view) -> None:
        # Futures resolve after the publish of their batch, so at the
        # publish of version v exactly the ops of versions < v are done.
        with self.lock:
            while self.applied < len(self.ops) and self.ops[self.applied].future.done():
                self.applied += 1
            self.prefix[view.version - 1] = self.applied

    def close(self, final_version: int) -> None:
        with self.lock:
            self.prefix[final_version] = len(self.ops)


@dataclass
class Request:
    stream: str
    q: np.ndarray  # (n, L) query series
    src: np.ndarray  # (n,) the archive row each query was made from
    due: float
    sent: float = 0.0
    done: Optional[float] = None
    version: int = -1
    dist: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None


@dataclass
class Stream:
    name: str
    kind: str
    spec: dict
    requests: List[Request] = field(default_factory=list)
    writes: List[Op] = field(default_factory=list)
    threads: List[threading.Thread] = field(default_factory=list)


def _dues(spec: dict, seconds: float, n: int, rng) -> np.ndarray:
    """Due times of a stream's ``n`` requests in the window: drawn uniformly
    (a Poisson process conditioned on its count), or with ``"arrivals":
    "periodic"`` evenly spaced, the same for every seed."""
    if spec.get("arrivals", "poisson") == "periodic":
        return (np.arange(n) + 0.5) * (seconds / max(n, 1))
    return np.sort(rng.uniform(0.0, seconds, n))


def _waiter(items: "queue.Queue", deadline_fn):
    import jax

    while True:
        item = items.get()
        if item is None:
            return
        req, fut = item
        try:
            left = max(1.0, deadline_fn() - time.perf_counter())
            r = fut.result(timeout=left)
            with jax.profiler.TraceAnnotation("bench.answer_to_host"):
                jax.block_until_ready((r.dist, r.ids))
                req.dist = np.asarray(r.dist)
                req.ids = np.asarray(r.ids)
            req.version = int(r.version)
            req.done = time.perf_counter()
        except Exception as e:  # noqa: BLE001 - recorded as a failed request
            req.error = f"{type(e).__name__}: {e}"


class Traffic:
    """The streams of one traffic file, started together for one window."""

    def __init__(self, spec: dict, seed: int, pool, pool_src, rows_of=None, noise=0.0):
        self.streams = [Stream(s["name"], s["kind"], s) for s in spec["streams"]]
        self.seed = seed
        self.pool, self.pool_src = pool, pool_src
        self.n_pool = len(pool)
        self.rows_of, self.noise = rows_of, noise
        self.writer = None
        self.t0 = 0.0
        self.t_end = 0.0
        self.stop = threading.Event()

    def _grace_end(self) -> float:
        return self.t_end + GRACE_S

    # -- kinds ----------------------------------------------------------------

    def _open_loop(self, st: Stream, srv, seconds: float, rng):
        n_req = int(round(st.spec["rate_per_s"] * seconds))
        nq = int(st.spec.get("queries_per_request", 1))
        dues = _dues(st.spec, seconds, n_req, rng)
        qidx = rng.integers(0, self.n_pool, (n_req, nq))
        # a share of lookups asks for a series of the newest acknowledged
        # insert (plus the same noise as the pool's queries)
        recent = rng.random(n_req) < st.spec.get("recent_share", 0.0)
        pick = rng.random(n_req)
        noise = rng.standard_normal((n_req, self.pool.shape[1])).astype(np.float32)
        st.requests = [Request(st.name, self.pool[qidx[i]], self.pool_src[qidx[i]],
                               float(dues[i])) for i in range(n_req)]
        items: "queue.Queue" = queue.Queue()
        waiter = threading.Thread(target=_waiter, args=(items, self._grace_end))

        def gen():
            import jax

            for i, req in enumerate(st.requests):
                due = self.t0 + req.due
                while True:
                    left = due - time.perf_counter()
                    if left <= 0:
                        break
                    time.sleep(min(left, 0.05))
                ids = self.writer.last_acked if recent[i] and self.writer else None
                if ids is not None:
                    x = ids[int(pick[i] * len(ids))]
                    req.src = np.array([x])
                    req.q = self.rows_of(req.src) + self.noise * noise[i]
                req.sent = time.perf_counter()
                try:
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        fut = srv.submit_search(req.q)
                    items.put((req, fut))
                except Exception as e:  # noqa: BLE001 - a refused request
                    req.error = f"{type(e).__name__}: {e}"
            items.put(None)

        return [threading.Thread(target=gen), waiter]

    def _closed_loop(self, st: Stream, srv, seconds: float, rng):
        nq = int(st.spec["queries_per_request"])
        lock = threading.Lock()

        def client(c):
            import jax

            crng = np.random.default_rng([self.seed, 11, c])
            while not self.stop.is_set():
                qi = crng.integers(0, self.n_pool, nq)
                req = Request(st.name, self.pool[qi], self.pool_src[qi], 0.0)
                req.sent = time.perf_counter()
                req.due = req.sent - self.t0
                with lock:
                    st.requests.append(req)
                try:
                    r = srv.submit_search(req.q).result(timeout=GRACE_S)
                    jax.block_until_ready((r.dist, r.ids))
                    req.dist, req.ids = np.asarray(r.dist), np.asarray(r.ids)
                    req.version = int(r.version)
                    req.done = time.perf_counter()
                except Exception as e:  # noqa: BLE001 - a failed request
                    req.error = f"{type(e).__name__}: {e}"

        return [threading.Thread(target=client, args=(c,)) for c in range(st.spec["clients"])]

    def _writers(self, st: Stream, srv, seconds: float, rng):
        """Writes due at a fixed rate (``rate_ops_per_s``), submitted in
        order by one thread; each is acknowledged when its future resolves,
        which the server does after the publish that makes it visible."""
        w = self.writer
        n_ops = int(round(st.spec["rate_ops_per_s"] * seconds))
        dues = _dues(st.spec, seconds, n_ops, rng)

        def gen():
            for due in dues.tolist():
                t = self.t0 + due
                while True:
                    left = t - time.perf_counter()
                    if left <= 0:
                        break
                    time.sleep(min(left, 0.05))
                op = w.next_op()
                op.due = due
                st.writes.append(w.submit(srv, op))

        return [threading.Thread(target=gen)]

    KINDS = {"open_loop": _open_loop, "closed_loop": _closed_loop, "writers": _writers}

    # -- driving ----------------------------------------------------------------

    def run(self, srv, seconds: float, writer=None, on_start=None):
        """Drive every stream for ``seconds``; returns the threads still
        alive ``GRACE_S`` past the window's end (none, normally)."""
        self.writer = writer
        threads = []
        for i, st in enumerate(self.streams):
            if st.kind not in self.KINDS:
                raise ValueError(f"unknown traffic kind {st.kind!r}")
            rng = np.random.default_rng([self.seed, 5, i])
            st.threads = self.KINDS[st.kind](self, st, srv, seconds, rng)
            threads += st.threads
        self.t0 = time.perf_counter() + 0.05
        self.t_end = self.t0 + seconds
        for t in threads:
            t.start()
        if on_start is not None:
            on_start(self)
        time.sleep(max(0.0, self.t_end - time.perf_counter()))
        self.stop.set()
        for t in threads:
            t.join(timeout=max(1.0, self._grace_end() - time.perf_counter()))
        return [t for t in threads if t.is_alive()]


class Writer:
    """The write schedule of a ``writers`` stream: inserts of fresh series,
    every ``delete_every``-th op a delete of the ``delete_rows`` oldest
    resident ids (retention).  Set-up and the window draw from the one
    schedule, so ids and rows continue across them."""

    def __init__(self, spec: dict, log: OpLog, rows_fn, first_id: int, resident):
        self.spec = spec
        self.log = log
        self.rows_fn = rows_fn  # batch index -> (rows, L) float32, from the seed
        self.first_id = first_id
        self.resident = collections.deque(resident)
        self.last_acked = None  # ids of the newest acknowledged insert
        self.n_ops = 0
        self.n_batches = 0
        self.rows_inserted = 0

    def next_op(self) -> Op:
        s = self.spec
        self.n_ops += 1
        if s.get("delete_every") and self.n_ops % s["delete_every"] == 0:
            k = min(s["delete_rows"], len(self.resident))
            ids = np.array([self.resident.popleft() for _ in range(k)], np.int32)
            return Op("delete", ids=ids)
        b = self.n_batches
        self.n_batches += 1
        n = s["batch_rows"]
        ids = np.arange(self.first_id + b * n, self.first_id + (b + 1) * n, dtype=np.int32)
        self.rows_inserted += n
        self.resident.extend(ids.tolist())
        return Op("insert", ids=ids, rows=self.rows_fn(b))

    def submit(self, srv, op: Op) -> Op:
        self.log.submit(srv, op)
        if op.kind == "insert" and not op.shed:
            op.future.add_done_callback(lambda f, ids=op.ids: self._acked(f, ids))
        return op

    def _acked(self, fut, ids) -> None:
        if fut.exception() is None:
            self.last_acked = ids


# ---------------------------------------------------------------------------
# End-to-end statistics over a stream's records
# ---------------------------------------------------------------------------


def percentile(values, q: int = 99) -> float:
    """The ``q``-th percentile (``statistics.quantiles``, inclusive)."""
    import statistics

    v = sorted(values)
    if len(v) < 2:
        return float(v[0]) if v else float("nan")
    return statistics.quantiles(v, n=100, method="inclusive")[q - 1]


def p99(values) -> float:
    return percentile(values, 99)


def latency_ms(st: Stream, traffic: Traffic, q: int) -> float:
    """Due to answer on the host, over every request due in the window; a
    request never answered counts as infinitely late."""
    return percentile(
        [(r.done - (traffic.t0 + r.due)) * 1e3 if r.done is not None else float("inf")
         for r in st.requests],
        q,
    )


def write_latency_ms(st: Stream, traffic: Traffic, q: int) -> float:
    """Due to acknowledged (visible), over every write due in the window;
    a write never acknowledged counts as infinitely late."""
    return percentile(
        [(o.t_ack - (traffic.t0 + o.due)) * 1e3 if o.t_ack is not None else float("inf")
         for o in st.writes],
        q,
    )


def queries_per_s(st: Stream, traffic: Traffic, q=None) -> float:
    """Queries answered on the host within the window, per second of it."""
    n = sum(len(r.q) for r in st.requests if r.done is not None and r.done <= traffic.t_end)
    return n / (traffic.t_end - traffic.t0)


# a traffic file's ``end_to_end`` entry names one of these, its stream and,
# for a latency, its percentile ``q``
STATS = {"latency_ms": latency_ms, "write_latency_ms": write_latency_ms,
         "queries_per_s": queries_per_s}


def counts(traffic: Traffic):
    """``(attempted, failed)`` over every stream: requests and writes due in
    the window; failed are errored, shed or never answered."""
    attempted = failed = 0
    for st in traffic.streams:
        for r in st.requests:
            attempted += 1
            failed += r.done is None
        for o in st.writes:
            if o.kind == "compact":
                continue
            attempted += 1
            failed += o.shed or o.error is not None or o.t_ack is None
    return attempted, failed
