"""Request-coalescing query batcher.

Queries submitted from any number of client threads are merged into one
padded device launch per coalescing window: the first pending request
opens a window of ``ServeConfig.coalesce_window_s``, every request
arriving before it closes (or before the batch reaches the largest
bucket) joins the batch, and the batch launches at the smallest
``q_buckets`` size that fits — real rows flagged by a ``q_valid`` mask,
exactly like the sharded planner's padded query blocks.  Because the
launch shapes are drawn from the finite bucket family, a warmed server
answers arbitrary mixed traffic from a handful of compiled executables;
``tests/test_serving.py`` asserts (via the trace-time dispatch counters)
that steady-state traffic triggers zero new compilations.

The coalescer is index-agnostic: it owns request queuing and padding and
delegates the actual search to a ``run_batch(Q_padded, q_valid, n_real)``
callable (the server's, which binds the current :class:`~repro.
serve_index.view.IndexView`).  A failed batch fails every request in it;
later batches are unaffected.

Every request carries a process-wide request id and every batch a batch
id.  With obs enabled the coalescer thread's phases are spans
(``serving.coalesce`` while it waits in :meth:`QueryCoalescer._take_batch`,
``serving.batch_search`` around the launch, ``serving.deliver`` while it
slices results and resolves futures), the launch annotation carries the
batch's ids, and a completion watcher thread times each batch's result
becoming ready on the device without the coalescer waiting for it.  All
timestamps are ``time.perf_counter()``, the clock of the obs spans.  With
obs disabled none of this reads a clock, starts a thread or syncs.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .. import obs
from .config import ServeConfig

__all__ = ["QueryCoalescer"]


_REQUEST_IDS = itertools.count()
_BATCH_IDS = itertools.count()

# in-flight depth: a pipelined server keeps a few batches queued on the
# device; more than the largest bound reads as overflow
_DEPTH_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 16.0)


class _Pending:
    __slots__ = ("Q", "future", "t_submit", "rid")

    def __init__(self, Q: np.ndarray, future: Future):
        self.Q = Q
        self.future = future
        self.t_submit = time.perf_counter()
        self.rid = next(_REQUEST_IDS)


class _Watcher:
    """Completion watcher: one thread that blocks on each dispatched
    batch's result in dispatch order and records when it was ready.

    It exists only while obs is on, never runs on the coalescer thread,
    and only observes: futures resolve from the coalescer as they do with
    obs off.  ``dispatched`` is written by the coalescer thread alone and
    ``ready`` by this thread alone, so their difference is the number of
    batches in flight without a lock."""

    def __init__(self):
        self.dispatched = 0
        self.ready = 0
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._loop, name="repro-serve-watcher", daemon=True)
        self._thread.start()

    def watch(self, arrays, t_returned: float, t_submits: List[float]) -> None:
        """Hand over one batch just dispatched (coalescer thread)."""
        self.dispatched += 1
        depth = self.dispatched - self.ready
        obs.gauge("serving_batches_in_flight", persistent=True).set(depth)
        obs.histogram("serving_inflight_depth", persistent=True,
                      buckets=_DEPTH_BUCKETS).record(depth)
        self._q.put((arrays, t_returned, t_submits))

    def close(self, join: bool) -> None:
        """Stop after the batches already handed over."""
        self._q.put(None)
        if join:
            self._thread.join()

    def _loop(self) -> None:
        while (item := self._q.get()) is not None:
            arrays, t_returned, t_submits = item
            try:
                obs.wait(arrays)
                failed = False
            except Exception:                 # noqa: BLE001 - the futures
                failed = True                 # carry the batch's error
            t_ready = time.perf_counter()
            self.ready += 1
            obs.gauge("serving_batches_in_flight", persistent=True).set(
                self.dispatched - self.ready)
            if failed:
                continue
            obs.histogram("serving_batch_inflight_seconds",
                          persistent=True).record(t_ready - t_returned)
            req_h = obs.histogram("serving_request_seconds", persistent=True)
            for t in t_submits:
                req_h.record(t_ready - t)


def _chain_chunks(futures: List[Future]) -> Future:
    """One future resolving to the row-concatenation of chunk futures
    (for requests larger than the largest bucket)."""
    out: Future = Future()
    remaining = [len(futures)]
    lock = threading.Lock()

    def done(_):
        with lock:
            remaining[0] -= 1
            if remaining[0]:
                return
        try:
            parts = [f.result() for f in futures]
        except BaseException as e:           # noqa: BLE001 - forwarded
            out.set_exception(e)
            return
        first = parts[0]
        out.set_result(first._replace(
            dist=jnp.concatenate([p.dist for p in parts], axis=0),
            ids=jnp.concatenate([p.ids for p in parts], axis=0),
            version=min(p.version for p in parts)))

    for f in futures:
        f.add_done_callback(done)
    return out


class QueryCoalescer:
    """Batches concurrent search requests into bucketed padded launches."""

    def __init__(self, run_batch: Callable, cfg: ServeConfig):
        self._run_batch = run_batch
        self.cfg = cfg
        self._pending: List[_Pending] = []
        self._pending_rows = 0
        self._cond = threading.Condition()
        self._stop = False
        self._watcher: Optional[_Watcher] = None   # coalescer thread only
        self._thread: threading.Thread = threading.Thread(
            target=self._loop, name="repro-serve-coalescer", daemon=True)

    # -- client side ---------------------------------------------------------

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        """Stop the worker; already-queued requests are still answered."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join()
        if self._watcher is not None:
            self._watcher.close(join=True)
            self._watcher = None

    def submit(self, Q: np.ndarray) -> Future:
        """Enqueue ``Q (n, D)``; resolves to a ``SearchResult``.  Requests
        wider than the largest bucket are split into bucket-sized chunks
        (their results re-concatenated transparently)."""
        maxb = self.cfg.max_batch
        if Q.shape[0] > maxb:
            futs = [self._submit_one(Q[i:i + maxb])
                    for i in range(0, Q.shape[0], maxb)]
            return _chain_chunks(futs)
        return self._submit_one(Q)

    def _submit_one(self, Q: np.ndarray) -> Future:
        fut: Future = Future()
        with self._cond:
            if self._stop:
                raise RuntimeError("coalescer is stopped")
            self._pending.append(_Pending(Q, fut))
            self._pending_rows += Q.shape[0]
            if obs.enabled():
                obs.gauge("serving_pending_queries",
                          persistent=True).set(self._pending_rows)
            self._cond.notify_all()
        return fut

    # -- worker side ---------------------------------------------------------

    def _take_batch(self) -> List[_Pending]:
        """Block until a batch is ready (window elapsed or bucket full);
        returns [] only when stopping with nothing queued."""
        maxb = self.cfg.max_batch
        with self._cond:
            while not self._pending and not self._stop:
                self._cond.wait()
            if not self._pending:
                return []
            deadline = self._pending[0].t_submit + self.cfg.coalesce_window_s
            while (not self._stop and self._pending_rows < maxb
                   and (left := deadline - time.perf_counter()) > 0):
                self._cond.wait(timeout=left)
            batch, rows = [], 0
            while self._pending and rows + self._pending[0].Q.shape[0] <= maxb:
                p = self._pending.pop(0)
                rows += p.Q.shape[0]
                batch.append(p)
            self._pending_rows -= rows
            if obs.enabled():
                obs.gauge("serving_pending_queries",
                          persistent=True).set(self._pending_rows)
            return batch

    def _loop(self) -> None:
        while True:
            t_wait = time.perf_counter() if obs.enabled() else None
            with obs.span("serving.coalesce"):
                batch = self._take_batch()
            if t_wait is not None and obs.enabled():
                obs.counter("serving_coalescer_idle_seconds",
                            persistent=True).inc(time.perf_counter() - t_wait)
            if not batch:
                return                        # stopped and drained
            watcher = self._current_watcher()
            if watcher is None:
                self._execute(batch, None)
                continue
            t_exec = time.perf_counter()
            self._execute(batch, watcher)
            obs.counter("serving_coalescer_busy_seconds",
                        persistent=True).inc(time.perf_counter() - t_exec)

    def _current_watcher(self) -> Optional[_Watcher]:
        """The completion watcher while obs is on, started on the first
        batch that needs it; with obs off, None (and any watcher left from
        an earlier traced stretch stops once it has drained)."""
        if obs.enabled():
            if self._watcher is None:
                self._watcher = _Watcher()
        elif self._watcher is not None:
            self._watcher.close(join=False)   # joining here would sync
            self._watcher = None
        return self._watcher

    def _execute(self, batch: List[_Pending],
                 watcher: Optional[_Watcher]) -> None:
        """Launch one batch and resolve its futures; ``watcher`` is given
        exactly when obs is on."""
        n_real = sum(p.Q.shape[0] for p in batch)
        bucket = self.cfg.bucket_for(n_real)
        D = batch[0].Q.shape[1]
        Qp = np.zeros((bucket, D), np.float32)
        Qp[:n_real] = np.concatenate([p.Q for p in batch], axis=0)
        q_valid = np.arange(bucket) < n_real
        batch_id = next(_BATCH_IDS)
        meta = {}
        if watcher is not None:
            t_launch = time.perf_counter()
            wait_h = obs.histogram("serving_coalesce_wait_seconds",
                                   persistent=True)
            for p in batch:
                wait_h.record(t_launch - p.t_submit)
            meta = dict(batch_id=batch_id, n_real=n_real, bucket=bucket,
                        first_request=batch[0].rid,
                        oldest_wait_ms=(t_launch - batch[0].t_submit) * 1e3)
        try:
            with obs.span("serving.batch_search", **meta) as sp:
                result = self._run_batch(jnp.asarray(Qp),
                                         jnp.asarray(q_valid), n_real)
                if watcher is not None:
                    t_returned = time.perf_counter()
                    sp.annotate(version=result.version)
                sp.fence((result.dist, result.ids))
        except BaseException as e:            # noqa: BLE001 - forwarded
            for p in batch:
                p.future.set_exception(e)
            return
        if watcher is not None:
            obs.histogram("serving_batch_dispatch_seconds",
                          persistent=True).record(t_returned - t_launch)
            watcher.watch((result.dist, result.ids), t_returned,
                          [p.t_submit for p in batch])
            obs.counter("serving_batches_total", persistent=True,
                        bucket=str(bucket)).inc()
            obs.counter("serving_queries_total", persistent=True).inc(n_real)
            # bucket bounds derive from q_buckets, so the layout is part
            # of the metric identity: servers with different configs in
            # one process get distinct series instead of a get-or-create
            # bucket-mismatch error in the coalescer thread
            obs.histogram("serving_batch_queries", persistent=True,
                          q_buckets=",".join(map(str, self.cfg.q_buckets)),
                          buckets=tuple(float(b) for b in
                                        self.cfg.q_buckets)).record(n_real)
        with obs.span("serving.deliver"):
            row = 0
            for p in batch:
                n = p.Q.shape[0]
                p.future.set_result(result._replace(
                    dist=result.dist[row:row + n],
                    ids=result.ids[row:row + n]))
                row += n
