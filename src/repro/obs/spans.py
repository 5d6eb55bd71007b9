"""Pipeline stage spans and the obs on/off switch.

``span("stage")`` times a host-visible pipeline stage into the
``stage_seconds`` histogram of :data:`repro.obs.registry.REGISTRY`
(labeled ``stage=<name>``), and bridges into device profiles through
``jax.profiler.TraceAnnotation`` so the same stage names show up on the
device timeline when a profiler trace is active.

Zero-overhead-by-default is the load-bearing contract (the reason the
spans are safe to leave wired into every layer of the search/ingest
pipeline):

* disabled (the default — enable with ``REPRO_OBS=1`` or
  :func:`enable`), ``span()`` returns a shared no-op context manager:
  no clock reads, no histogram writes, no ``TraceAnnotation``, and —
  critically — :meth:`Span.fence` NEVER calls ``block_until_ready``,
  so no device sync the un-instrumented code would not have done;
* enabled, :meth:`Span.fence` blocks on its argument (skipping tracers:
  fencing inside a traced computation is a no-op by construction), so
  async-dispatched device work is attributed to the span that launched
  it instead of leaking into whichever stage happens to block next;
* enabled with fencing off (``REPRO_OBS_FENCE=0`` or
  ``enable(fence=False)``), spans, annotations and metrics are kept but
  every fence is an identity: the instrumented code dispatches exactly as
  it does with obs disabled, so a pipelined caller stays pipelined.  This
  is the mode to leave on in production; device time per stage then comes
  from the profiler trace (the jitted stage bodies carry
  ``jax.named_scope`` names), not from the span's host time.

Spans nest and re-enter freely: each ``with`` entry pushes onto a
thread-local stack and records its own sample on exit, exceptions
included.  A span opened inside a traced function (e.g. under
``shard_map``) times the *trace*, which runs once per cache entry — real
per-call device time needs the span outside the traced region plus a
fence, which is exactly how the index/planner call sites are written.
"""

from __future__ import annotations

import os
import threading
import time
from typing import List, Optional

import jax

from .registry import REGISTRY

__all__ = ["ENV_VAR", "FENCE_ENV_VAR", "enabled", "fencing", "enable",
           "disable", "override", "span", "current_spans", "fence", "wait",
           "Span"]

ENV_VAR = "REPRO_OBS"
FENCE_ENV_VAR = "REPRO_OBS_FENCE"

_enabled = os.environ.get(ENV_VAR, "0").lower() not in ("", "0", "false")


def _env_fence() -> bool:
    return os.environ.get(FENCE_ENV_VAR, "1").lower() not in ("0", "false")


_fence = _env_fence()

_local = threading.local()

# test seam: monkeypatch to observe/forbid device syncs
_block = jax.block_until_ready


def enabled() -> bool:
    return _enabled


def fencing() -> bool:
    """True when enabled spans block on their device work."""
    return _enabled and _fence


def enable(fence: Optional[bool] = None) -> None:
    """Turn obs on; ``fence`` sets whether spans block on their device
    work (``None``: ``REPRO_OBS_FENCE``, fenced unless it is ``0``)."""
    global _enabled, _fence
    _enabled = True
    _fence = _env_fence() if fence is None else bool(fence)


def disable() -> None:
    global _enabled
    _enabled = False


class override:
    """Scoped enable/disable (tests); ``fence`` as in :func:`enable`."""

    def __init__(self, on: bool, fence: Optional[bool] = None):
        self.on = bool(on)
        self.fence = fence
        self._prev = None

    def __enter__(self):
        global _enabled, _fence
        self._prev = (_enabled, _fence)
        _enabled = self.on
        if self.fence is not None:
            _fence = bool(self.fence)
        return self

    def __exit__(self, *exc):
        global _enabled, _fence
        _enabled, _fence = self._prev
        return False


def _stack() -> List[str]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current_spans() -> tuple:
    """Names of the spans currently open on this thread, outermost first."""
    return tuple(_stack())


def _is_traced(x) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(x))


def fence(x):
    """``jax.block_until_ready(x)`` when obs is enabled with fencing on;
    identity (and in particular no device sync) when disabled, unfenced or
    ``x`` contains tracers."""
    if _enabled and _fence and not _is_traced(x):
        return _block(x)
    return x


def wait(x):
    """Block until ``x`` is ready whatever the switches say: for observers
    that run beside the instrumented code (the serving completion
    watcher), never for the code itself."""
    return _block(x)


class Span:
    """One timed stage entry (enabled path — see :func:`span`)."""

    __slots__ = ("name", "_meta", "_t0", "_annotation")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self._meta = meta
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self):
        _stack().append(self.name)
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self._meta)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] == self.name:
            st.pop()
        REGISTRY.histogram("stage_seconds", persistent=True,
                           stage=self.name).record(dt)
        return False

    def fence(self, x):
        """Block on ``x`` so its device work lands in this span (no-op on
        tracers and with fencing off); returns ``x`` for inline use."""
        return fence(x)

    def annotate(self, **meta) -> None:
        """Add keyword arguments known only inside the span to its trace
        annotation (the profiler keeps them as the event's arguments)."""
        self._annotation.set_metadata(**meta)


class _NullSpan:
    """Disabled path: one shared immutable no-op for every span() call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @staticmethod
    def fence(x):
        return x

    @staticmethod
    def annotate(**meta) -> None:
        pass


_NULL_SPAN = _NullSpan()


def span(name: str, **meta):
    """Context manager timing stage ``name`` (module docstring); ``meta``
    becomes the keyword arguments of its trace annotation."""
    if not _enabled:
        return _NULL_SPAN
    return Span(name, meta)
