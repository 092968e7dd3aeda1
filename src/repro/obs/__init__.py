"""Observability for the serving stack (DESIGN.md §17): one
``MetricsRegistry`` + one bounded ``SpanRecorder`` per engine, bundled
as an ``Observability`` object, with a threadlocal ambient context so
library layers (``core.engine``, ``core.device_cache``) record spans and
counters without threading an ``obs`` argument through the
``CandidateSource`` protocol.

Spans default **off** — every engine gets a registry (the ``stats``
views need one) but span recording costs nothing unless requested:

    eng = GraphQueryEngine(flat, obs=Observability(spans=True))
    ...
    eng.obs.export_trace("query.trace.json")

Compiles are counted too: once JAX is loaded, ``use_obs`` installs one
process-wide ``jax.monitoring`` listener that adds each backend compile
to the compiling thread's ambient registry (``engine.compiles``) and,
with spans on, records it as a ``compile`` span.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Optional

from repro.obs.metrics import (DEFAULT_BUCKETS, Histogram, MetricsRegistry,
                               StatsView)
from repro.obs.spans import Span, SpanRecorder

__all__ = ["DEFAULT_BUCKETS", "Histogram", "MetricsRegistry", "StatsView",
           "Span", "SpanRecorder", "Observability", "current_obs",
           "use_obs", "ambient_span", "ambient_count"]


class Observability:
    """One engine's metrics registry + span ring (DESIGN.md §17)."""

    def __init__(self, *, spans: bool = False, span_capacity: int = 65536,
                 metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.spans = SpanRecorder(capacity=span_capacity, enabled=spans)

    def span(self, name: str, *, qid=None, **args):
        return self.spans.span(name, qid=qid, **args)

    def export_trace(self, path: str) -> str:
        from repro.obs.export import write_trace
        return write_trace(path, self)


_tl = threading.local()


def current_obs() -> Optional[Observability]:
    """The ambient ``Observability`` set by ``use_obs`` on this thread
    (None outside any engine's filter pass)."""
    return getattr(_tl, "obs", None)


@contextlib.contextmanager
def use_obs(obs: Optional[Observability]):
    """Make ``obs`` the ambient context for the with-block.  The serving
    engine wraps its filter stage in this so ``core.engine`` records
    bucket / filter / assign_lb spans without an API change; restores
    the previous context on exit (re-entrant)."""
    if not _compile_listener_on and "jax" in sys.modules:
        _install_compile_listener()
    prev = getattr(_tl, "obs", None)
    _tl.obs = obs
    try:
        yield obs
    finally:
        _tl.obs = prev


def ambient_span(name: str, **args):
    """A child span on the ambient obs (``SpanRecorder.span``, so it
    carries ``cpu_ms``); with no ambient obs or spans off, a null context
    yielding a throwaway args dict."""
    obs = current_obs()
    if obs is None or not obs.spans.enabled:
        return contextlib.nullcontext({})
    return obs.spans.span(name, **args)


def ambient_count(name: str, value=1) -> None:
    """Add ``value`` to counter ``name`` of the ambient registry (a no-op
    outside ``use_obs``)."""
    obs = current_obs()
    if obs is not None:
        obs.metrics.counter_add(name, value)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener_on = False           # guarded_by: _compile_listener_lock
_compile_listener_lock = threading.Lock()


def _on_compile(event: str, duration_s: float, **_kw) -> None:
    """``jax.monitoring`` duration listener: runs on the compiling
    thread, right after the compile, so the span ends now."""
    if event != _COMPILE_EVENT:
        return
    obs = current_obs()
    if obs is None:
        return
    obs.metrics.counter_add("engine.compiles")
    if obs.spans.enabled:
        t1 = time.perf_counter()
        obs.spans.record("compile", t1 - duration_s, t1)


def _install_compile_listener() -> None:
    global _compile_listener_on
    with _compile_listener_lock:
        if _compile_listener_on:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_compile)
        _compile_listener_on = True
