"""Profiling spans: wall-clock timers feeding latency histograms, on the
profiler's clock.

``span("collector_drain")`` times a region and observes the duration into
the ``torr_span_duration_seconds{span="collector_drain"}`` histogram of a
:class:`~repro.obs.metrics.MetricsRegistry`. The engines wrap their host
phases with these — host decide/assemble, dispatcher enqueue, the
dispatcher's wait for backlog, the device wait, collector drain, host
observe — and the gateway its request decode and response encode, so the
host/device time split is readable live off ``/metrics``.

Every span also enters a ``jax.profiler.TraceAnnotation`` under its own
name, in both forms: when a profiler trace is running the program's host
phases land in it on the same clock as the device ops, nested as the
spans nest, so an idle gap on the device can be read against what the
host was doing. With the profiler off the annotation is one cheap object
per span.

Cost model: one ``perf_counter`` pair, one annotation and one histogram
observe per enter/exit. Engines with no registry or tracer wired use
:data:`NULL_SPAN` instead and pay two empty method calls per phase, so
instrumented code paths stay below the 3% overhead gate even when
observability is off.

Causal linking: every span exit additionally offers its interval to
:func:`repro.obs.trace.record_span` — when a :class:`~repro.obs.trace.
trace_scope` is active on the thread, the span is stamped onto the
in-flight windows' :class:`~repro.obs.trace.TraceContext`\\ s, turning the
histogram's anonymous samples into causally-linked per-window events.
With no scope active the hook is one thread-local ``getattr``.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from .metrics import LATENCY_BUCKETS_S, MetricsRegistry
from .trace import record_span

SPAN_METRIC = "torr_span_duration_seconds"


class _NullSpan:
    """Do-nothing span for uninstrumented engines: the hot path pays two
    empty method calls per phase, nothing else (no clock, no annotation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class span:
    """Context manager / decorator timing one named region.

    ``with span("collector_drain", registry): ...`` or::

        @span("host_decide", registry)
        def decide(...): ...

    The decorator form is thread-safe (per-call start times and
    annotations live on the call frame). A context-manager *instance*
    holds its start time and annotation, so don't share one instance
    across threads — construct per use, or keep one per single-threaded
    phase (what the engines do); construction after the first call is just
    a dict hit in the registry.
    """

    __slots__ = ("name", "_hist", "_t0", "_ann")

    def __init__(self, name: str, registry: Optional[MetricsRegistry] = None):
        self.name = name
        if registry is None:
            self._hist = None
        else:
            self._hist = registry.histogram(
                SPAN_METRIC,
                "Wall-clock duration of instrumented serving phases.",
                ["span"], buckets=LATENCY_BUCKETS_S,
            ).labels(span=name)
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        if self._hist is not None:
            self._hist.observe(dur)
        record_span(self.name, self._t0, dur)
        return False

    def __call__(self, fn):
        # decorator form: a fresh enter/exit per call, shared histogram
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with TraceAnnotation(self.name):
                t0 = time.perf_counter()
                try:
                    return fn(*args, **kwargs)
                finally:
                    dur = time.perf_counter() - t0
                    if self._hist is not None:
                        self._hist.observe(dur)
                    record_span(self.name, t0, dur)
        return wrapper
