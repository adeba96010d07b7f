"""repro.obs — the observability substrate (tracing + metrics).

WARP's performance story is a per-stage latency decomposition; this
package makes that decomposition *always available* instead of living in
one-off benchmark scripts. Two primitives:

- ``obs.trace`` — request-scoped span tracing (context-manager spans,
  injectable clock, bounded ring buffer, Chrome trace-event export for
  Perfetto).
- ``obs.metrics`` — a process-wide registry of counters / gauges /
  fixed-bucket histograms with Prometheus text + JSON snapshot
  exposition, plus the repo's single definition of ``time_fn`` and
  ``percentiles``.

Runtime state is a tri-level switch held in ``STATE``:

  disabled (default)   instrumented hot paths pay one attribute check
                       (``STATE.tracer is None`` / ``STATE.metrics is
                       None``) plus, per span, one check whether a
                       profiler session records — measured < 2% on the
                       retrieve path (``benchmarks/bench_obs.py`` ->
                       BENCH_obs.json).
  metrics              ``enable_metrics()``: counters/histograms record;
                       no spans, no forced synchronization beyond the
                       retrieve-latency block.
  tracing              ``set_tracer(Tracer(...))``: spans into the
                       tracer's ring buffer. The program is the same one
                       that runs untraced (no fences, no other compiled
                       callable); engine stage times come from the
                       device trace, where each op carries its stage's
                       ``warp.*`` named scope.

``span`` is the one call site for both sinks: while a profiler session
records (``jax.profiler.start_trace``), every span also opens a
``jax.profiler.TraceAnnotation`` of the same name and arguments, so the
server's spans sit on the profiler's clock in the same trace as the
device ops, with or without a tracer installed.

Layering: ``repro.obs`` imports nothing from the rest of ``repro`` —
core, serving, store, and launch all import *it*. Instrument sparse call
sites with the module-level one-liners (``count``/``gauge``/``observe``/
``span``) — they no-op against a disabled ``STATE``; hot loops hold
metric object references directly.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from repro.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS_S,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Stopwatch,
    percentiles,
    time_fn,
)
from repro.obs.trace import (
    NULL_SPAN,
    NULL_TRACER,
    NullTracer,
    ProfiledSpan,
    Span,
    Tracer,
    span_tree,
)

__all__ = [
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "DEFAULT_LATENCY_BUCKETS_S", "Stopwatch", "percentiles", "time_fn",
    # tracing
    "Span", "Tracer", "NullTracer", "NULL_TRACER", "NULL_SPAN",
    "ProfiledSpan", "span_tree",
    # runtime state
    "STATE", "enable_metrics", "disable_metrics", "set_tracer", "tracer",
    "disable_all",
    # convenience instrumentation
    "count", "gauge", "observe", "span",
]


class _ObsState:
    """Process-wide observability switch (see module docstring)."""

    __slots__ = ("metrics", "tracer")

    def __init__(self):
        self.metrics: MetricsRegistry | None = None
        self.tracer: Tracer | None = None


STATE = _ObsState()


def enable_metrics(registry: MetricsRegistry | None = None) -> MetricsRegistry:
    """Turn on metrics recording (into ``registry`` or the process
    default ``REGISTRY``); returns the active registry."""
    STATE.metrics = registry if registry is not None else REGISTRY
    return STATE.metrics


def disable_metrics() -> None:
    STATE.metrics = None


def set_tracer(t: Tracer | None) -> Tracer | None:
    """Install (or with None, remove) the process tracer; returns it."""
    STATE.tracer = t
    return t


def tracer():
    """The active tracer, or ``NULL_TRACER`` — always safe to call
    ``.span()`` on the result."""
    t = STATE.tracer
    return t if t is not None else NULL_TRACER


def disable_all() -> None:
    """Back to the zero-overhead default (tests reset through this)."""
    STATE.metrics = None
    STATE.tracer = None


# ---- sparse-call-site one-liners (no-ops when disabled) ----

def count(name: str, n: float = 1.0, help: str = "", **labels) -> None:
    reg = STATE.metrics
    if reg is not None:
        reg.counter(name, help, **labels).inc(n)


def gauge(name: str, value: float, help: str = "", **labels) -> None:
    reg = STATE.metrics
    if reg is not None:
        reg.gauge(name, help, **labels).set(value)


def observe(
    name: str, value: float, help: str = "", buckets=None, **labels
) -> None:
    reg = STATE.metrics
    if reg is not None:
        if buckets is None:
            buckets = DEFAULT_LATENCY_BUCKETS_S
        reg.histogram(name, help, buckets=buckets, **labels).observe(value)


def span(name: str, **args):
    """Context-manager span: into the active tracer, and as a profiler
    annotation of the same name while a profiler session records
    (``NULL_SPAN`` when neither is on)."""
    t = STATE.tracer
    inner = t.span(name, **args) if t is not None else NULL_SPAN
    if TraceAnnotation.is_enabled():
        return ProfiledSpan(name, args, inner)
    return inner
