"""Observability: span tracing, metrics, and per-op profiling.

The paper's claim is *end-to-end* latency; this package makes the repro
self-measuring end to end, with zero dependencies and zero cost when off:

  * **spans** (``obs.span`` / ``obs.get_tracer``) — nested wall-clock
    regions over the compile pipeline (one span per pass), runner builds
    (residency upload bytes, AOT warmup per (task, bucket)) and the
    serving lifecycle (dispatch/harvest batches, one retroactive span per
    request), exportable as Chrome/Perfetto trace-event JSON
    (``gcv.trace_to(path)`` / ``obs.export_chrome_trace``); live spans
    also land, under their names, on a ``jax.profiler`` trace taken while
    the tracer is enabled, on the device operations' clock;
  * **metrics** (``obs.MetricsRegistry`` / the process-global
    ``obs.metrics()``) — counters, gauges, and zero-safe histograms that
    ``GNNCVServeEngine.stats()``, ``CompiledModel.stats()`` and the
    plan/runner cache read from instead of keeping ad-hoc tallies;
  * **profiling** (``obs.profile_plan`` / ``obs.profile_report``, surfaced
    as ``CompiledModel.profile()`` / ``.profile_report()``) — measured
    seconds per MatOp with ``block_until_ready`` between ops, lined up
    against Step-4b's analytic predictions to yield the cost-model
    agreement rate recorded in ``BENCH_compile.json``.

Tracing is **off by default**; hot paths pay one attribute read per
instrumented site.  ``telemetry(True)`` (what
``CompileOptions(telemetry=True)`` routes through) force-enables the
tracer for a region; ``gcv.trace_to(path)`` enables it for a block and
writes the trace file on exit.
"""
from __future__ import annotations

import contextlib

from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, metrics)
from repro.obs.profile import (profile_plan, profile_report,  # noqa: F401
                               render_report)
from repro.obs.trace import (NOOP_SPAN, Span, Tracer,  # noqa: F401
                             clear, complete, enabled, export_chrome_trace,
                             get_tracer, instant, now, span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "metrics",
    "Span", "Tracer", "get_tracer", "span", "now", "enabled", "instant",
    "complete", "export_chrome_trace", "clear", "telemetry",
    "profile_plan", "profile_report", "render_report",
]


@contextlib.contextmanager
def telemetry(on: bool = True):
    """Force span recording for a region (no-op when ``on`` is falsy or
    the tracer is already enabled) — ``CompileOptions(telemetry=True)``
    wraps one compile in this so its pass spans record even outside a
    ``gcv.trace_to`` block."""
    tracer = get_tracer()
    if not on or tracer.enabled:
        yield tracer
        return
    tracer.enable()
    try:
        yield tracer
    finally:
        tracer.disable()
