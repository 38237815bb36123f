"""Observability: the serving stack's flight recorder.

``repro.obs.trace`` records request-lifecycle spans, per-step engine spans,
plan-decision audit instants and scheduler queue events against an injected
clock (virtual-clock bench runs trace deterministically);
``repro.obs.export`` emits the Chrome-trace/Perfetto JSON and JSONL
artifacts the ``python -m repro.launch.trace_report`` CLI consumes.
``repro.obs.trace.region`` marks the engine's steps and launches as
``serve.*`` regions of a ``jax.profiler`` trace, on the device's clock.
"""
from repro.obs.export import (
    load_trace,
    to_chrome,
    write_jsonl,
    write_trace,
)
from repro.obs.trace import (
    TRACE_SCHEMA_VERSION,
    ProcTrace,
    Tracer,
)

__all__ = [
    "TRACE_SCHEMA_VERSION", "Tracer", "ProcTrace",
    "to_chrome", "write_trace", "write_jsonl", "load_trace",
]
