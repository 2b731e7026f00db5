"""theanompi_tpu.observability — unified tracing, metrics, flight recorder.

The ONE observability subsystem for both halves of the framework: the
training stack (BSP/EASGD/GOSGD workers, exchangers, loaders) and the
serving stack (admission/prefill/decode) instrument through the same
three primitives:

- ``trace``   — thread-safe span tracer with Chrome-trace/Perfetto
  export (``with span("prefill", slot=i): ...``); no-op when disabled,
  so instrumentation lives in hot loops permanently.  One level is
  always on: **boundary spans** (``span(name, boundary=True)``: a
  scheduler tick and its dispatches, a training step and its phases —
  ``BOUNDARY_SPANS``) are recorded with tracing off, carry ``id`` and
  ``parent``, feed the flight rings, and open a
  ``jax.profiler.TraceAnnotation`` once a jax-importing module has
  installed the hook, so ``jax.profiler.trace`` alone puts the
  program's spans into a profile.  ``get_tracer().boundary_spans(since,
  until)`` returns them on ``time.perf_counter``.  The collector's
  pauses are timed too: ``gc_totals()`` over every collection, and a
  ``gc`` boundary span for each long one.
- ``metrics`` — a registry of labeled counters / gauges / fixed-bucket
  histograms with atomic snapshot, JSON and Prometheus-text exposition.
- ``flight``  — per-thread ring buffers of recent spans/events, dumped
  to a post-mortem JSON file on unhandled exception or explicit
  ``dump()``.

plus ``export`` (file dumps + an opt-in localhost HTTP endpoint incl.
``/health`` and ``/timeline``), ``live`` (the live telemetry plane:
per-rank frame shipping with HA endpoint failover, primary/standby
aggregators with the streaming doctor, the SLO watchdog, doctor-state
checkpoints — import as a submodule, ``from theanompi_tpu.observability
import live``), ``history`` (queryable run history over the persisted
verdict timelines), and a CLI (``python -m theanompi_tpu.observability
dump --format chrome`` / ``watch`` / ``doctor`` / ``merge`` /
``history``).

**Event bus**: ``publish_event(kind, fields)`` fans one structured
event out to every surface (instant trace event, flight ring, the
``events_total`` counter, registered subscribers).
``runtime.recorder.Recorder.log_event`` forwards here, so every
existing ``log_event`` call site — comm-fraction probes, serve
summaries, memory snapshots, restarts — feeds the bus unchanged.

Pure stdlib: importable without jax on the path (like ``analysis/``) —
the post-mortem machinery must work when the accelerator stack is the
thing that died.  Tracing enables via ``enable_tracing()`` or env
``THEANOMPI_OBS_TRACE=1``; metrics, flight recording and the boundary
spans are always on (bounded, cheap).
"""

from __future__ import annotations

import os
from typing import Callable, List

from theanompi_tpu.observability.flight import (
    FlightRecorder,
    get_flight_recorder,
)
from theanompi_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    bucket_quantile,
    counter_deltas,
    flatten_counters,
    get_registry,
    percentile,
)
from theanompi_tpu.observability.trace import (
    BOUNDARY_SPANS,
    Tracer,
    add_span,
    counter_event,
    disable_request_tracking,
    drain_request_digests,
    enable_request_tracking,
    flow_begin,
    flow_end,
    gc_totals,
    get_tracer,
    install_annotation_hook,
    instant,
    merge_raw_traces,
    raw_to_chrome,
    request_begin,
    request_end,
    request_flag,
    request_mark,
    request_stats,
    request_tracking_active,
    retained_requests,
    span,
    traced,
    worst_requests,
)

__all__ = [
    "BOUNDARY_SPANS",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Tracer",
    "add_span",
    "bucket_quantile",
    "counter_deltas",
    "counter_event",
    "count_xla_compiles",
    "counter_values",
    "disable_request_tracking",
    "disable_tracing",
    "drain_request_digests",
    "dump_all",
    "enable_request_tracking",
    "enable_tracing",
    "flatten_counters",
    "flow_begin",
    "flow_end",
    "gc_totals",
    "get_flight_recorder",
    "get_registry",
    "get_tracer",
    "install_annotation_hook",
    "instant",
    "merge_raw_traces",
    "percentile",
    "publish_event",
    "raw_to_chrome",
    "request_begin",
    "request_end",
    "request_flag",
    "request_mark",
    "request_stats",
    "request_tracking_active",
    "retained_requests",
    "set_process",
    "span",
    "subscribe",
    "traced",
    "worst_requests",
]

# boundary spans reach the flight rings with tracing off: a post-mortem
# holds the last ticks or steps, not events alone
get_tracer().span_sinks.append(get_flight_recorder().record_span)

_EVENTS = get_registry().counter(
    "events_total", "structured events through the observability bus"
)

_subscribers: List[Callable[[str, dict], None]] = []


def subscribe(fn: Callable[[str, dict], None]) -> None:
    """Register a bus subscriber: ``fn(kind, fields)`` per event."""
    _subscribers.append(fn)


def publish_event(kind: str, fields: dict) -> None:
    """Fan one structured event out to every observability surface.

    ``fields`` is read, never mutated or retained mutably — callers
    (``Recorder.log_event``) keep ownership of their row dicts."""
    _EVENTS.inc(kind=kind)
    get_flight_recorder().record(kind, **fields)
    tracer = get_tracer()
    if tracer.enabled:
        tracer.instant(kind, dict(fields) if fields else None)
    for fn in _subscribers:
        fn(kind, fields)


_XLA_PROGRAMS = get_registry().counter(
    "xla_programs_total",
    "programs handed to the XLA compiler or fetched from its persistent "
    "cache (one per new jit signature)",
)
_XLA_CACHE_HITS = get_registry().counter(
    "xla_cache_hits_total",
    "of xla_programs_total, those the persistent compile cache served",
)
_xla_counting = False


def count_xla_compiles() -> None:
    """Feed jax's own compile events into the two counters above
    (idempotent).  Counters ride every ``Recorder`` epoch row as deltas,
    so "this epoch compiled nothing" and "the second run was served from
    the cache" are read off the record instead of guessed from timings."""
    global _xla_counting
    if _xla_counting:
        return
    _xla_counting = True
    import jax.monitoring

    def on_duration(event, duration_secs, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            _XLA_PROGRAMS.inc()

    def on_event(event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            _XLA_CACHE_HITS.inc()

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def counter_values() -> dict:
    """Flattened ``name{labels} -> value`` view of every counter in
    the process registry — snapshot it at a boundary, snapshot again
    later, and ``counter_deltas`` tells you exactly what moved."""
    return flatten_counters(get_registry().snapshot())


def enable_tracing(buffer=None, sample=None) -> Tracer:
    """Turn span collection on (bounded buffer) and feed finished spans
    into the flight recorder's rings.  ``sample=N`` keeps 1-in-N spans
    per thread track (deterministic; instants/flows/counters always
    kept) for sustained production tracing; defaults to the
    ``THEANOMPI_OBS_SAMPLE`` env var, else keep-everything."""
    tracer = get_tracer()
    fr = get_flight_recorder()
    if fr.record_span not in tracer.span_sinks:
        tracer.span_sinks.append(fr.record_span)
    if sample is None:
        try:
            sample = int(os.environ.get("THEANOMPI_OBS_SAMPLE", "") or 1)
        except ValueError:
            sample = 1
    tracer.enable(buffer=buffer, sample=sample)
    return tracer


def disable_tracing() -> None:
    get_tracer().disable()


def set_process(pid: int, name=None) -> None:
    """Label this process's trace track (e.g. the SPMD process index)."""
    get_tracer().set_process(pid, name)


def dump_all(directory=None, prefix: str = ""):
    from theanompi_tpu.observability.export import dump_all as _impl

    return _impl(directory, prefix)


if os.environ.get("THEANOMPI_OBS_TRACE") == "1":
    enable_tracing()
