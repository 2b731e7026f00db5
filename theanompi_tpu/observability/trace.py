"""Span tracer — thread-safe, bounded, Chrome-trace/Perfetto exportable.

The reference's ``Recorder`` timed calc/comm/wait per iteration with
wall clocks (upstream ``lib/recorder.py``; SURVEY.md §3.7) — a table,
not a timeline.  This tracer keeps the timeline: every instrumented
region becomes a *span* (name, start, duration, thread track, args)
in a bounded in-memory buffer, exportable as Chrome trace-event JSON
that loads directly in ``chrome://tracing`` or https://ui.perfetto.dev.

Contracts:

- **Pure stdlib** — importable with no jax on the path (like
  ``analysis/``): the crashed-worker post-mortem path must never
  depend on the library that crashed.
- **Disabled records boundary spans only** — ``span()`` with tracing
  off returns a shared singleton whose enter/exit do nothing, so
  instrumentation stays in hot loops permanently (tier-1 guards the
  per-span cost; tests/test_observability.py::test_disabled_span_overhead).
  The one exception is the **boundary level**: ``span(name,
  boundary=True)`` is recorded whether or not tracing is enabled.
  Boundary spans sit where one layer hands work to the next (a
  scheduler tick and its dispatches, a training step and its phases:
  see ``BOUNDARY_SPANS``) — a handful per tick or step, never one per
  lane, block or request.  Each costs one
  small dict, one lock and one ``deque.append`` (a few microseconds;
  tests/test_observability_boundary.py holds it under 20 µs) against
  ticks and steps of tens of milliseconds.  A boundary span's arguments
  are the recorded event's own dict, so ``set()`` after the span has
  closed still reaches the buffer: the serving scheduler completes a
  dispatch span that way with counters its program returned, once the
  program has run (a span that closed without any argument records
  none, and takes none later).
- **Monotonic clocks** — timestamps come from ``time.perf_counter``
  (never wall clock), relative to the tracer's epoch, so spans across
  threads order correctly and NTP steps can't fold a trace.
  ``boundary_spans()`` hands them back on ``perf_counter`` itself, the
  clock a driver around the program reads, so a window's spans are
  selected by time.
- **Two clocks for a boundary span** — besides the buffer's
  ``perf_counter`` record, a boundary span opens whatever the
  *annotation hook* makes for its duration: the first jax-importing
  module of the program installs ``jax.profiler.TraceAnnotation``
  (``install_annotation_hook``; this module never imports jax), so in
  any profile taken with ``jax.profiler.trace`` the program's spans lie
  in the host plane, on the profile's own clock, above the device
  operations.  With no profiler session the annotation is a no-op in
  C++.  The tracer also keeps the pair (``time.time_ns()``, ``clock()``)
  read once at its creation: ``wall_offset_ns`` added to a
  ``perf_counter`` reading in nanoseconds gives Unix nanoseconds.
- **Parents** — a boundary span carries ``id`` and ``parent`` (the
  boundary span open on the same thread when it began; a thread-local
  stack), so a layer's self time is its duration minus its children's.
- **Bounded buffer** — a ``deque(maxlen=...)`` of finished spans; a
  week-long run keeps the newest window instead of OOMing the host.
- **Track ids** — ``pid`` is the worker/process track (defaults to
  ``os.getpid()``; SPMD launchers override it with the process index
  via ``set_process`` so merged traces line ranks up), ``tid`` is a
  small per-thread id assigned in first-span order and named after the
  thread (``EASGD_Worker-0`` etc. — the driver names its threads).
- **Causal flow events** — ``flow_begin``/``flow_end`` emit Chrome
  flow-event pairs (``ph: s``/``f``) sharing an id, so a message sent
  on one rank and drained on another renders as an ARROW between the
  two process tracks in Perfetto instead of two unrelated boxes
  (``transport.TcpMailbox`` stamps every frame with a ``(src_rank,
  seq)`` flow id).  ``counter_event`` emits Chrome counter samples
  (``ph: C``) — the trace-side record of gauge motion (inbox depth)
  the offline doctor correlates with spans.
- **Sampling** — ``sample_rate=N`` keeps 1-in-N spans per thread track
  (deterministic per-track counters: the kept set depends only on each
  track's span sequence, never on wall time), so sustained production
  runs can trace for hours without unbounded buffers.  Instant, flow
  and counter events are never sampled — pairing and gauge crossings
  must survive sampling.  Sampled-out spans are counted
  (``sampled_out``), never silent.
- **Tail-based request retention** — ``enable_request_tracking``
  opens a per-request buffer per ``request_begin(rid)``; every event
  whose args carry that ``rid`` (or whose flow id starts ``req:{rid}``)
  is routed into it BEFORE the 1-in-N sampling drop, so a retained
  request's story is never holey.  ``request_end`` keeps the buffer
  only when the request breached the latency threshold or was flagged
  (killed / readmitted / lost) and cheaply recycles it otherwise; a
  small worst-latency ring survives regardless of threshold so a
  green run still has its slowest request to explain.  Finished
  request digests queue for the live telemetry plane
  (``drain_request_digests``) — the aggregator's fleet-wide
  worst-offenders feed.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from functools import wraps
from typing import Any, Callable, Dict, List, Optional

DEFAULT_BUFFER = 100_000

# The boundary spans of the program, by layer (docs/observability.md has
# the arguments each carries and the metric that reads it).
BOUNDARY_SPANS = (
    # serving: ContinuousBatchingScheduler.step and what it calls
    "tick", "admit", "prefill", "prefill_chunk_dispatch", "decode_step",
    "spec_verify", "pick",
    # serving: a params tree becomes a scheduler's (paging.serving_params)
    "weights_relayout",
    # training: TpuModel.train_iter, the Recorder's phases, the print
    "train_iter", "wait", "calc", "print",
)

# ---- the boundary level's per-thread parent stack and annotation hook ----
_IDS = itertools.count(1)
_LOCAL = threading.local()
_ANNOTATE: Optional[Callable[..., Any]] = None


def _stack() -> list:
    try:
        return _LOCAL.stack
    except AttributeError:
        _LOCAL.stack = []
        return _LOCAL.stack


def install_annotation_hook(factory: Optional[Callable[..., Any]]) -> None:
    """``factory(name, **args)`` returns a context manager that a boundary
    span holds open for its duration.  The program's jax-importing
    modules pass ``jax.profiler.TraceAnnotation``; ``None`` removes it."""
    global _ANNOTATE
    _ANNOTATE = factory


class _NoopSpan:
    """Shared do-nothing span: the disabled-tracer fast path allocates
    nothing and touches no lock."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def cancel(self) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def set(self, **args) -> None:
        """Attach result fields discovered inside the span (e.g. bytes
        actually sent)."""
        self._args.update(args)

    def __enter__(self):
        self._t0 = self._tracer.clock()
        return self

    def __exit__(self, *exc):
        t = self._tracer
        t.add_span(self._name, self._t0, t.clock(), self._args or None)
        return False

    def cancel(self) -> None:
        """Leave the span without recording it (a phase that was started
        again before it was ended)."""


class _BoundarySpan(_Span):
    """A span of the boundary level: recorded with tracing off, knows
    its parent, and holds the annotation hook's context open."""

    __slots__ = ("_id", "_parent", "_ann")

    def __enter__(self):
        stack = _stack()
        self._parent = stack[-1] if stack else None
        self._id = next(_IDS)
        stack.append(self._id)
        hook = _ANNOTATE
        if hook is None:
            self._ann = None
        else:
            self._ann = hook(self._name, **self._args)
            self._ann.__enter__()
        self._t0 = self._tracer.clock()
        return self

    def _close(self, exc=(None, None, None)) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _stack()
        if self._id in stack:
            # children left open by an exception go with their parent
            del stack[stack.index(self._id):]

    def __exit__(self, *exc):
        t = self._tracer
        end = t.clock()
        self._close(exc)
        t._record(self._name, self._t0, end, self._args or None,
                  self._id, self._parent)
        return False

    def cancel(self) -> None:
        self._close()


class Tracer:
    """Thread-safe span collector with Chrome-trace export.

    ``clock`` is injectable (tests drive a fake timeline for the golden
    file); it must be monotonic and return seconds.  ``pid`` overrides
    the process track id (SPMD rank); ``buffer`` bounds the number of
    retained events (oldest dropped first).
    """

    def __init__(
        self,
        clock: Callable[[], float] = time.perf_counter,
        pid: Optional[int] = None,
        buffer: int = DEFAULT_BUFFER,
        process_name: Optional[str] = None,
        sample_rate: int = 1,
    ):
        import os

        self.enabled = False
        self.clock = clock
        self.pid = os.getpid() if pid is None else int(pid)
        self.process_name = process_name
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=int(buffer))
        self._epoch = clock()
        # one reading of both clocks: perf_counter (ns) + this = Unix ns
        self.wall_offset_ns = time.time_ns() - int(self._epoch * 1e9)
        # thread ident -> (small tid, thread name at registration)
        self._tracks: Dict[int, tuple] = {}
        self.dropped = 0  # events evicted by the bound (visible, not silent)
        # 1-in-N span sampling (1 = keep everything); per-track span
        # sequence counters make the kept set deterministic
        self.sample_rate = max(1, int(sample_rate))
        self.sampled_out = 0
        self._span_seq: Dict[int, int] = {}  # tid -> spans seen
        # called with each finished span dict (flight recorder feed);
        # invoked outside the buffer lock
        self.span_sinks: List[Callable[[dict], None]] = []
        # called with each point event (flow begin/end, counter sample)
        # — the live telemetry shipper's feed; same outside-the-lock
        # contract as span_sinks
        self.point_sinks: List[Callable[[dict], None]] = []
        # ---- tail-based per-request retention (off until
        # enable_request_tracking) -----------------------------------
        self._req_tracking = False
        self._req_threshold_s = float("inf")
        self._req_max_events = 512
        self._req_worst_cap = 8
        self._req_open: Dict[str, dict] = {}  # rid -> open record
        self._req_retained: deque = deque(maxlen=64)
        self._req_worst: List[dict] = []  # worst-latency ring (any status)
        self._req_digests: List[dict] = []  # pending live-plane digests
        self.req_tracked = 0
        self.req_retained_total = 0
        self.req_recycled = 0

    # ---- lifecycle -----------------------------------------------------
    def enable(
        self, buffer: Optional[int] = None, sample: Optional[int] = None
    ) -> None:
        with self._lock:
            if buffer is not None and buffer != self._buf.maxlen:
                self._buf = deque(self._buf, maxlen=int(buffer))
            if sample is not None:
                self.sample_rate = max(1, int(sample))
            self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._tracks.clear()
            self.dropped = 0
            self.sampled_out = 0
            self._span_seq.clear()
            self._epoch = self.clock()

    def set_process(self, pid: int, name: Optional[str] = None) -> None:
        """Re-label this tracer's process track (e.g. the SPMD process
        index) so multi-rank traces merge onto distinct named rows."""
        self.pid = int(pid)
        if name is not None:
            self.process_name = name

    # ---- per-request tail retention ------------------------------------
    def enable_request_tracking(
        self,
        threshold_s: float = 1.0,
        capacity: int = 64,
        max_events: int = 512,
        worst: int = 8,
    ) -> None:
        """Start tail-based per-request span retention.  A finished
        request is KEPT when its latency breaches ``threshold_s`` or it
        carries flags (readmitted / lost / killed), recycled otherwise;
        the ``worst`` lowest-latency-breakers ring keeps the slowest
        requests regardless, so a green run can still explain its p99.
        ``capacity`` bounds the retained ring, ``max_events`` the
        per-request buffer (overflow counted, never silent)."""
        with self._lock:
            self._req_tracking = True
            self._req_threshold_s = float(threshold_s)
            self._req_max_events = int(max_events)
            self._req_worst_cap = max(1, int(worst))
            self._req_retained = deque(
                self._req_retained, maxlen=max(1, int(capacity))
            )

    def disable_request_tracking(self) -> None:
        """Stop tracking and drop all per-request state (open buffers,
        retained ring, worst ring, pending digests, counters)."""
        with self._lock:
            self._req_tracking = False
            self._req_open.clear()
            self._req_retained.clear()
            self._req_worst = []
            self._req_digests = []
            self.req_tracked = 0
            self.req_retained_total = 0
            self.req_recycled = 0

    @property
    def request_tracking(self) -> bool:
        return self.enabled and self._req_tracking

    def request_begin(self, rid: str, **meta) -> None:
        """Open a per-request buffer.  IDEMPOTENT: a second begin for an
        open rid is a no-op, so the fleet router (which mints the id)
        and the replica scheduler (which sees the same id later, and is
        the only opener in router-less runs) can both call it."""
        if not (self.enabled and self._req_tracking):
            return
        rid = str(rid)
        with self._lock:
            if rid in self._req_open:
                return
            self._req_open[rid] = {
                "rid": rid,
                "t0": self.clock(),
                "meta": meta,
                "events": [],
                "flags": [],
                "marks": [],
                "truncated": 0,
            }
            self.req_tracked += 1

    def request_flag(self, rid: str, flag: str) -> None:
        """Mark an open request for unconditional retention (e.g.
        ``readmitted``, ``lost``) — flags beat the latency threshold."""
        if not (self.enabled and self._req_tracking):
            return
        with self._lock:
            rec = self._req_open.get(str(rid))
            if rec is not None and flag not in rec["flags"]:
                rec["flags"].append(str(flag))

    def request_mark(self, rid: str, name: str) -> None:
        """Stamp one named point on an open request's own clock (e.g.
        ``first_token`` — the TTFT anchor in its digest)."""
        if not (self.enabled and self._req_tracking):
            return
        with self._lock:
            rec = self._req_open.get(str(rid))
            if rec is not None:
                rec["marks"].append(
                    {"name": str(name), "ts": self._us(self.clock())}
                )

    def request_end(
        self, rid: str, status: str = "ok", **extra
    ) -> Optional[dict]:
        """Close an open request and decide retention.  No-op (None)
        for unknown/already-closed rids.  Returns the finished record;
        whether it was retained is ``record["retained"]``."""
        if not self._req_tracking:
            return None
        rid = str(rid)
        with self._lock:
            rec = self._req_open.pop(rid, None)
            if rec is None:
                return None
            t1 = self.clock()
            latency = t1 - rec["t0"]
            keep = (
                bool(rec["flags"])
                or status != "ok"
                or latency >= self._req_threshold_s
            )
            out = {
                "rid": rid,
                "status": str(status),
                "latency_s": round(latency, 9),
                "t_start_us": self._us(rec["t0"]),
                "t_end_us": self._us(t1),
                "flags": list(rec["flags"]),
                "meta": rec["meta"],
                "marks": rec["marks"],
                "events": rec["events"],
                "truncated": rec["truncated"],
                "retained": keep,
            }
            if extra:
                out.update(extra)
            if keep:
                self._req_retained.append(out)
                self.req_retained_total += 1
            else:
                self.req_recycled += 1
            # worst-latency ring: kept regardless of threshold so the
            # slowest request of a green run is still explainable
            self._req_worst.append(out)
            self._req_worst.sort(
                key=lambda r: r["latency_s"], reverse=True
            )
            del self._req_worst[self._req_worst_cap:]
            self._req_digests.append(self._digest_locked(out))
            del self._req_digests[:-256]
        # one top-level span per finished request: the merged-trace row
        # the per-phase children nest under (rid popped above, so this
        # span is not routed back into the buffer)
        self.add_span(
            "request", rec["t0"], t1,
            {"rid": rid, "status": status,
             "retained": keep, **({"flags": out["flags"]}
                                  if out["flags"] else {})},
        )
        return out

    def _digest_locked(self, out: dict) -> dict:
        """Compact live-plane summary of one finished request: latency,
        TTFT (from the ``first_token`` mark), coarse per-phase sums by
        ``req_*`` span name.  The real interval math lives in
        ``analysis.request_breakdown`` — this is the cheap wire form."""
        phases: Dict[str, float] = {}
        for ev in out["events"]:
            name = ev.get("name", "")
            if ev.get("ph") == "X" and name.startswith("req_"):
                phases[name[4:]] = round(
                    phases.get(name[4:], 0.0)
                    + float(ev.get("dur", 0.0)) / 1e6, 9,
                )
        d = {
            "rid": out["rid"],
            "status": out["status"],
            "latency_s": out["latency_s"],
            "flags": out["flags"],
            "retained": out["retained"],
            "n_events": len(out["events"]),
            "phases": phases,
        }
        for m in out["marks"]:
            if m["name"] == "first_token":
                d["ttft_s"] = round(
                    (m["ts"] - out["t_start_us"]) / 1e6, 9
                )
                break
        n_tokens = out.get("n_tokens")
        if n_tokens is not None:
            d["n_tokens"] = int(n_tokens)
            if "ttft_s" in d and n_tokens > 1:
                d["tpot_s"] = round(
                    (out["latency_s"] - d["ttft_s"]) / (n_tokens - 1), 9
                )
        return d

    def retained_requests(self) -> List[dict]:
        with self._lock:
            return list(self._req_retained)

    def worst_requests(self) -> List[dict]:
        """The worst-latency ring, slowest first (retained or not)."""
        with self._lock:
            return list(self._req_worst)

    def request_stats(self) -> dict:
        with self._lock:
            return {
                "tracking": self._req_tracking,
                "threshold_s": self._req_threshold_s,
                "tracked": self.req_tracked,
                "retained": self.req_retained_total,
                "recycled": self.req_recycled,
                "open": len(self._req_open),
                "retained_held": len(self._req_retained),
            }

    def drain_request_digests(self) -> List[dict]:
        """Hand off (and clear) the pending finished-request digests —
        the live telemetry shipper's per-frame feed."""
        with self._lock:
            out, self._req_digests = self._req_digests, []
            return out

    def _route_request_locked(self, ev: dict) -> None:
        """File ``ev`` into the per-request buffer(s) its args' ``rid``
        (or its ``req:{rid}`` flow id) names.  Runs BEFORE the sampling
        drop in ``add_span`` — a retained request's trace is complete
        even under 1-in-N sampling.  ``rid="*"`` broadcasts to every
        open request (install waits stall whoever is in flight)."""
        args = ev.get("args")
        rid = args.get("rid") if args else None
        if rid is None and ev.get("cat") == "flow":
            fid = str(ev.get("id", ""))
            if fid.startswith("req:"):
                rid = fid.split(":", 2)[1]
        if rid is None:
            return
        if rid == "*":
            recs = self._req_open.values()
        else:
            rec = self._req_open.get(str(rid))
            if rec is None:
                return
            recs = (rec,)
        for rec in recs:
            if len(rec["events"]) >= self._req_max_events:
                rec["truncated"] += 1
            else:
                rec["events"].append(ev)

    # ---- recording -----------------------------------------------------
    def _track_locked(self) -> int:
        th = threading.current_thread()
        entry = self._tracks.get(th.ident)
        if entry is None:
            entry = (len(self._tracks), th.name)
            self._tracks[th.ident] = entry
        return entry[0]

    def _push_locked(self, ev: dict) -> None:
        if len(self._buf) == self._buf.maxlen:
            self.dropped += 1
        self._buf.append(ev)

    def _us(self, t: float) -> float:
        return round((t - self._epoch) * 1e6, 3)

    def add_span(
        self,
        name: str,
        start: float,
        end: float,
        args: Optional[dict] = None,
    ) -> None:
        """Record a completed span from explicit ``clock()`` timestamps
        (a no-op while tracing is disabled)."""
        if self.enabled:
            self._record(name, start, end, args)

    def _record(self, name, start, end, args, span_id=None, parent=None):
        ev = {
            "ph": "X",
            "name": name,
            "ts": self._us(start),
            "dur": round(max(0.0, end - start) * 1e6, 3),
            "pid": self.pid,
        }
        if args:
            ev["args"] = args
        if span_id is not None:
            ev["id"] = span_id
            ev["parent"] = parent
        with self._lock:
            tid = ev["tid"] = self._track_locked()
            if self._req_tracking:
                # request buffers fill BEFORE the sampling drop: a
                # tail-retained request's story must never be holey
                self._route_request_locked(ev)
            if self.sample_rate > 1 and span_id is None:
                seq = self._span_seq.get(tid, 0)
                self._span_seq[tid] = seq + 1
                if seq % self.sample_rate:
                    # deterministically sampled out: every Nth span per
                    # track is kept (the first always survives, so short
                    # traces are never empty); accounted, never silent
                    self.sampled_out += 1
                    return
            self._push_locked(ev)
        for sink in self.span_sinks:
            sink(ev)

    def instant(self, name: str, args: Optional[dict] = None) -> None:
        """One point-in-time event (Chrome 'instant', thread-scoped)."""
        if not self.enabled:
            return
        ev = {
            "ph": "i",
            "name": name,
            "ts": self._us(self.clock()),
            "s": "t",
            "pid": self.pid,
        }
        if args:
            ev["args"] = args
        with self._lock:
            ev["tid"] = self._track_locked()
            if self._req_tracking:
                self._route_request_locked(ev)
            self._push_locked(ev)

    def _point_event(self, ev: dict, args: Optional[dict]) -> None:
        if args:
            ev["args"] = args
        with self._lock:
            ev["tid"] = self._track_locked()
            if self._req_tracking:
                self._route_request_locked(ev)
            self._push_locked(ev)
        for sink in self.point_sinks:
            sink(ev)

    def flow_begin(
        self, name: str, flow_id: str, args: Optional[dict] = None
    ) -> None:
        """Start half of a causal arrow (Chrome flow event ``ph: s``).
        Emit INSIDE the producing span (the send) so viewers bind the
        arrow tail to that slice; the matching ``flow_end`` with the
        same ``(name, flow_id)`` — typically on another rank — is the
        arrow head.  Never sampled: a one-sided arrow is worse than no
        arrow."""
        if not self.enabled:
            return
        self._point_event(
            {
                "ph": "s",
                "cat": "flow",
                "name": name,
                "id": str(flow_id),
                "ts": self._us(self.clock()),
                "pid": self.pid,
            },
            args,
        )

    def flow_end(
        self, name: str, flow_id: str, args: Optional[dict] = None
    ) -> None:
        """Finish half of a causal arrow (``ph: f``, binding to the
        enclosing slice — emit inside the consuming span)."""
        if not self.enabled:
            return
        self._point_event(
            {
                "ph": "f",
                "bp": "e",
                "cat": "flow",
                "name": name,
                "id": str(flow_id),
                "ts": self._us(self.clock()),
                "pid": self.pid,
            },
            args,
        )

    def counter_event(
        self, name: str, value: float, **series
    ) -> None:
        """One Chrome counter sample (``ph: C``) — the trace-timeline
        record of a gauge (inbox depth): unlike the metrics registry,
        each sample keeps its timestamp, so the offline doctor can find
        CROSSINGS (when the queue backed up, for how long).  ``series``
        labels the sample (e.g. ``rank="1"``)."""
        if not self.enabled:
            return
        ev = {
            "ph": "C",
            "name": name,
            "ts": self._us(self.clock()),
            "pid": self.pid,
        }
        self._point_event(ev, {**series, "value": float(value)})

    def span(self, name: str, boundary: bool = False, **args):
        """Context manager measuring a region; with tracing disabled a
        no-op unless ``boundary``."""
        if boundary:
            return _BoundarySpan(self, name, args)
        if not self.enabled:
            return _NOOP
        return _Span(self, name, args)

    # ---- export --------------------------------------------------------
    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._buf)

    def boundary_spans(
        self, since: Optional[float] = None, until: Optional[float] = None
    ) -> List[dict]:
        """The buffered boundary spans whose START lies in ``[since,
        until)``, oldest first, with ``start``/``end`` in seconds on the
        tracer's own clock (``perf_counter``: what a driver around the
        program reads), ``id``, ``parent``, ``tid`` and ``args``."""
        with self._lock:
            events = [ev for ev in self._buf
                      if ev["ph"] == "X" and "parent" in ev]
            epoch = self._epoch
        out = []
        for ev in events:
            start = epoch + ev["ts"] / 1e6
            if (since is not None and start < since) or (
                    until is not None and start >= until):
                continue
            out.append({
                "name": ev["name"], "start": start,
                "end": start + ev["dur"] / 1e6, "id": ev["id"],
                "parent": ev["parent"], "tid": ev["tid"],
                "args": ev.get("args") or {},
            })
        return out

    def _meta_events(self) -> List[dict]:
        out = []
        if self.process_name:
            out.append(
                {
                    "ph": "M",
                    "name": "process_name",
                    "pid": self.pid,
                    "tid": 0,
                    "args": {"name": self.process_name},
                }
            )
        with self._lock:
            tracks = list(self._tracks.values())
        for tid, name in sorted(tracks):
            out.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": self.pid,
                    "tid": tid,
                    "args": {"name": name},
                }
            )
        return out

    def chrome_trace(self) -> dict:
        """The Chrome trace-event document (JSON Object Format):
        metadata rows naming the tracks, then every buffered event.
        Loads as-is in chrome://tracing and ui.perfetto.dev."""
        other = {
            "producer": "theanompi_tpu.observability",
            "dropped_events": self.dropped,
        }
        if self.sample_rate > 1:
            other["sample_rate"] = self.sample_rate
            other["sampled_out"] = self.sampled_out
        return {
            "traceEvents": self._meta_events() + self.snapshot(),
            "displayTimeUnit": "ms",
            "otherData": other,
        }

    def export_chrome(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(), f, default=str)
            f.write("\n")
        return path

    def save_raw(self, path: str) -> str:
        """JSONL dump: one header line (track names), then one event per
        line — the offline format ``python -m theanompi_tpu.observability
        dump`` converts to Chrome JSON."""
        with self._lock:
            tracks = list(self._tracks.values())
        header = {
            "kind": "header",
            "pid": self.pid,
            "process_name": self.process_name,
            "tracks": {str(tid): name for tid, name in tracks},
            "dropped": self.dropped,
            # Unix nanoseconds of ts == 0: puts this file's events on a
            # profile's clock
            "epoch_unix_ns": int(self._epoch * 1e9) + self.wall_offset_ns,
        }
        if self.sample_rate > 1:
            header["sample_rate"] = self.sample_rate
            header["sampled_out"] = self.sampled_out
        with open(path, "w", encoding="utf-8") as f:
            f.write(json.dumps(header, default=str) + "\n")
            for ev in self.snapshot():
                f.write(json.dumps(ev, default=str) + "\n")
        return path


def raw_to_chrome(lines) -> dict:
    """Rebuild the Chrome trace document from ``save_raw`` JSONL lines
    (string iterable).  Unknown lines are skipped, not fatal — a raw
    file truncated by a crash should still open in Perfetto."""
    meta: List[dict] = []
    events: List[dict] = []
    dropped = 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if doc.get("kind") == "header":
            pid = doc.get("pid", 0)
            dropped = int(doc.get("dropped", 0) or 0)
            if doc.get("process_name"):
                meta.append(
                    {
                        "ph": "M",
                        "name": "process_name",
                        "pid": pid,
                        "tid": 0,
                        "args": {"name": doc["process_name"]},
                    }
                )
            for tid, name in sorted((doc.get("tracks") or {}).items()):
                meta.append(
                    {
                        "ph": "M",
                        "name": "thread_name",
                        "pid": pid,
                        "tid": int(tid),
                        "args": {"name": name},
                    }
                )
        elif "ph" in doc:
            events.append(doc)
    return {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "theanompi_tpu.observability",
            "dropped_events": dropped,
        },
    }


def merge_raw_traces(named_traces, align_clocks: bool = True) -> dict:
    """Merge several ``save_raw`` JSONL files into ONE Chrome trace
    document with a distinct, named process track per input — so
    Perfetto opens a multi-worker run as one timeline instead of one
    tab per rank (``python -m theanompi_tpu.observability merge``).

    ``named_traces``: iterable of ``(label, lines)`` where ``label``
    names the input (usually the filename stem) and ``lines`` is the
    raw JSONL line iterable.  Each file keeps its own header pid (the
    SPMD rank when the run used ``set_process``); files that COLLIDE on
    a pid — e.g. two single-process runs that both defaulted to
    ``os.getpid()`` — are remapped to the first free pid so their
    tracks never interleave.  Process tracks are named from the header
    ``process_name``, falling back to the label.  Unknown/corrupt lines
    are skipped (a crash-truncated rank must not sink the merge); the
    summed per-file drop counts are surfaced in ``otherData``.

    **Clock alignment** (``align_clocks=True``): per-rank tracer
    epochs are unsynchronized, so naively merged tracks render with an
    arbitrary horizontal skew.  When the inputs share matched flow
    send/recv pairs, the per-rank offsets recovered from their minimum
    one-way delays (``analysis.estimate_clock_offsets``) are
    subtracted from each file's timestamps, lining the tracks up on
    the anchor rank's clock; the applied offsets land in
    ``otherData["clock_offsets_us"]``.  A rank that shares NO flows
    with the rest cannot be aligned — it keeps its raw clock and gets
    a visible ``unaligned_clock`` warning row instead of a silently
    skewed track.  With no cross-file flows at all the merge is
    byte-identical to the unaligned one.
    """
    parsed: List[tuple] = []
    for label, lines in named_traces:
        header: Optional[dict] = None
        file_events: List[dict] = []
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if doc.get("kind") == "header" and header is None:
                header = doc
            elif "ph" in doc:
                file_events.append(doc)
        parsed.append((label, header, file_events))

    offsets: dict = {}
    unaligned: List[str] = []
    if align_clocks and len(parsed) > 1:
        from theanompi_tpu.observability import analysis

        flow_views = []
        for label, _header, file_events in parsed:
            fb: dict = {}
            fe: dict = {}
            for ev in file_events:
                ph = ev.get("ph")
                if ph == "s":
                    fb[str(ev.get("id"))] = float(ev.get("ts", 0.0))
                elif ph == "f":
                    fe[str(ev.get("id"))] = float(ev.get("ts", 0.0))
            flow_views.append(
                {"label": label, "flow_begin": fb, "flow_end": fe}
            )
        if analysis.flow_delay_edges(flow_views):
            offsets, unaligned = analysis.estimate_clock_offsets(
                flow_views
            )

    meta: List[dict] = []
    events: List[dict] = []
    used_pids: set = set()
    total_dropped = 0
    empty_inputs: List[str] = []
    for label, header, file_events in parsed:
        src_pid = int(
            (header or {}).get(
                "pid",
                file_events[0].get("pid", 0) if file_events else 0,
            )
            or 0
        )
        pid = src_pid
        while pid in used_pids:
            pid += 1
        used_pids.add(pid)
        name = (header or {}).get("process_name") or label
        total_dropped += int((header or {}).get("dropped", 0) or 0)
        meta.append(
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "tid": 0,
                "args": {"name": name},
            }
        )
        for tid, tname in sorted(((header or {}).get("tracks") or {}).items()):
            meta.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": pid,
                    "tid": int(tid),
                    "args": {"name": tname},
                }
            )
        if header is None and not file_events:
            # dead/empty rank: a worker that died before its first flush
            # used to vanish from the merged doc entirely — keep its
            # named process track and plant a visible warning row so the
            # absence IS the signal, not silence
            empty_inputs.append(label)
            events.append(
                {
                    "ph": "i",
                    "name": "empty_trace",
                    "s": "p",  # process-scoped marker
                    "ts": 0,
                    "pid": pid,
                    "tid": 0,
                    "args": {
                        "label": label,
                        "warning": "no header and no events in this "
                        "rank's raw trace (worker dead before first "
                        "flush, or truncated to nothing)",
                    },
                }
            )
            continue
        off = offsets.get(label, 0.0)
        if offsets and label in unaligned:
            # alignment happened for the others but this rank shares no
            # flows with them: its track keeps the raw clock — make the
            # skew VISIBLE instead of letting the viewer imply ordering
            events.append(
                {
                    "ph": "i",
                    "name": "unaligned_clock",
                    "s": "p",
                    "ts": 0,
                    "pid": pid,
                    "tid": 0,
                    "args": {
                        "label": label,
                        "warning": "no flow pairs connect this rank to "
                        "the aligned set — its timestamps keep the raw "
                        "per-process clock and may be skewed vs the "
                        "other tracks",
                    },
                }
            )
        for ev in file_events:
            if off:
                ev = {**ev, "ts": round(float(ev.get("ts", 0.0)) - off, 3)}
            if pid != src_pid or "pid" not in ev:
                ev = {**ev, "pid": pid}
            events.append(ev)
    other = {
        "producer": "theanompi_tpu.observability",
        "merged_inputs": len(used_pids),
        "dropped_events": total_dropped,
    }
    if empty_inputs:
        other["empty_inputs"] = empty_inputs
    if offsets:
        other["clock_offsets_us"] = {
            label: round(off, 3) for label, off in sorted(offsets.items())
        }
        if unaligned:
            other["clock_unaligned"] = unaligned
    return {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
        "otherData": other,
    }


# ---------------------------------------------------------------------------
# module-level singleton + convenience API (what call sites import)
# ---------------------------------------------------------------------------

_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, boundary: bool = False, **args):
    """``with span("prefill", slot=i): ...`` — the one-line hot-path
    instrumentation idiom.  Returns the shared no-op when disabled,
    unless ``boundary`` (module docstring: the boundary level)."""
    t = _TRACER
    if boundary:
        return _BoundarySpan(t, name, args)
    if not t.enabled:
        return _NOOP
    return _Span(t, name, args)


def instant(name: str, args: Optional[dict] = None) -> None:
    _TRACER.instant(name, args)


def flow_begin(name: str, flow_id: str, args: Optional[dict] = None) -> None:
    _TRACER.flow_begin(name, flow_id, args)


def flow_end(name: str, flow_id: str, args: Optional[dict] = None) -> None:
    _TRACER.flow_end(name, flow_id, args)


def counter_event(name: str, value: float, **series) -> None:
    _TRACER.counter_event(name, value, **series)


def add_span(name: str, start: float, end: float, args=None) -> None:
    _TRACER.add_span(name, start, end, args)


def enable_request_tracking(
    threshold_s: float = 1.0,
    capacity: int = 64,
    max_events: int = 512,
    worst: int = 8,
) -> None:
    _TRACER.enable_request_tracking(
        threshold_s, capacity=capacity, max_events=max_events, worst=worst
    )


def disable_request_tracking() -> None:
    _TRACER.disable_request_tracking()


def request_tracking_active() -> bool:
    """Cheap gate for request-phase instrumentation call sites."""
    t = _TRACER
    return t.enabled and t._req_tracking


def request_begin(rid: str, **meta) -> None:
    _TRACER.request_begin(rid, **meta)


def request_flag(rid: str, flag: str) -> None:
    _TRACER.request_flag(rid, flag)


def request_mark(rid: str, name: str) -> None:
    _TRACER.request_mark(rid, name)


def request_end(rid: str, status: str = "ok", **extra) -> Optional[dict]:
    return _TRACER.request_end(rid, status=status, **extra)


def retained_requests() -> List[dict]:
    return _TRACER.retained_requests()


def worst_requests() -> List[dict]:
    return _TRACER.worst_requests()


def request_stats() -> dict:
    return _TRACER.request_stats()


def drain_request_digests() -> List[dict]:
    return _TRACER.drain_request_digests()


def traced(name: Optional[str] = None):
    """Decorator form: ``@traced()`` (or ``@traced("label")``) wraps the
    function body in a span."""

    def deco(fn):
        label = name or fn.__qualname__

        @wraps(fn)
        def wrapper(*a, **kw):
            t = _TRACER
            if not t.enabled:
                return fn(*a, **kw)
            with _Span(t, label, {}):
                return fn(*a, **kw)

        return wrapper

    return deco
