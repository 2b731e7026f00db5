"""The program's own boundary spans, for the per-layer readers.

The program (``theanompi_tpu.observability``) records a span at each
layer boundary whether or not its tracing is enabled: a serving tick and
what it calls (``tick`` > ``admit``, ``prefill`` >
``prefill_chunk_dispatch`` + ``pick``, ``decode_step``, ``pick``), a
training step and its phases (``train_iter`` > ``wait`` + ``calc``,
``print``).  Each is a dict ``name, start, end, id, parent, tid, args``
with ``start``/``end`` in seconds on ``time.perf_counter``, the clock
the drivers read (``facts["t0"]``, ``facts["traced"]``, a tick's start),
so a window's spans are selected by time and nothing is snapshot before
and after.  The readers run in the program's process after
``driver.release()``; the tracer's buffer outlives the engine.

A program without the boundary level (a parent commit) has no such
buffer: every function here then returns nothing, and the readers
``None``.
"""

from __future__ import annotations

import statistics


def boundary_spans(since=None, until=None) -> list:
    """The program's buffered boundary spans whose start lies in
    ``[since, until)``, oldest first; ``[]`` where the program has
    none."""
    try:
        from theanompi_tpu import observability as obs

        get = obs.get_tracer().boundary_spans
    except (ImportError, AttributeError):
        return []
    return get(since, until)


def window(facts: dict, spans=None) -> list:
    """The spans of the measured window.  A serving driver's facts hold
    the window's start ``t0`` on ``perf_counter`` and its length (with
    the profiler's own pauses apart): the spans that start inside it.
    A training driver's hold no ``t0``: the newest ``facts["steps"]``
    ``train_iter`` spans and whatever started since the first of them.
    ``spans`` (the tests') stands in for the program's buffer."""
    if "t0" in facts:
        t0 = float(facts["t0"])
        until = t0 + float(facts["window_s"]) + float(facts.get("profiler_s", 0.0))
        if spans is None:
            return boundary_spans(t0, until)
        return [s for s in spans if t0 <= s["start"] < until]
    steps = int(facts.get("steps") or 0)
    if spans is None:
        spans = boundary_spans()
    iters = [s for s in spans if s["name"] == "train_iter"][-steps:] if steps else []
    if not iters:
        return []
    since = iters[0]["start"]
    return [s for s in spans if s["start"] >= since]


def named(spans, name: str) -> list:
    return [s for s in spans if s["name"] == name]


def duration(span) -> float:
    return span["end"] - span["start"]


def self_times(spans) -> dict:
    """``{id: seconds}``: each span's duration less its children's (the
    children of one span never overlap: one thread, one stack)."""
    own = {s["id"]: duration(s) for s in spans}
    for s in spans:
        if s["parent"] in own:
            own[s["parent"]] -= duration(s)
    return own


def child_names(spans) -> dict:
    """``{id: set of the names of its children}``."""
    out = {}
    for s in spans:
        out.setdefault(s["parent"], set()).add(s["name"])
    return out


def median_ms(seconds):
    seconds = list(seconds)
    return 1e3 * statistics.median(seconds) if seconds else None
