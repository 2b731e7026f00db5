"""The readers of the spans that account for the device's idle time
(``readers/host_gap_share.py``, ``span_rate.py``,
``span_self_median_with.py`` and ``span_median`` over ``host_inputs``),
on hand-made spans.  Nothing here is a device number."""

from __future__ import annotations

import types

import pytest

import run
import span_read

GAP = run.load_module("readers", "host_gap_share")
RATE = run.load_module("readers", "span_rate")
LAUNCH = run.load_module("readers", "span_self_median_with")
MEDIAN = run.load_module("readers", "span_median")


def span(name, start, end, id, parent=None, **args):
    return dict(name=name, start=start, end=end, id=id, parent=parent,
                tid=0, args=args)


def decode(t, id, tick):
    """A decode call at ``t`` (ms after 10 s): inputs 0.5 ms, launch
    1.5, then its pick: the draw 0.5, the wait, the transfer 0.5."""
    s = 10.0 + t / 1e3
    return [
        span("host_inputs", s, s + 0.0005, id + 1, id, arrays=4),
        span("decode_step", s, s + 0.002, id, tick, active=32),
        span("device_wait", s + 0.0025, s + 0.0095, id + 3, id + 2, drains=True),
        span("pick", s + 0.002, s + 0.010, id + 2, tick, rows=32),
    ]


def serve_spans():
    """Four ticks from 10 s (times in ms): a decode-only tick (0-12), a
    tick with a prefill of two calls and a decode (13-60), two
    decode-only ticks (61-70, 71-80); and a gc pause in the second."""
    return [
        # tick 1
        *decode(1, 10, 1),
        span("tick", 10.000, 10.012, 1, n=1),
        # tick 2: both calls enqueued, then the picks; the first wait
        # leaves the second call queued, the second drains
        span("host_inputs", 10.015, 10.016, 21, 20, arrays=5),
        span("prefill_chunk_dispatch", 10.015, 10.017, 20, 3),
        span("host_inputs", 10.017, 10.018, 23, 22, arrays=5),
        span("prefill_chunk_dispatch", 10.017, 10.019, 22, 3),
        span("device_wait", 10.020, 10.029, 25, 24, drains=False),
        span("pick", 10.020, 10.030, 24, 3, rows=4),
        span("device_wait", 10.030, 10.043, 27, 26, drains=True),
        span("pick", 10.030, 10.044, 26, 3, rows=4),
        span("prefill", 10.014, 10.045, 3, 2, rows=6, calls=2),
        span("gc", 10.0445, 10.0449, 28, 2, generation=2, collected=0,
             uncollectable=0),
        *decode(46, 30, 2),
        span("tick", 10.013, 10.060, 2, n=2),
        # ticks 3 and 4
        *decode(62, 40, 4),
        span("tick", 10.061, 10.072, 4, n=3),
        *decode(74, 50, 5),
        span("tick", 10.073, 10.085, 5, n=4),
    ]


FACTS = {"t0": 10.0, "window_s": 0.5, "profiler_s": 0.0}


def gap(on, off):
    return GAP.share(span_read.window(FACTS, serve_spans()), on, off)


def test_decode_only_ticks():
    # ticks 3 and 4 (61-85): the wait of 3 drains at 71.5, the next
    # decode's launch has enqueued at 76; the wait of 4 has no next
    # dispatch
    assert gap(10.0605, 10.0735) == pytest.approx(100.0 * 4.5 / 24.0)


def test_a_two_call_prefill_tick_drains_at_its_second_pick_only():
    # tick 2 alone: the second prefill pick drains at 43, the decode's
    # launch has enqueued at 48; the first pick's wait does not count
    assert gap(10.0125, 10.0135) == pytest.approx(100.0 * 5.0 / 47.0)
    # had the first wait drained, the gap would open at 29
    spans = [dict(s, args=dict(s["args"], drains=True)) if s["id"] == 25 else s
             for s in serve_spans()]
    assert GAP.share(spans, 10.0125, 10.0135) == pytest.approx(
        100.0 * 19.0 / 47.0)


def test_a_gap_that_spans_two_ticks():
    # ticks 1 and 2: tick 1's wait drains at 10.5, the first prefill call
    # of tick 2 has enqueued at 17; then 43 -> 48; tick 2's decode wait
    # has no next dispatch among the ticks read
    assert gap(9.9, 10.0125) == pytest.approx(100.0 * 0.0 / 12.0)
    assert gap(9.9, 10.0135) == pytest.approx(100.0 * (6.5 + 5.0) / 60.0)
    # all four: 6.5 + 5 + (55.5 -> 64) + (71.5 -> 76) over 85 ms
    assert gap(9.9, 10.1) == pytest.approx(100.0 * 24.5 / 85.0)


def test_host_gap_read_from_the_facts(monkeypatch):
    monkeypatch.setattr(span_read, "boundary_spans",
                        lambda since=None, until=None: serve_spans())
    ctx = types.SimpleNamespace(facts=dict(FACTS, traced=(9.9, 10.1)), args={})
    assert GAP.read(ctx) == pytest.approx(100.0 * 24.5 / 85.0)
    for traced in ((None, None), None):
        ctx.facts["traced"] = traced
        assert GAP.read(ctx) is None
    assert GAP.share(serve_spans(), 20.0, 30.0) is None  # no tick traced


def test_decode_inputs_and_launch():
    spans = span_read.window(FACTS, serve_spans())
    assert MEDIAN.median_ms(spans, "host_inputs", "decode_step") == pytest.approx(0.5)
    assert LAUNCH.self_median_ms(spans, "decode_step", "host_inputs") == pytest.approx(1.5)
    # the prefill calls' own inputs are not the decode's
    assert MEDIAN.median_ms(spans, "host_inputs", "prefill_chunk_dispatch") == pytest.approx(1.0)


def test_gc_time_a_second_of_the_window(monkeypatch):
    spans = span_read.window(FACTS, serve_spans())
    assert RATE.ms_per_s(spans, "gc", 0.5) == pytest.approx(0.4 / 0.5)
    assert RATE.ms_per_s([], "gc", 0.5) == 0.0
    assert RATE.ms_per_s(spans, "gc", 0.0) is None
    assert RATE.recorded("gc")
    monkeypatch.setattr(span_read, "boundary_spans",
                        lambda since=None, until=None: serve_spans())
    ctx = types.SimpleNamespace(facts=FACTS, args={"span": "gc"})
    assert RATE.read(ctx) == pytest.approx(0.8)


def test_a_program_without_these_spans_reads_nothing(monkeypatch):
    """A parent commit: boundary spans, but no ``host_inputs``,
    ``device_wait`` or ``gc`` among them, and no ``gc`` among the names
    the program records.  Every reader returns nothing and none raises."""
    from theanompi_tpu import observability as obs

    old = [s for s in serve_spans()
           if s["name"] not in ("host_inputs", "device_wait", "gc")]
    monkeypatch.setattr(span_read, "boundary_spans",
                        lambda since=None, until=None: old)
    monkeypatch.setattr(obs, "BOUNDARY_SPANS", tuple(
        n for n in obs.BOUNDARY_SPANS
        if n not in ("host_inputs", "device_wait", "counters_fetch", "gc")))
    facts = dict(FACTS, traced=(9.9, 10.1))
    for reader, args in (
            (GAP, {}), (RATE, {"span": "gc"}),
            (LAUNCH, {"span": "decode_step", "child": "host_inputs"}),
            (MEDIAN, {"span": "host_inputs", "parent": "decode_step"})):
        assert reader.read(types.SimpleNamespace(facts=facts, args=args)) is None
    # and a program with no tracer at all
    monkeypatch.undo()
    monkeypatch.setattr(obs, "get_tracer", lambda: object())
    for reader, args in ((GAP, {}), (RATE, {"span": "no_such_span"}),
                         (LAUNCH, {"span": "decode_step", "child": "host_inputs"})):
        assert reader.read(types.SimpleNamespace(facts=facts, args=args)) is None
