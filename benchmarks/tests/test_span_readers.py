"""The per-layer readers that read the program's own boundary spans
(``span_read.py`` and ``readers/span_*.py``), on hand-made spans, the
kernel's roofline reader on a hand-made device trace, and the rehearsal
run of every cell with ``--trace 1``: the new metrics are on its result
line.  Nothing here is a device number."""

from __future__ import annotations

import os
import types

import pytest

import run
import span_read
import trace_reduce
from test_harness import BENCH, BENCHMARK, CELLS, ROOT, rehearsal_for

PAD = run.load_module("readers", "span_pad_share")
SELF = run.load_module("readers", "span_self_median")
MEDIAN = run.load_module("readers", "span_median")
SHARE = run.load_module("readers", "span_share")
ROOFLINE = run.load_module("readers", "paged_attn_roofline")

NEW = {m["name"]: m for m in BENCHMARK["per_layer"]
       if m["source"] in ("program_span", "program_counter")
       or m["name"].endswith("_roofline_pct")}


def span(name, start, end, id, parent=None, **args):
    return dict(name=name, start=start, end=end, id=id, parent=parent,
                tid=0, args=args)


def serve_spans():
    """Three ticks from t = 10: a decode-only tick, a tick with a
    prefill, a decode-only tick; and one tick before the window."""
    return [
        span("tick", 1.0, 1.5, 90),
        span("pick", 1.1, 1.4, 91, 90),
        # tick 1: 10.0 .. 10.1, children 0.004 + 0.010 + 0.060
        span("admit", 10.000, 10.004, 2, 1),
        span("decode_step", 10.010, 10.020, 3, 1, active=32),
        span("pick", 10.030, 10.090, 4, 1, rows=32),
        span("tick", 10.0, 10.1, 1, n=1),
        # tick 2: 10.1 .. 10.5 with a prefill of 0.3 (dispatch 0.05,
        # its own pick 0.2), then the decode's pick 0.05
        span("admit", 10.100, 10.101, 6, 5),
        span("prefill_chunk_dispatch", 10.11, 10.16, 8, 7, rows=2, bucket=32,
             rows_computed=32, useful_tokens=40, computed_tokens=1024),
        span("pick", 10.17, 10.37, 9, 7, rows=32),
        span("prefill", 10.105, 10.405, 7, 5, rows=2, n_tokens=40),
        span("decode_step", 10.41, 10.42, 10, 5, active=32),
        span("pick", 10.43, 10.48, 11, 5, rows=32),
        span("tick", 10.1, 10.5, 5, n=2),
        # tick 3: 10.5 .. 10.58, pick 0.04
        span("decode_step", 10.51, 10.52, 13, 12, active=32),
        span("pick", 10.53, 10.57, 14, 12, rows=32),
        span("prefill_chunk_dispatch", 10.571, 10.572, 15, 12, rows=1,
             bucket=16, rows_computed=32, useful_tokens=11,
             computed_tokens=512),
        span("tick", 10.5, 10.58, 12, n=3),
    ]


SERVE_FACTS = {"t0": 10.0, "window_s": 0.5, "profiler_s": 0.1}


def test_window_selects_by_the_drivers_clock():
    spans = span_read.window(SERVE_FACTS, serve_spans())
    assert len(spans) == 15 and all(s["start"] >= 10.0 for s in spans)
    # the window's end is t0 + window + the profiler's own pauses
    short = span_read.window({"t0": 10.0, "window_s": 0.1}, serve_spans())
    assert {s["id"] for s in short} == {1, 2, 3, 4}
    assert span_read.window({"t0": 50.0, "window_s": 1.0}, serve_spans()) == []


def test_window_of_a_training_run_is_its_newest_steps():
    spans = []
    for i in range(5):
        t = float(i)
        spans += [span("wait", t + 0.01, t + 0.02, 10 * i + 1, 10 * i),
                  span("calc", t + 0.02, t + 0.07, 10 * i + 2, 10 * i),
                  span("train_iter", t, t + 0.1, 10 * i, iter=i),
                  span("print", t + 0.1, t + 0.11, 10 * i + 3)]
    mine = span_read.window({"steps": 2}, spans)
    assert [s["args"]["iter"] for s in span_read.named(mine, "train_iter")] == [3, 4]
    assert len(mine) == 8
    assert span_read.window({"steps": 0}, spans) == []
    assert span_read.window({"steps": 3}, []) == []


def test_pad_share_is_one_minus_useful_over_computed():
    spans = span_read.window(SERVE_FACTS, serve_spans())
    got = PAD.share(spans, "prefill_chunk_dispatch", "useful_tokens",
                    "computed_tokens")
    assert got == pytest.approx(100.0 * (1.0 - 51 / 1536))
    assert PAD.share([], "prefill_chunk_dispatch", "useful_tokens",
                     "computed_tokens") is None
    assert PAD.share(spans, "no_such_span", "useful_tokens",
                     "computed_tokens") is None


def test_self_time_takes_the_children_and_not_the_grandchildren():
    spans = span_read.window(SERVE_FACTS, serve_spans())
    own = span_read.self_times(spans)
    assert own[1] == pytest.approx(0.1 - 0.004 - 0.010 - 0.060)
    # tick 2: less admit, prefill, decode_step and ITS pick; the
    # prefill's own children come off the prefill
    assert own[5] == pytest.approx(0.4 - 0.001 - 0.3 - 0.01 - 0.05)
    assert own[7] == pytest.approx(0.3 - 0.05 - 0.2)
    assert own[12] == pytest.approx(0.08 - 0.01 - 0.04 - 0.001)
    # median of 26, 39, 29 ms
    assert SELF.self_median_ms(spans, "tick") == pytest.approx(29.0)
    assert SELF.self_median_ms([], "tick") is None


def test_pick_of_the_decode_only_ticks():
    spans = span_read.window(SERVE_FACTS, serve_spans())
    # ticks 1 and 3 ran no prefill: their picks are 60 and 40 ms
    assert MEDIAN.median_ms(spans, "pick", "tick", "prefill") == pytest.approx(50.0)
    # every pick directly under a tick: 60, 50, 40
    assert MEDIAN.median_ms(spans, "pick", "tick") == pytest.approx(50.0)
    # every pick: 60, 200, 50, 40
    assert MEDIAN.median_ms(spans, "pick") == pytest.approx(55.0)
    assert MEDIAN.median_ms(spans, "calc") is None


def test_share_of_one_span_in_another():
    spans = [span("train_iter", 0.0, 1.0, 1), span("wait", 0.0, 0.25, 2, 1),
             span("train_iter", 1.0, 2.0, 3), span("wait", 1.0, 1.25, 4, 3)]
    assert SHARE.share(spans, "wait", "train_iter") == pytest.approx(25.0)
    assert SHARE.share(spans, "calc", "train_iter") == 0.0
    assert SHARE.share([], "wait", "train_iter") is None


def roofline_ctx(ops, kernel="paged_decode_attn"):
    dev = trace_reduce.Device(index=0, ops=ops)
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(devices=[dev]),
        # (start, seconds, tokens, prefill tick, resident tokens)
        facts={"ticks": [(9.0, 0.1, 32, False, 5000),
                         (10.0, 0.1, 32, False, 1000),
                         (10.1, 0.4, 34, True, 1500),
                         (12.5, 0.1, 32, False, 7000)],
               "traced": (9.5, 12.0)},
        config={"n_layer": 2, "n_embd": 64, "n_head": 4,
                "compute_dtype": "bfloat16", "engine": {"kv_dtype": "fp32"}},
        peaks={"hbm_bytes_per_s": 1e6}, args={"kernel": kernel})


def test_roofline_share_of_the_named_kernel_on_three_events():
    ops = [("%paged_decode_attn.1 = f32[2]{0} custom-call(...)", 0.0, 1e9),
           ("%fusion.7 = f32[2]{0} fusion(...)", 1e9, 2e9),
           ("%paged_decode_attn.2 = f32[2]{0} custom-call(...)", 2e9, 5e9)]
    ctx = roofline_ctx(ops)
    # 2 x 2 layers x 64 x 2 bytes a resident token; the two ticks that
    # start in the traced stretch hold 2500; the kernel ran 4 s
    assert ROOFLINE.kv_bytes_per_token(ctx.config) == 512
    assert ROOFLINE.read(ctx) == pytest.approx(100.0 * 2500 * 512 / 1e6 / 4.0)
    # an int8 pool: a byte a value and a float32 scale a (row, head)
    ctx.config["engine"]["kv_dtype"] = "int8"
    assert ROOFLINE.kv_bytes_per_token(ctx.config) == 2 * 2 * (64 + 16)
    # the XLA gather in the kernel's place, a kernel without a name, no
    # trace, an untraced run: nothing to read
    assert ROOFLINE.read(roofline_ctx(ops[1:2])) is None
    assert ROOFLINE.read(roofline_ctx(
        [("%_paged_decode_fn.3 = f32[2]{0} custom-call(...)", 0.0, 1e9)])) is None
    ctx.trace = None
    assert ROOFLINE.read(ctx) is None
    ctx = roofline_ctx(ops)
    ctx.facts["traced"] = (None, None)
    assert ROOFLINE.read(ctx) is None


def test_span_readers_find_nothing_in_a_program_without_the_buffer(monkeypatch):
    """A parent commit's tracer has no ``boundary_spans``: the readers
    return nothing and do not raise."""
    from theanompi_tpu import observability as obs

    monkeypatch.setattr(obs, "get_tracer", lambda: object())
    assert span_read.boundary_spans() == []
    for reader, args in (
            (PAD, {"span": "prefill_chunk_dispatch", "useful": "useful_tokens",
                   "computed": "computed_tokens"}),
            (SELF, {"span": "tick"}), (MEDIAN, {"span": "calc"}),
            (SHARE, {"part": "wait", "whole": "train_iter"})):
        for facts in ({"t0": 0.0, "window_s": 1e12}, {"steps": 3}):
            ctx = types.SimpleNamespace(facts=facts, args=args)
            assert reader.read(ctx) is None


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_rehearsal_prints_the_cells_new_metrics(cell, tmp_path, capsys):
    if CELLS[cell]["chips"] > 1:
        import jax

        if len(jax.devices()) < CELLS[cell]["chips"]:
            pytest.skip("needs virtual devices (see conftest.py)")
    # a root of its own (links to the benchmark's files): the profiler's
    # directory lies under the root, and another worker may be running
    # test_harness's traced rehearsal of this cell at the same moment
    os.symlink(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    os.symlink(BENCH, tmp_path / "benchmarks")
    result, _ = run.run_cell(cell, 2**31 + 4242, 1.0, True,
                             rehearsal=rehearsal_for(cell), root=str(tmp_path))
    capsys.readouterr()
    got = result["metrics"]  # test_harness judges `correct`; not again here
    want = {n for n, m in NEW.items()
            if cell in m["workloads"] and m["source"] != "device_trace"}
    assert want, cell
    assert want <= set(got), want - set(got)
    for name in want:
        assert got[name]["unit"] == NEW[name]["unit"]
        assert got[name]["value"] >= 0.0
    if "serve_prefill_pad_pct" in want:
        assert 0.0 < got["serve_prefill_pad_pct"]["value"] < 100.0
    if "train_input_wait_pct" in want:
        assert got["train_input_wait_pct"]["value"] < 100.0
    # the recorded trace is a training step's: no paged kernel in it
    assert "serve_paged_attn_roofline_pct" not in got
