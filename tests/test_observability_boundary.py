"""The boundary level of the span tracer (ISSUE 25).

A boundary span is recorded whether or not tracing is enabled, carries
``id`` and ``parent``, feeds the flight rings, and holds the annotation
hook's context open for its duration; ordinary spans stay a no-op while
tracing is off.  The call sites: a serving tick and what it calls, a
training step and its phases.  Device kernels carry stable names.
"""

import ast
import math
import os
import subprocess
import sys
import time

import pytest

from theanompi_tpu import observability as obs
from theanompi_tpu.observability import trace as trace_mod
from theanompi_tpu.observability.flight import FlightRecorder
from theanompi_tpu.observability.trace import Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracing_off():
    """The process-global tracer, disabled and empty for one test (an
    earlier test of a full run may have left it enabled)."""
    tracer = obs.get_tracer()
    was = tracer.enabled
    tracer.disable()
    tracer.clear()
    try:
        yield tracer
    finally:
        tracer.enabled = was
        tracer.clear()


def _self_times(spans):
    spans = list(spans)
    own = {s["id"]: s["end"] - s["start"] for s in spans}
    for s in spans:
        if s["parent"] in own:
            own[s["parent"]] -= s["end"] - s["start"]
    return own


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

def test_boundary_span_is_recorded_with_tracing_disabled():
    t = Tracer(pid=1)
    assert not t.enabled
    with t.span("ordinary", slot=1):
        pass
    t.add_span("ordinary_explicit", 0.0, 1.0)
    assert t.snapshot() == []
    with t.span("tick", boundary=True, n=1) as s:
        s.set(produced=3)
    assert [e["name"] for e in t.snapshot()] == ["tick"]
    tick = t.snapshot()[0]
    assert tick["args"] == {"n": 1, "produced": 3}
    assert tick["parent"] is None and isinstance(tick["id"], int)


def test_parent_and_id_nest_and_self_time_closes():
    ticks = iter(float(i) for i in range(100))
    t = Tracer(clock=lambda: next(ticks), pid=1)
    with t.span("tick", boundary=True):                      # 1 .. 8
        with t.span("prefill", boundary=True):               # 2 .. 5
            with t.span("prefill_chunk_dispatch", boundary=True):  # 3 .. 4
                pass
        with t.span("decode_step", boundary=True):           # 6 .. 7
            pass
    spans = {s["name"]: s for s in t.boundary_spans()}
    assert spans["tick"]["parent"] is None
    assert spans["prefill"]["parent"] == spans["tick"]["id"]
    assert (spans["prefill_chunk_dispatch"]["parent"]
            == spans["prefill"]["id"])
    assert spans["decode_step"]["parent"] == spans["tick"]["id"]
    assert len({s["id"] for s in spans.values()}) == 4
    # self time = duration less the children's: tick is 7 long with
    # children of 3 and 1, prefill 3 long with a child of 1
    own = _self_times(spans.values())
    assert own[spans["tick"]["id"]] == pytest.approx(3.0)
    assert own[spans["prefill"]["id"]] == pytest.approx(2.0)
    assert own[spans["decode_step"]["id"]] == pytest.approx(1.0)
    # on the tracer's own clock, selected by start time
    assert spans["tick"]["start"] == 1.0 and spans["tick"]["end"] == 8.0
    assert [s["name"] for s in t.boundary_spans(2.5, 6.5)] == [
        "prefill_chunk_dispatch", "decode_step"]


def test_a_span_left_open_by_an_exception_does_not_adopt_later_spans():
    t = Tracer(pid=1)
    with pytest.raises(RuntimeError):
        with t.span("train_iter", boundary=True):
            t.span("calc", boundary=True).__enter__()  # never exited
            raise RuntimeError("step failed")
    with t.span("train_iter", boundary=True):
        pass
    later = t.boundary_spans()[-1]
    assert later["name"] == "train_iter" and later["parent"] is None


def test_threads_have_their_own_parent_stacks():
    import threading

    t = Tracer(pid=1)
    seen = {}

    def worker():
        with t.span("train_iter", boundary=True):
            pass
        seen["worker"] = t.boundary_spans()[-1]

    with t.span("tick", boundary=True):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert seen["worker"]["parent"] is None  # not the main thread's tick


def test_buffer_stays_bounded_with_tracing_off():
    t = Tracer(pid=1, buffer=8)
    for i in range(20):
        with t.span("tick", boundary=True, n=i):
            pass
    spans = t.boundary_spans()
    assert len(spans) == 8 and t.dropped == 12
    assert [s["args"]["n"] for s in spans] == list(range(12, 20))


def test_boundary_spans_are_never_sampled_out():
    t = Tracer(pid=1, sample_rate=4)
    t.enable()
    for _ in range(8):
        with t.span("ordinary"):
            pass
        with t.span("tick", boundary=True):
            pass
    names = [e["name"] for e in t.snapshot()]
    assert names.count("tick") == 8 and names.count("ordinary") < 8


def test_flight_ring_receives_boundary_spans_with_tracing_off(tracing_off):
    """Without this a post-mortem of a default run held events and no
    span: the ring was fed through ``enable_tracing`` alone."""
    ring = obs.get_flight_recorder()
    ring.clear()
    with obs.span("ordinary"):
        pass
    with obs.span("tick", boundary=True, n=7):
        pass
    mine = [e for events in ring.snapshot().values() for e in events]
    assert [e["name"] for e in mine] == ["tick"]
    # and a tracer of one's own feeds whatever sink it is given
    fr, t = FlightRecorder(capacity=4), Tracer(pid=1)
    t.span_sinks.append(fr.record_span)
    with t.span("train_iter", boundary=True):
        pass
    assert [e["name"] for e in fr.snapshot()["MainThread"]] == ["train_iter"]


def test_annotation_hook_is_held_open_for_the_span(monkeypatch):
    calls = []

    class Annotation:
        def __init__(self, name, **args):
            self.name = name
            calls.append(("init", name, args))

        def __enter__(self):
            calls.append(("enter", self.name))

        def __exit__(self, *exc):
            calls.append(("exit", self.name))

    monkeypatch.setattr(trace_mod, "_ANNOTATE", None)
    obs.install_annotation_hook(Annotation)
    t = Tracer(pid=1)
    with t.span("tick", boundary=True, n=3):
        with t.span("ordinary"):
            pass
        with t.span("pick", boundary=True, rows=2):
            pass
    assert calls == [
        ("init", "tick", {"n": 3}), ("enter", "tick"),
        ("init", "pick", {"rows": 2}), ("enter", "pick"), ("exit", "pick"),
        ("exit", "tick"),
    ]
    obs.install_annotation_hook(None)
    with t.span("tick", boundary=True):
        pass
    assert len(calls) == 6


def test_the_program_installs_the_profilers_annotation():
    import jax

    import theanompi_tpu.models.base  # noqa: F401  (installs on import)
    import theanompi_tpu.serving.engine  # noqa: F401

    assert trace_mod._ANNOTATE is jax.profiler.TraceAnnotation
    # with no profiler session the annotation is inert; the span records
    with obs.span("tick", boundary=True, n=1):
        pass
    assert [s["name"] for s in obs.get_tracer().boundary_spans()
            if s["name"] != "gc"][-1] == "tick"


def test_importable_and_recording_without_jax():
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "import theanompi_tpu.observability as o\n"
        "with o.span('tick', boundary=True, n=1):\n"
        "    with o.span('ordinary'):\n"
        "        pass\n"
        "assert [s['name'] for s in o.get_tracer().boundary_spans()\n"
        "        if s['name'] != 'gc'] == ['tick']\n"
        "assert sys.modules.get('jax') is None\n"
        "print('OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_wall_offset_puts_perf_counter_on_the_unix_clock():
    t = Tracer(pid=1)
    unix_ns = int(time.perf_counter() * 1e9) + t.wall_offset_ns
    assert abs(unix_ns - time.time_ns()) < 50_000_000  # 50 ms: coarse clocks


def test_boundary_span_overhead(tracing_off):
    """Sibling of ``test_disabled_span_overhead``: the always-on level
    must stay cheap enough for a handful of spans per tick or step.  It
    is judged against a calibration loop timed in the same process (the
    least a context manager that takes keyword arguments can cost), the
    best of alternating batches of each, so that a loaded host slows
    both: a span costs about ten of it (a few microseconds), and may
    cost forty."""
    class Bare:
        def __init__(self, **args):
            self.args = args

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def per_entry(make, n=2_000):
        t0 = time.perf_counter()
        for i in range(n):
            with make(i):
                pass
        return (time.perf_counter() - t0) / n

    bare, span = [], []
    for _ in range(10):
        bare.append(per_entry(lambda i: Bare(n=i)))
        span.append(per_entry(lambda i: obs.span("tick", boundary=True, n=i)))
    assert min(span) < 40 * min(bare), (
        f"boundary span costs {min(span) * 1e6:.2f}µs, "
        f"{min(span) / min(bare):.1f} bare context managers")


# ---------------------------------------------------------------------------
# the call sites
# ---------------------------------------------------------------------------

def test_recorder_phases_are_boundary_children_of_train_iter(tracing_off):
    from theanompi_tpu.runtime.recorder import Recorder

    rec = Recorder(verbose=False)
    with obs.span("train_iter", boundary=True, iter=1):
        rec.start("wait")
        rec.end("wait")
        rec.start("calc")
        rec.start("calc")  # started again: the first is dropped
        rec.end("calc")
        rec.start("comm")  # an ordinary phase: off with tracing off
        rec.end("comm")
    rec.print_train_info(1)
    # a collection may fall anywhere: its span is no phase of the step
    spans = [s for s in tracing_off.boundary_spans() if s["name"] != "gc"]
    assert [s["name"] for s in spans] == ["wait", "calc", "train_iter", "print"]
    by = {s["name"]: s for s in spans}
    assert by["wait"]["parent"] == by["calc"]["parent"] == by["train_iter"]["id"]
    assert by["print"]["parent"] is None


@pytest.mark.parametrize("n_slots, lengths", [
    (3, (5, 21, 9, 13)),
    (6, (5, 21, 9, 13, 7, 30, 11)),  # more lanes than the program has rows
])
def test_paged_tick_spans_nest_and_count_the_padding(tracing_off, n_slots,
                                                     lengths):
    """One tiny paged run: ``tick`` > ``prefill`` >
    ``prefill_chunk_dispatch``; the dispatch spans' ``useful_tokens`` sum
    to the scheduler's own ``prefill_tokens``, ``computed_tokens`` is
    ``prefill_rows x bucket`` (the program's narrow width, not the
    lanes'), and a ``prefill`` span says how many calls it made."""
    import jax

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.runtime.mesh import make_mesh
    from theanompi_tpu.serving import (
        ContinuousBatchingScheduler, PagedServingEngine, Request,
    )

    cfg = dict(seq_len=64, vocab_size=32, d_model=32, n_heads=4, n_layers=2,
               batch_size=2, n_synth_train=2, n_synth_val=1, comm_probe=False,
               print_freq=10_000)
    model = TransformerLM(config=cfg, mesh=make_mesh(devices=jax.devices()[:1]))
    from theanompi_tpu.serving.paging import PREFILL_ROWS

    engine = PagedServingEngine(model, n_slots=n_slots, max_len=64,
                                block_size=8, buckets=(8, 16, 64),
                                prefill_chunk=16)
    assert engine.prefill_rows == min(n_slots, PREFILL_ROWS)
    sched = ContinuousBatchingScheduler(engine)
    for i, n in enumerate(lengths):
        sched.submit(Request(id=f"r{i}", prompt=list(range(1, n + 1)),
                             max_new_tokens=4))
    tracing_off.clear()
    ticks = 0
    while not sched.idle:
        sched.step()
        ticks += 1
    spans = tracing_off.boundary_spans()
    by_id = {s["id"]: s for s in spans}
    tick_spans = [s for s in spans if s["name"] == "tick"]
    assert len(tick_spans) == ticks
    assert [s["args"]["n"] for s in tick_spans] == list(range(1, ticks + 1))
    assert sum(s["args"]["produced"] for s in tick_spans) == 4 * len(lengths)
    dispatches = [s for s in spans if s["name"] == "prefill_chunk_dispatch"]
    assert len(dispatches) == sched.stats["prefill_chunks"] > 0
    for d in dispatches:
        prefill = by_id[d["parent"]]
        assert prefill["name"] == "prefill"
        assert by_id[prefill["parent"]]["name"] == "tick"
        a = d["args"]
        assert a["rows_computed"] == engine.prefill_rows
        assert a["bucket"] in engine.chunk_buckets
        assert a["computed_tokens"] == engine.prefill_rows * a["bucket"]
        assert 0 < a["useful_tokens"] <= a["rows"] * a["bucket"]
    assert (sum(d["args"]["useful_tokens"] for d in dispatches)
            == sched.stats["prefill_tokens"])
    prefills = [s for s in spans if s["name"] == "prefill"]
    assert (sum(p["args"]["n_tokens"] for p in prefills)
            == sched.stats["prefill_tokens"])
    for p in prefills:
        mine = [d for d in dispatches if d["parent"] == p["id"]]
        assert p["args"]["calls"] == len(mine) == math.ceil(
            p["args"]["rows"] / engine.prefill_rows)
        assert sum(d["args"]["rows"] for d in mine) == p["args"]["rows"]
    assert max(p["args"]["calls"] for p in prefills) == math.ceil(
        n_slots / PREFILL_ROWS)
    # every other child of a tick is one of the boundary names, and a
    # decode's pick hangs under the tick itself
    for s in spans:
        assert s["name"] in obs.BOUNDARY_SPANS
    picks = [s for s in spans if s["name"] == "pick"]
    assert {by_id[p["parent"]]["name"] for p in picks} == {"tick", "prefill"}
    admits = [s for s in spans if s["name"] == "admit"]
    assert sum(a["args"]["admitted"] for a in admits) == len(lengths)
    # self time is never negative and closes: children lie inside
    own = _self_times(spans)
    assert all(v >= -1e-9 for v in own.values())


def test_block_pool_records_no_span_per_freed_block(tracing_off):
    from theanompi_tpu.serving.paging import BlockPool

    tracing_off.enable()
    try:
        pool = BlockPool(9, 4)
        pool.release_all(pool.alloc(6))
    finally:
        tracing_off.disable()
    names = [e["name"] for e in tracing_off.snapshot()]
    assert names == ["block_alloc"]


# ---------------------------------------------------------------------------
# names on the device
# ---------------------------------------------------------------------------

def _pallas_calls():
    """(file, line, keyword names) of every ``pallas_call(...)`` in the
    package, read from the source."""
    out = []
    pkg = os.path.join(ROOT, "theanompi_tpu")
    for dirpath, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            tree = ast.parse(open(path, encoding="utf-8").read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                name = fn.attr if isinstance(fn, ast.Attribute) else getattr(fn, "id", "")
                if name == "pallas_call":
                    out.append((os.path.relpath(path, ROOT), node.lineno,
                                [k.arg for k in node.keywords]))
    return out


def test_every_pallas_call_passes_a_name():
    calls = _pallas_calls()
    assert len(calls) >= 8
    missing = [(f, line) for f, line, kw in calls if "name" not in kw]
    assert not missing, f"pallas_call without name=: {missing}"


def test_paged_programs_carry_their_scopes():
    import jax
    import numpy as np

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.runtime.mesh import make_mesh
    from theanompi_tpu.serving import PagedServingEngine

    cfg = dict(seq_len=32, vocab_size=32, d_model=32, n_heads=4, n_layers=2,
               batch_size=2, n_synth_train=2, n_synth_val=1, comm_probe=False,
               compute_dtype="bfloat16")
    model = TransformerLM(config=cfg, mesh=make_mesh(devices=jax.devices()[:1]))
    eng = PagedServingEngine(model, n_slots=2, max_len=32, block_size=8)
    s, nb = eng.n_slots, eng.blocks_per_seq
    decode = eng._paged_decode_jit.lower(
        model.params, eng.init_state(), np.zeros((s,), np.int32),
        np.zeros((s, nb), np.int32), np.zeros((s,), np.int32),
        np.zeros((s,), bool)).as_text(debug_info=True)
    prefill = eng._paged_prefill_jit.lower(
        model.params, eng.init_state(), np.zeros((s, 8), np.int32),
        np.zeros((s, nb), np.int32), np.zeros((s,), np.int32),
        np.ones((s,), np.int32), np.zeros((s,), bool)).as_text(debug_info=True)
    for text in (decode, prefill):
        for scope in ("/embed/", "/layer0/qkv/", "/layer1/qkv/cast_weights/",
                      "/layer0/pool_update/", "/layer1/paged_attn/",
                      "/layer0/attn_out/", "/layer1/mlp/cast_weights/",
                      "/head/cast_weights/"):
            assert scope in text, scope


def test_train_step_carries_its_scopes():
    """Scopes are metadata on the lowered operations: the layers of
    AlexNet by name under ``forward``, ``loss``, ``exchange``,
    ``update``."""
    import jax

    from theanompi_tpu.models.alex_net import AlexNet

    model = AlexNet(config=dict(image_size=64, n_classes=4, batch_size=2,
                                n_synth_train=2, n_synth_val=1,
                                comm_probe=False),
                    mesh=None)
    fn = model.compile_train()
    x, y = next(iter(model.data.train_batches()))
    text = fn.lower(model.params, model.net_state, model.opt_state, x, y,
                    jax.random.PRNGKey(0)).as_text(debug_info=True)
    # the forward's scopes under jvp(...), the backward's under
    # transpose(jvp(...)): "jit(shard_step)/jvp(forward)/conv1/add"
    for scope in ("(forward)/conv1/", "(forward)/lrn1/", "(forward)/pool1/",
                  "(forward)/conv5/", "(forward)/fc6/", "(forward)/fc8/",
                  "transpose(jvp(forward))/conv1/", "(loss)/", "exchange/psum",
                  "update/mul"):
        assert scope in text, scope
