"""The boundary spans that say what the host does while the device waits
for it: ``host_inputs`` under every dispatch, ``device_wait`` under every
pick (``drains`` on the last wait before the next dispatch), and ``gc``
from the tracer's collector hook, a child of whatever boundary span the
collection interrupted.  ``counters_fetch`` is a latent model's
(``tests/test_latent_lm.py``); a dense model has none."""

import gc
import time

import jax
import pytest

from theanompi_tpu import observability as obs
from theanompi_tpu.models.transformer import TransformerLM, make_draft
from theanompi_tpu.observability import trace as trace_mod
from theanompi_tpu.runtime.mesh import make_mesh
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler, PagedServingEngine, Request,
)
from theanompi_tpu.serving.paging import PREFILL_ROWS

CFG = dict(seq_len=64, vocab_size=32, d_model=32, n_heads=4, n_layers=2,
           batch_size=2, n_synth_train=2, n_synth_val=1, comm_probe=False,
           print_freq=10_000)
DISPATCHES = ("decode_step", "prefill_chunk_dispatch", "spec_verify")


@pytest.fixture(scope="module")
def model():
    return TransformerLM(config=dict(CFG),
                         mesh=make_mesh(devices=jax.devices()[:1]))


def _engine(model, n_slots):
    return PagedServingEngine(model, n_slots=n_slots, max_len=64,
                              block_size=8, buckets=(8, 16, 64),
                              prefill_chunk=16)


def _run(sched, lengths, n_new=4):
    for i, n in enumerate(lengths):
        sched.submit(Request(id=f"r{i}", prompt=list(range(1, n + 1)),
                             max_new_tokens=n_new))
    t0 = time.perf_counter()
    sched.run()
    return obs.get_tracer().boundary_spans(t0)


def test_host_inputs_nest_under_every_dispatch_and_dense_fetches_no_counters(
        model):
    spans = _run(ContinuousBatchingScheduler(_engine(model, 3)), (5, 21, 9))
    by_id = {s["id"]: s for s in spans}
    inputs = [s for s in spans if s["name"] == "host_inputs"]
    parents = [by_id[s["parent"]]["name"] for s in inputs]
    assert set(parents) == {"decode_step", "prefill_chunk_dispatch"}
    # one under each dispatch, and the dispatch's launch follows it
    for name in ("decode_step", "prefill_chunk_dispatch"):
        calls = [s for s in spans if s["name"] == name]
        assert parents.count(name) == len(calls) > 0
    for s in inputs:
        parent = by_id[s["parent"]]
        assert parent["start"] <= s["start"] <= s["end"] <= parent["end"]
        assert s["args"]["arrays"] == (
            4 if parent["name"] == "decode_step" else 5)
    assert not [s for s in spans if s["name"] == "counters_fetch"]


def test_host_inputs_nest_under_spec_verify(model):
    target = PagedServingEngine(model, n_slots=2, max_len=64,
                                buckets=(8, 16, 64), block_size=8)
    draft = PagedServingEngine(make_draft(model, n_layers=1), n_slots=2,
                               max_len=64, buckets=(8, 16, 64), block_size=8)
    sched = ContinuousBatchingScheduler(target, spec_k=3, draft_engine=draft)
    spans = _run(sched, (5, 11), n_new=8)
    by_id = {s["id"]: s for s in spans}
    verify = [s for s in spans if s["name"] == "spec_verify"]
    assert verify
    for v in verify:
        kids = [s for s in spans
                if s["parent"] == v["id"] and s["name"] != "gc"]
        assert [k["name"] for k in kids] == ["host_inputs"]
        assert kids[0]["args"]["arrays"] == 5
    assert "spec_verify_dispatch" not in {s["name"] for s in spans}
    assert all(by_id[s["parent"]]["name"] == "pick"
               for s in spans if s["name"] == "device_wait")


def test_device_wait_under_every_pick_and_only_the_last_before_a_dispatch_drains(
        model):
    """Six lanes pending at once make two prefill calls of four rows in
    the first tick, each completing prompts: both are enqueued before
    the first pick waits, so the first wait leaves the second call on
    the device and does not drain; the second does, and so does every
    decode pick."""
    n = PREFILL_ROWS + 2
    spans = _run(ContinuousBatchingScheduler(_engine(model, n)),
                 [3 + i for i in range(n)])
    by_id = {s["id"]: s for s in spans}
    picks = [s for s in spans if s["name"] == "pick"]
    waits = [s for s in spans if s["name"] == "device_wait"]
    assert len(waits) == len(picks) > 0
    for w in waits:
        pick = by_id[w["parent"]]
        assert pick["name"] == "pick"
        assert pick["start"] <= w["start"] <= w["end"] <= pick["end"]
    # every call of this run completes prompts, so each has a pick: in
    # start order a drained wait is the last wait before a dispatch or
    # the last of all, and a wait that does not drain has a later
    # call's program still queued behind it
    ordered = sorted(waits + [s for s in spans if s["name"] in DISPATCHES],
                     key=lambda s: s["start"])
    for here, after in zip(ordered, ordered[1:] + [None]):
        if here["name"] != "device_wait":
            continue
        last = after is None or after["name"] in DISPATCHES
        assert here["args"]["drains"] is last
    first = [s for s in spans if s["name"] == "prefill"][0]
    prefill_waits = [w["args"]["drains"] for w in waits
                     if by_id[w["parent"]]["parent"] == first["id"]]
    assert prefill_waits == [False, True]


def test_a_collection_inside_a_tick_is_a_gc_span_under_that_tick(model):
    sched = ContinuousBatchingScheduler(_engine(model, 2))
    admit = sched._admit_paged

    def admit_after_a_collection():
        gc.collect()
        admit()

    sched._admit_paged = admit_after_a_collection
    sched.submit(Request(id="r", prompt=[1, 2, 3], max_new_tokens=2))
    t0 = time.perf_counter()
    sched.step()
    spans = obs.get_tracer().boundary_spans(t0)
    by_id = {s["id"]: s for s in spans}
    (tick,) = [s for s in spans if s["name"] == "tick"]
    oldest = [s for s in spans
              if s["name"] == "gc" and s["args"]["generation"] == 2]
    mine = [s for s in oldest if by_id.get(s["parent"]) is tick]
    assert mine
    assert tick["start"] <= mine[0]["start"] <= mine[0]["end"] <= tick["end"]
    assert set(mine[0]["args"]) == {"generation", "collected", "uncollectable"}


def test_a_collection_between_ticks_is_a_root_span():
    t0 = time.perf_counter()
    gc.collect()
    spans = [s for s in obs.get_tracer().boundary_spans(t0)
             if s["name"] == "gc" and s["args"]["generation"] == 2]
    assert spans and spans[-1]["parent"] is None


def test_a_short_young_collection_moves_the_totals_and_records_no_span():
    for _ in range(5):
        before = obs.gc_totals()
        t0 = time.perf_counter()
        gc.collect(0)
        after = obs.gc_totals()
        assert after["collections"] >= before["collections"] + 1
        took = after["seconds"] - before["seconds"]
        assert took > 0.0
        if took < trace_mod.GC_SPAN_S:
            spans = [s for s in obs.get_tracer().boundary_spans(t0)
                     if s["name"] == "gc" and s["args"]["generation"] == 0]
            assert spans == []
            return
    pytest.fail("no young collection took under GC_SPAN_S in five tries")


def test_gc_hook_cost_per_collection():
    """The hook runs for every collection, the young ones thousands of
    times a second: its fast path (no span) is judged against a bare
    callback of the same signature, the best of alternating batches of
    each, as ``test_boundary_span_overhead`` judges a span.  It costs
    about seven bare calls, and may cost twenty."""
    info = {"generation": 0, "collected": 0, "uncollectable": 0}

    def bare(phase, info):
        return None

    def per_collection(hook, n=2_000):
        t0 = time.perf_counter()
        for _ in range(n):
            hook("start", info)
            hook("stop", info)
        return (time.perf_counter() - t0) / n

    totals = list(trace_mod._GC_TOTALS)
    was = gc.isenabled()
    gc.disable()  # no real collection between a start and its stop
    try:
        base, hook = [], []
        for _ in range(10):
            base.append(per_collection(bare))
            hook.append(per_collection(trace_mod._on_gc))
    finally:
        trace_mod._GC_TOTALS[:] = totals
        if was:
            gc.enable()
    assert min(hook) < 20 * min(base), (
        f"gc hook costs {min(hook) * 1e6:.2f}µs a collection, "
        f"{min(hook) / min(base):.1f} bare callbacks")
    assert trace_mod._on_gc in gc.callbacks
    assert sum(cb is trace_mod._on_gc for cb in gc.callbacks) == 1
