"""The narrow paged prefill program, split over a tick's pending lanes
(ISSUE 26).

Contracts under test (CPU, float32, a tiny model):

- one tick feeds EVERY lane that holds unfed prompt tokens, in
  ``ceil(pending / prefill_rows)`` calls of the engine's one narrow
  shape, and the greedy tokens are those of an engine whose prefill
  program is ``n_slots`` wide, token for token;
- multi-chunk prompts and prefix-cache hits (``p0 > 0``) cross a split
  unchanged;
- one prefill program per chunk bucket met, whatever the number of
  pending lanes: a lone request per bucket (the benchmark's warm-up)
  leaves nothing to build;
- the speculative verify program keeps its ``n_slots`` rows and its one
  trace;
- the ``prefill`` boundary span says how many calls the tick made.
"""

import math

import numpy as np
import pytest

import jax

from theanompi_tpu import observability as obs
from theanompi_tpu.models.transformer import TransformerLM, make_draft
from theanompi_tpu.runtime.mesh import make_mesh
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler,
    PagedServingEngine,
    Request,
)
from theanompi_tpu.serving.paging import PREFILL_ROWS

CFG = dict(
    seq_len=64,
    vocab_size=32,
    d_model=32,
    n_heads=4,
    n_layers=2,
    batch_size=2,
    n_synth_train=2,
    n_synth_val=1,
    comm_probe=False,
    print_freq=10_000,
)
N_SLOTS = 8
GEOMETRY = dict(n_slots=N_SLOTS, max_len=64, buckets=(8, 16, 64),
                block_size=8, prefill_chunk=16)


@pytest.fixture(scope="module")
def model():
    return TransformerLM(config=dict(CFG),
                         mesh=make_mesh(devices=jax.devices()[:1]))


@pytest.fixture(scope="module")
def narrow(model):
    return PagedServingEngine(model, **GEOMETRY)


@pytest.fixture(scope="module")
def wide(model):
    """The program of before: as many rows as lanes, one call a tick."""
    return PagedServingEngine(model, prefill_rows=N_SLOTS, **GEOMETRY)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 32, size=n).tolist()


def _requests(lengths, n_new=6):
    return [Request(id=f"r{i}", prompt=_prompt(i, n), max_new_tokens=n_new)
            for i, n in enumerate(lengths)]


def _dispatches(tracer):
    return [s for s in tracer.boundary_spans()
            if s["name"] == "prefill_chunk_dispatch"]


def test_the_default_width_is_the_engines_constant(model, narrow, wide):
    assert narrow.prefill_rows == PREFILL_ROWS < N_SLOTS == wide.prefill_rows
    small = PagedServingEngine(model, n_slots=2, max_len=64, block_size=8)
    assert small.prefill_rows == 2  # never wider than the lanes there are


@pytest.mark.parametrize("n", [1, PREFILL_ROWS, PREFILL_ROWS + 1, N_SLOTS])
def test_one_tick_feeds_every_pending_lane(narrow, wide, n):
    """``n`` lanes pending at once: ``ceil(n / R)`` calls in the first
    tick, every lane advanced, and the tokens of the wide program."""
    lengths = [3 + 2 * i for i in range(n)]  # 3..17: buckets 8, 16, 64
    tracer = obs.get_tracer()
    sched = ContinuousBatchingScheduler(narrow)
    for r in _requests(lengths):
        sched.submit(r)
    tracer.clear()
    sched.step()
    want_calls = math.ceil(n / PREFILL_ROWS)
    calls = _dispatches(tracer)
    assert len(calls) == want_calls == sched.stats["prefill_chunks"]
    assert [c["args"]["rows"] for c in calls] == [
        min(PREFILL_ROWS, n - g) for g in range(0, n, PREFILL_ROWS)]
    # each call's bucket is chosen from its own rows
    for g, c in zip(range(0, n, PREFILL_ROWS), calls):
        longest = min(16, max(lengths[g:g + PREFILL_ROWS]))
        assert c["args"]["bucket"] == narrow.pick_chunk_bucket(longest)
        assert c["args"]["rows_computed"] == PREFILL_ROWS
    (prefill,) = [s for s in tracer.boundary_spans() if s["name"] == "prefill"]
    # every call (and its draw) is enqueued before the first pick waits
    children = sorted((s for s in tracer.boundary_spans()
                       if s["parent"] == prefill["id"]),
                      key=lambda s: s["start"])
    assert [s["name"] for s in children] == (
        ["prefill_chunk_dispatch"] * want_calls + ["pick"] * want_calls)
    assert {s["args"]["rows"] for s in children[want_calls:]} == {PREFILL_ROWS}
    assert prefill["args"]["calls"] == want_calls
    assert prefill["args"]["rows"] == n
    assert prefill["args"]["n_tokens"] == sum(min(16, m) for m in lengths)
    for slot, m in zip(sched.slots, lengths):
        assert slot.n_fed == min(16, m)
    got = sched.run()

    ref = ContinuousBatchingScheduler(wide)
    for r in _requests(lengths):
        ref.submit(r)
    ref.step()
    assert ref.stats["prefill_chunks"] == 1
    want = ref.run()
    assert got == want
    assert sched.stats["prefill_tokens"] == ref.stats["prefill_tokens"]


@pytest.mark.parametrize("kv_dtype,paged_attn", [
    ("fp32", "xla"), ("fp32", "pallas"), ("int8", "xla"), ("int8", "pallas"),
])
def test_multichunk_prompts_and_prefix_hits_cross_a_split(
    model, narrow, wide, kv_dtype, paged_attn
):
    """Six lanes of two or three chunks each, five of them admitted on
    a cached prefix (``p0 > 0``: the shared blocks' rows are read from
    the layers' pool arrays, not written again), in two calls a tick.
    The narrow engine decodes through the XLA gather or the kernel,
    over a float or an int8 pool; the wide one always gathers."""
    if (kv_dtype, paged_attn) != ("fp32", "xla"):
        narrow = PagedServingEngine(model, kv_dtype=kv_dtype,
                                    paged_attn=paged_attn, **GEOMETRY)
        assert narrow.paged_attn_effective == paged_attn
    if kv_dtype != "fp32":
        wide = PagedServingEngine(model, prefill_rows=N_SLOTS,
                                  kv_dtype=kv_dtype, **GEOMETRY)
    shared = _prompt(99, 24)  # three full blocks of 8

    def drive(engine):
        sched = ContinuousBatchingScheduler(engine)
        sched.submit(Request(id="first", prompt=shared + [1, 2, 3],
                             max_new_tokens=3))
        sched.run()
        for i in range(5):
            sched.submit(Request(id=f"hit{i}",
                                 prompt=shared + _prompt(i, 14 + 3 * i),
                                 max_new_tokens=5))
        sched.submit(Request(id="miss", prompt=_prompt(7, 40),
                             max_new_tokens=5))
        chunks = sched.stats["prefill_chunks"]
        sched.step()
        per_tick = sched.stats["prefill_chunks"] - chunks
        return sched.run(), per_tick, sched.stats

    got, calls, stats = drive(narrow)
    want, one, ref_stats = drive(wide)
    assert (calls, one) == (2, 1)
    assert stats["prefix_hit_tokens"] == ref_stats["prefix_hit_tokens"] == 5 * 24
    assert stats["prefill_tokens"] == ref_stats["prefill_tokens"]
    assert got == want


def test_one_program_per_bucket_whatever_the_pending_lanes(model):
    """The benchmark's warm-up: a lone request per chunk bucket.  After
    it no number of pending lanes builds another prefill program."""
    engine = PagedServingEngine(model, **GEOMETRY)
    sched = ContinuousBatchingScheduler(engine)
    assert engine.chunk_buckets == (8, 16)
    for i, b in enumerate(engine.chunk_buckets):
        sched.submit(Request(id=f"warm{i}", prompt=_prompt(i, b),
                             max_new_tokens=2))
        sched.run()
    assert engine._n_prefill_traces == len(engine.chunk_buckets)
    for n in (1, 2, 3, PREFILL_ROWS, PREFILL_ROWS + 1, N_SLOTS):
        for r in _requests([5 + (3 * i) % 30 for i in range(n)], n_new=3):
            r.id = f"n{n}.{r.id}"
            sched.submit(r)
        sched.run()
    assert engine._n_prefill_traces == len(engine.chunk_buckets)
    assert engine._n_decode_traces == 1


def test_verify_program_keeps_its_rows_and_its_one_trace(model, narrow):
    """The speculative verify dispatch shares ``_paged_chunk_fn`` and is
    fed by the caller's ``n_slots``-row arrays: untouched."""
    draft = PagedServingEngine(make_draft(model, n_layers=1), **GEOMETRY)
    seen = []
    inner = narrow.verify_chunks

    def verify_chunks(params, state, tokens, *rest):
        state, logits = inner(params, state, tokens, *rest)
        seen.append((np.asarray(tokens).shape, logits.shape))
        return state, logits

    before = narrow._n_verify_traces
    narrow.verify_chunks = verify_chunks
    try:
        sched = ContinuousBatchingScheduler(narrow, spec_k=3,
                                            draft_engine=draft)
        reqs = _requests([4, 9, 17, 5, 30, 6], n_new=7)
        for r in reqs:
            sched.submit(r)
        got = sched.run()
    finally:
        del narrow.verify_chunks
    assert narrow._n_verify_traces == before + 1
    assert set(seen) == {((N_SLOTS, 4), (N_SLOTS, 4, CFG["vocab_size"]))}
    plain = ContinuousBatchingScheduler(narrow)
    for r in _requests([4, 9, 17, 5, 30, 6], n_new=7):
        plain.submit(r)
    assert got == plain.run()
    # the draft's own prefill is the narrow program too
    assert draft.prefill_rows == PREFILL_ROWS


def test_sampled_first_tokens_cross_a_split_unchanged(narrow, wide):
    """The draw of a completing lane is keyed by its request alone: six
    sampling requests in two calls draw what one wide call draws."""
    def drive(engine):
        sched = ContinuousBatchingScheduler(engine)
        for i, n in enumerate((4, 9, 13, 6, 11, 5)):
            sched.submit(Request(id=f"s{i}", prompt=_prompt(i, n),
                                 max_new_tokens=5, temperature=0.8,
                                 top_k=8, seed=100 + i))
        return sched.run()

    assert drive(narrow) == drive(wide)
