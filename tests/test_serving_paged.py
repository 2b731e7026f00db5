"""Paged KV cache, chunked multi-slot prefill, prefix reuse (ISSUE 8).

Acceptance contracts under test:

- **Golden equivalence**: greedy decode through block tables is
  token-identical to the training forward recomputed a token at a
  time (``test_serving._recompute_greedy``) on the same prompts
  (whole-prompt AND chunked prefill, plain dp AND tp meshes).
- **Prefix cache correctness**: hit vs miss produce identical outputs;
  refcounts drop to zero on finish (only the cache's own references
  survive, and evicting them empties the pool).
- **Backpressure**: block-pool exhaustion defers admission cleanly —
  every request still completes, nothing crashes, and a request that
  could NEVER fit is refused at submit with a clear error.
- **Zero recompiles**: slot admission/retirement and table churn never
  retrace — one decode program ever, one prefill program per chunk
  bucket (pinned via trace counters).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.runtime.mesh import DATA_AXIS, make_mesh
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler,
    PagedServingEngine,
    Request,
)
from theanompi_tpu.serving.paging import BlockPool, PrefixCache

from test_serving import _recompute_greedy  # the oracle: tests/ is on the path

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


@pytest.fixture(scope="module")
def model():
    mesh = make_mesh(devices=jax.devices()[:1])
    return TransformerLM(config=dict(CFG), mesh=mesh)


@pytest.fixture(scope="module")
def paged(model):
    return PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8
    )


@pytest.fixture(scope="module")
def paged_chunked(model):
    return PagedServingEngine(
        model, n_slots=4, max_len=64, buckets=(8, 16, 64), block_size=8,
        prefill_chunk=16,
    )


# ---------------------------------------------------------------------------
# golden equivalence paged vs the training forward
# ---------------------------------------------------------------------------

def test_paged_greedy_matches_recompute(model, paged):
    """The headline contract: same prompts → identical greedy tokens
    through block-table gather/scatter as from the training forward
    recomputed a token at a time."""
    for prompt, n_new in [
        ([3, 1, 4, 1, 5], 12),          # pads into bucket 8
        ([7, 2, 9, 4, 4, 1, 0, 30, 2, 2, 11], 8),   # bucket 16
        (list(range(20)), 33),          # bucket 64, >=32 decode steps
    ]:
        want = _recompute_greedy(model, list(prompt), n_new)
        got = paged.greedy(list(prompt), n_new)
        assert got == want, f"paged diverged on prompt {prompt[:4]}..."


def test_chunked_prefill_matches_whole_prompt(paged, paged_chunked):
    """A prompt longer than prefill_chunk is fed in block-sized chunks
    interleaved with ticks — final tokens identical to one-shot."""
    prompt = list(np.random.RandomState(0).randint(0, 32, size=37))
    want = paged.greedy(list(prompt), 10)
    got = paged_chunked.greedy(list(prompt), 10)
    assert got == want


def _aligned_copy(a, align=64):
    """``a`` copied into memory aligned to ``align`` bytes.  The CPU
    client shares a host buffer with jax only when it is aligned like
    its own; malloc hands NumPy such memory now and then, which is why
    the bug flickered instead of failing every time."""
    raw = np.empty(a.nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def test_dispatch_inputs_cannot_alias_scheduler_state(paged_chunked,
                                                      monkeypatch):
    """PR 21's flicker, pinned: a jitted program must read what the
    scheduler's host arrays held AT DISPATCH, whatever the scheduler
    writes into them afterwards.  Dispatch returns before the program
    has read its inputs, and on the CPU client an aligned NumPy buffer
    handed to jax is shared, not copied (``serving.engine.host_input``).
    Here the hazard is made as certain as it can be: the scheduler's
    ``_tokens``/``_tables``/``_lengths`` sit in aligned memory, and
    right after every decode dispatch they are overwritten, the program
    is left to finish, and the arrays restored — with private input
    copies nothing changes; with shared buffers the program decodes
    token 0 at position 0 through the trash block."""
    eng = paged_chunked
    reqs = [
        ("a", [1, 2, 3], 7),
        ("b", list(np.random.RandomState(7).randint(0, 32, size=30)), 5),
        ("c", [4], 9),
        ("e", [5, 5, 5, 5, 5, 5], 4),
    ]

    def serve(interleaved):
        out, scheds = {}, []
        for rid, prompt, n in reqs:
            if not scheds or not interleaved:
                sched = ContinuousBatchingScheduler(eng)
                for name in ("_tokens", "_tables", "_lengths"):
                    setattr(sched, name, _aligned_copy(getattr(sched, name)))
                scheds.append(sched)
            scheds[-1].submit(
                Request(id=rid, prompt=list(prompt), max_new_tokens=n))
        for s in scheds:
            out.update(s.run())
        return out

    serial = serve(interleaved=False)
    real = PagedServingEngine.decode_step_paged

    def hostile(self, params, state, tokens, tables, lengths, active):
        out = real(self, params, state, tokens, tables, lengths, active)
        held = [np.array(a) for a in (tokens, tables, lengths)]
        for a in (tokens, tables, lengths):
            a[...] = 0
        jax.block_until_ready(out)
        for a, was in zip((tokens, tables, lengths), held):
            a[...] = was
        return out

    monkeypatch.setattr(PagedServingEngine, "decode_step_paged", hostile)
    for _ in range(3):
        assert serve(interleaved=True) == serial


def test_paged_on_tp_mesh_matches(model):
    """Tensor-parallel serving through block tables: heads shard over
    tp, decode tokens unchanged."""
    cfg_tp = dict(CFG, tp=2)
    mesh_tp = TransformerLM.build_mesh(config=cfg_tp)
    tp_model = TransformerLM(config=cfg_tp, mesh=mesh_tp)
    # the oracle runs the same weights unsharded
    ref = TransformerLM(config=dict(CFG),
                        mesh=make_mesh(devices=jax.devices()[:1]))
    ref.params = jax.device_get(tp_model.params)
    want = _recompute_greedy(ref, [5, 3, 2], 6)
    eng = PagedServingEngine(
        tp_model, n_slots=1, max_len=64, block_size=8
    )
    assert eng.greedy([5, 3, 2], 6) == want


def test_pool_rows_shard_over_dp():
    """On a multi-device dp mesh with a divisible block count, the
    row axis of every layer's pool array lands sharded over dp (whole
    blocks per device); indivisible counts fall back to replication,
    never crash."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh()  # all 8 fake devices on dp
    model = TransformerLM(config=CFG, mesh=mesh)
    eng = PagedServingEngine(
        model, n_slots=8, max_len=64, block_size=8, n_blocks=64
    )
    state = eng.init_state()
    assert eng.programs.pool_spec == P(DATA_AXIS, None)
    assert len(state["k"]) == len(state["v"]) == CFG["n_layers"]
    for leaf in state["k"] + state["v"]:
        # 4 heads of 8: 32 numbers a row, stored 128 lanes wide
        assert leaf.shape == (64 * 8, 128)
        assert leaf.sharding.spec == eng.programs.pool_spec
    eng2 = PagedServingEngine(
        model, n_slots=8, max_len=64, block_size=8, n_blocks=9
    )
    assert eng2.programs.pool_spec == P(None, None)
    assert eng2.init_state()["k"][0].sharding.spec == eng2.programs.pool_spec


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_pool_heads_shard_over_tp(kv_dtype):
    """On a dp x tp mesh a row's heads are split over tp (and the rows
    over dp): the width is the heads' own, unpadded, so that a shard
    holds whole heads; the int8 scale planes follow the same spec."""
    from jax.sharding import PartitionSpec as P
    from theanompi_tpu.runtime.mesh import TP_AXIS

    cfg_tp = dict(CFG, tp=2)
    tp_model = TransformerLM(config=cfg_tp,
                             mesh=TransformerLM.build_mesh(config=cfg_tp))
    eng = PagedServingEngine(tp_model, n_slots=2, max_len=64, block_size=8,
                             n_blocks=16, kv_dtype=kv_dtype)
    assert eng.programs.pool_spec == P(DATA_AXIS, TP_AXIS)
    assert eng.programs.row_width == CFG["d_model"]  # 32: no padding across shards
    state = eng.init_state()
    for side in ("k", "v"):
        for leaf in state[side]:
            assert leaf.shape == (16 * 8, 32)
            assert leaf.sharding.spec == eng.programs.pool_spec
            assert leaf.addressable_shards[0].data.shape == (16 * 8 // 4, 16)
    if kv_dtype == "int8":
        for leaf in state["ks"] + state["vs"]:
            assert leaf.shape == (16 * 8, CFG["n_heads"])
            assert leaf.sharding.spec == eng.programs.pool_spec
    want = PagedServingEngine(
        TransformerLM(config=dict(CFG), mesh=make_mesh(devices=jax.devices()[:1])),
        n_slots=2, max_len=64, block_size=8, n_blocks=16, kv_dtype=kv_dtype,
    )
    want.model.params = jax.device_get(tp_model.params)
    prompt = [5, 3, 2, 9, 9, 1, 30, 4, 4, 7, 11]
    assert eng.greedy(list(prompt), 8) == want.greedy(
        list(prompt), 8, params=want.model.params)


# ---------------------------------------------------------------------------
# the pool's layout: one lane-aligned array a layer, updated in place
# ---------------------------------------------------------------------------

# (d_model, heads): 4 heads of 8 are 32 numbers a row, stored 128 wide;
# 2 heads of 64 are 128, a multiple of 128 lanes as they stand
@pytest.fixture(scope="module", params=[(32, 4), (128, 2)],
                ids=["width32", "width128"])
def sized_model(request):
    d_model, n_heads = request.param
    return TransformerLM(
        config=dict(CFG, d_model=d_model, n_heads=n_heads),
        mesh=make_mesh(devices=jax.devices()[:1]))


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_state_is_one_lane_aligned_array_a_layer(sized_model, kv_dtype):
    """``k`` and ``v`` (and an int8 pool's ``ks``/``vs``) are lists of
    one two-dimensional array a layer, a row 128 lanes wide or a
    multiple; a program returns leaves of the same shapes; and
    ``kv_block_bytes`` counts what is stored."""
    eng = PagedServingEngine(sized_model, n_slots=2, max_len=64,
                             block_size=8, n_blocks=9, kv_dtype=kv_dtype)
    assert eng.programs.row_width == 128
    state = eng.init_state()
    assert sorted(state) == (
        ["k", "ks", "v", "vs"] if kv_dtype == "int8" else ["k", "v"])
    rows = 9 * 8
    for side, leaves in state.items():
        assert isinstance(leaves, list) and len(leaves) == eng.n_layers
        for leaf in leaves:
            assert leaf.shape == (
                rows, eng.programs.n_heads if side in ("ks", "vs") else 128)
    assert state["k"][0].dtype == (
        jnp.int8 if kv_dtype == "int8" else jnp.float32)
    stored = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    assert eng.kv_block_bytes() * eng.n_blocks == stored
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), state)
    tables = np.zeros((2, eng.blocks_per_seq), np.int32)
    tables[0, 0] = 3
    old = state
    state, logits = eng.decode_step_paged(
        sized_model.params, state, np.array([5, 0], np.int32), tables,
        np.array([0, 0], np.int32), np.array([True, False]))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), state) == shapes
    # every leaf was donated to the program, which wrote into it
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old))
    # lane 0's token went to row 0 of block 3, every head side by side,
    # the padding columns left at zero; the idle lane's to the trash block
    width = eng.programs.n_heads * eng.programs.head_dim
    k0 = np.asarray(state["k"][0])
    assert np.any(k0[3 * 8, :width] != 0) and not np.any(k0[3 * 8, width:])
    assert not np.any(k0[8:3 * 8]) and not np.any(k0[3 * 8 + 1:])


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_kernel_gather_chunks_and_prefix_hits_serve_the_same_tokens(
    sized_model, kv_dtype
):
    """Greedy tokens are identical between the XLA gather and the
    kernel, between whole-prompt and chunked prefill, and across
    prefix hits whose blocks two requests share."""
    def mk(**kw):
        return PagedServingEngine(
            sized_model, n_slots=4, max_len=64, buckets=(8, 16, 64),
            block_size=8, kv_dtype=kv_dtype, **kw)

    shared = list(np.random.RandomState(1).randint(0, 32, size=24))

    def drive(engine):
        sched = ContinuousBatchingScheduler(engine)
        sched.submit(Request(id="a", prompt=shared + [7], max_new_tokens=6))
        for _ in range(2):  # two chunks of 16 at most: a's prefill
            sched.step()    # completes and inserts its full blocks
        sched.submit(Request(id="b", prompt=shared + [9], max_new_tokens=6))
        sched.submit(Request(id="c", prompt=shared + [9, 3],
                             max_new_tokens=4))
        sched.submit(Request(id="d", prompt=shared[::-1] + shared[:13],
                             max_new_tokens=5))
        return sched.run(), sched.stats["prefix_hit_tokens"]

    want, hit = drive(mk(paged_attn="xla"))
    assert hit == 48  # b and c each reuse a's three full blocks
    for kw in (dict(paged_attn="pallas"),
               dict(paged_attn="xla", prefill_chunk=16),
               dict(paged_attn="pallas", prefill_chunk=16),
               dict(paged_attn="xla", prefix_cache=False)):
        got, got_hit = drive(mk(**kw))
        assert got == want, kw
        assert got_hit == (0 if kw.get("prefix_cache") is False else 48)


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------

def test_prefix_hit_outputs_identical_and_counted(model, paged):
    """A shared system prompt is prefilled once; later requests reuse
    its blocks — and their outputs are identical to cold prefills (an
    engine without a prefix cache, each request alone)."""
    cold = PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8,
        prefix_cache=False,
    )
    shared = list(np.random.RandomState(1).randint(0, 32, size=24))
    sched = ContinuousBatchingScheduler(paged)
    sched.submit(Request(id="a", prompt=shared + [7], max_new_tokens=6))
    sched.step()  # a's prefill completes and inserts its full blocks
    sched.submit(Request(id="b", prompt=shared + [9], max_new_tokens=6))
    sched.submit(Request(id="c", prompt=shared + [9, 3], max_new_tokens=4))
    out = sched.run()
    base = {}
    for rid, p, n in (("a", shared + [7], 6), ("b", shared + [9], 6),
                      ("c", shared + [9, 3], 4)):
        s = ContinuousBatchingScheduler(cold)
        s.submit(Request(id=rid, prompt=list(p), max_new_tokens=n))
        base.update(s.run())
        assert s.stats["prefix_hits"] == 0
    assert out == base
    # b and c each reused the 3 full shared blocks (24 tokens)
    assert sched.stats["prefix_hits"] == 2
    assert sched.stats["prefix_hit_tokens"] == 48
    # and those tokens were never pushed through prefill again
    total = sum(len(p) for p in (shared + [7], shared + [9],
                                 shared + [9, 3]))
    assert sched.stats["prefill_tokens"] == total - 48


def test_refcounts_drop_to_zero_on_finish(model):
    """After every request finishes, the only live references are the
    prefix cache's own; with the cache disabled the pool is empty, and
    evicting the cache empties it too."""
    eng = PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 64), block_size=8,
        prefix_cache=False,
    )
    sched = ContinuousBatchingScheduler(eng)
    for i in range(3):
        sched.submit(Request(id=f"r{i}", prompt=[i + 1, 2, 3],
                             max_new_tokens=5))
    sched.run()
    assert sched.pool.n_used == 0
    assert sched.pool.n_free == sched.pool.n_blocks - 1

    eng2 = PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 64), block_size=8
    )
    sched2 = ContinuousBatchingScheduler(eng2)
    sched2.submit(Request(id="a", prompt=list(range(20)),
                          max_new_tokens=4))
    sched2.run()
    # 20 tokens -> 2 full blocks cached, each held ONLY by the cache
    assert sched2.pool.n_used == len(sched2.prefix) == 2
    for digest in list(sched2.prefix._entries):
        assert sched2.pool.ref(sched2.prefix._entries[digest]) == 1
    sched2.prefix.evict_unused()
    assert sched2.pool.n_used == 0


def test_prefix_cache_never_matches_entire_prompt():
    """The final prompt token is always prefilled (its logits feed the
    first decode), even when the whole prompt is cached."""
    pool = BlockPool(n_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]  # exactly 2 full blocks
    blocks = pool.alloc(2)
    cache.insert(prompt, blocks)
    hits, n = cache.match(list(prompt))
    # cap at (len-1)//bs = 1 block: the last block is recomputed
    assert len(hits) == 1 and n == 4
    for b in hits:
        pool.release(b)


def test_block_pool_accounting_and_errors():
    pool = BlockPool(n_blocks=4, block_size=8)  # 3 allocatable
    assert pool.n_free == 3
    a = pool.alloc(2)
    assert pool.n_used == 2 and pool.ref(a[0]) == 1
    assert pool.alloc(2) is None      # only 1 left: all-or-nothing
    assert pool.n_used == 2           # failed alloc grants nothing
    pool.retain(a[0])
    pool.release(a[0])
    assert pool.n_used == 2           # still referenced once
    pool.release(a[0])
    assert pool.n_used == 1
    with pytest.raises(ValueError, match="unallocated"):
        pool.release(a[0])
    with pytest.raises(ValueError, match="unallocated"):
        pool.retain(99)
    with pytest.raises(ValueError, match="trash"):
        BlockPool(n_blocks=1, block_size=8)


# ---------------------------------------------------------------------------
# exhaustion backpressure
# ---------------------------------------------------------------------------

def test_pool_exhaustion_is_clean_backpressure(model):
    """More demand than blocks: admissions defer (counted), every
    request still completes, outputs unperturbed, pool drains."""
    eng = PagedServingEngine(
        model, n_slots=4, max_len=64, buckets=(8, 64), block_size=8,
        n_blocks=9, prefix_cache=False,  # 8 usable blocks = 64 rows
    )
    sched = ContinuousBatchingScheduler(eng)
    reqs = [(f"r{i}", [i + 1, 2, 3], 20) for i in range(4)]  # 3 blocks ea
    for rid, prompt, n in reqs:
        sched.submit(Request(id=rid, prompt=list(prompt),
                             max_new_tokens=n))
    out = sched.run()
    assert len(out) == 4
    assert sched.stats["backpressure_events"] > 0
    assert sched.pool.n_used == 0
    # outputs match an uncontended run
    roomy = PagedServingEngine(
        model, n_slots=4, max_len=64, buckets=(8, 64), block_size=8,
        prefix_cache=False,
    )
    s2 = ContinuousBatchingScheduler(roomy)
    for rid, prompt, n in reqs:
        s2.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=n))
    assert s2.run() == out


def test_impossible_request_refused_at_submit(model):
    eng = PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 64), block_size=8,
        n_blocks=5,  # 4 usable blocks = 32 rows < max_len
    )
    sched = ContinuousBatchingScheduler(eng)
    with pytest.raises(ValueError, match="never be admitted"):
        sched.submit(Request(id="huge", prompt=[1] * 30,
                             max_new_tokens=10))  # 5 blocks > 4


def test_exhaustion_evicts_idle_prefix_blocks(model):
    """Cached-but-idle prefix blocks yield to live sequences before
    admission backpressures."""
    eng = PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 64), block_size=8,
        n_blocks=9,  # 8 usable
    )
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(Request(id="a", prompt=list(range(20)),
                         max_new_tokens=4))  # 3 blocks; 2 cached after
    sched.run()
    assert sched.pool.n_used == 2  # the cache's references
    # a 7-block request only fits if the cache gives its 2 blocks back
    sched.submit(Request(id="b", prompt=list(range(7, 57)),
                         max_new_tokens=5))
    out = sched.run()
    assert len(out["b"]) == 5
    assert sched.stats["backpressure_events"] == 0


# ---------------------------------------------------------------------------
# zero recompiles
# ---------------------------------------------------------------------------

def test_zero_recompiles_across_admission_and_retirement(model):
    """Block tables and lengths are DATA: any churn of admissions,
    retirements, prefix hits and chunk boundaries retraces nothing —
    one decode program, one prefill program per chunk bucket."""
    eng = PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8,
        prefill_chunk=16,
    )
    rng = np.random.RandomState(3)
    sched = ContinuousBatchingScheduler(eng)
    for i in range(3):
        sched.submit(Request(
            id=f"w{i}",
            prompt=list(rng.randint(0, 32, size=rng.randint(2, 40))),
            max_new_tokens=3,
        ))
    sched.run()
    prefill_before = eng._n_prefill_traces
    decode_before = eng._n_decode_traces
    assert decode_before == 1
    assert prefill_before <= len(eng.chunk_buckets)
    # churn: a second wave through a FRESH scheduler (new tables, new
    # pool, same engine programs)
    sched2 = ContinuousBatchingScheduler(eng)
    for i in range(4):
        sched2.submit(Request(
            id=f"x{i}",
            prompt=list(rng.randint(0, 32, size=rng.randint(2, 40))),
            max_new_tokens=4,
        ))
    sched2.run()
    assert eng._n_decode_traces == decode_before
    assert eng._n_prefill_traces <= len(eng.chunk_buckets)


def test_engine_geometry_validation(model):
    with pytest.raises(ValueError, match="block_size"):
        PagedServingEngine(model, n_slots=1, max_len=64, block_size=0)
    with pytest.raises(ValueError, match="trash block"):
        PagedServingEngine(model, n_slots=1, max_len=64, block_size=8,
                           n_blocks=1)
    with pytest.raises(ValueError, match="prefill_chunk"):
        PagedServingEngine(model, n_slots=1, max_len=64, block_size=8,
                           prefill_chunk=0)
    eng = PagedServingEngine(model, n_slots=2, max_len=64,
                             buckets=(8, 16, 64), block_size=8,
                             prefill_chunk=20)
    # ladder = buckets at or under the cap, plus the cap itself
    assert eng.chunk_buckets == (8, 16, 20)
    with pytest.raises(ValueError, match="exceeds the device pool"):
        eng.make_pool(n_blocks=eng.n_blocks + 1)
