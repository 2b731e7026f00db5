"""The fault-tolerant serving fleet (ISSUE 12).

Acceptance contracts under test:

- **Token-identical failover**: kill a replica with streams in flight →
  exactly one eviction, its streams re-admit on a surviving replica,
  and every output equals the uninterrupted single-engine run — greedy
  AND sampled (``Request.token_index0`` keeps the per-index sampling
  keys aligned across the replay).
- **Prefix-affinity routing**: replicas gossip radix summaries; a
  shared-prefix workload routes to the replica already holding the
  blocks (affine placements counted, hit tokens > 0).
- **Radix > chain**: under pool pressure the radix cache's LRU
  leaf-first eviction keeps the shared trunk resident where the chain
  cache's all-or-nothing sweep drops it — higher hit tokens, strictly
  fewer prefilled tokens, identical outputs.
- **Health shedding**: a 503-tripped replica receives ZERO new
  admissions until green; in-flight streams keep running.
- **Drain-on-leave**: in-flight slots run to completion, new
  admissions are refused with counted backpressure, every block is
  released exactly once (refcount audit), then a clean ``leave()`` —
  no eviction alert.
- **Transport parity**: the same router drives a real TCP replica
  through ``transport.request()``.
"""

import time

import numpy as np
import pytest

import jax

from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.runtime.mesh import make_mesh
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler,
    FleetRouter,
    PagedServingEngine,
    Request,
    SchedulerDraining,
    ServingMetrics,
)
from theanompi_tpu.serving.fleet import FleetError, ServeReplica
from theanompi_tpu.serving.paging import PrefixCache
from theanompi_tpu.serving.radix import (
    RadixPrefixCache,
    chain_digests,
    score_prompt,
)

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
GEOM = dict(n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8)


@pytest.fixture(scope="module")
def model():
    mesh = make_mesh(devices=jax.devices()[:1])
    return TransformerLM(config=dict(CFG), mesh=mesh)


def _engine(model, **over):
    kw = dict(GEOM)
    kw.update(over)
    return PagedServingEngine(model, **kw)


def _replica(model, name, warm=True, **kw):
    rep = ServeReplica(name, _engine(model), **kw).start()
    if warm:
        # compile outside any eviction window: a cold tick takes
        # seconds on this rig and must not read as replica death —
        # greedy AND sampled paths (the batched sampler compiles
        # lazily on its first temperature>0 pick)
        rep.handle(("submit", {"id": "_warm", "prompt": [1, 2, 3],
                               "max_new_tokens": 2}))
        rep.handle(("submit", {"id": "_warms", "prompt": [1, 2, 3],
                               "max_new_tokens": 2, "temperature": 0.5,
                               "seed": 1}))
        deadline = time.monotonic() + 120
        while not rep.scheduler.idle:
            assert time.monotonic() < deadline, "warmup never drained"
            time.sleep(0.01)
    return rep


def _prompts(n, lo=4, hi=12, seed=0):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, CFG["vocab_size"], size=rng.randint(lo, hi)).tolist()
        for _ in range(n)
    ]


def _submit_all(router, prompts, max_new=6, **req_kw):
    for j, p in enumerate(prompts):
        router.submit(Request(id=f"q{j}", prompt=list(p),
                              max_new_tokens=max_new, **req_kw))


# ---------------------------------------------------------------------------
# radix cache unit behavior
# ---------------------------------------------------------------------------

class _FakePool:
    """Refcount-only pool for cache unit tests."""

    def __init__(self, block_size=8):
        self.block_size = block_size
        self.refs = {}
        self._next = 1

    def give(self, n):
        out = []
        for _ in range(n):
            self.refs[self._next] = 1
            out.append(self._next)
            self._next += 1
        return out

    def retain(self, b):
        self.refs[b] += 1

    def release(self, b):
        self.refs[b] -= 1
        if self.refs[b] == 0:
            del self.refs[b]

    def ref(self, b):
        return self.refs.get(b, 0)


def test_radix_match_semantics_mirror_chain():
    """Same cap, same full-block-only sharing, same counters as the
    chain cache — only eviction and summaries differ."""
    pool = _FakePool()
    cache = RadixPrefixCache(pool)
    prompt = list(range(20))  # 2 full blocks + tail at bs=8
    blocks = pool.give(2)
    assert cache.insert(prompt, blocks) == 2
    hit, tokens = cache.match(prompt)
    assert hit == blocks and tokens == 16
    # a 16-token prompt caps at ONE block: its final token must always
    # be prefilled (its logits are the first decode input), exactly the
    # chain cache's (len-1)//bs rule
    hit2, tokens2 = cache.match(list(range(16)))
    assert hit2 == blocks[:1] and tokens2 == 8
    # a prompt diverging in block 0 shares nothing
    hit3, tokens3 = cache.match([9] * 20)
    assert hit3 == [] and tokens3 == 0
    assert cache.hits == 2 and cache.misses == 1
    for b in hit + hit2:
        pool.release(b)  # caller refs back


def test_radix_partial_eviction_keeps_hot_trunk():
    """evict_unused(need) frees the COLDEST leaves first and stops at
    ``need``; the chain cache's sweep would have dropped everything."""
    pool = _FakePool()
    cache = RadixPrefixCache(pool)
    trunk = list(range(16))  # 2 shared blocks
    tail_a = trunk + [1] * 8
    tail_b = trunk + [2] * 8
    ba = pool.give(3)
    cache.insert(tail_a, ba)
    for b in ba:
        pool.release(b)  # the slot finished; cache refs remain
    bb = pool.give(1)
    hit, _ = cache.match(tail_b)
    assert hit == ba[:2]  # partial overlap shares the trunk
    cache.insert(tail_b, ba[:2] + bb)
    for b in hit + bb:
        pool.release(b)
    assert len(cache) == 4  # trunk(2) + two tails
    # everything idle (cache holds the only refs); need=1 must evict
    # exactly ONE leaf — the LRU tail_a leaf — and keep the trunk
    assert cache.evict_unused(1) == 1
    assert len(cache) == 3
    # probe one token past tail_b so the match cap admits all 3 blocks:
    # trunk AND tail_b's leaf survived; tail_a's (the LRU leaf) went
    hit_after, tok_after = cache.match(tail_b + [3])
    assert tok_after == 24
    for b in hit_after:
        pool.release(b)
    # need=None keeps chain semantics: sweep everything droppable
    assert cache.evict_unused() == 3
    assert len(cache) == 0 and pool.refs == {}


def test_radix_interior_nodes_never_evict_under_live_children():
    pool = _FakePool()
    cache = RadixPrefixCache(pool)
    prompt = list(range(24))  # 3-block chain
    blocks = pool.give(3)
    cache.insert(prompt, blocks)
    for b in blocks:
        pool.release(b)  # slot refs gone; cache refs remain
    # a live request holds the deepest block: nothing is evictable
    # above it until the leaf itself is free
    pool.retain(blocks[2])
    assert cache.evict_unused() == 0  # leaf busy, trunk pinned by child
    pool.release(blocks[2])
    assert cache.evict_unused() == 3


def test_summary_and_score_prompt_round_trip():
    pool = _FakePool()
    cache = RadixPrefixCache(pool)
    prompt = list(range(16))
    cache.insert(prompt, pool.give(2))
    summary = cache.summary()
    assert len(summary) == 2
    assert score_prompt(prompt, 8, summary) == 2
    assert score_prompt(list(range(8)) + [5] * 8, 8, summary) == 1
    assert score_prompt([7] * 16, 8, summary) == 0
    assert score_prompt(prompt, 8, []) == 0
    # digests are the chain cache's: cross-implementation scoring works
    assert summary[0] in {d.hex() for d in chain_digests(prompt, 8)}


def test_score_prompt_weighted_depth_dominates_recency():
    """ISSUE 13 satellite (d): depth × recency scoring — a deeper
    match always outranks a fresher shallower one (recency scales in
    (0.5, 1.0], so it can never cross a whole block of reusable
    prefill), and at EQUAL depth the fresher summary wins."""
    from theanompi_tpu.serving.radix import score_prompt_weighted

    prompt = list(range(16))
    d0, d1 = [d.hex() for d in chain_digests(prompt, 8)]
    cold_tail = ["%040x" % i for i in range(6)]
    # depth 2 held in the COLDEST positions still beats depth 1 at MRU
    deep_cold = cold_tail + [d0, d1]
    shallow_hot = [d0] + cold_tail
    w_deep, depth_deep = score_prompt_weighted(prompt, 8, deep_cold)
    w_shallow, depth_shallow = score_prompt_weighted(
        prompt, 8, shallow_hot
    )
    assert (depth_deep, depth_shallow) == (2, 1)
    assert w_deep > w_shallow
    # equal depth: the replica whose chain is MRU-warm outranks the
    # one holding it in entries about to be LRU-evicted
    hot = [d0, d1] + cold_tail
    cold = cold_tail + [d0, d1]
    assert score_prompt_weighted(prompt, 8, hot)[0] \
        > score_prompt_weighted(prompt, 8, cold)[0]
    # no match stays (0.0, 0); empty summary too
    assert score_prompt_weighted([7] * 16, 8, hot) == (0.0, 0)
    assert score_prompt_weighted(prompt, 8, []) == (0.0, 0)


class _StubReplica:
    """Protocol-level stand-in: enough of the replica surface for
    router placement tests (summary/headroom are the subject, no
    engine required)."""

    def __init__(self, summary=(), headroom=0, block_size=8,
                 healthy=True, backpressure=0, drain_refusals=0):
        self.summary = list(summary)
        self.headroom = headroom
        self.block_size = block_size
        self.healthy = healthy
        self.backpressure = backpressure
        self.drain_refusals = drain_refusals
        self.submitted = []

    def handle(self, msg):
        kind = msg[0]
        if kind == "hello":
            return {"ok": True, "v": 1, "block_size": self.block_size,
                    "n_slots": 2, "max_len": 64}
        if kind == "submit":
            self.submitted.append(msg[1])
            return {"ok": True, "ticks": 1}
        if kind == "poll":
            return {"ok": True, "streams": {}, "ticks": 1,
                    "healthy": self.healthy, "draining": False,
                    "idle": True, "summary": list(self.summary),
                    "headroom": self.headroom,
                    "backpressure": self.backpressure,
                    "drain_refusals": self.drain_refusals}
        return {"ok": False}


def test_router_places_by_depth_times_recency():
    """Equal-depth candidates: the router picks the replica whose
    matching chain is warm (MRU-first summary position), deterministic
    — not a round-robin coin flip."""
    prompt = list(range(16))
    d0, d1 = [d.hex() for d in chain_digests(prompt, 8)]
    cold_tail = ["%040x" % i for i in range(6)]
    warm = _StubReplica(summary=[d0, d1] + cold_tail)
    cold = _StubReplica(summary=cold_tail + [d0, d1])
    deep = _StubReplica(summary=cold_tail + [d0, d1])
    shallow = _StubReplica(summary=[d0])
    router = FleetRouter(evict_after_s=60.0)
    router.add_replica("warm", warm)
    router.add_replica("cold", cold)
    router.pump()  # absorb summaries/headroom from poll replies
    for _ in range(4):  # deterministic, not alternating
        assert router.route(prompt) == ("warm", 2)
    # and a deeper match beats a fresher shallower one
    router2 = FleetRouter(evict_after_s=60.0)
    router2.add_replica("deep", deep)
    router2.add_replica("shallow", shallow)
    router2.pump()
    for _ in range(4):
        assert router2.route(prompt) == ("deep", 2)


def test_router_breaks_ties_on_advertised_headroom():
    """Reuse being equal (identical summaries; and again on the cold
    path with no summaries), placement goes where the advertised pool
    headroom is — replicas trade reuse against capacity."""
    prompt = list(range(16))
    digests = [d.hex() for d in chain_digests(prompt, 8)]
    roomy = _StubReplica(summary=digests, headroom=40)
    full = _StubReplica(summary=digests, headroom=2)
    router = FleetRouter(evict_after_s=60.0)
    router.add_replica("roomy", roomy)
    router.add_replica("full", full)
    router.pump()
    for _ in range(4):
        assert router.route(prompt)[0] == "roomy"
    # cold prompts: least-loaded ties ALSO break on headroom
    cold_router = FleetRouter(evict_after_s=60.0)
    cold_router.add_replica("roomy", _StubReplica(headroom=40))
    cold_router.add_replica("full", _StubReplica(headroom=2))
    cold_router.pump()
    for _ in range(4):
        assert cold_router.route([9] * 12)[0] == "roomy"


def test_replica_poll_reply_advertises_pool_headroom(model):
    """A real replica's poll reply carries its BlockPool's free-block
    count, and allocation moves it."""
    rep = _replica(model, "r0", warm=False)
    try:
        before = rep.handle(("poll", {}))["headroom"]
        assert before == rep.scheduler.pool.n_free > 0
        rep.handle(("submit", {"id": "s0", "prompt": [1, 2, 3, 4],
                               "max_new_tokens": 4}))
        deadline = time.monotonic() + 60.0
        while not rep.scheduler.idle:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        after = rep.handle(("poll", {"s0": 0}))["headroom"]
        assert isinstance(after, int)
    finally:
        rep.stop()


def test_router_scaling_signals_golden():
    """One snapshot of the demand-vs-capacity picture, whole-dict
    golden: backlog, roster composition, refusal counters, and
    per-replica headroom — the feed the tuning driver sizes the
    fleet by."""
    router = FleetRouter(evict_after_s=60.0)
    router.add_replica("a", _StubReplica(headroom=40, backpressure=2))
    router.add_replica("b", _StubReplica(headroom=8, drain_refusals=1))
    router.add_replica("c", _StubReplica(headroom=12, healthy=False))
    router.pump()  # absorb poll replies; c's red health sheds it
    router.submit(Request(id="q0", prompt=[1, 2, 3],
                          max_new_tokens=4))
    assert router.scaling_signals() == {
        "queue_depth": 1,
        "replicas_total": 3,
        "replicas_live": 3,
        "replicas_admitting": 2,
        "replicas_shedding": 1,
        "backpressure_refusals": 2,
        "drain_refusals": 1,
        "drain_reroutes": 0,
        "shed_events": 1,
        "requests_lost": 0,
        "headroom": {"a": 40, "b": 8, "c": 12},
        "headroom_total": 60,
        "headroom_min": 8,
    }


def test_router_scaling_signals_exports_gauges():
    """The snapshot is also the gauge refresh: queue depth, admitting
    count, backpressure sum, and labeled per-replica headroom land in
    the metrics registry on every call."""
    from theanompi_tpu.serving import metrics as smetrics
    router = FleetRouter(evict_after_s=60.0)
    router.add_replica("a", _StubReplica(headroom=40, backpressure=2))
    router.add_replica("b", _StubReplica(headroom=8, backpressure=3))
    router.pump()
    sig = router.scaling_signals()
    assert smetrics.FLEET_QUEUE_DEPTH.value() == sig["queue_depth"] == 0
    assert smetrics.FLEET_ADMITTING.value() == 2
    assert smetrics.FLEET_BACKPRESSURE.value() == 5
    assert smetrics.FLEET_HEADROOM.value(replica="a") == 40
    assert smetrics.FLEET_HEADROOM.value(replica="b") == 8


def test_router_counts_lost_requests():
    """A stream that cannot re-admit anywhere after an eviction is a
    counted loss (stats + scaling snapshot), not a silent drop."""
    clock = {"t": 0.0}
    router = FleetRouter(evict_after_s=0.5,
                         clock=lambda: clock["t"])
    rep = _StubReplica(headroom=40)
    router.add_replica("a", rep)
    router.pump()
    router.submit(Request(id="q0", prompt=[1, 2, 3],
                          max_new_tokens=4))
    # the only replica goes silent past the eviction window; with no
    # survivor to re-admit on, the stream is lost — and counted
    rep.handle = lambda msg: (_ for _ in ()).throw(
        ConnectionError("down"))
    clock["t"] = 1.0
    router.pump()
    assert router.stats["evictions"] == 1
    assert router.stats["requests_lost"] == 1
    assert router.scaling_signals()["requests_lost"] == 1


def test_radix_scheduler_outputs_match_chain(model):
    """prefix_impl changes eviction policy, never tokens."""
    engine = _engine(model)
    prompts = _prompts(4, seed=3)
    outs = {}
    for impl in ("chain", "radix"):
        sched = ContinuousBatchingScheduler(engine, prefix_impl=impl)
        for j, p in enumerate(prompts):
            sched.submit(Request(id=f"p{j}", prompt=list(p),
                                 max_new_tokens=4))
        outs[impl] = sched.run()
    assert outs["chain"] == outs["radix"]


def test_radix_beats_chain_under_pool_pressure(model):
    """The fleet's cache claim, engine-level: a shared trunk + cold
    tails + pool pressure.  The radix cache evicts only the shortfall
    (trunk survives), the chain cache sweeps everything idle — so the
    radix run reuses more prefix tokens and prefills strictly fewer."""
    engine = _engine(model, n_slots=2)
    rng = np.random.RandomState(7)
    trunk = rng.randint(0, CFG["vocab_size"], size=16).tolist()
    # phase 1 caches the 2-block trunk; the fillers (4 blocks each, 9
    # usable blocks total) exhaust the pool mid-admission, forcing the
    # eviction valve; phase 3 re-asks for the trunk.  The radix cache
    # evicts exactly the shortfall (one cold leaf — the trunk's deeper
    # block), keeping the trunk head resident; the chain cache's sweep
    # drops every idle entry, trunk included.
    phase1 = [trunk + rng.randint(0, CFG["vocab_size"], size=4).tolist()
              for _ in range(2)]
    fillers = [rng.randint(0, CFG["vocab_size"], size=30).tolist()
               for _ in range(2)]
    phase3 = [trunk + rng.randint(0, CFG["vocab_size"], size=4).tolist()
              for _ in range(2)]
    results = {}
    for impl in ("chain", "radix"):
        sched = ContinuousBatchingScheduler(
            engine, pool=engine.make_pool(10), prefix_impl=impl
        )
        rid = 0
        for batch in (phase1, fillers, phase3):
            for p in batch:
                sched.submit(Request(id=f"r{rid}", prompt=list(p),
                                     max_new_tokens=2))
                rid += 1
            sched.run()
        results[impl] = (
            sched.stats["prefix_hit_tokens"],
            sched.stats["prefill_tokens"],
            dict(sched.finished),
        )
    hit_chain, fed_chain, out_chain = results["chain"]
    hit_radix, fed_radix, out_radix = results["radix"]
    assert out_chain == out_radix  # policy, never tokens
    assert hit_radix > hit_chain
    assert fed_radix < fed_chain


# ---------------------------------------------------------------------------
# fleet: routing, failover, shedding, drain
# ---------------------------------------------------------------------------

def test_fleet_matches_single_engine_and_affinity_routes(model):
    r0 = _replica(model, "r0")
    r1 = _replica(model, "r1")
    try:
        router = FleetRouter(evict_after_s=5.0,
                             metrics=ServingMetrics())
        router.add_replica("r0", r0)
        router.add_replica("r1", r1)
        rng = np.random.RandomState(11)
        shared = rng.randint(0, CFG["vocab_size"], size=16).tolist()
        prompts = [
            shared + rng.randint(0, CFG["vocab_size"], size=4).tolist()
            for _ in range(4)
        ]
        # first request lands somewhere and caches the trunk
        router.submit(Request(id="q0", prompt=list(prompts[0]),
                              max_new_tokens=4))
        router.run(timeout_s=120)
        first_home = router._streams["q0"].replica
        for j, p in enumerate(prompts[1:], start=1):
            router.submit(Request(id=f"q{j}", prompt=list(p),
                                  max_new_tokens=4))
        out = router.run(timeout_s=120)
        # affinity: every later shared-prefix request followed the blocks
        stats = router.fleet_stats()
        assert stats["routed_affine"] == 3, stats
        assert stats["affine_hit_tokens"] >= 3 * 16
        assert stats["affinity_hit_rate"] > 0.5
        for j in range(1, 4):
            assert router._streams[f"q{j}"].replica == first_home
        # outputs match the uninterrupted single-engine reference
        ref_engine = _engine(model)
        for j, p in enumerate(prompts):
            assert out[f"q{j}"] == ref_engine.greedy(list(p), 4), j
        assert stats["evictions"] == 0
        summary = router.metrics.summary()
        assert summary["n_requests"] == 4
    finally:
        r0.stop()
        r1.stop()


def test_fleet_kill_replica_readmits_token_identical(model):
    """THE robustness headline: kill mid-stream → exactly one eviction
    → the orphaned streams finish elsewhere, token-identical — greedy
    and sampled both (sampled pins token_index0 key alignment)."""
    r0 = _replica(model, "r0")
    r1 = _replica(model, "r1")
    alerts = []
    try:
        # the window must sit WELL above a contended tick: polls
        # serialize with replica ticks, and under a full-suite CPU a
        # tick can stretch past 0.5s — a too-tight window evicts a
        # LIVE replica and the drill's exactly-one-eviction claim dies
        # to rig noise (the committed drill uses 3.0s for the same
        # reason)
        router = FleetRouter(
            evict_after_s=2.5,
            on_alert=lambda rule, msg: alerts.append(rule),
        )
        router.add_replica("r0", r0)
        router.add_replica("r1", r1)
        prompts = _prompts(4, seed=5)
        reqs = [
            Request(id=f"g{j}", prompt=list(p), max_new_tokens=16)
            for j, p in enumerate(prompts[:2])
        ] + [
            Request(id=f"s{j}", prompt=list(p), max_new_tokens=16,
                    temperature=0.8, top_k=8, seed=123 + j)
            for j, p in enumerate(prompts[2:])
        ]
        for r in reqs:
            router.submit(r)
        # let a few tokens land, then kill whichever replica holds q g0
        deadline = time.monotonic() + 60
        while not router._streams["g0"].tokens:
            assert time.monotonic() < deadline
            router.pump()
            time.sleep(0.005)
        victim = router._streams["g0"].replica
        (r0 if victim == "r0" else r1).kill()
        out = router.run(timeout_s=180)
        stats = router.fleet_stats()
        assert stats["evictions"] == 1
        assert stats["readmissions"] >= 1
        assert alerts.count("replica_evicted") == 1
        assert alerts.count("request_readmitted") == stats["readmissions"]
        # reference: uninterrupted single engine, same requests
        ref = _engine(model)
        sched = ContinuousBatchingScheduler(ref)
        for r in reqs:
            sched.submit(Request(id=r.id, prompt=list(r.prompt),
                                 max_new_tokens=r.max_new_tokens,
                                 temperature=r.temperature, top_k=r.top_k,
                                 seed=r.seed))
        expect = sched.run()
        assert out == expect
    finally:
        r0.stop()
        r1.stop()


def test_fleet_shed_on_health_red_until_green(model):
    """A 503-tripped replica gets ZERO new admissions until its health
    probe returns green — pinned, not best-effort."""
    r0 = _replica(model, "r0")
    r1 = _replica(model, "r1")
    try:
        healthy = {"r0": True}
        r0.set_health_fn(lambda: healthy["r0"])
        router = FleetRouter(evict_after_s=10.0)
        router.add_replica("r0", r0)
        router.add_replica("r1", r1)
        healthy["r0"] = False
        router.pump()  # absorb the red health bit
        for j in range(4):
            router.submit(Request(id=f"h{j}", prompt=[1 + j, 2, 3],
                                  max_new_tokens=2))
        router.run(timeout_s=120)
        stats = router.fleet_stats()
        assert stats["shed_events"] == 1
        assert stats["replicas"]["r0"]["tokens_out"] == 0
        assert all(
            router._streams[f"h{j}"].replica == "r1" for j in range(4)
        )
        # green again: r0 returns to rotation and takes traffic
        healthy["r0"] = True
        router.pump()
        for j in range(4, 8):
            router.submit(Request(id=f"h{j}", prompt=[1 + j, 2, 3],
                                  max_new_tokens=2))
        router.run(timeout_s=120)
        homes = {router._streams[f"h{j}"].replica for j in range(4, 8)}
        assert "r0" in homes
        assert router.fleet_stats()["replicas"]["r0"]["shed_seconds"] > 0
    finally:
        r0.stop()
        r1.stop()


def test_scheduler_drain_refuses_completes_and_releases(model):
    """The drain-on-leave satellite at scheduler level: in-flight slots
    run to completion, new submissions raise counted backpressure, and
    every block releases exactly once (refcount audit)."""
    engine = _engine(model)
    sched = ContinuousBatchingScheduler(engine)
    prompts = _prompts(3, seed=9)
    for j, p in enumerate(prompts):
        sched.submit(Request(id=f"d{j}", prompt=list(p), max_new_tokens=4))
    sched.step()  # some in flight, some maybe queued
    sched.begin_drain()
    with pytest.raises(SchedulerDraining):
        sched.submit(Request(id="late", prompt=[1, 2], max_new_tokens=2))
    assert sched.stats["drain_refusals"] == 1
    ticks = 0
    while not sched.idle:
        sched.step()
        ticks += 1
        assert ticks < 10_000
    # every accepted request finished — drain dropped nothing
    assert sorted(sched.finished) == [f"d{j}" for j in range(3)]
    # refcount audit: the only remaining references are the prefix
    # cache's own (one per entry); evicting them empties the pool, and
    # a double release anywhere would have raised in BlockPool.release
    assert sched.pool.n_used == len(sched.prefix)
    sched.prefix.evict_unused()
    assert sched.pool.n_used == 0
    assert sched.pool.n_free == sched.pool.n_blocks - 1


def test_fleet_drain_on_leave_clean(model):
    """Router-level drain: the draining replica takes no new work, its
    in-flight streams complete (never dropped), then it leaves the
    roster cleanly — zero evictions, zero eviction alerts."""
    r0 = _replica(model, "r0")
    r1 = _replica(model, "r1")
    alerts = []
    try:
        router = FleetRouter(
            evict_after_s=10.0,
            on_alert=lambda rule, msg: alerts.append(rule),
        )
        router.add_replica("r0", r0)
        router.add_replica("r1", r1)
        prompts = _prompts(4, seed=13)
        _submit_all(router, prompts, max_new=8)
        router.pump()
        drained = (
            "r0" if any(
                s.replica == "r0" and not s.done
                for s in router._streams.values()
            ) else "r1"
        )
        router.drain_replica(drained, timeout_s=120)
        assert router.roster.is_member(drained) is False
        # new admissions all land on the survivor
        for j in range(4, 7):
            router.submit(Request(id=f"q{j}", prompt=[j, 1, 2],
                                  max_new_tokens=2))
        out = router.run(timeout_s=120)
        assert len(out) == 7 and all(len(v) > 0 for v in out.values())
        survivor = ({"r0", "r1"} - {drained}).pop()
        for j in range(4, 7):
            assert router._streams[f"q{j}"].replica == survivor
        stats = router.fleet_stats()
        assert stats["evictions"] == 0
        assert "replica_evicted" not in alerts
        assert router.roster.n_evictions == 0
    finally:
        r0.stop()
        r1.stop()


def test_fleet_over_tcp_transport(model):
    """Same router, real sockets: a ServeReplica behind a port is
    driven through transport.request() — hello, routed submits, polls,
    completion."""
    from theanompi_tpu.runtime.multiprocess import find_free_port

    port = find_free_port()
    rep = ServeReplica("tcp0", _engine(model), port=port)
    rep.start()
    rep.handle(("submit", {"id": "_warm", "prompt": [1, 2, 3],
                           "max_new_tokens": 2}))
    deadline = time.monotonic() + 120
    while not rep.scheduler.idle:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    try:
        router = FleetRouter(evict_after_s=10.0, rpc_deadline_s=30.0)
        router.add_replica("tcp0", ("127.0.0.1", port))
        prompts = _prompts(2, seed=17)
        _submit_all(router, prompts, max_new=4)
        out = router.run(timeout_s=120)
        ref = _engine(model)
        for j, p in enumerate(prompts):
            assert out[f"q{j}"] == ref.greedy(list(p), 4)
    finally:
        rep.stop()


def test_fleet_no_admitting_replica_is_loud(model):
    r0 = _replica(model, "r0", warm=False)
    try:
        router = FleetRouter(evict_after_s=10.0)
        router.add_replica("r0", r0)
        router._call(router._replicas["r0"], ("drain",))
        router._replicas["r0"].draining = True
        with pytest.raises(FleetError):
            router.submit(Request(id="x", prompt=[1, 2],
                                  max_new_tokens=2))
    finally:
        r0.stop()


# ---------------------------------------------------------------------------
# live plane: replica_evicted + request_readmitted alerts (counter-delta
# rules, mirroring the training tier's worker_evicted golden)
# ---------------------------------------------------------------------------


def _live_frame(rank, seq, counters):
    from theanompi_tpu.observability import live

    return {
        "kind": live.FRAME_KIND, "v": live.FRAME_VERSION, "rank": rank,
        "seq": seq, "t_wall": 0.0, "sample_rate": 1, "dropped": 0,
        "spans": {"names": [], "idx": [], "ts": [], "dur": []},
        "ctrs": {"ts": [], "key": [], "val": []},
        "flows": {"b_id": [], "b_ts": [], "f_id": [], "f_ts": []},
        "counters": counters, "hist": {},
    }


def test_replica_evicted_and_readmitted_alert_exactly_once():
    from theanompi_tpu.observability import live

    agg = live.Aggregator(log=lambda line: None)
    ev_key = 'membership_evictions_total{plane="serve",rank="r1"}'
    re_key = 'serve_fleet_readmissions_total{replica="r1"}'
    agg.ingest(_live_frame("router", 1, {ev_key: 1.0, re_key: 2.0}))
    v1 = agg.close_window()
    ev = [a for a in v1["alerts"] if a["rule"] == "replica_evicted"]
    re_ = [a for a in v1["alerts"] if a["rule"] == "request_readmitted"]
    assert len(ev) == 1 and ev[0]["rank"] == "r1"
    assert "replica" in ev[0]["message"]
    assert len(re_) == 2 and all(a["rank"] == "r1" for a in re_)
    # a serve-plane eviction must NOT double-page as worker_evicted
    assert not [a for a in v1["alerts"] if a["rule"] == "worker_evicted"]
    # a frame with no fresh deltas never re-alerts (the alerted totals
    # are remembered), and a later window without fleet counters is
    # silent too
    agg.ingest(_live_frame("router", 2, {}))
    v2 = agg.close_window()
    assert not [
        a for a in v2["alerts"]
        if a["rule"] in ("replica_evicted", "request_readmitted")
    ]
    # a FRESH delta (second kill) pages exactly once more
    agg.ingest(_live_frame("router", 3, {ev_key: 1.0}))
    v3 = agg.close_window()
    ev3 = [a for a in v3["alerts"] if a["rule"] == "replica_evicted"]
    assert len(ev3) == 1


# ---------------------------------------------------------------------------
# the committed serve chaos drill, for real (in-process, no subprocesses
# — cheap enough for tier-1, unlike the training drills)
# ---------------------------------------------------------------------------


def test_serve_chaos_drill_passes_for_real():
    """What the perf_gate FLEET leg runs: kill → exactly one eviction
    (one alert) → re-admission(s) → token-identical outputs → p99
    within tolerance.  Any violation is a named string in the verdict."""
    from theanompi_tpu.runtime.chaos import run_serve_drill

    verdict = run_serve_drill(n_replicas=3, n_requests=6,
                              max_new_tokens=16, timeout=240.0)
    assert verdict["violations"] == []
    assert verdict["ok"] is True
    assert verdict["evictions"] == 1
    assert verdict["eviction_alerts"] == 1
    assert verdict["readmissions"] >= 1
    assert verdict["token_identical"] is True
    assert verdict["streams_in_flight_at_kill"] >= 1


def test_load_replica_checkpointless_spin_up(model, tmp_path):
    """The replacement path a supervisor runs after an eviction: one
    call from the durable checkpoint to a started replica that joins
    the fleet and serves identically to the source model."""
    from theanompi_tpu.serving.loader import load_replica
    from theanompi_tpu.utils import checkpoint

    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, model.checkpoint_state())
    rep = load_replica(
        path, "fresh", config=dict(CFG), mesh=model.mesh,
        n_slots=2, max_len=64, block_size=8,
    )
    try:
        # radix cache by default: the fleet's summaries exist
        from theanompi_tpu.serving.radix import RadixPrefixCache

        assert isinstance(rep.scheduler.prefix, RadixPrefixCache)
        router = FleetRouter(evict_after_s=30.0)
        router.add_replica("fresh", rep)
        prompts = _prompts(2, seed=21)
        _submit_all(router, prompts, max_new=4)
        out = router.run(timeout_s=120)
        ref = _engine(model)
        for j, p in enumerate(prompts):
            assert out[f"q{j}"] == ref.greedy(list(p), 4)
    finally:
        rep.stop()
