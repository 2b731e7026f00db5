"""The serving tree (ISSUE 32): a ``dense`` model's programs read their
matrices and tables in the compute dtype, cast once where a tree becomes
a scheduler's (``paging.serving_params``), not by every call.

Contracts under test:

- **The same arithmetic**: with ``compute_dtype='bfloat16'`` the prefill,
  decode and verify programs give bit-identical logits and pools for the
  float32 tree and for its serving tree (float and int8 pool, one device
  and the 4 x 2 dp x tp mesh, whose leaf shardings the cast keeps).
- **Idempotent, and the identity** where there is nothing to cast: a
  serving tree comes back as the same arrays, and so does any tree of a
  model without a compute dtype and of the latent family.
- **Where it is applied**: the scheduler's and the draft's constructors,
  a replica's install, ``relayout_for_serving`` (so that
  ``validate_swap`` compares like with like: a publish -> install ->
  rollback round trip on a bfloat16 replica), ``load_engine``.
- **Who owns it**: the scheduler; the model is not touched, and once
  scheduler and engine are dropped nothing refers to a cast leaf.
"""

import gc
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu import observability as obs
from theanompi_tpu.models.transformer import TransformerLM, make_draft
from theanompi_tpu.publish import (
    CenterPublisher, SwapRefused, WeightSubscriber, validate_swap,
)
from theanompi_tpu.runtime.mesh import make_mesh
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler, PagedServingEngine, Request, ServeReplica,
    load_engine,
)
from theanompi_tpu.serving.dense import COMPUTE_LEAVES
from theanompi_tpu.serving.latent import LatentPrograms
from theanompi_tpu.serving.loader import relayout_for_serving
from theanompi_tpu.serving.paging import serving_params

CFG = dict(
    seq_len=64, vocab_size=32, d_model=32, n_heads=4, n_layers=2,
    batch_size=2, n_synth_train=2, n_synth_val=1, comm_probe=False,
    print_freq=10_000,
)
BF16 = dict(CFG, compute_dtype="bfloat16")
GEOM = dict(n_slots=2, max_len=64, block_size=8, n_blocks=16)


def _one_device():
    return make_mesh(devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def model():
    return TransformerLM(config=dict(BF16), mesh=_one_device())


def _named(tree):
    """(leaf's own name, leaf) over a params tree."""
    return [(path[-1].key, a)
            for path, a in jax.tree_util.tree_leaves_with_path(tree)]


def _assert_in_layout(tree, dtype=jnp.bfloat16):
    for name, a in _named(tree):
        want = dtype if name in COMPUTE_LEAVES else jnp.float32
        assert a.dtype == want, (name, a.dtype)


# ---------------------------------------------------------------------------
# the same arithmetic
# ---------------------------------------------------------------------------

def _three_programs(eng, params):
    """A prefill call, a decode tick over what it wrote, a verify call
    over both: logits and the pool after each."""
    state = eng.init_state()
    out = []
    rows = [{"tokens": [5, 3, 2, 9, 1], "p0": 0, "table": [1, 2]},
            {"tokens": [7, 7, 4], "p0": 0, "table": [3, 4]}]
    state, logits = eng.prefill_chunks(params, state, rows)
    out.append((np.asarray(logits), jax.device_get(state)))
    tables = np.zeros((eng.n_slots, eng.blocks_per_seq), np.int32)
    tables[0, :2], tables[1, :2] = [1, 2], [3, 4]
    lengths = np.array([5, 3], np.int32)
    active = np.array([True, True])
    state, logits = eng.decode_step_paged(
        params, state, np.array([11, 30], np.int32), tables, lengths, active)
    out.append((np.asarray(logits), jax.device_get(state)))
    state, logits = eng.verify_chunks(
        params, state, np.array([[4, 8, 15], [16, 23, 0]], np.int32), tables,
        lengths + 1, np.array([3, 2], np.int32), active)
    out.append((np.asarray(logits), jax.device_get(state)))
    return out


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("mesh", ["one_device", "dp4_tp2"])
def test_serving_tree_gives_bit_identical_logits(mesh, kv_dtype):
    if mesh == "one_device":
        m = TransformerLM(config=dict(BF16), mesh=_one_device())
    else:
        cfg = dict(BF16, tp=2)
        m = TransformerLM(config=cfg, mesh=TransformerLM.build_mesh(config=cfg))
        assert (m.mesh.shape["dp"], m.mesh.shape["tp"]) == (4, 2)
    eng = PagedServingEngine(m, kv_dtype=kv_dtype, **GEOM)
    tree = eng.serving_params(m.params)
    _assert_in_layout(tree)
    assert jax.tree.structure(tree) == jax.tree.structure(m.params)
    for a, b in zip(jax.tree.leaves(m.params), jax.tree.leaves(tree)):
        assert a.dtype == jnp.float32  # the model's own tree is untouched
        assert b.sharding.is_equivalent_to(a.sharding, a.ndim)
    want, got = _three_programs(eng, m.params), _three_programs(eng, tree)
    for (lw, sw), (lg, sg) in zip(want, got):
        assert lw.dtype == np.float32 and np.isfinite(lw).all()
        np.testing.assert_array_equal(lw, lg)
        for a, b in zip(jax.tree.leaves(sw), jax.tree.leaves(sg)):
            np.testing.assert_array_equal(a, b)
    # one program each, whichever tree came first: two dtypes, two traces
    assert eng._n_decode_traces == 2 and eng._n_verify_traces == 2


# ---------------------------------------------------------------------------
# idempotent; the identity where there is nothing to cast
# ---------------------------------------------------------------------------

def test_serving_params_twice_is_once_and_the_same_arrays(model):
    eng = PagedServingEngine(model, **GEOM)
    once = eng.serving_params(model.params)
    twice = eng.serving_params(once)
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(twice)):
        assert a is b
    # by the model alone, as the loader reaches it
    for a, b in zip(jax.tree.leaves(once),
                    jax.tree.leaves(serving_params(model, once))):
        assert a is b
    # what was cast is the float32 leaf's own rounding, nothing else
    for (name, a), b in zip(_named(model.params), jax.tree.leaves(once)):
        if name in COMPUTE_LEAVES:
            np.testing.assert_array_equal(
                np.asarray(a.astype(jnp.bfloat16)), np.asarray(b))
        else:
            assert a is b


@pytest.mark.parametrize("family", ["dense_float32", "latent"])
def test_serving_params_is_the_identity_without_a_cast_to_make(family):
    if family == "dense_float32":
        m = TransformerLM(config=dict(CFG), mesh=_one_device())
        eng = PagedServingEngine(m, **GEOM)
        assert eng.compute_dtype is None
        tree = eng.serving_params(m.params)
        sched = ContinuousBatchingScheduler(eng)
        for a, b, c in zip(*map(jax.tree.leaves,
                                (m.params, tree, sched.params))):
            assert a is b and a is c
    else:
        tree = {"attn": {"wq": np.ones((2, 2), np.float32)},
                "table": np.ones((3, 2), np.float32)}
        assert LatentPrograms.serving_params(tree, jnp.bfloat16) is tree


# ---------------------------------------------------------------------------
# where it is applied, what it reports, who owns it
# ---------------------------------------------------------------------------

def test_scheduler_holds_the_tree_reports_it_and_lets_it_go(model):
    eng = PagedServingEngine(model, **GEOM)
    t0 = obs.get_tracer().clock()
    sched = ContinuousBatchingScheduler(eng)
    _assert_in_layout(sched.params)
    f32 = sum(a.nbytes for a in jax.tree.leaves(model.params))
    cast_in = sum(a.nbytes for n, a in _named(model.params)
                  if n in COMPUTE_LEAVES)
    assert sched.stats["weight_bytes"] == f32 - cast_in // 2
    span, = [s for s in obs.get_tracer().boundary_spans(t0)
             if s["name"] == "weights_relayout"]
    n_cast = sum(n in COMPUTE_LEAVES for n, _ in _named(model.params))
    assert span["args"] == {"leaves_cast": n_cast, "bytes_in": cast_in,
                            "bytes_out": cast_in // 2}
    # the model keeps its float32 arrays, and serves the oracle's tokens
    # (the same program a float32 tree runs: bit-identical logits above)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(model.params))
    sched.submit(Request(id="r", prompt=[5, 3, 2], max_new_tokens=4))
    got = sched.run()["r"]
    assert got == eng.greedy([5, 3, 2], 4, params=model.params)
    # a tree handed in again is bound as it is and the span says so
    t1 = obs.get_tracer().clock()
    held = sched.params
    sched.install_params(held)
    assert all(a is b for a, b in zip(jax.tree.leaves(held),
                                      jax.tree.leaves(sched.params)))
    span, = [s for s in obs.get_tracer().boundary_spans(t1)
             if s["name"] == "weights_relayout"]
    assert span["args"]["leaves_cast"] == 0
    # dropped with its owners: nothing global keeps a cast leaf alive
    ref = weakref.ref(sched.params[2]["attn"]["wq"])
    assert ref() is not model.params[2]["attn"]["wq"]
    del sched, eng, held
    gc.collect()
    assert ref() is None


def test_draft_engine_holds_its_serving_tree(model):
    draft = make_draft(model, n_layers=1)
    eng = PagedServingEngine(model, **GEOM)
    deng = PagedServingEngine(draft, **GEOM)
    sched = ContinuousBatchingScheduler(eng, spec_k=2, draft_engine=deng)
    _assert_in_layout(sched._spec.draft_params)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(draft.params))
    sched.submit(Request(id="r", prompt=[5, 3, 2, 9], max_new_tokens=6))
    assert sched.run()["r"] == eng.greedy([5, 3, 2, 9], 6)


def _perturb(tree, eps=0.05):
    rng = np.random.RandomState(7)
    return jax.tree.map(
        lambda a: (a + eps * rng.standard_normal(a.shape)).astype(a.dtype),
        tree)


def test_publish_install_rollback_round_trip_on_a_bfloat16_replica(model):
    """The publish path on a replica whose scheduler holds a serving
    tree: ``validate_swap`` refuses any dtype difference, so the incoming
    float32 snapshot has to arrive in serving layout
    (``relayout_for_serving``), and a rollback installs the prior
    serving tree as it is."""
    host0 = jax.tree.map(np.array, jax.device_get(model.params))
    host1 = _perturb(host0)
    pub = CenterPublisher(lambda: host1, publish_every=1)
    ann = pub.publish()
    eng, oracle = (PagedServingEngine(model, **GEOM) for _ in range(2))
    rep = ServeReplica("bf0", eng, params=relayout_for_serving(model, host0))
    # not started: no tick thread, every tick is driven by hand
    sub = WeightSubscriber(
        rep, lambda g: pub.snapshot(g),
        relayout=lambda p: relayout_for_serving(model, p))

    def serve(rid):
        rep.handle(("submit", {"id": rid, "prompt": [5, 3, 2, 9],
                               "max_new_tokens": 6}))
        while not rep.scheduler.idle:
            with rep._lock:
                rep.scheduler.step()
        return rep.handle(("poll", {rid: 0}))["streams"][rid]["toks"]

    gen0_tree = rep.scheduler.params
    _assert_in_layout(gen0_tree)
    # the raw snapshot would be refused: float32 against bfloat16
    with pytest.raises(SwapRefused, match="recompile hazard"):
        validate_swap(gen0_tree, host1)
    toks0 = serve("a")
    assert toks0 == oracle.greedy([5, 3, 2, 9], 6, params=host0)
    assert sub.poll(ann) is True
    assert rep.serving_generation == 1 and rep.installs == 1
    _assert_in_layout(rep.scheduler.params)
    assert rep.scheduler.stats["weight_bytes"] == sum(
        a.nbytes for a in jax.tree.leaves(gen0_tree))
    toks1 = serve("b")
    assert toks1 == oracle.greedy([5, 3, 2, 9], 6, params=host1)
    assert toks1 != toks0
    assert sub.flag_regression(1) is True
    assert rep.serving_generation == 0 and rep.installs == 2
    for a, b in zip(jax.tree.leaves(rep.scheduler.params),
                    jax.tree.leaves(gen0_tree)):
        assert a is b  # the prior tree, bound as it was
    assert serve("c") == toks0
    # nothing retraced: every installed tree had the served one's avals
    assert eng._n_decode_traces == 1


def test_load_engine_hands_the_model_the_serving_tree(model, tmp_path):
    from theanompi_tpu.utils import checkpoint

    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, model.checkpoint_state())
    eng = load_engine(path, config=dict(BF16), mesh=model.mesh, n_slots=1,
                      max_len=64)
    _assert_in_layout(eng.model.params)
    sched = ContinuousBatchingScheduler(eng)
    for a, b in zip(jax.tree.leaves(eng.model.params),
                    jax.tree.leaves(sched.params)):
        assert a is b  # one copy of the weights in the deployment
    want = PagedServingEngine(model, n_slots=1, max_len=64)
    assert eng.greedy([2, 7, 1, 8], 8) == want.greedy([2, 7, 1, 8], 8)
