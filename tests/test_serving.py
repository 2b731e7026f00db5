"""Serving subsystem: KV-cache decode parity, continuous batching,
checkpoint → serving round-trips.

Acceptance (ISSUE 1): greedy KV-cache decode is argmax-identical to the
no-cache full-recompute forward for >= 32 steps; the continuous-batching
scheduler serves >= 3 overlapping requests with outputs identical to
serial execution; a training checkpoint round-trips into serving with
values and shardings preserved.

The oracle is ``_recompute_greedy``: the TRAINING forward, recomputed a
token at a time.  ``tests/test_serving_paged.py`` pins the paged cache's
own contracts (prefix reuse, backpressure, zero recompiles) against it
too.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.runtime.mesh import TP_AXIS, make_mesh
from theanompi_tpu.runtime.recorder import Recorder
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler,
    PagedServingEngine,
    Request,
    ServingMetrics,
    load_engine,
    restore_params_for_serving,
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


def _model(mesh=None, **over):
    mesh = mesh if mesh is not None else make_mesh(devices=jax.devices()[:1])
    return TransformerLM(config=dict(CFG, **over), mesh=mesh)


def _recompute_greedy(model, prompt, n_new):
    """No-cache baseline: full forward over a FIXED padded buffer each
    step, logits read at the last real position (causal attention makes
    positions independent of anything to their right, so one compiled
    length serves the whole decode)."""
    t = int(model.config.seq_len)
    fn = jax.jit(
        lambda p, s, x: model.net.apply(p, s, x, train=False, rng=None)[0]
    )
    buf = np.zeros((1, t), np.int32)
    seq = list(prompt)
    out = []
    for _ in range(n_new):
        buf[0, : len(seq)] = seq
        logits = fn(model.params, model.net_state, jnp.asarray(buf))
        tok = int(jnp.argmax(logits[0, len(seq) - 1]))
        out.append(tok)
        seq.append(tok)
    return out


# ---------------------------------------------------------------------------
# KV-cache decode parity
# ---------------------------------------------------------------------------

def test_greedy_kv_decode_matches_recompute_32_steps():
    """The acceptance bar: >= 32 decode steps, argmax-identical to the
    full-recompute baseline, through a non-trivial bucket pad."""
    model = _model()
    eng = PagedServingEngine(model, n_slots=2, max_len=64,
                             buckets=(8, 16, 64))
    prompt = [3, 1, 4, 1, 5]  # pads 5 -> bucket 8
    got = eng.greedy(prompt, 33)
    want = _recompute_greedy(model, prompt, 33)
    assert got == want


@pytest.mark.parametrize("chunk", [None, 8], ids=["whole", "chunked"])
def test_prefill_logits_close_to_recompute(chunk):
    """Beyond argmax: the prefill's last-token logits numerically match
    the training forward's, the prompt fed whole (bucket 16) or in two
    chunks of the bucket-8 program through the same block table."""
    model = _model()
    eng = PagedServingEngine(model, n_slots=1, max_len=64, buckets=(8, 16, 64),
                             block_size=8, prefill_chunk=chunk)
    prompt = [7, 2, 9, 4, 4, 1, 0, 30, 2, 2, 11]
    table = eng.make_pool().alloc(eng.max_seq_blocks(len(prompt)))
    state, step = eng.init_state(), chunk or len(prompt)
    for p0 in range(0, len(prompt), step):
        state, logits = eng.prefill_chunks(model.params, state, [
            {"tokens": prompt[p0:p0 + step], "p0": p0, "table": table}])
    logits = logits[0]

    t = int(model.config.seq_len)
    buf = np.zeros((1, t), np.int32)
    buf[0, : len(prompt)] = prompt
    full, _ = model.net.apply(
        model.params, model.net_state, jnp.asarray(buf), train=False, rng=None
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[0, len(prompt) - 1]),
        rtol=1e-4, atol=1e-4,
    )


def test_engine_rejects_unservable_configs():
    with pytest.raises(ValueError, match="sp=1"):
        mesh = TransformerLM.build_mesh(config=dict(CFG, sp=2))
        PagedServingEngine(_model(mesh=mesh, sp=2))
    with pytest.raises(ValueError, match="moe"):
        PagedServingEngine(_model(moe_experts=1, moe_aux_coef=0.0))
    with pytest.raises(ValueError, match="positional"):
        PagedServingEngine(_model(), max_len=128)  # > trained seq_len


def test_a_block_family_is_picked_once_and_refuses_its_own():
    """The engine looks a model's ``block`` up in ``paging.PROGRAMS`` and
    nowhere else: a block without programs is refused at construction,
    and what only one family cannot serve is refused by that family's
    own constructor (through the engine: above for dense,
    ``tests/test_latent_lm.py`` for latent)."""
    from types import SimpleNamespace

    from theanompi_tpu.runtime.config import Config
    from theanompi_tpu.serving import paging
    from theanompi_tpu.serving.dense import DensePrograms
    from theanompi_tpu.serving.latent import LatentPrograms

    assert paging.PROGRAMS == {"dense": DensePrograms,
                               "latent_moe": LatentPrograms}
    model = _model()
    eng = PagedServingEngine(model, n_slots=1, max_len=64)
    assert type(eng.programs) is DensePrograms and not eng.programs.latent
    assert LatentPrograms.latent
    unknown = SimpleNamespace(
        config=Config(model.config.asdict(), block="sliding_window"),
        mesh=model.mesh)
    with pytest.raises(ValueError, match="no serving programs for "
                                         "block='sliding_window'"):
        PagedServingEngine(unknown)
    # the families' constructors, alone
    with pytest.raises(ValueError, match="positional"):
        DensePrograms(SimpleNamespace(model=model, max_len=65))
    with pytest.raises(ValueError, match="moe_experts=0"):
        DensePrograms(SimpleNamespace(model=SimpleNamespace(
            config=Config(model.config.asdict(), moe_experts=2)), max_len=64))
    with pytest.raises(ValueError, match="compute dtype"):
        LatentPrograms(SimpleNamespace(kv_dtype="int8"))


def test_bucket_validation_rejects_non_int_and_duplicates():
    """Prefill buckets are compile-time shapes: construction must
    refuse anything that isn't a sorted set of positive ints with a
    clear error, instead of recompiling (or crashing) per request."""
    from theanompi_tpu.serving.engine import _validate_buckets

    # normalization: sorted tuple of ints, numpy ints accepted
    assert _validate_buckets([64, 8, 16], 64) == (8, 16, 64)
    assert _validate_buckets([np.int64(8), 16], 64) == (8, 16)
    with pytest.raises(TypeError, match="recompile per request"):
        _validate_buckets([8, 16.5], 64)
    with pytest.raises(TypeError, match="bool"):
        _validate_buckets([8, True], 64)
    with pytest.raises(TypeError, match="iterable of ints"):
        _validate_buckets(32, 64)
    with pytest.raises(ValueError, match="duplicate"):
        _validate_buckets([8, 8, 16], 64)
    with pytest.raises(ValueError, match=">= 1"):
        _validate_buckets([0, 8], 64)
    with pytest.raises(ValueError, match="at least one"):
        _validate_buckets([], 64)
    with pytest.raises(ValueError, match="exceeds max_len"):
        _validate_buckets([8, 128], 64)


def test_engine_construction_rejects_bad_buckets():
    with pytest.raises(TypeError, match="recompile per request"):
        PagedServingEngine(_model(), n_slots=1, max_len=64, buckets=(8.0, 64))
    with pytest.raises(ValueError, match="duplicate"):
        PagedServingEngine(_model(), n_slots=1, max_len=64,
                           buckets=(8, 8, 64))
    # unsorted input is normalized, not refused
    eng = PagedServingEngine(_model(), n_slots=1, max_len=64, buckets=(64, 8))
    assert eng.buckets == (8, 64)


def test_chunk_longer_than_buckets_is_refused():
    """The scheduler cuts a prompt into chunks of the largest bucket; a
    caller that hands the engine more than that is refused, not served
    by a program compiled on the spot."""
    eng = PagedServingEngine(_model(), n_slots=1, max_len=64, buckets=(8,))
    assert eng.greedy(list(range(9)), 2) == _recompute_greedy(
        eng.model, list(range(9)), 2)  # two chunks of the one program
    with pytest.raises(ValueError, match="bucket"):
        eng.prefill_chunks(eng.model.params, eng.init_state(), [
            {"tokens": list(range(9)), "p0": 0, "table": [1]}])


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_kwargs", [
    dict(n_slots=2, buckets=(8, 64)),
    dict(n_slots=4, buckets=(8, 16, 64), block_size=8, prefill_chunk=16),
], ids=["whole_prompt", "chunked"])
def test_scheduler_interleaved_matches_serial(engine_kwargs):
    """>= 3 overlapping requests on fewer slots than requests (forced
    queueing + join-on-finish recycling): per-request outputs must be
    IDENTICAL to each request run alone — with whole-prompt prefill, and
    through chunked prefill (a 30-token prompt in chunks of 16,
    interleaved with the others' decode ticks)."""
    model = _model()
    eng = PagedServingEngine(model, max_len=64, **engine_kwargs)
    reqs = [
        ("a", [1, 2, 3], 7),
        ("b", list(np.random.RandomState(7).randint(0, 32, size=30)), 5),
        ("c", [4], 9),
        ("d", [11, 30, 2, 2], 1),  # finishes at prefill
        ("e", [5, 5, 5, 5, 5, 5], 4),
    ]
    # serial baseline: each request alone in a fresh scheduler
    serial = {}
    for rid, prompt, n in reqs:
        s = ContinuousBatchingScheduler(eng)
        s.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=n))
        serial.update(s.run())
    # interleaved: all five queued at once over 2 slots
    sched = ContinuousBatchingScheduler(eng)
    for rid, prompt, n in reqs:
        sched.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=n))
    inter = sched.run()
    assert inter == serial
    assert len(inter) == 5
    assert len(inter["d"]) == 1
    assert [len(inter[r]) for r, _, n in reqs] == [n for _, _, n in reqs]


def test_scheduler_mid_stream_admission():
    """A request admitted while others are mid-decode joins a recycled
    slot without disturbing their outputs."""
    model = _model()
    eng = PagedServingEngine(model, n_slots=2, max_len=64, buckets=(8, 64))
    first = [("x", [1, 2], 6), ("y", [3, 4], 6)]
    sched = ContinuousBatchingScheduler(eng)
    for rid, prompt, n in first:
        sched.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=n))
    for _ in range(3):  # x/y mid-stream
        sched.step()
    sched.submit(Request(id="late", prompt=[7, 7, 7], max_new_tokens=4))
    out = sched.run()
    serial = {}
    for rid, prompt, n in first + [("late", [7, 7, 7], 4)]:
        s = ContinuousBatchingScheduler(eng)
        s.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=n))
        serial.update(s.run())
    assert out == serial


def test_scheduler_eos_stops_early():
    model = _model()
    eng = PagedServingEngine(model, n_slots=1, max_len=64, buckets=(8, 64))
    probe = ContinuousBatchingScheduler(eng)
    probe.submit(Request(id="p", prompt=[1, 2, 3], max_new_tokens=8))
    full = probe.run()["p"]
    # stop on a token at its FIRST occurrence in the stream (an earlier
    # duplicate would legitimately stop sooner)
    k = max(i for i, t in enumerate(full) if t not in full[:i])
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(
        Request(id="q", prompt=[1, 2, 3], max_new_tokens=8, eos_id=full[k])
    )
    out = sched.run()["q"]
    assert out == full[: k + 1]


def test_scheduler_refuses_oversized_request():
    eng = PagedServingEngine(_model(), n_slots=1, max_len=64)
    sched = ContinuousBatchingScheduler(eng)
    with pytest.raises(ValueError, match="cache rows"):
        sched.submit(Request(id="big", prompt=[1] * 60, max_new_tokens=10))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_ttft_tpot_and_recorder_events():
    t = {"now": 100.0}
    rec = Recorder(verbose=False)
    m = ServingMetrics(recorder=rec, clock=lambda: t["now"])
    m.admitted("r1", n_prompt=5)
    t["now"] = 100.5
    m.first_token("r1")
    t["now"] = 102.5
    m.finished("r1", n_out=5)  # 4 decode gaps over 2s -> tpot 0.5
    row = m.rows[0]
    assert row["ttft_s"] == pytest.approx(0.5)
    assert row["tpot_s"] == pytest.approx(0.5)
    kinds = [e["kind"] for e in rec.events]
    assert "serve_request" in kinds
    s = m.summary()
    assert s["n_requests"] == 1 and s["n_tokens_out"] == 5
    assert [e["kind"] for e in rec.events].count("serve_summary") == 1


def test_scheduler_feeds_metrics():
    eng = PagedServingEngine(_model(), n_slots=2, max_len=64, buckets=(8, 64))
    rec = Recorder(verbose=False)
    metrics = ServingMetrics(recorder=rec)
    sched = ContinuousBatchingScheduler(eng, metrics=metrics)
    for i in range(3):
        sched.submit(Request(id=f"r{i}", prompt=[i + 1, 2], max_new_tokens=3))
    sched.run()
    s = metrics.summary()
    assert s["n_requests"] == 3
    assert s["n_tokens_out"] == 9
    for k in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s"):
        assert s[k] >= 0.0
    assert sum(e["kind"] == "serve_request" for e in rec.events) == 3
    # the run's reuse/capacity stats ride the summary
    assert s["engine_stats"]["pool_blocks"] == eng.n_blocks - 1
    assert 0 < s["engine_stats"]["pool_peak_used_blocks"] <= eng.n_blocks - 1


# ---------------------------------------------------------------------------
# checkpoint → serving round-trip
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_preserves_values_and_serving_output(tmp_path):
    from theanompi_tpu.utils import checkpoint

    model = _model()
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, model.checkpoint_state())

    eng = load_engine(path, config=dict(CFG), mesh=model.mesh, n_slots=1,
                      max_len=64)
    # values preserved leaf-for-leaf
    for a, b in zip(
        jax.tree.leaves(model.params), jax.tree.leaves(eng.model.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # replicated layout on a dp mesh
    for leaf in jax.tree.leaves(eng.model.params):
        assert leaf.sharding.is_fully_replicated
    # and the restored engine decodes exactly like the source model
    prompt = [2, 7, 1, 8]
    assert eng.greedy(prompt, 8) == _recompute_greedy(model, prompt, 8)


def test_checkpoint_to_tensor_parallel_serving(tmp_path):
    """A dp-trained checkpoint re-lays into Megatron tp sharding for
    serving (via _build_param_specs) and still decodes identically."""
    from theanompi_tpu.utils import checkpoint

    src = _model()
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, src.checkpoint_state())
    baseline = _recompute_greedy(src, [5, 3, 2], 6)

    cfg_tp = dict(CFG, tp=2)
    mesh_tp = TransformerLM.build_mesh(config=cfg_tp)  # (dp=4, tp=2)
    tp_model = TransformerLM(config=cfg_tp, mesh=mesh_tp)
    restore_params_for_serving(tp_model, path)
    # attention/MLP matrices landed SHARDED over tp, not replicated
    blk = tp_model.params[2]
    wq = blk["attn"]["wq"]
    assert wq.sharding.spec == P(None, TP_AXIS)
    assert blk["mlp_out"]["w"].sharding.spec == P(TP_AXIS, None)
    np.testing.assert_array_equal(
        np.asarray(wq), np.asarray(src.params[2]["attn"]["wq"])
    )
    eng = PagedServingEngine(tp_model, n_slots=1, max_len=64)
    assert eng.greedy([5, 3, 2], 6) == baseline


def test_loader_rejects_wrong_architecture(tmp_path):
    from theanompi_tpu.utils import checkpoint

    model = _model()
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, model.checkpoint_state())
    with pytest.raises(ValueError, match="different params structure"):
        load_engine(path, config=dict(CFG, n_layers=3), mesh=model.mesh)
