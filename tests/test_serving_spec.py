"""Decode-speed layers (ISSUE 11): speculative decoding, int8 KV
blocks, fused Pallas paged attention.

Acceptance contracts under test:

- **Spec token identity**: greedy speculative decode is token-identical
  to non-speculative greedy on dp AND tp meshes, for any draft — the
  draft only changes how many tokens a round emits, never their values.
  Sampling requests keep the same property (per-index keys).
- **Acceptance edges**: spec_k=0 is the plain path (and refuses a
  dangling draft engine); an always-wrong draft degrades to one token
  per round (accept_rate 0) without perturbing the stream; the target
  as its own draft accepts everything (accept_rate 1, k+1 tokens per
  full round).
- **int8 KV**: per-row quantized blocks keep prefix share-and-reuse
  exact (reuse ON == reuse OFF), chunked == whole-prompt prefill, and
  at least double the blocks per byte vs fp32.
- **Pallas paged decode**: the fused kernel matches the XLA gather
  path allclose (fp32 and int8 pools) and is exercised in interpret
  mode here in tier-1; unsupported pools fall back to XLA, recorded.
- **Zero recompiles**: acceptance-length churn and draft/slot churn
  never retrace — one verify program per chunk width, ever.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models.transformer import TransformerLM, make_draft
from theanompi_tpu.runtime.mesh import make_mesh
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler,
    PagedServingEngine,
    Request,
    SpecDecoder,
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

PROMPTS = [
    ([3, 1, 4, 1, 5], 12),
    ([7, 2, 9, 4, 4, 1, 0, 30, 2, 2, 11], 8),
    (list(range(20)), 16),
]


@pytest.fixture(scope="module")
def model():
    mesh = make_mesh(devices=jax.devices()[:1])
    return TransformerLM(config=dict(CFG), mesh=mesh)


@pytest.fixture(scope="module")
def engine(model):
    return PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8
    )


@pytest.fixture(scope="module")
def draft_engine(model):
    draft = make_draft(model, n_layers=1)
    return PagedServingEngine(
        draft, n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8
    )


def _run_one(eng, prompt, n_new, **kw):
    sched = ContinuousBatchingScheduler(eng, **kw)
    sched.submit(Request(id="r", prompt=list(prompt), max_new_tokens=n_new))
    out = sched.run()["r"]
    return out, sched


# ---------------------------------------------------------------------------
# speculative decoding: token identity
# ---------------------------------------------------------------------------

def test_spec_greedy_token_identical(engine, draft_engine):
    """The headline contract: greedy spec == greedy plain, token for
    token, across prompts and draft lengths."""
    for prompt, n_new in PROMPTS:
        want = engine.greedy(list(prompt), n_new)
        for k in (1, 3, 4):
            got = engine.greedy(list(prompt), n_new, spec_k=k,
                                draft_engine=draft_engine)
            assert got == want, f"spec k={k} diverged on {prompt[:4]}..."


def test_spec_interleaved_matches_serial(engine, draft_engine):
    """Continuous-batching determinism survives speculation: overlapped
    requests produce the same outputs as each alone (and as plain)."""
    reqs = [
        ("a", [1, 2, 3], 7),
        ("b", list(np.random.RandomState(7).randint(0, 32, size=30)), 5),
        ("c", [4], 9),
    ]
    sched = ContinuousBatchingScheduler(engine, spec_k=3,
                                        draft_engine=draft_engine)
    for rid, p, n in reqs:
        sched.submit(Request(id=rid, prompt=list(p), max_new_tokens=n))
    got = sched.run()
    for rid, p, n in reqs:
        assert got[rid] == engine.greedy(list(p), n), rid


def test_spec_on_dp_mesh_matches():
    """Spec decode across a multi-device dp mesh: block pool dp-sharded,
    tables/lengths still host data, tokens unchanged."""
    mesh = make_mesh()  # all fake devices on dp
    model = TransformerLM(config=dict(CFG), mesh=mesh)
    eng = PagedServingEngine(model, n_slots=2, max_len=64,
                             buckets=(8, 16, 64), block_size=8)
    drf = PagedServingEngine(make_draft(model, 1), n_slots=2, max_len=64,
                             buckets=(8, 16, 64), block_size=8)
    prompt, n_new = PROMPTS[1]
    want = eng.greedy(list(prompt), n_new)
    assert eng.greedy(list(prompt), n_new, spec_k=3,
                      draft_engine=drf) == want


def test_spec_on_tp_mesh_matches():
    """Tensor-parallel target + tensor-parallel draft: heads shard over
    tp in both pools, spec tokens unchanged."""
    cfg_tp = dict(CFG, tp=2)
    mesh_tp = TransformerLM.build_mesh(config=cfg_tp)
    model = TransformerLM(config=cfg_tp, mesh=mesh_tp)
    eng = PagedServingEngine(model, n_slots=1, max_len=64, block_size=8)
    drf = PagedServingEngine(make_draft(model, 1), n_slots=1, max_len=64,
                             block_size=8)
    want = eng.greedy([5, 3, 2], 6)
    assert eng.greedy([5, 3, 2], 6, spec_k=2, draft_engine=drf) == want


def test_spec_sampling_token_identical(engine, draft_engine):
    """Sampled streams too: every pick draws with the request's own
    (seed, token_index) key, so speculation can't perturb them."""
    req = dict(prompt=[5, 1, 9, 9], max_new_tokens=10, temperature=0.8,
               top_k=5, seed=123)
    plain = ContinuousBatchingScheduler(engine)
    plain.submit(Request(id="s", **req))
    want = plain.run()["s"]
    spec = ContinuousBatchingScheduler(engine, spec_k=3,
                                       draft_engine=draft_engine)
    spec.submit(Request(id="s", **req))
    assert spec.run()["s"] == want


def test_spec_eos_mid_round(engine, draft_engine):
    """An accepted token hitting eos finishes the request mid-round —
    stream equals the plain path's eos-truncated stream."""
    prompt, n_new = PROMPTS[0]
    plain = engine.greedy(list(prompt), n_new)
    eos = plain[2]  # finishes on the 3rd generated token
    want_sched = ContinuousBatchingScheduler(engine)
    want_sched.submit(Request(id="e", prompt=list(prompt),
                              max_new_tokens=n_new, eos_id=int(eos)))
    want = want_sched.run()["e"]
    got_sched = ContinuousBatchingScheduler(engine, spec_k=4,
                                            draft_engine=draft_engine)
    got_sched.submit(Request(id="e", prompt=list(prompt),
                             max_new_tokens=n_new, eos_id=int(eos)))
    assert got_sched.run()["e"] == want
    assert want[-1] == eos and len(want) < n_new


# ---------------------------------------------------------------------------
# acceptance-rate edges
# ---------------------------------------------------------------------------

def test_spec_k0_is_plain_and_refuses_dangling_draft(engine, draft_engine):
    out, sched = _run_one(engine, [1, 2, 3], 5)
    assert sched.spec_summary() is None  # spec_k=0: no spec machinery
    with pytest.raises(ValueError, match="spec_k=0"):
        ContinuousBatchingScheduler(engine, draft_engine=draft_engine)


def test_spec_all_reject_degrades_to_one_token_per_round(model, engine):
    """A draft that always proposes a token the target never picks:
    accept_rate exactly 0, one emitted token per round, stream still
    identical to plain."""
    prompt, n_new = PROMPTS[1]
    plain = engine.greedy(list(prompt), n_new)
    bad_tok = next(t for t in range(CFG["vocab_size"]) if t not in plain)
    draft = make_draft(model, n_layers=1)
    head = dict(draft.params[-1])
    head["w"] = jnp.zeros_like(head["w"])
    head["b"] = jnp.zeros_like(head["b"]).at[bad_tok].set(100.0)
    draft.params = list(draft.params[:-1]) + [head]
    drf = PagedServingEngine(draft, n_slots=2, max_len=64,
                             buckets=(8, 16, 64), block_size=8)
    got, sched = _run_one(engine, prompt, n_new, spec_k=3,
                          draft_engine=drf)
    assert got == plain
    s = sched.spec_summary()
    assert s["accepted"] == 0 and s["accept_rate"] == 0.0
    assert s["emitted"] == s["rounds"]  # 1 token per round, no more


def test_spec_all_accept_with_self_draft(model, engine):
    """The target as its own draft accepts every proposal: accept_rate
    1.0 and full rounds emit k+1 tokens."""
    self_draft = PagedServingEngine(model, n_slots=2, max_len=64,
                                    buckets=(8, 16, 64), block_size=8)
    prompt, n_new = PROMPTS[0]
    got, sched = _run_one(engine, prompt, n_new, spec_k=3,
                          draft_engine=self_draft)
    assert got == engine.greedy(list(prompt), n_new)
    s = sched.spec_summary()
    assert s["accept_rate"] == 1.0
    assert s["rounds"] < n_new  # strictly fewer target rounds than tokens
    assert s["emitted"] == n_new - 1  # prefill emitted the first token


def test_spec_budget_clamp_and_zero_recompile(engine, draft_engine):
    """Lanes near their token budget clamp k_eff (true_len DATA, not a
    shape): requests of every remaining-budget phase drain through ONE
    verify program, and a second scheduler retraces nothing."""
    before = engine._n_verify_traces
    for n_new in (2, 3, 5, 9):
        got, _ = _run_one(engine, [4, 4, 4], n_new, spec_k=4,
                          draft_engine=draft_engine)
        assert got == engine.greedy([4, 4, 4], n_new)
        assert len(got) == n_new
    assert engine._n_verify_traces - before <= 1


def test_spec_decoder_validates_geometry(model, engine, draft_engine):
    with pytest.raises(ValueError, match="k must be >= 1"):
        SpecDecoder(engine, draft_engine, 0)
    mismatched = PagedServingEngine(make_draft(model, 1), n_slots=4,
                                    max_len=64, block_size=8)
    with pytest.raises(ValueError, match="n_slots"):
        SpecDecoder(engine, mismatched, 2)


# ---------------------------------------------------------------------------
# int8 KV blocks
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_i8(model):
    return PagedServingEngine(
        model, n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8,
        kv_dtype="int8",
    )


def test_int8_kv_prefix_share_and_reuse_equivalence(model, engine_i8):
    """Quantization is per row, once, on write — a prefix-shared block
    reads back the same bytes for every consumer, so reuse ON == reuse
    OFF exactly (including chunked prefill)."""
    shared = list(np.random.RandomState(1).randint(0, 32, size=24))
    reqs = [("a", shared + [7], 6), ("b", shared + [9], 6),
            ("c", shared + [9, 3], 4)]
    sched = ContinuousBatchingScheduler(engine_i8)
    for rid, p, n in reqs:
        sched.submit(Request(id=rid, prompt=list(p), max_new_tokens=n))
        sched.step()  # space arrivals so reuse can engage
    out = sched.run()
    assert sched.stats["prefix_hits"] >= 1  # reuse really engaged
    no_reuse = ContinuousBatchingScheduler(engine_i8)
    no_reuse.prefix = None
    for rid, p, n in reqs:
        no_reuse.submit(Request(id=rid, prompt=list(p), max_new_tokens=n))
        no_reuse.step()
    assert no_reuse.run() == out


def test_int8_kv_chunked_matches_whole_prompt(model):
    """The quantized image is what chunk queries attend, so chunk
    boundaries cannot move the numerics: chunked == one-shot."""
    whole = PagedServingEngine(model, n_slots=2, max_len=64,
                               buckets=(8, 16, 64), block_size=8,
                               kv_dtype="int8")
    chunked = PagedServingEngine(model, n_slots=2, max_len=64,
                                 buckets=(8, 16, 64), block_size=8,
                                 kv_dtype="int8", prefill_chunk=16)
    prompt = list(np.random.RandomState(0).randint(0, 32, size=37))
    assert whole.greedy(list(prompt), 10) == chunked.greedy(list(prompt), 10)


def test_int8_kv_capacity_at_least_doubles(engine, engine_i8):
    """The ISSUE-11 capacity criterion: at equal cache bytes, int8
    holds >= 2x the blocks (~3.8x at head_dim 64; 2.67x at this test
    geometry's head_dim 8)."""
    budget = 64 * engine.kv_block_bytes()
    ratio = engine_i8.blocks_at_budget(budget) / engine.blocks_at_budget(budget)
    assert ratio >= 2.0
    assert engine_i8.kv_block_bytes() < engine.kv_block_bytes()


def test_int8_kv_greedy_drift_is_bounded(engine, engine_i8):
    """int8 KV is lossy — the contract is bounded drift, probed like
    bench_serve's detail.kv_quant: most greedy tokens agree."""
    agree = total = 0
    for prompt, n_new in PROMPTS:
        a = engine.greedy(list(prompt), n_new)
        b = engine_i8.greedy(list(prompt), n_new)
        agree += sum(x == y for x, y in zip(a, b))
        total += n_new
    assert agree / total >= 0.8, f"int8 drift too high: {agree}/{total}"


def test_int8_kv_composes_with_spec(model, engine_i8):
    """Spec token-identity holds WITHIN the int8 engine (spec-on vs
    spec-off over the same quantized cache)."""
    drf = PagedServingEngine(make_draft(model, 1), n_slots=2, max_len=64,
                             buckets=(8, 16, 64), block_size=8,
                             kv_dtype="int8")
    prompt, n_new = PROMPTS[1]
    want = engine_i8.greedy(list(prompt), n_new)
    assert engine_i8.greedy(list(prompt), n_new, spec_k=3,
                            draft_engine=drf) == want


def test_kv_dtype_validation(model):
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedServingEngine(model, n_slots=1, max_len=64, block_size=8,
                           kv_dtype="fp8")
    with pytest.raises(ValueError, match="paged_attn"):
        PagedServingEngine(model, n_slots=1, max_len=64, block_size=8,
                           paged_attn="cuda")


# ---------------------------------------------------------------------------
# Pallas paged-attention decode kernel
# ---------------------------------------------------------------------------

def _xla_paged_reference(q, kp, vp, tables, lengths, bs, scale):
    s, h, hd = q.shape
    nt = tables.shape[1]
    rows = (tables[:, :, None] * bs
            + np.arange(bs)[None, None, :]).reshape(s, -1)
    kc, vc = kp[rows], vp[rows]
    sc = np.einsum("shd,sthd->sht", q, kc) * scale
    mask = np.arange(nt * bs)[None, :] <= lengths[:, None]
    sc = np.where(mask[:, None, :], sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("sht,sthd->shd", p, vc)


def _flat_pool(x, width):
    """(rows, H, hd) -> the engine's (rows, width) pool: a row's heads
    side by side, zeros up to ``width``."""
    x = np.asarray(x).reshape(x.shape[0], -1)
    return np.pad(x, ((0, 0), (0, width - x.shape[1])))


# widths that are no multiple of 128 lanes as they stand (32 and 40
# numbers a row, stored 128 wide) and ones that are (128; 256 exactly)
@pytest.mark.parametrize("h,hd", [(4, 8), (5, 8), (2, 64), (4, 64)])
def test_pallas_paged_kernel_matches_xla_fp32_and_int8(h, hd):
    """The kernel-level allclose pin, exercised in interpret mode:
    fused in-kernel gather over the flat ``(rows, width)`` pool ==
    materialized XLA gather, fp32 and int8 pools, including short
    lengths (a lane's grid steps stop at its length)."""
    from theanompi_tpu.ops.pallas_paged import paged_decode_attention
    from theanompi_tpu.parallel.quantize import (
        dequantize_blocks, quantize_blocks,
    )

    rng = np.random.RandomState(0)
    s, bs, nb, nt = 3, 4, 10, 5
    width = -(-h * hd // 128) * 128
    q = rng.randn(s, h, hd).astype(np.float32)
    kp = rng.randn(nb * bs, h, hd).astype(np.float32)
    vp = rng.randn(nb * bs, h, hd).astype(np.float32)
    tables = np.array(
        [[1, 3, 5, 0, 0], [2, 4, 6, 7, 0], [8, 9, 1, 2, 3]], np.int32
    )
    lengths = np.array([9, 14, 0], np.int32)  # incl. a length-0 lane
    want = _xla_paged_reference(q, kp, vp, tables, lengths, bs, hd ** -0.5)
    got = np.asarray(paged_decode_attention(
        q, _flat_pool(kp, width), _flat_pool(vp, width), tables, lengths,
        block_size=bs,
    ))
    assert got.shape == (s, h, hd)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    kq, ks = quantize_blocks(jnp.asarray(kp))
    vq, vs = quantize_blocks(jnp.asarray(vp))
    want8 = _xla_paged_reference(
        q, np.asarray(dequantize_blocks(kq, ks)),
        np.asarray(dequantize_blocks(vq, vs)), tables, lengths, bs,
        hd ** -0.5,
    )
    kq, vq = _flat_pool(kq, width), _flat_pool(vq, width)
    got8 = np.asarray(paged_decode_attention(
        q, kq, vq, tables, lengths,
        block_size=bs, k_scale=np.asarray(ks), v_scale=np.asarray(vs),
    ))
    np.testing.assert_allclose(got8, want8, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="k_scale"):
        paged_decode_attention(q, kq, vq, tables, lengths, block_size=bs)
    with pytest.raises(ValueError, match="rows, width"):  # the old layout
        paged_decode_attention(q, kp, vp, tables, lengths, block_size=bs)


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("kind", ["edges", "full", "one_long"])
def test_pallas_paged_kernel_walks_the_lanes_resident_blocks(kind, kv_dtype):
    """The grid is the list of resident (lane, block) pairs: an idle
    lane, lanes a row short of, at and past a block's boundary, a lane
    in the table's last column, every lane full, one long lane among
    short ones: each equals the XLA gather."""
    from theanompi_tpu.ops.kernel_cases import ragged_lengths
    from theanompi_tpu.ops.pallas_paged import paged_decode_attention
    from theanompi_tpu.parallel.quantize import (
        dequantize_blocks, quantize_blocks,
    )

    rng = np.random.RandomState(1)
    s, h, hd, bs, nb, nt = 6, 5, 8, 4, 30, 6
    q = rng.randn(s, h, hd).astype(np.float32)
    kp = rng.randn(nb * bs, h, hd).astype(np.float32)
    vp = rng.randn(nb * bs, h, hd).astype(np.float32)
    tables = rng.randint(1, nb, (s, nt)).astype(np.int32)
    lengths = ragged_lengths(kind, s, bs, nt * bs)
    kw = {}
    if kv_dtype == "int8":
        (kq, ks), (vq, vs) = (quantize_blocks(jnp.asarray(a))
                              for a in (kp, vp))
        kw = dict(k_scale=np.asarray(ks), v_scale=np.asarray(vs))
        kp = np.asarray(dequantize_blocks(kq, ks))
        vp = np.asarray(dequantize_blocks(vq, vs))
        pools = _flat_pool(kq, 128), _flat_pool(vq, 128)
    else:
        pools = _flat_pool(kp, 128), _flat_pool(vp, 128)
    got = np.asarray(paged_decode_attention(
        q, *pools, tables, lengths, block_size=bs, **kw))
    want = _xla_paged_reference(q, kp, vp, tables, lengths, bs, hd ** -0.5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind,s,span,per_lane", [
    ("edges", 5, 4, 6), ("edges", 7, 512, 35), ("full", 3, 32, 32),
    ("one_long", 64, 512, 35), ("past_the_table", 4, 8, 3),
])
def test_lane_steps_lists_each_lanes_resident_groups(kind, s, span, per_lane):
    """``total`` is the sum of ``length // span + 1``, ``lane`` does not
    decrease and each lane's ``j`` runs ``0 .. n - 1``; a length past
    the table is held to the table's width."""
    from theanompi_tpu.ops.kernel_cases import ragged_lengths
    from theanompi_tpu.ops.pallas_paged import lane_steps

    if kind == "past_the_table":
        lengths = np.array([0, 10 ** 6, 23, 24], np.int32)
    else:
        lengths = ragged_lengths(kind, s, span, per_lane * span)
    lane, j, total = jax.jit(
        lambda ln: lane_steps(ln, span, per_lane))(lengths)
    assert lane.shape == j.shape == (s * per_lane,)
    assert lane.dtype == j.dtype == jnp.int32
    n = np.minimum(lengths // span + 1, per_lane)
    assert int(total) == n.sum()
    lane, j = np.asarray(lane)[:n.sum()], np.asarray(j)[:n.sum()]
    np.testing.assert_array_equal(lane, np.repeat(np.arange(s), n))
    np.testing.assert_array_equal(
        j, np.concatenate([np.arange(k) for k in n]))


def test_paged_kernels_refuse_a_list_of_another_shape():
    from theanompi_tpu.ops.pallas_paged import (
        lane_steps, mla_paged_decode, paged_decode_attention,
    )

    lengths = np.array([3, 9], np.int32)
    tables = np.ones((2, 4), np.int32)
    steps = lane_steps(lengths, 4, 3)  # a table of three columns
    with pytest.raises(ValueError, match=r"lane_steps\(lengths, 4, 4\)"):
        paged_decode_attention(
            np.zeros((2, 2, 8), np.float32), np.zeros((20, 128), np.float32),
            np.zeros((20, 128), np.float32), tables, lengths, block_size=4,
            steps=steps)
    with pytest.raises(ValueError, match=r"lane_steps\(lengths, 8, 2\)"):
        mla_paged_decode(
            np.zeros((2, 2, 16), np.float32), np.zeros((2, 2, 8), np.float32),
            np.zeros((20, 128), np.float32), tables, lengths, block_size=4,
            scale=1.0, group=2, steps=steps)


def test_decode_step_span_says_how_far_the_kernels_grid_engages(model):
    """``attn_steps`` is what the lanes hold (every lane one step at
    least), ``attn_steps_table`` what the table's width would walk."""
    from theanompi_tpu import observability as obs

    eng = PagedServingEngine(model, n_slots=3, max_len=64, block_size=8,
                             buckets=(8, 16, 64), paged_attn="pallas")
    sched = ContinuousBatchingScheduler(eng)
    t0 = sched.clock()
    sched.submit(Request(id="a", prompt=list(range(1, 20)), max_new_tokens=3))
    sched.submit(Request(id="b", prompt=[4, 5, 6], max_new_tokens=3))
    sched.run()
    spans = [s["args"] for s in obs.get_tracer().boundary_spans(t0)
             if s["name"] == "decode_step"]
    assert spans and all(a["attn_steps_table"] == 3 * 8 for a in spans)
    # the first decode tick: lengths 19 and 3 and an idle lane, in
    # blocks of 8: 3 + 1 + 1 steps
    assert spans[0]["attn_steps"] == 5
    assert all(3 <= a["attn_steps"] <= 6 for a in spans)


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_pallas_engine_decode_allclose_to_xla(model, kv_dtype):
    """Engine-level pin: the same decode tick through paged_attn='xla'
    and 'pallas' produces allclose logits and identical greedy tokens."""
    mk = lambda attn: PagedServingEngine(  # noqa: E731
        model, n_slots=2, max_len=64, buckets=(8, 16, 64), block_size=8,
        kv_dtype=kv_dtype, paged_attn=attn,
    )
    xla, pal = mk("xla"), mk("pallas")
    assert pal.paged_attn_effective == "pallas"  # supported on 1 device
    prompt = [7, 2, 9, 4, 4, 1, 0, 30, 2, 2, 11]
    assert xla.greedy(list(prompt), 10) == pal.greedy(list(prompt), 10)
    # raw logits, same state/tables through both programs
    sched = ContinuousBatchingScheduler(xla)
    sched.submit(Request(id="x", prompt=list(prompt), max_new_tokens=1))
    sched._admit_paged()
    state, _ = xla.prefill_chunks(
        model.params, sched.state,
        [{"tokens": prompt, "p0": 0, "table": sched.slots[0].blocks}],
    )
    toks = np.array([prompt[-1], 0], np.int32)
    lens = np.array([len(prompt) - 1, 0], np.int32)
    act = np.array([True, False])
    sx, lx = xla.decode_step_paged(
        model.params, jax.tree.map(jnp.array, state),
        toks, sched._tables, lens, act,
    )
    sp, lp = pal.decode_step_paged(
        model.params, jax.tree.map(jnp.array, state),
        toks, sched._tables, lens, act,
    )
    np.testing.assert_allclose(
        np.asarray(lx[0]), np.asarray(lp[0]), rtol=1e-4, atol=1e-4
    )


def test_pallas_refused_on_multidevice_mesh_auto_selects_xla():
    """A dp-sharded pool cannot run the single-shard kernel: demanding
    it is an error at engine build (never a quiet swap), and 'auto'
    selects the XLA gather by the same rule."""
    mesh = make_mesh()  # 8 fake devices
    if mesh.devices.size == 1:
        pytest.skip("single-device environment")
    model = TransformerLM(config=dict(CFG), mesh=mesh)
    with pytest.raises(ValueError, match="single-device pool"):
        PagedServingEngine(model, n_slots=2, max_len=64, block_size=8,
                           paged_attn="pallas")
    eng = PagedServingEngine(model, n_slots=2, max_len=64, block_size=8,
                             paged_attn="auto")
    assert eng.paged_attn_effective == "xla"
    out = eng.greedy([5, 3, 2], 4)
    assert len(out) == 4
