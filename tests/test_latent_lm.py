"""The ``block='latent_moe'`` model (``ops/latent_block.py``) and its
paged programs (``serving/latent.py``) against the benchmark's plain
reference (``benchmarks/references/xing4.0-29b-a4b.py``: an independent
``jax.numpy`` forward pass from the same equations), at a small size on
the CPU: ``d`` 64, 4 heads, ranks 24/16, nope/rope/v 16/8/16, 8 experts
top-2 and a shared one, 4 streams, one dense and two expert layers,
vocabulary 256; weights from the reference's ``make_weights`` (the
seeded initialisation of the configuration file), held in float32.

Tolerance ``TOL`` = 2e-5 on logits of magnitude 0.5: both sides are
float32 and differ in the order of their sums alone (absorbed against
expanded products, sorted groups against a loop over experts, a running
softmax against a whole one); what was read is 2e-7 to 1e-6.  A planted
fault (no stream mixing, no selection bias, no YaRN scale, no rotary
part) moves the logits by 1e-3 or more, a thousand times that.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.ops import attention as A
from theanompi_tpu.ops import latent_block as LB
from theanompi_tpu.ops import pallas_mhc, pallas_paged
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler, PagedServingEngine, Request,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5

# the published keys at the small size (what the reference reads)
PUB = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2,
    num_hidden_layers=3, first_k_dense_replace=1, vocab_size=256,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=4096,
                      type="yarn"),
)
ROPE = dict(theta=10000.0, factor=64.0, original_max_position=4096,
            beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
# the same sizes under the program's keys
PROGRAM = dict(
    block="latent_moe", seq_len=64, vocab_size=256, d_model=64, n_heads=4,
    n_layers=3, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, ffn_hidden=96, first_k_dense=1,
    moe_experts=8, moe_top_k=2, moe_hidden=32, n_shared_experts=1,
    route_scale=2.0, rms_norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20,
    hc_eps=1e-6, hc_clamp=30.0, rope=ROPE, init_weights=False,
    batch_size=2, n_synth_train=2, n_synth_val=1, comm_probe=False,
    print_freq=10000,
)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmarks", "references", "xing4.0-29b-a4b.py")
    spec = importlib.util.spec_from_file_location("ref_xing4", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["ref_xing4"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    one = jax.devices()[:1]
    m = TransformerLM(
        config=PROGRAM, mesh=TransformerLM.build_mesh(devices=one, config=PROGRAM))
    assert m.opt_state is None  # init_weights=False: shapes, no state
    weights = ref.make_weights(PUB, 3)
    assert jax.tree.structure(weights) == jax.tree.structure(m.params)
    assert ([a.shape for a in jax.tree.leaves(weights)]
            == [a.shape for a in jax.tree.leaves(m.params)])
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(weights))
    m.bf16_weights = weights
    m.params = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    return m


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _apply(model, tokens):
    y, _ = model.net.apply(model.params, model.net_state,
                           jnp.asarray(tokens)[None])
    return np.asarray(y[0])


def _reference(ref, model, tokens):
    rows, start = ref.logits(PUB, model.bf16_weights, tokens)
    assert start == 0
    return np.asarray(rows)[: len(tokens)]


# ---- (a) apply against the reference ----------------------------------------

def test_apply_matches_the_reference(ref, model):
    toks = _tokens(40)
    want = _reference(ref, model, toks)
    assert np.abs(want).max() > 0.1  # logits worth comparing
    np.testing.assert_allclose(_apply(model, toks), want, atol=TOL, rtol=0)


def _no_mixing(x, phi_t, ab, *, n, **_):
    """Coefficients of a plain residual: read the streams' mean, add the
    sublayer's output to every stream, mix nothing."""
    t = x.shape[0]
    row = jnp.concatenate([jnp.full((n,), 1.0 / n), jnp.ones((n,)),
                           jnp.eye(n).reshape(-1)])
    return jnp.tile(jnp.pad(row, (0, pallas_mhc.LANES - row.size)), (t, 1))


@pytest.mark.parametrize("fault", ["mixing", "selection_bias", "yarn_scale",
                                   "rotary"])
def test_a_part_left_out_of_the_program_is_caught(ref, model, monkeypatch, fault):
    """Acceptance: removing the hyper-connection mixing, the selection
    bias, the YaRN scale or the rotary part from the program makes (a)
    fail, by far."""
    toks = _tokens(40)
    want = _reference(ref, model, toks)
    if fault == "mixing":
        monkeypatch.setattr(pallas_mhc, "coefficients_xla", _no_mixing)
    elif fault == "selection_bias":
        from theanompi_tpu.parallel import moe

        real = moe.route
        monkeypatch.setattr(
            moe, "route", lambda *a, bias=None, **k: real(*a, bias=None, **k))
    elif fault == "yarn_scale":
        attn = model.net.layers[1].attn
        monkeypatch.setattr(attn, "scale", (attn.nope + attn.rope) ** -0.5)
    else:
        monkeypatch.setattr(LB, "rope_interleaved", lambda x, *a, **k: x)
    assert np.abs(_apply(model, toks) - want).max() > 50 * TOL


# ---- (b) paged prefill in chunks, then decode, at every position -------------

@pytest.mark.parametrize("paged_attn", ["xla", "pallas"])
def test_paged_prefill_and_decode_match_the_reference_everywhere(
        ref, model, paged_attn):
    """Two lanes of unequal length, the longer prompt over five blocks
    and three chunks: the logits of every prompt position (the verify
    form of the chunk program) and of every decoded position (through
    the latent cache, absorbed) against the reference's full pass."""
    eng = PagedServingEngine(
        model, n_slots=2, max_len=32, block_size=4, prefill_chunk=8,
        paged_attn=paged_attn, prefix_cache=False)
    state = eng.init_state()
    # 2 lanes x 8 blocks + the trash block, rows of 16 + 8 stored 128 wide
    assert [a.shape for a in state["kv"]] == [(17 * 4, 128)] * 3
    prompts = [_tokens(19, 1), _tokens(6, 2)]
    n_new = 5
    full = [p + _tokens(n_new, 7 + i) for i, p in enumerate(prompts)]
    want = [_reference(ref, model, seq) for seq in full]
    tables = np.zeros((2, eng.blocks_per_seq), np.int32)
    tables[0, :7] = np.arange(1, 8)      # 19 + 5 tokens: 6 blocks
    tables[1, :3] = np.arange(8, 11)
    fed = [0, 0]
    while any(fed[i] < len(prompts[i]) for i in range(2)):
        chunk = np.zeros((2, 8), np.int32)
        true_len = np.zeros((2,), np.int32)
        for i in range(2):
            part = prompts[i][fed[i]:fed[i] + 8]
            chunk[i, :len(part)] = part
            true_len[i] = len(part)
        state, logits = eng.verify_chunks(
            model.params, state, chunk, tables, np.array(fed), true_len,
            true_len > 0)
        for i in range(2):
            got = np.asarray(logits[i, :true_len[i]])
            np.testing.assert_allclose(
                got, want[i][fed[i]:fed[i] + true_len[i]], atol=TOL, rtol=0)
            fed[i] += int(true_len[i])
    lengths = np.array([len(p) for p in prompts], np.int32)
    for j in range(n_new):
        tokens = np.array([full[i][lengths[i]] for i in range(2)], np.int32)
        state, logits = eng.decode_step_paged(
            model.params, state, tokens, tables, lengths, np.ones((2,), bool))
        for i in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[i]), want[i][lengths[i]], atol=TOL, rtol=0)
        lengths += 1
    hit, load, pairs = np.asarray(eng.last_counters)
    assert 2 <= hit <= 2 * 4 and 1 <= load <= 2  # two expert layers, two tokens
    assert pairs == 2 * 2 * 2  # every pick of both tokens: all experts held


def test_served_tokens_are_the_references_and_the_spans_carry_the_counters(
        ref, model):
    """Through the scheduler: chunked prefill by the narrow program, then
    decoding; greedy tokens equal the reference's at every step (gap 0),
    and the boundary spans of the calls hold the experts' counters."""
    from theanompi_tpu import observability as obs

    eng = PagedServingEngine(
        model, n_slots=3, max_len=48, block_size=4, prefill_chunk=8,
        paged_attn="pallas", prefill_rows=2)
    sched = ContinuousBatchingScheduler(eng)
    assert sched.stats["latent_rows_capacity"] == (eng.n_blocks - 1) * 4
    t0 = sched.clock()
    prompts = [_tokens(n, 20 + n) for n in (19, 5, 11)]
    for i, p in enumerate(prompts):
        sched.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=6))
    sched.step()
    assert sched.stats["latent_rows_resident"] > 0
    out = sched.run()
    for i, p in enumerate(prompts):
        gaps = ref.served_gaps(PUB, model.bf16_weights, p, out[f"r{i}"])
        assert len(gaps) == 6 and max(gaps) <= TOL
    spans = [s for s in obs.get_tracer().boundary_spans(t0)
             if "tokens_routed" in s["args"]]
    assert {s["name"] for s in spans} == {"decode_step", "prefill_chunk_dispatch"}
    for s in spans:
        a = s["args"]
        assert 1 <= a["expert_load_max"] <= a["tokens_routed"]
        assert a["expert_load_max"] <= a["experts_hit"] <= 2 * 8
    # the decode kernel's grid: 48 positions in blocks of 4 are one
    # group of 12 a lane, so the lists are as long as the table is wide
    decode = [s["args"] for s in spans if s["name"] == "decode_step"]
    assert all(a["attn_steps"] == a["attn_steps_table"] == 3 for a in decode)
    prefill = [s["args"] for s in spans if s["name"] == "prefill_chunk_dispatch"]
    assert sum(a["tokens_routed"] for a in prefill) == 19 + 5 + 11
    assert all(a["tokens_routed"] == a["useful_tokens"] for a in prefill)


# ---- (c) the absorbed path against the expanded one --------------------------

def test_absorbed_decode_equals_expanded_attention():
    attn = LB.LatentAttention(64, 4, 24, 16, 16, 8, 16, 1e-6, ROPE)
    ap = attn.init(jax.random.PRNGKey(0), jnp.float32)
    ap = {k: 5.0 * v if v.ndim == 2 else v for k, v in ap.items()}
    t = 13
    hid = jax.random.normal(jax.random.PRNGKey(1), (t, 64))
    q_nope, q_rope, row = attn.project(ap, hid, jnp.arange(t))
    expanded = LB.causal_attend(attn, 1)(ap, q_nope, q_rope, row)[-1]
    # the same rows as a paged pool: blocks of 4 behind a shuffled table
    bs, table = 4, np.array([[3, 1, 4, 2]], np.int32)
    pool = jnp.zeros((5 * bs, 128)).at[
        (table[0][np.arange(t) // bs] * bs + np.arange(t) % bs), :24].set(row)
    o_lat = pallas_paged.mla_decode_xla(
        attn.absorb(ap, q_nope[-1:]), q_rope[-1:], pool, jnp.asarray(table),
        jnp.array([t - 1]), block_size=bs, scale=attn.scale)
    absorbed = attn.unabsorb(ap, o_lat, jnp.float32)[0]
    assert float(jnp.abs(expanded).max()) > 0.05
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("kind", ["edges", "full", "one_long"])
def test_latent_decode_kernel_walks_the_lanes_resident_groups(kind):
    """``mla_paged_decode``'s grid is the list of resident (lane, block
    group) pairs; at its edges (an idle lane, lanes a row short of, at
    and past a group's boundary, the table's last column, every lane
    full, one long lane among short ones) it equals the XLA form.  The
    table's 7 columns make a ragged last group of 3."""
    from theanompi_tpu.ops.kernel_cases import ragged_lengths

    s, h, c, r, bs, nt, nb, g = 6, 4, 16, 8, 4, 7, 20, 3
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q_lat = jax.random.normal(keys[0], (s, h, c))
    q_rope = jax.random.normal(keys[1], (s, h, r))
    pool = jax.random.normal(keys[2], (nb * bs, 128))
    tables = jax.random.randint(keys[3], (s, nt), 1, nb, jnp.int32)
    lengths = jnp.asarray(ragged_lengths(kind, s, g * bs, nt * bs))
    kw = dict(block_size=bs, scale=(c + r) ** -0.5)
    got = pallas_paged.mla_paged_decode(
        q_lat, q_rope, pool, tables, lengths, group=g, **kw)
    want = pallas_paged.mla_decode_xla(
        q_lat, q_rope, pool, tables, lengths, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---- (f) the write-back matrix is doubly stochastic --------------------------

@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_h_res_is_doubly_stochastic_at_the_clamps_extremes(form):
    n, d, t = 4, 64, 6
    m = 2 * n + n * n
    x = jax.random.normal(jax.random.PRNGKey(0), (t, n * d))
    phi = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (m, n * d))
    # biases far past the clamp of 30 either way: +90 on a cyclic
    # permutation, -90 off it (the state's own part, alpha x 2.4, rides on top)
    perm = jnp.roll(jnp.eye(n), 1, axis=1)
    bias = jnp.zeros((m,)).at[2 * n:].set((180.0 * perm - 90.0).reshape(-1))
    phi_t, ab = pallas_mhc.pack_coefficients(phi, jnp.full((3,), 0.1), bias, n)
    kw = dict(n=n, eps=1e-6, iters=20, clamp=30.0)
    if form == "xla":
        coef = pallas_mhc.coefficients_xla(x, phi_t, ab, **kw)
    else:
        _, coef = pallas_mhc.mhc_pre(x, phi_t, ab, **kw)
    res = np.asarray(coef[:, 2 * n:m]).reshape(t, n, n)
    assert np.isfinite(res).all() and (res >= 0).all()
    np.testing.assert_allclose(res.sum(axis=2), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-4)
    assert res.max() > 0.9 and res.min() < 1e-6  # the clamp was reached


# ---- (g) YaRN, against numbers written here ----------------------------------

def test_yarn_frequencies_and_softmax_scale():
    """dim 64, theta 10,000, factor 64, 4,096 original positions, 32 and 1
    rotations: the correction dimensions are 10.47 and 22.51, so the
    frequencies 0..10 are kept, 23..31 are divided by 64, and the ramp
    runs over 13 steps between; m(64, 1) = 0.1 ln 64 + 1 = 1.4158883."""
    inv = np.asarray(A.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(inv[10], 10000.0 ** (-20 / 64), rtol=1e-6)
    np.testing.assert_allclose(inv[31], 10000.0 ** (-62 / 64) / 64, rtol=1e-6)
    # halfway up the ramp (index 16.5 lies between): 6/13 of the way at 16
    want16 = plain[16] / 64 * (6 / 13) + plain[16] * (7 / 13)
    np.testing.assert_allclose(inv[16], want16, rtol=1e-5)
    assert A.yarn_mscale(64.0, 1.0) == pytest.approx(1.4158883, rel=1e-6)
    attn = LB.LatentAttention(3584, 32, 768, 512, 128, 64, 128, 1e-6, ROPE)
    assert attn.rope_scale == pytest.approx(1.0)
    assert attn.scale == pytest.approx(192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)
    assert attn.scale == pytest.approx(0.1446789, rel=1e-5)
    # no scaling: plain rotary frequencies and the plain scale
    np.testing.assert_allclose(np.asarray(A.yarn_inv_freq(64)), plain, rtol=1e-6)
    assert LB.LatentAttention(64, 4, 24, 16, 16, 8, 16, 1e-6, {}).scale == (
        pytest.approx(24 ** -0.5))


def test_rotary_pairs_are_interleaved_and_norm_preserving():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 8))
    inv = A.yarn_inv_freq(8)
    pos = jnp.arange(5) + 3
    y = np.asarray(A.rope_interleaved(x, pos, inv))
    # pair i of the input is (x[2i], x[2i+1]); it lands at (i, 4 + i)
    ang = np.asarray(pos)[:, None] * np.asarray(inv)
    xe, xo = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    np.testing.assert_allclose(
        y[..., :4], xe * np.cos(ang)[:, None] - xo * np.sin(ang)[:, None], atol=1e-6)
    np.testing.assert_allclose(
        y[..., 4:], xe * np.sin(ang)[:, None] + xo * np.cos(ang)[:, None], atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # a dot product of two rotated vectors depends on the distance alone
    a, b = np.asarray(x)[0, 0], np.asarray(x)[1, 1]
    def rot(v, p):
        return np.asarray(A.rope_interleaved(jnp.asarray(v)[None], jnp.array([p]), inv))[0]
    assert np.dot(rot(a, 7), rot(b, 4)) == pytest.approx(
        np.dot(rot(a, 107), rot(b, 104)), abs=1e-4)


# ---- the engine's guards -----------------------------------------------------

def test_a_latent_pool_holds_the_compute_dtype_and_any_length(model):
    with pytest.raises(ValueError, match="compute dtype"):
        PagedServingEngine(model, kv_dtype="int8")
    eng = PagedServingEngine(model, n_slots=2, max_len=4096, block_size=32)
    assert eng.max_len == 4096  # no position table caps it
    # a block: 32 rows x 3 layers x 128 stored lanes x 4 bytes
    assert eng.kv_block_bytes() == 32 * 3 * 128 * 4
