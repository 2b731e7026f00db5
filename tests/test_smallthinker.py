"""The ``latent_moe`` block family with grouped-query layers
(``ops/latent_block.py`` ``GroupedAttention``, ``serving/latent.py``'s
global pools and window rings, ``ops/pallas_paged.py``'s grouped and
windowed decode) against the benchmark's plain reference
(``benchmarks/references/smallthinker-21b-a3b.py``: an independent
``jax.numpy`` forward pass, keys and values repeated to every head, the
window a mask), at a small size on the CPU: ``d`` 64, 14 query heads
over 2 key/value heads of 8, four layers (global NoPE, then three
rotary window layers of 16: one period of the published pattern), 8
softmax-routed ReGLU experts top-3 routed on the attention's input, one
stream; vocabulary 256.  Weights from the reference's ``make_weights``,
held in float32.

Tolerance ``TOL`` = 5e-5 on logits of magnitude 0.5: both sides are
float32 and differ in the order of their sums alone (a span loop with a
running softmax against whole rows, sorted groups against a loop over
experts).  A planted fault (the window dropped, the router fed the
feed-forward's input) moves the logits by 1e-3 or more; int8 operands
by 1e-2.
"""

import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kimi_linear import _serve_logits
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.ops import latent_block as LB
from theanompi_tpu.ops.pallas_paged import (
    lane_steps, paged_decode_attention, paged_decode_xla,
)
from theanompi_tpu.parallel.moe import MoeMlp
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler, PagedServingEngine, Request,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5
W = 16  # the window

# the published keys at the small size (what the reference reads)
PUB = dict(
    hidden_size=64, num_attention_heads=14, num_key_value_heads=2, head_dim=8,
    num_hidden_layers=4, rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1],
    sliding_window_size=W, rope_theta=10000, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, moe_ffn_hidden_size=32, vocab_size=256,
    rms_norm_eps=1e-6,
)
# the same sizes under the program's keys
PROGRAM = dict(
    block="latent_moe", mixer="gqa", seq_len=64, vocab_size=256, d_model=64,
    n_heads=14, n_kv_heads=2, head_dim=8, n_layers=4, window=W,
    window_layers=[2, 3, 4], rope_layers=[2, 3, 4], rope_layout="half",
    rope={"theta": 10000}, first_k_dense=0, moe_experts=8, moe_top_k=3,
    moe_hidden=32, n_shared_experts=0, moe_scoring="softmax",
    moe_activation="relu", moe_route_from="attn", rms_norm_eps=1e-6,
    hc_mult=1, init_weights=False, batch_size=2, n_synth_train=2,
    n_synth_val=1, comm_probe=False, print_freq=10000,
)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmarks", "references",
                        "smallthinker-21b-a3b.py")
    spec = importlib.util.spec_from_file_location("ref_smallthinker", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["ref_smallthinker"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    one = jax.devices()[:1]
    m = TransformerLM(
        config=PROGRAM, mesh=TransformerLM.build_mesh(devices=one, config=PROGRAM))
    blocks = m.net.layers[1:-2]
    assert [(b.kind, b.attn.window, b.attn.rotary) for b in blocks] == [
        ("gqa", None, False)] + [("gqa", W, True)] * 3
    weights = ref.make_weights(PUB, 3)
    assert jax.tree.structure(weights) == jax.tree.structure(m.params)
    assert ([a.shape for a in jax.tree.leaves(weights)]
            == [a.shape for a in jax.tree.leaves(m.params)])
    # softmax routing: no selection bias; one stream: no hyper-connections
    assert "route_bias" not in m.params[1]["moe"]
    assert "hc_attn" not in m.params[1]
    m.bf16_weights = weights
    m.params = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    return m


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _reference(ref, model, tokens, precision="float32"):
    rows, start = ref.logits(PUB, model.bf16_weights, tokens, precision)
    assert start == 0
    return np.asarray(rows)[: len(tokens)]


def _apply(model, tokens):
    y, _ = model.net.apply(model.params, model.net_state,
                           jnp.asarray(tokens)[None])
    return np.asarray(y[0])


# ---- (a) the model's apply against the reference ------------------------------

def test_apply_matches_the_reference_past_the_window(ref, model):
    """60 tokens through a window of 16: the window layers see the last
    16 positions, the global one all of them."""
    toks = _tokens(60)
    want = _reference(ref, model, toks)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(_apply(model, toks), want, atol=TOL, rtol=0)
    # the control one precision below is told apart by far
    low = _reference(ref, model, toks, "int8")
    assert np.abs(low - want).max() > 100 * TOL


@pytest.mark.parametrize("fault", ["no_window", "router_on_ffn_input",
                                   "interleaved_rotary"])
def test_a_part_left_out_of_the_program_is_caught(ref, model, monkeypatch,
                                                  fault):
    """The window dropped, the router fed the feed-forward's normed input
    (where the experts compute) instead of the attention's, or rotary
    pairs taken interleaved: each moves the logits by far."""
    toks = _tokens(40, 1)
    want = _reference(ref, model, toks)
    blocks = model.net.layers[1:-2]
    if fault == "no_window":
        for b in blocks:
            monkeypatch.setattr(b.attn, "window", None)
    elif fault == "router_on_ffn_input":
        for b in blocks:
            monkeypatch.setattr(b, "route_from_attn", False)
    else:
        for b in blocks:
            monkeypatch.setattr(b.attn, "rope_layout", "interleaved")
    assert np.abs(_apply(model, toks) - want).max() > 20 * TOL


# ---- (b) prefill in chunks, then decode, through pools and rings -------------

def _engine(model, **kw):
    args = dict(n_slots=3, max_len=64, block_size=8, prefill_chunk=16,
                prefill_rows=2, kv_dtype="fp32", paged_attn="xla")
    args.update(kw)
    return PagedServingEngine(model, **args)


def test_state_sizes_follow_the_window_and_the_chunk(model):
    """A window layer's ring is W + C - 1 rows in whole blocks (16 + 16
    - 1 = 31: four blocks of 8), capped at a lane's table; the global
    layer's keys and values are a block's bytes (two arrays, 16 numbers
    a row stored 128 wide); prefix reuse is off and says why."""
    eng = _engine(model, prefix_cache=True)
    p = eng.programs
    assert (p.n_full, p.n_window, p.window) == (1, 3, W)
    assert (p.ring_blocks, p.ring_rows) == (4, 32)
    assert p.lane_state == "window rings" and not eng.prefix_cache_enabled
    assert eng.kv_block_bytes() == 2 * 8 * 128 * 4
    assert p.ring_bytes() == 3 * 2 * 3 * 32 * 128 * 4
    state = eng.init_state()
    assert [a.shape for a in state["k"]] == [(eng.n_blocks * 8, 128)]
    assert [a.shape for a in state["ring_k"]] == [(3 * 32, 128)] * 3
    assert state["kv"] == []
    # a chunk as long as the table: the ring is the table
    assert _engine(model, prefill_chunk=64).programs.ring_blocks == 8


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_then_decode_matches_the_full_forward_pass(
        ref, model, impl):
    """Four requests over three lanes: a prompt of 45 tokens in three
    chunks whose decode runs to 55 positions (the ring of 32 rows wraps
    twice, and a window layer's first block is partial), a lane reused
    by a second request (its ring not cleared: the window's mask by
    position keeps the first request's rows out), a lane idle until
    tick 5.  Logits of every produced position against the reference's
    forward pass of prompt + greedy tokens.  ``pallas``: the decode
    kernel interpreted."""
    eng = _engine(model, paged_attn=impl)
    requests = [
        (0, _tokens(45, 1), 10, 0),
        (1, _tokens(9, 2), 12, 0),
        (0, _tokens(20, 3), 6, 1),   # lane 0 again once it is free
        (2, _tokens(33, 4), 4, 5),   # an idle lane until tick 5
    ]
    got = _serve_logits(eng, model, requests)
    for i, (_, prompt, n_new, _) in enumerate(requests):
        served = [int(np.argmax(row)) for row in got[i]]
        want = _reference(ref, model, prompt + served[:-1])[len(prompt) - 1:]
        assert want.shape == got[i].shape
        np.testing.assert_allclose(got[i], want, atol=TOL, rtol=0,
                                   err_msg=f"request {i}")


def test_a_ring_shorter_than_window_plus_chunk_fails_the_comparison(
        ref, model, monkeypatch):
    """The planted fault the tolerance must catch: rings of W rows
    alone, so a chunk's last rows overwrite keys its first queries
    still need."""
    eng = _engine(model)
    monkeypatch.setattr(eng.programs, "ring_blocks", W // 8)
    monkeypatch.setattr(eng.programs, "ring_rows", W)
    requests = [(0, _tokens(45, 1), 3, 0)]
    got = _serve_logits(eng, model, requests)[0]
    prompt = requests[0][1]
    served = [int(np.argmax(row)) for row in got]
    want = _reference(ref, model, prompt + served[:-1])[len(prompt) - 1:]
    assert np.abs(got - want).max() > 20 * TOL


def test_scheduler_serves_with_prefix_reuse_off_and_counts_rows(ref, model):
    from theanompi_tpu import observability as obs

    eng = _engine(model, prefix_cache=True)
    sched = ContinuousBatchingScheduler(eng)
    assert sched.prefix is None
    assert sched.stats["prefix_reuse"] == "off: window rings"
    assert sched.stats["window_ring_bytes"] == eng.programs.ring_bytes()
    assert "recurrent_state_bytes" not in sched.stats
    shared = _tokens(24, 9)
    reqs = [Request(id=f"g{i}", prompt=shared + _tokens(5 + 4 * i, 10 + i),
                    max_new_tokens=5) for i in range(4)]
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    assert sched.stats["prefix_hits"] == 0
    for r in reqs:
        want = _reference(ref, model, r.prompt + done[r.id][:-1])
        assert done[r.id] == np.argmax(
            want[len(r.prompt) - 1:], axis=-1).tolist(), r.id
    spans = obs.get_tracer().boundary_spans(t0)
    decode = [s["args"] for s in spans if s["name"] == "decode_step"]
    prefill = [s["args"] for s in spans
               if s["name"] == "prefill_chunk_dispatch"]
    assert decode and prefill
    for a in decode:
        # one global layer, three window layers; a lane reads its
        # length + 1 rows, a window at most W
        assert a["kv_rows_full"] >= a["active"]
        assert a["kv_rows_window"] <= 3 * W * a["active"]
        assert a["kv_rows_window"] <= 3 * a["kv_rows_full"]
    assert sum(a["kv_rows_window"] for a in prefill) > 0
    with pytest.raises(ValueError, match="window rings"):
        ContinuousBatchingScheduler(eng, spec_k=2, draft_engine=eng)


def test_decode_step_span_counts_the_grouped_kernels_groups(ref, model,
                                                             monkeypatch):
    """A table twice the grouped kernel's group wide (at 32 a group,
    blocks of one row under 64 columns and a ring of 31 blocks): the
    kernel attends to ``GQA_GROUP``
    blocks a grid step, two steps a full lane.  ``attn_steps`` is the
    sum over lanes of ``length // span + 1`` at the lengths the decode
    call is handed, ``attn_steps_table`` lanes × 2; the tokens served
    through the interpreted kernel are the reference's (a lane past the
    first span takes two steps, its window's first position inside the
    first)."""
    from theanompi_tpu import observability as obs
    from theanompi_tpu.ops.pallas_paged import GQA_GROUP

    bs = 64 // (2 * GQA_GROUP)
    eng = _engine(model, block_size=bs, paged_attn="pallas")
    assert eng.blocks_per_seq == 2 * GQA_GROUP
    span = GQA_GROUP * bs
    assert (eng.programs.attn_span, eng.programs.attn_steps) == (span, 2)
    handed = []
    decode = eng.decode_step_paged

    def record(params, state, tokens, tables, lengths, active):
        handed.append(np.array(lengths))
        return decode(params, state, tokens, tables, lengths, active)

    monkeypatch.setattr(eng, "decode_step_paged", record)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [Request(id="long", prompt=_tokens(40, 21), max_new_tokens=4),
            Request(id="short", prompt=_tokens(5, 22), max_new_tokens=4)]
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    for r in reqs:
        want = _reference(ref, model, r.prompt + done[r.id][:-1])
        assert done[r.id] == np.argmax(
            want[len(r.prompt) - 1:], axis=-1).tolist(), r.id
    spans = [s["args"] for s in obs.get_tracer().boundary_spans(t0)
             if s["name"] == "decode_step"]
    assert len(spans) == len(handed) > 0
    for a, lengths in zip(spans, handed):
        assert a["attn_steps"] == int((lengths // span + 1).sum())
        assert a["attn_steps_table"] == 3 * 2
    assert max(a["attn_steps"] for a in spans) >= 4  # a lane of two steps


@pytest.mark.parametrize("lengths,full,window", [
    ([0], 1, 3), ([W - 2], W - 1, 3 * (W - 1)), ([W - 1], W, 3 * W),
    ([W, 40], W + 1 + 41, 3 * 2 * W)])
def test_decode_rows_are_what_each_reach_reads(model, lengths, full, window):
    """``kv_rows_full`` counts ``length + 1`` a lane in the global layer,
    ``kv_rows_window`` ``min(length + 1, W)`` in each of the three
    window layers."""
    rows = _engine(model).programs.decode_rows(np.asarray(lengths))
    assert rows == {"kv_rows_full": full, "kv_rows_window": window}


# ---- (c) the decode kernel: groups, windows, rings ----------------------------

def _pools(rng, n_rows, kv, hd):
    width = -(-kv * hd // 128) * 128
    k = np.zeros((n_rows, width), np.float32)
    v = np.zeros((n_rows, width), np.float32)
    k[:, :kv * hd] = rng.randn(n_rows, kv * hd)
    v[:, :kv * hd] = rng.randn(n_rows, kv * hd)
    return k, v


@pytest.mark.parametrize("group,window,ring", [
    (7, None, False), (7, 12, False), (7, 12, True), (1, 12, True),
    (1, 12, False)])
def test_decode_kernel_matches_its_oracle(group, window, ring):
    """Interpret mode against ``paged_decode_xla``: lanes at 0, W - 1, W,
    W + 1 and the table's last position; with ``ring``, every lane's
    table is its ring's column map (3 blocks of 4 rows, so the longer
    lanes wrap it)."""
    rng = np.random.RandomState(group + (window or 0) + ring)
    kv, hd, bs, nt, r = 2, 8, 4, 8, 4
    s, h = 5, kv * group
    w = window or 12
    lengths = np.array([0, w - 1, w, w + 1, nt * bs - 1], np.int32)
    if ring:
        tables = (1 + np.arange(s)[:, None] * r
                  + np.arange(nt)[None, :] % r).astype(np.int32)
        nb = 1 + s * r
    else:
        nb = 40
        tables = rng.randint(1, nb, (s, nt)).astype(np.int32)
    q = rng.randn(s, h, hd).astype(np.float32)
    k, v = _pools(rng, nb * bs, kv, hd)
    kw = dict(block_size=bs, kv_heads=kv, window=window)
    got = np.asarray(paged_decode_attention(q, k, v, tables, lengths, **kw))
    want = np.asarray(paged_decode_xla(q, k, v, tables, lengths, **kw))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_a_head_a_key_value_head_without_a_window_is_the_ungrouped_kernel(
        kv_dtype):
    """Group 1 without a window takes the ungrouped kernel's arithmetic
    as it was: told ``kv_heads`` = heads, the outputs equal the call
    that says nothing of groups, to the last bit."""
    from theanompi_tpu.parallel.quantize import quantize_blocks

    rng = np.random.RandomState(3)
    s, h, hd, bs, nb, nt = 4, 5, 8, 4, 12, 5
    q = rng.randn(s, h, hd).astype(np.float32)
    tables = rng.randint(1, nb, (s, nt)).astype(np.int32)
    lengths = np.array([0, 3, 4, 19], np.int32)
    kw = {}
    if kv_dtype == "int8":
        (kq, ks), (vq, vs) = (quantize_blocks(jnp.asarray(
            rng.randn(nb * bs, h, hd).astype(np.float32))) for _ in range(2))
        pools = [np.pad(np.asarray(a).reshape(nb * bs, -1), ((0, 0), (0, 88)))
                 for a in (kq, vq)]
        kw = dict(k_scale=np.asarray(ks), v_scale=np.asarray(vs))
    else:
        pools = _pools(rng, nb * bs, h, hd)
    plain = paged_decode_attention(q, *pools, tables, lengths, block_size=bs,
                                   **kw)
    told = paged_decode_attention(q, *pools, tables, lengths, block_size=bs,
                                  kv_heads=h, **kw)
    assert np.array_equal(np.asarray(plain), np.asarray(told))
    if kv_dtype == "int8":
        with pytest.raises(ValueError, match="int8 pool"):
            paged_decode_attention(q, *pools, tables, lengths, block_size=bs,
                                   window=8, **kw)


@pytest.mark.parametrize("lengths,starts,span", [
    ([0, 11, 12, 13, 31], [0, 0, 1, 2, 20], 4),
    ([5, 40, 63], [0, 25, 48], 8)])
def test_lane_steps_begin_at_each_lanes_start(lengths, starts, span):
    """With starts, a lane's steps run from ``start // span`` to
    ``length // span``, lanes in order."""
    per_lane = 8
    lane, j, total = lane_steps(np.asarray(lengths, np.int32), span, per_lane,
                                np.asarray(starts, np.int32))
    first = np.asarray(starts) // span
    n = np.minimum(np.asarray(lengths) // span + 1, per_lane) - first
    assert int(total) == n.sum()
    lane, j = np.asarray(lane)[:n.sum()], np.asarray(j)[:n.sum()]
    np.testing.assert_array_equal(lane, np.repeat(np.arange(len(n)), n))
    np.testing.assert_array_equal(
        j, np.concatenate([np.arange(a, a + k) for a, k in zip(first, n)]))


# ---- (d) ReGLU experts and the pre-attention router --------------------------

def _plain_moe(params, x, route_x, top_k, act):
    """A loop over tokens and their chosen experts: softmax over every
    expert, the top ``top_k`` renormalised."""
    p = jax.nn.softmax(np.asarray(route_x) @ np.asarray(params["wg"]), axis=-1)
    out = np.zeros_like(np.asarray(x))
    for t in range(x.shape[0]):
        idx = np.argsort(-np.asarray(p[t]))[:top_k]
        w = np.asarray(p[t])[idx] / np.asarray(p[t])[idx].sum()
        for e, we in zip(idx, w):
            g = x[t] @ params["w_gate"][e]
            u = x[t] @ params["w_up"][e]
            out[t] += we * np.asarray((act(g) * u) @ params["w_down"][e])
    return out


@pytest.mark.parametrize("activation", ["relu", "silu"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_gated_experts_and_a_router_fed_other_rows_match_a_plain_loop(
        activation, impl):
    moe = MoeMlp(8, 32, top_k=3, ep_axis=None, emit_aux=False, gated=True,
                 activation=activation)
    params, _, _ = moe.init(jax.random.PRNGKey(0), (16,))
    kx, kr = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (12, 16))
    other = jax.random.normal(kr, (12, 16))
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    y, counts, _ = moe.forward(params, x, route_x=other, impl=impl)
    want = _plain_moe(params, x, other, 3, act)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=1e-5)
    assert int(counts.sum()) == 12 * 3
    # the rows routed on decide the result: routing x itself differs
    own, _, _ = moe.forward(params, x, impl=impl)
    assert np.abs(np.asarray(own) - want).max() > 1e-2
