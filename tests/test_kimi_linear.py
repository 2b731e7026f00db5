"""The ``latent_moe`` block family with recurrent layers (``ops/kda.py``,
``serving/latent.py``) against the benchmark's plain reference
(``benchmarks/references/kimi-linear-48b-a3b.py``: an independent
``jax.numpy`` forward pass, the recurrence a scan over tokens), at a
small size on the CPU: ``d`` 64, 4 heads, linear layers of head
dimension 16 and a convolution of 4, latent layers of rank 16 and
nope/rope/v 16/8/16 without positions, 16 routed experts top-4 of which
the first 8 are held, a shared one, one stream; five layers (linear,
linear, linear, latent, linear), the first dense; vocabulary 256.
Weights from the reference's ``make_weights`` (the seeded initialisation
of the configuration file: decays of 0.88 to 0.999 a token), held in
float32.

Tolerance ``TOL`` = 5e-5 on logits of magnitude 0.5: both sides are
float32 and differ in the order of their sums alone (the chunked
recurrence's triangular inverse against a scan over tokens, absorbed
against expanded products, sorted groups against a loop over experts);
what was read is 3e-7 to 4e-6.  A planted fault (a state dropped at a
chunk boundary or at the hand-over to decode, a lane not cleared, the
convolution's inputs not carried, an idle lane decayed) moves the logits
by 1e-3 or more; int8 operands by 1e-2.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.ops import kda
from theanompi_tpu.parallel.moe import MoeMlp
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler, PagedServingEngine, Request,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5

LINEAR = dict(head_dim=16, num_heads=4, short_conv_kernel_size=4,
              kda_layers=[1, 2, 3, 5], full_attn_layers=[4])
# the published keys at the small size (what the reference reads)
PUB = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    linear_attn_config=LINEAR, intermediate_size=96, moe_intermediate_size=32,
    num_experts=8, published=dict(num_experts=16), num_experts_per_token=4,
    num_shared_experts=1, routed_scaling_factor=2.446, num_hidden_layers=5,
    first_k_dense_replace=1, vocab_size=256, rms_norm_eps=1e-5,
)
# the same sizes under the program's keys
PROGRAM = dict(
    block="latent_moe", seq_len=96, vocab_size=256, d_model=64, n_heads=4,
    n_layers=5, q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mla_nope=True,
    kda_layers=[1, 2, 3, 5], kda_head_dim=16, kda_conv=4, ffn_hidden=96,
    first_k_dense=1, moe_experts=16, moe_experts_held=8, moe_top_k=4,
    moe_hidden=32, n_shared_experts=1, route_scale=2.446, rms_norm_eps=1e-5,
    hc_mult=1, init_weights=False, batch_size=2, n_synth_train=2,
    n_synth_val=1, comm_probe=False, print_freq=10000,
)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmarks", "references",
                        "kimi-linear-48b-a3b.py")
    spec = importlib.util.spec_from_file_location("ref_kimi", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["ref_kimi"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    one = jax.devices()[:1]
    m = TransformerLM(
        config=PROGRAM, mesh=TransformerLM.build_mesh(devices=one, config=PROGRAM))
    assert m.opt_state is None
    assert [b.kind for b in m.net.layers[1:-2]] == [
        "kda", "kda", "kda", "mla", "kda"]
    weights = ref.make_weights(PUB, 3)
    assert jax.tree.structure(weights) == jax.tree.structure(m.params)
    assert ([a.shape for a in jax.tree.leaves(weights)]
            == [a.shape for a in jax.tree.leaves(m.params)])
    # one stream: no hyper-connection parameters at all; the held share
    assert "hc_attn" not in m.params[1] and "wq" in m.params[4]["attn"]
    assert m.params[2]["moe"]["w_gate"].shape[0] == 8
    assert m.params[2]["moe"]["wg"].shape[1] == 16
    m.bf16_weights = weights
    m.params = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    return m


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _reference(ref, model, tokens, precision="float32"):
    rows, start = ref.logits(PUB, model.bf16_weights, tokens, precision)
    assert start == 0
    return np.asarray(rows)[: len(tokens)]


# ---- (a) the layer's two forms against the reference's scan -------------------

@pytest.mark.parametrize("form", ["scan", "chunk", "kernel"])
def test_linear_layer_forms_match_the_references_scan(ref, model, form):
    """One linear layer over 150 tokens (two whole chunks of 64 and a
    padded third): the program's projections and convolution, then the
    recurrence token by token, chunked in XLA, or as the interpreted
    kernel, against ``_delta_attention`` of the reference."""
    mixer = model.net.layers[1].kda
    mp = model.params[1]["kda"]
    t = 150
    hid = jax.random.normal(jax.random.PRNGKey(5), (t, 64))
    want = ref._delta_attention(mp, hid, (4, 16, 4, 1e-5), ref._mm("float32"))
    u, g, beta = mixer.project(mp, hid)
    past = jnp.zeros((1, 3, u.shape[-1]))
    q, k, v = mixer.convolve(mp, past, u[None])
    pad = -t % 64

    def fit(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    s0 = jnp.zeros((1, 4, 16, 16))
    run = {"scan": kda.kda_scan_xla, "chunk": kda.kda_chunk_xla,
           "kernel": kda.kda_chunk_prefill}[form]
    o, s = run(s0, fit(q), fit(k), fit(v), fit(g[None]), fit(beta[None]))
    got = mixer.out(mp, o[0, :t], hid)
    assert float(jnp.abs(want).max()) > 0.01
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # padding (g = 0, beta = 0) left the state where the last token put it
    _, s_exact = kda.kda_scan_xla(s0, q, k, v, g[None], beta[None])
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_exact), atol=2e-6)


# ---- (b) the model's apply against the reference ------------------------------

def test_apply_with_mixed_layer_kinds_matches_the_reference(ref, model):
    toks = _tokens(70)
    want = _reference(ref, model, toks)
    assert np.abs(want).max() > 0.1
    y, _ = model.net.apply(model.params, model.net_state,
                           jnp.asarray(toks)[None])
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=TOL, rtol=0)
    # the control one precision below is told apart by far
    low = _reference(ref, model, toks, "int8")
    assert np.abs(low - want).max() > 100 * TOL


# ---- (c) prefill in chunks, then decode, through pool and state ---------------

def _engine(model, **kw):
    args = dict(n_slots=3, max_len=96, block_size=8, prefill_chunk=16,
                prefill_rows=2, kv_dtype="fp32", paged_attn="xla")
    args.update(kw)
    return PagedServingEngine(model, **args)


def _serve_logits(eng, model, requests):
    """Drive the engine by hand: ``requests`` is a list of (lane, prompt,
    n_new, start tick); returns {index: logits (n_new, V)} of every
    position that produced a token, greedy."""
    state = eng.init_state()
    params = model.params
    bs = eng.block_size
    tables = np.zeros((eng.n_slots, eng.blocks_per_seq), np.int32)
    lengths = np.zeros((eng.n_slots,), np.int32)
    tokens = np.zeros((eng.n_slots,), np.int32)
    free = list(range(1, eng.n_blocks))
    live, out = {}, {i: [] for i in range(len(requests))}
    tick = 0
    pending = list(enumerate(requests))
    while pending or live:
        for item in [p for p in pending if p[1][3] <= tick
                     and p[1][0] not in live]:
            i, (lane, prompt, n_new, _) = item
            pending.remove(item)
            need = -(-(len(prompt) + n_new) // bs)
            blocks = [free.pop() for _ in range(need)]
            tables[lane, :] = 0
            tables[lane, :need] = blocks
            lengths[lane] = 0
            live[lane] = dict(i=i, prompt=prompt, n_new=n_new, fed=0,
                              blocks=blocks, decoding=False)
        rows, who = [], []
        for lane, r in live.items():
            if r["fed"] < len(r["prompt"]) and len(rows) < eng.prefill_rows:
                chunk = r["prompt"][r["fed"]:r["fed"] + eng.prefill_chunk]
                rows.append({"tokens": chunk, "p0": r["fed"],
                             "table": r["blocks"], "lane": lane})
                who.append((lane, len(chunk)))
        if rows:
            state, logits = eng.prefill_chunks(params, state, rows)
            for j, (lane, n) in enumerate(who):
                r = live[lane]
                r["fed"] += n
                lengths[lane] = r["fed"]
                if r["fed"] == len(r["prompt"]):
                    out[r["i"]].append(np.asarray(logits[j]))
                    tokens[lane] = int(np.argmax(logits[j]))
                    r["decoding"] = "next"
        active = np.array([lane in live and live[lane]["decoding"] is True
                           for lane in range(eng.n_slots)])
        if active.any():
            state, logits = eng.decode_step_paged(
                params, state, tokens, tables, lengths, active)
            for lane in np.nonzero(active)[0]:
                r = live[lane]
                lengths[lane] += 1
                out[r["i"]].append(np.asarray(logits[lane]))
                tokens[lane] = int(np.argmax(logits[lane]))
        for lane in list(live):
            r = live[lane]
            if r["decoding"] == "next":
                r["decoding"] = True
            if len(out[r["i"]]) >= r["n_new"]:
                free.extend(r["blocks"])
                del live[lane]
        tick += 1
    return {i: np.stack(v[:requests[i][2]]) for i, v in out.items()}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_then_decode_matches_the_full_forward_pass(
        ref, model, impl):
    """Three requests over three lanes, one lane idle at first and one
    reused: a prompt of 45 tokens enters in three chunks (state carried
    from chunk to chunk and into decode), a second request takes lane 0
    after the first has left it (its state must be cleared), lane 2
    joins late (idle, then active beside the others).  Logits of every
    produced position against the reference's forward pass of prompt +
    greedy tokens.  ``pallas``: the kernels interpreted."""
    eng = _engine(model, paged_attn=impl)
    assert eng.programs.recurrent and not eng.prefix_cache_enabled
    state = eng.init_state()
    assert len(state["kv"]) == 1 and len(state["kda"]) == 4
    assert eng.kv_block_bytes() == 8 * 128 * 4  # one latent layer's rows
    assert eng.programs.recurrent_state_bytes() == 4 * 3 * (
        4 * 16 * 16 * 4 + 3 * 192 * 4)
    requests = [
        (0, _tokens(45, 1), 6, 0),
        (1, _tokens(9, 2), 12, 0),
        (0, _tokens(20, 3), 5, 1),   # reuses lane 0 once it is free
        (2, _tokens(33, 4), 4, 5),   # an idle lane until tick 5
    ]
    got = _serve_logits(eng, model, requests)
    for i, (_, prompt, n_new, _) in enumerate(requests):
        served = [int(np.argmax(row)) for row in got[i]]
        seq = prompt + served[:-1]
        want = _reference(ref, model, seq)[len(prompt) - 1:]
        assert want.shape == got[i].shape
        np.testing.assert_allclose(got[i], want, atol=TOL, rtol=0,
                                   err_msg=f"request {i}")


def test_a_lane_not_cleared_or_an_idle_lane_decayed_fails_the_comparison(
        ref, model, monkeypatch):
    """The planted faults the tolerance must catch: the second request
    on lane 0 inheriting the first one's state."""
    eng = _engine(model)
    requests = [(0, _tokens(45, 1), 3, 0), (0, _tokens(20, 3), 3, 1)]
    real = jnp.where
    fault = {"on": True}

    def keep_state(cond, a, b):
        # `fresh` clears with where(fresh, 0, state): hand the state on
        if fault["on"] and isinstance(a, (int, float)) and a == 0 and getattr(
                b, "ndim", 0) >= 3:
            return b
        return real(cond, a, b)

    from theanompi_tpu.serving import latent

    monkeypatch.setattr(latent.jnp, "where", keep_state)
    got = _serve_logits(eng, model, requests)
    fault["on"] = False
    prompt = requests[1][1]
    served = [int(np.argmax(row)) for row in got[1]]
    want = _reference(ref, model, prompt + served[:-1])[len(prompt) - 1:]
    assert np.abs(got[1] - want).max() > 20 * TOL


# ---- (d) through the scheduler ------------------------------------------------

def test_scheduler_serves_with_prefix_reuse_off_and_says_so(ref, model):
    eng = _engine(model, prefix_cache=True)
    sched = ContinuousBatchingScheduler(eng)
    assert sched.prefix is None
    assert sched.stats["prefix_reuse"] == "off: recurrent state"
    assert sched.stats["recurrent_state_bytes"] == (
        eng.programs.recurrent_state_bytes())
    shared = _tokens(24, 9)
    reqs = [Request(id=f"r{i}", prompt=shared + _tokens(5 + i, 10 + i),
                    max_new_tokens=4) for i in range(5)]
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    assert sched.stats["prefix_hits"] == 0
    assert sched.stats["recurrent_lanes_reset"] == 5
    for r in reqs:
        served = done[r.id]
        want = _reference(ref, model, r.prompt + served[:-1])
        best = np.argmax(want[len(r.prompt) - 1:], axis=-1)
        assert served == best.tolist(), r.id
    # the spans carry the share's own count of the experts' work
    from theanompi_tpu import observability as obs

    spans = [s for s in obs.get_tracer().boundary_spans()
             if s["name"] in ("decode_step", "prefill_chunk_dispatch")
             and "pairs_routed" in s["args"]]
    assert spans
    for s in spans:
        a = s["args"]
        # four expert layers, at most top-4 picks a token on held experts
        assert 0 <= a["pairs_routed"] <= 4 * 4 * a["tokens_routed"]
        assert a["experts_hit"] <= 4 * 8


def test_speculation_is_refused_for_a_model_with_recurrent_layers(model):
    eng = _engine(model)
    with pytest.raises(ValueError, match="lane 3 outside"):
        eng.prefill_chunks(model.params, eng.init_state(), [
            {"tokens": [1, 2], "p0": 0, "table": [1], "lane": 3}])
    with pytest.raises(ValueError, match="rolled back"):
        ContinuousBatchingScheduler(eng, spec_k=2, draft_engine=eng)
    with pytest.raises(ValueError, match="rolled back"):
        eng.verify_chunks(model.params, eng.init_state(),
                          np.zeros((3, 3), np.int32),
                          np.zeros((3, eng.blocks_per_seq), np.int32),
                          np.zeros((3,), np.int32), np.ones((3,), np.int32),
                          np.ones((3,), bool))


# ---- (e) the share tied to the model ------------------------------------------

def test_four_holders_of_64_experts_sum_to_the_uncut_layer_at_256_top_8(ref):
    """The expert layer at the configuration's counts (256 routed, top-8,
    2.446, a shared expert) and small widths: four layers told they hold
    experts 0-63, 64-127, 128-191 and 192-255, each given its slice of
    the expert leaves, sum to what the reference gives for the whole
    layer with all 256 held, the shared expert counted once."""
    d, f, e, k, n = 32, 16, 256, 8, 96
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    mp = {
        "wg": 0.5 * jax.random.normal(ks[0], (d, e)),
        "route_bias": 0.02 * jax.random.normal(ks[1], (e,)),
        "w_gate": 0.2 * jax.random.normal(ks[2], (e, d, f)),
        "w_up": 0.2 * jax.random.normal(ks[3], (e, d, f)),
        "w_down": 0.2 * jax.random.normal(ks[4], (e, f, d)),
        "shared": {"w_gate": 0.2 * jax.random.normal(ks[5], (d, f)),
                   "w_up": 0.2 * jax.random.normal(ks[6], (d, f)),
                   "w_down": 0.2 * jax.random.normal(ks[7], (f, d))},
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    mm = ref._mm("float32")
    idx, w = ref._route(mp, x, k, 2.446)
    want = ref._experts(mp, x, idx, w, mm, n) + ref._ffn(mp["shared"], x, mm)

    kw = dict(top_k=k, ep_axis=None, gated=True, scoring="sigmoid",
              n_shared=1, route_scale=2.446)
    total, pairs = 0.0, 0
    for first in range(0, e, 64):
        held = {key: v[first:first + 64] if key in ("w_gate", "w_up", "w_down")
                else v for key, v in mp.items()}
        layer = MoeMlp(e, f, experts_held=(first, 64), **kw)
        shapes, _, _ = jax.eval_shape(
            lambda key: layer.init(key, (d,)), jax.random.PRNGKey(0))
        assert shapes["w_gate"].shape == (64, d, f)  # leaves hold the share
        assert shapes["wg"].shape == (d, e)          # the router all 256
        y, counts, _ = layer.forward(held, x)
        total, pairs = total + y, pairs + int(counts.sum())
    assert pairs == n * k  # every pick computed by exactly one holder
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    # one holder alone computes its experts' part and no more
    one = ref._experts(
        {key: v[:64] if key in ("w_gate", "w_up", "w_down") else v
         for key, v in mp.items()}, x, idx, w, mm, n)
    first = MoeMlp(e, f, experts_held=(0, 64), **kw).forward(
        {key: v[:64] if key in ("w_gate", "w_up", "w_down") else v
         for key, v in mp.items()}, x)[0]
    np.testing.assert_allclose(
        np.asarray(first), np.asarray(one + ref._ffn(mp["shared"], x, mm)),
        atol=2e-5)
