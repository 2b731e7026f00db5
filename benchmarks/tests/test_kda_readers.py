"""The readers of the recurrent model's per-layer metrics
(``readers/kda_decode_roofline.py``, ``kda_prefill_roofline.py``,
``moe_held_roofline.py``, and ``op_time_share.py`` and
``latent_decode_roofline.py`` on this configuration's keys) on hand-made
spans and a hand-made device trace, the ``flops`` file against hand
counts, and the new cell's files against each other.  Nothing here is a
device number."""

from __future__ import annotations

import json
import os
import types

import pytest

import run
import trace_reduce
import traced_calls
from test_harness import BENCH, BENCHMARK, CELLS
from test_span_readers import span

CELL = "kimilinear-serve-closed64-reasoning"
CONFIG = json.load(open(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.json")))
FLOPS = run.load_module("flops", "kimi-linear-48b-a3b")
READERS = {name: run.load_module("readers", name) for name in (
    "kda_decode_roofline", "kda_prefill_roofline", "moe_held_roofline",
    "op_time_share", "latent_decode_roofline", "serve_mfu")}
PEAKS = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e11}
STATE = 7 * 32 * 128 * 128 * 4  # a lane's matrices over the linear layers


def spans():
    """One call before the traced stretch, a decode call and a prefill
    call inside it, a decode call inside it whose counters never came,
    one call after it."""
    return [
        span("decode_step", 9.0, 9.01, 1, active=64, experts_hit=500,
             expert_load_max=9, pairs_routed=1000, tokens_routed=64),
        span("decode_step", 10.1, 10.11, 2, active=60, experts_hit=480,
             expert_load_max=6, pairs_routed=970, tokens_routed=60),
        span("prefill_chunk_dispatch", 10.2, 10.25, 3, rows=1, bucket=2048,
             useful_tokens=1800, computed_tokens=2048, experts_hit=512,
             expert_load_max=90, pairs_routed=28000, tokens_routed=1800),
        span("decode_step", 10.6, 10.61, 4, active=61),
        span("decode_step", 12.5, 12.51, 5, active=64, experts_hit=490,
             expert_load_max=4, pairs_routed=1010, tokens_routed=64),
    ]


FACTS = {"t0": 8.0, "window_s": 6.0, "profiler_s": 0.2, "traced": (10.0, 12.0),
         # (start, seconds, tokens, prefill tick, resident tokens)
         "ticks": [(9.0, 0.1, 64, False, 90000), (10.1, 0.1, 60, False, 100000),
                   (10.2, 0.4, 62, True, 120000), (12.5, 0.1, 64, False, 70000)]}


def ctx(ops, monkeypatch, given=None, **args):
    given = spans() if given is None else given
    monkeypatch.setattr(traced_calls.span_read, "boundary_spans",
                        lambda since=None, until=None: [
                            s for s in given if since <= s["start"] < until])
    dev = trace_reduce.Device(index=0, ops=ops)
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(devices=[dev]), facts=dict(FACTS),
        config=CONFIG, flops=FLOPS, peaks=PEAKS, args=args, chips=1)


OPS = [("%kda_decode.1 = f32[2]{0} custom-call(...)", 0.0, 1e9),
       ("%fusion.7 = f32[2]{0} fusion(...)", 1e9, 3e9),
       ("%moe_grouped_mm_gate.4 = bf16[2]{0} custom-call(...)", 3e9, 4e9),
       ("%moe_grouped_mm_down.6 = bf16[2]{0} custom-call(...)", 4e9, 6e9),
       ("%kda_chunk_prefill.2 = f32[2]{0} custom-call(...)", 6e9, 8e9),
       ("%mla_paged_decode.3 = f32[2]{0} custom-call(...)", 8e9, 8.5e9),
       ("%kda_decode.9 = f32[2]{0} custom-call(...)", 9e9, 10e9)]


def test_flops_file_against_hand_counts():
    # ISSUE 33's recount: a linear mixer 39.5 M, a latent one 29.1 M
    linear = (2304 * 12288 + 4 * 12288 + 2 * (2304 * 128 + 128 * 4096)
              + 2304 * 32 + 4096 * 2304)
    latent = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    assert round(linear / 1e6, 1) == 39.5 and round(latent / 1e6, 1) == 29.1
    assert FLOPS.held_picks_per_token(CONFIG) == 2.0  # 8 x 64 / 256, expected
    expert = 3 * 2304 * 1024
    experts = 2304 * 256 + expert * (2.0 + 1)
    want = 7 * linear + 2 * latent + 3 * 2304 * 9216 + 8 * experts
    assert FLOPS.per_token_params(CONFIG) == want
    assert FLOPS.state_flops_per_token(CONFIG) == 7 * 6 * 32 * 128 * 128
    # only the two latent layers attend to resident positions
    assert FLOPS.attention_flops_per_position(CONFIG) == 2 * 32 * 320 * 2
    assert FLOPS.forward_flops(CONFIG, 10, 100) == (
        (2 * want + 7 * 6 * 32 * 128 * 128) * 10 + 2 * 20480 * 100)
    assert (FLOPS.forward_flops(CONFIG, 10, 100, logit_rows=3)
            - FLOPS.forward_flops(CONFIG, 10, 100)) == 2 * 2304 * 40960 * 3
    assert FLOPS.expert_pair_flops(CONFIG) == 6 * 2304 * 1024
    assert FLOPS.expert_bytes(CONFIG) == 3 * 2304 * 1024 * 2  # 14.2 MB
    assert FLOPS.latent_row_bytes(CONFIG) == 576 * 2 * 2
    assert FLOPS.kda_state_bytes(CONFIG) == STATE
    assert FLOPS.kda_token_bytes(CONFIG) == 7 * 5 * 4096 * 4


def test_kda_decode_roofline_counts_a_read_and_a_write_of_each_decoding_lane(
        monkeypatch):
    c = ctx(OPS, monkeypatch, kernel="kda_decode")
    # of the traced decode calls one carries its count: 60 lanes; the
    # kernel ran 2 s
    want = 100.0 * 2 * 60 * STATE / 1e9 / 2.0
    assert READERS["kda_decode_roofline"].read(c) == pytest.approx(want)
    assert READERS["kda_decode_roofline"].read(
        ctx(OPS[1:4], monkeypatch, kernel="kda_decode")) is None
    c.trace = None
    assert READERS["kda_decode_roofline"].read(c) is None


def test_kda_prefill_roofline_takes_the_larger_bound_of_each_call(monkeypatch):
    c = ctx(OPS, monkeypatch, kernel="kda_chunk")
    by_flops = 1800 * 7 * 6 * 32 * 128 * 128 / 1e11
    by_bytes = (1800 * 7 * 5 * 4096 * 4 + 2 * 1 * STATE) / 1e9
    assert by_bytes > by_flops  # at these peaks the bytes bind
    assert READERS["kda_prefill_roofline"].read(c) == pytest.approx(
        100.0 * by_bytes / 2.0)
    fast = dict(PEAKS, hbm_bytes_per_s=1e13)
    c.peaks = fast
    assert READERS["kda_prefill_roofline"].read(c) == pytest.approx(
        100.0 * by_flops / 2.0)
    assert READERS["kda_prefill_roofline"].read(
        ctx(OPS[:4], monkeypatch, kernel="kda_chunk")) is None


def test_held_roofline_counts_the_pairs_computed_here(monkeypatch):
    c = ctx(OPS, monkeypatch, kernel="moe_grouped_mm")
    pair, nbytes = 6 * 2304 * 1024, 3 * 2304 * 1024 * 2
    decode = max(970 * pair / 1e11, 480 * nbytes / 1e9)      # bytes bind
    prefill = max(28000 * pair / 1e11, 512 * nbytes / 1e9)
    assert decode == 480 * nbytes / 1e9
    assert READERS["moe_held_roofline"].read(c) == pytest.approx(
        100.0 * (decode + prefill) / 3.0)
    # a program whose spans lack pairs_routed (a parent commit): nothing
    old = [dict(s, args={k: v for k, v in s["args"].items()
                         if k != "pairs_routed"}) for s in spans()]
    assert READERS["moe_held_roofline"].read(
        ctx(OPS, monkeypatch, given=old, kernel="moe_grouped_mm")) is None


def test_time_share_and_the_accepted_readers_on_this_configurations_keys(
        monkeypatch):
    share = READERS["op_time_share"].read(ctx(OPS, monkeypatch, prefixes=["kda_"]))
    assert share == pytest.approx(100.0 * 4.0 / 9.5)  # busy 9.5 s of 10
    # the latent decode kernel reads the rows of the TWO latent layers
    c = ctx(OPS, monkeypatch, kernel="mla_paged_decode")
    want = 100.0 * 220000 * 576 * 2 * 2 / 1e9 / 0.5
    assert READERS["latent_decode_roofline"].read(c) == pytest.approx(want)
    c = ctx(OPS, monkeypatch)
    c.facts.update(prefill_tokens=1000, decode_tokens=3000,
                   prefill_attended=50000, decode_attended=400000)
    want = 100.0 * FLOPS.forward_flops(CONFIG, 4000, 450000) / (6.0 * 1e11)
    assert READERS["serve_mfu"].read(c) == pytest.approx(want)


def test_readers_find_nothing_in_a_program_without_the_new_spans(monkeypatch):
    """A parent commit's spans carry no counters and its trace no such
    kernel: every new reader returns nothing and does not raise."""
    bare = [span("decode_step", 10.1, 10.11, 2, active=32),
            span("prefill_chunk_dispatch", 10.2, 10.25, 3, rows=1, bucket=64,
                 useful_tokens=40, computed_tokens=64)]
    for name, args in (("kda_decode_roofline", {"kernel": "kda_decode"}),
                       ("kda_prefill_roofline", {"kernel": "kda_chunk"}),
                       ("moe_held_roofline", {"kernel": "moe_grouped_mm"}),
                       ("op_time_share", {"prefixes": ["kda_"]})):
        for ops in (OPS[1:2], OPS):
            c = ctx(ops, monkeypatch, given=bare, **args)
            if name == "op_time_share" and ops is OPS:
                continue  # the kernels' time is the trace's own
            assert READERS[name].read(c) is None, name
        if name != "op_time_share":  # it reads the trace alone
            c.facts = {}
            assert READERS[name].read(c) is None, name


def test_the_new_cells_files_agree():
    cell = CELLS[CELL]
    assert cell["chips"] == 1 and cell["config"] == "kimi-linear-48b-a3b"
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    assert entry["source"] == CONFIG["source"]
    assert set(entry["reduced"]) == set(CONFIG["reduced"]) == set(CONFIG["published"])
    assert CONFIG["published"]["num_experts"] == 256
    assert CONFIG["published"]["vocab_size"] == 163840
    assert CONFIG["published"]["num_hidden_layers"] == 27
    assert "twelve chips" in CONFIG["assumed"]["deployment"]
    pc, eng = CONFIG["program_config"], CONFIG["engine"]
    lin = CONFIG["linear_attn_config"]
    # the program's keys say what the published ones say
    for ours, theirs in (("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
                         ("n_layers", "num_hidden_layers"), ("vocab_size", "vocab_size"),
                         ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"), ("ffn_hidden", "intermediate_size"),
                         ("moe_hidden", "moe_intermediate_size"),
                         ("moe_experts_held", "num_experts"),
                         ("moe_top_k", "num_experts_per_token"),
                         ("n_shared_experts", "num_shared_experts"),
                         ("first_k_dense", "first_k_dense_replace"),
                         ("route_scale", "routed_scaling_factor"),
                         ("rms_norm_eps", "rms_norm_eps"),
                         ("mla_nope", "mla_use_nope")):
        assert pc[ours] == CONFIG[theirs], ours
    assert pc["moe_experts"] == CONFIG["published"]["num_experts"]
    assert pc["kda_layers"] == lin["kda_layers"] == [1, 2, 3, 5, 6, 7, 9]
    assert lin["full_attn_layers"] == [4, 8]
    assert (pc["kda_head_dim"], pc["kda_conv"], pc["n_heads"]) == (
        lin["head_dim"], lin["short_conv_kernel_size"], lin["num_heads"])
    assert pc["hc_mult"] == 1
    mix = json.load(open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")))
    assert eng["max_len"] == pc["seq_len"] == CONFIG["n_positions"]
    # the issue's named fallback: prompts clipped at 8,192 (PERF.md, PR 33)
    assert mix["max_total"] == 8192 + 1536 <= eng["max_len"]
    assert mix["clients"] == eng["n_slots"] == 64
    import traffic

    sizes = traffic.sizes(mix)
    assert max(p + o for p, o in sizes) <= mix["max_total"]
    assert sum(p for p, _ in sizes) / len(sizes) == pytest.approx(1024, abs=1)
    assert sum(o for _, o in sizes) / len(sizes) == pytest.approx(512, abs=1)
    # some prompt is longer than one chunk: state carried between chunks
    assert max(p for p, _ in sizes) > 3 * eng["prefill_chunk"]
    # every lane at full length: the pool the configuration states
    assert (eng["n_blocks"] - 1) * eng["block_size"] == 64 * 17920
    # the published keys are the catalog's, but for the four reduced
    for key, value in (("hidden_size", 2304), ("head_dim", 72),
                       ("num_experts_per_token", 8), ("kv_lora_rank", 512),
                       ("moe_intermediate_size", 1024), ("intermediate_size", 9216),
                       ("routed_scaling_factor", 2.446), ("model_max_length", 1048576)):
        assert CONFIG[key] == value, key
