"""The readers of the latent model's per-layer metrics
(``traced_calls.py`` and ``readers/latent_decode_roofline.py``,
``moe_expert_roofline.py``, ``mhc_roofline.py``, ``op_time_share.py``,
``moe_load_max.py``) on hand-made spans and a hand-made device trace, the
``flops`` file against hand counts, and the new cell's files against
each other.  Nothing here is a device number."""

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

CELL = "xing4-serve-closed32-mooncake"
CONFIG = json.load(open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")))
FLOPS = run.load_module("flops", "xing4.0-29b-a4b")
READERS = {name: run.load_module("readers", name) for name in (
    "latent_decode_roofline", "moe_expert_roofline", "mhc_roofline",
    "op_time_share", "moe_load_max")}
PEAKS = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e11}


def spans():
    """One call before the traced stretch, a decode call and a prefill
    call inside it, a decode call inside it whose counters never came,
    one call after it."""
    return [
        span("decode_step", 9.0, 9.01, 1, active=32, experts_hit=250,
             expert_load_max=9, tokens_routed=32),
        span("decode_step", 10.1, 10.11, 2, active=32, experts_hit=280,
             expert_load_max=6, tokens_routed=32),
        span("prefill_chunk_dispatch", 10.2, 10.25, 3, rows=3, bucket=2048,
             useful_tokens=5000, computed_tokens=8192, experts_hit=320,
             expert_load_max=400, tokens_routed=5000),
        span("decode_step", 10.6, 10.61, 4, active=30),
        span("decode_step", 12.5, 12.51, 5, active=32, experts_hit=270,
             expert_load_max=4, tokens_routed=32),
    ]


FACTS = {"t0": 8.0, "window_s": 6.0, "profiler_s": 0.2, "traced": (10.0, 12.0),
         # (start, seconds, tokens, prefill tick, resident tokens)
         "ticks": [(9.0, 0.1, 32, False, 90000), (10.1, 0.1, 32, False, 100000),
                   (10.2, 0.4, 34, True, 120000), (12.5, 0.1, 32, False, 70000)]}


def ctx(ops, monkeypatch, **args):
    monkeypatch.setattr(traced_calls.span_read, "boundary_spans",
                        lambda since=None, until=None: [
                            s for s in spans() if since <= s["start"] < until])
    dev = trace_reduce.Device(index=0, ops=ops)
    return types.SimpleNamespace(
        trace=trace_reduce.Trace(devices=[dev]), facts=dict(FACTS),
        config=CONFIG, flops=FLOPS, peaks=PEAKS, args=args, chips=1)


OPS = [("%mla_paged_decode.1 = f32[2]{0} custom-call(...)", 0.0, 1e9),
       ("%fusion.7 = f32[2]{0} fusion(...)", 1e9, 3e9),
       ("%moe_grouped_mm_gate.4 = bf16[2]{0} custom-call(...)", 3e9, 4e9),
       ("%moe_grouped_mm_down.6 = bf16[2]{0} custom-call(...)", 4e9, 6e9),
       ("%mhc_pre.2 = bf16[2]{0} custom-call(...)", 6e9, 6.5e9),
       ("%mhc_post.3 = bf16[2]{0} custom-call(...)", 6.5e9, 8e9),
       ("%mla_paged_decode.9 = f32[2]{0} custom-call(...)", 9e9, 10e9)]


def test_traced_calls_are_those_with_counters_inside_the_stretch():
    got = traced_calls.calls(FACTS, spans())
    assert [c["tokens_routed"] for c in got] == [32, 5000]
    assert traced_calls.calls({"t0": 8.0, "window_s": 6.0}, spans()) == []
    assert traced_calls.calls(dict(FACTS, traced=(None, None)), spans()) == []
    trace = trace_reduce.Trace(devices=[trace_reduce.Device(index=0, ops=OPS)])
    assert traced_calls.kernel_seconds(trace, "moe_grouped_mm") == pytest.approx(3.0)
    assert traced_calls.kernel_seconds(trace, "mhc_pre", "mhc_post") == pytest.approx(2.0)
    assert traced_calls.kernel_seconds(trace, "mla_prefill") == 0.0


def test_flops_file_against_hand_counts():
    # attention 28.41 M a layer, an expert 11.01 M, the dense layer's
    # feed-forward 99.09 M (ISSUE 29's recount)
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
                 + 4096 * 3584)
    assert round(attention / 1e6, 2) == 28.41
    hyper = 2 * 24 * 14336
    routed_and_shared = 5 * 3 * 3584 * 1024 + 3584 * 64
    want = 6 * (attention + hyper) + 3 * 3584 * 9216 + 5 * routed_and_shared
    assert FLOPS.per_token_params(CONFIG) == want
    assert FLOPS.attention_flops_per_position(CONFIG) == 2 * 32 * 320 * 6
    head = 3584 * 131072
    assert FLOPS.forward_flops(CONFIG, 10, 100) == 2 * want * 10 + 20480 * 6 * 100
    assert (FLOPS.forward_flops(CONFIG, 10, 100, logit_rows=3)
            - FLOPS.forward_flops(CONFIG, 10, 100)) == 2 * head * 3
    assert FLOPS.expert_flops_per_token(CONFIG) == 5 * 4 * 6 * 3584 * 1024
    assert FLOPS.expert_bytes(CONFIG) == 3 * 3584 * 1024 * 2  # 22.0 MB
    assert FLOPS.latent_row_bytes(CONFIG) == 576 * 2 * 6
    assert FLOPS.hyper_bytes_per_token(CONFIG) == 12 * (3 * 4 * 3584 + 2 * 3584) * 2


def test_latent_decode_roofline_counts_resident_rows(monkeypatch):
    c = ctx(OPS, monkeypatch, kernel="mla_paged_decode")
    # the two ticks that start in the stretch hold 220,000 rows of 6,912
    # bytes over the six layers; the kernel ran 2 s
    want = 100.0 * 220000 * 6912 / 1e9 / 2.0
    assert READERS["latent_decode_roofline"].read(c) == pytest.approx(want)
    assert READERS["latent_decode_roofline"].read(
        ctx(OPS[1:2], monkeypatch, kernel="mla_paged_decode")) is None
    c.trace = None
    assert READERS["latent_decode_roofline"].read(c) is None


def test_expert_roofline_takes_the_larger_bound_of_each_call(monkeypatch):
    c = ctx(OPS, monkeypatch, kernel="moe_grouped_mm")
    flops, nbytes = 5 * 4 * 6 * 3584 * 1024, 3 * 3584 * 1024 * 2
    decode = max(32 * flops / 1e11, 280 * nbytes / 1e9)   # bytes bind
    prefill = max(5000 * flops / 1e11, 320 * nbytes / 1e9)  # operations bind
    assert decode == 280 * nbytes / 1e9 and prefill == 5000 * flops / 1e11
    got = READERS["moe_expert_roofline"].read(c)
    assert got == pytest.approx(100.0 * (decode + prefill) / 3.0)
    assert READERS["moe_expert_roofline"].read(
        ctx(OPS[:2], monkeypatch, kernel="moe_grouped_mm")) is None


def test_mhc_roofline_and_time_share(monkeypatch):
    c = ctx(OPS, monkeypatch, kernels=["mhc_pre", "mhc_post"])
    per_token = 12 * (3 * 4 * 3584 + 2 * 3584) * 2
    want = 100.0 * 5032 * per_token / 1e9 / 2.0
    assert READERS["mhc_roofline"].read(c) == pytest.approx(want)
    share = READERS["op_time_share"].read(ctx(OPS, monkeypatch, prefixes=["mhc_"]))
    assert share == pytest.approx(100.0 * 2.0 / 9.0)  # busy 9 s of 10
    assert READERS["op_time_share"].read(
        ctx(OPS, monkeypatch, prefixes=["no_such_kernel"])) is None


def test_load_max_is_the_median_ratio_to_the_mean_load(monkeypatch):
    c = ctx(OPS, monkeypatch)
    # 6 over 32 x 4 / 64 = 2, and 400 over 5000 x 4 / 64 = 312.5
    want = (6 / 2.0 + 400 / 312.5) / 2
    assert READERS["moe_load_max"].read(c) == pytest.approx(want)


def test_readers_find_nothing_in_a_program_without_counters(monkeypatch):
    """A parent commit's spans carry no counters and its trace no such
    kernel: every new reader returns nothing and does not raise."""
    monkeypatch.setattr(traced_calls.span_read, "boundary_spans",
                        lambda since=None, until=None: [
                            span("decode_step", 10.1, 10.11, 2, active=32)])
    dev = trace_reduce.Device(index=0, ops=OPS[1:2])
    for name, args in (("latent_decode_roofline", {"kernel": "mla_paged_decode"}),
                       ("moe_expert_roofline", {"kernel": "moe_grouped_mm"}),
                       ("mhc_roofline", {"kernels": ["mhc_pre", "mhc_post"]}),
                       ("op_time_share", {"prefixes": ["mhc_"]}),
                       ("moe_load_max", {})):
        c = types.SimpleNamespace(
            trace=trace_reduce.Trace(devices=[dev]), facts=dict(FACTS),
            config=CONFIG, flops=FLOPS, peaks=PEAKS, args=args, chips=1)
        assert READERS[name].read(c) is None, name


def test_the_new_cells_files_agree():
    cell = CELLS[CELL]
    assert cell["chips"] == 1 and cell["config"] == "xing4.0-29b-a4b"
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    assert entry["source"] == CONFIG["source"]
    assert set(entry["reduced"]) == set(CONFIG["reduced"]) == set(CONFIG["published"])
    pc, eng = CONFIG["program_config"], CONFIG["engine"]
    # the program's keys say what the published ones say
    for ours, theirs in (("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
                         ("n_layers", "num_hidden_layers"), ("vocab_size", "vocab_size"),
                         ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"), ("ffn_hidden", "intermediate_size"),
                         ("moe_hidden", "moe_intermediate_size"),
                         ("moe_experts", "n_routed_experts"),
                         ("moe_top_k", "num_experts_per_tok"),
                         ("n_shared_experts", "n_shared_experts"),
                         ("first_k_dense", "first_k_dense_replace"),
                         ("route_scale", "routed_scaling_factor"),
                         ("hc_mult", "hc_mult"), ("hc_sinkhorn_iters", "hc_sinkhorn_iters"),
                         ("hc_clamp", "mhc_h_res_clamp_max")):
        assert pc[ours] == CONFIG[theirs], ours
    assert pc["rope"]["factor"] == CONFIG["rope_scaling"]["factor"]
    mix = json.load(open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")))
    assert mix["max_total"] == eng["max_len"] == pc["seq_len"]
    assert mix["clients"] == eng["n_slots"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] >= mix["max_total"]
    import traffic

    sizes = traffic.sizes(mix)
    assert max(p + o for p, o in sizes) <= eng["max_len"]
    assert sum(p for p, _ in sizes) / len(sizes) == pytest.approx(7590, abs=1)
    assert sum(o for _, o in sizes) / len(sizes) == pytest.approx(182, abs=1)
    # the pool the configuration states: usable rows
    assert (eng["n_blocks"] - 1) * eng["block_size"] == 393216
