"""CPU tests of the benchmark harness at tiny sizes.  Nothing here is a
device number: the rehearsal argument of the harness's functions selects
the tiny sizes, and a result line from here names the CPU."""

from __future__ import annotations

import gzip
import io
import json
import os
import re
from contextlib import redirect_stderr, redirect_stdout

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

import run  # noqa: E402
import trace_reduce  # noqa: E402

R2 = os.path.join(ROOT, "docs", "perf", "trace_r2")
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
CONFIGS = {c["name"]: c for c in BENCHMARK["configs"]}
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def rehearsal_for(cell: str) -> dict:
    """The tiny sizes of a cell: ``benchmarks/tests/rehearsals/<driver>.json``
    (a new driver brings its own)."""
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", CELLS[cell]["traffic"] + ".json")))
    reh = json.load(open(os.path.join(
        HERE, "rehearsals", traffic["driver"] + ".json")))
    reh = {k: v for k, v in reh.items() if not k.startswith("_")}
    reh["trace_fixture"] = R2
    return reh


def drive(cell: str, seed: int, trace: int, seconds: float = 1.0,
          rehearsal=None):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(
            ["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", str(trace)],
            rehearsal=rehearsal if rehearsal is not None else rehearsal_for(cell),
        )
    lines = out.getvalue().strip().splitlines()
    return rc, lines, err.getvalue()


# ---------------------------------------------------------------------------
# BENCHMARK.json against the contract's limits
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keys_names_and_units():
    assert sorted(BENCHMARK) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCHMARK[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in BENCHMARK["end_to_end"])
    four = sum(1 for w in BENCHMARK["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCHMARK["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCHMARK["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and w["config"] in CONFIGS
    for c in BENCHMARK["configs"]:
        assert c["file"].startswith(tuple(BENCHMARK["paths"]))
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in BENCHMARK["workloads"])


def test_every_moves_names_a_metric_each_listed_cell_reports():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        target = e2e[m["moves"]]
        cells = m.get("workloads", list(CELLS))
        for cell in cells:
            assert cell in CELLS
            assert cell in target.get("workloads", list(CELLS)), (m, cell)
    for cell in CELLS:
        mine = [m for m in BENCHMARK["end_to_end"]
                if cell in m.get("workloads", list(CELLS))]
        assert len(mine) >= 2, cell  # setup_s and one other
        assert any(cell in m.get("workloads", list(CELLS))
                   for m in BENCHMARK["per_layer"]), cell


def test_every_cell_and_metric_has_its_files():
    for cell in CELLS:
        loaded = run.load_cell(cell)
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", loaded.traffic["driver"] + ".py"))
        for kind in ("references", "flops"):
            assert os.path.isfile(os.path.join(
                BENCH, kind, loaded.config_name + ".py"))
        for name, m in loaded.per_layer.items():
            assert os.path.isfile(os.path.join(
                BENCH, "readers", m["reader"] + ".py")), name
            on_file = json.load(open(os.path.join(
                BENCH, "metrics", name + ".json")))
            for key in ("layer", "unit", "moves", "source", "better"):
                assert on_file[key] == m[key], (name, key)
        assert loaded.limits and all(
            v is None or v >= 0 for v in loaded.limits.values())


def test_run_py_names_no_cell_configuration_or_metric():
    text = open(os.path.join(BENCH, "run.py")).read()
    names = (list(CELLS) + list(CONFIGS)
             + [m["name"] for m in BENCHMARK["per_layer"]]
             + [m["name"] for m in BENCHMARK["end_to_end"]
                if m["name"] != "setup_s"])
    assert not [n for n in names if n in text]


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------

def test_run_refuses_a_cpu_backend():
    cell = next(iter(CELLS))
    out, errs = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(errs):
        rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                       "--trace", "0"])
    assert rc == run.EXIT_NO_CHIP
    assert out.getvalue().strip() == ""  # no result line
    assert "not a TPU" in errs.getvalue()


def test_unknown_workload_is_refused():
    out, errs = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(errs):
        rc = run.main(["--workload", "no-such-cell", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc == run.EXIT_BAD_CELL and out.getvalue().strip() == ""


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_at_a_toy_size(cell, trace):
    if CELLS[cell]["chips"] > 1:
        import jax

        if len(jax.devices()) < CELLS[cell]["chips"]:
            pytest.skip("needs virtual devices (see conftest.py)")
    # a seed larger than 32 signed bits hold
    rc, lines, err = drive(cell, 2**31 + 12345, trace)
    assert rc == 0, err
    last = json.loads(lines[-1])
    keys = list(last)
    assert keys[-1] == "compared"  # the numbers compared come last
    want = CONTRACT_KEYS + (["breakdown"] if trace else [])
    assert keys[:-1] == want, keys
    assert last["correct"] is True, err
    assert last["device"]["platform"] == "cpu"  # and says so
    assert last["attempted"] > 0 and last["failed"] == 0
    loaded = run.load_cell(cell)
    if trace:
        assert set(last["metrics"]) <= set(loaded.per_layer)
        assert last["metrics"], "no per-layer metric was read"
        assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
        assert len(last["breakdown"]["device_ops"]) <= 10
        assert len(last["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(last["metrics"]) == set(loaded.end_to_end)
        assert all(v["value"] > 0 for v in last["metrics"].values())
    for name, row in last["compared"].items():
        assert f"compared {name} = " in err  # and on standard error too


def test_the_same_seed_gives_the_same_numbers_compared():
    cell = sorted(CELLS)[0]
    a = json.loads(drive(cell, 77, 0)[1][-1])["compared"]
    b = json.loads(drive(cell, 77, 0)[1][-1])["compared"]
    c = json.loads(drive(cell, 78, 0)[1][-1])["compared"]
    assert a == b and a != c


# ---------------------------------------------------------------------------
# the trace reduction, on the one recorded device trace in the repo
# ---------------------------------------------------------------------------

def _analyze_trace_totals():
    """What ``scripts/analyze_trace.py`` sums: complete events on the
    device's "XLA Ops" thread of the Perfetto JSON beside the xplane."""
    path = trace_reduce.find_xplane(R2).replace(".xplane.pb", ".trace.json.gz")
    ev = json.load(gzip.open(path, "rt"))["traceEvents"]
    pids = {e["pid"] for e in ev if e.get("ph") == "M"
            and e.get("name") == "process_name"
            and "TPU" in (e["args"].get("name") or "")}
    tids = {(e["pid"], e["tid"]) for e in ev if e.get("ph") == "M"
            and e.get("name") == "thread_name" and e["pid"] in pids
            and e["args"].get("name") == "XLA Ops"}
    tot = {}
    for e in ev:
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in tids:
            tot[e["name"]] = tot.get(e["name"], 0.0) + e.get("dur", 0) / 1e6
    return tot


def test_trace_reduction_agrees_with_analyze_trace_on_the_r2_trace():
    t = trace_reduce.reduce(R2)
    assert len(t.devices) == 1 and t.steps() == 30
    # docs/perf/NOTES.md: 11.15 ms busy per step for this trace
    assert t.busy_s / t.steps() * 1e3 == pytest.approx(11.15, abs=0.01)
    assert 0.0 < t.idle_pct_worst() < 100.0
    want, got = _analyze_trace_totals(), t.op_totals()
    assert set(want) == set(got)
    for name, seconds in want.items():
        assert got[name] == pytest.approx(seconds, rel=1e-3, abs=1e-6), name
    b = t.breakdown()
    assert b["device_ops"][0][0] == max(want, key=want.get)


def test_interval_arithmetic():
    assert trace_reduce.covered([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert trace_reduce.subtract([(0, 4), (6, 8)], [(3, 7)]) == 4
    assert trace_reduce.subtract([(0, 4)], []) == 4
    assert trace_reduce.short_name("%fusion.5 = f32[2]{0} fusion(...)") == "fusion.5"


# ---------------------------------------------------------------------------
# operations from shapes, peaks
# ---------------------------------------------------------------------------

def test_alexnet_flops_against_a_hand_count():
    flops = run.load_module("flops", "alexnet128")
    cfg = json.load(open(os.path.join(BENCH, "configs", "alexnet128.json")))
    by_name = {n: (f, dx) for n, f, dx in flops.layers(cfg)}
    # conv2 at 128 px: 15 x 15 outputs, 256 filters of 5 x 5 x 96
    assert by_name["conv2"] == (2 * 15 * 15 * 256 * 5 * 5 * 96, True)
    # conv1 needs no gradient to its input; fc6 sees 3 x 3 x 256
    assert by_name["conv1"] == (2 * 32 * 32 * 96 * 11 * 11 * 3, False)
    assert by_name["fc6"][0] == 2 * 2304 * 4096
    fwd = sum(f for f, _ in by_name.values())
    assert flops.train_flops_per_sample(cfg) == 3 * fwd - by_name["conv1"][0]
    assert flops.n_params(cfg) == 34_066_792


def test_transformer_flops_against_a_hand_count_of_one_layer():
    flops = run.load_module("flops", "gpt2-xl")
    cfg = json.load(open(os.path.join(BENCH, "configs", "gpt2-xl.json")))
    one = dict(cfg, n_layer=1)
    # one layer at 1600 wide, feed-forward 6400: q, k, v, o are 1600 x
    # 1600 each, the two feed-forward matrices 1600 x 6400; the head is
    # 1600 x 50257.  A token that attends to 300 resident tokens does
    # 2 x 1600 operations for the scores and as many for the values of
    # each.
    layer = 2 * (4 * 1600 * 1600 + 2 * 1600 * 6400)
    assert layer == 61_440_000
    head = 2 * 1600 * 50257
    assert flops.forward_flops(one, tokens=1, attended=300) == (
        layer + head + 4 * 1600 * 300)
    # 48 layers, 7 tokens that see 10 positions between them
    assert flops.forward_flops(cfg, tokens=7, attended=10) == (
        7 * (48 * layer + head) + 48 * 4 * 1600 * 10)


SERVE_CELLS = sorted(
    c for c in CELLS if "prompt_len" in run.load_cell(c).traffic)


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_traffic_is_a_function_of_the_seed_alone_and_fits_the_context(cell):
    import traffic

    loaded = run.load_cell(cell)
    mix, vocab = loaded.traffic, int(loaded.config["vocab_size"])
    positions = int(loaded.config["n_positions"])
    big = 2**31 + 977  # more than 32 signed bits hold
    a, b = (traffic.generate(mix, big, vocab) for _ in range(2))
    assert a == b
    c = traffic.generate(mix, big + 1, vocab)
    shape = lambda reqs: [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
    # every seed offers the same sizes in the same order, other tokens
    assert shape(a) == shape(c) == traffic.sizes(mix)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert len(a) == mix["n_requests"]
    for r in a:
        total = len(r["prompt"]) + r["max_new_tokens"]
        assert total <= min(positions, mix["max_total"])
        assert len(r["prompt"]) >= 1 and r["max_new_tokens"] >= 1
        assert all(0 <= t < vocab for t in r["prompt"])
    # the list is the distribution the file states, not a draw from it
    for key, col in (("prompt_len", 0), ("output_len", 1)):
        spec = mix[key]
        if spec["dist"] == "lognormal":
            mean = sum(s[col] for s in traffic.sizes(mix)) / len(a)
            assert mean == pytest.approx(spec["mean"], abs=0.02), key


def test_traffic_fixed_lengths_and_a_shared_prefix():
    """The two parameters no cell uses yet: the cells PERF.md plans
    (sessions over a system prompt, replays at one length) must be
    addable as data files alone."""
    import traffic

    mix = {"n_requests": 6, "order_seed": 1, "max_total": 64, "shared_prefix": 5,
           "prompt_len": {"dist": "fixed", "value": 12},
           "output_len": {"dist": "lognormal", "mean": 9, "sigma": 0.5,
                          "min": 2, "max": 60}}
    reqs = traffic.generate(mix, 3, 97)
    assert {len(r["prompt"]) for r in reqs} == {12}
    assert len({tuple(r["prompt"][:5]) for r in reqs}) == 1
    assert len({tuple(r["prompt"][5:]) for r in reqs}) == 6


def test_peaks_table_is_keyed_by_device_kind():
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "_source" in peaks


def test_reader_that_finds_nothing_returns_nothing():
    import types

    for name in ("device_idle", "op_exposed", "rate_mfu", "fact_percentile"):
        reader = run.load_module("readers", name)
        ctx = types.SimpleNamespace(
            facts={}, trace=None, peaks={"bf16_flops_per_s": 1.0}, flops=None,
            config={}, chips=1,
            args={"pattern": "no-such-op", "rate_fact": "r", "flops_fn": "f", "fact": "h", "percentile": 50},
        )
        assert reader.read(ctx) is None
    t = trace_reduce.reduce(R2)
    ctx.trace = t
    assert run.load_module("readers", "op_exposed").read(ctx) is None  # no match
    ctx.args = {"pattern": r"^fusion\.545$"}
    share = run.load_module("readers", "op_exposed").read(ctx)
    assert 0.0 < share < 100.0


# ---------------------------------------------------------------------------
# what the serve driver's latencies count: the window's own requests
# ---------------------------------------------------------------------------

T0 = 100.0  # the window opens


def _serve_driver(traced=(None, None)):
    """The serve driver's latency arithmetic over a hand-filled recorder:
    no model, no scheduler, no jax array."""
    mod = run.load_module("drivers", "serve_paged")
    d = object.__new__(mod.Driver)
    d.rec = mod.Recorder()
    d._facts = {"t0": T0, "traced": traced}
    return d


def _request(d, rid, sent, first=None, done=None, n_out=None):
    d.rec.admitted(rid, 8, sent)
    if first is not None:
        d.rec.first_token(rid, first)
    if done is not None:
        d.rec.finished(rid, n_out, done)


LATENCY_CASES = {
    # sent by set-up, finished inside the window: in neither list
    "sent_before_the_window": (
        dict(sent=T0 - 0.5, first=T0 - 0.4, done=T0 + 3.0, n_out=11), [], []),
    # set-up's burst: first token inside the window too, still in neither
    "sent_before_first_token_inside": (
        dict(sent=T0 - 0.01, first=T0 + 0.6, done=T0 + 3.0, n_out=11), [], []),
    "sent_and_finished_inside": (
        dict(sent=T0 + 1.0, first=T0 + 1.08, done=T0 + 2.08, n_out=11),
        [80.0], [100.0]),
    # a request need not have finished to have a time to its first token
    "first_token_and_no_finish": (
        dict(sent=T0 + 1.0, first=T0 + 1.08), [80.0], []),
    "one_token_answer": (
        dict(sent=T0 + 1.0, first=T0 + 1.08, done=T0 + 1.08, n_out=1),
        [80.0], []),
    "sent_and_no_first_token_yet": (dict(sent=T0 + 1.0), [], []),
    "sent_as_the_window_opens": (
        dict(sent=T0, first=T0 + 0.074, done=T0 + 0.274, n_out=3),
        [74.0], [100.0]),
}


@pytest.mark.parametrize("case", sorted(LATENCY_CASES))
def test_serve_latencies_count_requests_sent_inside_the_window(case):
    request, want_ttft, want_tpot = LATENCY_CASES[case]
    d = _serve_driver()
    _request(d, "r0", **request)
    ttft, tpot = d._latencies()
    assert ttft == pytest.approx(want_ttft) and tpot == pytest.approx(want_tpot)


TRACED = (T0 + 6.0, T0 + 10.5)  # the profiler's start began, its stop ended
TRACED_CASES = {
    # (request, kept)
    "finished_before_the_profiler_started": (
        dict(sent=T0 + 1.0, first=T0 + 1.08, done=T0 + 5.9, n_out=40), True),
    "running_when_the_profiler_started": (
        dict(sent=T0 + 5.0, first=T0 + 5.08, done=T0 + 7.0, n_out=20), False),
    "sent_inside_the_traced_stretch": (
        dict(sent=T0 + 8.0, first=T0 + 8.08, done=T0 + 12.0, n_out=40), False),
    "sent_after_the_profiler_stopped": (
        dict(sent=T0 + 10.6, first=T0 + 10.68, done=T0 + 14.0, n_out=30), True),
    # still running when the window closes: it ends past the profiler's
    # stop, so the profiler touched it if it was sent before that
    "unfinished_sent_before_the_stop": (
        dict(sent=T0 + 9.0, first=T0 + 9.08), False),
    "unfinished_sent_after_the_stop": (
        dict(sent=T0 + 44.0, first=T0 + 44.08), True),
}


@pytest.mark.parametrize("case", sorted(TRACED_CASES))
def test_traced_run_drops_what_the_profiler_touched(case):
    request, kept = TRACED_CASES[case]
    d = _serve_driver(traced=TRACED)
    _request(d, "r0", **request)
    ttft, tpot = d._latencies()
    assert len(ttft) == (1 if kept else 0)
    assert len(tpot) == (1 if kept and "done" in request else 0)
    # and untraced every one of them counts
    d._facts["traced"] = (None, None)
    assert len(d._latencies()[0]) == 1


def test_serve_latencies_over_a_window_that_opens_on_set_ups_burst():
    """Set-up's twelve, all finished inside the window, and four of the
    window's own, of which one is unfinished and one a one-token answer:
    the lists hold the window's own and nothing of the burst."""
    d = _serve_driver()
    for i in range(12):  # set-up's burst: slow first tokens, all finished
        _request(d, f"s{i}", sent=T0 - 7.0, first=T0 - 7.0 + 0.15 + 0.04 * i,
                 done=T0 + 1.0 + i, n_out=50)
    _request(d, "a", sent=T0 + 1.0, first=T0 + 1.074, done=T0 + 6.074, n_out=51)
    _request(d, "b", sent=T0 + 2.0, first=T0 + 2.081, done=T0 + 4.081, n_out=21)
    _request(d, "c", sent=T0 + 3.0, first=T0 + 3.088, done=T0 + 3.088, n_out=1)
    _request(d, "d", sent=T0 + 44.9, first=T0 + 44.973)
    ttft, tpot = d._latencies()
    assert sorted(ttft) == pytest.approx([73.0, 74.0, 81.0, 88.0])
    assert sorted(tpot) == pytest.approx([100.0, 100.0])


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell_reports_the_sample_counts_of_its_latencies(cell, trace):
    """``run.py`` prints what the driver hands it as ``end_to_end`` in the
    observation line before the result line (the result line's keys are
    the contract's): ``n_ttft`` and ``n_tpot`` are there, and neither
    counts more requests than the window sent."""
    rc, lines, err = drive(cell, 2**31 + 4321, trace)
    assert rc == 0, err
    last = json.loads(lines[-1])
    said = [json.loads(l) for l in lines[:-1]]
    e2e = next(s["end_to_end"] for s in reversed(said) if "end_to_end" in s)
    assert 2 <= e2e["n_tpot"] <= e2e["n_ttft"] <= last["attempted"]
    assert "n_ttft" not in last["metrics"] and "n_tpot" not in last["metrics"]
    slowest = next(s["slowest_first_tokens"] for s in said
                   if "slowest_first_tokens" in s)
    assert slowest == sorted(slowest, reverse=True) and len(slowest) <= 12
    # (a toy prompt spans several chunks, so it can outlast its last tick)
    assert all(ms > 0 and prompt >= 1 and tick_ms > 0 and calls >= 1
               for ms, prompt, tick_ms, calls in slowest)
    if not trace:
        # the percentiles are those of the counted lists
        assert e2e["serve_ttft_p50_ms"] <= e2e["serve_ttft_p95_ms"]
        assert slowest[0][0] >= e2e["serve_ttft_p95_ms"]


def test_setup_s_leaves_out_the_runtimes_own_start_up(monkeypatch):
    """``setup_s`` runs from the process's start to the window's start less
    the one call that claims the chips (``jax.devices()``, the machine's
    start-up of the runtime); the observation line gives both parts."""
    import time

    import jax

    real = jax.devices

    def slow_devices(*a, **kw):
        time.sleep(0.4)
        return real(*a, **kw)

    monkeypatch.setattr(jax, "devices", slow_devices)
    cell = sorted(c for c in CELLS if CELLS[c]["chips"] == 1)[0]
    rc, lines, err = drive(cell, 2**31 + 777, 0)
    assert rc == 0, err
    last = json.loads(lines[-1])
    said = next(json.loads(l) for l in reversed(lines[:-1])
                if "backend_start_s" in l)
    assert 0.4 <= said["backend_start_s"] < 2.0
    assert last["metrics"]["setup_s"]["value"] == pytest.approx(
        said["since_start_s"] - said["backend_start_s"])
    assert said["end_to_end"]["setup_s"] == last["metrics"]["setup_s"]["value"]
