"""scripts/perf_gate.sh — the CI perf gate (ISSUE 6 satellite).

Smoke-tested end-to-end with fixture BENCH JSONs and the committed
3-rank doctor trace: green run exits 0, a throughput regression exits
nonzero through bench_compare, and an unmet ``--min-overlap`` exits
nonzero through the doctor.  The gate script is pure bash+stdlib, so
this is cheap enough for tier-1.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO, "scripts", "perf_gate.sh")
TRACE = os.path.join(
    REPO, "tests", "data", "observability", "doctor_rank0_trace_raw.jsonl"
)


def _bench_json(path, value, trace=None, live_alerts=None):
    detail = {"wall_s": 2.0}
    if trace:
        detail["observability"] = {"trace_raw": trace}
    if live_alerts is not None:
        detail.setdefault("observability", {})["live"] = {
            "windows": 3,
            "alerts_total": live_alerts,
            "alerts": [],
        }
    doc = {
        "metric": "alexnet128_bsp_images_per_sec_per_chip",
        "value": value,
        "unit": "images/sec/chip",
        "vs_baseline": 1.0,
        "measured_now": True,
        "detail": detail,
    }
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


ARTIFACT = os.path.join(REPO, ".graftlint_artifact.json")


def _run_gate(env_extra):
    env = dict(os.environ)
    # the serve leg runs a real (CPU-rehearsal) serving bench when no
    # pre-produced JSON is given — too slow for every smoke test here,
    # so it is opt-in per test (mirroring PERF_GATE_BENCH_JSON); same
    # for the chaos leg's multi-process drill (PERF_GATE_CHAOS_JSON)
    env.setdefault("PERF_GATE_SERVE", "0")
    env.setdefault("PERF_GATE_CHAOS", "0")
    env.setdefault("PERF_GATE_FLEET", "0")
    env.setdefault("PERF_GATE_BSP", "0")
    env.setdefault("PERF_GATE_PUBLISH", "0")
    env.setdefault("PERF_GATE_TUNE", "0")
    # the LINT leg stays default-ON; feeding the committed artifact
    # back as the "current" document keeps the smoke tests off the
    # analyzer run (the dedicated LINT tests below exercise the real
    # path and the failure shapes)
    env.setdefault("PERF_GATE_LINT_CURRENT", ARTIFACT)
    env.update(env_extra)
    return subprocess.run(
        ["bash", GATE], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=300,
    )


def _serve_json(path, value=150.0, trace=TRACE, metrics=None,
                ratio=3.5, hit_rate=0.57, fed=72, no_reuse=168,
                token_identical=True, accept_rate=0.78,
                kv_ratio=2.65, kv_drift=0.0, spec=True, kv_quant=True,
                forensics=True, coverage=0.97, retained=0, tracked=6):
    """A BENCH_serve-shaped fixture with the paged + decode-speed
    acceptance fields (detail.spec / detail.kv_quant, ISSUE 11) and
    the request-forensics section (detail.request_forensics, ISSUE
    20)."""
    obs = {"trace_raw": trace}
    if metrics:
        obs["metrics_json"] = metrics
    detail = {
        "wall_s": 0.2,
        "ttft_p99_s": 0.02,
        "tpot_p99_s": 0.01,
        "observability": obs,
        "paged": {
            "long_tail": {"concurrency_ratio": ratio,
                          "contiguous_slots": 2,
                          "paged_peak_concurrent": 7},
            "prefix": {"hit_rate": hit_rate,
                       "prefill_tokens": fed,
                       "prefill_tokens_no_reuse": no_reuse},
        },
    }
    if spec:
        detail["spec"] = {
            "token_identical": token_identical,
            "accept_rate": accept_rate,
            "speedup": 1.62,
            "k": 8,
            "rounds": 9,
            "draft_dispatches": 65,
            "verify_dispatches": 9,
        }
    if kv_quant:
        detail["kv_quant"] = {
            "blocks_per_chip_ratio": kv_ratio,
            "greedy_drift": kv_drift,
            "pool_blocks_fp32": 17,
            "pool_blocks_int8": 45,
        }
    if forensics:
        detail["request_forensics"] = {
            "threshold_s": 30.0,
            "tracked": tracked,
            "retained": retained,
            "recycled": tracked - retained,
            "retained_rids": [f"req{i}" for i in range(retained)],
            "coverage": coverage,
            "slowest": {
                "rid": "req0",
                "latency_s": 0.24,
                "coverage": coverage,
                "phases": {"queue": 0.0001, "prefill": 0.056,
                           "decode": 0.184, "spec_rollback": 0.0,
                           "install_wait": 0.0, "backpressure": 0.0,
                           "readmission": 0.0},
            },
        }
    doc = {
        "metric": "transformer_serve_tokens_per_sec",
        "value": value,
        "unit": "generated tokens/sec",
        "vs_baseline": 1.0,
        "measured_now": True,
        "detail": detail,
    }
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def _metrics_json(path, ttft_s):
    """A registry-snapshot-shaped metrics file with one TTFT
    observation landing in the bucket covering ``ttft_s``."""
    bounds = [0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
              0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0]
    # per-bucket (non-cumulative) counts: one observation, landing in
    # the first bucket whose bound covers it (or +Inf)
    hit = next((str(b) for b in bounds if ttft_s <= b), "+Inf")
    buckets = {str(b): 0 for b in bounds}
    buckets["+Inf"] = 0
    buckets[hit] = 1
    doc = {"serve_ttft_seconds": {
        "kind": "histogram", "help": "t", "bucket_bounds": bounds,
        "series": [{"labels": {}, "buckets": buckets,
                    "sum": ttft_s, "count": 1}],
    }}
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


@pytest.fixture()
def fixtures(tmp_path):
    base = _bench_json(tmp_path / "base.json", 100.0)
    good = _bench_json(tmp_path / "good.json", 101.0, trace=TRACE)
    slow = _bench_json(tmp_path / "slow.json", 80.0, trace=TRACE)
    return base, good, slow


def test_gate_green(fixtures):
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
    })
    assert r.returncode == 0, r.stderr
    assert "green" in r.stderr


def test_gate_fails_on_regression(fixtures):
    base, _, slow = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": slow,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_TOLERANCE": "0.05",
    })
    assert r.returncode != 0
    assert "REGRESSION" in (r.stdout + r.stderr)


def test_gate_fails_on_overlap_threshold(fixtures):
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_MIN_OVERLAP": "1.1",  # unreachable: always violated
    })
    assert r.returncode != 0
    assert "THRESHOLD VIOLATION" in (r.stdout + r.stderr)


def test_gate_loud_without_baseline(fixtures, tmp_path):
    _, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": str(tmp_path / "missing.json"),
    })
    assert r.returncode == 2
    assert "baseline" in r.stderr


def test_gate_fails_when_bench_live_plane_alerted(fixtures, tmp_path):
    """A bench that ran with THEANOMPI_LIVE=1 and raised watchdog
    alerts fails the gate even when throughput and overlap pass."""
    base, _, _ = fixtures
    alerted = _bench_json(
        tmp_path / "alerted.json", 101.0, trace=TRACE, live_alerts=2
    )
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": alerted,
        "PERF_GATE_BASELINE": base,
    })
    assert r.returncode != 0
    assert "live watchdog alert" in r.stderr


def test_gate_watchdog_leg_requires_straggler_to_fire(fixtures, tmp_path):
    """The planted-straggler self-test: an unreachable --max-straggler
    means the fixture cannot fire, and the gate must call the live
    plane broken instead of passing green."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_STRAGGLER_MAX": "10.0",  # fixture index ~0.61
    })
    assert r.returncode != 0
    assert "did NOT fire" in r.stderr


def test_gate_watchdog_leg_skippable(fixtures, tmp_path):
    """PERF_GATE_WATCHDOG=0 restores the pre-live gate behavior —
    alerts in the bench JSON are not inspected."""
    base, _, _ = fixtures
    alerted = _bench_json(
        tmp_path / "alerted.json", 101.0, trace=TRACE, live_alerts=2
    )
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": alerted,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
    })
    assert r.returncode == 0, r.stderr
    assert "green" in r.stderr


def test_gate_extracts_trace_from_bench_json(fixtures, tmp_path):
    """Without PERF_GATE_TRACE the gate finds the trace path inside the
    bench JSON's detail.observability — the wiring bench.py emits."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_MIN_OVERLAP": "0.0",
    })
    assert r.returncode == 0, r.stderr
    assert "doctor:" in r.stderr and "doctor_rank0" in r.stderr


# ---------------------------------------------------------------------------
# serve leg (ISSUE 8 satellite): BENCH_serve diff + SLO gate + paged
# acceptance checks, smoke-tested on fixture JSONs like the bench leg
# ---------------------------------------------------------------------------

def _serve_env(fixtures, serve_json, **extra):
    base, good, _ = fixtures
    env = {
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_SERVE": "1",
        "PERF_GATE_SERVE_JSON": serve_json,
        "PERF_GATE_SERVE_BASELINE": serve_json,
    }
    env.update(extra)
    return env


def test_gate_serve_leg_green(fixtures, tmp_path):
    serve = _serve_json(tmp_path / "serve.json",
                        metrics=_metrics_json(tmp_path / "m.json", 0.02))
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode == 0, r.stderr
    assert "paged: ratio 3.5" in r.stderr
    assert "green" in r.stderr


def test_gate_serve_leg_fails_on_ttft_slo(fixtures, tmp_path):
    """The doctor's --max-ttft-p99-s flag gates the serve leg: a
    metrics snapshot showing a 20s TTFT p99 violates a 1s SLO."""
    serve = _serve_json(tmp_path / "serve.json",
                        metrics=_metrics_json(tmp_path / "m.json", 20.0))
    r = _run_gate(_serve_env(fixtures, serve,
                             PERF_GATE_MAX_TTFT_P99="1.0"))
    assert r.returncode != 0
    assert "THRESHOLD VIOLATION" in (r.stdout + r.stderr)


def test_gate_serve_leg_fails_on_concurrency_ratio(fixtures, tmp_path):
    """A paged engine that cannot hold >= 2x the contiguous engine's
    concurrency at equal cache memory fails the acceptance check."""
    serve = _serve_json(tmp_path / "serve.json", ratio=1.2)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "concurrency ratio" in (r.stdout + r.stderr)


def test_gate_serve_leg_fails_without_prefix_reuse(fixtures, tmp_path):
    serve = _serve_json(tmp_path / "serve.json", hit_rate=0.0)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "prefix" in (r.stdout + r.stderr)


def test_gate_serve_leg_fails_when_reuse_saves_nothing(fixtures, tmp_path):
    serve = _serve_json(tmp_path / "serve.json", fed=168, no_reuse=168)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "no-reuse baseline" in (r.stdout + r.stderr)


def test_gate_spec_leg_green_reports(fixtures, tmp_path):
    """Green spec/kv-quant fields sail through and are reported."""
    serve = _serve_json(tmp_path / "serve.json")
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode == 0, r.stderr
    assert "spec: identical, accept 0.78" in r.stderr


def test_gate_spec_leg_fails_on_token_divergence(fixtures, tmp_path):
    """Greedy spec decode diverging from plain greedy is a correctness
    bug, not a perf miss — the gate fails loudly."""
    serve = _serve_json(tmp_path / "serve.json", token_identical=False)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "NOT token-identical" in (r.stdout + r.stderr)


def test_gate_spec_leg_fails_below_min_accept(fixtures, tmp_path):
    serve = _serve_json(tmp_path / "serve.json", accept_rate=0.05)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "acceptance rate" in (r.stdout + r.stderr)
    # the floor is a knob
    r2 = _run_gate(_serve_env(fixtures, serve,
                              PERF_GATE_SERVE_MIN_ACCEPT="0.01"))
    assert r2.returncode == 0, r2.stderr


def test_gate_spec_leg_fails_on_missing_section(fixtures, tmp_path):
    serve = _serve_json(tmp_path / "serve.json", spec=False)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "no detail.spec" in (r.stdout + r.stderr)


def test_gate_kv_quant_violations(fixtures, tmp_path):
    """int8 capacity below 2x, or greedy drift past the bound, fail."""
    low = _serve_json(tmp_path / "low.json", kv_ratio=1.4)
    r = _run_gate(_serve_env(fixtures, low))
    assert r.returncode != 0
    assert "blocks-per-chip" in (r.stdout + r.stderr)
    drifty = _serve_json(tmp_path / "drift.json", kv_drift=0.9)
    r2 = _run_gate(_serve_env(fixtures, drifty))
    assert r2.returncode != 0
    assert "greedy drift" in (r2.stdout + r2.stderr)


def test_gate_spec_leg_escape_hatch(fixtures, tmp_path):
    """PERF_GATE_SPEC=0 skips the decode-speed acceptance only — the
    paged acceptance checks still run."""
    serve = _serve_json(tmp_path / "serve.json", token_identical=False,
                        kv_ratio=1.0)
    r = _run_gate(_serve_env(fixtures, serve, PERF_GATE_SPEC="0"))
    assert r.returncode == 0, r.stderr
    assert "paged: ratio 3.5" in r.stderr


def test_gate_forensics_leg_green(fixtures, tmp_path):
    """Green forensics fields sail through; the planted-slow selftest
    runs and passes as part of the leg."""
    serve = _serve_json(tmp_path / "serve.json")
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode == 0, r.stderr
    assert "forensics: 6 tracked, 0 retained" in r.stderr
    assert "forensics selftest" in r.stderr
    assert "green" in r.stderr


def test_gate_forensics_fails_on_low_coverage(fixtures, tmp_path):
    """A slowest request the doctor cannot explain (phase attribution
    below the floor) fails the gate."""
    serve = _serve_json(tmp_path / "serve.json", coverage=0.5)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "cannot explain where the tail went" in (r.stdout + r.stderr)
    # the floor is a knob
    r2 = _run_gate(_serve_env(
        fixtures, serve, PERF_GATE_FORENSICS_MIN_COVERAGE="0.4"))
    assert r2.returncode == 0, r2.stderr


def test_gate_forensics_fails_on_green_retention(fixtures, tmp_path):
    """Tail retention firing on a healthy bench run means the flags or
    threshold are mis-tuned — noise, not signal — and fails the gate."""
    serve = _serve_json(tmp_path / "serve.json", retained=3)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "retained on a green run" in (r.stdout + r.stderr)


def test_gate_forensics_fails_on_missing_section(fixtures, tmp_path):
    serve = _serve_json(tmp_path / "serve.json", forensics=False)
    r = _run_gate(_serve_env(fixtures, serve))
    assert r.returncode != 0
    assert "no detail.request_forensics" in (r.stdout + r.stderr)


def test_gate_forensics_escape_hatch(fixtures, tmp_path):
    """PERF_GATE_FORENSICS=0 skips the forensics acceptance only — the
    paged and spec checks still run."""
    serve = _serve_json(tmp_path / "serve.json", forensics=False)
    r = _run_gate(_serve_env(fixtures, serve, PERF_GATE_FORENSICS="0"))
    assert r.returncode == 0, r.stderr
    assert "paged: ratio 3.5" in r.stderr


def test_gate_serve_missing_baseline_skips_diff_not_slos(fixtures, tmp_path):
    """First round: no BENCH_serve_r*.json yet — the diff is skipped
    loudly but the SLO and paged acceptance checks still run."""
    serve = _serve_json(tmp_path / "serve.json")
    r = _run_gate(_serve_env(
        fixtures, serve,
        PERF_GATE_SERVE_BASELINE=str(tmp_path / "missing.json"),
    ))
    assert r.returncode == 0, r.stderr
    assert "skipping serve diff" in r.stderr
    assert "paged acceptance" in r.stderr


# ---------------------------------------------------------------------------
# failover leg (ISSUE 9): the kill-primary drill — the gate must prove
# the HA plane promotes a standby AND keeps the planted-straggler alert
# ---------------------------------------------------------------------------

def test_gate_failover_leg_green(fixtures):
    """Default-on failover drill: the committed fixture promotes the
    standby and the straggler alert survives the takeover."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",  # isolate the failover leg
    })
    assert r.returncode == 0, r.stderr
    assert "failover: promoted at window" in r.stderr
    assert "post-takeover straggler alert" in r.stderr
    assert "green" in r.stderr


def test_gate_failover_leg_detects_blackout(fixtures):
    """A standby that never promotes (promotion threshold unreachable)
    is a monitoring blackout — the gate must fail, not pass green."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER_PROMOTE_MISS": "999",
    })
    assert r.returncode != 0
    assert "blackout" in r.stderr


def test_gate_failover_leg_detects_lost_alert(fixtures):
    """A drill that promotes but fires no straggler alert (threshold
    unreachable) means the alert was lost across the takeover — fail."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_STRAGGLER_MAX": "10.0",  # fixture index ~0.61
    })
    assert r.returncode != 0
    # the drill still exits 1 (the failover announcement itself is an
    # alert), so the loss is caught by the structure check
    assert "FAILOVER VIOLATION" in r.stderr
    assert "no straggler alert" in r.stderr


def test_gate_failover_leg_skippable(fixtures):
    """PERF_GATE_FAILOVER=0 restores the pre-HA gate behavior."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
    })
    assert r.returncode == 0, r.stderr
    assert "failover drill" not in r.stderr
    assert "green" in r.stderr


# ---------------------------------------------------------------------------
# chaos leg (ISSUE 10): the elastic-membership drill verdict gates the
# round — smoke-tested on fixture verdicts like the other legs
# ---------------------------------------------------------------------------

def _chaos_json(path, ok=True, kills=1, evictions=1, rejoins=1,
                loss_delta=0.01, tolerance=0.25, violations=None,
                rules=("EASGD", "GOSGD")):
    doc = {"rules": {}, "ok": ok}
    for rule in rules:
        doc["rules"][rule] = {
            "rule": rule,
            "ok": ok,
            "violations": list(violations or ()),
            "kills_observed": kills,
            "evictions": evictions,
            "rejoins": rejoins,
            "readmissions": 1,
            "restarts": {"1": 1},
            "exit_codes": {"0": 0, "1": 77, "2": 0},
            "baseline_loss": 1.0,
            "chaos_loss": 1.0 + loss_delta,
            "loss_delta": loss_delta,
            "loss_tolerance": tolerance,
        }
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def test_gate_chaos_leg_green(fixtures, tmp_path):
    base, good, _ = fixtures
    chaos = _chaos_json(tmp_path / "chaos.json")
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_CHAOS": "1",
        "PERF_GATE_CHAOS_JSON": chaos,
    })
    assert r.returncode == 0, r.stderr
    assert "chaos [EASGD]: 1 kill -> 1 eviction" in r.stderr
    assert "chaos [GOSGD]" in r.stderr
    assert "green" in r.stderr


def test_gate_chaos_leg_fails_on_violation(fixtures, tmp_path):
    """A drill that recorded a violation (e.g. the respawn never
    re-admitted) fails the gate with the violation surfaced."""
    base, good, _ = fixtures
    chaos = _chaos_json(
        tmp_path / "chaos.json", ok=False,
        violations=["the respawned rank never re-admitted"],
    )
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_CHAOS": "1",
        "PERF_GATE_CHAOS_JSON": chaos,
    })
    assert r.returncode != 0
    assert "CHAOS VIOLATION" in r.stderr
    assert "never re-admitted" in r.stderr


def test_gate_chaos_leg_fails_on_eviction_kill_mismatch(fixtures, tmp_path):
    """An ok-flagged verdict whose eviction count does not match the
    kill count is still refused — the structure check is independent
    of the drill's self-assessment."""
    base, good, _ = fixtures
    chaos = _chaos_json(tmp_path / "chaos.json", kills=1, evictions=2)
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_CHAOS": "1",
        "PERF_GATE_CHAOS_JSON": chaos,
    })
    assert r.returncode != 0
    assert "eviction(s) for 1 kill(s)" in (r.stdout + r.stderr)


def test_gate_chaos_leg_skippable(fixtures):
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_CHAOS": "0",
    })
    assert r.returncode == 0, r.stderr
    assert "chaos drill" not in r.stderr
    assert "chaos [" not in r.stderr
    assert "green" in r.stderr


# ---------------------------------------------------------------------------
# fleet leg (ISSUE 12): the serving-fleet kill drill verdict gates the
# round — smoke-tested on fixture verdicts like the chaos leg
# ---------------------------------------------------------------------------

def _fleet_json(path, ok=True, kills=1, evictions=1, eviction_alerts=None,
                readmissions=3, token_identical=True,
                ttft_delta=0.4, ttft_tol=3.0, tpot_delta=0.05,
                tpot_tol=3.0, violations=None):
    doc = {"rules": {"SERVE": {
        "rule": "SERVE",
        "ok": ok,
        "violations": list(violations or ()),
        "n_replicas": 3,
        "n_requests": 8,
        "kills_observed": kills,
        "killed": "r0",
        "streams_in_flight_at_kill": 2,
        "evictions": evictions,
        "eviction_alerts": (
            evictions if eviction_alerts is None else eviction_alerts
        ),
        "readmissions": readmissions,
        "readmission_alerts": readmissions,
        "token_identical": token_identical,
        "baseline": {"ttft_p99_s": 0.4, "tpot_p99_s": 0.02,
                     "n_tokens": 192},
        "chaos": {"ttft_p99_s": 0.4 + ttft_delta,
                  "tpot_p99_s": 0.02 + tpot_delta, "n_tokens": 192},
        "ttft_p99_s_delta": ttft_delta,
        "ttft_p99_s_tolerance": ttft_tol,
        "tpot_p99_s_delta": tpot_delta,
        "tpot_p99_s_tolerance": tpot_tol,
    }}, "ok": ok}
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def test_gate_fleet_leg_green(fixtures, tmp_path):
    base, good, _ = fixtures
    fleet = _fleet_json(tmp_path / "fleet.json")
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_FLEET": "1",
        "PERF_GATE_FLEET_JSON": fleet,
    })
    assert r.returncode == 0, r.stderr
    assert "fleet: 1 kill -> 1 eviction" in r.stderr
    assert "token-identical" in r.stderr
    assert "green" in r.stderr


def test_gate_fleet_leg_detects_blackout(fixtures, tmp_path):
    """A drill whose in-flight streams never re-admitted is a serving
    blackout: the structure check refuses it even when the verdict
    self-reports ok."""
    base, good, _ = fixtures
    fleet = _fleet_json(tmp_path / "fleet.json", readmissions=0)
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_FLEET": "1",
        "PERF_GATE_FLEET_JSON": fleet,
    })
    assert r.returncode != 0
    assert "no stream re-admitted" in (r.stdout + r.stderr)


def test_gate_fleet_leg_fails_on_non_identical_output(fixtures, tmp_path):
    base, good, _ = fixtures
    fleet = _fleet_json(
        tmp_path / "fleet.json", ok=False, token_identical=False,
        violations=["outputs diverged from the uninterrupted run"],
    )
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_FLEET": "1",
        "PERF_GATE_FLEET_JSON": fleet,
    })
    assert r.returncode != 0
    assert "FLEET VIOLATION" in r.stderr
    assert "outputs diverged" in (r.stdout + r.stderr)


def test_gate_fleet_leg_fails_on_eviction_mismatch(fixtures, tmp_path):
    """Two evictions for one kill = the roster double-paged; one kill
    with zero eviction alerts = the live plane missed it.  Both are
    refused independent of the drill's self-assessment."""
    base, good, _ = fixtures
    fleet = _fleet_json(tmp_path / "fleet.json", evictions=2,
                        eviction_alerts=2)
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_FLEET": "1",
        "PERF_GATE_FLEET_JSON": fleet,
    })
    assert r.returncode != 0
    assert "eviction(s) for 1 kill(s)" in (r.stdout + r.stderr)


def test_gate_fleet_leg_fails_on_p99_overrun(fixtures, tmp_path):
    base, good, _ = fixtures
    fleet = _fleet_json(tmp_path / "fleet.json", ttft_delta=5.0,
                        ttft_tol=3.0)
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_FLEET": "1",
        "PERF_GATE_FLEET_JSON": fleet,
    })
    assert r.returncode != 0
    assert "exceeds tolerance" in (r.stdout + r.stderr)


def test_gate_fleet_leg_skippable(fixtures):
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_FLEET": "0",
    })
    assert r.returncode == 0, r.stderr
    assert "fleet drill" not in r.stderr
    assert "fleet:" not in r.stderr
    assert "green" in r.stderr


# ---------------------------------------------------------------------------
# BSP leg (ISSUE 13): the elastic-BSP shrink/rejoin drill verdict gates
# the round — smoke-tested on fixture verdicts like the other legs
# ---------------------------------------------------------------------------

def _bsp_json(path, ok=True, kills=1, evictions=1, alerts=None,
              bit_identical=True, world_restored=True, rejoined=True,
              monotone=True, extra_recompiles=0, loss_delta=0.01,
              tolerance=0.25, violations=None):
    doc = {"rules": {"BSP": {
        "rule": "BSP",
        "ok": ok,
        "violations": list(violations or ()),
        "n_ranks": 3,
        "kill_rank": 1,
        "kill_iter": 6,
        "n_steps": 20,
        "kills_observed": kills,
        "evictions": evictions,
        "worker_evicted_alerts": (
            evictions if alerts is None else alerts
        ),
        "resized_step_bit_identical": bit_identical,
        "generations": {"0": [1, 2, 3], "2": [1, 2, 3]},
        "generation_monotone": monotone,
        "world_restored": world_restored,
        "rejoined": rejoined,
        "resizes": {"shrink": 1, "expand": 1},
        "apply_traces": {"0": 2, "2": 2},
        "extra_recompiles": extra_recompiles,
        "baseline_loss": 2.0,
        "chaos_loss": 2.0 + loss_delta,
        "loss_delta": loss_delta,
        "loss_tolerance": tolerance,
    }}, "ok": ok}
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def _bsp_env(fixtures, bsp_json):
    base, good, _ = fixtures
    return {
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_BSP": "1",
        "PERF_GATE_BSP_JSON": bsp_json,
    }


def test_gate_bsp_leg_green(fixtures, tmp_path):
    r = _run_gate(_bsp_env(fixtures, _bsp_json(tmp_path / "bsp.json")))
    assert r.returncode == 0, r.stderr
    assert "bsp: 1 kill -> 1 eviction" in r.stderr
    assert "resize bit-identical" in r.stderr
    assert "green" in r.stderr


def test_gate_bsp_leg_detects_blackout(fixtures, tmp_path):
    """A drill whose respawned rank never re-expanded the world is a
    capacity blackout: refused even when the verdict self-reports
    ok."""
    bsp = _bsp_json(tmp_path / "bsp.json", world_restored=False,
                    rejoined=False)
    r = _run_gate(_bsp_env(fixtures, bsp))
    assert r.returncode != 0
    assert "never re-expanded the world" in (r.stdout + r.stderr)


def test_gate_bsp_leg_fails_on_non_identical_resize(fixtures, tmp_path):
    bsp = _bsp_json(
        tmp_path / "bsp.json", ok=False, bit_identical=False,
        violations=["survivors' post-resize step is NOT bit-identical"],
    )
    r = _run_gate(_bsp_env(fixtures, bsp))
    assert r.returncode != 0
    assert "BSP VIOLATION" in r.stderr
    assert "bit-identical" in (r.stdout + r.stderr)


def test_gate_bsp_leg_fails_on_eviction_mismatch(fixtures, tmp_path):
    """Two evictions for one kill = followers double-evicted; one kill
    with zero worker_evicted alerts = the live plane missed it.  Both
    refused independent of the drill's self-assessment."""
    bsp = _bsp_json(tmp_path / "bsp.json", evictions=2, alerts=2)
    r = _run_gate(_bsp_env(fixtures, bsp))
    assert r.returncode != 0
    assert "eviction(s) for 1 kill(s)" in (r.stdout + r.stderr)
    bsp2 = _bsp_json(tmp_path / "bsp2.json", alerts=0)
    env = _bsp_env(fixtures, bsp2)
    r2 = _run_gate(env)
    assert r2.returncode != 0
    assert "worker_evicted alert(s)" in (r2.stdout + r2.stderr)


def test_gate_bsp_leg_fails_on_extra_recompiles(fixtures, tmp_path):
    bsp = _bsp_json(tmp_path / "bsp.json", extra_recompiles=2)
    r = _run_gate(_bsp_env(fixtures, bsp))
    assert r.returncode != 0
    assert "beyond the single expected resize recompile" in (
        r.stdout + r.stderr
    )


def test_gate_bsp_leg_skippable(fixtures):
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_BSP": "0",
    })
    assert r.returncode == 0, r.stderr
    assert "bsp drill" not in r.stderr
    assert "bsp:" not in r.stderr
    assert "green" in r.stderr


# ---------------------------------------------------------------------------
# publish leg (ISSUE 18): the online-learning live-swap drill verdict
# gates the round — smoke-tested on fixture verdicts like the other legs
# ---------------------------------------------------------------------------

def _publish_json(path, ok=True, publishes=1, installs=None,
                  gen0_identical=True, ab_identical=True,
                  planted="regression", rollbacks=1, alerts=None,
                  post_rollback=True, refused=True, extra_recompiles=0,
                  violations=None):
    doc = {"rules": {"PUBLISH": {
        "rule": "PUBLISH",
        "ok": ok,
        "violations": list(violations or ()),
        "n_requests": 6,
        "publish_every": 3,
        "n_publishes": publishes,
        "install_deferred_while_busy": True,
        "token_identical_gen0": gen0_identical,
        "n_installs": publishes if installs is None else installs,
        "ab_cohort_identical": ab_identical,
        "ab_verdict_unplanted": "pass",
        "ab_verdict_planted": planted,
        "rollbacks": rollbacks,
        "post_rollback_identical": post_rollback,
        "refused_bad_dtype": refused,
        "extra_recompiles": extra_recompiles,
        "weights_rolled_back_alerts": (
            rollbacks if alerts is None else alerts
        ),
    }}, "ok": ok}
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def _publish_env(fixtures, publish_json):
    base, good, _ = fixtures
    return {
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_PUBLISH": "1",
        "PERF_GATE_PUBLISH_JSON": publish_json,
    }


def test_gate_publish_leg_green(fixtures, tmp_path):
    r = _run_gate(
        _publish_env(fixtures, _publish_json(tmp_path / "pub.json"))
    )
    assert r.returncode == 0, r.stderr
    assert "publish: 1 publish -> 1 install" in r.stderr
    assert "cohorts token-identical" in r.stderr
    assert "green" in r.stderr


def test_gate_publish_leg_fails_on_install_mismatch(fixtures, tmp_path):
    """Two installs for one publish = the subscriber double-applied;
    refused independent of the drill's self-assessment."""
    pub = _publish_json(tmp_path / "pub.json", installs=2)
    r = _run_gate(_publish_env(fixtures, pub))
    assert r.returncode != 0
    assert "install per publish" in (r.stdout + r.stderr)


def test_gate_publish_leg_fails_on_torn_stream(fixtures, tmp_path):
    pub = _publish_json(
        tmp_path / "pub.json", ok=False, gen0_identical=False,
        violations=["cohort A is NOT token-identical to the gen-0 "
                    "reference"],
    )
    r = _run_gate(_publish_env(fixtures, pub))
    assert r.returncode != 0
    assert "PUBLISH VIOLATION" in r.stderr


def test_gate_publish_leg_fails_on_missed_rollback(fixtures, tmp_path):
    """A planted SLO regression that never rolls back (or double-rolls)
    is a broken canary loop — both shapes refused."""
    none = _publish_json(tmp_path / "none.json", rollbacks=0, alerts=0)
    r = _run_gate(_publish_env(fixtures, none))
    assert r.returncode != 0
    assert "rollback(s)" in (r.stdout + r.stderr)
    silent = _publish_json(tmp_path / "silent.json", alerts=0)
    r2 = _run_gate(_publish_env(fixtures, silent))
    assert r2.returncode != 0
    assert "weights_rolled_back" in (r2.stdout + r2.stderr)


def test_gate_publish_leg_fails_on_recompiles(fixtures, tmp_path):
    pub = _publish_json(tmp_path / "pub.json", extra_recompiles=2)
    r = _run_gate(_publish_env(fixtures, pub))
    assert r.returncode != 0
    assert "params-as-data" in (r.stdout + r.stderr)


def test_gate_publish_leg_fails_on_unrefused_shape(fixtures, tmp_path):
    pub = _publish_json(tmp_path / "pub.json", refused=False)
    r = _run_gate(_publish_env(fixtures, pub))
    assert r.returncode != 0
    assert "not refused before install" in (r.stdout + r.stderr)


def test_gate_publish_leg_skippable(fixtures):
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_PUBLISH": "0",
    })
    assert r.returncode == 0, r.stderr
    assert "publish drill" not in r.stderr
    assert "publish:" not in r.stderr
    assert "green" in r.stderr


# ---------------------------------------------------------------------------
# lint leg (ISSUE 14 satellite): the graftlint artifact diff, default-on
# ---------------------------------------------------------------------------

def _lint_current(tmp_path, mutate=None):
    """A current-artifact fixture derived from the committed one."""
    doc = json.load(open(ARTIFACT))
    if mutate:
        mutate(doc)
    path = tmp_path / "lint_current.json"
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def test_gate_lint_leg_green_runs_real_analyzer(fixtures):
    """No PERF_GATE_LINT_CURRENT: the leg analyzes the tree through
    the incremental cache and must match the committed artifact.  That
    match is what this test is for: the per-pass budget, which has its
    own tests below, is set to a minute, which no loaded host reaches."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_LINT_CURRENT": "",
        "PERF_GATE_LINT_PASS_BUDGET_MS": "60000",
    })
    assert r.returncode == 0, r.stderr
    assert "lint artifact diff" in r.stderr
    assert "graftlint_diff: clean" in r.stdout


def test_gate_lint_leg_fails_on_new_finding(fixtures, tmp_path):
    base, good, _ = fixtures

    def add_finding(doc):
        doc["findings"].append({
            "fingerprint": "0123456789abcdef", "rule": "GL-P001",
            "pass": "protocol", "severity": "warning", "file": "x.py",
            "line": 1, "symbol": "f", "message": "m", "snippet": "s",
            "fixable": False,
        })

    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_LINT_CURRENT": _lint_current(tmp_path, add_finding),
    })
    assert r.returncode != 0
    assert "NEW FINDING" in r.stdout
    assert "LINT VIOLATION" in r.stderr


def test_gate_lint_leg_fails_on_step_trace_drift(fixtures, tmp_path):
    base, good, _ = fixtures

    def drift(doc):
        key = sorted(doc["step_traces"])[0]
        doc["step_traces"][key] = list(doc["step_traces"][key]) + ["psum"]

    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_LINT_CURRENT": _lint_current(tmp_path, drift),
    })
    assert r.returncode != 0
    assert "STEP-TRACE DRIFT" in r.stdout
    assert "LINT VIOLATION" in r.stderr


def test_gate_lint_leg_fails_on_missing_baseline(fixtures, tmp_path):
    """An absent committed artifact is a loud failure, not a skip —
    a gate that silently baselines against nothing is no gate."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_LINT_BASELINE": str(tmp_path / "missing.json"),
    })
    assert r.returncode != 0
    assert "LINT VIOLATION" in r.stderr


def test_gate_lint_leg_skippable(fixtures, tmp_path):
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_LINT": "0",
        "PERF_GATE_LINT_BASELINE": str(tmp_path / "missing.json"),
    })
    assert r.returncode == 0, r.stderr
    assert "lint artifact diff" not in r.stderr


def test_gate_lint_per_pass_budget_violation(fixtures):
    """ISSUE 17: the LINT leg pins a per-pass wall-time budget over
    `--bench --format json` — an impossibly small budget must trip it
    on the real analyzer run, naming the offending pass."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_LINT_CURRENT": "",
        "PERF_GATE_LINT_PASS_BUDGET_MS": "0.1",
    })
    assert r.returncode != 0
    assert "LINT VIOLATION" in r.stderr
    assert "budget" in r.stderr


def test_gate_lint_budget_skipped_on_smoke_path(fixtures):
    """The pre-produced --current path never runs the analyzer, so
    the per-pass budget must not fire there even when impossibly
    small — otherwise every artifact smoke test would pay the full
    uncached bench."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_LINT_PASS_BUDGET_MS": "0.001",
    })
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# tune leg (ISSUE 16): the self-tuning driver's own drill — the gate
# must prove the sweep finds a planted winner AND refuses a planted
# regression, against a COPY of presets.py (never the real file)
# ---------------------------------------------------------------------------

def test_gate_tune_leg_green(fixtures):
    """Default fixture landscapes: planted-better converges and
    commits; planted-regression refuses and leaves the copy
    byte-identical. Both sweeps are seeded, so this is deterministic."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_TUNE": "1",
    })
    assert r.returncode == 0, r.stderr
    assert "tune: planted winner adopted" in r.stderr
    assert "planted regression refused" in r.stderr
    assert "green" in r.stderr


def _fake_tune_driver(tmp_path):
    """A driver stand-in that 'passes' the planted-better leg (it
    really commits the expected winners via presets_io) and then, on
    its second invocation, claims to have adopted a change in
    regression mode — the shape of a tuner whose verdict gate broke."""
    script = tmp_path / "fake_driver.py"
    script.write_text(
        "import json, os, sys\n"
        "sys.path.insert(0, os.getcwd())\n"
        "args = sys.argv[1:]\n"
        "presets = args[args.index('--presets') + 1]\n"
        f"state = {str(tmp_path / 'state.txt')!r}\n"
        "first = not os.path.exists(state)\n"
        "open(state, 'a').write('x')\n"
        "if first:\n"
        "    from theanompi_tpu.tuning.presets_io import update_presets\n"
        "    update_presets(presets, 'serve',\n"
        "                   {'spec_k': 16, 'kv_dtype': 'int8'})\n"
        "    print(json.dumps({'ok': True, 'committed': True,\n"
        "                      'changed': {'spec_k': 16,\n"
        "                                  'kv_dtype': 'int8'},\n"
        "                      'trials': {'run': 0, 'cached': 0}}))\n"
        "else:\n"
        "    print(json.dumps({'ok': True, 'committed': True,\n"
        "                      'changed': {'spec_k': 0},\n"
        "                      'trials': {'run': 0, 'cached': 0}}))\n"
    )
    return str(script)


def test_gate_tune_leg_detects_adopted_regression(fixtures, tmp_path):
    """A tuner that commits anything in regression mode is a broken
    gate — the structure check must fail the round."""
    base, good, _ = fixtures
    fake = _fake_tune_driver(tmp_path)
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_TUNE": "1",
        "PERF_GATE_TUNE_CMD": f"python {fake}",
    })
    assert r.returncode != 0
    assert "TUNE VIOLATION" in r.stderr
    assert "ADOPTED" in r.stderr


def test_gate_tune_leg_detects_missed_winner(fixtures, tmp_path):
    """A sweep that completes without committing the planted winner
    (here: a driver that refuses everything) fails the better leg."""
    base, good, _ = fixtures
    script = tmp_path / "no_commit.py"
    script.write_text(
        "import json\n"
        "print(json.dumps({'ok': True, 'committed': False,\n"
        "                  'changed': {},\n"
        "                  'trials': {'run': 0, 'cached': 0}}))\n"
    )
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_TUNE": "1",
        "PERF_GATE_TUNE_CMD": f"python {script}",
    })
    assert r.returncode != 0
    assert "TUNE VIOLATION" in r.stderr
    assert "did not commit" in r.stderr


def test_gate_tune_leg_skippable(fixtures):
    """PERF_GATE_TUNE=0 restores the pre-tuning gate behavior."""
    base, good, _ = fixtures
    r = _run_gate({
        "PERF_GATE_BENCH_JSON": good,
        "PERF_GATE_BASELINE": base,
        "PERF_GATE_WATCHDOG": "0",
        "PERF_GATE_FAILOVER": "0",
        "PERF_GATE_TUNE": "0",
    })
    assert r.returncode == 0, r.stderr
    assert "tune drill" not in r.stderr
