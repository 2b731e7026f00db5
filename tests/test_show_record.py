"""The record-inspection script (reference's show_record analog,
SURVEY §3.7): loads the Recorder's JSONL, renders curves, and surfaces
the structured event rows (comm-fraction probe, memory, async wire)."""

import importlib.util
import json
import os
import sys


def _load_module():
    p = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "show_record.py",
    )
    spec = importlib.util.spec_from_file_location("show_record", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_record(path):
    rows = [
        {"kind": "comm_fraction", "frac": 0.25, "n_dp": 8},
        {"kind": "async_wire", "dtype": "float16", "n_exchanges": 12},
        {"kind": "train", "iter": 10, "cost": 2.0, "error": 0.9,
         "calc": 1.0, "comm": 0.1, "wait": 0.0, "load": 0.0},
        {"kind": "train", "iter": 20, "cost": 1.5, "error": 0.7,
         "calc": 1.0, "comm": 0.1, "wait": 0.0, "load": 0.0},
        {"kind": "val", "iter": 20, "cost": 1.6, "error": 0.8,
         "error_top5": 0.3},
    ]
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_load_splits_kinds(tmp_path):
    mod = _load_module()
    p = str(tmp_path / "record.jsonl")
    _write_record(p)
    train, val, events = mod.load(p)
    assert [r["iter"] for r in train] == [10, 20]
    assert len(val) == 1
    assert {e["kind"] for e in events} == {"comm_fraction", "async_wire"}


def test_main_renders_and_prints_events(tmp_path, capsys, monkeypatch):
    import pytest

    pytest.importorskip("matplotlib")  # PNG assertion needs the renderer
    mod = _load_module()
    p = str(tmp_path / "record.jsonl")
    _write_record(p)
    out_png = str(tmp_path / "out.png")
    monkeypatch.setattr(sys, "argv", ["show_record.py", p, out_png])
    mod.main()
    captured = capsys.readouterr().out
    assert "[comm_fraction]" in captured and "frac=0.25" in captured
    assert "[async_wire]" in captured and "dtype=float16" in captured
    # matplotlib is present in this environment: a PNG must land
    assert os.path.exists(out_png) and os.path.getsize(out_png) > 0


def test_analyze_trace_reproduces_r2_op_budget():
    """scripts/analyze_trace.py is the op-level attribution path; pin
    its aggregation against the committed r2 chip trace."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trace = os.path.join(repo, "docs", "perf", "trace_r2")
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "analyze_trace.py"),
         trace, "5"],
        capture_output=True, text=True, timeout=120, cwd=repo,
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = out.stdout.strip().splitlines()
    # 30 traced steps at ~11.15 ms/step busy
    assert "~30 steps" in lines[0] and "11.15" in lines[0]
    # the top op is the LRN1 bwd banded matmul at ~9.6% of busy time
    assert "fusion.545" in lines[1] and "9.6%" in lines[1]
    assert len(lines) == 6  # header + top_n rows


def test_analyze_trace_counts_steps_per_device_not_summed(tmp_path):
    """Advisor r4 low: a multi-device trace runs the same step once per
    device; summing module events across ALL module tids inflated the
    step count (and deflated ms/step) by the device count. Steps must be
    the per-(pid,tid) max."""
    import gzip
    import json
    import subprocess
    import sys

    def meta(pid, tid, name, kind):
        e = {"ph": "M", "pid": pid, "name": kind,
             "args": {"name": name}}
        if tid is not None:
            e["tid"] = tid
        return e

    ev = []
    for pid in (1, 2):  # two devices
        ev.append(meta(pid, None, f"TPU:{pid}", "process_name"))
        ev.append(meta(pid, 10, "XLA Ops", "thread_name"))
        ev.append(meta(pid, 20, "XLA Modules", "thread_name"))
        for step in range(3):  # 3 steps, mirrored on both devices
            ev.append({"ph": "X", "pid": pid, "tid": 20,
                       "name": "jit_step", "ts": step * 100, "dur": 90})
            ev.append({"ph": "X", "pid": pid, "tid": 10,
                       "name": "fusion.1", "ts": step * 100, "dur": 80_000})
    trace = tmp_path / "t.trace.json.gz"
    with gzip.open(trace, "wt") as f:
        json.dump({"traceEvents": ev}, f)

    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "analyze_trace.py"),
         str(trace), "3"],
        capture_output=True, text=True, timeout=120, cwd=repo,
    )
    assert out.returncode == 0, out.stderr[-500:]
    head = out.stdout.strip().splitlines()[0]
    # 6 ops x 80ms = 480ms busy, mirrored on 2 devices over 3 steps:
    # per-device per-step = 480 / (3 x 2) = 80 ms — the same number a
    # single-device trace of this workload would report
    assert "~3 steps x 2 devices" in head, head
    assert "80.000 ms/step" in head, head
