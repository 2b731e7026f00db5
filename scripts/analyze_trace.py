#!/usr/bin/env python
"""Aggregate a committed jax.profiler Perfetto trace into a per-op time
table — readable without TensorBoard.

Usage: python scripts/analyze_trace.py [trace_dir_or_json_gz] [top_n]

Works on the ``*.trace.json.gz`` half of a profiler dump (plain JSON);
sums complete ('X') events on the device pid's "XLA Ops" thread, so
module-level and async-overlay rows don't double-count.

Defaults to the committed ``docs/perf/trace_r2`` (one chip, before PR
1).  A new trace comes from ``scripts/capture_trace.py`` sent through
the chip tool; tracing on the v5e behind it: not yet tried.
"""

import collections
import glob
import gzip
import json
import os
import sys


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                            recursive=True))
    if not hits:
        sys.exit(f"no *.trace.json.gz under {path}")
    return hits[-1]


def main():
    path = find_trace(sys.argv[1] if len(sys.argv) > 1 else "docs/perf/trace_r2")
    top_n = int(sys.argv[2]) if len(sys.argv) > 2 else 30
    d = json.load(gzip.open(path, "rt"))
    ev = d["traceEvents"]

    device_pids = {
        e["pid"]
        for e in ev
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and "TPU" in (e["args"].get("name") or "")
    }
    ops_tids = {
        (e["pid"], e["tid"])
        for e in ev
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and (not device_pids or e["pid"] in device_pids)  # CPU traces
        and e["args"].get("name") == "XLA Ops"
    }
    module_tids = {
        (e["pid"], e["tid"])
        for e in ev
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and (not device_pids or e["pid"] in device_pids)
        and e["args"].get("name") == "XLA Modules"
    }
    agg, cnt_per_tid = collections.Counter(), collections.Counter()
    modules_per_tid = collections.Counter()
    ops_tids_seen = set()
    total = 0.0
    for e in ev:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        if key in ops_tids:
            ms = e.get("dur", 0) / 1e3
            agg[e["name"]] += ms
            cnt_per_tid[(key, e["name"])] += 1
            ops_tids_seen.add(key)
            total += ms
        elif key in module_tids:
            modules_per_tid[key] += 1
    # A multi-device trace mirrors the SAME step on every device: both
    # the step count (module executions) and the op sums accumulate once
    # per device. Normalize BOTH sides to one device — steps = the max
    # per-(pid,tid) module count (not the sum across tids), and ms sums
    # divided by the number of DEVICES (distinct pids) that produced ops
    # events — so ms/step stays device-count invariant and comparable to
    # the pinned single-device r2 budget. NOT max per-op count for
    # steps: loop bodies (grad_accum scans etc.) fire one op name many
    # times/step. NOT (pid,tid) ops-thread tuples for the divisor: a
    # device exposing several ops threads (or idle ops tids emitting no
    # events) would under/over-normalize (ADVICE r5 item 4).
    steps = (max(modules_per_tid.values()) if modules_per_tid else 0) or (
        max(cnt_per_tid.values()) if cnt_per_tid else 1)
    n_dev = max(1, len({pid for pid, _tid in ops_tids_seen}))
    norm = steps * n_dev
    print(f"{path}: {total:.1f} ms busy over ~{steps} steps"
          + (f" x {n_dev} devices" if n_dev > 1 else "")
          + f" = {total / norm:.3f} ms/step")
    run = 0.0
    for name, ms in agg.most_common(top_n):
        run += ms
        print(f"{ms / norm:7.3f} ms/step {100 * ms / total:5.1f}% "
              f"cum{100 * run / total:5.1f}%  {name[:90]}")


if __name__ == "__main__":
    main()
