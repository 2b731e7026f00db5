#!/usr/bin/env python3
"""Readings that the limits of a cell's check of outputs are set from.

    python3 benchmarks/calibrate.py --workload <name> --seeds 1,2,3 --control-seeds 1,2,3

In one process, for each seed: the program's numbers against the plain
reference (the lower reading), and for each control seed the control's
and every planted fault's numbers against the same reference (the upper
readings).  What is read, and how, is the cell's driver's business
(``calibrate`` in its file).  Prints one JSON line per reading and writes
them all to ``chiprun_out/calibrate-<workload>.jsonl``.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None, rehearsal=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--reference-only", action="store_true",
                    help="no program run: the control's and the faults' "
                    "readings alone, on one chip at the cell's own size")
    args = ap.parse_args(argv)
    ints = lambda s: [int(v) for v in s.split(",") if v]
    cell = run.load_cell(args.workload)
    if rehearsal is not None:
        run.apply_rehearsal(cell, rehearsal)
    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    with open(os.path.join(out_dir, f"calibrate-{args.workload}.jsonl"), "a") as f:
        def emit(**row):
            rows.append(row)
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        def context(seed):
            return run.make_context(cell, seed, rehearsal,
                                    need_chips=not args.reference_only)[0]

        driver_mod = run.load_module("drivers", cell.traffic["driver"])
        driver_mod.calibrate(context, ints(args.seeds),
                             ints(args.control_seeds), emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
