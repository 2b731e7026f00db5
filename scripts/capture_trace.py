#!/usr/bin/env python
"""Capture a jax.profiler trace of the flagship AlexNet BSP step.

Usage: python scripts/capture_trace.py [outdir] [config_overrides_json]

The Perfetto half of the dump (``*.trace.json.gz``) is plain JSON —
``scripts/analyze_trace.py`` aggregates it into a per-op time table so
the hot spots are readable without TensorBoard.

Runs on the platform its process is given.  On the chip (sent through
the chip tool, one process) it traces the real size and writes under
``chiprun_out/`` so the trace comes back; with ``JAX_PLATFORMS=cpu`` it
shrinks to a smoke of the same code path under ``.scratch/``.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from theanompi_tpu.models.alex_net import AlexNet
from theanompi_tpu.ops import platform
from theanompi_tpu.runtime.mesh import make_mesh, shard_batch


def main():
    on_cpu = not platform.on_tpu()
    # CPU smokes must not land next to real-chip traces
    outdir = sys.argv[1] if len(sys.argv) > 1 else (
        ".scratch/trace_cpu_smoke" if on_cpu else "chiprun_out/trace")
    overrides = json.loads(sys.argv[2]) if len(sys.argv) > 2 else {}
    mesh = make_mesh()
    cfg = dict(
        # full-size AlexNet steps take many seconds EACH on the CPU —
        # shrink there so the smoke path finishes
        batch_size=64 if on_cpu else 512,
        compute_dtype="bfloat16",
        lr=1e-3,
        n_synth_batches=2 if on_cpu else 8,
        print_freq=10_000,
    )
    cfg.update(overrides)  # update, not **: overrides may replace defaults
    model = AlexNet(config=cfg, mesh=mesh)
    n_warm, n_trace = (2, 3) if on_cpu else (10, 20)
    train_fn = model.compile_train()
    batches = [shard_batch(mesh, b) for b in model.data.train_batches()]
    p, s, o = model.params, model.net_state, model.opt_state
    keys = list(jax.random.split(jax.random.PRNGKey(0), 64))

    def step(p, s, o, i):
        x, y = batches[i % len(batches)]
        return train_fn(p, s, o, x, y, keys[i % len(keys)])

    for i in range(n_warm):  # compile + steady-state warmup outside the trace
        p, s, o, loss, err = step(p, s, o, i)
    jax.block_until_ready(loss)

    os.makedirs(outdir, exist_ok=True)
    with jax.profiler.trace(outdir):
        t0 = time.perf_counter()
        for i in range(n_trace):
            p, s, o, loss, err = step(p, s, o, i)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    print(f"traced {n_trace} steps in {dt:.3f}s -> {dt / n_trace * 1e3:.2f} "
          f"ms/step ({n_trace * model.global_batch / dt:.0f} img/s) -> {outdir}")


if __name__ == "__main__":
    main()
