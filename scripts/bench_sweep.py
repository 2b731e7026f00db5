#!/usr/bin/env python
"""Sweep AlexNet step-time knobs on the chip (perf exploration; bench.py
stays the canonical single-number harness).  Send it through the chip
tool as ONE command: `python scripts/bench_sweep.py [config ...]`."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from theanompi_tpu.models.alex_net import AlexNet
from theanompi_tpu.runtime.mesh import make_mesh, shard_batch

# ONE cache policy for the whole repo (theanompi_tpu/cachedir.py)
from theanompi_tpu.cachedir import configure_compile_cache

configure_compile_cache(jax)


def measure(cfg_overrides, steps=120):
    mesh = make_mesh()
    model = AlexNet(
        config=dict(
            batch_size=512,
            compute_dtype="bfloat16",
            lr=1e-3,
            n_synth_batches=8,
            print_freq=10_000,
            **cfg_overrides,
        ),
        mesh=mesh,
    )
    train_fn = model.compile_train()
    batches = [shard_batch(mesh, b) for b in model.data.train_batches()]
    p, s, o = model.params, model.net_state, model.opt_state
    keys = list(jax.random.split(jax.random.PRNGKey(0), 256))

    def step(p, s, o, i):
        x, y = batches[i % len(batches)]
        return train_fn(p, s, o, x, y, keys[i % len(keys)])

    for i in range(8):
        p, s, o, loss, err = step(p, s, o, i)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        p, s, o, loss, err = step(p, s, o, i)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return steps * model.global_batch / dt


if __name__ == "__main__":
    from theanompi_tpu.utils.benchmark import PERF_SWEEP_CONFIGS

    configs = [(name, dict(cfg)) for name, cfg in PERF_SWEEP_CONFIGS]
    # Every config runs in THIS process, one after another: a chip
    # belongs to one process at a time, so the sweep starts no child
    # (the rule "a parent that spawns a bench stays off jax" holds
    # trivially — nothing is spawned).  Name configs to run a subset.
    only = sys.argv[1:] or None
    if only:
        known = {name for name, _ in configs}
        bad = [a for a in only if a not in known]
        if bad:
            sys.exit(f"unknown config(s) {bad}; choose from {sorted(known)}")
    for name, cfg in configs:
        if only and name not in only:
            continue
        ips = measure(cfg)
        print(f"{name:16s} {ips:10.0f} img/s", flush=True)
