#!/usr/bin/env python
"""The quickest proof that the program still starts on the chip.

    python chip_smoke.py            # one TPU chip: train, serve, kernels
    python chip_smoke.py --chips 4  # four chips: BSP dp=4 against one chip

One process, no subprocess, no probe, no retry: JAX is imported once,
``jax.devices()[0].platform`` must be ``"tpu"`` (anything else exits
non-zero with the reason), and the phases run through the entry points a
user calls.  The first failing check raises and ends the run — nothing
here catches a phase's failure.

- *train* — ``theanompi_tpu.BSP().init(devices=1, ...).wait()`` on
  AlexNet at full width (every layer, 128 px, 1000 classes, per-chip
  batch 512, bf16), synthetic data from the seed, two short epochs each
  with its validation pass.
- *serve* — ``TransformerLM`` at ``bench_serve.py``'s real knobs behind
  ``PagedServingEngine`` → ``ContinuousBatchingScheduler`` with the
  Pallas decode kernel, eight seeded requests of mixed prompt length;
  the interleaved run must return the tokens of the same requests served
  one at a time and of an engine with the XLA gather.
- *kernels* — every Pallas kernel the package ships, compiled by Mosaic,
  against its XLA oracle (``theanompi_tpu/ops/kernel_cases.py``).
- ``--chips 4`` runs ONLY the path that exists across chips and what it
  is compared with: the same AlexNet BSP job over ``dp=4`` and over one
  of the four devices at the same global batch.

Earlier lines carry observations (wall times, compile counts — not
metrics).  The LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The phase functions take a ``size`` argument that only the tests pass
(``"tiny"``, under ``JAX_PLATFORMS=cpu``); the script has no option for it.
"""

from __future__ import annotations

import argparse
import json
import logging
import re
import sys
import time

import jax
import numpy as np

ALEXNET = dict(
    modelfile="theanompi_tpu.models.alex_net", modelclass="AlexNet"
)
# full width: every layer of models/alex_net.py at its width; depth is
# not cut either — the model is small.  lr as in bench.py (synthetic
# data diverges at the ImageNet default).
TRAIN_CFG = {
    "real": dict(batch_size=512, image_size=128, n_classes=1000,
                 compute_dtype="bfloat16"),
    # fp32: on the CPU a bf16 config keeps fp32 conv operands and only
    # narrows outputs (ops.layers), which is neither the chip's math nor
    # tight enough for the 1-vs-N tolerance at 16 examples a step
    "tiny": dict(batch_size=4, image_size=64, n_classes=8),
}
TRAIN_COMMON = dict(
    lr=1e-3, n_epochs=2, n_synth_batches=3, n_synth_val_batches=1,
    print_freq=1, comm_probe=False,
)
# bench_serve.py's _KNOBS_REAL, by name
SERVE_CFG = {
    "real": dict(d_model=512, n_heads=8, n_layers=8, vocab_size=4096,
                 max_len=1024, block_size=32, n_slots=32, n_blocks=257,
                 prefill_chunk=256,
                 prompt_lens=(12, 300, 40, 100, 200, 24, 64, 150),
                 new_tokens=(12, 8, 16, 1, 10, 12, 6, 9)),
    "tiny": dict(d_model=32, n_heads=4, n_layers=2, vocab_size=64,
                 max_len=64, block_size=8, n_slots=4, n_blocks=33,
                 prefill_chunk=16,
                 prompt_lens=(3, 30, 1, 4, 6, 12, 20, 9),
                 new_tokens=(7, 5, 9, 1, 4, 6, 3, 5)),
}


def say(phase: str, **observations) -> None:
    """One observation line (never the last line of the run)."""
    print(json.dumps({"phase": phase, **observations}), flush=True)


class CompileCount(logging.Handler):
    """XLA programs built in a span, and which of them the persistent
    compile cache served — by name, from jax's own (debug) log line, so
    a warm run can be seen to fetch ``jit_shard_step`` rather than
    compile it."""

    KEYS = ("xla_programs_total", "xla_cache_hits_total")
    LOGGER = logging.getLogger("jax._src.compiler")
    HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")

    def emit(self, record):
        hit = self.HIT.match(record.getMessage())
        if hit:
            self.hits.append(hit.group(1))
        if record.levelno >= logging.WARNING:
            # the span keeps jax's debug chatter to itself (a host whose
            # root logger prints everything would drown stderr) but
            # still passes on what the logger would have said anyway
            self.LOGGER.parent.handle(record)

    def _read(self):
        from theanompi_tpu import observability as obs

        return [obs.counter_values().get(k, 0.0) for k in self.KEYS]

    def __enter__(self):
        self.hits = []
        self._was = self.LOGGER.level, self.LOGGER.propagate
        self.LOGGER.setLevel(logging.DEBUG)
        self.LOGGER.propagate = False
        self.LOGGER.addHandler(self)
        self._t0, self._base = time.perf_counter(), self._read()
        return self

    def __exit__(self, *exc):
        self.LOGGER.removeHandler(self)
        level, self.LOGGER.propagate = self._was
        self.LOGGER.setLevel(level)
        programs, hits = (
            int(b - a) for a, b in zip(self._base, self._read())
        )
        self.seen = dict(
            seconds=round(time.perf_counter() - self._t0, 2),
            xla_programs=programs, xla_cache_hits=hits,
            cache_hits_for=sorted(set(self.hits)),
        )


def _bsp_job(devices, model_config, **worker_kwargs):
    """The user's three lines: rule, init, wait."""
    import theanompi_tpu

    rule = theanompi_tpu.BSP()
    rule.init(devices=devices, model_config=model_config, **ALEXNET,
              **worker_kwargs)
    return rule


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(size: str = "real", seed: int = 0) -> dict:
    cfg = dict(TRAIN_CFG[size], **TRAIN_COMMON, seed=seed)
    with CompileCount() as cc:
        rule = _bsp_job(1, cfg, val_freq=1)
        model = rule.wait()
    rec = rule.worker.recorder
    n_epochs, n_batches = cfg["n_epochs"], cfg["n_synth_batches"]

    losses = [row["cost"] for row in rec.history]
    assert len(losses) == n_epochs * n_batches, losses
    assert np.isfinite(losses).all(), f"train losses not finite: {losses}"
    vals = [row["cost"] for row in rec.val_history]
    assert len(vals) == n_epochs and np.isfinite(vals).all(), vals
    epochs = [e for e in rec.events if e["kind"] == "epoch"]
    assert [e["epoch"] for e in epochs] == list(range(n_epochs)), epochs
    assert model.current_epoch == n_epochs

    device = jax.devices()[0]
    for leaf in jax.tree.leaves((model.params, model.opt_state)):
        assert leaf.devices() == {device}, (
            f"a parameter lives on {leaf.devices()}, not on {device}"
        )
    # epoch 0 compiles the step, the validation pass and the recorder's
    # scalar adds; the second epoch must find every program built
    built = [e["counters"].get("xla_programs_total", 0) for e in epochs]
    assert built[0] > 0 and built[1] == 0, (
        f"XLA programs built per epoch: {built} — the second epoch "
        "compiled something"
    )
    return dict(
        cc.seen, losses=[round(v, 4) for v in losses],
        val_losses=[round(v, 4) for v in vals],
        epoch_seconds=[e["seconds"] for e in epochs],
        n_params=model.n_params,
    )


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _serve_requests(k: dict, seed: int):
    rng = np.random.RandomState(seed)
    return [
        (f"r{i}", rng.randint(0, k["vocab_size"], size=n).tolist(), m)
        for i, (n, m) in enumerate(zip(k["prompt_lens"], k["new_tokens"]))
    ]


def _serve(engine, requests, interleaved: bool) -> dict:
    from theanompi_tpu.serving import ContinuousBatchingScheduler, Request

    def submit(sched, rid, prompt, n):
        sched.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=n))

    if interleaved:
        sched = ContinuousBatchingScheduler(engine)
        for r in requests:
            submit(sched, *r)
        return sched.run()
    out = {}
    for r in requests:
        sched = ContinuousBatchingScheduler(engine)
        submit(sched, *r)
        out.update(sched.run())
    return out


def _traces(engine):
    return (engine._n_prefill_traces, engine._n_decode_traces,
            engine._n_verify_traces)


def serve_phase(size: str = "real", seed: int = 0) -> dict:
    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.ops import platform
    from theanompi_tpu.serving import PagedServingEngine

    k = SERVE_CFG[size]
    cfg = dict(
        seq_len=k["max_len"], vocab_size=k["vocab_size"],
        d_model=k["d_model"], n_heads=k["n_heads"], n_layers=k["n_layers"],
        batch_size=1, n_synth_train=2, n_synth_val=1, comm_probe=False,
        print_freq=10_000, seed=seed,
    )
    # one server on one chip: the pool is a single-device pool, which
    # is what the fused kernel serves
    model = TransformerLM(
        config=cfg,
        mesh=TransformerLM.build_mesh(devices=jax.devices()[:1], config=cfg),
    )

    def engine(paged_attn):
        return PagedServingEngine(
            model, n_slots=k["n_slots"], max_len=k["max_len"],
            block_size=k["block_size"], n_blocks=k["n_blocks"],
            prefill_chunk=k["prefill_chunk"], paged_attn=paged_attn,
        )

    requests = _serve_requests(k, seed)
    assert max(k["prompt_lens"]) > k["prefill_chunk"]  # one multi-chunk
    want_lens = {rid: n for rid, _, n in requests}
    seen = {}

    # (1) the configuration users run — backend-default matmul
    # precision: continuous batching must not change anyone's tokens
    pallas = engine("pallas")
    assert pallas.paged_attn_effective == "pallas"
    with CompileCount() as cc:
        inter = _serve(pallas, requests, interleaved=True)
    seen["default_precision"] = cc.seen
    assert {r: len(t) for r, t in inter.items()} == want_lens, inter
    assert all(
        0 <= t < k["vocab_size"] for toks in inter.values() for t in toks
    )
    # the request mix above compiled every program it needs: a second
    # scheduler over the same engine must add no trace (shapes are data)
    before = _traces(pallas)
    again = _serve(pallas, requests, interleaved=True)
    assert _traces(pallas) == before, (
        f"a second scheduler retraced: {before} -> {_traces(pallas)}"
    )
    assert again == inter
    serial = _serve(pallas, requests, interleaved=False)
    assert inter == serial, _first_diff("interleaved", inter, "serial", serial)

    # (2) Pallas decode against the XLA gather.  The MXU's default
    # fp32 matmul is bf16 passes, grouped differently by the two paths
    # (~3e-3 on the attention output), which greedy argmax over random
    # weights does not survive; at "highest" both are fp32-exact and
    # token identity — the serving path's contract — is decidable.
    # The setting reaches the dots inside the kernel body too.
    with jax.default_matmul_precision("highest"), CompileCount() as cc:
        hi_pallas = _serve(pallas, requests, interleaved=True)
        hi_serial = _serve(pallas, requests, interleaved=False)
        hi_xla = _serve(engine("xla"), requests, interleaved=True)
    seen["highest_precision"] = cc.seen
    assert hi_pallas == hi_serial, _first_diff(
        "interleaved", hi_pallas, "serial", hi_serial)
    assert hi_pallas == hi_xla, _first_diff(
        "pallas", hi_pallas, "xla gather", hi_xla)

    if platform.on_tpu():
        # compiled, not interpreted: the decode program the scheduler
        # ran carries the Mosaic custom call
        state = jax.eval_shape(pallas.init_state)
        s, nb = k["n_slots"], pallas.blocks_per_seq

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, np.int32)

        text = pallas._paged_decode_jit.lower(
            model.params, state, i32(s), i32(s, nb), i32(s),
            jax.ShapeDtypeStruct((s,), np.bool_),
        ).as_text()
        assert "tpu_custom_call" in text, "decode kernel was not compiled"
    return dict(
        seen, requests=len(requests),
        tokens=sum(len(t) for t in inter.values()),
        same_tokens_default_vs_highest=sum(
            inter[r] == hi_pallas[r] for r in inter),
        traces=dict(zip(("prefill", "decode", "verify"), _traces(pallas))),
    )


def _first_diff(a_name, a, b_name, b) -> str:
    for rid in a:
        if a[rid] != b.get(rid):
            return (f"request {rid}: {a_name} {a[rid]} != "
                    f"{b_name} {b.get(rid)}")
    return f"{a_name} and {b_name} answered different requests"


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernels_phase(size: str = "real", seed: int = 0) -> dict:
    from theanompi_tpu.ops import kernel_cases

    with CompileCount() as cc:
        results = [
            kernel_cases.check_case(case, seed=seed)
            for case in kernel_cases.cases(size)
        ]
    return dict(
        cc.seen, cases=len(results),
        compiled=sum(r["compiled"] for r in results),
        max_abs_err={r["case"]: float(f"{r['max_abs_err']:.3g}")
                     for r in results},
    )


# ---------------------------------------------------------------------------
# four chips: BSP dp=4 against one chip
# ---------------------------------------------------------------------------

def multichip_phase(size: str = "real", seed: int = 0) -> dict:
    """The same AlexNet BSP job over dp=4 and over ONE of the four devices,
    from the same seed at the same global batch (4 × 512 against 2048 on
    one — one v5e chip holds the 2048 step in 4 GB, compiled for a
    described chip in PR 21), ``lr_linear_scaling=False`` so both take
    the same step.  Dropout is off: its masks are drawn per shard."""
    from theanompi_tpu.runtime.mesh import shard_batch

    n = 4
    devices = jax.devices()
    assert len(devices) >= n, f"need {n} devices, have {len(devices)}"
    per_chip = TRAIN_CFG[size]["batch_size"]
    common = {
        **TRAIN_CFG[size], **TRAIN_COMMON, "seed": seed, "n_epochs": 1,
        "dropout_rate": 0.0, "lr_linear_scaling": False,
    }

    def run(devs, batch_size):
        rule = _bsp_job(devs, dict(common, batch_size=batch_size),
                        val_freq=0)
        model = rule.wait()
        losses = [row["cost"] for row in rule.worker.recorder.history]
        assert len(losses) == common["n_synth_batches"], losses
        # where the run's loader put a batch and its parameters — code
        # that never saw a second chip may put everything on the first
        mesh_devices = set(model.mesh.devices.flat)
        assert len(mesh_devices) == len(devs)
        x, y = shard_batch(
            model.mesh, next(iter(model.data.train_batches())),
            spec=model.batch_spec,
        )
        assert x.addressable_shards[0].data.shape[0] == batch_size
        for arr in (x, y, *jax.tree.leaves(model.params)):
            on = {s.device for s in arr.addressable_shards}
            assert on == mesh_devices, (
                f"shards on {len(on)} device(s), mesh has {len(devs)}"
            )
        # what the step the run just took exchanges
        text = model.train_fn.lower(
            model.params, model.net_state, model.opt_state, x, y,
            jax.random.PRNGKey(0),
        ).compile().as_text()
        n_all_reduce = len(re.findall(r"all-reduce(?:-start)?\(", text))
        return losses, jax.device_get(model.params), n_all_reduce

    with CompileCount() as cc:
        losses_n, params_n, ar_n = run(devices[:n], per_chip)
        losses_1, params_1, ar_1 = run(devices[:1], per_chip * n)
    assert ar_n > 0, "the dp step compiled without an all-reduce"
    assert np.isfinite(losses_n).all() and np.isfinite(losses_1).all()
    # tests/test_bsp.py's 1-vs-N tolerances: the first step's loss (the
    # same parameters on both sides), then the parameters three steps on
    np.testing.assert_allclose(losses_n[0], losses_1[0], rtol=2e-4)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(params_n), jax.tree.leaves(params_1),
                    strict=True):
        np.testing.assert_allclose(a, b, atol=2e-5)
        worst = max(worst, float(np.max(np.abs(a - b))))
    return dict(
        cc.seen, dp=n, global_batch=per_chip * n,
        losses_dp=[round(v, 5) for v in losses_n],
        losses_one=[round(v, 5) for v in losses_1],
        max_param_diff=float(f"{worst:.3g}"),
        all_reduces=dict(dp=ar_n, one=ar_1),
    )


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(
            f"chip_smoke: jax.devices()[0].platform is {first.platform!r}, "
            "not 'tpu' — this script proves the program on the chip and "
            "has no CPU fallback",
            file=sys.stderr,
        )
        return 1
    if len(devices) != args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} "
            "device(s)",
            file=sys.stderr,
        )
        return 1

    from theanompi_tpu import observability as obs
    from theanompi_tpu.cachedir import configure_compile_cache

    cache = configure_compile_cache(jax)
    obs.count_xla_compiles()
    say("start", compile_cache=cache, chips=args.chips, seed=args.seed)

    if args.chips == 4:
        say("multichip", **multichip_phase(seed=args.seed))
    else:
        say("train", **train_phase(seed=args.seed))
        say("serve", **serve_phase(seed=args.seed))
        say("kernels", **kernels_phase(seed=args.seed))

    say("done", wall_seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({
        "ok": True,
        "device": {"platform": first.platform, "kind": first.device_kind,
                   "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
