#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line for the driver.

Metric (BASELINE.md): ImageNet images/sec/chip on the flagship AlexNet
ImageNet-128px BSP configuration. Protocol per BASELINE.md: warmup steps
excluded, compile excluded, `block_until_ready` fenced, per-chip img/s =
global_throughput / chips.

``detail`` additionally carries the roofline view (VERDICT r2 #2):
``flops_per_step_per_chip`` from XLA's own cost analysis of the
compiled step, ``tflops_sustained_per_chip``, and ``mfu_pct`` against
the detected chip's bf16 peak — so cross-round progress is judged
against the hardware ceiling, not only against last round's number. It also carries ``efficiency``
(VERDICT r2 #4): the BASELINE scaling-efficiency curve via
``utils.benchmark.scaling_efficiency`` whenever more than one chip is
visible, else the trivial 1-chip row.

``vs_baseline`` is 1.0: the reference's published numbers are not
recoverable in this environment (BASELINE.json `published: {}` — see
BASELINE.md), so there is no external denominator; cross-PR progress
is tracked by the driver's ledger.

Chip or fail: without ``THEANOMPI_BENCH_CPU=1`` the bench requires
``jax.devices()[0].platform == "tpu"`` and exits non-zero otherwise;
a failed candidate or efficiency phase fails the run.  One process:
nothing here starts a child, so the chip is never held twice.
"""

import json
import os
import sys
import time

# CPU rehearsal (VERDICT r3 #2): with THEANOMPI_BENCH_CPU=1 the platform
# is pinned to an 8-fake-device CPU mesh and every window shrinks so the
# SAME assembled main() runs end-to-end through emit() in seconds — the
# default test suite exercises it (tests/test_benchmark.py).  Without the
# variable the bench requires a TPU and fails fast on anything else: a
# number from another platform is never printed under this metric's name.
# Env must be set before jax imports, hence the placement above
# `import jax`.
CPU_REHEARSAL = os.environ.get("THEANOMPI_BENCH_CPU") == "1"
if CPU_REHEARSAL:
    os.environ["JAX_PLATFORMS"] = "cpu"
    from theanompi_tpu.cachedir import cpu_xla_flags

    os.environ["XLA_FLAGS"] = cpu_xla_flags(os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp


def _require_tpu() -> None:
    """Chip or fail: one in-process look at the device, no probe child
    (a child that touched the chip would hold it against this process),
    no retry, no stand-in number."""
    platform = jax.devices()[0].platform
    if not CPU_REHEARSAL and platform != "tpu":
        sys.exit(
            f"[bench] platform is {platform!r}, not 'tpu' — set "
            "THEANOMPI_BENCH_CPU=1 for the CPU rehearsal"
        )


def emit(value: float, vs_baseline: float, detail: dict) -> None:
    """THE one JSON line the driver parses."""
    print(
        json.dumps(
            {
                "metric": "alexnet128_bsp_images_per_sec_per_chip",
                "value": round(value, 2),
                "unit": "images/sec/chip",
                "vs_baseline": vs_baseline,
                "detail": detail,
            }
        )
    )


# approximate bf16 peak TFLOP/s per chip by device_kind substring —
# roofline denominators, not guarantees (public spec-sheet numbers)
_PEAK_BF16_TFLOPS = (
    ("v6 lite", 918.0), ("v6e", 918.0),
    ("v5 lite", 197.0), ("v5e", 197.0),
    ("v5p", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 46.0),
)


def _peak_tflops(device_kind: str):
    """(peak, source) for the roofline denominator.  A device kind that
    is not in the table is an error, not a default: a guessed peak
    would put a wrong MFU under a real device's name."""
    kind = device_kind.lower()
    for key, peak in _PEAK_BF16_TFLOPS:
        if key in kind:
            return peak, key
    if kind == "cpu":
        return None, None  # rehearsal rig: no roofline to speak of
    raise ValueError(
        f"device_kind {device_kind!r} matches no entry of "
        "_PEAK_BF16_TFLOPS — add its published bf16 peak"
    )


def _flops_per_step(train_fn, example_args):
    """Per-step FLOPs from XLA's cost analysis of the compiled step —
    the analytic numerator for MFU, computed by the compiler (not
    hand-math in a doc, per VERDICT r2 weak #2)."""
    cost = train_fn.lower(*example_args).compile().cost_analysis()
    f = float(cost.get("flops", 0.0))
    return f if f > 0 else None


def _efficiency_curve(n_chips: int, per_chip_value: float, knobs: dict):
    """BASELINE.md's second metric: efficiency(N) = per-chip img/s at N
    ÷ per-chip img/s at 1. With one visible chip the curve is the
    trivial row; with more, measure the real 1→N curve."""
    if n_chips <= 1:
        return [
            {
                "devices": 1,
                "images_per_sec": round(per_chip_value, 2),
                "per_chip": round(per_chip_value, 2),
                "efficiency": 1.0,
            }
        ]
    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.utils.benchmark import scaling_efficiency

    counts = [n for n in (1, 2, 4, 8, 16, 32) if n <= n_chips]
    if counts[-1] != n_chips:
        counts.append(n_chips)
    rows = scaling_efficiency(
        AlexNet,
        dict(
            batch_size=knobs["eff_batch"],
            image_size=knobs["image_size"],
            compute_dtype="bfloat16",
            lr=1e-3,
            n_synth_batches=knobs["n_synth_batches"],
            print_freq=10_000,
        ),
        device_counts=counts,
        n_steps=knobs["eff_steps"],
    )
    return [
        {k: (round(v, 4) if isinstance(v, float) else v) for k, v in r.items()}
        for r in rows
    ]


# every size that differs between the real bench and the CPU rehearsal,
# in one place — the rehearsal must exercise the SAME code path, only
# smaller (VERDICT r3 #2)
_KNOBS_REAL = dict(
    per_chip_bs=512,  # throughput knee from the bs sweep (128→512: +27%)
    image_size=128,
    n_synth_batches=8,
    n_candidates=None,  # all of BENCH_CANDIDATES
    est_steps=12,
    warmup_steps=5,
    calib_steps=25,
    window_target_s=3.0,
    window_min_steps=50,
    eff_batch=256,
    eff_steps=10,
)
_KNOBS_REHEARSAL = dict(
    per_chip_bs=4,
    # 64 is the smallest size that keeps every AlexNet feature map
    # non-degenerate (32 empties the last MaxPool — see MaxPool.init)
    image_size=64,
    n_synth_batches=2,
    # ALL candidates: the scarce TPU window runs every staged config,
    # so every one must have executed end-to-end in rehearsal first
    # (r5: poolbwd's Pallas bwd would otherwise first run on the chip)
    n_candidates=None,
    est_steps=2,
    warmup_steps=1,
    calib_steps=2,
    window_target_s=0.2,
    window_min_steps=3,
    eff_batch=8,
    eff_steps=2,
)


# ---- closed-loop tuning contract (theanompi_tpu/tuning/trials.py) ---------
# The trial harness injects one candidate config via env: a JSON
# knob->value map in THEANOMPI_TUNE_OVERRIDES plus a workload seed in
# THEANOMPI_BENCH_SEED.  The bench applies what it understands, echoes
# the FULL map back in detail.tuning (the harness refuses a trial whose
# echo mismatches — an unapplied knob must never score a candidate),
# and exits loudly on a knob it does not know.
TUNE_SEED = int(os.environ.get("THEANOMPI_BENCH_SEED", "0") or 0)


def _tune_overrides():
    raw = os.environ.get("THEANOMPI_TUNE_OVERRIDES", "")
    if not raw.strip():
        return None
    try:
        overrides = json.loads(raw)
    except ValueError as e:
        print(f"[bench] bad THEANOMPI_TUNE_OVERRIDES json: {e}",
              file=sys.stderr)
        sys.exit(2)
    if not isinstance(overrides, dict):
        print("[bench] THEANOMPI_TUNE_OVERRIDES must be a JSON object",
              file=sys.stderr)
        sys.exit(2)
    return overrides


# every size that differs between the real EASGD arm and its CPU
# rehearsal, one place (same discipline as _KNOBS_*): the rehearsal
# runs the SAME loop, only smaller.  steps_per_worker must clear the
# ladder's top τ (40) or a big-τ candidate never exchanges and the
# registry's required detail.easgd.exchanges check rightly kills it.
_EASGD_KNOBS_REAL = dict(
    model=dict(seq_len=128, vocab_size=256, d_model=128, n_heads=8,
               n_layers=2, batch_size=8),
    n_workers=2,
    steps_per_worker=120,
    warmup_steps=5,
)
_EASGD_KNOBS_REHEARSAL = dict(
    model=dict(seq_len=32, vocab_size=64, d_model=32, n_heads=4,
               n_layers=2, batch_size=2),
    n_workers=2,
    steps_per_worker=44,
    warmup_steps=2,
)


def _easgd_main():
    """The EASGD bench arm (``THEANOMPI_BENCH_RULE=EASGD``): the
    workload the ``easgd`` tuning plan measures ``easgd_tau`` against.

    Simulated workers train a small TransformerLM and exchange with an
    in-process :class:`EasgdServerCore` every τ local steps — the real
    elastic math, membership roster, and the online-learning
    ``CenterPublisher`` cadence (docs/online_learning.md), minus the
    TCP transport.  Everything runs in the MAIN thread, round-robin:
    this rig's CPU client segfaults under threaded jax dispatch, and
    the server core's handler is host-numpy so in-process calls are
    safe.  Headline: aggregate worker steps/sec (its own metric name —
    the driver's history compares like against like, never against the
    BSP images/sec line).
    """
    tune = _tune_overrides()
    tau = 10
    tune_echo = None
    if tune is not None:
        for t_name, t_value in sorted(tune.items()):
            if t_name == "easgd_tau":
                tau = int(t_value)
            else:
                print(f"[bench] unknown EASGD tune override {t_name!r}",
                      file=sys.stderr)
                sys.exit(2)
        tune_echo = {
            "overrides": tune,
            "seed": TUNE_SEED,
            "budget": os.environ.get("THEANOMPI_TUNE_BUDGET", "full"),
            "inert": [],
        }
    knobs = _EASGD_KNOBS_REHEARSAL if CPU_REHEARSAL else _EASGD_KNOBS_REAL
    if os.environ.get("THEANOMPI_TUNE_BUDGET") == "short":
        # successive-halving first rung: half the window, same τ reach
        # (44 > the ladder's top τ=40, so every rung still exchanges)
        knobs = dict(knobs, steps_per_worker=max(44, knobs["steps_per_worker"] // 2))

    from theanompi_tpu import observability as observability
    from theanompi_tpu.observability import live as obs_live

    observability.enable_tracing()
    telemetry = obs_live.maybe_start_from_env("easgd0")
    _require_tpu()
    if CPU_REHEARSAL:
        print(
            f"[bench] CPU rehearsal (EASGD arm): {jax.device_count()} "
            "fake devices, windows shrunk",
            file=sys.stderr,
        )
    from theanompi_tpu.cachedir import configure_compile_cache

    configure_compile_cache(jax)

    import numpy as np

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.parallel.distributed_async import EasgdServerCore
    from theanompi_tpu.runtime.mesh import replicate, shard_batch

    cfg = dict(
        knobs["model"],
        lr=0.05,
        n_synth_train=4,
        n_synth_val=1,
        print_freq=10_000,
    )
    mesh = TransformerLM.build_mesh(config=cfg)
    model = TransformerLM(config=cfg, mesh=mesh)
    train_fn = model.compile_train()
    batches = [shard_batch(mesh, b) for b in model.data.train_batches()]
    keys = list(jax.random.split(jax.random.PRNGKey(TUNE_SEED), 2100))

    n_workers = knobs["n_workers"]
    n_steps = knobs["steps_per_worker"]
    alpha = 0.5
    publish_every = 2  # ≥1 publication even when only ⌊steps/τ⌋ = 1
    # exchange per worker lands — the knob's required publish check
    # must depend on the rule running, not on a lucky τ

    # the center is a HOST copy: the server core's elastic math is
    # plain numpy, exactly what rides the TCP path in production
    center = jax.tree.map(np.array, jax.device_get(model.params))
    core = EasgdServerCore(center, alpha=alpha, publish_every=publish_every)

    # per-worker training state on the shared mesh; distinct key slices
    # stand in for per-worker data/rng diversity (synthetic workload)
    workers = []
    for w in range(n_workers):
        core.handler({"kind": "join", "rank": w})
        workers.append({
            "rank": w,
            "state": jax.tree.map(
                jnp.copy, (model.params, model.net_state, model.opt_state)
            ),
            "local_steps": 0,
        })

    def step_worker(wk, i):
        p, s, o = wk["state"]
        x, y = batches[(i * n_workers + wk["rank"]) % len(batches)]
        k = keys[(i * n_workers + wk["rank"]) % len(keys)]
        p, s, o, loss, _ = train_fn(p, s, o, x, y, k)
        wk["state"] = (p, s, o)
        return loss

    def exchange(wk):
        host = jax.tree.map(np.array, jax.device_get(wk["state"][0]))
        with observability.span("easgd_exchange", rank=wk["rank"],
                                tau=tau):
            reply = core.handler({
                "kind": "exchange", "rank": wk["rank"],
                "params": host, "step": wk["local_steps"],
            })
        p = replicate(mesh, reply["params"])
        wk["state"] = (p,) + wk["state"][1:]

    # warmup: compile + settle, outside the measured window
    for i in range(knobs["warmup_steps"]):
        for wk in workers:
            loss = step_worker(wk, i)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for i in range(n_steps):
        for wk in workers:
            with observability.span("train_iter", iter=i,
                                    rank=wk["rank"]):
                loss = step_worker(wk, i + knobs["warmup_steps"])
            wk["local_steps"] += 1
            if wk["local_steps"] % tau == 0:
                exchange(wk)
    for wk in workers:
        jax.block_until_ready(wk["state"][0])
    dt = time.perf_counter() - t0
    assert jnp.isfinite(loss), f"EASGD bench diverged: loss={loss}"

    steps_per_sec = n_workers * n_steps / dt
    ann = core.publisher.announcement()
    detail = {
        "chips": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
        "workers": n_workers,
        "steps_per_worker": n_steps,
        "total_s": round(dt, 3),
        "loss_final": float(loss),
        "easgd": {
            "tau": tau,
            "alpha": alpha,
            "exchanges": core.n_exchanges,
            "publish": {
                "publish_every": publish_every,
                "published": core.publisher.n_published,
                "center_generation": (
                    ann["generation"] if ann is not None else 0
                ),
            },
        },
    }
    live_summary = None
    if telemetry is not None:
        try:
            live_summary = telemetry.stop()
        except Exception as e:  # the monitor must never cost the number
            live_summary = f"failed: {type(e).__name__}: {e}"
    try:
        paths = observability.dump_all(prefix="bench_easgd_")
        detail["observability"] = {
            "trace_chrome": paths["trace_chrome"],
            "trace_raw": paths["trace_raw"],
            "metrics": observability.get_registry().snapshot(),
        }
        if live_summary is not None:
            detail["observability"]["live"] = live_summary
        if "doctor" in paths:
            detail["observability"]["doctor"] = paths["doctor"]
    except OSError as e:  # export must never discard the measurement
        print(f"[bench] observability export failed: {e}",
              file=sys.stderr, flush=True)
        detail["observability"] = f"failed: {type(e).__name__}: {e}"
    if tune_echo is not None:
        detail["tuning"] = tune_echo
    print(
        json.dumps(
            {
                "metric": "transformer_easgd_steps_per_sec",
                "value": round(steps_per_sec, 2),
                "unit": "worker steps/sec",
                "vs_baseline": 1.0,
                "detail": detail,
            }
        )
    )


def main():
    if os.environ.get("THEANOMPI_BENCH_SERVE") == "1":
        # serving-side bench (BENCH_serve schema: generated tokens/s +
        # TTFT/TPOT percentiles under a Poisson workload) — one driver
        # entry point, two benches; bench_serve.py owns the schema
        import bench_serve

        # explicit empty argv: bench.py's own flags must not leak into
        # bench_serve's parser (--replicas rides the env knob here)
        bench_serve.main([])
        return
    if os.environ.get("THEANOMPI_BENCH_RULE") == "EASGD":
        # the elastic-averaging arm (easgd tuning plan): simulated
        # workers against an in-process EASGD server core with the
        # online-learning publisher live — easgd_tau is a REAL knob
        # there, not the inert echo it used to be on the BSP workload
        _easgd_main()
        return
    knobs = _KNOBS_REHEARSAL if CPU_REHEARSAL else _KNOBS_REAL
    # candidate-config injection for the self-tuning driver: model-config
    # knobs ride into every staged candidate's build, the trace sampling
    # knob into enable_tracing.  easgd_tau no longer lands here: the
    # registry routes it to the easgd plan, whose driver sets
    # THEANOMPI_BENCH_RULE=EASGD and takes the branch above — on the
    # BSP workload it is an unknown override and exits loudly.
    tune = _tune_overrides()
    tune_model_cfg = {}
    tune_sample = None
    tune_inert = []
    if tune is not None:
        for t_name, t_value in sorted(tune.items()):
            if t_name == "exchange_bucket_mb":
                tune_model_cfg["exchange_bucket_mb"] = float(t_value)
            elif t_name == "trace_sample":
                tune_sample = int(t_value)
            else:
                print(f"[bench] unknown tune override {t_name!r}",
                      file=sys.stderr)
                sys.exit(2)
    # span tracing for the whole bench (bounded buffer): the emitted
    # JSON carries the export paths + a metrics snapshot, so perf
    # rounds ship comm/compute attribution, not just wall clocks
    from theanompi_tpu import observability as observability
    from theanompi_tpu.observability import live as obs_live

    observability.enable_tracing(sample=tune_sample)
    # live plane (THEANOMPI_LIVE=1): aggregator + watchdog ride the
    # bench — detail.observability.live carries windows/alerts, and the
    # perf gate's watchdog leg asserts the green path stayed silent
    telemetry = obs_live.maybe_start_from_env("rank0")
    _require_tpu()
    if CPU_REHEARSAL:
        print(
            f"[bench] CPU rehearsal: {jax.device_count()} fake devices, "
            "windows shrunk",
            file=sys.stderr,
        )

    # persistent XLA compile cache, placed by the one rule in
    # theanompi_tpu/cachedir.py: warm re-runs skip the AlexNet compiles,
    # and the post-window cost-analysis lowering of the already-compiled
    # winner deserializes instead of recompiling
    from theanompi_tpu.cachedir import configure_compile_cache

    configure_compile_cache(jax)

    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.runtime.mesh import make_mesh, shard_batch
    # perf-knob candidates (docs/perf/NOTES.md): a short timing window
    # picks the fastest on THIS hardware before the real measurement,
    # so a config that regresses can never win
    from theanompi_tpu.utils.benchmark import BENCH_CANDIDATES

    CANDIDATES = BENCH_CANDIDATES[: knobs["n_candidates"]]
    n_chips = jax.device_count()
    device_kind = jax.devices()[0].device_kind
    mesh = make_mesh()
    per_chip_bs = knobs["per_chip_bs"]

    def build(extra):
        cfg = dict(
            batch_size=per_chip_bs,
            image_size=knobs["image_size"],
            compute_dtype="bfloat16",
            lr=1e-3,  # throughput bench: avoid divergence on synth data
            n_synth_batches=knobs["n_synth_batches"],
            print_freq=10_000,
            **extra,
        )
        # the tuning candidate outranks the staged candidates: every
        # config in the selection window measures the SAME knob value
        cfg.update(tune_model_cfg)
        model = AlexNet(config=cfg, mesh=mesh)
        return model, model.compile_train()

    # device-resident batches, cycled: measure compute+exchange, not host
    # IO (the reference hid loading behind compute, so steady-state step
    # time is the honest comparison). Shapes are config-invariant, so one
    # set serves every candidate.
    first_model, first_fn = build(dict(CANDIDATES[0][1]))
    batches = [shard_batch(mesh, b) for b in first_model.data.train_batches()]
    # pre-split per-step keys (round-1 wart: one key reused every step
    # made every iteration draw identical dropout masks)
    keys = list(jax.random.split(jax.random.PRNGKey(TUNE_SEED), 2100))

    def make_step(train_fn):
        def step(p, s, o, i):
            x, y = batches[i % len(batches)]
            return train_fn(p, s, o, x, y, keys[i % len(keys)])

        return step

    def short_est(model, train_fn, n=None):
        """Per-step seconds over a small fenced window (post-warmup).

        Runs on COPIES of the training state: the jitted step donates
        its input buffers, and the winner's real measurement must start
        from still-valid model.params."""
        n = n or knobs["est_steps"]
        step = make_step(train_fn)
        p, s, o = jax.tree.map(
            jnp.copy, (model.params, model.net_state, model.opt_state)
        )
        for i in range(min(3, n)):
            p, s, o, loss, _ = step(p, s, o, i)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for i in range(n):
            p, s, o, loss, _ = step(p, s, o, i)
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / n

    picks = {}
    best = ("r1-default", first_model, first_fn)
    best_est = short_est(first_model, first_fn)
    picks["r1-default"] = round(best_est * 1e3, 3)
    for name, extra in CANDIDATES[1:]:
        # a candidate that fails to build or run fails the bench: a
        # swallowed failure would report a winner among the survivors
        # as if the staged list had been measured
        m, fn = build(dict(extra))
        est = short_est(m, fn)
        picks[name] = round(est * 1e3, 3)
        if est < best_est:
            prev = best
            best_est, best = est, (name, m, fn)
            del prev
        else:
            del m, fn

    chosen, model, train_fn = best
    # drop every non-winner reference before the canonical window — an
    # extra resident param+opt-state set would perturb HBM pressure in
    # the number compared across rounds
    del first_model, first_fn, best
    step = make_step(train_fn)
    params, net_state, opt_state = model.params, model.net_state, model.opt_state

    # warmup (already compiled by the selection window; settle a few steps)
    for i in range(knobs["warmup_steps"]):
        params, net_state, opt_state, loss, err = step(params, net_state, opt_state, i)
    jax.block_until_ready(loss)

    # calibrate step time (the measured window blocks exactly once, at
    # the end)
    n_calib = knobs["calib_steps"]
    t0 = time.perf_counter()
    for i in range(n_calib):
        params, net_state, opt_state, loss, err = step(params, net_state, opt_state, i)
    jax.block_until_ready(loss)
    est = (time.perf_counter() - t0) / n_calib

    # size the real window for >= target seconds on-device, single final fence
    n_steps = max(
        knobs["window_min_steps"],
        min(2000, int(knobs["window_target_s"] / max(est, 1e-9))),
    )
    t0 = time.perf_counter()
    for i in range(n_steps):
        # the span makes the measured window legible to the doctor and
        # the live watchdog (steps, fractions, straggler accounting);
        # ~1µs against ms-scale steps, identical across rounds
        with observability.span("train_iter", iter=i):
            params, net_state, opt_state, loss, err = step(params, net_state, opt_state, i)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    assert jnp.isfinite(loss), f"bench diverged: loss={loss}"

    global_bs = per_chip_bs * n_chips
    imgs_per_sec = n_steps * global_bs / dt
    per_chip = imgs_per_sec / n_chips

    # roofline: FLOPs of the winner's compiled step (fwd+bwd+exchange+
    # update), sustained TFLOP/s, and % of the chip's bf16 peak.
    # cost_analysis of the SPMD-partitioned executable reports the
    # PER-DEVICE module's work, so this is per-chip already — no second
    # division by n_chips (that would under-report MFU n_chips-fold)
    x0, y0 = batches[0]
    flops = _flops_per_step(
        train_fn, (params, net_state, opt_state, x0, y0, keys[0])
    )
    peak, peak_source = _peak_tflops(device_kind)
    tflops = mfu = None
    if flops is not None:
        tflops = flops * n_steps / dt / 1e12
        if peak:
            mfu = 100.0 * tflops / peak

    detail = {
        "chips": n_chips,
        "device_kind": device_kind,
        "per_chip_batch": per_chip_bs,
        "steps": n_steps,
        "total_s": round(dt, 3),
        "loss_final": float(loss),
        "compute_dtype": "bfloat16",
        "config": chosen,
        "candidate_ms_per_step": picks,
        "flops_per_step_per_chip": flops,
        # `is not None`, not truthiness: a legitimate 0.0 must be
        # reported as 0.0, not conflated with "analysis unavailable"
        "tflops_sustained_per_chip": round(tflops, 2) if tflops is not None else None,
        "peak_bf16_tflops": peak,
        "peak_source": peak_source,
        "mfu_pct": round(mfu, 1) if mfu is not None else None,
    }
    # free the winner's param/opt-state set and the resident batch pool
    # BEFORE the efficiency curve builds fresh per-device-count models —
    # holding both is exactly the OOM the guard below would then catch
    # every round
    del model, train_fn, step, params, net_state, opt_state, batches
    del x0, y0
    detail["efficiency"] = _efficiency_curve(n_chips, per_chip, knobs)
    live_summary = None
    if telemetry is not None:
        try:
            live_summary = telemetry.stop()
        except Exception as e:  # the monitor must never cost the number
            live_summary = f"failed: {type(e).__name__}: {e}"
    try:
        # comm/compute attribution rides the BENCH line: trace export
        # paths (open trace.json in chrome://tracing / Perfetto) + the
        # atomic metrics snapshot (exchanger wire bytes, step windows)
        paths = observability.dump_all(prefix="bench_")
        detail["observability"] = {
            "trace_chrome": paths["trace_chrome"],
            "trace_raw": paths["trace_raw"],
            "metrics": observability.get_registry().snapshot(),
        }
        if live_summary is not None:
            # windows + watchdog alerts from the in-bench live plane;
            # the perf gate fails a round whose green path alerted
            detail["observability"]["live"] = live_summary
        if "doctor" in paths:
            # the doctor's self-diagnosis rides the BENCH line too:
            # comm/compute/idle fractions and overlap are MECHANIZED
            # (observability/analysis.py), so a perf round's claims
            # carry their own evidence — and scripts/bench_compare.py
            # can gate on the next round's deltas
            detail["observability"]["doctor"] = paths["doctor"]
            with open(paths["doctor"]) as f:
                report = json.load(f)
            detail["observability"]["fractions"] = {
                label: rank.get("fractions")
                for label, rank in report.get("ranks", {}).items()
                if not rank.get("empty")
            }
    except OSError as e:  # export must never discard the measurement
        print(f"[bench] observability export failed: {e}",
              file=sys.stderr, flush=True)
        detail["observability"] = f"failed: {type(e).__name__}: {e}"
    if tune is not None:
        # echo the candidate config: the trial harness proves injection
        # by comparing this against what it sent
        detail["tuning"] = {
            "overrides": tune,
            "seed": TUNE_SEED,
            "budget": os.environ.get("THEANOMPI_TUNE_BUDGET", "full"),
            "inert": tune_inert,
        }
    emit(per_chip, 1.0, detail)


if __name__ == "__main__":
    main()
