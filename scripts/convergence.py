#!/usr/bin/env python
"""Convergence evidence for the BASELINE configs (VERDICT r2 #6).

The reference's correctness bar was training-to-convergence (SURVEY.md
§5) — unit algebra can't show that staleness/elastic dynamics behave.
This script produces the reduced-scale CPU evidence, committed under
``docs/convergence/``:

  (a) ``bsp``   — Cifar10 BSP, 1 device vs 8 devices at the SAME global
                  batch, trained to a target val error (not a few-step
                  smoke): both runs' per-epoch curves + the target hit.
  (b) ``easgd`` — EASGD (2 workers × 4 devices, τ=4) vs BSP on the
                  same epoch budget: center-model val curve vs BSP val
                  curve (the elastic-averaging dynamics next to their
                  synchronous baseline).
  (c) ``lsgan`` — LS-GAN under GOSGD (BASELINE config #5): generator /
                  discriminator loss trajectories across gossip workers.

Data: the deterministic synthetic CIFAR fallback (class-conditional
Gaussians, providers.py) — learnable, so "target error" is meaningful;
no network exists in this environment for the real set (SURVEY §0).

Usage (repo root; ~minutes per mode on one CPU):

    python scripts/convergence.py all --out docs/convergence
"""

import argparse
import json
import os
import pathlib
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEVICES = 8


def _force_cpu_mesh():
    """Pin this process to 8 fake CPU devices.  Must run before jax is
    imported: the platform and the device count are environment."""
    # stall forensics (r5: a sweep run parked at zero CPU with no
    # external debugger on the rig): SIGUSR1 dumps all Python thread
    # stacks to stderr, and a periodic dump leaves the evidence of a
    # deadlocked collective in the log
    import faulthandler
    import signal

    faulthandler.register(signal.SIGUSR1, all_threads=True)
    faulthandler.dump_traceback_later(1800, repeat=True, exit=False)

    from theanompi_tpu.cachedir import configure_compile_cache, cpu_xla_flags

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = cpu_xla_flags(
        os.environ.get("XLA_FLAGS", ""), fake_devices=N_DEVICES
    )

    import jax

    configure_compile_cache(jax)


def _rows(record_path):
    return [json.loads(l) for l in open(record_path) if l.strip()]


def _val_curve(record_path):
    return [
        {"iter": r["iter"], "cost": r["cost"], "error": r["error"]}
        for r in _rows(record_path)
        if r["kind"] == "val"
    ]


def _val_curve_full(record_path):
    """Like _val_curve but keeps every provenance field the recorder
    stamped (n_exchanges, t_wall, coalesced_epochs) — the EASGD center
    curve must be self-diagnosing (VERDICT r3 #1)."""
    return [
        {k: v for k, v in r.items() if k not in ("kind", "error_top5")}
        for r in _rows(record_path)
        if r["kind"] == "val"
    ]


def _write(out_dir, name, obj):
    out_dir.mkdir(parents=True, exist_ok=True)
    p = out_dir / name
    with open(p, "w") as f:
        json.dump(obj, f, indent=1)
    print(f"wrote {p}")


# fixed budget shared by (a) and (b): same data, same global batch.
# lr_linear_scaling OFF: these runs hold the GLOBAL batch constant
# across device counts, so the reference's per-worker lr scaling would
# both break the 1-vs-8 identity and overshoot (0.01x8 diverges).
CIFAR_CFG = dict(
    batch_size=32,  # per shard; global 256 on the 8-device mesh
    n_synth_train=2048,
    n_synth_val=512,
    n_epochs=12,
    lr=0.01,
    lr_linear_scaling=False,
    print_freq=1000,
    comm_probe=False,
    dropout_rate=0.0,
    seed=7,
    # hardened task (VERDICT r3 weak #3 / #3): 15% of labels in BOTH
    # splits reassigned to a random other class + wider sample noise.
    # The val floor is then ≈0.15 by construction — curves land
    # strictly between chance (0.9) and zero, so 1-vs-8, EASGD-vs-BSP
    # and τ/α differences show up in the curves instead of everything
    # saturating at 0.0 mid-run (the round-3 defect).
    synth_hardness={"label_noise": 0.15, "noise": 0.5},
)
# floor ≈ 0.15 (label noise) + class-overlap ε + finite-sample gap;
# the target asserts "learned to near the floor", not "memorized"
BSP_TARGET_VAL_ERR = 0.30


def _bsp_val_curve(ckpt, cfg, n_dev=8):
    """Drive ONE BSP run (init -> wait) and return its val curve — the
    shared harness for every convergence mode, so all artifacts are
    produced by the identical driving contract."""
    import jax

    import theanompi_tpu

    ckpt.mkdir(parents=True, exist_ok=True)
    rule = theanompi_tpu.BSP()
    rule.init(
        devices=jax.devices()[:n_dev],
        model_config=cfg,
        checkpoint_dir=str(ckpt),
        val_freq=1,
    )
    rule.wait()
    return _val_curve(ckpt / "record_rank0.jsonl")


def run_bsp(out_dir):
    curves = {}
    for tag, n_dev in (("dev8", 8), ("dev1", 1)):
        cfg = dict(CIFAR_CFG)
        # SAME global batch either way: 8×32 == 1×256
        cfg["batch_size"] = CIFAR_CFG["batch_size"] * 8 // n_dev
        curves[tag] = _bsp_val_curve(
            out_dir / f"_run_bsp_{tag}", cfg, n_dev=n_dev
        )
    final8 = curves["dev8"][-1]["error"]
    final1 = curves["dev1"][-1]["error"]
    result = {
        "config": CIFAR_CFG,
        "target_val_error": BSP_TARGET_VAL_ERR,
        "val_curves": curves,
        "final_val_error": {"dev8": final8, "dev1": final1},
        "target_hit": {"dev8": final8 <= BSP_TARGET_VAL_ERR,
                       "dev1": final1 <= BSP_TARGET_VAL_ERR},
    }
    _write(out_dir, "bsp_1v8.json", result)
    print(f"BSP final val err: dev8={final8:.4f} dev1={final1:.4f} "
          f"(target {BSP_TARGET_VAL_ERR})")
    return result


def _wire_variant_sweep(out_dir, prefix, variants, base_cfg=None):
    """Shared harness for config-variant sweeps: one `_bsp_val_curve`
    run per (tag, config-extra), returning ``(curves, finals)`` — the
    collection loop, artifact shape, and naming live HERE so sibling
    sweeps (int8ef, zero) cannot drift."""
    curves = {}
    for tag, extra in variants:
        curves[tag] = _bsp_val_curve(
            out_dir / f"_run_{prefix}_{tag}",
            dict(base_cfg or CIFAR_CFG, **extra),
        )
    finals = {k: v[-1]["error"] for k, v in curves.items()}
    return curves, finals


def run_int8ef(out_dir):
    """BSP on the hardened task through three wires on the SAME budget:
    fp32 `ar`, plain `int8`, and `int8` with error feedback — the
    committed convergence evidence for the EF claim (r4): the low-bit
    wire with residuals tracks the fp32 curve, and the artifact shows
    all three rather than asserting it."""
    wires = (
        ("ar", {}),
        ("int8", {"exch_strategy": "int8"}),
        ("int8_ef", {"exch_strategy": "int8", "error_feedback": True}),
    )
    curves, finals = _wire_variant_sweep(out_dir, "int8ef", wires)
    result = {
        "config": CIFAR_CFG,
        # the experimental variable, per curve — the artifact must be
        # self-describing (which wire produced which curve)
        "wire_configs": {tag: extra for tag, extra in wires},
        "val_curves": curves,
        "final_val_error": finals,
        # the claim: EF keeps the quantized wire within noise of fp32
        "ef_tracks_ar": abs(finals["int8_ef"] - finals["ar"]) <= 0.05,
    }
    _write(out_dir, "int8_ef_vs_ar.json", result)
    print(f"int8-EF final val err: {finals} (ef_tracks_ar="
          f"{result['ef_tracks_ar']})")
    return result


def run_easgd(out_dir):
    import jax

    import theanompi_tpu

    # synchronous baseline on the same budget (shared harness)
    bsp_curve = _bsp_val_curve(out_dir / "_run_easgd_bspref", dict(CIFAR_CFG))

    ea_ckpt = out_dir / "_run_easgd"
    ea_ckpt.mkdir(parents=True, exist_ok=True)
    # batch_size is PER SHARD (per device).  Each worker owns 4 devices,
    # so 64/shard → per-worker global batch 256, matching the BSP run's
    # global 256 (the round-3 artifact used 128/shard → 512/worker, and
    # the comment claiming parity was wrong — VERDICT r3 weak #1b).
    # Data is sharded across workers: 2048/2 = 1024 samples/worker →
    # 4 iters/worker/epoch; τ=2 → 2 elastic exchanges per worker per
    # epoch — real paper-like cadence at this reduced scale.
    tau, alpha = 2, 0.5
    ea = theanompi_tpu.EASGD()
    ea.init(
        devices=jax.devices(),
        model_config=dict(CIFAR_CFG, batch_size=64),
        n_workers=2,
        tau=tau,
        alpha=alpha,
        checkpoint_dir=str(ea_ckpt),
        val_freq=1,
        verbose=False,
    )
    ea.wait()
    # the server validates the CENTER each epoch and logs through its
    # own recorder (record_server.jsonl); the driver's final post-join
    # validation (rank 0's record) duplicates the last epoch's value.
    # Rows carry n_exchanges + t_wall + coalesced_epochs provenance
    # (async_workers._center_duties), kept by _val_curve below.
    center_curve = _val_curve_full(ea_ckpt / "record_server.jsonl")
    result = {
        "config": CIFAR_CFG,
        "tau": tau,
        "alpha": alpha,
        "bsp_val_curve": bsp_curve,
        "easgd_center_val_curve": center_curve,
        "final": {
            "bsp": bsp_curve[-1]["error"] if bsp_curve else None,
            "easgd_center": center_curve[-1]["error"] if center_curve else None,
        },
    }
    _write(out_dir, "easgd_vs_bsp.json", result)
    print(f"EASGD vs BSP final val err: {result['final']}")
    return result


def run_zero(out_dir):
    """Compressed ZeRO-1 on the hardened task (r5): replicated BSP vs
    zero1 through each wire tier on the same budget.

    Measured finding (r5, reproduced at 18 epochs): the RN ``int8``
    gradient scatter converges to the floor but takes one TRANSIENT
    instability excursion mid-run (~0.2 → 0.9 → recovery, ~+30% epochs
    to the floor on this task); ``int8_sr`` (unbiased rounding) shrinks
    the excursion and reaches the floor within the nominal budget, and
    ``fp16s`` is indistinguishable from the fp32 wire. Recommendation
    encoded in the artifact: prefer ``fp16s`` or ``int8_sr`` for
    zero's gradient leg."""
    variants = (
        ("replicated", {}),
        ("zero_ar", {"zero1": True}),
        ("zero_int8", {"zero1": True, "exch_strategy": "int8"}),
        ("zero_int8_sr", {"zero1": True, "exch_strategy": "int8_sr"}),
        ("zero_fp16s", {"zero1": True, "exch_strategy": "fp16s"}),
    )
    curves, finals = _wire_variant_sweep(out_dir, "zero", variants)
    ar = finals["zero_ar"]
    # the RN-int8 excursion claim must be SHOWN, not asserted: run the
    # int8 leg again on an extended budget and compute the
    # floor-reaching epoch from the curve itself
    ext_epochs = int(CIFAR_CFG["n_epochs"] * 1.5)
    int8_ext = _bsp_val_curve(
        out_dir / "_run_zero_int8_ext",
        dict(CIFAR_CFG, zero1=True, exch_strategy="int8",
             n_epochs=ext_epochs),
    )
    floor = ar + 0.01
    reached = [i + 1 for i, r in enumerate(int8_ext)
               if r["error"] <= floor]
    result = {
        "config": CIFAR_CFG,
        "variant_configs": {tag: dict(extra) for tag, extra in variants},
        "val_curves": curves,
        "final_val_error": finals,
        "tracks_ar_at_budget": {
            tag: abs(finals[tag] - ar) <= 0.05
            for tag, _ in variants
            if tag.startswith("zero_") and tag != "zero_ar"
        },
        "int8_extended": {
            "n_epochs": ext_epochs,
            "val_curve": int8_ext,
            "floor_threshold": floor,
            "first_epoch_at_floor": reached[0] if reached else None,
        },
    }
    _write(out_dir, "zero_compressed.json", result)
    print(f"zero final val err: {finals}; int8@{ext_epochs}ep reaches "
          f"floor at epoch {reached[0] if reached else 'never'}")
    return result


def run_easgd_sweep(out_dir):
    """EASGD across its operating range on the hardened task (VERDICT r4
    #4): τ∈{2,10} × {2,4} workers, plus a GOSGD p_push∈{0.25,1.0} leg —
    the reference's whole asynchrony argument is the τ tradeoff (τ hides
    exchange latency; staleness grows), and the preset default τ=10
    previously had zero committed evidence.

    Worker-global batch is held at 64 across worker counts (per-shard
    batch scales with devices/worker) so every run sees the same
    iteration granularity: 2048/n_workers samples/worker → 16 (w2) / 8
    (w4) iters/epoch — τ=10 then exchanges ~1.6×/epoch (w2), a real
    paper-like cadence rather than one exchange per run."""
    import jax

    import theanompi_tpu

    n_epochs = 12
    # synchronous reference at the same global batch 64 and budget
    bsp_curve = _bsp_val_curve(
        out_dir / "_run_sweep_bspref",
        dict(CIFAR_CFG, batch_size=8, n_epochs=n_epochs),
    )

    rows = []
    for tau in (2, 10):
        for n_workers in (2, 4):
            ckpt = out_dir / f"_run_easgd_t{tau}_w{n_workers}"
            ckpt.mkdir(parents=True, exist_ok=True)
            per_shard = 64 // (N_DEVICES // n_workers)
            ea = theanompi_tpu.EASGD()
            ea.init(
                devices=jax.devices(),
                model_config=dict(
                    CIFAR_CFG, batch_size=per_shard, n_epochs=n_epochs
                ),
                n_workers=n_workers,
                tau=tau,
                alpha=0.5,
                checkpoint_dir=str(ckpt),
                val_freq=1,
                verbose=False,
            )
            ea.wait()
            curve = _val_curve_full(ckpt / "record_server.jsonl")
            row = {
                "tau": tau,
                "n_workers": n_workers,
                "per_shard_batch": per_shard,
                "center_val_curve": curve,
                "final_center_val_error": (
                    curve[-1]["error"] if curve else None
                ),
                "n_exchanges_final": (
                    curve[-1].get("n_exchanges") if curve else None
                ),
            }
            rows.append(row)
            print(
                f"EASGD tau={tau} w={n_workers}: final center err "
                f"{row['final_center_val_error']} "
                f"(exchanges {row['n_exchanges_final']})"
            )

    # GOSGD p_push leg on the SAME hardened task (gossip's analog of τ:
    # push probability sets the exchange cadence)
    gosgd_rows = []
    for p_push in (0.25, 1.0):
        ckpt = out_dir / f"_run_gosgd_p{int(p_push * 100)}"
        ckpt.mkdir(parents=True, exist_ok=True)
        go = theanompi_tpu.GOSGD()
        go.init(
            devices=jax.devices(),
            model_config=dict(CIFAR_CFG, batch_size=16, n_epochs=n_epochs),
            n_workers=2,
            p_push=p_push,
            checkpoint_dir=str(ckpt),
            val_freq=1,
            verbose=False,
        )
        go.wait()
        consensus = _val_curve(ckpt / "record_rank0.jsonl")
        grow = {
            "p_push": p_push,
            "final_consensus_val_error": (
                consensus[-1]["error"] if consensus else None
            ),
            "n_pushes": [w.n_pushes for w in go.worker.workers],
            "n_merges": [w.n_merges for w in go.worker.workers],
        }
        gosgd_rows.append(grow)
        print(
            f"GOSGD p_push={p_push}: final consensus err "
            f"{grow['final_consensus_val_error']} pushes={grow['n_pushes']}"
        )

    result = {
        "config": dict(CIFAR_CFG, n_epochs=n_epochs),
        "worker_global_batch": 64,
        "bsp_ref_val_curve": bsp_curve,
        "bsp_ref_final": bsp_curve[-1]["error"] if bsp_curve else None,
        "easgd": rows,
        "gosgd_p_push": gosgd_rows,
    }
    _write(out_dir, "easgd_sweep.json", result)
    return result


def run_lsgan(out_dir):
    import jax

    import theanompi_tpu

    ckpt = out_dir / "_run_lsgan"
    ckpt.mkdir(parents=True, exist_ok=True)
    rule = theanompi_tpu.GOSGD()
    rule.init(
        devices=jax.devices(),
        modelfile="theanompi_tpu.models.lsgan",
        modelclass="LSGAN",
        model_config=dict(
            batch_size=32,
            base_width=16,
            latent_dim=32,
            n_synth_train=2048,
            n_synth_val=256,
            n_epochs=6,
            print_freq=4,  # a train row every 4 iters — the committed
            # trajectory needs points, not just the final line
            seed=7,
        ),
        n_workers=2,
        p_push=0.25,
        checkpoint_dir=str(ckpt),
        val_freq=0,
        verbose=False,
    )
    rule.wait()
    # recorder (cost, error) slots carry (d_loss, g_loss) for the GAN
    per_rank = {}
    for rank in (0, 1):
        rec = ckpt / f"record_rank{rank}.jsonl"
        if rec.exists():
            per_rank[f"rank{rank}"] = [
                {"iter": r["iter"], "d_loss": r["cost"], "g_loss": r["error"]}
                for r in _rows(rec)
                if r["kind"] == "train"
            ]
    gm = [row["g_loss"] for rows in per_rank.values() for row in rows]
    result = {
        "rule": "GOSGD",
        "p_push": 0.25,
        "trajectories": per_rank,
        "g_loss_first": gm[0] if gm else None,
        "g_loss_last": gm[-1] if gm else None,
    }
    _write(out_dir, "lsgan_gosgd.json", result)
    print(f"LSGAN GOSGD g_loss first={result['g_loss_first']} "
          f"last={result['g_loss_last']}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["bsp", "easgd", "easgd_sweep", "lsgan",
                                     "int8ef", "zero", "plots", "all"])
    ap.add_argument("--out", default="docs/convergence")
    args = ap.parse_args()
    _force_cpu_mesh()
    out = pathlib.Path(args.out)
    if args.mode in ("bsp", "all"):
        run_bsp(out)
    if args.mode in ("int8ef", "all"):
        run_int8ef(out)
    if args.mode in ("easgd", "all"):
        run_easgd(out)
    if args.mode == "easgd_sweep":
        # not part of "all": ~7 full training runs; produced on demand
        # and committed (docs/convergence/easgd_sweep.json)
        run_easgd_sweep(out)
    if args.mode == "zero":
        run_zero(out)
    if args.mode in ("lsgan", "all"):
        run_lsgan(out)
    if args.mode in ("plots", "all"):
        render_plots(out)




def render_plots(out_dir):
    """Render the committed JSON curves to PNGs (matplotlib, Agg)."""
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    out_dir = pathlib.Path(out_dir)

    p = out_dir / "bsp_1v8.json"
    if p.exists():
        d = json.load(open(p))
        fig, ax = plt.subplots(1, 2, figsize=(9, 3.2))
        for tag, curve in d["val_curves"].items():
            it = [r["iter"] for r in curve]
            ax[0].plot(it, [r["cost"] for r in curve], marker="o", label=tag)
            ax[1].plot(it, [r["error"] for r in curve], marker="o", label=tag)
        ax[1].axhline(d["target_val_error"], ls="--", c="gray", lw=1,
                      label="target")
        ax[0].set_ylabel("val cost"); ax[1].set_ylabel("val error")
        for a in ax:
            a.set_xlabel("iteration"); a.legend()
        fig.suptitle("Cifar10 BSP: 8 devices vs 1 device, same global batch")
        fig.tight_layout()
        fig.savefig(out_dir / "bsp_1v8.png", dpi=120)
        print(f"wrote {out_dir / 'bsp_1v8.png'}")

    p = out_dir / "easgd_vs_bsp.json"
    if p.exists():
        d = json.load(open(p))
        fig, ax = plt.subplots(figsize=(5.5, 3.4))
        for name, key in (("BSP (sync)", "bsp_val_curve"),
                          ("EASGD center", "easgd_center_val_curve")):
            curve = d[key]
            ax.plot([r["iter"] for r in curve], [r["error"] for r in curve],
                    marker="o", label=name)
        ax.set_xlabel("iteration"); ax.set_ylabel("val error")
        ax.set_title(f"EASGD (2 workers, tau={d['tau']}, alpha={d['alpha']}) "
                     "vs BSP, same budget")
        ax.legend(); fig.tight_layout()
        fig.savefig(out_dir / "easgd_vs_bsp.png", dpi=120)
        print(f"wrote {out_dir / 'easgd_vs_bsp.png'}")

    p = out_dir / "int8_ef_vs_ar.json"
    if p.exists():
        d = json.load(open(p))
        fig, ax = plt.subplots(figsize=(5.5, 3.4))
        for tag, label in (("ar", "fp32 ar"), ("int8", "int8 wire"),
                           ("int8_ef", "int8 + error feedback")):
            curve = d["val_curves"][tag]
            ax.plot([r["iter"] for r in curve], [r["error"] for r in curve],
                    marker="o", label=label)
        ax.set_xlabel("iteration"); ax.set_ylabel("val error")
        ax.set_title("Quantized wire vs fp32, same budget (EF residuals)")
        ax.legend(); fig.tight_layout()
        fig.savefig(out_dir / "int8_ef_vs_ar.png", dpi=120)
        print(f"wrote {out_dir / 'int8_ef_vs_ar.png'}")

    p = out_dir / "zero_compressed.json"
    if p.exists():
        d = json.load(open(p))
        fig, ax = plt.subplots(figsize=(6.2, 3.8))
        for tag, curve in d["val_curves"].items():
            ax.plot(range(1, len(curve) + 1),
                    [r["error"] for r in curve], marker=".", label=tag)
        ext = d.get("int8_extended")
        if ext:
            c = ext["val_curve"]
            ax.plot(range(1, len(c) + 1), [r["error"] for r in c],
                    ls="--", alpha=0.7,
                    label=f"zero_int8 ({ext['n_epochs']}ep)")
        ax.set_xlabel("epoch"); ax.set_ylabel("val error")
        ax.set_title("ZeRO-1 wire tiers (the int8 RN transient is the "
                     "curve-shape finding)")
        ax.legend(fontsize=8); fig.tight_layout()
        fig.savefig(out_dir / "zero_compressed.png", dpi=120)
        print(f"wrote {out_dir / 'zero_compressed.png'}")

    p = out_dir / "easgd_sweep.json"
    if p.exists():
        d = json.load(open(p))
        fig, ax = plt.subplots(figsize=(6.2, 3.8))
        ref = d["bsp_ref_val_curve"]
        ax.plot(range(1, len(ref) + 1), [r["error"] for r in ref],
                c="k", lw=1.5, label="BSP ref")
        for row in d["easgd"]:
            c = row["center_val_curve"]
            # x = epoch (provenance) — iteration counts differ across
            # worker counts at fixed worker-global batch
            xs = [r.get("epoch", i + 1) for i, r in enumerate(c)]
            ax.plot(xs, [r["error"] for r in c], marker=".",
                    label=f"tau={row['tau']} w={row['n_workers']}")
        ax.set_xlabel("epoch"); ax.set_ylabel("center val error")
        ax.set_title("EASGD operating range (hardened task, floor≈0.15)")
        ax.legend(fontsize=8); fig.tight_layout()
        fig.savefig(out_dir / "easgd_sweep.png", dpi=120)
        print(f"wrote {out_dir / 'easgd_sweep.png'}")

    p = out_dir / "lsgan_gosgd.json"
    if p.exists():
        d = json.load(open(p))
        fig, ax = plt.subplots(figsize=(5.5, 3.4))
        for rank, rows in d["trajectories"].items():
            ax.plot([r["iter"] for r in rows], [r["g_loss"] for r in rows],
                    marker=".", label=f"{rank} g_loss")
            ax.plot([r["iter"] for r in rows], [r["d_loss"] for r in rows],
                    marker=".", ls="--", alpha=0.6, label=f"{rank} d_loss")
        ax.set_xlabel("iteration"); ax.set_ylabel("loss")
        ax.set_title("LS-GAN under GOSGD (gossip, 2 workers)")
        ax.legend(fontsize=8); fig.tight_layout()
        fig.savefig(out_dir / "lsgan_gosgd.png", dpi=120)
        print(f"wrote {out_dir / 'lsgan_gosgd.png'}")


if __name__ == "__main__":
    main()
