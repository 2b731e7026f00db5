"""The committed chaos drills — kill → evict → (respawn|re-admit).

Three drills share this module and the ``perf_gate.sh`` discipline:

**Training drill** (``--rule EASGD|GOSGD``, PR 10): kill a worker
process mid-run, require exactly one eviction, a respawn, a
checkpointless re-admission, and a final loss within tolerance of an
uninterrupted baseline.

**Serving drill** (``--rule SERVE``, ISSUE 12 — the perf_gate FLEET
leg): kill a serving replica with streams in flight, require exactly
one eviction, every in-flight stream re-admitted on a surviving
replica, outputs **token-identical** to an uninterrupted fleet run
(the router journals accepted tokens and replays prompt + prefix
through the ordinary prefill path), and p99 TTFT/TPOT within
tolerance of the uninterrupted run.  The fleet is in-process
(``serving/fleet.py`` replicas are threads behind the same protocol a
TCP replica serves), so the drill is deterministic and CI-sized.
As of ISSUE 20 the chaos phase also runs under request-forensics
tracking: the killed stream's retained trace must tell the failover as
ONE causal tree — queue -> prefill -> decode -> ``req_readmit`` (with
its fresh flow arrow) -> decode on the survivor
(:func:`check_readmit_trace`); a re-admission whose trace lost the
story is a violation.

**Elastic BSP drill** (``--rule BSP``, ISSUE 13 — the perf_gate BSP
leg): kill one rank of a synchronous data-parallel fleet mid-run.
Require exactly one eviction (the consensus leader's — fleet-wide) and
exactly one ``worker_evicted`` live-plane alert; the survivors'
replayed post-resize step must be **bit-identical to a fresh
(n−1)-rank world's** (bucket plans re-derived for the shrunken world,
EF residuals reset — ``elastic_bsp.reference_step`` is the oracle,
itself numpy-oracle pinned in tests); the respawned rank must rejoin
and re-expand the world under a bumped generation; the final loss must
stay within tolerance of the uninterrupted baseline; and the whole
episode may recompile exactly ONCE (the shrunken world's apply
program) — trace-counter pinned.  Ranks run as threads over real
localhost sockets (jax dispatch serialized — the legacy-jaxlib guard);
the identical worker runs one-per-process via ``launch.py --rule
BSP_ELASTIC`` under ``spawn_elastic``.

``python -m theanompi_tpu.runtime.chaos`` rehearses the elastic
membership story (docs/elasticity.md) end-to-end on real OS processes:

1. an UNINTERRUPTED baseline run of the async rule (the loss yardstick),
2. the CHAOS run: the same fleet under :func:`spawn_elastic`, with a
   ``kill`` fault injected into one worker mid-run
   (``THEANOMPI_FAULT_PLAN`` → ``FaultInjector``).  The dead rank must
   be EVICTED by its server/peers (exactly one eviction observed at the
   anchor), the supervisor respawns it, and the fresh incarnation must
   RE-ADMIT checkpointlessly (EASGD center pull / GOSGD peer snapshot).

The verdict is JSON on stdout; exit 1 on any violation:

- the anchor (EASGD server / GOSGD consensus rank) must finish clean —
  an exception propagating into a surviving rank fails the drill,
- exactly ``1`` eviction and ``>= 1`` re-admission per kill,
- final validation loss within tolerance of the uninterrupted baseline
  (``chaos <= baseline + max(abs_tol, rel_tol * |baseline|)`` — one
  sided: elasticity must not cost convergence, beating the baseline is
  fine).

This module is what ``scripts/perf_gate.sh``'s chaos leg runs
(``PERF_GATE_CHAOS=1``); tests smoke the gate plumbing on fixture
verdicts and run the EASGD drill for real under the ``distributed``
marker.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

# small enough to drill in CI, big enough that the fleet provably
# outlives the kill->evict->respawn->rejoin sequence: the dataset is
# SHARDED across workers (n_synth_train / batch / workers iterations
# per worker epoch), and the respawned rank must rejoin a job that is
# still running
DEFAULT_CONFIG = {
    "batch_size": 16,
    "n_synth_train": 384,
    "n_synth_val": 64,
    "dropout_rate": 0.0,
    "print_freq": 1000,
    "comm_probe": False,
    "seed": 5,
}


def _read_rows(path: str) -> List[dict]:
    rows = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    continue  # truncated tail row
    except OSError:
        pass
    return rows


def _last_val_cost(path: str) -> Optional[float]:
    costs = [r["cost"] for r in _read_rows(path) if r.get("kind") == "val"]
    return float(costs[-1]) if costs else None


def _membership_counts(path: str) -> Dict[str, int]:
    """Evictions/rejoins the ANCHOR observed, plus the server-side
    re-admission count from the summary row."""
    out = {"evictions": 0, "rejoins": 0, "readmissions": 0}
    for r in _read_rows(path):
        if r.get("kind") == "membership":
            if r.get("event") == "evict":
                out["evictions"] += 1
            elif r.get("event") == "rejoin":
                out["rejoins"] += 1
        elif r.get("kind") == "membership_summary":
            out["readmissions"] = int(r.get("readmissions", 0) or 0)
            out.setdefault("summary", r)
    return out


def _anchor_record(rule: str, ckpt_dir: str) -> str:
    name = "record_server.jsonl" if rule == "EASGD" else "record_rank0.jsonl"
    return os.path.join(ckpt_dir, name)


def run_drill(
    rule: str = "EASGD",
    n_procs: int = 3,
    kill_rank: int = 1,
    kill_iter: int = 10,
    rejoin_after_s: float = 10.0,
    heartbeat_timeout: float = 6.0,
    slow_iter_s: float = 0.75,
    n_epochs: int = 3,
    tau: int = 1,
    p_push: float = 0.5,
    tolerance_rel: float = 0.5,
    tolerance_abs: float = 0.25,
    workdir: str = "/tmp/theanompi_chaos",
    timeout: float = 900.0,
    env_extra: Optional[Dict[str, str]] = None,
    run_baseline: bool = True,
    modelfile: str = "theanompi_tpu.models.cifar10",
    modelclass: str = "Cifar10_model",
    config_overrides: Optional[dict] = None,
) -> dict:
    """One rule's kill-evict-respawn-readmit drill; returns the verdict
    dict (``ok`` + ``violations`` + the numbers behind them)."""
    from theanompi_tpu.runtime.multiprocess import (
        find_free_port,
        spawn_elastic,
        spawn_local,
    )

    if rule not in ("EASGD", "GOSGD"):
        raise ValueError(f"rule must be EASGD or GOSGD, not {rule!r}")
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config_overrides or {})
    base_dir = os.path.join(workdir, f"{rule.lower()}_baseline")
    chaos_dir = os.path.join(workdir, f"{rule.lower()}_chaos")
    for d in (base_dir, chaos_dir):
        os.makedirs(d, exist_ok=True)

    def _argv(ckpt_dir: str) -> List[str]:
        argv = [
            "--rule", rule,
            "--modelfile", modelfile,
            "--modelclass", modelclass,
            "--config", json.dumps(dict(cfg, n_epochs=n_epochs)),
            "--checkpoint-dir", ckpt_dir,
            "--async-port-base", str(find_free_port()),
            "--heartbeat-timeout", str(heartbeat_timeout),
        ]
        if rule == "EASGD":
            argv += ["--tau", str(tau), "--duties-coalesce", "0"]
        else:
            argv += ["--p-push", str(p_push)]
        return argv

    verdict: dict = {
        "rule": rule,
        "n_procs": n_procs,
        "kill_rank": kill_rank,
        "kill_iter": kill_iter,
        "violations": [],
    }

    if run_baseline:
        spawn_local(
            n_procs, _argv(base_dir), local_device_count=1,
            env_extra=env_extra, timeout=timeout, stream_output=False,
        )
        verdict["baseline_loss"] = _last_val_cost(
            _anchor_record(rule, base_dir)
        )

    # the fault plan: the kill, plus a per-iteration slowdown on every
    # non-anchor rank.  The slowdown is WALL-CLOCK only (no math
    # changes) and exists to keep the fleet alive long enough for the
    # respawned rank to rejoin a still-running job — a CI-sized run
    # would otherwise finish inside the respawn window.  The respawn
    # itself runs at full speed (the supervisor strips the plan).
    plan = [f"kill@{kill_rank}:{kill_iter}"]
    if slow_iter_s:
        for r in range(1, n_procs):
            plan.append(f"slow@{r}:1:{slow_iter_s}")
    report = spawn_elastic(
        n_procs,
        _argv(chaos_dir),
        local_device_count=1,
        env_extra=dict(
            env_extra or {},
            THEANOMPI_FAULT_PLAN=";".join(plan),
        ),
        timeout=timeout,
        stream_output=False,
        restarts_per_rank=1,
        restart_delay_s=rejoin_after_s,
    )
    verdict["restarts"] = report["restarts"]
    verdict["kills_observed"] = report["kills_observed"]
    verdict["exit_codes"] = report["exit_codes"]
    verdict["chaos_loss"] = _last_val_cost(_anchor_record(rule, chaos_dir))
    verdict.update(_membership_counts(_anchor_record(rule, chaos_dir)))

    # ---- the acceptance criteria, as violations ----------------------
    v = verdict["violations"]
    if report["kills_observed"] < 1:
        v.append("the injected kill never fired (no rank died)")
    if report["restarts"].get(kill_rank, 0) < 1:
        v.append(f"killed rank {kill_rank} was never respawned")
    if verdict["evictions"] != report["kills_observed"]:
        v.append(
            f"expected exactly one eviction per kill, saw "
            f"{verdict['evictions']} eviction(s) for "
            f"{report['kills_observed']} kill(s)"
        )
    if verdict["rejoins"] + verdict["readmissions"] < 1:
        v.append("the respawned rank never re-admitted")
    surviving_bad = {
        r: c for r, c in report["exit_codes"].items()
        if c not in (0, None) and int(r) != kill_rank
    }
    if surviving_bad:
        v.append(
            f"surviving ranks exited nonzero (an exception propagated "
            f"into a train loop?): {surviving_bad}"
        )
    if verdict["chaos_loss"] is None:
        v.append("chaos run produced no validation row")
    if run_baseline:
        base_loss = verdict.get("baseline_loss")
        if base_loss is None:
            v.append("baseline run produced no validation row")
        elif verdict["chaos_loss"] is not None:
            tol = max(tolerance_abs, tolerance_rel * abs(base_loss))
            verdict["loss_tolerance"] = round(tol, 6)
            verdict["loss_delta"] = round(
                verdict["chaos_loss"] - base_loss, 6
            )
            if verdict["loss_delta"] > tol:
                v.append(
                    f"chaos loss {verdict['chaos_loss']:.4f} exceeds "
                    f"baseline {base_loss:.4f} by {verdict['loss_delta']:.4f} "
                    f"(> tolerance {tol:.4f}) — recovery cost convergence"
                )
    verdict["ok"] = not v
    return verdict


# rehearsal-sized transformer for the serving drill: small enough to
# compile in seconds on one CPU core, big enough that streams live long
# enough to be killed mid-flight
SERVE_CONFIG = {
    "seq_len": 64,
    "vocab_size": 32,
    "d_model": 32,
    "n_heads": 4,
    "n_layers": 2,
    "batch_size": 2,
    "n_synth_train": 2,
    "n_synth_val": 1,
    "comm_probe": False,
    "print_freq": 10_000,
}


def check_readmit_trace(record: dict) -> dict:
    """Verify a killed stream's retained trace tells the whole story
    as ONE causal tree: queue -> prefill -> decode (on the victim) ->
    the ``req_readmit`` hop (with its fresh flow arrow) -> decode again
    (on the survivor).  A stream killed before it produced any token
    (the hop's ``journaled`` arg is 0) legitimately has no victim-side
    phases; for those only the survivor-side chain is required.
    Returns ``{"ok": bool, "full_tree": bool, "missing": [...],
    "order": [...]}`` — importable so the drill and the golden test
    assert the identical contract."""
    spans = sorted(
        (ev for ev in record.get("events", ()) if ev.get("ph") == "X"),
        key=lambda ev: ev.get("ts", 0),
    )
    rid = record.get("rid", "")
    missing = []
    readmit = [ev for ev in spans if ev.get("name") == "req_readmit"]
    if not readmit:
        missing.append("req_readmit span")
        hop_ts = None
        journaled = 0
    else:
        hop_ts = readmit[0].get("ts", 0)
        journaled = int(readmit[0].get("args", {}).get("journaled", 0) or 0)

    decode_names = ("req_decode", "req_spec")
    prefill_names = ("req_prefill",)

    def first_ts(names, after=None):
        for ev in spans:
            if ev.get("name") in names and (
                after is None or ev.get("ts", 0) >= after
            ):
                return ev.get("ts", 0)
        return None

    full_tree = False
    if hop_ts is not None:
        # Survivor side — required for every readmitted stream: the
        # hop re-enters the queue, prefills from the journal, decodes.
        q_after = first_ts(("req_queue",), after=hop_ts)
        p_after = first_ts(prefill_names, after=hop_ts)
        d_after = first_ts(decode_names, after=hop_ts)
        if q_after is None:
            missing.append("req_queue span after the readmission hop")
        if p_after is None:
            missing.append("prefill span after the readmission hop")
        if d_after is None:
            missing.append("decode span after the readmission hop")
        # whole-tick and per-dispatch spans overlap (the admission
        # tick's decode span starts at the admission timestamp), so
        # order on the decode phase's END, not its first start
        d_end = max(
            (ev.get("ts", 0) + ev.get("dur", 0) for ev in spans
             if ev.get("name") in decode_names
             and ev.get("ts", 0) >= hop_ts),
            default=None,
        )
        if (q_after is not None and p_after is not None
                and d_end is not None
                and not (q_after <= p_after <= d_end)):
            missing.append(
                "post-hop order is not queue<=prefill<=decode")
        # Victim side — required only when the stream had produced
        # tokens before the kill (journaled > 0).
        q_before = first_ts(("req_queue",))
        p_before = first_ts(prefill_names)
        d_before = [ev for ev in spans if ev.get("name") in decode_names
                    and ev.get("ts", 0) <= hop_ts]
        if journaled > 0:
            if q_before is None or q_before > hop_ts:
                missing.append("req_queue span before the readmission hop")
            if p_before is None or p_before > hop_ts:
                missing.append("prefill span before the readmission hop")
            if not d_before:
                missing.append("decode span before the readmission hop")
            if (not missing and not (q_before <= p_before <= hop_ts)):
                missing.append("phase order is not queue<=prefill<=readmit")
        full_tree = bool(
            q_before is not None and q_before <= hop_ts
            and p_before is not None and p_before <= hop_ts
            and d_before and d_after is not None and not missing
        )
        # the hop's flow arrow: begin (ph s) from the router with the
        # journal-length suffix, bound (ph f) by the accepting replica
        flow_ids = {
            ev.get("id") for ev in record.get("events", ())
            if ev.get("ph") in ("s", "f")
        }
        if not any(
            isinstance(i, str) and i.startswith(f"req:{rid}:r")
            for i in flow_ids
        ):
            missing.append("readmission flow arrow (req:<rid>:r<n>)")
    return {
        "ok": not missing,
        "full_tree": full_tree,
        "missing": missing,
        "order": [ev.get("name") for ev in spans],
        "flags": list(record.get("flags", ())),
    }


def run_serve_drill(
    n_replicas: int = 3,
    n_requests: int = 8,
    max_new_tokens: int = 24,
    shared_prefix_len: int = 16,
    evict_after_s: float = 3.0,
    p99_tolerance_rel: float = 2.0,
    p99_tolerance_abs: float = 3.0,
    timeout: float = 300.0,
    seed: int = 0,
    config_overrides: Optional[dict] = None,
) -> dict:
    """The serving-fleet kill drill; returns the verdict dict.

    Protocol: build an N-replica fleet, run the workload uninterrupted
    (the baseline — outputs AND p99 latencies), then rerun it on a
    fresh fleet over the SAME warmed engines, kill the busiest replica
    once every stream has tokens in flight, and compare.
    """
    import time

    import numpy as np

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.runtime.mesh import make_mesh
    from theanompi_tpu.serving import (
        PagedServingEngine,
        Request,
        ServingMetrics,
    )
    from theanompi_tpu.serving.fleet import FleetRouter, ServeReplica

    import jax

    cfg = dict(SERVE_CONFIG)
    cfg.update(config_overrides or {})
    mesh = make_mesh(devices=jax.devices()[:1])
    model = TransformerLM(config=cfg, mesh=mesh)
    geom = dict(n_slots=2, max_len=cfg["seq_len"], buckets=(8, 16, 64),
                block_size=8)
    engines = [PagedServingEngine(model, **geom) for _ in range(n_replicas)]

    rng = np.random.RandomState(seed)
    trunk = rng.randint(0, cfg["vocab_size"],
                        size=shared_prefix_len).tolist()
    prompts = []
    for j in range(n_requests):
        if j % 2 == 0:  # half share the system prompt (affinity work)
            p = trunk + rng.randint(0, cfg["vocab_size"], size=4).tolist()
        else:
            p = rng.randint(0, cfg["vocab_size"],
                            size=int(rng.randint(4, 12))).tolist()
        prompts.append(p)

    def requests():
        out = []
        for j, p in enumerate(prompts):
            if j == n_requests - 1:  # one sampled stream rides along:
                # token_index0 must keep its keys aligned across replay
                out.append(Request(id=f"q{j}", prompt=list(p),
                                   max_new_tokens=max_new_tokens,
                                   temperature=0.8, top_k=8, seed=42))
            else:
                out.append(Request(id=f"q{j}", prompt=list(p),
                                   max_new_tokens=max_new_tokens))
        return out

    def build_fleet(alerts):
        reps = [
            ServeReplica(f"r{i}", engines[i]).start()
            for i in range(n_replicas)
        ]
        router = FleetRouter(
            evict_after_s=evict_after_s,
            metrics=ServingMetrics(),
            on_alert=lambda rule, msg: alerts.append(rule),
        )
        for i, rep in enumerate(reps):
            router.add_replica(f"r{i}", rep)
        return reps, router

    def warm(reps):
        # one prompt per chunk bucket: baseline and chaos runs must
        # both see fully-warmed programs, or compile time masquerades
        # as TTFT and poisons the p99 comparison
        for rep in reps:
            for wi, n in enumerate((3, 12, 20)):
                rep.handle(("submit", {
                    "id": f"_warm{wi}", "prompt": list(range(1, n + 1)),
                    "max_new_tokens": 2,
                }))
            # the sampled pick path compiles lazily — warm it too
            rep.handle(("submit", {
                "id": "_warms", "prompt": [1, 2, 3],
                "max_new_tokens": 2, "temperature": 0.5, "seed": 1,
            }))
        deadline = time.monotonic() + timeout
        while not all(r.scheduler.idle for r in reps):
            if time.monotonic() > deadline:
                raise RuntimeError("serve drill warmup never drained")
            time.sleep(0.01)

    verdict: dict = {
        "rule": "SERVE",
        "n_replicas": n_replicas,
        "n_requests": n_requests,
        "kills_observed": 1,
        "violations": [],
    }
    v = verdict["violations"]

    # ---- baseline: the uninterrupted fleet ---------------------------
    base_alerts: list = []
    reps, router = build_fleet(base_alerts)
    try:
        warm(reps)
        for r in requests():
            router.submit(r)
        base_out = router.run(timeout_s=timeout)
        base_sum = router.metrics.summary()
    finally:
        for rep in reps:
            rep.stop()
    if router.fleet_stats()["evictions"] != 0:
        v.append("baseline fleet run evicted a replica — the drill rig "
                 "itself is unstable (evict_after_s too tight?)")
    verdict["baseline"] = {
        "ttft_p99_s": round(base_sum["ttft_p99_s"], 4),
        "tpot_p99_s": round(base_sum["tpot_p99_s"], 4),
        "n_tokens": base_sum["n_tokens_out"],
    }

    # ---- chaos: kill the busiest replica mid-stream ------------------
    # request forensics arm over the chaos phase only: the threshold is
    # far above any drill latency, so retention is driven purely by the
    # ``readmitted``/``lost`` flags — the killed stream's whole trace
    # survives, everything else recycles
    from theanompi_tpu import observability as obs

    tracer_was_enabled = obs.get_tracer().enabled
    if not tracer_was_enabled:
        # request tracking rides the tracer; the drill CLI runs with
        # tracing off, so switch it on for the chaos phase only
        obs.enable_tracing()
    obs.enable_request_tracking(threshold_s=max(timeout, 600.0))
    alerts = []
    reps, router = build_fleet(alerts)
    try:
        for r in requests():
            router.submit(r)
        # kill once some replica has >= 2 open streams with accepted
        # tokens journaled — a genuinely mid-stream kill, early enough
        # that plenty of budget remains to finish elsewhere
        deadline = time.monotonic() + timeout

        def open_with_tokens():
            by = {}
            for s in router._streams.values():
                if not s.done and s.tokens:
                    by[s.replica] = by.get(s.replica, 0) + 1
            return by
        open_by = {}
        while True:
            open_by = open_with_tokens()
            if open_by and max(open_by.values()) >= min(2, n_requests):
                break
            if time.monotonic() > deadline:
                if open_by:
                    break  # settle for the busiest we ever saw
                raise RuntimeError("streams never started producing")
            router.pump()
            time.sleep(0.002)
        victim = max(open_by, key=open_by.get)
        next(rep for rep in reps if rep.name == victim).kill()
        verdict["killed"] = victim
        verdict["streams_in_flight_at_kill"] = open_by.get(victim, 0)
        chaos_out = router.run(timeout_s=timeout)
        chaos_sum = router.metrics.summary()
    finally:
        for rep in reps:
            rep.stop()

    retained = obs.retained_requests()
    obs.disable_request_tracking()
    if not tracer_was_enabled:
        obs.disable_tracing()
    readmitted = [
        r for r in retained if "readmitted" in r.get("flags", ())
    ]
    stats = router.fleet_stats()
    verdict["evictions"] = stats["evictions"]
    verdict["readmissions"] = stats["readmissions"]
    verdict["forensics"] = {
        "retained": len(retained),
        "retained_rids": sorted(r["rid"] for r in retained),
        "readmitted_traces": {
            r["rid"]: check_readmit_trace(r) for r in readmitted
        },
    }
    verdict["eviction_alerts"] = alerts.count("replica_evicted")
    verdict["readmission_alerts"] = alerts.count("request_readmitted")
    verdict["token_identical"] = chaos_out == base_out
    verdict["chaos"] = {
        "ttft_p99_s": round(chaos_sum["ttft_p99_s"], 4),
        "tpot_p99_s": round(chaos_sum["tpot_p99_s"], 4),
        "n_tokens": chaos_sum["n_tokens_out"],
    }

    # ---- the acceptance criteria, as violations ----------------------
    if verdict["evictions"] != 1:
        v.append(f"expected exactly one eviction for one kill, saw "
                 f"{verdict['evictions']}")
    if verdict["eviction_alerts"] != 1:
        v.append(f"expected exactly one replica_evicted alert, saw "
                 f"{verdict['eviction_alerts']}")
    if verdict["readmissions"] < 1:
        v.append("no in-flight stream re-admitted — the kill was a "
                 "monitoring blackout, not a survived failure")
    else:
        if not readmitted:
            v.append("re-admission happened but no retained trace "
                     "carries the 'readmitted' flag — tail forensics "
                     "lost the killed stream's story")
        traces = verdict["forensics"]["readmitted_traces"]
        for rid, chk in sorted(traces.items()):
            if not chk["ok"]:
                v.append(
                    f"retained trace for re-admitted stream {rid!r} is "
                    f"missing: {', '.join(chk['missing'])} — not one "
                    "causal queue->prefill->decode->readmit->decode tree"
                )
        if traces and not any(chk["full_tree"] for chk in traces.values()):
            v.append(
                "no re-admitted stream's trace shows the full "
                "queue->prefill->decode->readmit->decode tree — every "
                "victim was killed before producing a token")
    if not verdict["token_identical"]:
        diff = [k for k in base_out if chaos_out.get(k) != base_out[k]]
        v.append(f"outputs diverged from the uninterrupted run for "
                 f"streams {diff[:4]} — replay is NOT token-identical")
    for metric in ("ttft_p99_s", "tpot_p99_s"):
        base_p, chaos_p = verdict["baseline"][metric], verdict["chaos"][metric]
        tol = max(p99_tolerance_abs, p99_tolerance_rel * base_p)
        delta = chaos_p - base_p
        verdict[f"{metric}_delta"] = round(delta, 4)
        verdict[f"{metric}_tolerance"] = round(tol, 4)
        if delta > tol:
            v.append(
                f"{metric} {chaos_p:.4f}s exceeds baseline {base_p:.4f}s "
                f"by {delta:.4f}s (> tolerance {tol:.4f}s) — failover "
                "cost the tail latency SLO"
            )
    verdict["ok"] = not v
    return verdict


def run_publish_drill(
    n_requests: int = 6,
    max_new_tokens: int = 16,
    publish_every: int = 3,
    alpha: float = 0.5,
    timeout: float = 300.0,
    seed: int = 0,
    config_overrides: Optional[dict] = None,
) -> dict:
    """The online-learning-loop drill (``--rule PUBLISH``); returns the
    verdict dict.

    Protocol: a 2-replica fleet serves generation 0 while an in-process
    ``EasgdServerCore`` absorbs exchanges until its ``CenterPublisher``
    fires generation 1 MID-DECODE.  The subscriber on the canary
    replica pulls/validates immediately, but the install must defer to
    the between-ticks gap — cohort A (pinned gen 0, in flight at the
    publish) must finish token-identical to a single-scheduler gen-0
    reference.  Then cohort B pins gen 1 on the canary and a control
    cohort pins gen 0 on the baseline replica (A/B serving): each must
    be token-identical to its generation's reference.  A PLANTED SLO
    regression on the gen-1 cohort must flip the A/B verdict, trigger
    exactly ONE rollback (re-flagging is a no-op) and exactly one
    ``weights_rolled_back`` live-plane alert, and a post-rollback
    cohort must again match the gen-0 reference.  A bad-shape snapshot
    must be REFUSED before install (the GL-W recompile hazard), and
    the whole episode — warm → install → rollback, >= 2 generations —
    must be zero-recompile (prefill/decode trace counters pinned).
    """
    import time

    import numpy as np

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.observability import live as obs_live
    from theanompi_tpu.observability.metrics import (
        counter_deltas,
        flatten_counters,
        get_registry,
    )
    from theanompi_tpu.parallel.distributed_async import EasgdServerCore
    from theanompi_tpu.publish import WeightSubscriber, SwapRefused, ab
    from theanompi_tpu.runtime.mesh import make_mesh
    from theanompi_tpu.serving import PagedServingEngine, Request
    from theanompi_tpu.serving.fleet import FleetRouter, ServeReplica
    from theanompi_tpu.serving.loader import relayout_for_serving
    from theanompi_tpu.serving.metrics import ServingMetrics
    from theanompi_tpu.serving.scheduler import ContinuousBatchingScheduler

    import jax

    cfg = dict(SERVE_CONFIG)
    cfg.update(config_overrides or {})
    mesh = make_mesh(devices=jax.devices()[:1])
    model = TransformerLM(config=cfg, mesh=mesh)
    geom = dict(n_slots=2, max_len=cfg["seq_len"], buckets=(8, 16, 64),
                block_size=8)

    verdict: dict = {
        "rule": "PUBLISH",
        "n_requests": n_requests,
        "publish_every": publish_every,
        "violations": [],
    }
    v = verdict["violations"]
    base_counters = flatten_counters(get_registry().snapshot())

    # ---- the publisher side: a live EASGD core over the same model ---
    params_gen0 = jax.tree.map(np.array, jax.device_get(model.params))
    core = EasgdServerCore(
        jax.tree.map(np.copy, params_gen0), alpha=alpha,
        publish_every=publish_every,
    )
    rng = np.random.RandomState(seed)
    # a deterministic "worker trajectory": center + small perturbation,
    # so the published generation 1 is genuinely different weights
    worker = jax.tree.map(
        lambda a: a + rng.normal(0, 0.02, a.shape).astype(a.dtype)
        if a.dtype == np.float32 else a,
        params_gen0,
    )
    core.handler({"kind": "join", "rank": 0})

    def exchange_once():
        return core.handler(
            {"kind": "exchange", "rank": 0,
             "params": jax.tree.map(np.copy, worker)}
        )

    # ---- references: one scheduler per generation, same prompts ------
    prompts = [
        rng.randint(0, cfg["vocab_size"],
                    size=int(rng.randint(4, 12))).tolist()
        for _ in range(n_requests)
    ]

    def requests(tag):
        return [
            Request(id=f"{tag}{j}", prompt=list(p),
                    max_new_tokens=max_new_tokens)
            for j, p in enumerate(prompts)
        ]

    # one warmed engine serves both generations' references — exactly
    # the params-as-data property the drill is certifying
    ref_eng = PagedServingEngine(model, **geom)

    def reference(params):
        sched = ContinuousBatchingScheduler(ref_eng, params=params)
        for r in requests("ref"):
            sched.submit(r)
        done = sched.run()
        return [list(done[f"ref{j}"]) for j in range(n_requests)]

    ref0 = reference(relayout_for_serving(model, params_gen0))

    # ---- the fleet: baseline replica + canary with a subscriber ------
    engines = [PagedServingEngine(model, **geom) for _ in range(2)]
    reps = [ServeReplica(f"r{i}", engines[i]).start() for i in range(2)]
    router = FleetRouter(evict_after_s=3600.0, metrics=ServingMetrics())
    for i, rep in enumerate(reps):
        router.add_replica(f"r{i}", rep)
    canary = reps[1]

    def fetch(generation):
        reply = core.handler(
            {"kind": "weights", "generation": int(generation)}
        )
        return reply if reply.get("ok") else None

    sub = WeightSubscriber(
        canary, fetch,
        relayout=lambda p: relayout_for_serving(model, p),
    )

    def run_cohort(tag, pin):
        ids = []
        for r in requests(tag):
            router.submit(r, generation=pin)
            ids.append(r.id)
        out = router.run(timeout_s=timeout)
        return [list(out[i]) for i in ids]

    def wait_idle(deadline):
        while not all(r.scheduler.idle for r in reps):
            if time.monotonic() > deadline:
                raise RuntimeError("fleet never drained")
            time.sleep(0.005)

    try:
        # warm every chunk bucket on both replicas so compile time never
        # masquerades as decode work, then PIN the trace counters — the
        # whole multi-generation episode must add zero
        for wi, n in enumerate((3, 12, 20)):
            for rep in reps:
                rep.handle(("submit", {
                    "id": f"_warm{wi}", "prompt": list(range(1, n + 1)),
                    "max_new_tokens": 2,
                }))
            # drain between lengths: batching a short prompt with a
            # long one would bucket it up and leave the short chunk
            # shape untraced — the cohorts would then pay a "recompile"
            # the episode check wrongly blames on the swap
            wait_idle(time.monotonic() + timeout)
        traces0 = [
            (e._n_prefill_traces, e._n_decode_traces) for e in engines
        ]

        # ---- cohort A on gen 0, publish fired MID-DECODE -------------
        for r in requests("a"):
            router.submit(r, generation=0)
        deadline = time.monotonic() + timeout
        # let decode genuinely start before the publish lands
        while not any(
            s.tokens and not s.done for s in router._streams.values()
        ):
            if time.monotonic() > deadline:
                raise RuntimeError("cohort A never started decoding")
            router.pump()
            time.sleep(0.002)
        ann = None
        for _ in range(publish_every):
            reply = exchange_once()
            ann = reply.get("publish", ann)
        verdict["n_publishes"] = core.publisher.n_published
        if ann is None or ann.get("generation") != 1:
            v.append(f"publisher never announced generation 1 after "
                     f"{publish_every} exchanges (got {ann})")
        canary_busy = not canary.scheduler.idle
        sub.poll(ann)  # pull + validate NOW; install defers if busy
        verdict["install_deferred_while_busy"] = bool(
            canary_busy and canary.serving_generation == 0
        )
        a_out = router.run(timeout_s=timeout)
        cohort_a = [list(a_out[f"a{j}"]) for j in range(n_requests)]
        verdict["token_identical_gen0"] = cohort_a == ref0
        if cohort_a != ref0:
            v.append(
                "cohort A (admitted on generation 0, publish mid-decode)"
                " is NOT token-identical to the gen-0 reference — the "
                "install tore into in-flight streams"
            )

        # the between-ticks install applies once the canary drains
        wait_idle(time.monotonic() + timeout)
        deadline = time.monotonic() + timeout
        while canary.serving_generation != 1:
            if time.monotonic() > deadline:
                raise RuntimeError("canary never installed generation 1")
            time.sleep(0.005)
        verdict["n_installs"] = reps[0].installs + reps[1].installs
        if verdict["n_installs"] != verdict.get("n_publishes", 0):
            v.append(
                f"expected exactly one install per publish fleet-wide "
                f"(1 subscriber), saw {verdict['n_installs']} install(s)"
                f" for {verdict.get('n_publishes', 0)} publish(es)"
            )
        router.pump()  # poll replies refresh per-replica generations

        # gen-1 reference AFTER the install (same published tree)
        snap = fetch(1)
        ref1 = reference(relayout_for_serving(model, snap["params"]))

        # ---- A/B: cohort B pins gen 1, control pins gen 0 ------------
        cohort_b = run_cohort("b", pin=1)
        control = run_cohort("c", pin=0)
        verdict["ab_cohort_identical"] = (
            cohort_b == ref1 and control == ref0
        )
        if cohort_b != ref1:
            v.append("gen-1 cohort is NOT token-identical to the gen-1 "
                     "reference — version pinning leaked generations")
        if control == ref1 and ref1 != ref0:
            v.append("gen-0 control cohort matches the gen-1 reference "
                     "— pinning routed it to the canary")
        if control != ref0:
            v.append("gen-0 control cohort is NOT token-identical to "
                     "the gen-0 reference")

        # ---- planted SLO regression → exactly one rollback -----------
        base_rows = router.metrics.cohort_rows(0)
        cand_rows = [
            dict(r, ttft_s=r["ttft_s"] + 5.0, tpot_s=r["tpot_s"] + 5.0)
            for r in router.metrics.cohort_rows(1)
        ]
        verdict["ab_verdict_unplanted"] = ab.compare_cohorts(
            base_rows, router.metrics.cohort_rows(1)
        )["verdict"]
        planted = ab.compare_cohorts(base_rows, cand_rows)
        verdict["ab_verdict_planted"] = planted["verdict"]
        if planted["verdict"] != "regression":
            v.append(
                f"planted +5s SLO regression judged "
                f"{planted['verdict']!r}, not 'regression'"
            )
        rolled = sub.flag_regression(1)
        rolled_again = sub.flag_regression(1)
        verdict["rollbacks"] = sub.rollbacks
        if not rolled or rolled_again or sub.rollbacks != 1:
            v.append(
                f"expected exactly one rollback for one flagged "
                f"generation, saw rollbacks={sub.rollbacks} "
                f"(first={rolled}, reflag={rolled_again})"
            )
        deadline = time.monotonic() + timeout
        while canary.serving_generation != 0:
            if time.monotonic() > deadline:
                raise RuntimeError("canary never rolled back to gen 0")
            time.sleep(0.005)
        router.pump()

        # ---- post-rollback cohort must match gen 0 again -------------
        post = run_cohort("p", pin=0)
        verdict["post_rollback_identical"] = post == ref0
        if post != ref0:
            v.append("post-rollback cohort is NOT token-identical to "
                     "the gen-0 reference — rollback restored the "
                     "wrong weights")

        # ---- bad-shape snapshot refused loudly before install --------
        bad = jax.tree.map(
            lambda a: np.zeros(np.shape(a) + (1,), np.asarray(a).dtype),
            params_gen0,
        )
        bad_sub = WeightSubscriber(
            canary,
            lambda g: {"generation": g, "params": bad},
        )
        gen_before = canary.serving_generation
        try:
            bad_sub.pull(7)
            verdict["refused_bad_dtype"] = False
            v.append("a wrong-shape snapshot was NOT refused — the "
                     "GL-W recompile hazard reached install")
        except SwapRefused:
            verdict["refused_bad_dtype"] = (
                canary.serving_generation == gen_before
                and bad_sub.refusals == 1
            )
            if not verdict["refused_bad_dtype"]:
                v.append("refusal raised but the replica still moved "
                         "generations")

        # ---- zero-recompile across >= 2 generations ------------------
        traces1 = [
            (e._n_prefill_traces, e._n_decode_traces) for e in engines
        ]
        extra = sum(
            (p1 - p0) + (d1 - d0)
            for (p0, d0), (p1, d1) in zip(traces0, traces1)
        )
        verdict["extra_recompiles"] = extra
        if extra != 0:
            v.append(
                f"{extra} recompile(s) across the install/rollback "
                "episode — the swap is supposed to be params-as-data "
                "(trace counters pinned)"
            )
    finally:
        for rep in reps:
            rep.stop()

    # ---- exactly one weights_rolled_back alert through the live plane
    deltas = counter_deltas(
        flatten_counters(get_registry().snapshot()), base_counters
    )
    rb_deltas = {
        k: val for k, val in deltas.items()
        if k.startswith("publish_rollbacks_total")
    }
    agg = obs_live.Aggregator(log=lambda line: None)
    agg.ingest({
        "kind": obs_live.FRAME_KIND, "v": obs_live.FRAME_VERSION,
        "rank": "serve_canary", "seq": 1, "t_wall": 0.0,
        "sample_rate": 1, "dropped": 0,
        "spans": {"names": [], "idx": [], "ts": [], "dur": []},
        "ctrs": {"ts": [], "key": [], "val": []},
        "flows": {"b_id": [], "b_ts": [], "f_id": [], "f_ts": []},
        "counters": rb_deltas, "hist": {},
    })
    win = agg.close_window()
    alerts = [
        a for a in win["alerts"] if a["rule"] == "weights_rolled_back"
    ]
    verdict["weights_rolled_back_alerts"] = len(alerts)
    if len(alerts) != 1:
        v.append(
            f"expected exactly one weights_rolled_back alert, saw "
            f"{len(alerts)}"
        )

    verdict["ok"] = not v
    return verdict


def run_bsp_drill(
    n_ranks: int = 3,
    kill_rank: int = 1,
    kill_iter: int = 6,
    n_steps: int = 22,
    rejoin_after_s: float = 2.5,
    evict_after_s: float = 1.25,
    step_delay_s: float = 0.12,
    tolerance_rel: float = 0.5,
    tolerance_abs: float = 0.05,
    timeout: float = 240.0,
    program_config: Optional[dict] = None,
    run_baseline: bool = True,
) -> dict:
    """The elastic-BSP kill drill; returns the verdict dict.

    Protocol: run the uninterrupted baseline through the transport-free
    reference driver (the threaded fleet is pinned bit-identical to it
    by test), then a real threaded fleet over localhost sockets with
    one rank dying mid-run, a respawn after ``rejoin_after_s``, and
    compare: exactly one eviction + one ``worker_evicted`` alert, the
    resized step bit-identical to the fresh smaller world, rejoin
    re-expansion under a bumped generation, loss within tolerance, and
    exactly one recompile (the shrunken world's apply program)."""
    import threading
    import time as _time

    import numpy as np

    from theanompi_tpu.observability import live as obs_live
    from theanompi_tpu.observability.metrics import (
        counter_deltas,
        flatten_counters,
        get_registry,
    )
    from theanompi_tpu.parallel import elastic_bsp as eb
    from theanompi_tpu.runtime.multiprocess import find_free_port

    cfg = dict(program_config or {})
    verdict: dict = {
        "rule": "BSP",
        "n_ranks": n_ranks,
        "kill_rank": kill_rank,
        "kill_iter": kill_iter,
        "n_steps": n_steps,
        "kills_observed": 0,
        "violations": [],
    }
    v = verdict["violations"]

    if run_baseline:
        base_prog = eb.BSPTrainProgram(**cfg)
        base_params, _ = eb.run_reference(base_prog, n_steps, n_ranks)
        verdict["baseline_loss"] = base_prog.loss(base_params)

    # ---- the chaos fleet: threads over real localhost sockets --------
    base_counters = flatten_counters(get_registry().snapshot())
    addresses = [("127.0.0.1", find_free_port()) for _ in range(n_ranks)]
    events: List[tuple] = []
    ev_lock = threading.Lock()

    def on_event(rank):
        def hook(kind, member, generation):
            with ev_lock:
                events.append((rank, kind, member, generation))
        return hook

    workers = {}
    programs = {}
    for r in range(n_ranks):
        programs[r] = eb.BSPTrainProgram(**cfg)
        workers[r] = eb.ElasticBSPWorker(
            r, addresses, programs[r], n_steps=n_steps,
            evict_after_s=evict_after_s,
            step_delay_s=step_delay_s,
            die_at_step=kill_iter if r == kill_rank else None,
            step_timeout_s=timeout / 2,
            on_event=on_event(r),
        )
    threads = {
        r: threading.Thread(
            target=workers[r].run, name=f"bsp-rank{r}", daemon=True
        )
        for r in workers
    }
    rejoiner = None
    try:
        for t in threads.values():
            t.start()
        # respawn the killed rank after the delay (the supervisor's
        # restart_delay_s analog) — its fresh program instance keeps
        # the recompile accounting per incarnation
        deadline = _time.monotonic() + timeout
        while not workers[kill_rank]._killed:
            if _time.monotonic() > deadline:
                raise RuntimeError("the injected kill never fired")
            _time.sleep(0.02)
        verdict["kills_observed"] = 1
        _time.sleep(rejoin_after_s)
        rejoin_prog = eb.BSPTrainProgram(**cfg)
        survivors = [r for r in range(n_ranks) if r != kill_rank]
        rejoiner = eb.ElasticBSPWorker(
            kill_rank, addresses, rejoin_prog, n_steps=n_steps,
            members=survivors,
            evict_after_s=evict_after_s,
            step_delay_s=step_delay_s,
            step_timeout_s=timeout / 2,
            rejoin=True,
            on_event=on_event(f"{kill_rank}'"),
        )
        threads["rejoin"] = threading.Thread(
            target=rejoiner.run, name=f"bsp-rank{kill_rank}-rejoin",
            daemon=True,
        )
        threads["rejoin"].start()
        for key, t in threads.items():
            t.join(timeout=max(1.0, deadline - _time.monotonic()))
            if t.is_alive():
                v.append(f"worker thread {key} never finished")
    finally:
        for w in list(workers.values()) + ([rejoiner] if rejoiner else []):
            try:
                w.stop()
            except Exception:
                pass

    survivors = [workers[r] for r in range(n_ranks) if r != kill_rank]
    crashed = {
        r: repr(w.error) for r, w in workers.items()
        if w.error is not None
    }
    if rejoiner is not None and rejoiner.error is not None:
        crashed[f"{kill_rank}'"] = repr(rejoiner.error)
    if crashed:
        v.append(
            f"surviving ranks raised (an exception propagated into a "
            f"train loop?): {crashed}"
        )

    # ---- exactly one eviction, fleet-wide ----------------------------
    evictions = [e for e in events if e[1] == "evict"]
    verdict["evictions"] = len(evictions)
    if len(evictions) != 1:
        v.append(
            f"expected exactly one eviction for one kill, saw "
            f"{len(evictions)}: {evictions}"
        )
    # ---- exactly one worker_evicted alert through the live plane -----
    deltas = counter_deltas(
        flatten_counters(get_registry().snapshot()), base_counters
    )
    bsp_deltas = {
        k: val for k, val in deltas.items()
        if k.startswith("membership_evictions_total")
        and 'plane="bsp"' in k
    }
    agg = obs_live.Aggregator(log=lambda line: None)
    agg.ingest({
        "kind": obs_live.FRAME_KIND, "v": obs_live.FRAME_VERSION,
        "rank": "bsp_leader", "seq": 1, "t_wall": 0.0,
        "sample_rate": 1, "dropped": 0,
        "spans": {"names": [], "idx": [], "ts": [], "dur": []},
        "ctrs": {"ts": [], "key": [], "val": []},
        "flows": {"b_id": [], "b_ts": [], "f_id": [], "f_ts": []},
        "counters": bsp_deltas, "hist": {},
    })
    win = agg.close_window()
    alerts = [
        a for a in win["alerts"] if a["rule"] == "worker_evicted"
    ]
    verdict["worker_evicted_alerts"] = len(alerts)
    if len(alerts) != 1:
        v.append(
            f"expected exactly one worker_evicted alert, saw "
            f"{len(alerts)}"
        )

    # ---- resized step bit-identical to a fresh (n-1)-world step ------
    cap = next(
        (w.resize_capture for w in survivors
         if w.resize_capture is not None), None,
    )
    if cap is None or cap.get("params_after") is None:
        verdict["resized_step_bit_identical"] = False
        v.append("no survivor captured a post-resize step")
    else:
        oracle = eb.BSPTrainProgram(**cfg)
        ref_params, _ref_opt, ref_sum = eb.reference_step(
            oracle, cap["params"], cap["opt"], cap["step"],
            cap["members"],
        )
        import jax

        same_sum = all(
            np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(cap["grad_sum"]),
                jax.tree.leaves(ref_sum),
            )
        )
        same_params = all(
            np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(cap["params_after"]),
                jax.tree.leaves(ref_params),
            )
        )
        verdict["resized_step_bit_identical"] = bool(
            same_sum and same_params
        )
        if not (same_sum and same_params):
            v.append(
                "survivors' post-resize step is NOT bit-identical to a "
                "fresh smaller-world step (stale EF residual or bucket "
                "plan not re-derived?)"
            )

    # ---- rejoin re-expands under a bumped generation -----------------
    gens = {w.rank: list(w.generations) for w in survivors}
    verdict["generations"] = gens
    verdict["generation_monotone"] = all(
        all(b > a for a, b in zip(g, g[1:])) for g in gens.values()
    )
    if not verdict["generation_monotone"]:
        v.append(f"generation sequence not strictly increasing: {gens}")
    verdict["world_restored"] = all(
        w.world == n_ranks for w in survivors
    ) and (rejoiner is not None and rejoiner.world == n_ranks)
    verdict["rejoined"] = bool(
        rejoiner is not None and rejoiner.final_loss is not None
    )
    if not verdict["world_restored"] or not verdict["rejoined"]:
        v.append(
            "the respawned rank never re-expanded the world (rejoin "
            f"failed; worlds {[w.world for w in survivors]}, rejoiner "
            f"{None if rejoiner is None else rejoiner.world})"
        )
    verdict["resizes"] = {
        "shrink": max(w.n_shrinks for w in survivors),
        "expand": max(w.n_expands for w in survivors),
    }

    # ---- recompile pin: exactly one resize recompile -----------------
    # each survivor: ONE grad program ever, apply programs == worlds
    # seen (n and n-1 — the re-expansion reuses the cached n-world
    # program); the rejoiner's fresh incarnation compiles its own pair
    extra = 0
    for r in range(n_ranks):
        if r == kill_rank:
            continue
        extra += max(0, programs[r].grad_traces - 1)
        extra += max(0, programs[r].apply_traces - 2)
    if rejoiner is not None:
        extra += max(0, rejoin_prog.grad_traces - 1)
        extra += max(0, rejoin_prog.apply_traces - 1)
    verdict["apply_traces"] = {
        r: programs[r].apply_traces for r in range(n_ranks)
        if r != kill_rank
    }
    verdict["extra_recompiles"] = extra
    if extra != 0:
        v.append(
            f"{extra} recompile(s) beyond the single expected resize "
            "recompile (trace counters)"
        )

    # ---- loss within tolerance of the uninterrupted baseline ---------
    losses = [
        w.final_loss for w in survivors if w.final_loss is not None
    ]
    verdict["chaos_loss"] = max(losses) if losses else None
    if verdict["chaos_loss"] is None:
        v.append("chaos run produced no final loss")
    elif run_baseline:
        base_loss = verdict["baseline_loss"]
        tol = max(tolerance_abs, tolerance_rel * abs(base_loss))
        verdict["loss_tolerance"] = round(tol, 6)
        verdict["loss_delta"] = round(
            verdict["chaos_loss"] - base_loss, 6
        )
        if verdict["loss_delta"] > tol:
            v.append(
                f"chaos loss {verdict['chaos_loss']:.4f} exceeds "
                f"baseline {base_loss:.4f} by "
                f"{verdict['loss_delta']:.4f} (> tolerance {tol:.4f}) "
                "— recovery cost convergence"
            )
    verdict["ok"] = not v
    return verdict


def main(argv=None) -> int:
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="theanompi_tpu.runtime.chaos", description=__doc__
    )
    p.add_argument("--rule", action="append",
                   choices=["EASGD", "GOSGD", "SERVE", "BSP", "PUBLISH"],
                   help="drill this rule (repeatable; default: EASGD). "
                   "SERVE runs the in-process serving-fleet kill drill "
                   "(evict → re-admit → token-identical, p99 gate); "
                   "BSP runs the elastic-BSP shrink/rejoin drill "
                   "(evict → resize bit-identical to the fresh smaller "
                   "world → re-expand, one-recompile gate); PUBLISH "
                   "runs the online-learning-loop drill (publish "
                   "mid-decode → between-ticks install → A/B pinned "
                   "cohorts → planted-regression rollback, "
                   "zero-recompile gate)")
    p.add_argument("--n-procs", type=int, default=3)
    p.add_argument("--kill-rank", type=int, default=1)
    p.add_argument("--kill-iter", type=int, default=10)
    p.add_argument("--rejoin-after", type=float, default=10.0,
                   help="supervisor delay before respawning the kill — "
                   "keep rejoin-after + process startup ABOVE "
                   "--heartbeat-timeout so the eviction provably "
                   "precedes the re-admission")
    p.add_argument("--heartbeat-timeout", type=float, default=6.0)
    p.add_argument("--slow-iter", type=float, default=0.75,
                   help="wall-clock slowdown per iteration injected "
                   "into the surviving ranks so the run outlives the "
                   "respawn window (no math changes)")
    p.add_argument("--n-epochs", type=int, default=3)
    p.add_argument("--tolerance-rel", type=float, default=0.5)
    p.add_argument("--tolerance-abs", type=float, default=0.25)
    p.add_argument("--workdir", default="/tmp/theanompi_chaos")
    p.add_argument("--timeout", type=float, default=900.0)
    p.add_argument("--no-baseline", action="store_true",
                   help="skip the uninterrupted run (no loss gate)")
    p.add_argument("--serve-replicas", type=int, default=3)
    p.add_argument("--serve-requests", type=int, default=8)
    p.add_argument("--serve-evict-after", type=float, default=3.0)
    p.add_argument("--serve-p99-tolerance", type=float, default=2.0,
                   help="relative p99 TTFT/TPOT tolerance vs the "
                   "uninterrupted fleet run (abs floor 3s covers the "
                   "eviction window at CI scale)")
    p.add_argument("--bsp-ranks", type=int, default=3)
    p.add_argument("--bsp-steps", type=int, default=22)
    p.add_argument("--bsp-kill-iter", type=int, default=6,
                   help="step the elastic-BSP victim dies at")
    p.add_argument("--bsp-rejoin-after", type=float, default=2.5,
                   help="seconds before the killed BSP rank respawns — "
                   "keep it above --bsp-evict-after so the eviction "
                   "provably precedes the re-admission")
    p.add_argument("--bsp-evict-after", type=float, default=1.25)
    p.add_argument("--publish-requests", type=int, default=6)
    p.add_argument("--publish-every", type=int, default=3,
                   help="exchanges per center publication in the "
                   "PUBLISH drill (the publisher cadence knob)")
    args = p.parse_args(argv)

    out = {"rules": {}, "ok": True}
    for rule in args.rule or ["EASGD"]:
        if rule == "BSP":
            verdict = run_bsp_drill(
                n_ranks=args.bsp_ranks,
                kill_rank=args.kill_rank,
                kill_iter=args.bsp_kill_iter,
                n_steps=args.bsp_steps,
                rejoin_after_s=args.bsp_rejoin_after,
                evict_after_s=args.bsp_evict_after,
                timeout=args.timeout,
                run_baseline=not args.no_baseline,
            )
        elif rule == "PUBLISH":
            # the PUBLISH drill runs the EASGD core in-process, whose
            # membership lines print to stdout; stdout of this CLI must
            # carry ONLY the verdict JSON (perf_gate json.load's it)
            import contextlib

            with contextlib.redirect_stdout(sys.stderr):
                verdict = run_publish_drill(
                    n_requests=args.publish_requests,
                    publish_every=args.publish_every,
                    timeout=args.timeout,
                )
        elif rule == "SERVE":
            verdict = run_serve_drill(
                n_replicas=args.serve_replicas,
                n_requests=args.serve_requests,
                evict_after_s=args.serve_evict_after,
                p99_tolerance_rel=args.serve_p99_tolerance,
                timeout=args.timeout,
            )
        else:
            verdict = run_drill(
                rule=rule,
                n_procs=args.n_procs,
                kill_rank=args.kill_rank,
                kill_iter=args.kill_iter,
                rejoin_after_s=args.rejoin_after,
                heartbeat_timeout=args.heartbeat_timeout,
                slow_iter_s=args.slow_iter,
                n_epochs=args.n_epochs,
                tolerance_rel=args.tolerance_rel,
                tolerance_abs=args.tolerance_abs,
                workdir=args.workdir,
                timeout=args.timeout,
                run_baseline=not args.no_baseline,
            )
        out["rules"][rule] = verdict
        out["ok"] = out["ok"] and verdict["ok"]
        for viol in verdict["violations"]:
            print(f"[chaos] {rule} VIOLATION: {viol}", file=sys.stderr,
                  flush=True)
    print(json.dumps(out, indent=2))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
