"""Trial harness: one candidate config, one subprocess bench, one verdict.

A trial runs a full candidate config through the plan's bench
(``bench.py`` / ``bench_serve.py``) in a subprocess: the config rides
the ``THEANOMPI_TUNE_OVERRIDES`` env channel (a JSON knob→value map
the benches apply and echo back in ``detail.tuning``), the workload
seed rides ``THEANOMPI_BENCH_SEED``, and the successive-halving budget
tier rides ``THEANOMPI_TUNE_BUDGET``.  The harness collects the BENCH
JSON line, the dumped trace (when the bench exported one) and the
live-plane verdict timeline (``THEANOMPI_LIVE_PERSIST``).

The verdict (:func:`judge`) is a composition of every instrument the
repo already trusts — nothing here invents a new quality bar:

1. ``scripts/bench_compare.py``'s :func:`compare` vs the incumbent's
   BENCH JSON (headline + latency detail keys, tolerance-gated);
2. the knob registry's declarative ``detail`` checks (the same fields
   the perf_gate legs assert: spec token identity, kv drift, fleet
   scaling signals);
3. doctor threshold flags over the candidate's dumped trace
   (``observability.analysis.check_thresholds``);
4. ``observability history diff`` incumbent-timeline → candidate
   timeline (``max_new_alerts`` etc.) — the round-over-round gate the
   PR 9 carryover asked for.

Any red flag disqualifies; a missing optional artifact is a note.

Trials journal to JSONL keyed by a content fingerprint of
``(plan, config, budget, seed, bench argv)``: a crashed sweep re-runs
the driver and every already-measured trial returns from the journal
instead of re-measuring.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import subprocess
from typing import Any, Dict, List, Mapping, Optional, Sequence

from theanompi_tpu.tuning.knobs import Knob

# env channel contract with bench.py / bench_serve.py
ENV_OVERRIDES = "THEANOMPI_TUNE_OVERRIDES"
ENV_SEED = "THEANOMPI_BENCH_SEED"
ENV_BUDGET = "THEANOMPI_TUNE_BUDGET"


class TrialError(RuntimeError):
    """A trial that cannot even be attempted (bad spec, dead journal)."""


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


_bench_compare = None


def bench_compare_mod():
    """``scripts/bench_compare.py`` as a module (scripts/ is not a
    package; the comparator stays the single source of truth)."""
    global _bench_compare
    if _bench_compare is None:
        path = os.path.join(_repo_root(), "scripts", "bench_compare.py")
        spec = importlib.util.spec_from_file_location(
            "theanompi_tpu._bench_compare", path
        )
        if spec is None or spec.loader is None:
            raise TrialError(f"cannot load comparator at {path}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _bench_compare = mod
    return _bench_compare


def fingerprint(plan: str, config: Mapping[str, Any], budget: str,
                seed: int, bench_cmd: Sequence[str]) -> str:
    """Content key for the journal: same trial → same key, any knob,
    budget, seed or bench change → different key."""
    blob = json.dumps(
        {
            "plan": plan,
            "config": {k: config[k] for k in sorted(config)},
            "budget": budget,
            "seed": int(seed),
            "bench": list(bench_cmd),
        },
        sort_keys=True,
        separators=(",", ":"),
    )
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()


class Journal:
    """Append-only JSONL of finished trials, keyed by fingerprint.

    Loading tolerates a torn final line (the crash the journal exists
    for); every :meth:`put` is flushed+fsynced so a finished trial is
    never re-measured."""

    def __init__(self, path: str):
        self.path = path
        self._done: Dict[str, dict] = {}
        if os.path.exists(path):
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue  # torn tail from a crash mid-write
                    key = rec.get("key")
                    if isinstance(key, str):
                        self._done[key] = rec
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)

    def __len__(self) -> int:
        return len(self._done)

    def get(self, key: str) -> Optional[dict]:
        return self._done.get(key)

    def put(self, rec: dict) -> None:
        key = rec["key"]
        self._done[key] = rec
        with open(self.path, "a", encoding="utf-8") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())


def run_trial(
    plan: str,
    config: Mapping[str, Any],
    *,
    budget: str,
    seed: int,
    workdir: str,
    bench_cmd: Sequence[str],
    journal: Optional[Journal] = None,
    env_extra: Optional[Mapping[str, str]] = None,
    timeout_s: float = 1800.0,
) -> dict:
    """Measure one candidate; returns the trial record (journal shape).

    The record: ``key``, inputs, ``rc``, ``bench`` (the BENCH JSON or
    None), ``timeline`` (verdict-timeline path or None), ``error``
    (parse/launch failure message or None) and ``cached`` (True when
    the journal already had it — nothing was launched)."""
    if budget not in ("short", "full"):
        raise TrialError(f"budget must be short|full, got {budget!r}")
    key = fingerprint(plan, config, budget, seed, bench_cmd)
    if journal is not None:
        hit = journal.get(key)
        if hit is not None:
            rec = dict(hit)
            rec["cached"] = True
            return rec

    trial_dir = os.path.join(workdir, key[:12])
    os.makedirs(trial_dir, exist_ok=True)
    timeline = os.path.join(trial_dir, "timeline.jsonl")
    env = dict(os.environ)
    env.update(
        {
            ENV_OVERRIDES: json.dumps(dict(config), sort_keys=True),
            ENV_SEED: str(int(seed)),
            ENV_BUDGET: budget,
            # trials always run the CPU-rehearsal path of the real
            # benches; a TPU sweep overrides via env_extra
            "THEANOMPI_BENCH_CPU": "1",
            # live plane on, persisted: the verdict timeline is the
            # history-diff gate's input
            "THEANOMPI_LIVE": "1",
            "THEANOMPI_LIVE_PERSIST": timeline,
        }
    )
    if env_extra:
        env.update(env_extra)

    rec: dict = {
        "key": key,
        "plan": plan,
        "config": dict(config),
        "budget": budget,
        "seed": int(seed),
        "bench_cmd": list(bench_cmd),
        "rc": None,
        "bench": None,
        "timeline": None,
        "error": None,
        "cached": False,
    }
    # The parent that spawns a bench stays OFF jax: a chip belongs to
    # one process at a time, and a parent that had touched it would
    # hold it against the child.  This package imports no jax
    # (tests/test_chip_smoke.py pins `"jax" not in sys.modules`), and
    # the trials run one at a time.
    try:
        proc = subprocess.run(
            list(bench_cmd),
            capture_output=True,
            text=True,
            env=env,
            cwd=_repo_root(),
            timeout=timeout_s,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        rec["error"] = f"bench launch failed: {type(e).__name__}: {e}"
        if journal is not None:
            journal.put(rec)
        return rec
    rec["rc"] = proc.returncode
    doc = bench_compare_mod().extract_bench(proc.stdout or "")
    if doc is None:
        tail = (proc.stdout or "").strip().splitlines()[-3:]
        err = (proc.stderr or "").strip().splitlines()[-3:]
        rec["error"] = (
            f"no BENCH JSON in bench stdout (rc={proc.returncode}; "
            f"stdout tail {tail!r}; stderr tail {err!r})"
        )
    else:
        rec["bench"] = doc
        # injection must be provable: a bench that echoes overrides
        # must echo exactly what was sent, else the measurement did
        # not measure the candidate
        echoed = ((doc.get("detail") or {}).get("tuning") or {}).get(
            "overrides"
        )
        if echoed is not None and dict(echoed) != dict(config):
            rec["error"] = (
                f"override echo mismatch: sent {dict(config)!r}, bench "
                f"applied {dict(echoed)!r}"
            )
    if os.path.exists(timeline) and os.path.getsize(timeline) > 0:
        rec["timeline"] = timeline
    if journal is not None:
        journal.put(rec)
    return rec


# ---------------------------------------------------------------------------
# the verdict
# ---------------------------------------------------------------------------


def _headline(rec: Optional[dict]) -> Optional[float]:
    if not rec or not rec.get("bench"):
        return None
    try:
        return float(rec["bench"]["value"])
    except (KeyError, TypeError, ValueError):
        return None


def _doctor_violations(rec: dict, flags: Mapping[str, float]) -> List[str]:
    """Doctor threshold flags over the candidate's dumped trace (the
    path the bench advertises in ``detail.observability.trace_raw``)."""
    detail = (rec.get("bench") or {}).get("detail") or {}
    obs = detail.get("observability")
    trace = obs.get("trace_raw") if isinstance(obs, Mapping) else None
    if not trace or not os.path.exists(str(trace)):
        return []  # nothing dumped: the detail checks still stand
    from theanompi_tpu.observability import analysis

    with open(str(trace), "r", encoding="utf-8") as f:
        lines = f.readlines()
    report = analysis.analyze([("rank0", lines)])
    return [
        f"doctor: {v}"
        for v in analysis.check_thresholds(report, **dict(flags))
    ]


def _history_violations(
    incumbent: dict, candidate: dict, flags: Mapping[str, float]
) -> List[str]:
    """``observability history diff`` incumbent→candidate over the two
    persisted verdict timelines — the round-over-round gate."""
    a, b = incumbent.get("timeline"), candidate.get("timeline")
    if not a or not b or not os.path.exists(a) or not os.path.exists(b):
        return []
    from theanompi_tpu.observability import history

    sa = history.summarize(history.read_timeline(a))
    sb = history.summarize(history.read_timeline(b))
    out = history.diff(sa, sb, **dict(flags))
    return [f"history diff: {v}" for v in out.get("violations", [])]


def judge(
    incumbent: dict,
    candidate: dict,
    knobs: Sequence[Knob],
    tolerance: float = 0.05,
) -> dict:
    """The structured verdict for one candidate vs the incumbent.

    ``{"pass": bool, "flags": [...], "notes": [...], "rows": [...],
    "headline": {...}}`` — ``flags`` non-empty means disqualified (any
    red flag disqualifies; there is no partial credit)."""
    flags: List[str] = []
    notes: List[str] = []
    rows: List[dict] = []

    if candidate.get("error"):
        flags.append(f"trial error: {candidate['error']}")
    if candidate.get("rc") not in (0, None):
        flags.append(f"bench exited {candidate['rc']}")
    cand_doc = candidate.get("bench")
    inc_doc = incumbent.get("bench")
    if cand_doc is None:
        flags.append("no candidate BENCH JSON")
    if inc_doc is None:
        flags.append("no incumbent BENCH JSON to compare against")

    if cand_doc is not None and inc_doc is not None:
        rows, cmp_notes = bench_compare_mod().compare(
            inc_doc, cand_doc, tolerance
        )
        notes.extend(f"bench_compare: {n}" for n in cmp_notes)
        for r in rows:
            if r["regression"]:
                flags.append(
                    f"bench_compare: {r['metric']} "
                    f"{r['delta_pct']:+.1f}% beyond {tolerance:.0%} "
                    "tolerance"
                )
        detail = cand_doc.get("detail") or {}
        doctor_flags: Dict[str, float] = {}
        history_flags: Dict[str, float] = {}
        for knob in knobs:
            for check in knob.checks:
                status, msg = check.evaluate(detail)
                if status == "violation":
                    flags.append(f"check[{knob.name}]: {msg}")
                elif status == "missing":
                    notes.append(f"check[{knob.name}]: {msg}")
            doctor_flags.update(knob.doctor_flags)
            history_flags.update(knob.history_flags)
        if doctor_flags:
            flags.extend(_doctor_violations(candidate, doctor_flags))
        if history_flags:
            flags.extend(
                _history_violations(incumbent, candidate, history_flags)
            )
        if not candidate.get("timeline"):
            notes.append("no candidate verdict timeline — history "
                         "diff skipped")

    inc_v, cand_v = _headline(incumbent), _headline(candidate)
    return {
        "pass": not flags,
        "flags": flags,
        "notes": notes,
        "rows": rows,
        "headline": {
            "metric": (cand_doc or inc_doc or {}).get("metric"),
            "incumbent": inc_v,
            "candidate": cand_v,
            "ratio": (
                round(cand_v / inc_v, 6)
                if inc_v not in (None, 0) and cand_v is not None
                else None
            ),
        },
    }


__all__ = [
    "ENV_BUDGET",
    "ENV_OVERRIDES",
    "ENV_SEED",
    "Journal",
    "TrialError",
    "bench_compare_mod",
    "fingerprint",
    "judge",
    "run_trial",
]
