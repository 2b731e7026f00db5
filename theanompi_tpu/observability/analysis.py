"""Trace analytics — the offline "doctor".

The tracer (``trace.py``) records what happened; this module answers
whether it was any good.  It consumes the raw JSONL the tracer writes
(``save_raw``; one file per rank) and reconstructs the run the way the
Theano-MPI paper accounts for it (arXiv:1605.08325 §per-step time
accounting) and the CUDA-Aware-MPI characterization study argues
scaling claims must be made (arXiv:1810.11112): mechanized comm /
compute fractions, per-rank stragglers, queue stalls — numbers, not
eyeballed timelines.

What it computes, per rank (= per input raw file):

- **Step reconstruction** — every ``train_iter`` span is one step:
  count, total/mean/p50/max wall time.
- **Time fractions** — compute (``train_iter``), comm (transport +
  exchange spans), input wait (``data_wait``/``inbox_wait``) and idle,
  as overlap-aware interval unions over the rank's trace window (two
  threads both sending concurrently count the wall time once).
- **Comm/compute overlap** — the fraction of comm wall time hidden
  under compute: THE number behind the framework's whole value
  proposition (keep the math busy while the exchanger moves weights).
- **Straggler index** — cumulative time to each step boundary measured
  from the rank's OWN first step (clock-offset-free: per-rank raw
  traces have unsynchronized epochs), compared against the fastest
  rank at every common boundary.
- **Queue stalls** — windows where the ``inbox_depth`` counter events
  (``Tracer.counter_event``) sat above zero, correlated with
  ``inbox_wait`` spans, so a backed-up mailbox has a start, an end and
  a depth instead of being a vibe.
- **Flow accounting** — every ``flow_begin`` must meet its
  ``flow_end`` across the rank set; unmatched arrows mean frames that
  were sent and never drained (lost, or a dead receiver).

plus serving TTFT/TPOT percentiles from a metrics-registry snapshot's
histogram buckets (``bucket_quantile`` — the estimator
``BENCH_serve`` falls back to when its exact-row window overflows).

Pure stdlib, pure functions over parsed dicts: ``analyze`` never
touches the live tracer, so it can run against a week-old artifact
directory on a laptop.  The CLI wrapper is
``python -m theanompi_tpu.observability doctor`` (human table or
``--json``; ``--max-straggler`` / ``--min-overlap`` / ``--max-stall-s``
/ ``--max-ttft-p99-s`` turn verdicts into nonzero exit codes, which is
how CI gates on them).

The same math also runs ONLINE: ``StreamingDoctor`` is ``analyze``
restated as an incremental, windowed accumulator (shared pure helpers
— ``merge_intervals``/``intersect_total``/``straggler_summary``/
``StallTracker``), the verdict engine under the live telemetry plane
(``observability/live.py``) and the ``watch`` CLI; its whole
accumulated state round-trips through versioned JSON
(``snapshot()``/``restore()``), which is what the aggregator
checkpoints so a promoted standby keeps the run's cumulative trends.  Fractions from
1-in-N sampled traces carry 95% error bars (``fractions_ci95``), and
threshold checks compare against the conservative end of the interval
so a sampled trace cannot flake a CI gate.  ``estimate_clock_offsets``
recovers per-rank clock skew from the min one-way delay of matched
flow send/recv pairs — ``merge_raw_traces`` applies it so merged
timelines line up across hosts.

The **request doctor** (``request_breakdown`` / ``request_report`` /
``check_request_thresholds``) runs the same interval algebra over ONE
request's retained span buffer (``Tracer.retained_requests``): every
microsecond of a slow request's latency is attributed to exactly one
phase — queue, backpressure, prefill, decode, spec-rollback,
install-wait, readmission — by priority-ordered interval subtraction,
so the phase column sums to (at most) the measured latency and the
remainder is reported honestly as ``unattributed``.  The CLI wrapper
is ``python -m theanompi_tpu.observability requests`` (and
``doctor --request RID``); ``--max-queue-frac`` /
``--max-p99-unattributed-frac`` turn the attribution into CI gates.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional, Tuple

# span-name → category tables.  One definition: the instrumentation
# sites (workers/transport/async_workers/loader) and this file must
# agree on names, and here is where the agreement lives.
COMPUTE_SPANS = ("train_iter",)
COMM_SPANS = (
    "tcp_send",
    "tcp_recv",
    "tcp_request",
    "tcp_serve",
    "mbox_send",
    "comm",
    "easgd_exchange",
    "gosgd_push",
    "gosgd_merge",
)
WAIT_SPANS = ("data_wait", "inbox_wait")


# ---------------------------------------------------------------------------
# parsing
# ---------------------------------------------------------------------------

def parse_raw(label: str, lines: Iterable[str]) -> dict:
    """One rank's raw JSONL → a plain dict of its events, corrupt lines
    skipped (same tolerance as ``raw_to_chrome``: a crash-truncated
    rank must still be diagnosable)."""
    header: Optional[dict] = None
    spans: List[dict] = []
    counters: List[dict] = []
    flow_begin: Dict[str, float] = {}
    flow_end: Dict[str, float] = {}
    n_events = 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if doc.get("kind") == "header" and header is None:
            header = doc
            continue
        ph = doc.get("ph")
        if ph is None:
            continue
        n_events += 1
        if ph == "X":
            spans.append(doc)
        elif ph == "C":
            counters.append(doc)
        elif ph == "s":
            flow_begin[str(doc.get("id"))] = float(doc.get("ts", 0.0))
        elif ph == "f":
            flow_end[str(doc.get("id"))] = float(doc.get("ts", 0.0))
    h = header or {}
    return {
        "label": label,
        "pid": h.get("pid"),
        "process_name": h.get("process_name") or label,
        "dropped": int(h.get("dropped", 0) or 0),
        "sample_rate": int(h.get("sample_rate", 1) or 1),
        "sampled_out": int(h.get("sampled_out", 0) or 0),
        "empty": header is None and n_events == 0,
        "spans": spans,
        "counters": counters,
        "flow_begin": flow_begin,
        "flow_end": flow_end,
    }


# ---------------------------------------------------------------------------
# interval math (µs in, µs out; callers convert to seconds at the edge)
# ---------------------------------------------------------------------------

def merge_intervals(
    intervals: List[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    """Sorted union of half-open intervals — overlapping spans (e.g.
    two sender threads in flight at once) count wall time ONCE."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: List[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def intersect_total(
    a: List[Tuple[float, float]], b: List[Tuple[float, float]]
) -> float:
    """Total overlap between two MERGED interval lists (linear scan)."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _spans_named(rank: dict, names: Tuple[str, ...]) -> List[dict]:
    wanted = set(names)
    return [s for s in rank["spans"] if s.get("name") in wanted]


def _intervals(spans: List[dict]) -> List[Tuple[float, float]]:
    return merge_intervals(
        [(float(s["ts"]), float(s["ts"]) + float(s.get("dur", 0.0)))
         for s in spans]
    )


def sampled_ci95(frac: float, n_kept: int, rate: int) -> float:
    """95% half-width on a time fraction computed from a 1-in-``rate``
    sampled trace that kept ``n_kept`` spans of the category.

    The kept set is deterministic, not random, so this is a modeling
    approximation, not an exact CI: treat the kept spans as a 1/rate
    thinning of the span stream, giving the scaled duration total a
    relative standard error of ~sqrt((rate-1)/n_kept) (Poisson-style
    count noise; duration dispersion is absorbed into the same
    factor).  rate=1 means every span was kept — the fraction is
    exact and the half-width is 0.  Clamped to [0, 1]: a fraction is
    never uncertain past the whole window."""
    if rate <= 1 or n_kept <= 0 or frac <= 0:
        return 0.0
    import math

    return min(1.0, 1.96 * frac * math.sqrt((rate - 1) / n_kept))


def _nearest_rank(sorted_vals: List[float], pct: float) -> float:
    if not sorted_vals:
        return float("nan")
    k = max(
        0,
        min(
            len(sorted_vals) - 1,
            int(round(pct / 100.0 * (len(sorted_vals) - 1))),
        ),
    )
    return sorted_vals[k]


# ---------------------------------------------------------------------------
# per-rank reconstruction
# ---------------------------------------------------------------------------

def _analyze_rank(rank: dict, stall_min_s: float) -> dict:
    spans = rank["spans"]
    if not spans:
        return {
            "empty": True,
            "pid": rank["pid"],
            "n_spans": 0,
            "dropped": rank["dropped"],
            "sample_rate": rank["sample_rate"],
            "sampled_out": rank["sampled_out"],
        }
    t0 = min(float(s["ts"]) for s in spans)
    t1 = max(float(s["ts"]) + float(s.get("dur", 0.0)) for s in spans)
    window = max(0.0, t1 - t0)

    steps = sorted(
        _spans_named(rank, COMPUTE_SPANS), key=lambda s: float(s["ts"])
    )
    durs = sorted(float(s.get("dur", 0.0)) / 1e6 for s in steps)
    compute = _intervals(_spans_named(rank, COMPUTE_SPANS))
    comm = _intervals(_spans_named(rank, COMM_SPANS))
    wait = _intervals(_spans_named(rank, WAIT_SPANS))
    busy = merge_intervals(compute + comm + wait)
    overlap_us = intersect_total(comm, compute)

    out = {
        "empty": False,
        "pid": rank["pid"],
        "n_spans": len(spans),
        "window_s": window / 1e6,
        "steps": {
            "n": len(steps),
            "total_s": sum(durs),
            "mean_s": (sum(durs) / len(durs)) if durs else float("nan"),
            "p50_s": _nearest_rank(durs, 50),
            "max_s": durs[-1] if durs else float("nan"),
        },
        "fractions": {
            "compute": total(compute) / window if window else 0.0,
            "comm": total(comm) / window if window else 0.0,
            "input_wait": total(wait) / window if window else 0.0,
            "idle": (window - total(busy)) / window if window else 0.0,
        },
        # fraction of comm wall time hidden under compute — the overlap
        # the framework exists to create; None when the rank did no comm
        "comm_compute_overlap": (
            overlap_us / total(comm) if total(comm) > 0 else None
        ),
        "dropped": rank["dropped"],
        "sample_rate": rank["sample_rate"],
        "sampled_out": rank["sampled_out"],
    }
    rate = rank["sample_rate"]
    if rate > 1:
        # error bars on fractions computed from a sampled trace: the
        # 1-in-N keep rate and the per-category kept-span counts bound
        # how much duration the dropped spans could have carried.
        # Present ONLY for sampled traces — rate-1 reports (and the
        # golden fixture) keep their exact shape.
        n_c = len(_spans_named(rank, COMPUTE_SPANS))
        n_m = len(_spans_named(rank, COMM_SPANS))
        n_w = len(_spans_named(rank, WAIT_SPANS))
        fr = out["fractions"]
        ci = {
            "compute": sampled_ci95(fr["compute"], n_c, rate),
            "comm": sampled_ci95(fr["comm"], n_m, rate),
            "input_wait": sampled_ci95(fr["input_wait"], n_w, rate),
        }
        # idle is derived from the busy union of all three — its
        # uncertainty compounds theirs (root-sum-square)
        ci["idle"] = min(
            1.0,
            (ci["compute"] ** 2 + ci["comm"] ** 2
             + ci["input_wait"] ** 2) ** 0.5,
        )
        out["fractions_ci95"] = ci
        if out["comm_compute_overlap"] is not None:
            # absolute half-width on the [0,1] ratio (scale-free — an
            # observed overlap of 0 from a sparse sample is still
            # uncertain); the scarcer category's count dominates
            out["comm_compute_overlap_ci95"] = sampled_ci95(
                1.0, min(n_c, n_m), rate
            )
    out["stalls"] = _find_stalls(rank, wait, stall_min_s)
    return out


def _step_boundaries(rank: dict) -> List[float]:
    """Cumulative seconds from this rank's FIRST step start to each
    step's end — per-rank-relative, so unsynchronized tracer epochs
    across processes cancel out."""
    steps = sorted(
        _spans_named(rank, COMPUTE_SPANS), key=lambda s: float(s["ts"])
    )
    if not steps:
        return []
    base = float(steps[0]["ts"])
    return [
        (float(s["ts"]) + float(s.get("dur", 0.0)) - base) / 1e6
        for s in steps
    ]


class StallTracker:
    """Streaming depth>0 window detector for ONE counter series.

    ``feed`` takes timestamped samples in order and returns a closed
    ``(start, end, max_depth)`` window (µs) whenever the depth drains
    back to zero; ``flush`` closes a still-open window at the last
    sample seen.  The offline ``_find_stalls`` and the live plane's
    online doctor run the SAME instance logic, so a stall means one
    thing whether it was found post-mortem or mid-run."""

    __slots__ = ("start", "max_depth", "last_ts")

    def __init__(self):
        self.start: Optional[float] = None
        self.max_depth = 0.0
        self.last_ts: Optional[float] = None

    def feed(self, ts: float, val: float):
        self.last_ts = ts
        if val > 0:
            if self.start is None:
                self.start, self.max_depth = ts, val
            else:
                self.max_depth = max(self.max_depth, val)
            return None
        if self.start is None:
            return None
        out = (self.start, ts, self.max_depth)
        self.start = None
        return out

    def flush(self):
        """Close a never-drained window at the last sample (a backed-up
        mailbox at dump/window time is a stall, not invisible)."""
        if self.start is None or self.last_ts is None:
            return None
        out = (self.start, self.last_ts, self.max_depth)
        self.start = None
        return out


def stall_row(
    key: Any,
    window: Tuple[float, float, float],
    wait_intervals: List[Tuple[float, float]],
) -> dict:
    """One report row from a closed StallTracker window: duration plus
    its overlap with blocked-recv (``inbox_wait``) spans — depth>0
    while nobody is in recv means the consumer was busy elsewhere (a
    scheduling stall); depth>0 inside recv means the drain itself is
    the bottleneck."""
    a, b, depth = window
    return {
        "inbox_rank": key,
        "start_s": a / 1e6,
        "end_s": b / 1e6,
        "duration_s": (b - a) / 1e6,
        "max_depth": depth,
        "recv_wait_overlap_s": intersect_total(
            [(a, b)], wait_intervals
        ) / 1e6,
    }


def _find_stalls(
    rank: dict,
    wait_intervals: List[Tuple[float, float]],
    stall_min_s: float,
) -> List[dict]:
    """Windows where an inbox-depth counter sat above zero (one
    StallTracker per labeled series)."""
    series: Dict[Any, List[Tuple[float, float]]] = {}
    for ev in rank["counters"]:
        if ev.get("name") != "inbox_depth":
            continue
        args = ev.get("args") or {}
        key = args.get("rank")
        series.setdefault(key, []).append(
            (float(ev.get("ts", 0.0)), float(args.get("value", 0.0)))
        )
    out = []
    for key, samples in sorted(
        series.items(), key=lambda kv: str(kv[0])
    ):
        samples.sort()
        tracker = StallTracker()
        windows = [w for ts, val in samples
                   if (w := tracker.feed(ts, val)) is not None]
        tail = tracker.flush()
        if tail is not None:
            windows.append(tail)
        for w in windows:
            if (w[1] - w[0]) / 1e6 < stall_min_s:
                continue
            out.append(stall_row(key, w, wait_intervals))
    return out


def straggler_summary(boundaries: Dict[str, List[float]]) -> dict:
    """Stragglers: lag behind the fastest rank at each common step
    boundary, measured per-rank-relative (clock-offset-free).

    ``boundaries[label]`` is the cumulative seconds from that rank's
    first step start to each step end (``_step_boundaries``).  Pure —
    the offline ``analyze`` calls it over whole traces, the streaming
    doctor over its growing per-rank boundary lists."""
    straggler: dict = {
        "n_common_steps": 0,
        "per_rank": {},
        "straggler_rank": None,
        "max_straggler_index": 0.0,
    }
    if len(boundaries) >= 2:
        n_common = min(len(b) for b in boundaries.values())
        straggler["n_common_steps"] = n_common
        fastest = [
            min(b[k] for b in boundaries.values()) for k in range(n_common)
        ]
        worst = (None, 0.0)
        for label, b in sorted(boundaries.items()):
            lags = [b[k] - fastest[k] for k in range(n_common)]
            final = lags[-1] if lags else 0.0
            idx = (
                final / fastest[-1]
                if n_common and fastest[-1] > 0
                else 0.0
            )
            straggler["per_rank"][label] = {
                "final_lag_s": final,
                "mean_lag_s": sum(lags) / len(lags) if lags else 0.0,
                "straggler_index": idx,
            }
            if idx > worst[1]:
                worst = (label, idx)
        straggler["straggler_rank"] = worst[0]
        straggler["max_straggler_index"] = worst[1]
    return straggler


# ---------------------------------------------------------------------------
# cross-rank clock alignment from flow send/recv pairs
# ---------------------------------------------------------------------------

def flow_delay_edges(
    ranks: List[dict],
) -> Dict[Tuple[str, str], float]:
    """Minimum observed one-way delay (µs, receiver clock minus sender
    clock) per directed ``(sender_label, receiver_label)`` pair, from
    every flow id that BEGINS in one rank's trace and ENDS in
    another's.  Each observation is ``true_delay + epoch(sender) −
    epoch(receiver)``; the minimum over many frames approaches the
    epoch skew plus the link's floor latency — the NTP/PTP trick,
    applied to flow arrows the transport already stamps."""
    begun: Dict[str, Tuple[str, float]] = {}
    for r in ranks:
        for fid, ts in r["flow_begin"].items():
            begun[fid] = (r["label"], ts)
    edges: Dict[Tuple[str, str], float] = {}
    for r in ranks:
        for fid, ts in r["flow_end"].items():
            src = begun.get(fid)
            if src is None or src[0] == r["label"]:
                continue  # unmatched, or an in-process round trip
            key = (src[0], r["label"])
            d = ts - src[1]
            if key not in edges or d < edges[key]:
                edges[key] = d
    return edges


def estimate_clock_offsets(
    ranks: List[dict],
) -> Tuple[Dict[str, float], List[str]]:
    """Per-rank clock offsets (µs) from flow-pair min delays, plus the
    labels that could not be aligned — the offline entrypoint
    (``merge_raw_traces``).  The live aggregator maintains its delay
    edges incrementally and calls ``offsets_from_edges`` directly."""
    labels = [r["label"] for r in ranks]
    return offsets_from_edges(flow_delay_edges(ranks), labels)


def offsets_from_edges(
    edges: Dict[Tuple[str, str], float], labels: Iterable[str]
) -> Tuple[Dict[str, float], List[str]]:
    """Solve ``flow_delay_edges`` output into per-rank offsets.

    Subtracting ``offsets[label]`` from a rank's timestamps maps them
    onto the anchor rank's clock.  Where BOTH directions between two
    ranks carry flows, the symmetric floor latency cancels
    (``(d_ab − d_ba) / 2``); a one-directional pair uses the raw min
    delay — biased late by the link's floor latency, which is the
    conservative direction (never moves an effect before its cause).
    Ranks are aligned breadth-first from each connected component's
    label-sorted first member (offset 0); ranks with no cross-rank
    flows at all come back in ``unaligned`` so callers can WARN
    instead of silently rendering skewed tracks."""
    labels = list(labels)
    adj: Dict[str, set] = {}
    for a, b in edges:
        adj.setdefault(a, set()).add(b)
        adj.setdefault(b, set()).add(a)
    offsets: Dict[str, float] = {}
    for label in sorted(labels):
        if label in offsets or label not in adj:
            continue
        offsets[label] = 0.0  # component anchor
        frontier = [label]
        while frontier:
            a = frontier.pop()
            for b in sorted(adj[a]):
                if b in offsets:
                    continue
                d_ab = edges.get((a, b))
                d_ba = edges.get((b, a))
                if d_ab is not None and d_ba is not None:
                    skew = (d_ab - d_ba) / 2.0
                elif d_ab is not None:
                    skew = d_ab
                else:
                    skew = -d_ba
                # skew ≈ epoch(a) − epoch(b), i.e. how much LATER b's
                # clock reads than a's for the same instant; offset
                # maps b onto the anchor clock (subtract it from b's
                # timestamps): offset(b) = offset(a) + skew
                offsets[b] = offsets[a] + skew
                frontier.append(b)
    unaligned = [l for l in sorted(labels) if l not in offsets]
    return offsets, unaligned


# ---------------------------------------------------------------------------
# serving percentiles from a metrics snapshot
# ---------------------------------------------------------------------------

# the two serving-latency SLO metrics and their report keys — one
# definition shared by the offline doctor and the live plane's
# per-window SLO feed
SLO_HISTOGRAMS = (
    ("serve_ttft_seconds", "ttft"),
    ("serve_tpot_seconds", "tpot"),
)


def percentiles_from_buckets(bounds, counts, count) -> dict:
    """One serving-percentile row (p50/p99 + honest estimator label)
    from an aggregated histogram — shared by the snapshot path below
    and the live plane's per-window bucket deltas."""
    from theanompi_tpu.observability.metrics import bucket_quantile

    return {
        "count": int(count),
        "p50_s": bucket_quantile(bounds, counts, 0.50),
        "p99_s": bucket_quantile(bounds, counts, 0.99),
        "estimator": "histogram",
    }


def serving_percentiles(snapshot: dict) -> dict:
    """TTFT/TPOT p50/p99 estimated from the registry snapshot's
    histogram buckets (``bucket_quantile``), label series summed.  The
    offline mirror of ``ServingMetrics.summary``'s overflow fallback —
    and the honest label says so (``estimator: histogram``)."""
    from theanompi_tpu.observability.metrics import sum_histogram_buckets

    out = {}
    for metric, key in SLO_HISTOGRAMS:
        agg = sum_histogram_buckets(snapshot.get(metric))
        if agg is None:
            continue
        bounds, counts, count = agg
        out[key] = percentiles_from_buckets(bounds, counts, count)
    return out


# ---------------------------------------------------------------------------
# the streaming doctor: analyze(), restated incrementally
# ---------------------------------------------------------------------------

def split_intervals(
    intervals: List[Tuple[float, float]], t: float
) -> Tuple[List[Tuple[float, float]], List[Tuple[float, float]]]:
    """Partition MERGED intervals at ``t`` (an interval straddling the
    cut is split) — the freeze primitive that keeps the streaming
    accumulator's live state bounded without losing totals."""
    before: List[Tuple[float, float]] = []
    after: List[Tuple[float, float]] = []
    for a, b in intervals:
        if b <= t:
            before.append((a, b))
        elif a >= t:
            after.append((a, b))
        else:
            before.append((a, t))
            after.append((t, b))
    return before, after


def _category(name) -> Optional[str]:
    if name in COMPUTE_SPANS:
        return "compute"
    if name in COMM_SPANS:
        return "comm"
    if name in WAIT_SPANS:
        return "wait"
    return None


_CATS = ("compute", "comm", "wait")


class _RankAcc:
    """One rank's streaming state: current-window buffers + bounded
    cumulative interval algebra (live merged lists, frozen totals)."""

    __slots__ = (
        "live", "frozen", "frozen_overlap", "frozen_busy", "t_frozen",
        "t_min", "t_max", "max_dur", "counts", "n_spans", "sample_rate",
        "dropped", "step_base", "boundaries", "step_durs",
        "steps_capped", "trackers", "stalls", "win", "win_steps",
        "win_counters",
    )

    def __init__(self):
        self.live = {c: [] for c in _CATS}
        self.frozen = {c: 0.0 for c in _CATS}
        self.frozen_overlap = 0.0
        self.frozen_busy = 0.0
        self.t_frozen: Optional[float] = None
        self.t_min: Optional[float] = None
        self.t_max: Optional[float] = None
        self.max_dur = 0.0
        self.counts = {c: 0 for c in _CATS}
        self.n_spans = 0
        self.sample_rate = 1
        self.dropped = 0
        self.step_base: Optional[float] = None
        self.boundaries: List[float] = []
        self.step_durs: List[float] = []
        self.steps_capped = False
        self.trackers: Dict[Any, StallTracker] = {}
        self.stalls: List[dict] = []
        self.win: Dict[str, List[Tuple[float, float]]] = {
            c: [] for c in _CATS
        }
        self.win_steps: List[Tuple[float, float]] = []
        self.win_counters: List[Tuple[float, Any, float]] = []


# version stamp on StreamingDoctor.snapshot() documents (and therefore
# on the aggregator checkpoints that embed them).  Policy: restore()
# refuses a snapshot whose version it does not know — silently
# misreading a future layout would fabricate verdicts, and a monitor
# that lies is worse than one that restarts cold (docs/observability.md
# "Surviving aggregator loss").
DOCTOR_SNAPSHOT_VERSION = 1
DOCTOR_SNAPSHOT_KIND = "tmpi_streaming_doctor"


class StreamingDoctor:
    """``analyze()`` restated as an incremental, windowed accumulator —
    the online doctor under the live telemetry plane.

    Feed each rank's raw trace events as they arrive
    (``feed(label, events)``); ``close_window()`` emits a verdict over
    everything fed since the previous close, shaped like the offline
    report (``ranks`` with fractions/overlap, cumulative
    ``stragglers``, ``stalls``, optional ``serving``) so
    ``check_thresholds`` gates a WINDOW exactly the way it gates a
    finished run.  ``cumulative()`` is the whole-stream report: the
    same interval-union math as ``analyze`` (the pure helpers are
    shared), kept bounded by freezing interval detail older than the
    stream's tail into plain totals — a week of monitoring holds a
    bounded working set while its lifetime fractions stay exact up to
    the freeze additivity (windows partition time, so union and
    intersection totals add across the freeze cut).

    Clock honesty: every rank's math runs on ITS OWN timestamps
    (per-rank fractions, per-rank-relative step boundaries), exactly
    like the offline doctor — no cross-rank timestamp comparison, so
    unsynchronized tracer epochs cannot skew verdicts.
    """

    # live merged-interval lists longer than this freeze their old end
    # into totals; spans can start at most 2×max_dur before the newest
    # end seen, so the cut never amputates a span yet to arrive
    MAX_LIVE_INTERVALS = 4096
    MAX_STEPS = 1_000_000  # boundary/dur caps: ~8 MB/rank worst case
    MAX_OPEN_FLOWS = 100_000  # unmatched arrow halves retained

    @classmethod
    def _cap_flows(cls, half: Dict[str, str]) -> None:
        while len(half) > cls.MAX_OPEN_FLOWS:
            del half[next(iter(half))]  # oldest first (insertion order)

    def __init__(self, stall_min_s: float = 0.0):
        self.stall_min_s = float(stall_min_s)
        self.ranks: Dict[str, _RankAcc] = {}
        self.n_windows = 0
        # cross-rank flow accounting (ids are globally unique)
        self._flow_begun: Dict[str, str] = {}
        self._flow_ended: Dict[str, str] = {}
        self._flows_matched = 0

    # ---- ingest --------------------------------------------------------
    def feed(
        self,
        label: str,
        events: Iterable[dict],
        sample_rate: int = 1,
        dropped: int = 0,
    ) -> None:
        """Absorb raw trace-event dicts (``ph`` X/C/s/f, µs timestamps
        on the rank's own clock) into the current window."""
        acc = self.ranks.get(label)
        if acc is None:
            acc = self.ranks[label] = _RankAcc()
        acc.sample_rate = max(acc.sample_rate, int(sample_rate))
        acc.dropped += int(dropped)
        for ev in events:
            ph = ev.get("ph")
            if ph == "X":
                ts = float(ev.get("ts", 0.0))
                dur = float(ev.get("dur", 0.0))
                acc.n_spans += 1
                acc.t_min = ts if acc.t_min is None else min(acc.t_min, ts)
                end = ts + dur
                acc.t_max = (
                    end if acc.t_max is None else max(acc.t_max, end)
                )
                acc.max_dur = max(acc.max_dur, dur)
                cat = _category(ev.get("name"))
                if cat is None:
                    continue
                acc.counts[cat] += 1
                acc.win[cat].append((ts, end))
                if cat == "compute":
                    acc.win_steps.append((ts, dur))
            elif ph == "C":
                if ev.get("name") != "inbox_depth":
                    continue
                args = ev.get("args") or {}
                acc.win_counters.append(
                    (
                        float(ev.get("ts", 0.0)),
                        args.get("rank"),
                        float(args.get("value", 0.0)),
                    )
                )
            elif ph == "s":
                fid = str(ev.get("id"))
                # frames interleave across ranks, so either half of an
                # arrow can arrive first — match symmetrically, retain
                # only the unmatched half (bounded)
                if self._flow_ended.pop(fid, None) is not None:
                    self._flows_matched += 1
                else:
                    self._flow_begun[fid] = label
                    self._cap_flows(self._flow_begun)
            elif ph == "f":
                fid = str(ev.get("id"))
                if self._flow_begun.pop(fid, None) is not None:
                    self._flows_matched += 1
                else:
                    self._flow_ended[fid] = label
                    self._cap_flows(self._flow_ended)

    # ---- windowing -----------------------------------------------------
    def close_window(self, final: bool = False) -> dict:
        """Verdict over everything fed since the last close, report-
        shaped so ``check_thresholds`` applies verbatim.  Stragglers
        are cumulative (lag is a property of the whole run so far);
        fractions/stalls are this window's.

        ``final=True`` is the end-of-stream flush: still-open stall
        windows are CLOSED at their last sample (the offline doctor's
        ``StallTracker.flush``) instead of reported as ongoing, so a
        replayed trace's last verdict matches what ``analyze`` says
        about the same tail."""
        self.n_windows += 1
        out: dict = {"window": self.n_windows, "ranks": {},
                     "stalls": [], "warnings": []}
        boundaries: Dict[str, List[float]] = {}
        for label, acc in sorted(self.ranks.items()):
            row = self._close_rank_window(acc, final=final)
            if row is not None:
                out["ranks"][label] = row
                for s in row.pop("_stall_rows"):
                    out["stalls"].append({"rank": label, **s})
            if acc.boundaries:
                boundaries[label] = acc.boundaries
        out["stragglers"] = straggler_summary(boundaries)
        return _round_floats(out)

    def _close_rank_window(
        self, acc: _RankAcc, final: bool = False
    ) -> Optional[dict]:
        win_int = {c: merge_intervals(acc.win[c]) for c in _CATS}
        steps = sorted(acc.win_steps)
        counters = sorted(acc.win_counters, key=lambda s: s[0])
        acc.win = {c: [] for c in _CATS}
        acc.win_steps = []
        acc.win_counters = []

        # stall trackers run on the stream even when the window is
        # otherwise idle; overlap is measured against the rank's
        # retained wait intervals (live + this window)
        wait_ivs = merge_intervals(acc.live["wait"] + win_int["wait"])
        stall_rows: List[dict] = []
        for ts, key, val in counters:
            tr = acc.trackers.get(key)
            if tr is None:
                tr = acc.trackers[key] = StallTracker()
            w = tr.feed(ts, val)
            if w is not None and (w[1] - w[0]) / 1e6 >= self.stall_min_s:
                row = stall_row(key, w, wait_ivs)
                stall_rows.append(row)
                acc.stalls.append(row)
        # a still-open stall alerts NOW, not when it finally drains;
        # the end-of-stream flush CLOSES it at the last sample instead
        # (offline-doctor semantics: a backed-up mailbox at the end of
        # the trace is a stall with an end, not a perpetual "ongoing")
        for key, tr in sorted(acc.trackers.items(),
                              key=lambda kv: str(kv[0])):
            if tr.start is not None and tr.last_ts is not None:
                if final:
                    w = tr.flush()
                    if (w[1] - w[0]) / 1e6 >= self.stall_min_s:
                        row_ = stall_row(key, w, wait_ivs)
                        stall_rows.append(row_)
                        acc.stalls.append(row_)
                else:
                    w = (tr.start, tr.last_ts, tr.max_depth)
                    if (w[1] - w[0]) / 1e6 >= self.stall_min_s:
                        stall_rows.append(
                            {**stall_row(key, w, wait_ivs),
                             "ongoing": True}
                        )

        has_spans = any(win_int.values()) or steps
        row: Optional[dict] = None
        if has_spans:
            all_iv = [iv for c in _CATS for iv in win_int[c]]
            t0 = min(a for a, _ in all_iv)
            t1 = max(b for _, b in all_iv)
            window = max(t1 - t0, 1e-9)
            busy = merge_intervals(
                win_int["compute"] + win_int["comm"] + win_int["wait"]
            )
            comm_total = total(win_int["comm"])
            overlap = intersect_total(win_int["comm"], win_int["compute"])
            durs = sorted(d / 1e6 for _, d in steps)
            row = {
                "window_s": window / 1e6,
                "steps": {
                    "n": len(durs),
                    "mean_s": (
                        sum(durs) / len(durs) if durs else float("nan")
                    ),
                    "max_s": durs[-1] if durs else float("nan"),
                },
                "fractions": {
                    "compute": total(win_int["compute"]) / window,
                    "comm": comm_total / window,
                    "input_wait": total(win_int["wait"]) / window,
                    "idle": max(0.0, (window - total(busy)) / window),
                },
                "comm_compute_overlap": (
                    overlap / comm_total if comm_total > 0 else None
                ),
            }
        elif stall_rows:
            row = {"window_s": 0.0, "steps": {"n": 0}}
        if row is not None:
            row["_stall_rows"] = stall_rows

        # fold the window into the cumulative structures
        for c in _CATS:
            if win_int[c]:
                acc.live[c] = merge_intervals(acc.live[c] + win_int[c])
        self._maybe_freeze(acc)
        for ts, dur in steps:
            if acc.step_base is None:
                acc.step_base = ts
            if len(acc.boundaries) < self.MAX_STEPS:
                acc.boundaries.append((ts + dur - acc.step_base) / 1e6)
                acc.step_durs.append(dur / 1e6)
            else:
                acc.steps_capped = True
        return row

    # ---- durable state -------------------------------------------------
    def snapshot(self) -> dict:
        """The doctor's whole accumulated state as one versioned,
        JSON-serializable dict: frozen-interval totals, the live
        interval tails, step boundaries, stall trackers (including a
        window still open mid-stall), current-window buffers and flow
        halves.  ``restore(snapshot())`` — even through a JSON
        round-trip — reproduces ``cumulative()`` EXACTLY, which is what
        lets a promoted standby or restarted aggregator carry a long
        run's trends across the takeover instead of starting at zero."""
        ranks: Dict[str, dict] = {}
        for label, acc in self.ranks.items():
            ranks[label] = {
                "live": {c: [list(iv) for iv in acc.live[c]]
                         for c in _CATS},
                "frozen": dict(acc.frozen),
                "frozen_overlap": acc.frozen_overlap,
                "frozen_busy": acc.frozen_busy,
                "t_frozen": acc.t_frozen,
                "t_min": acc.t_min,
                "t_max": acc.t_max,
                "max_dur": acc.max_dur,
                "counts": dict(acc.counts),
                "n_spans": acc.n_spans,
                "sample_rate": acc.sample_rate,
                "dropped": acc.dropped,
                "step_base": acc.step_base,
                "boundaries": list(acc.boundaries),
                "step_durs": list(acc.step_durs),
                "steps_capped": acc.steps_capped,
                # key types matter (counter args carry int OR str rank
                # labels) — a [key, state] pair list survives JSON, a
                # dict would stringify int keys
                "trackers": [
                    [key, {"start": tr.start, "max_depth": tr.max_depth,
                           "last_ts": tr.last_ts}]
                    for key, tr in acc.trackers.items()
                ],
                "stalls": [dict(s) for s in acc.stalls],
                "win": {c: [list(iv) for iv in acc.win[c]]
                        for c in _CATS},
                "win_steps": [list(t) for t in acc.win_steps],
                "win_counters": [list(t) for t in acc.win_counters],
            }
        return {
            "kind": DOCTOR_SNAPSHOT_KIND,
            "v": DOCTOR_SNAPSHOT_VERSION,
            "stall_min_s": self.stall_min_s,
            "n_windows": self.n_windows,
            "flows": {
                "begun": dict(self._flow_begun),
                "ended": dict(self._flow_ended),
                "matched": self._flows_matched,
            },
            "ranks": ranks,
        }

    @classmethod
    def restore(cls, snap: dict) -> "StreamingDoctor":
        """Rebuild a doctor from ``snapshot()`` output.  Refuses
        anything that is not a known-version doctor snapshot — see the
        version policy above ``DOCTOR_SNAPSHOT_VERSION``."""
        if not isinstance(snap, dict) or snap.get("kind") != \
                DOCTOR_SNAPSHOT_KIND:
            raise ValueError(
                "not a StreamingDoctor snapshot (kind="
                f"{snap.get('kind') if isinstance(snap, dict) else type(snap).__name__!r})"
            )
        v = snap.get("v")
        if v != DOCTOR_SNAPSHOT_VERSION:
            raise ValueError(
                f"doctor snapshot version {v!r} not supported (this "
                f"build reads v{DOCTOR_SNAPSHOT_VERSION}); re-run the "
                "matching build or start the monitor cold"
            )
        d = cls(stall_min_s=float(snap.get("stall_min_s", 0.0)))
        d.n_windows = int(snap.get("n_windows", 0))
        fl = snap.get("flows") or {}
        d._flow_begun = {str(k): str(lab)
                         for k, lab in (fl.get("begun") or {}).items()}
        d._flow_ended = {str(k): str(lab)
                         for k, lab in (fl.get("ended") or {}).items()}
        d._flows_matched = int(fl.get("matched", 0))
        for label, doc in (snap.get("ranks") or {}).items():
            acc = d.ranks[str(label)] = _RankAcc()
            acc.live = {
                c: [(float(a), float(b))
                    for a, b in (doc.get("live") or {}).get(c, [])]
                for c in _CATS
            }
            acc.frozen = {c: float((doc.get("frozen") or {}).get(c, 0.0))
                          for c in _CATS}
            acc.frozen_overlap = float(doc.get("frozen_overlap", 0.0))
            acc.frozen_busy = float(doc.get("frozen_busy", 0.0))
            acc.t_frozen = doc.get("t_frozen")
            acc.t_min = doc.get("t_min")
            acc.t_max = doc.get("t_max")
            acc.max_dur = float(doc.get("max_dur", 0.0))
            acc.counts = {c: int((doc.get("counts") or {}).get(c, 0))
                          for c in _CATS}
            acc.n_spans = int(doc.get("n_spans", 0))
            acc.sample_rate = int(doc.get("sample_rate", 1))
            acc.dropped = int(doc.get("dropped", 0))
            acc.step_base = doc.get("step_base")
            acc.boundaries = [float(b) for b in doc.get("boundaries", [])]
            acc.step_durs = [float(s) for s in doc.get("step_durs", [])]
            acc.steps_capped = bool(doc.get("steps_capped", False))
            for key, st in doc.get("trackers", []):
                tr = StallTracker()
                tr.start = st.get("start")
                tr.max_depth = float(st.get("max_depth", 0.0))
                tr.last_ts = st.get("last_ts")
                acc.trackers[key] = tr
            acc.stalls = [dict(s) for s in doc.get("stalls", [])]
            acc.win = {
                c: [(float(a), float(b))
                    for a, b in (doc.get("win") or {}).get(c, [])]
                for c in _CATS
            }
            acc.win_steps = [
                (float(a), float(b)) for a, b in doc.get("win_steps", [])
            ]
            acc.win_counters = [
                (float(ts), key, float(val))
                for ts, key, val in doc.get("win_counters", [])
            ]
        return d

    def _maybe_freeze(self, acc: _RankAcc) -> None:
        if all(
            len(acc.live[c]) <= self.MAX_LIVE_INTERVALS for c in _CATS
        ):
            return
        cut = (acc.t_max or 0.0) - 2.0 * max(acc.max_dur, 1.0)
        if acc.t_frozen is not None and cut <= acc.t_frozen:
            return
        before = {}
        after = {}
        for c in _CATS:
            before[c], after[c] = split_intervals(acc.live[c], cut)
        acc.frozen_overlap += intersect_total(
            before["comm"], before["compute"]
        )
        acc.frozen_busy += total(
            merge_intervals(
                before["compute"] + before["comm"] + before["wait"]
            )
        )
        for c in _CATS:
            acc.frozen[c] += total(before[c])
            acc.live[c] = after[c]
        acc.t_frozen = cut

    # ---- whole-stream report ------------------------------------------
    def cumulative(self) -> dict:
        """The stream so far as ONE report, shaped like ``analyze()``'s
        (the replay of a finished run reproduces the post-mortem
        verdict — golden-tested)."""
        report: dict = {"ranks": {}, "warnings": []}
        boundaries: Dict[str, List[float]] = {}
        for label, acc in sorted(self.ranks.items()):
            report["ranks"][label] = self._cumulative_rank(acc)
            if acc.n_spans == 0:
                report["warnings"].append(
                    f"{label}: empty stream — no spans received from "
                    "this rank yet"
                )
            if acc.dropped:
                report["warnings"].append(
                    f"{label}: {acc.dropped} events dropped before "
                    "shipping — fractions undercount the dropped window"
                )
            if acc.steps_capped:
                report["warnings"].append(
                    f"{label}: step history capped at {self.MAX_STEPS} "
                    "boundaries — straggler lag reflects the capped "
                    "prefix"
                )
            if acc.boundaries:
                boundaries[label] = acc.boundaries
        report["stragglers"] = straggler_summary(boundaries)
        unmatched_begin = sorted(self._flow_begun)
        report["flows"] = {
            "begun": self._flows_matched + len(self._flow_begun),
            "ended": self._flows_matched + len(self._flow_ended),
            "matched": self._flows_matched,
            "unmatched_begin": unmatched_begin,
            "unmatched_end": sorted(self._flow_ended),
        }
        if unmatched_begin:
            report["warnings"].append(
                f"{len(unmatched_begin)} flow(s) begun but never "
                "drained — frames in flight, lost, or the receiver's "
                "stream is behind"
            )
        stalls = []
        for label, acc in sorted(self.ranks.items()):
            for s in acc.stalls:
                stalls.append({"rank": label, **s})
            # ongoing stalls are visible in the lifetime report too
            wait_ivs = acc.live["wait"]
            for key, tr in sorted(acc.trackers.items(),
                                  key=lambda kv: str(kv[0])):
                if tr.start is not None and tr.last_ts is not None:
                    w = (tr.start, tr.last_ts, tr.max_depth)
                    if (w[1] - w[0]) / 1e6 >= self.stall_min_s:
                        stalls.append(
                            {"rank": label,
                             **stall_row(key, w, wait_ivs)}
                        )
        report["stalls"] = stalls
        return _round_floats(report)

    def _cumulative_rank(self, acc: _RankAcc) -> dict:
        if acc.n_spans == 0:
            return {
                "empty": True,
                "n_spans": 0,
                "sample_rate": acc.sample_rate,
                "dropped": acc.dropped,
            }
        window = max((acc.t_max or 0.0) - (acc.t_min or 0.0), 1e-9)
        totals = {
            c: acc.frozen[c] + total(acc.live[c]) for c in _CATS
        }
        busy = acc.frozen_busy + total(
            merge_intervals(
                acc.live["compute"] + acc.live["comm"] + acc.live["wait"]
            )
        )
        overlap = acc.frozen_overlap + intersect_total(
            acc.live["comm"], acc.live["compute"]
        )
        durs = sorted(acc.step_durs)
        out = {
            "empty": False,
            "n_spans": acc.n_spans,
            "window_s": window / 1e6,
            "steps": {
                "n": len(durs),
                "total_s": sum(durs),
                "mean_s": (
                    sum(durs) / len(durs) if durs else float("nan")
                ),
                "p50_s": _nearest_rank(durs, 50),
                "max_s": durs[-1] if durs else float("nan"),
            },
            "fractions": {
                "compute": totals["compute"] / window,
                "comm": totals["comm"] / window,
                "input_wait": totals["wait"] / window,
                "idle": max(0.0, (window - busy) / window),
            },
            "comm_compute_overlap": (
                overlap / totals["comm"] if totals["comm"] > 0 else None
            ),
            "sample_rate": acc.sample_rate,
            "dropped": acc.dropped,
        }
        if acc.sample_rate > 1:
            fr = out["fractions"]
            ci = {
                "compute": sampled_ci95(
                    fr["compute"], acc.counts["compute"], acc.sample_rate
                ),
                "comm": sampled_ci95(
                    fr["comm"], acc.counts["comm"], acc.sample_rate
                ),
                "input_wait": sampled_ci95(
                    fr["input_wait"], acc.counts["wait"], acc.sample_rate
                ),
            }
            ci["idle"] = min(
                1.0,
                (ci["compute"] ** 2 + ci["comm"] ** 2
                 + ci["input_wait"] ** 2) ** 0.5,
            )
            out["fractions_ci95"] = ci
            if out["comm_compute_overlap"] is not None:
                out["comm_compute_overlap_ci95"] = sampled_ci95(
                    1.0,
                    min(acc.counts["compute"], acc.counts["comm"]),
                    acc.sample_rate,
                )
        return out


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

def analyze(
    named_traces: Iterable[Tuple[str, Iterable[str]]],
    metrics_snapshot: Optional[dict] = None,
    stall_min_s: float = 0.0,
) -> dict:
    """The doctor's whole diagnosis as one JSON-serializable dict.

    ``named_traces``: ``(label, raw JSONL lines)`` per rank — the same
    shape ``merge_raw_traces`` takes.  ``metrics_snapshot``: an
    optional registry ``snapshot()`` dict (the ``*metrics.json``
    artifact) for the serving section.  ``stall_min_s`` filters queue
    stalls shorter than the threshold.
    """
    ranks = [parse_raw(label, lines) for label, lines in named_traces]
    report: dict = {"ranks": {}, "warnings": []}
    boundaries: Dict[str, List[float]] = {}
    for r in ranks:
        ra = _analyze_rank(r, stall_min_s)
        report["ranks"][r["label"]] = ra
        if ra["empty"]:
            report["warnings"].append(
                f"{r['label']}: empty trace — dead worker or truncated "
                "file (rank kept visible, not dropped)"
            )
            continue
        if ra["dropped"]:
            report["warnings"].append(
                f"{r['label']}: {ra['dropped']} events evicted by the "
                "buffer bound — fractions undercount the evicted window"
            )
        b = _step_boundaries(r)
        if b:
            boundaries[r["label"]] = b

    report["stragglers"] = straggler_summary(boundaries)

    # ---- cross-rank flow accounting: arrows must close
    begun: Dict[str, str] = {}
    ended: Dict[str, str] = {}
    for r in ranks:
        for fid in r["flow_begin"]:
            begun[fid] = r["label"]
        for fid in r["flow_end"]:
            ended[fid] = r["label"]
    matched = set(begun) & set(ended)
    report["flows"] = {
        "begun": len(begun),
        "ended": len(ended),
        "matched": len(matched),
        "unmatched_begin": sorted(set(begun) - matched),
        "unmatched_end": sorted(set(ended) - matched),
    }
    if report["flows"]["unmatched_begin"]:
        report["warnings"].append(
            f"{len(report['flows']['unmatched_begin'])} flow(s) begun "
            "but never drained — frames in flight at dump time, lost, "
            "or the receiver's trace is missing"
        )

    stalls = [
        {"rank": label, **s}
        for label, ra in sorted(report["ranks"].items())
        for s in ra.get("stalls", [])
    ]
    report["stalls"] = stalls

    if metrics_snapshot:
        serving = serving_percentiles(metrics_snapshot)
        if serving:
            report["serving"] = serving
    return _round_floats(report)


def _round_floats(doc: Any, ndigits: int = 9) -> Any:
    """Stable report floats (the golden fixture pins the whole dict)."""
    if isinstance(doc, float):
        return round(doc, ndigits)
    if isinstance(doc, dict):
        return {k: _round_floats(v, ndigits) for k, v in doc.items()}
    if isinstance(doc, list):
        return [_round_floats(v, ndigits) for v in doc]
    return doc


# ---------------------------------------------------------------------------
# verdicts
# ---------------------------------------------------------------------------

def check_thresholds_structured(
    report: dict,
    max_straggler: Optional[float] = None,
    min_overlap: Optional[float] = None,
    max_stall_s: Optional[float] = None,
    max_ttft_p99_s: Optional[float] = None,
    max_tpot_p99_s: Optional[float] = None,
) -> List[dict]:
    """Violations as structured rows (``rule``/``rank``/``value``/
    ``threshold``/``message``) — what the live watchdog turns into
    alerts and the CLI renders as strings.  Empty = healthy.

    Fractions from a SAMPLED trace carry error bars
    (``*_ci95``); threshold comparisons use the conservative end of
    the interval — the gate only fires when the violation survives
    the sampling uncertainty, so a 1-in-N trace cannot flake CI."""
    v: List[dict] = []
    idx = report.get("stragglers", {}).get("max_straggler_index", 0.0)
    if max_straggler is not None and idx > max_straggler:
        who = report["stragglers"].get("straggler_rank")
        v.append({
            "rule": "max_straggler", "rank": who, "value": idx,
            "threshold": max_straggler,
            "message": (
                f"straggler index {idx:.4f} > {max_straggler} "
                f"(rank {who})"
            ),
        })
    if min_overlap is not None:
        for label, ra in sorted(report.get("ranks", {}).items()):
            ov = ra.get("comm_compute_overlap")
            if ov is None:
                continue
            ci = float(ra.get("comm_compute_overlap_ci95") or 0.0)
            if ov + ci < min_overlap:
                note = f" (+{ci:.4f} ci95)" if ci else ""
                v.append({
                    "rule": "min_overlap", "rank": label, "value": ov,
                    "threshold": min_overlap,
                    "message": (
                        f"{label}: comm/compute overlap {ov:.4f}"
                        f"{note} < {min_overlap}"
                    ),
                })
    if max_stall_s is not None:
        for s in report.get("stalls", []):
            if s["duration_s"] > max_stall_s:
                v.append({
                    "rule": "max_stall_s", "rank": s.get("rank"),
                    "value": s["duration_s"], "threshold": max_stall_s,
                    "message": (
                        f"{s['rank']}: inbox stall "
                        f"{s['duration_s']:.4f}s > {max_stall_s}s "
                        f"(depth {s['max_depth']:.0f})"
                    ),
                })
    serving = report.get("serving", {})
    for key, bound in (
        ("ttft", max_ttft_p99_s),
        ("tpot", max_tpot_p99_s),
    ):
        if bound is not None and key in serving:
            p99 = serving[key]["p99_s"]
            if p99 > bound:
                v.append({
                    "rule": f"max_{key}_p99_s", "rank": None,
                    "value": p99, "threshold": bound,
                    "message": f"{key} p99 {p99:.4f}s > {bound}s",
                })
    return v


def check_thresholds(report: dict, **thresholds) -> List[str]:
    """Violations as human strings (empty = healthy).  The CLI exits
    nonzero when any fire — the perf-regression gate."""
    return [
        row["message"]
        for row in check_thresholds_structured(report, **thresholds)
    ]


# ---------------------------------------------------------------------------
# human rendering
# ---------------------------------------------------------------------------

def _pct(x) -> str:
    return "-" if x is None else f"{100.0 * x:5.1f}%"


def render_report(report: dict) -> str:
    lines: List[str] = []
    hdr = (
        f"{'rank':<14} {'steps':>6} {'mean ms':>8} {'compute':>8} "
        f"{'comm':>7} {'wait':>7} {'idle':>7} {'overlap':>8}"
    )
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for label, ra in sorted(report.get("ranks", {}).items()):
        if ra.get("empty"):
            lines.append(f"{label:<14} EMPTY TRACE (dead worker?)")
            continue
        st, fr = ra["steps"], ra["fractions"]
        mean_ms = (
            f"{st['mean_s'] * 1e3:8.2f}" if st["n"] else f"{'-':>8}"
        )
        lines.append(
            f"{label:<14} {st['n']:>6} {mean_ms} "
            f"{_pct(fr['compute']):>8} {_pct(fr['comm']):>7} "
            f"{_pct(fr['input_wait']):>7} {_pct(fr['idle']):>7} "
            f"{_pct(ra['comm_compute_overlap']):>8}"
        )
        ci = ra.get("fractions_ci95")
        if ci:
            ov_ci = ra.get("comm_compute_overlap_ci95")
            lines.append(
                f"{'':<14} sampled 1/{ra.get('sample_rate', '?')}: "
                f"±{100 * ci['compute']:.1f}% compute, "
                f"±{100 * ci['comm']:.1f}% comm"
                + (
                    f", ±{100 * ov_ci:.1f}% overlap (95% ci)"
                    if ov_ci is not None
                    else " (95% ci)"
                )
            )
    sg = report.get("stragglers", {})
    if sg.get("per_rank"):
        lines.append("")
        lines.append(
            f"stragglers (over {sg['n_common_steps']} common steps; "
            "lag vs fastest rank at each boundary):"
        )
        for label, row in sorted(sg["per_rank"].items()):
            mark = "  <-- STRAGGLER" if label == sg["straggler_rank"] and \
                sg["max_straggler_index"] > 0 else ""
            lines.append(
                f"  {label:<12} final lag {row['final_lag_s'] * 1e3:8.2f} ms"
                f"  index {row['straggler_index']:.4f}{mark}"
            )
    if report.get("stalls"):
        lines.append("")
        lines.append("inbox stalls (depth > 0 windows):")
        for s in report["stalls"]:
            lines.append(
                f"  {s['rank']:<12} [{s['start_s']:.4f}s .. "
                f"{s['end_s']:.4f}s] depth<= {s['max_depth']:.0f}  "
                f"in-recv {s['recv_wait_overlap_s'] * 1e3:.2f} ms"
            )
    fl = report.get("flows", {})
    if fl.get("begun") or fl.get("ended"):
        lines.append("")
        lines.append(
            f"flows: {fl['matched']}/{fl['begun']} matched"
            + (
                f", {len(fl['unmatched_begin'])} never drained"
                if fl.get("unmatched_begin")
                else ""
            )
        )
    if report.get("serving"):
        lines.append("")
        for key, row in sorted(report["serving"].items()):
            lines.append(
                f"serving {key}: p50 {row['p50_s'] * 1e3:.2f} ms  "
                f"p99 {row['p99_s'] * 1e3:.2f} ms  "
                f"({row['count']} obs, {row['estimator']} estimator)"
            )
    for w in report.get("warnings", []):
        lines.append(f"WARNING: {w}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# the request doctor: one retained request → a phase attribution
# ---------------------------------------------------------------------------

# the phase list, in REPORT order.  One definition: the
# instrumentation sites (scheduler/fleet) emit the ``req_*`` spans,
# the tracer's tail retention buffers them, and this table is where
# the agreement on what they MEAN lives.
REQUEST_PHASES = (
    "queue",
    "backpressure",
    "prefill",
    "decode",
    "spec_rollback",
    "install_wait",
    "readmission",
)

# span names contributing to each phase.  ``prefill`` is the
# scheduler's per-lane phase span (``req_prefill``).  ``req_spec``
# counts as decode wall time; its rolled-back share is carved out
# scalar-wise below.
_PHASE_SPANS = {
    "queue": ("req_queue",),
    "backpressure": ("req_backpressure",),
    "prefill": ("req_prefill",),
    "decode": ("req_decode", "req_spec"),
    "install_wait": ("req_install_wait",),
    "readmission": ("req_readmit",),
}

# attribution priority, highest first: when two phases overlap in wall
# time (a backpressure stall measured while the lane also sat queued,
# an install wait spanning a decode tick) the HIGHER-priority phase
# keeps the overlap and the lower one is clipped around it — every
# microsecond lands in exactly one phase, so the columns sum to at
# most the measured latency instead of double-counting.  Rarer,
# more-actionable causes outrank the steady-state ones.
_PHASE_PRIORITY = (
    "readmission",
    "install_wait",
    "backpressure",
    "prefill",
    "decode",
    "queue",
)


def request_breakdown(record: dict) -> dict:
    """One retained request record (``Tracer.retained_requests`` /
    ``worst_requests`` element) → its phase attribution.

    Pure interval math over the buffered spans, clipped to the
    request's own ``[t_start_us, t_end_us]`` window and assigned by
    ``_PHASE_PRIORITY`` subtraction (``merge_intervals`` /
    ``intersect_total`` — the same primitives the rank doctor runs).
    ``spec_rollback`` is then carved scalar-wise out of decode: each
    ``req_spec`` span donates ``dur × rolled_back / max(1, proposed)``
    — the share of the round's wall time spent verifying proposals the
    target rejected.  Returns phase seconds, the unattributed
    remainder, and ``coverage`` (attributed / latency) — the number
    the FORENSICS perf-gate leg pins ≥ 0.9."""
    t0 = float(record.get("t_start_us", 0.0))
    t1 = float(record.get("t_end_us", t0))
    events = record.get("events") or []
    spans = [ev for ev in events if ev.get("ph") == "X"]

    def _clipped(names: Tuple[str, ...]) -> List[Tuple[float, float]]:
        wanted = set(names)
        ivs: List[Tuple[float, float]] = []
        for s in spans:
            if s.get("name") not in wanted:
                continue
            a = float(s.get("ts", 0.0))
            b = a + float(s.get("dur", 0.0))
            a, b = max(a, t0), min(b, t1)
            if b > a:
                ivs.append((a, b))
        return merge_intervals(ivs)

    phases = {p: 0.0 for p in REQUEST_PHASES}
    assigned: List[Tuple[float, float]] = []
    for phase in _PHASE_PRIORITY:
        iv = _clipped(_PHASE_SPANS[phase])
        phases[phase] = (total(iv) - intersect_total(iv, assigned)) / 1e6
        assigned = merge_intervals(assigned + iv)

    rollback_us = 0.0
    for s in spans:
        if s.get("name") != "req_spec":
            continue
        args = s.get("args") or {}
        proposed = float(args.get("proposed", 0) or 0)
        rolled = float(args.get("rolled_back", 0) or 0)
        if rolled > 0:
            rollback_us += (
                float(s.get("dur", 0.0)) * rolled / max(1.0, proposed)
            )
    # the carve can never exceed what decode actually owns after the
    # priority subtraction (a rollback share of time clipped away by a
    # higher-priority phase is already attributed there)
    rollback_s = min(rollback_us / 1e6, phases["decode"])
    phases["spec_rollback"] = rollback_s
    phases["decode"] -= rollback_s

    latency = float(record.get("latency_s", max(0.0, (t1 - t0) / 1e6)))
    attributed = sum(phases.values())
    unattributed = max(0.0, latency - attributed)
    out = {
        "rid": record.get("rid"),
        "status": record.get("status", "ok"),
        "flags": list(record.get("flags") or []),
        "latency_s": latency,
        "phases": dict(phases),
        "attributed_s": attributed,
        "unattributed_s": unattributed,
        "coverage": (
            min(1.0, attributed / latency) if latency > 0 else 1.0
        ),
        "n_events": len(events),
        "truncated": int(record.get("truncated", 0)),
    }
    if "n_tokens" in record:
        out["n_tokens"] = record["n_tokens"]
    for mark in record.get("marks") or []:
        if mark.get("name") == "first_token":
            out["ttft_s"] = max(
                0.0, (float(mark.get("ts", t0)) - t0) / 1e6
            )
            break
    return _round_floats(out)


def request_report(records: Iterable[dict]) -> dict:
    """Fleet-level view over many retained requests: per-request rows
    (worst-first), aggregate phase fractions, and the p50/p99 request
    breakdowns — the phase-attribution table the ISSUE's doctor
    prints.  ``p50``/``p99`` are the breakdowns of the requests AT
    those latency ranks (nearest-rank, same estimator as the rank
    doctor), not an average: an attribution table that sums to one
    real request's measured latency, not to a synthetic blend."""
    rows = [request_breakdown(r) for r in records]
    by_lat = sorted(rows, key=lambda r: r["latency_s"])
    out: dict = {
        "n_requests": len(rows),
        "requests": sorted(rows, key=lambda r: -r["latency_s"]),
    }
    total_lat = sum(r["latency_s"] for r in rows)
    totals = {
        p: sum(r["phases"][p] for r in rows) for p in REQUEST_PHASES
    }
    out["phase_totals_s"] = totals
    out["phase_fractions"] = {
        p: (totals[p] / total_lat if total_lat > 0 else 0.0)
        for p in REQUEST_PHASES
    }
    out["unattributed_s"] = sum(r["unattributed_s"] for r in rows)
    out["unattributed_frac"] = (
        out["unattributed_s"] / total_lat if total_lat > 0 else 0.0
    )
    if by_lat:
        for pct, key in ((50, "p50"), (99, "p99")):
            k = max(
                0,
                min(
                    len(by_lat) - 1,
                    int(round(pct / 100.0 * (len(by_lat) - 1))),
                ),
            )
            row = by_lat[k]
            out[key] = {
                "rid": row["rid"],
                "latency_s": row["latency_s"],
                "phases": dict(row["phases"]),
                "unattributed_s": row["unattributed_s"],
                "coverage": row["coverage"],
            }
    return _round_floats(out)


def check_request_thresholds(
    report: dict,
    max_queue_frac: Optional[float] = None,
    max_p99_unattributed_frac: Optional[float] = None,
) -> List[dict]:
    """Request-attribution violations as structured rows (same shape
    as ``check_thresholds_structured``; empty = healthy).

    ``max_queue_frac`` gates the AGGREGATE queue share of total
    request latency — the capacity signal (requests spending their
    lives queued means the fleet is undersized, not slow).
    ``max_p99_unattributed_frac`` gates the p99 request's unexplained
    remainder — the doctor's own honesty check: a tail request whose
    latency the phases cannot explain means an instrumentation gap,
    and the gate fails instead of shrugging."""
    v: List[dict] = []
    if max_queue_frac is not None:
        qf = float(
            (report.get("phase_fractions") or {}).get("queue", 0.0)
        )
        if qf > max_queue_frac:
            v.append({
                "rule": "max_queue_frac", "rank": None, "value": qf,
                "threshold": max_queue_frac,
                "message": (
                    f"queue fraction {qf:.4f} > {max_queue_frac} of "
                    "total request latency — admission-bound fleet"
                ),
            })
    if max_p99_unattributed_frac is not None:
        p99 = report.get("p99")
        if p99 and p99.get("latency_s", 0.0) > 0:
            uf = float(p99["unattributed_s"]) / float(p99["latency_s"])
            if uf > max_p99_unattributed_frac:
                v.append({
                    "rule": "max_p99_unattributed_frac",
                    "rank": p99.get("rid"), "value": uf,
                    "threshold": max_p99_unattributed_frac,
                    "message": (
                        f"p99 request {p99.get('rid')}: "
                        f"{100 * uf:.1f}% of its "
                        f"{p99['latency_s']:.4f}s latency is "
                        "unattributed > "
                        f"{100 * max_p99_unattributed_frac:.1f}% — "
                        "instrumentation gap in the phase list"
                    ),
                })
    return v


def load_requests(path) -> dict:
    """Parse a ``*requests.json`` artifact (``export.dump_all``'s
    request-forensics document).  Refuses anything that is not one —
    pointing the request doctor at a metrics snapshot should say so,
    not render an empty table."""
    with open(path) as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict) or doc.get("kind") != "tmpi_requests":
        raise ValueError(
            f"{path}: not a request-forensics artifact (kind="
            f"{doc.get('kind') if isinstance(doc, dict) else type(doc).__name__!r})"
        )
    return doc


def _ms(x: float) -> str:
    return f"{x * 1e3:9.2f}"


def render_request_breakdown(row: dict) -> str:
    """One request's attribution as a human table — the
    ``doctor --request RID`` view."""
    lines: List[str] = []
    flags = (
        " [" + ",".join(row["flags"]) + "]" if row.get("flags") else ""
    )
    lines.append(
        f"request {row.get('rid')}  status={row.get('status')}{flags}"
    )
    lines.append(
        f"  latency {row['latency_s'] * 1e3:.2f} ms"
        + (
            f"  ttft {row['ttft_s'] * 1e3:.2f} ms"
            if "ttft_s" in row else ""
        )
        + (
            f"  tokens {row['n_tokens']}" if "n_tokens" in row else ""
        )
    )
    lines.append(f"  {'phase':<14} {'ms':>9} {'share':>7}")
    lat = row["latency_s"] or 1e-12
    for p in REQUEST_PHASES:
        s = row["phases"][p]
        if s <= 0:
            continue
        lines.append(f"  {p:<14} {_ms(s)} {100 * s / lat:6.1f}%")
    lines.append(
        f"  {'unattributed':<14} {_ms(row['unattributed_s'])} "
        f"{100 * row['unattributed_s'] / lat:6.1f}%"
    )
    lines.append(
        f"  coverage {100 * row['coverage']:.1f}% over "
        f"{row['n_events']} events"
        + (
            f" (TRUNCATED: {row['truncated']} dropped)"
            if row.get("truncated") else ""
        )
    )
    return "\n".join(lines) + "\n"


def render_request_report(report: dict, worst: int = 5) -> str:
    """The fleet table: worst-``worst`` requests with their dominant
    phase, then the p50/p99 attribution rows, then aggregate phase
    fractions."""
    lines: List[str] = []
    n = report.get("n_requests", 0)
    lines.append(f"retained requests: {n}")
    if not n:
        return lines[0] + "\n"
    hdr = (
        f"  {'rid':<14} {'status':<9} {'latency ms':>10} "
        f"{'dominant phase':<16} {'coverage':>8}"
    )
    lines.append(hdr)
    lines.append("  " + "-" * (len(hdr) - 2))
    for row in report["requests"][: max(0, int(worst))]:
        dom = max(REQUEST_PHASES, key=lambda p: row["phases"][p])
        if row["unattributed_s"] > row["phases"][dom]:
            dom = "unattributed"
        flags = "!" if row.get("flags") else " "
        lines.append(
            f"  {str(row.get('rid')):<14} {row['status']:<9}"
            f"{flags}{row['latency_s'] * 1e3:>9.2f} {dom:<16} "
            f"{100 * row['coverage']:>7.1f}%"
        )
    for key in ("p50", "p99"):
        pr = report.get(key)
        if not pr:
            continue
        parts = [
            f"{p} {pr['phases'][p] * 1e3:.1f}ms"
            for p in REQUEST_PHASES
            if pr["phases"][p] > 0
        ]
        if pr["unattributed_s"] > 0:
            parts.append(f"unattributed {pr['unattributed_s'] * 1e3:.1f}ms")
        lines.append(
            f"{key} ({pr['rid']}, {pr['latency_s'] * 1e3:.2f} ms): "
            + (", ".join(parts) if parts else "no attributed time")
        )
    fr = report.get("phase_fractions") or {}
    shares = [
        f"{p} {100 * fr[p]:.1f}%" for p in REQUEST_PHASES
        if fr.get(p, 0.0) > 0.0005
    ]
    if report.get("unattributed_frac", 0.0) > 0.0005:
        shares.append(
            f"unattributed {100 * report['unattributed_frac']:.1f}%"
        )
    if shares:
        lines.append("fleet latency shares: " + ", ".join(shares))
    return "\n".join(lines) + "\n"
