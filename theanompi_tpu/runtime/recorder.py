"""Recorder — per-iteration timing and metric bookkeeping.

Re-creation of the reference's homegrown profiler
(upstream ``theanompi/lib/recorder.py``, class ``Recorder``; SURVEY.md
§3.7 / §6 "Tracing"): wall-clock split per iteration into calc / comm /
wait / load segments, running train loss+error, per-epoch val error, a
print every K iterations, and a record dumped to disk for offline plots.

TPU-honesty note: JAX dispatch is async, so a naive ``time.time()`` around
a jitted call measures dispatch, not compute.  With the default
``sync_each_iter=False`` the models deliberately do NOT fence each step
(a fence after every step stalls the dispatch pipeline), so ``calc`` rows record dispatch time only; true throughput is
what ``end_epoch`` wall-time and ``bench.py`` report.  Set
``sync_each_iter=True`` in the model config for reference-style honest
per-step calc/comm/wait splits, or drive ``jax.profiler`` traces for
op-level depth.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

from theanompi_tpu import observability as _obs

PHASES = ("calc", "comm", "wait", "load")
# the phases recorded as boundary spans (always on, children of the
# model's `train_iter` span); `comm` and `load` stay ordinary spans
BOUNDARY_PHASES = ("calc", "wait")


class Recorder:
    def __init__(
        self,
        print_freq: int = 40,
        rank: int = 0,
        verbose: bool = True,
        save_dir: Optional[str] = None,
        tensorboard_dir: Optional[str] = None,
    ):
        self.print_freq = int(print_freq)
        self.rank = rank
        self.verbose = verbose
        self.save_dir = save_dir
        # Optional TensorBoard mirror of the JSONL record (SURVEY.md §6
        # metrics row: "structured JSONL + optional TensorBoard
        # writer"). torch's SummaryWriter is the only TB implementation
        # in this environment; unavailable → warn once, JSONL only.
        self._tb = None
        if tensorboard_dir:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(log_dir=tensorboard_dir)
            except Exception as e:
                print(
                    f"tensorboard writer unavailable "
                    f"({type(e).__name__}: {e}); recording JSONL only",
                    flush=True,
                )

        self._t0: Dict[str, float] = {}
        self._spans: Dict[str, object] = {}  # phase -> its open trace span
        # accumulated seconds per phase since last print
        self._acc: Dict[str, float] = {p: 0.0 for p in PHASES}
        # full history rows for offline plotting (reference dumps a record
        # file loadable by a show_record.py-style script)
        self.history: List[dict] = []

        self._train_cost = 0.0
        self._train_err = 0.0
        self._train_n = 0
        self.epoch_start: Optional[float] = None
        # counter baseline for per-epoch deltas: captured at the first
        # start_epoch (so compile/startup counts never pollute epoch 0)
        # and rolled forward at every end_epoch
        self._counter_base: Optional[Dict[str, float]] = None
        self.val_history: List[dict] = []
        # one-off structured events (comm-fraction probe, restarts, …);
        # saved to the record file with their own `kind`
        self.events: List[dict] = []

    # ---- timing segments ------------------------------------------------
    def start(self, what: str = "calc") -> None:
        # every start/end pair is also a trace span — the phase columns
        # become a timeline for free.  `calc` and `wait` are boundary
        # spans (recorded with tracing off, and annotations in a
        # profile); the others are no-ops unless tracing is enabled.
        stale = self._spans.pop(what, None)
        if stale is not None:
            stale.cancel()  # started again before it was ended
        span = _obs.span(what, boundary=what in BOUNDARY_PHASES)
        self._spans[what] = span.__enter__()
        self._t0[what] = time.perf_counter()

    def end(self, what: str = "calc") -> float:
        t0 = self._t0.pop(what, None)
        if t0 is None:
            return 0.0
        dt = time.perf_counter() - t0
        self._acc[what] = self._acc.get(what, 0.0) + dt
        self._spans.pop(what).__exit__(None, None, None)
        return dt

    # ---- epoch ----------------------------------------------------------
    def start_epoch(self) -> None:
        self.epoch_start = time.perf_counter()
        if self._counter_base is None:
            self._counter_base = _obs.counter_values()

    def end_epoch(self, count: int, epoch: int) -> float:
        now = time.perf_counter()
        dt = now - self.epoch_start if self.epoch_start is not None else 0.0
        if self.epoch_start is not None:
            _obs.add_span("epoch", self.epoch_start, now, {"epoch": epoch})
        if self.verbose and self.rank == 0:
            print(f"epoch {epoch} took {dt:.2f}s", flush=True)
        if self._tb is not None:
            self._tb.add_scalar("epoch/seconds", dt, epoch)
        # per-epoch JSONL row with the metric-counter DELTAS since the
        # previous boundary (ROADMAP observability open item): the
        # record becomes self-contained — iterations, gossip pushes,
        # bytes on the wire per epoch — without scraping /metrics
        cur = _obs.counter_values()
        deltas = _obs.counter_deltas(cur, self._counter_base or {})
        self._counter_base = cur
        self.events.append(
            {
                "kind": "epoch",
                "epoch": epoch,
                "iter": count,
                "seconds": round(dt, 6),
                "counters": deltas,
            }
        )
        self.epoch_start = None
        return dt

    # ---- train metrics --------------------------------------------------
    def train_error(self, count: int, cost, error) -> None:
        # cost/error may be device scalars: accumulate lazily (tiny on-device
        # adds) and only materialize at the print boundary, so metric
        # bookkeeping never forces a per-step host↔device sync.
        # One recorder can be fed by models on different device meshes
        # (two committed scalars can't add): on an actual device-set
        # mismatch, materialize the old accumulator once and continue
        # lazily on the new mesh. Checked explicitly rather than with a
        # bare `except ValueError`, which would swallow unrelated errors
        # (e.g. a model returning a non-scalar).
        import jax

        acc, new = self._train_cost, cost
        if (
            isinstance(acc, jax.Array)
            and isinstance(new, jax.Array)
            and acc.devices() != new.devices()
        ):
            self._train_cost = float(self._train_cost)
            self._train_err = float(self._train_err)
        self._train_cost = self._train_cost + cost
        self._train_err = self._train_err + error
        self._train_n += 1

    def print_train_info(self, count: int, force: bool = False) -> None:
        # boundary span: at a print boundary this is the one sync of the
        # window (`float` of the accumulated cost), else next to nothing
        with _obs.span("print", boundary=True):
            self._print_train_info(count, force)

    def _print_train_info(self, count: int, force: bool) -> None:
        if (count % self.print_freq != 0 and not force) or self._train_n == 0:
            return
        n = self._train_n
        row = {
            "iter": count,
            "cost": float(self._train_cost) / n,  # the one sync per window
            "error": float(self._train_err) / n,
            **{p: self._acc.get(p, 0.0) for p in PHASES},
        }
        self.history.append(row)
        if self._tb is not None:
            self._tb.add_scalar("train/cost", row["cost"], count)
            self._tb.add_scalar("train/error", row["error"], count)
            for p in PHASES:
                self._tb.add_scalar(f"time/{p}", row[p], count)
        if self.verbose and self.rank == 0:
            t = {p: row[p] for p in PHASES}
            print(
                f"iter {count}: cost {row['cost']:.4f} err {row['error']:.4f} "
                f"| calc {t['calc']:.3f}s comm {t['comm']:.3f}s "
                f"wait {t['wait']:.3f}s load {t['load']:.3f}s",
                flush=True,
            )
        self._train_cost = self._train_err = 0.0
        self._train_n = 0
        for p in PHASES:
            self._acc[p] = 0.0

    # ---- one-off events -------------------------------------------------
    def log_event(self, kind: str, **fields) -> None:
        """Record a structured one-off row (e.g. the train-start
        comm-fraction probe — the reference printed calc/comm per window;
        SURVEY.md §3.7)."""
        row = {"kind": kind, **fields}
        self.events.append(row)
        # thin forwarder into the observability bus (instant trace
        # event + flight ring + events_total counter + subscribers):
        # every existing log_event call site gains tracing for free.
        # The recorder's own row above stays the JSONL contract — the
        # bus reads `fields`, never mutates it (regression-tested:
        # tests/test_observability.py::test_log_event_bus_roundtrip).
        _obs.publish_event(kind, fields)
        if self._tb is not None:
            self._tb.add_text(f"event/{kind}", json.dumps(fields))
        if self.verbose and self.rank == 0:
            body = " ".join(
                f"{k} {v:.4g}" if isinstance(v, float) else f"{k} {v}"
                for k, v in fields.items()
            )
            print(f"[{kind}] {body}", flush=True)

    # ---- val metrics ----------------------------------------------------
    def val_error(
        self, count: int, cost: float, error: float, error_top5: float = 0.0,
        extra: Optional[dict] = None,
    ) -> None:
        """``extra``: provenance fields merged into the JSONL row — the
        EASGD server stamps each center validation with its exchange
        count and wall clock so a frozen-center artifact is
        self-diagnosing (VERDICT r3 #1)."""
        self.val_history.append(
            {
                "iter": count,
                "cost": float(cost),
                "error": float(error),
                "error_top5": float(error_top5),
                **(extra or {}),
            }
        )
        if self._tb is not None:
            self._tb.add_scalar("val/cost", float(cost), count)
            self._tb.add_scalar("val/error", float(error), count)
            self._tb.add_scalar("val/error_top5", float(error_top5), count)

    def print_val_info(self, count: int) -> None:
        if not self.val_history:
            return
        row = self.val_history[-1]
        if self.verbose and self.rank == 0:
            print(
                f"val @ iter {count}: cost {row['cost']:.4f} "
                f"err {row['error']:.4f} err5 {row['error_top5']:.4f}",
                flush=True,
            )

    # ---- deep profiling -------------------------------------------------
    def profile(self, logdir: str):
        """Context manager: capture a ``jax.profiler`` trace (Perfetto/
        XProf) around a training window — the op-level complement to the
        calc/comm/wait wall-clock splits (reference used Theano's
        ``profile=True`` for this; SURVEY.md §6 Tracing row)."""
        import jax

        return jax.profiler.trace(logdir)

    # ---- persistence ----------------------------------------------------
    def save(self, path: Optional[str] = None) -> str:
        """Dump the record as JSONL (reference pickles a list; we keep the
        same offline-plotting contract with a friendlier format)."""
        if self._train_n:
            # flush the partial window so short runs / run tails aren't lost
            last_iter = self.history[-1]["iter"] + self._train_n if self.history else self._train_n
            self.print_train_info(last_iter, force=True)
        if path is None:
            d = self.save_dir or "."
            os.makedirs(d, exist_ok=True)
            path = os.path.join(d, f"record_rank{self.rank}.jsonl")
        with open(path, "w") as f:
            for row in self.events:
                f.write(json.dumps(row) + "\n")
            for row in self.history:
                f.write(json.dumps({"kind": "train", **row}) + "\n")
            for row in self.val_history:
                f.write(json.dumps({"kind": "val", **row}) + "\n")
        if self._tb is not None:
            self._tb.flush()
        return path

    def close(self) -> None:
        """Release the TensorBoard writer (no-op without one)."""
        if self._tb is not None:
            self._tb.close()
            self._tb = None

    @staticmethod
    def load(path: str) -> List[dict]:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
