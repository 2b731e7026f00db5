"""Driver: a language model served by the program's paged server.

``TransformerLM`` at the configuration's sizes -> ``PagedServingEngine``
(the geometry in the configuration's ``engine``; ``prefill_rows``, the
bucket ladder and ``prefix_impl`` at the program's defaults) ->
``ContinuousBatchingScheduler``.  The model gets the weights the
configuration's reference makes from the seed.  The window drives
``scheduler.submit`` / ``scheduler.step()``; the benchmark's own recorder
sits on the scheduler's ``metrics`` hooks with the benchmark's clock.

Closed loop (``traffic["loop"] == "closed"``): ``clients`` requests are
in flight at all times; a client sends its next request (the next of the
mix's list) when its last one finished.  Set-up warms the chunk buckets
the mix's prompts can reach and the decode program, then runs the loop
until ``warm_finished`` requests have finished, so that the window opens
on a system in its steady state.

What the driver hands the harness (``BENCHMARK.json`` says which of them
are end-to-end metrics): ``serve_tokens_per_s`` (tokens every tick of
the window produced over the window), and over the requests that the
clients sent inside the window the median and the 95th percentile of
the time to the first token (first token minus the moment the client
sent it, queueing included; over every such request whose first token
came inside the window, finished or not) and of the time per output
token ((finish - first token) / (output tokens - 1); over those that
also finished inside it with more than one token):
``serve_ttft_p50_ms``, ``serve_ttft_p95_ms``, ``serve_tpot_p50_ms``,
``serve_tpot_p95_ms``, with the two sample counts ``n_ttft`` and
``n_tpot`` beside them.  What set-up sent (its opening tick holds
``clients`` arrivals at once, which a closed loop never repeats) counts
in the tokens, in the check of outputs and in no latency.

The check of outputs: once the window has closed and the program's state
is freed, a sample of the finished requests drawn from the seed, the
longest among them, goes through the plain reference once each (prompt
with its served tokens); compared is how far a served token's reference
logit lies below the reference's best.
"""

from __future__ import annotations

import bisect
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import traffic as traffic_gen


class Recorder:
    """The scheduler's ``metrics`` hooks, duck-typed; times are the
    benchmark's clock as the scheduler passes them."""

    def __init__(self):
        self.sent, self.first, self.done, self.n_out = {}, {}, {}, {}
        self.engine_stats = None

    def admitted(self, rid, prompt_len, t, generation=0):
        self.sent[rid] = t

    def first_token(self, rid, t):
        self.first[rid] = t

    def finished(self, rid, n_tokens, t):
        self.done[rid], self.n_out[rid] = t, n_tokens

    def set_engine_stats(self, stats):
        self.engine_stats = stats


class Driver:
    def __init__(self, ctx):
        self.ctx, self.cfg, self.t = ctx, ctx.config, ctx.traffic
        if ctx.chips != 1:
            raise RuntimeError("serve_paged drives one replica on one chip")
        self._facts = {}

    # ------------------------------------------------------------------
    def setup(self) -> None:
        marks = {"start": time.perf_counter() - self.ctx.t0}

        def mark(name):
            marks[name] = time.perf_counter() - self.ctx.t0

        from theanompi_tpu.models.transformer import TransformerLM
        from theanompi_tpu.serving import (
            ContinuousBatchingScheduler, PagedServingEngine, Request,
        )

        self.Request = Request
        cfg, seed = self.cfg, self.ctx.seed
        pc = dict(cfg["program_config"], seed=seed % (2**31 - 1))
        mesh = TransformerLM.build_mesh(devices=list(self.ctx.devices), config=pc)
        model = TransformerLM(config=pc, mesh=mesh)
        mark("model_init")
        # the program's own random weights and its optimizer state make
        # way for the benchmark's weights (same tree, same placement)
        shapes = [a.shape for a in jax.tree.leaves(model.params)]
        treedef = jax.tree.structure(model.params)
        model.params = model.opt_state = None
        weights = self.ctx.reference.make_weights(cfg, seed)
        if ([a.shape for a in jax.tree.leaves(weights)] != shapes
                or jax.tree.structure(weights) != treedef):
            raise RuntimeError("the reference's weights do not fit the "
                               "program's tree")
        replicated = NamedSharding(mesh, P())
        self.weights = jax.tree.map(
            lambda a: jax.device_put(a, replicated), weights)
        del weights
        model.params = self.weights
        jax.block_until_ready(self.weights)
        mark("weights")

        self.model = model
        self.engine = PagedServingEngine(model, **cfg["engine"])
        self.rec = Recorder()
        self.sched = ContinuousBatchingScheduler(
            self.engine, metrics=self.rec, clock=time.perf_counter)
        self.requests = traffic_gen.generate(
            self.t, seed, int(pc["vocab_size"]))
        self.next = 0
        self.by_id = {}

        # the chunk buckets this mix's prompts can reach and the decode
        # program, one lone request each, so that no shape is first met
        # inside the window (one that is ends the run: run.py counts it)
        rng = np.random.default_rng(seed & 0xFFFFFFFF)
        longest = max(len(r["prompt"]) for r in self.requests)
        buckets = self.engine.chunk_buckets
        reach = next((i for i, b in enumerate(buckets) if b >= longest),
                     len(buckets) - 1)
        for i, b in enumerate(buckets[:reach + 1]):
            self.sched.submit(Request(
                id=f"warm{i}",
                prompt=rng.integers(0, int(pc["vocab_size"]), int(b)).tolist(),
                max_new_tokens=2))
            while not self.sched.idle:
                self.sched.step()
        mark("buckets_warmed")
        # steady state: the closed loop until enough requests finished
        target = int(self.t["warm_finished"])
        n0 = len(self.rec.done)
        while len(self.rec.done) - n0 < target:
            self._feed()
            self.sched.step()
        mark("steady")
        self.ctx.say(setup_marks_s=marks,
                     chunk_buckets=list(self.engine.chunk_buckets),
                     paged_attn=self.engine.paged_attn_effective,
                     prefill_rows=self.engine.prefill_rows)

    # ------------------------------------------------------------------
    def _feed(self) -> int:
        """Closed loop: keep ``clients`` requests in flight.  The mix's
        list is cycled (ids get the cycle's number), so a system of any
        speed is offered the same sizes over and over."""
        sched, sent = self.sched, 0
        in_flight = len(sched.queue) + sched.n_active
        while in_flight < int(self.t["clients"]):
            cycle, i = divmod(self.next, len(self.requests))
            r = self.requests[i]
            self.next += 1
            rid = r["id"] if cycle == 0 else f"{r['id']}.{cycle}"
            req = self.Request(id=rid, prompt=list(r["prompt"]),
                               max_new_tokens=r["max_new_tokens"])
            self.by_id[rid] = req
            sched.submit(req)
            in_flight += 1
            sent += 1
        return sent

    def window(self, seconds: float, tracer) -> None:
        sched, stats = self.sched, self.sched.stats
        ticks = []  # (start, seconds, tokens, was a prefill tick, resident)
        calls = []  # prefill calls each tick made (for the tail's print)
        trace_at = float(self.t["trace_after_s"])
        trace_for = float(self.t["trace_s"])
        trace_on = trace_off = None
        stall = 0.0  # in the profiler's start and stop: no tick's time
        sent = 0
        done_before = set(self.rec.done)
        prefill_before = stats["prefill_tokens"]
        next_before = self.next
        t0 = time.perf_counter()
        t_end = t0 + seconds
        now = t0
        while now < t_end:
            if tracer is not None and trace_on is None and now - t0 >= trace_at:
                trace_on = time.perf_counter()
                tracer.start()
                began = time.perf_counter()
                stall += began - trace_on
            sent += self._feed()
            chunks = stats["prefill_chunks"]
            ts = time.perf_counter()
            produced = sched.step()
            now = time.perf_counter()
            # what the lanes that decode hold after this tick, in tokens
            resident = sum(
                len(s.request.prompt) + len(s.request.output)
                for s in sched.slots if s.request is not None and s.decoding)
            ticks.append((ts, now - ts, produced,
                          stats["prefill_chunks"] > chunks, resident))
            calls.append(stats["prefill_chunks"] - chunks)
            if len(ticks) % 32 == 0:
                self.ctx.memory.sample()
            if trace_on is not None and trace_off is None and now - began >= trace_for:
                tracer.stop()
                trace_off = time.perf_counter()
                stall += trace_off - now
                now = trace_off
        if trace_on is not None and trace_off is None:
            now = time.perf_counter()
            tracer.stop()
            trace_off = time.perf_counter()
            stall += trace_off - now
        t1 = time.perf_counter()
        self.window_s = t1 - t0
        self.ticks, self.tick_calls = ticks, calls
        self.sent_in_window = sent
        self.finished_ids = [r for r in self.rec.done if r not in done_before]
        # a pause of the host shows here and nowhere else
        self.ctx.say(ticks=len(ticks), profiler_s=stall,
                     slowest_ticks_s=sorted(t[1] for t in ticks)[-3:])
        self._facts.update(
            # what the per-layer readers divide by: the window without the
            # pauses of the profiler's own start and stop (none untraced)
            ticks=ticks, window_s=self.window_s - stall, t0=t0,
            traced=(trace_on, trace_off), profiler_s=stall,
            finished=len(self.finished_ids),
            prefill_tokens=stats["prefill_tokens"] - prefill_before,
            # positions the prompts sent in the window attend to between
            # them: a prompt of L tokens, L (L + 1) / 2
            prefill_attended=sum(
                len(r["prompt"]) * (len(r["prompt"]) + 1) // 2
                for r in (self.requests[i % len(self.requests)]
                          for i in range(next_before, self.next))),
            decode_tokens=sum(t[2] for t in ticks),
            decode_attended=sum(t[4] for t in ticks),
        )

    # ------------------------------------------------------------------
    def _latency_ids(self):
        """The requests sent inside the window that got their first token
        there; in a traced run those that the profiler's start and stop
        (pauses of the host, tenths of a second each) did not touch (one
        still running when the window closes ends past them)."""
        rec = self.rec
        t0 = self._facts["t0"]
        on, off = self._facts["traced"]
        return [r for r in rec.first
                if rec.sent[r] >= t0 and (
                    on is None or rec.sent[r] > off
                    or rec.done.get(r, float("inf")) < on)]

    def _latencies(self):
        """Over the requests sent inside the window: the time to the
        first token of every one that got it there, the time per output
        token of every one that also finished there with more than one
        token."""
        rec, ids = self.rec, self._latency_ids()
        ttft = [1e3 * (rec.first[r] - rec.sent[r]) for r in ids]
        tpot = [1e3 * (rec.done[r] - rec.first[r]) / (rec.n_out[r] - 1)
                for r in ids if r in rec.done and rec.n_out[r] > 1]
        return ttft, tpot

    def _slowest_first_tokens(self, n: int = 12):
        """What sets the tail of the time to the first token: the ``n``
        longest as [ms, prompt tokens, ms of the tick that emitted it,
        prefill calls of that tick] (a tick with one call takes two
        programs' time, one with a second call three; longer is a pause
        of the host)."""
        rec = self.rec
        starts = [t[0] for t in self.ticks]
        rows = []
        for r in sorted(self._latency_ids(), reverse=True,
                        key=lambda r: rec.first[r] - rec.sent[r])[:n]:
            i = bisect.bisect_right(starts, rec.first[r]) - 1
            rows.append([1e3 * (rec.first[r] - rec.sent[r]),
                         len(self.by_id[r].prompt),
                         1e3 * self.ticks[i][1], self.tick_calls[i]])
        return rows

    def end_to_end_values(self) -> dict:
        ttft, tpot = self._latencies()
        tokens = sum(t[2] for t in self.ticks)
        self._facts.update(ttft_ms=ttft, tpot_ms=tpot, tokens=tokens)
        self.ctx.say(slowest_first_tokens=self._slowest_first_tokens())
        out = {"serve_tokens_per_s": tokens / self.window_s,
               "n_ttft": len(ttft), "n_tpot": len(tpot)}
        for name, values in (("ttft", ttft), ("tpot", tpot)):
            if len(values) >= 2:
                out[f"serve_{name}_p50_ms"] = float(np.percentile(values, 50))
                out[f"serve_{name}_p95_ms"] = float(np.percentile(values, 95))
        return out

    def facts(self) -> dict:
        return self._facts

    def attempted_failed(self):
        """Requests the window sent; none is refused or dropped (those
        still in flight when the window closes are not failures)."""
        return self.sent_in_window, 0

    def release(self) -> None:
        """Free the pool and the program's objects; the weights stay for
        the reference (they are the benchmark's own arrays)."""
        self.served = {r: (list(self.by_id[r].prompt), list(self.by_id[r].output))
                       for r in self.finished_ids}
        self.sched.state = None
        self.model.params = None
        self.sched = self.engine = self.model = None
        self.by_id = None

    def sample(self):
        """The requests the check compares: drawn from the seed, the
        longest finished one among them."""
        ids = sorted(self.served, key=lambda r: float(r[1:]))
        if not ids:
            return []
        rng = np.random.default_rng([self.ctx.seed & 0xFFFFFFFF, 11])
        k = min(int(self.t["check_requests"]), len(ids))
        longest = max(ids, key=lambda r: sum(map(len, self.served[r])))
        rest = [r for r in ids if r != longest]
        picked = list(rng.choice(rest, size=k - 1, replace=False)) if k > 1 else []
        return [longest] + [str(r) for r in picked]

    def check(self, precision: str = "float32") -> dict:
        ref = self.ctx.reference
        gaps, n_requests = [], 0
        for rid in self.sample():
            prompt, out = self.served[rid]
            gaps += ref.served_gaps(self.cfg, self.weights, prompt, out,
                                    precision=precision)
            n_requests += 1
        if not gaps:
            return {"token_gap_max": float("inf"), "token_gap_mean": float("inf")}
        return {
            "token_gap_max": max(gaps),
            "token_gap_mean": sum(gaps) / len(gaps),
            "_where": {"requests": n_requests, "tokens": len(gaps),
                       "nonzero": sum(1 for g in gaps if g > 0)},
        }


def calibrate(context, seeds, control_seeds, emit) -> None:
    """Readings for the limits: per seed a short window at the cell's own
    load, the program's numbers, and for the control seeds the control's
    (the tokens the lower precision puts first at each position of the
    same prompts and served tokens)."""
    for seed in seeds:
        ctx = context(seed)
        d = Driver(ctx)
        d.setup()
        d.window(float(ctx.traffic.get("calibrate_s", 20.0)), None)
        e2e = d.end_to_end_values()
        d.release()
        numbers = d.check()
        emit(kind="program", seed=seed, finished=len(d.finished_ids),
             **{k: v for k, v in numbers.items() if not k.startswith("_")},
             where=numbers.get("_where"), end_to_end=e2e)
        if seed in control_seeds:
            numbers = d.check(precision="int8")
            emit(kind="control_int8", seed=seed,
                 **{k: v for k, v in numbers.items() if not k.startswith("_")},
                 where=numbers.get("_where"))
        d.weights = None
        del d
