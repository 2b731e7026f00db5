"""Continuous batching over a fixed set of decode slots.

The classic serving problem: requests arrive at arbitrary times with
arbitrary prompt/output lengths, but the efficient decode program is one
fixed-shape step over ``n_slots`` sequences.  Static batching would wait
for a full batch and hold every finished sequence hostage until the
longest one ends; continuous batching instead treats each slot as an
independent lane — a request joins the moment a slot is free (its
prefill runs between decode ticks) and leaves the moment it finishes,
returning the slot to the pool.  The decode step never changes shape,
so admission/retirement cause ZERO recompilation.

The engine is ``paging.PagedServingEngine``: slots own *block tables*
into a shared pool.  Admission allocates exactly the blocks a request
can ever need (prompt + ``max_new_tokens``), reuses cached prefix blocks
(refcounted, prefilled once per distinct prefix), and defers — clean
backpressure, never a crash — when the pool is exhausted (after
evicting idle cached prefixes).  Prefill is **chunked and batched**:
every tick, every admitted-but-unprefilled lane advances by up to
``prefill_chunk`` prompt tokens, ``prefill_rows`` lanes to a padded
dispatch of the engine's narrow prefill program, interleaved with
decode ticks so a giant prompt cannot hide the TTFT of requests queued
behind it.  Finishing releases the slot's blocks back to the pool —
join-on-finish recycling that also reclaims memory.

Determinism contract (tested): every per-slot computation in the engine
is independent across the slot axis, so a request's output under any
interleaving equals its output under serial execution — continuous
batching changes latency, never results.  Sampling requests keep the
same property: each draw is keyed by the request's seed folded with its
token index (``serving.sampling.request_key``), never by batch
position or tick number.

Sampling: ``temperature=0`` (the default) is the greedy argmax path,
bit-identical to the parity-tested decode; ``temperature>0`` samples
from the temperature-scaled, optionally top-k-filtered logits through
one shared jitted sampler — sampling-config changes cause ZERO
recompiles (see ``serving/sampling.py``).  Token picks are **batched
device-side**: one fused argmax/sample over every active slot per
tick, one host transfer — never a per-slot round trip.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from theanompi_tpu import observability as obs
from theanompi_tpu.serving import metrics as smetrics

_REG = obs.get_registry()
_TOKENS = _REG.counter(
    "serve_tokens_generated_total", "tokens generated across requests"
)
_ADMITTED = _REG.counter("serve_requests_admitted_total", "requests admitted")
_FINISHED = _REG.counter("serve_requests_finished_total", "requests finished")
_SLOTS = _REG.gauge("serve_slots_active", "decode slots currently occupied")
_QUEUE = _REG.gauge("serve_queue_depth", "requests waiting for a slot")


@dataclass
class Request:
    """One generation request.

    ``temperature=0`` = greedy (exact argmax — the default and the
    parity-tested path); ``temperature>0`` samples, optionally through
    a ``top_k`` filter, deterministically per ``seed`` (unseeded
    requests derive a stable seed from their id).
    """

    id: str
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    temperature: float = 0.0
    top_k: int = 0
    seed: Optional[int] = None
    # token-index origin for sampling keys: a fleet re-admission
    # replays prompt + accepted tokens through a FRESH request, and its
    # first new pick must draw with the key the original stream would
    # have used at that index (request_key(seed, id, token_index0 +
    # len(output))) — greedy streams don't care, sampled streams stay
    # identical across a replica failover
    token_index0: int = 0
    # filled by the scheduler
    output: List[int] = field(default_factory=list)

    def __post_init__(self):
        if len(self.prompt) < 1:
            raise ValueError(f"request {self.id!r}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.id!r}: max_new_tokens must be >= 1"
            )
        if self.temperature < 0:
            raise ValueError(
                f"request {self.id!r}: temperature must be >= 0 "
                f"(0 = greedy), got {self.temperature}"
            )
        if self.top_k < 0:
            raise ValueError(
                f"request {self.id!r}: top_k must be >= 0 "
                f"(0 = disabled), got {self.top_k}"
            )


class SchedulerDraining(RuntimeError):
    """Raised by ``submit`` once ``begin_drain`` ran — the counted
    refusal a fleet router turns into route-elsewhere."""


class _Slot:
    __slots__ = ("request", "produced", "blocks", "n_fed", "decoding")

    def __init__(self):
        self.request: Optional[Request] = None
        self.produced = 0   # tokens generated so far for the request
        self.blocks: List[int] = []  # block ids this slot holds
        self.n_fed = 0      # prompt tokens resident (hits + fed)
        self.decoding = False  # prompt fully prefilled


class ContinuousBatchingScheduler:
    """Admission queue + slot table driving one serving engine.

    ``step()`` is one serving tick: admissions, the batched
    chunked-prefill dispatches, then one batched decode step for every
    active slot.  ``run()`` loops until drained.  Completed requests
    land in ``finished`` (id → token list) and are reported to
    ``metrics`` when one is attached.

    ``pool`` overrides the block allocator — the bench caps it below
    the device pool to pin equal-cache-memory comparisons.
    """

    def __init__(self, engine, metrics=None, params=None,
                 clock=time.perf_counter, pool=None,
                 spec_k: int = 0, draft_engine=None, draft_params=None,
                 prefix_impl: Optional[str] = None):
        self.engine = engine
        self.metrics = metrics
        # the model generation these params came from (publish/ live
        # installs set it alongside the whole-tree params rebind); it
        # labels admissions and the token counter so A/B cohorts stay
        # separable in /metrics
        self.model_generation = 0
        self.clock = clock
        self.slots = [_Slot() for _ in range(engine.n_slots)]
        self.queue: List[Request] = []
        self.finished: Dict[str, List[int]] = {}
        # drain-on-leave: a draining scheduler finishes its in-flight
        # slots and queued requests but REFUSES new submissions with
        # counted backpressure (the fleet router routes them elsewhere)
        self.draining = False
        # request-buffer ownership: a standalone scheduler closes each
        # rid's retention buffer when the request finishes; a fleet
        # replica's scheduler must NOT — the router owns the stream's
        # end-to-end story (a replica-side finish is not the end of it:
        # the stream may yet be re-admitted elsewhere), so fleet.py
        # clears this and closes buffers router-side
        self.owns_request_buffers = True
        self._tokens = np.zeros((engine.n_slots,), np.int32)
        self._sampler = None  # built lazily on the first sampling request
        # per-run reuse/capacity stats (host-side, exact — the registry
        # counters are process-global and shared across schedulers)
        self.stats = {
            "weight_bytes": 0,  # of the tree the programs read
            "peak_concurrent": 0,
            "prefill_tokens": 0,
            "prefill_chunks": 0,
            "prefix_hits": 0,
            "prefix_misses": 0,
            "prefix_hit_tokens": 0,
            "backpressure_events": 0,
            "drain_refusals": 0,
        }
        self.install_params(
            params if params is not None else engine.model.params)
        # request forensics (observability request tracking, all gated
        # on obs.request_tracking_active()): enqueue timestamps for
        # queue-wait spans, when the head of the queue started stalling
        # on pool backpressure, and rids finished this tick — their
        # buffers close at the END of step() so the tick's phase spans
        # land inside them first
        self._n_ticks = 0  # the `tick` boundary span's number
        self._req_enq: Dict[str, float] = {}
        self._bp_since: Optional[float] = None
        self._req_done: List[tuple] = []
        # mid-tick admission timestamps (cleared each step): the
        # whole-tick phase span for a request admitted partway through
        # a tick starts at its admission, not the tick edge, so its
        # queue wait is never double-billed as prefill
        self._req_tick_adm: Dict[str, float] = {}
        # (span, tokens routed, device array) of program calls whose
        # experts' counters nobody has fetched yet (a latent model's;
        # `_note_counters`)
        self._counters: List[tuple] = []
        if pool is not None and pool.block_size != engine.block_size:
            raise ValueError("pool/engine block_size mismatch")
        self.pool = pool if pool is not None else engine.make_pool()
        impl = prefix_impl if prefix_impl is not None else engine.prefix_impl
        if impl not in ("chain", "radix"):
            raise ValueError(
                f"prefix_impl must be 'chain' or 'radix', got {impl!r}"
            )
        if engine.prefix_cache_enabled:
            if impl == "radix":
                from theanompi_tpu.serving.radix import RadixPrefixCache

                self.prefix = RadixPrefixCache(self.pool)
            else:
                from theanompi_tpu.serving.paging import PrefixCache

                self.prefix = PrefixCache(self.pool)
        else:
            self.prefix = None
        lane_state = engine.programs.lane_state
        if lane_state:
            # a prefix cannot be rebuilt from blocks alone
            if int(spec_k):
                raise ValueError(
                    "spec_k>0 needs a model whose past is rows alone: a "
                    "rejected draft cannot be rolled back out of "
                    f"{lane_state}")
            self.stats["prefix_reuse"] = f"off: {lane_state}"
        if engine.programs.latent and engine.programs.recurrent:
            # per-lane state beside the pool (serving/latent.py): its
            # size and the first chunks that cleared a lane
            self.stats["recurrent_state_bytes"] = (
                engine.programs.recurrent_state_bytes())
            self.stats["recurrent_lanes_reset"] = 0
        if engine.programs.latent and engine.programs.n_window:
            # the window layers' rings, beside the weights' bytes
            self.stats["window_ring_bytes"] = engine.programs.ring_bytes()
        self.state = engine.init_state()
        if engine.programs.latent:
            # the latent pool's size and fill, in rows (= tokens)
            self.stats["latent_rows_capacity"] = (
                (self.pool.n_blocks - 1) * engine.block_size)
            self.stats["latent_rows_resident"] = 0
        self._tables = np.zeros(
            (engine.n_slots, engine.blocks_per_seq), np.int32
        )
        self._lengths = np.zeros((engine.n_slots,), np.int32)
        self._spec = None
        if int(spec_k):
            if draft_engine is None:
                raise ValueError(
                    "spec_k>0 needs a draft_engine (see "
                    "models.transformer.make_draft)"
                )
            from theanompi_tpu.serving.spec import SpecDecoder

            self._spec = SpecDecoder(
                engine, draft_engine, int(spec_k),
                draft_params=draft_params,
            )
        elif draft_engine is not None:
            raise ValueError("draft_engine given but spec_k=0 — pass "
                             "spec_k>=1 to enable speculation")

    # ------------------------------------------------------------------
    def install_params(self, params) -> None:
        """Make ``params`` the tree every later program call reads: the
        engine's serving tree of it (``paging.serving_params``: a
        ``dense`` model's matrices and tables in the compute dtype, cast
        here once and not by every call), bound whole.  The tree is this
        scheduler's own and dies with it; the caller's arrays and the
        model are left as they were.  Between ticks only (a replica
        calls it from its idle gap)."""
        self.params = self.engine.serving_params(params)
        self.stats["weight_bytes"] = sum(
            int(a.nbytes) for a in jax.tree.leaves(self.params))

    def begin_drain(self) -> None:
        """Stop admitting: queued + in-flight requests run to
        completion (their blocks release through the ordinary finish
        path), every later ``submit`` raises ``SchedulerDraining`` and
        counts.  The fleet's drain-on-leave protocol: a replica drains,
        reports idle, then ``leave()``s its roster cleanly."""
        self.draining = True

    def end_drain(self) -> None:
        """Reopen admissions after a drain ran its course — the forced
        publish-install path composes ``begin_drain`` → idle →
        ``install_params`` apply → ``end_drain`` so a saturated replica
        still takes rollouts (fleet.ServeReplica)."""
        self.draining = False

    @property
    def idle(self) -> bool:
        """Nothing queued, nothing in flight — a draining scheduler
        reports its drain complete through this."""
        return not self.queue and self.n_active == 0

    def submit(self, request: Request) -> None:
        if self.draining:
            self.stats["drain_refusals"] += 1
            smetrics.DRAIN_REFUSALS.inc()
            raise SchedulerDraining(
                f"request {request.id!r} refused: scheduler is draining"
            )
        total = len(request.prompt) + request.max_new_tokens
        if total > self.engine.max_len:
            raise ValueError(
                f"request {request.id!r} needs {total} cache rows > "
                f"max_len={self.engine.max_len}"
            )
        need = self.engine.max_seq_blocks(total)
        if need > self.pool.n_blocks - 1:
            raise ValueError(
                f"request {request.id!r} needs {need} KV blocks > "
                f"pool capacity {self.pool.n_blocks - 1} — it could "
                "never be admitted"
            )
        if self.metrics is not None:
            self.metrics.admitted(request.id, len(request.prompt),
                                  t=self.clock(),
                                  generation=self.model_generation)
        self.queue.append(request)
        if obs.request_tracking_active():
            # idempotent: under a fleet the router already opened this
            # rid at its own submit (the true request start); in
            # router-less runs this IS the open
            obs.request_begin(request.id, prompt_len=len(request.prompt))
            self._req_enq[request.id] = self.clock()
        _ADMITTED.inc()
        _QUEUE.set(len(self.queue))

    def spec_summary(self) -> Optional[Dict]:
        """Speculation accounting for this run (None when spec is off):
        rounds, dispatch counts, proposed/accepted totals, accept_rate,
        tokens_per_round — the ``detail.spec`` feed for bench_serve."""
        return self._spec.summary() if self._spec is not None else None

    @property
    def n_active(self) -> int:
        """Occupied slots (prefilling or decoding)."""
        return sum(1 for s in self.slots if s.request is not None)

    def _note_concurrency(self) -> None:
        self.stats["peak_concurrent"] = max(
            self.stats["peak_concurrent"], self.n_active
        )

    def _finish(self, i: int) -> None:
        slot, req = self.slots[i], self.slots[i].request
        self.finished[req.id] = req.output
        if self.metrics is not None:
            self.metrics.finished(req.id, len(req.output), t=self.clock())
        # join-on-finish recycling also reclaims memory: every block
        # reference this slot holds goes back to the pool
        # (prefix-cached blocks just drop one ref and live on)
        self.pool.release_all(slot.blocks)
        slot.blocks = []
        slot.n_fed = 0
        slot.decoding = False
        self._tables[i, :] = 0
        self._lengths[i] = 0
        if self._spec is not None:
            self._spec.release_slot(i)
        slot.request = None
        slot.produced = 0
        if obs.request_tracking_active():
            # close the request buffer at the END of step(), after the
            # tick's phase spans have landed in it
            self._req_done.append((req.id, len(req.output)))
        _FINISHED.inc()
        _SLOTS.set(self.n_active)

    # ------------------------------------------------------------------
    # token picking (batched, device-side)
    # ------------------------------------------------------------------
    def _pick_batch(self, reqs: List[Optional[Request]], logits):
        """Next token for every row of ``logits`` (N, V) in ONE device
        dispatch + ONE host transfer.  ``reqs[i] is None`` marks a row
        whose pick is discarded (inactive lane) — it rides the greedy
        path with a dummy key.  Greedy rows are exact argmax; sampling
        rows draw with the SAME per-request key as the single-row
        sampler, so batching never perturbs a stream."""
        return self._pick_tokens(
            [(r, len(r.output)) if r is not None else None for r in reqs],
            logits,
        )

    def _pick_tokens(self, picks, logits):
        """The general batched pick: row i of ``logits`` (N, V) draws
        for ``picks[i] = (request, token_index)`` (None = discarded
        row).  The explicit token index is what the speculative-verify
        path needs — one dispatch picks a request's NEXT ``k+1`` tokens
        at indices ``len(output) + [0, k]``, each with the exact key the
        non-speculative path would have used at that index."""
        # boundary span: the draw's enqueue, the wait for the device
        # (its child) and the transfer to the host
        with obs.span("pick", boundary=True, rows=len(picks)):
            return self._fetch(self._draw_tokens(picks, logits), drains=True)

    @staticmethod
    def _fetch(drawn, drains: bool):
        """``drawn`` on the host, its wait for the device under a
        ``device_wait`` boundary span.  ``drains``: no program dispatched
        after ``drawn``'s is still to run, so from the wait's end the
        device has nothing queued until the host's next dispatch.  The
        copy is asked for before the wait, so that it follows the program
        as ``np.asarray`` alone would have it: asked for after the wait,
        it cost a pick 0.24 ms on a v5e (PERF.md, section 6)."""
        drawn.copy_to_host_async()
        with obs.span("device_wait", boundary=True, drains=drains):
            drawn.block_until_ready()
        return np.asarray(drawn)

    def _draw_tokens(self, picks, logits):
        """The draw of ``_pick_tokens``, enqueued behind the program
        that makes ``logits`` and not waited for: a device array."""
        import jax.numpy as jnp

        if not any(p is not None and p[0].temperature > 0.0 for p in picks):
            return jnp.argmax(logits, axis=-1)
        if self._sampler is None:
            from theanompi_tpu.serving.sampling import Sampler

            self._sampler = Sampler()
        from theanompi_tpu.serving.sampling import request_key

        n = len(picks)
        temps = np.zeros((n,), np.float32)
        topks = np.zeros((n,), np.int32)
        keys = np.zeros((n, 2), np.uint32)
        for i, p in enumerate(picks):
            if p is None or p[0].temperature == 0.0:
                continue
            r, idx = p
            temps[i] = r.temperature
            topks[i] = r.top_k
            keys[i] = np.asarray(
                request_key(r.seed, r.id, r.token_index0 + idx)
            )
        return self._sampler.draw_batch(logits, keys, temps, topks)

    def _note_counters(self, span, tokens_routed: int) -> None:
        """Remember that ``span``'s program call returned experts'
        counters (``engine.last_counters``; None for a model without
        experts: nothing to do)."""
        counters = getattr(self.engine, "last_counters", None)
        if counters is not None:
            self._counters.append((span, int(tokens_routed), counters))

    def _fetch_counters(self) -> None:
        """Complete the spans of the calls noted so far with
        ``experts_hit``, ``expert_load_max``, ``pairs_routed`` and
        ``tokens_routed``.
        Called behind a pick, so every program noted has run and the
        fetch waits for nothing; a boundary span's arguments are the
        recorded event's own, so a span that has closed still takes
        them."""
        for span, tokens_routed, counters in self._counters:
            hit, load, pairs = np.asarray(counters).tolist()
            span.set(experts_hit=hit, expert_load_max=load,
                     pairs_routed=pairs, tokens_routed=tokens_routed)
        self._counters.clear()

    def _emit(self, i: int, token: int) -> bool:
        """Append one generated token to slot i's request; True when the
        request just finished (eos or budget)."""
        slot = self.slots[i]
        req = slot.request
        req.output.append(token)
        slot.produced += 1
        if slot.produced == 1:
            if self.metrics is not None:
                self.metrics.first_token(req.id, t=self.clock())
            obs.request_mark(req.id, "first_token")
        return (
            slot.produced >= req.max_new_tokens
            or (req.eos_id is not None and token == req.eos_id)
        )

    # ------------------------------------------------------------------
    # request-forensics phase spans (no-ops unless request tracking is
    # on — obs.request_tracking_active(); spans carry rid args, so the
    # tracer routes each into its request's retention buffer)
    # ------------------------------------------------------------------
    def _note_admitted(self, rid: str) -> None:
        """Retroactive queue-wait (and backpressure-stall) spans for a
        just-admitted request."""
        if not obs.request_tracking_active():
            self._req_enq.pop(rid, None)
            return
        now = self.clock()
        self._req_tick_adm[rid] = now
        t_enq = self._req_enq.pop(rid, None)
        if t_enq is not None:
            obs.add_span("req_queue", t_enq, now, {"rid": rid})
        if self._bp_since is not None:
            # the head of the queue sat on an exhausted pool from
            # _bp_since until this admission unstuck it
            obs.add_span(
                "req_backpressure", self._bp_since, now, {"rid": rid}
            )
            self._bp_since = None

    def _close_finished_requests(self) -> None:
        """End the request buffers of every rid finished this tick —
        runs LAST in step() so every phase span has already landed."""
        if self.owns_request_buffers:
            for rid, n_tokens in self._req_done:
                obs.request_end(rid, n_tokens=n_tokens)
        self._req_done.clear()

    # ------------------------------------------------------------------
    # the tick's three parts
    # ------------------------------------------------------------------
    def _admit_paged(self) -> None:
        """Free slots take queued requests FIFO; each admission reuses
        every cached prefix block it can, then allocates exactly the
        fresh blocks the request can ever need.  An exhausted pool
        (after evicting idle cached prefixes) defers admission to a
        later tick — backpressure, never a crash — and preserves FIFO
        (nothing behind the stuck head jumps the queue)."""
        if not self.queue:
            return
        with obs.span("admit", boundary=True) as span:
            queued = len(self.queue)
            refused = self.stats["backpressure_events"]
            self._admit_free_slots()
            span.set(
                admitted=queued - len(self.queue),
                refused=self.stats["backpressure_events"] - refused,
            )

    def _admit_free_slots(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot.request is not None or not self.queue:
                continue
            req = self.queue[0]
            need = self.engine.max_seq_blocks(
                len(req.prompt) + req.max_new_tokens
            )
            hits: List[int] = []
            hit_tokens = 0
            if self.prefix is not None:
                hits, hit_tokens = self.prefix.match(req.prompt, rid=req.id)
            fresh = self.pool.alloc(need - len(hits), rid=req.id)
            if fresh is None and self.prefix is not None:
                # the shortfall rides along so a need-aware cache (the
                # radix tree) can evict ONLY the coldest tails; the
                # chain cache ignores it and sweeps everything idle
                shortfall = (need - len(hits)) - self.pool.n_free
                self.prefix.evict_unused(max(1, shortfall))
                fresh = self.pool.alloc(need - len(hits), rid=req.id)
            if fresh is None:
                # roll back the prefix refs; the request stays queued
                self.pool.release_all(hits)
                self.stats["backpressure_events"] += 1
                smetrics.ADMISSION_BACKPRESSURE.inc()
                if (self._bp_since is None
                        and obs.request_tracking_active()):
                    self._bp_since = self.clock()
                break
            self.queue.pop(0)
            self._note_admitted(req.id)
            slot.request = req
            slot.blocks = hits + fresh
            slot.n_fed = hit_tokens
            slot.decoding = False
            self._tables[i, :] = 0
            self._tables[i, :len(slot.blocks)] = slot.blocks
            self._lengths[i] = hit_tokens
            self.stats["prefix_hits"] += 1 if hits else 0
            self.stats["prefix_misses"] += 0 if hits else 1
            self.stats["prefix_hit_tokens"] += hit_tokens
            self._note_concurrency()
            _SLOTS.set(self.n_active)
            _QUEUE.set(len(self.queue))

    def _prefill_tick_paged(self) -> int:
        """Every lane still holding unfed prompt tokens advances by one
        chunk, in as many calls of the engine's narrow prefill program
        (``prefill_rows`` lanes × the group's own bucket) as the lanes
        need.  A lane whose prompt completes emits its first token this
        tick; longer prompts resume next tick, interleaved with decode."""
        pending = [
            i for i, s in enumerate(self.slots)
            if s.request is not None
            and s.n_fed < len(s.request.prompt)
        ]
        if not pending:
            return 0
        # boundary span over the whole prefill half of the tick: row
        # prep, the dispatches (a span each inside), the picks, the emit
        with obs.span("prefill", boundary=True, rows=len(pending)) as span:
            return self._prefill_pending(pending, span)

    def _prefill_pending(self, pending: List[int], span) -> int:
        track = obs.request_tracking_active()
        if track:
            # rids up front: a lane that completes AND finishes this
            # tick has slot.request=None by the span-emit point below
            t0 = self.clock()
            rids = [self.slots[i].request.id for i in pending]
        cap = (
            self.engine.prefill_chunk
            if self.engine.prefill_chunk is not None
            else self.engine.chunk_buckets[-1]
        )
        chunks = {}
        for i in pending:
            s = self.slots[i]
            chunks[i] = s.request.prompt[s.n_fed:s.n_fed + cap]
        n_tokens = sum(len(c) for c in chunks.values())
        width = self.engine.prefill_rows
        # every group's call and draw are enqueued before the first
        # pick waits: the device runs call n+1 while the host emits
        # call n's first tokens, which wait for their own call alone
        calls = []  # (the group's lanes, its completing requests, draw)
        for g in range(0, len(pending), width):
            lanes = pending[g:g + width]
            self.state, logits = self.engine.prefill_chunks(
                self.params, self.state,
                [{"tokens": chunks[i], "p0": self.slots[i].n_fed,
                  "table": self.slots[i].blocks, "lane": i} for i in lanes],
            )
            if "recurrent_lanes_reset" in self.stats:
                self.stats["recurrent_lanes_reset"] += sum(
                    self.slots[i].n_fed == 0 for i in lanes)
            self._note_counters(self.engine.last_span,
                                sum(len(chunks[i]) for i in lanes))
            reqs = []  # per lane: its request if the prompt completes
            for i in lanes:
                s = self.slots[i]
                s.n_fed += len(chunks[i])
                self._lengths[i] = s.n_fed
                reqs.append(
                    s.request if s.n_fed >= len(s.request.prompt) else None
                )
            drawn = None
            if any(r is not None for r in reqs):
                drawn = self._draw_tokens(
                    [(r, len(r.output)) if r is not None else None
                     for r in reqs + [None] * (width - len(lanes))],
                    logits,
                )
            calls.append((lanes, reqs, drawn))
        span.set(n_tokens=n_tokens, calls=len(calls))
        self.stats["prefill_chunks"] += len(calls)
        self.stats["prefill_tokens"] += n_tokens
        produced = 0
        # lane order, so emits are too
        for n, (lanes, reqs, drawn) in enumerate(calls, 1):
            if drawn is None:
                continue
            with obs.span("pick", boundary=True, rows=width):
                picks = self._fetch(drawn, drains=n == len(calls))
            for i, req, token in zip(lanes, reqs, picks):
                if req is None:
                    continue
                s = self.slots[i]
                if self.prefix is not None:
                    self.prefix.insert(req.prompt, s.blocks)
                s.decoding = True
                produced += 1
                if self._emit(i, int(token)):
                    self._finish(i)
        if track:
            # one req_prefill phase span per lane covering the WHOLE
            # prefill half of the tick (row prep, the dispatches, and
            # the blocking picks) — host time a dispatch-only span
            # would leave unattributed
            t1 = self.clock()
            for rid, i in zip(rids, pending):
                obs.add_span(
                    "req_prefill", t0, t1,
                    {"rid": rid, "n_tokens": len(chunks[i])},
                )
        return produced

    def _decode_tick_paged(self) -> int:
        decoding = np.array(
            [s.decoding for s in self.slots], dtype=bool
        )
        if not decoding.any():
            return 0
        track = obs.request_tracking_active()
        if track:
            t0 = self.clock()
            rids = [
                s.request.id if decoding[i] else None
                for i, s in enumerate(self.slots)
            ]
        for i, slot in enumerate(self.slots):
            self._tokens[i] = (
                slot.request.output[-1] if decoding[i] else 0
            )
        programs = self.engine.programs
        with obs.span(
            "decode_step", boundary=True, active=int(decoding.sum()),
            # the decode kernel's grid: what the lanes hold, and what
            # the table's width would walk
            attn_steps=int(
                (self._lengths // programs.attn_span + 1).sum()),
            attn_steps_table=len(self.slots) * programs.attn_steps,
            # the key/value rows the attention must read, by the reach
            # of the layers
            **programs.decode_rows(self._lengths[decoding]),
        ) as span:
            self.state, logits = self.engine.decode_step_paged(
                self.params, self.state, self._tokens,
                self._tables, self._lengths, decoding,
            )
        self._note_counters(span, int(decoding.sum()))
        # the tick wrote each active lane's token at row `length`;
        # advance AFTER the dispatch so next tick writes the next row
        self._lengths[decoding] += 1
        toks = self._pick_batch(
            [s.request if decoding[i] else None
             for i, s in enumerate(self.slots)],
            logits,
        )
        if self._counters:
            with obs.span("counters_fetch", boundary=True,
                          calls=len(self._counters)):
                self._fetch_counters()
        produced = 0
        for i in range(len(self.slots)):
            if not decoding[i]:
                continue
            produced += 1
            if self._emit(i, int(toks[i])):
                self._finish(i)
        if track:
            t1 = self.clock()
            for i in range(len(self.slots)):
                if rids[i] is not None:
                    obs.add_span("req_decode", t0, t1, {"rid": rids[i]})
        return produced

    # ------------------------------------------------------------------
    # speculative tick (serving/spec.py holds the draft-side state)
    # ------------------------------------------------------------------
    def _spec_tick_paged(self) -> int:
        """One speculative round replacing the plain decode tick: the
        draft proposes up to ``k`` tokens per decoding lane, the target
        scores all of them in ONE ``verify_chunks`` dispatch, and each
        lane emits its accepted run plus the target's own next pick
        (1..k+1 tokens).  Token streams are identical to the plain tick
        by construction — position ``j``'s pick is only used when every
        earlier proposal matched the target's pick."""
        spec = self._spec
        decoding = np.array([s.decoding for s in self.slots], dtype=bool)
        if not decoding.any():
            return 0
        track = obs.request_tracking_active()
        if track:
            t0 = self.clock()
            rids = [
                s.request.id if decoding[i] else None
                for i, s in enumerate(self.slots)
            ]
            accepted = [0] * len(self.slots)
        for i, slot in enumerate(self.slots):
            if decoding[i] and not spec._blocks[i]:
                spec.ensure_slot(i, slot.request.prompt,
                                 slot.request.max_new_tokens,
                                 rid=slot.request.id)
        n = len(self.slots)
        k = spec.k
        last = np.zeros((n,), np.int32)
        k_eff = np.zeros((n,), np.int32)
        for i, slot in enumerate(self.slots):
            if not decoding[i]:
                continue
            last[i] = slot.request.output[-1]
            # budget clamp: a lane about to finish verifies a shorter
            # chunk — rows past its block allocation must never hold
            # live K/V.  k_eff is DATA (true_len below), never a shape.
            rem = slot.request.max_new_tokens - slot.produced
            k_eff[i] = min(k, rem - 1)
        p0 = self._lengths.copy()
        props = spec.propose(decoding, last, k_eff)
        c = k + 1
        tokens = np.zeros((n, c), np.int32)
        true_len = np.zeros((n,), np.int32)
        for i in range(n):
            if not decoding[i]:
                continue
            tokens[i, 0] = last[i]
            tokens[i, 1:1 + k_eff[i]] = props[i, :k_eff[i]]
            true_len[i] = k_eff[i] + 1
        with obs.span("spec_verify", boundary=True,
                      active=int(decoding.sum()),
                      proposed=int(k_eff.sum())):
            self.state, logits = self.engine.verify_chunks(
                self.params, self.state, tokens, self._tables, p0,
                true_len, decoding,
            )
        spec.stats["verify_dispatches"] += 1
        spec.stats["rounds"] += 1
        picks = self._pick_tokens(
            [
                (self.slots[i].request,
                 len(self.slots[i].request.output) + j)
                if decoding[i] and j <= k_eff[i] else None
                for i in range(n) for j in range(c)
            ],
            logits.reshape(n * c, -1),
        ).reshape(n, c)
        produced = 0
        for i in range(n):
            if not decoding[i]:
                continue
            slot = self.slots[i]
            a = 0
            while a < k_eff[i] and int(picks[i, a]) == int(props[i, a]):
                a += 1
            finished = False
            m = 0
            for j in range(a + 1):  # accepted proposals + the pick
                m += 1
                produced += 1
                if self._emit(i, int(picks[i, j])):
                    finished = True
                    break
            spec.note_lane(int(k_eff[i]), a, m)
            if track:
                accepted[i] = a
            # target K/V bookkeeping: rows p0..p0+m-1 hold the emitted
            # prefix's tokens; everything past them is masked garbage
            self._lengths[i] = int(p0[i]) + m
            if finished:
                self._finish(i)  # also releases the draft mirror
            else:
                spec.commit(i, a, int(k_eff[i]), props[i], int(last[i]),
                            int(p0[i]))
        if track:
            # req_spec = this request's share of the speculative round;
            # proposed/accepted let the doctor carve the rolled-back
            # fraction out as the spec_rollback phase
            t1 = self.clock()
            for i in range(len(self.slots)):
                if rids[i] is not None:
                    obs.add_span(
                        "req_spec", t0, t1,
                        {"rid": rids[i], "proposed": int(k_eff[i]),
                         "accepted": int(accepted[i]),
                         "rolled_back": max(
                             0, int(k_eff[i]) - int(accepted[i])
                         )},
                    )
        return produced

    def _step_paged(self) -> int:
        self._admit_paged()
        produced = self._prefill_tick_paged()
        produced += (
            self._spec_tick_paged() if self._spec is not None
            else self._decode_tick_paged()
        )
        if "latent_rows_resident" in self.stats:
            self.stats["latent_rows_resident"] = int(self._lengths.sum())
        return produced

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One tick: admissions, chunked prefill, then one decode
        step.  Returns the number of tokens generated."""
        self._n_ticks += 1
        # boundary span over the whole tick; its children are `admit`,
        # `prefill`, `decode_step`/`spec_verify`, `pick` and
        # `counters_fetch`, so its self time is the Python between the
        # programs
        with obs.span("tick", boundary=True, n=self._n_ticks,
                      active=self.n_active, queued=len(self.queue)) as span:
            produced = self._tick()
            span.set(produced=produced)
        return produced

    def _tick(self) -> int:
        track = obs.request_tracking_active()
        if track:
            # whole-tick phase accounting: a decoding lane spends real
            # wall time sitting through OTHER lanes' prefill chunks and
            # the tick's host bookkeeping — time the per-dispatch spans
            # alone leave unattributed.  One span per in-flight rid per
            # tick, named for the phase the request is IN (decode wall
            # time is what TPOT measures; prefill wall time is what
            # TTFT measures), clipped to mid-tick admission.
            t0 = self.clock()
            self._req_tick_adm.clear()
            phase_of: Dict[str, str] = {}
            for s in self.slots:
                if s.request is not None:
                    feeding = s.n_fed < len(s.request.prompt)
                    phase_of[s.request.id] = (
                        "req_prefill" if feeding else "req_decode"
                    )
        produced = self._step_paged()
        if track:
            t1 = self.clock()
            for s in self.slots:
                if s.request is not None:
                    rid = s.request.id
                    if rid not in phase_of:
                        feeding = s.n_fed < len(s.request.prompt)
                        phase_of[rid] = (
                            "req_prefill" if feeding else "req_decode"
                        )
            for rid, _n in self._req_done:
                # finished mid-tick: it was producing tokens, so its
                # share of this tick reads as decode unless it entered
                # the tick still feeding prompt
                phase_of.setdefault(rid, "req_decode")
            for rid, name in phase_of.items():
                start = max(t0, self._req_tick_adm.get(rid, t0))
                if t1 > start:
                    obs.add_span(name, start, t1, {"rid": rid})
        if self._req_done:
            self._close_finished_requests()
        _TOKENS.inc(produced, model_generation=str(self.model_generation))
        return produced

    def run(self, max_ticks: int = 100_000) -> Dict[str, List[int]]:
        """Drive ``step()`` until queue and slots drain.  Returns
        ``finished`` (id → generated tokens).

        SLO feed: under ``THEANOMPI_LIVE=1``/``THEANOMPI_LIVE_AGG``
        (observability/live.py) the run heartbeats telemetry frames —
        the TTFT/TPOT histogram deltas this scheduler's metrics write
        become per-window percentiles on the aggregator, so the
        watchdog's ``max_ttft_p99_s``/``max_tpot_p99_s`` rules watch a
        serving run the way ``max_straggler`` watches training."""
        from theanompi_tpu.observability import live as obs_live

        telemetry = obs_live.maybe_start_from_env("serve")
        ticks = 0
        try:
            while self.queue or self.n_active:
                ticks += 1
                if ticks > max_ticks:
                    raise RuntimeError(
                        f"scheduler did not drain within {max_ticks} ticks"
                    )
                self.step()
        finally:
            if telemetry is not None:
                telemetry.stop()
        if self.metrics is not None:
            stats = dict(self.stats)
            stats["pool_peak_used_blocks"] = self.pool.peak_used
            stats["pool_blocks"] = self.pool.n_blocks - 1
            if self.prefix is not None:
                stats["prefix_entries"] = len(self.prefix)
            if self._spec is not None:
                stats["spec"] = self._spec.summary()
            self.metrics.set_engine_stats(stats)
        return self.finished
