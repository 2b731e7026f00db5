"""The serving fleet: N paged engines behind one fault-tolerant door.

The paper's core claim (arXiv:1605.08325) is that a fleet of
independently-scheduled workers beats one monolith; PR 10 took the
*training* tier there (heartbeat rosters, eviction, checkpointless
re-admission).  This module is the same move for serving — three landed
subsystems composed into the millions-of-users story:

- **paging** (PR 8/11): each replica is a ``PagedServingEngine`` +
  ``ContinuousBatchingScheduler`` — prefix cache, chunked prefill,
  zero-recompile tables.
- **membership** (PR 10): replicas live in a ``parallel.membership``
  ``Roster`` (plane ``"serve"``).  Heartbeats piggyback on the
  router's ordinary poll replies — an answered poll IS a liveness
  proof, no extra frames — and a silent replica is EVICTED, never
  waited on.
- **transport** (PR 7/10/this PR): the router speaks
  ``transport.request()``'s request/reply channel (retries, rpc flow
  ids, spans, and now a per-call deadline budget), so replicas can be
  in-process objects (tests, the chaos drill) or real TCP endpoints
  (``ServeReplica(port=...)``) behind the SAME router code path.

Robustness contract (the chaos drill in ``runtime/chaos.py`` gates it):

- **Kill a replica mid-stream** and its in-flight requests re-admit on
  a surviving replica with token-identical output.  The router
  journals every accepted token per stream, so re-admission submits a
  FRESH request whose prompt is ``original prompt + accepted tokens``
  and whose budget is the remaining tokens; the replay rides the
  ordinary prefill path (the prefix cache makes it cheap when the
  surviving replica has seen the prefix) and ``Request.token_index0``
  keeps sampled streams drawing with the original per-index keys.
  Greedy AND sampled outputs are identical to an uninterrupted run by
  construction.
- **Drain-on-leave**: a draining replica finishes its in-flight slots,
  refuses new admissions (counted backpressure the router re-routes),
  then ``leave()``s the roster cleanly — zero accepted requests
  dropped, zero eviction alerts.
- **Health shedding**: a replica whose live doctor trips ``/health``
  503 is shed from the admission rotation — zero new admissions until
  it reports green — while its in-flight streams run on.

Routing is **prefix-affine**: replicas gossip compact radix-tree
summaries (``radix.RadixPrefixCache.summary`` — content digests, no
tokens, MRU-first) in their poll replies, and the router scores each
incoming prompt against every live summary by **match depth ×
recency** (``radix.score_prompt_weighted`` — a replica whose matching
chain is warm outranks one holding the same depth in entries about to
be LRU-evicted), placing the request where the longest live prefix is
resident.  Poll replies also advertise **pool headroom** (free KV
blocks), the placement tiebreak: reuse being equal, the request goes
where capacity is; cold prompts fall back to least-loaded with the
same tiebreak.  ``detail.fleet`` in ``bench_serve.py --replicas N``
measures the win over round-robin.

Observability: replica threads are named (per-replica trace tracks);
evictions raise ONE ``replica_evicted`` alert and re-admissions page
``request_readmitted`` through the live plane's counter-delta rules
(``serve_fleet_readmissions_total``).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from theanompi_tpu import observability as obs
from theanompi_tpu.parallel import transport
from theanompi_tpu.parallel.membership import Roster
from theanompi_tpu.serving import metrics as smetrics
from theanompi_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    SchedulerDraining,
)

PROTOCOL_VERSION = 1

_REG = obs.get_registry()
_FORCED_DRAIN_INSTALLS = _REG.counter(
    "publish_forced_drain_installs_total",
    "publish installs that composed a forced drain on a saturated "
    "replica (expected rollout path under sustained load — not paged)",
)


class FleetError(RuntimeError):
    """No replica could take a request (fleet down / all draining)."""


class ReplicaKilled(ConnectionError):
    """In-process stand-in for a dead TCP endpoint: calls into a
    killed replica fail exactly like a refused connection, so the
    router's failure path is one code path for both transports."""


class ServeReplica:
    """One serving engine behind the fleet's request/reply protocol.

    ``handle(msg)`` is the single protocol entry — it IS the
    ``TcpServerChannel`` handler when ``port`` is given, and the
    router calls it directly for in-process replicas.  A background
    thread drives scheduler ticks; every protocol access and every
    tick serialize on ``self._lock`` (the scheduler is not
    thread-safe — the GL-T graftlint pass watches exactly this
    surface).

    ``health_fn`` mirrors the live plane's ``/health`` contract: a
    zero-arg callable returning True (green) or False (503).  Wire the
    live watchdog's ``ok()`` here in production; tests and the chaos
    drill inject trips directly.

    **Live weight installs** (``theanompi_tpu.publish``): a
    ``WeightSubscriber`` hands validated snapshots to
    :meth:`install_params`, which queues them and applies BETWEEN
    ticks — only when the scheduler is fully idle (no queued, no
    active streams), so a request admitted against generation G
    decodes every token against G.  The apply is a whole-tree rebind
    of ``scheduler.params`` (params are data to the jitted step — no
    retrace), the serving-generation marker is assigned LAST, and each
    install bumps an install epoch through the same
    ``parallel.membership`` generation machinery the training planes
    use.  Honest limit: a replica that is never idle never installs —
    drain it (or let admission gaps occur) to take a publish.
    """

    def __init__(
        self,
        name: str,
        engine,
        params=None,
        port: Optional[int] = None,
        health_fn=None,
        prefix_impl: str = "radix",
        summary_cap: int = 256,
        tick_idle_s: float = 0.002,
        install_max_wait_s: float = 30.0,
        **sched_kwargs,
    ):
        self.name = str(name)
        self.engine = engine
        self._lock = threading.Lock()
        self.scheduler = ContinuousBatchingScheduler(
            engine, params=params, prefix_impl=prefix_impl, **sched_kwargs
        )
        # the ROUTER owns each stream's retention buffer: a replica-side
        # finish is not the end of the request's story (the stream may
        # yet be re-admitted elsewhere), so this scheduler must not
        # close buffers — the router's _absorb_poll closes them when it
        # sees the stream complete
        self.scheduler.owns_request_buffers = False
        self.summary_cap = int(summary_cap)
        self.tick_idle_s = float(tick_idle_s)
        self._health_fn = health_fn
        self._streams: Dict[str, Request] = {}
        self.ticks = 0
        # live weight publication (publish/): the generation this
        # replica currently serves, a deferred install slot, and an
        # install epoch riding the membership-roster generation
        # machinery (every applied install re-joins, which bumps)
        self.serving_generation = 0
        self.installs = 0
        self._pending_install: Optional[Tuple[Any, int]] = None
        # forced-drain install (the saturated-replica gap): a pending
        # install older than install_max_wait_s composes begin_drain →
        # idle → apply → end_drain so a never-idle replica still makes
        # rollout progress (<= 0 disables the forcing)
        self.install_max_wait_s = float(install_max_wait_s)
        self._pending_install_since: Optional[float] = None
        self._forced_drain = False
        self.forced_drain_installs = 0
        self._install_roster = Roster("publish", evict_after_s=3600.0)
        self.install_epoch = self._install_roster.join(self.name)
        self._killed = False
        self._stop = threading.Event()
        self.port = port
        self.channel = (
            transport.TcpServerChannel(port, self.handle)
            if port is not None else None
        )
        self._thread = threading.Thread(
            target=self._loop, name=f"ServeReplica-{self.name}", daemon=True
        )

    # ---- lifecycle ---------------------------------------------------
    def start(self) -> "ServeReplica":
        self._thread.start()
        return self

    def stop(self) -> None:
        """Graceful teardown (tests): stop ticking, close the port."""
        self._stop.set()
        if self.channel is not None:
            self.channel.close()
        self._thread.join(timeout=5.0)

    def kill(self) -> None:
        """The chaos hammer: die NOW, mid-stream, without goodbye.
        In-flight slots are abandoned exactly as a SIGKILL'd process
        abandons them; subsequent ``handle`` calls raise like a dead
        endpoint refuses connections."""
        self._killed = True
        self._stop.set()
        if self.channel is not None:
            self.channel.close()

    @property
    def healthy(self) -> bool:
        if self._health_fn is None:
            return True
        try:
            return bool(self._health_fn())
        except Exception:
            return False  # a crashing health probe is not green

    def set_health_fn(self, fn) -> None:
        self._health_fn = fn

    def _loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                work = bool(self.scheduler.queue) or self.scheduler.n_active
                if work:
                    with obs.span("replica_tick", replica=self.name):
                        self.scheduler.step()
                    self.ticks += 1
                    self._maybe_force_drain_locked()
                elif self._pending_install is not None:
                    # between-ticks install point: no queued and no
                    # active streams, so nothing can observe the swap
                    # mid-flight (torn installs impossible by position)
                    self._apply_install_locked()
            if not work:
                time.sleep(self.tick_idle_s)

    # ---- live weight installs (publish/) -----------------------------
    @property
    def pending_generation(self) -> Optional[int]:
        p = self._pending_install
        return p[1] if p is not None else None

    def install_params(
        self, params, generation: int, rollback: bool = False
    ) -> int:
        """Queue ``params`` for a between-ticks install under
        ``generation``.  Applied immediately when the scheduler is
        idle, otherwise deferred to the tick loop's next idle gap.
        Non-rollback installs must advance the generation — a stale or
        duplicate generation is refused LOUDLY (the subscriber's
        monotone-pull contract makes this a bug, not a race); only an
        explicit ``rollback=True`` may move the marker backward."""
        generation = int(generation)
        with self._lock:
            pend = self._pending_install
            held = max(
                self.serving_generation,
                pend[1] if pend is not None else 0,
            )
            if not rollback and generation <= held:
                raise ValueError(
                    f"replica {self.name!r}: install of generation "
                    f"{generation} refused — already serving/holding "
                    f"generation {held} (rollbacks must say "
                    "rollback=True)"
                )
            self._pending_install = (params, generation)
            if self.scheduler.idle:
                self._apply_install_locked()
            elif self._pending_install_since is None:
                # rollout-progress clock starts at the FIRST deferral;
                # a newer snapshot replacing a still-pending one keeps
                # the original stamp (the gap is what matters)
                self._pending_install_since = time.monotonic()
        return generation

    def _maybe_force_drain_locked(self) -> None:
        """The saturated-replica install gap: a replica that is never
        idle would hold a pending install forever.  Once the deferral
        outlives ``install_max_wait_s``, begin a drain — the router
        observes ``draining`` in the next poll reply and routes new
        work elsewhere; in-flight streams finish, the idle gap applies
        the install, and ``_apply_install_locked`` reopens admissions.
        Expected rollout path under sustained load: counted
        (``publish_forced_drain_installs_total``), never paged."""
        if (
            self._pending_install is None
            or self._forced_drain
            or self.scheduler.draining
            or self.install_max_wait_s <= 0
            or self._pending_install_since is None
        ):
            return
        waited = time.monotonic() - self._pending_install_since
        if waited < self.install_max_wait_s:
            return
        self.scheduler.begin_drain()
        self._forced_drain = True

    def _apply_install_locked(self) -> None:
        """Apply the queued install.  Caller holds ``self._lock`` and
        has proven the scheduler idle.  The swap is a WHOLE-TREE rebind
        — never per-leaf stores into the live tree (the GL-W003 torn-
        install shape) — and the generation markers are assigned only
        after the new tree is fully in place."""
        params, generation = self._pending_install
        self._pending_install = None
        self._pending_install_since = None
        forced = self._forced_drain
        track = obs.request_tracking_active()
        if track:
            t0 = obs.get_tracer().clock()
        with obs.span(
            "weights_install", replica=self.name, generation=generation
        ):
            # cached prefix KV was computed under the OUTGOING weights;
            # serving it against the new tree would silently leak the
            # old generation into pinned streams.  The scheduler is
            # idle, so every cached block holds exactly the cache's own
            # reference and a full sweep empties the cache.
            prefix = getattr(self.scheduler, "prefix", None)
            if prefix is not None:
                prefix.evict_unused(None)
            # in the engine's serving layout (a tree that came through
            # `relayout_for_serving`, or a rollback's prior tree, is
            # already there and is bound as it is)
            self.scheduler.install_params(params)
            self.installs += 1
            # install epoch: the membership roster's rejoin bump IS the
            # monotone epoch counter (generation machinery reused, not
            # reinvented) — distinct from serving_generation, which the
            # publisher owns and a rollback may rewind
            self.install_epoch = self._install_roster.join(self.name)
            self.scheduler.model_generation = generation
            self.serving_generation = generation  # marker LAST
        if forced:
            # the drain existed only to make this install possible —
            # rejoin the admission rotation (the router un-drains this
            # replica from its next poll reply)
            self._forced_drain = False
            self.scheduler.end_drain()
            self.forced_drain_installs += 1
            _FORCED_DRAIN_INSTALLS.inc(replica=self.name)
        if track:
            # install-wait phase spans for any stream still open on
            # THIS replica (none in the ordinary idle-gap install; the
            # span is the honest record if an install ever applies with
            # streams in flight)
            t1 = obs.get_tracer().clock()
            for rid in self._streams:
                if rid not in self.scheduler.finished:
                    obs.add_span(
                        "req_install_wait", t0, t1,
                        {"rid": rid, "generation": generation},
                    )
        obs.publish_event(
            "weights_installed",
            {
                "replica": self.name,
                "generation": generation,
                "install_epoch": self.install_epoch,
                "forced_drain": forced,
            },
        )

    # ---- protocol ----------------------------------------------------
    def handle(self, msg: Any) -> Any:
        """One protocol message → one reply dict.  Raises
        :class:`ReplicaKilled` after ``kill()`` so in-process callers
        share the TCP caller's failure path."""
        if self._killed:
            raise ReplicaKilled(f"replica {self.name!r} is dead")
        kind = msg[0]
        if kind == "hello":
            return {
                "ok": True,
                "v": PROTOCOL_VERSION,
                "name": self.name,
                "block_size": int(self.engine.block_size),
                "n_slots": int(self.engine.n_slots),
                "max_len": int(self.engine.max_len),
                "generation": int(self.serving_generation),
            }
        if kind == "submit":
            return self._handle_submit(msg[1])
        if kind == "poll":
            return self._handle_poll(msg[1])
        if kind == "drain":
            with self._lock:
                self.scheduler.begin_drain()
            return {"ok": True}
        if kind == "health":
            return {"ok": True, "healthy": self.healthy}
        return {"ok": False, "reason": f"unknown message kind {kind!r}"}

    def _handle_submit(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        req = Request(
            id=str(spec["id"]),
            prompt=[int(t) for t in spec["prompt"]],
            max_new_tokens=int(spec["max_new_tokens"]),
            eos_id=(None if spec.get("eos_id") is None
                    else int(spec["eos_id"])),
            temperature=float(spec.get("temperature", 0.0)),
            top_k=int(spec.get("top_k", 0)),
            seed=(None if spec.get("seed") is None else int(spec["seed"])),
            token_index0=int(spec.get("token_index0", 0)),
        )
        with self._lock:
            try:
                self.scheduler.submit(req)
            except SchedulerDraining:
                return {"ok": False, "reason": "draining"}
            except ValueError as e:  # impossible geometry — loud, not lost
                return {"ok": False, "reason": f"refused: {e}"}
            self._streams[req.id] = req
        # arrow head of the router→replica hand-off: the flow id is
        # reconstructed from the spec alone (``req:{rid}`` for the
        # initial hop, ``req:{rid}:r{token_index0}`` for a re-admission
        # — token_index0 IS the journal length at resubmit), so the
        # replica needs no side channel to pair the router's begin
        fid = (
            f"req:{req.id}" if req.token_index0 == 0
            else f"req:{req.id}:r{req.token_index0}"
        )
        obs.flow_end("req", fid, {"rid": req.id, "replica": self.name})
        return {"ok": True, "ticks": self.ticks}

    def _handle_poll(self, cursors: Dict[str, int]) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        with self._lock:
            for rid, cursor in cursors.items():
                req = self._streams.get(rid)
                if req is None:
                    continue  # unknown stream: the router re-routed it
                done = rid in self.scheduler.finished
                toks = [int(t) for t in req.output[int(cursor):]]
                out[rid] = {"toks": toks, "done": done}
                if done:
                    del self._streams[rid]
            summary = []
            if self.scheduler.prefix is not None:
                fn = getattr(self.scheduler.prefix, "summary", None)
                if fn is not None:
                    summary = fn(self.summary_cap)
            pool = getattr(self.scheduler, "pool", None)
            reply = {
                "ok": True,
                "streams": out,
                "ticks": self.ticks,
                "healthy": self.healthy,
                "draining": self.scheduler.draining,
                "idle": self.scheduler.idle,
                # the serving generation rides every poll reply: the
                # router's per-replica view powers version-pinned
                # admission (A/B cohorts) with no extra frames
                "generation": int(self.serving_generation),
                "summary": summary,
                # pool headroom rides the poll reply as a placement
                # tiebreak: equal-affinity candidates go to the replica
                # with the most free KV blocks, not just fewest streams
                "headroom": (
                    int(pool.n_free) if pool is not None else 0
                ),
                # demand-pressure counters for scaling_signals(): how
                # often THIS replica pushed work away
                "backpressure": int(
                    self.scheduler.stats.get("backpressure_events", 0)
                ),
                "drain_refusals": int(
                    self.scheduler.stats.get("drain_refusals", 0)
                ),
            }
        return reply


class _Stream:
    """The router's journal for one accepted request: everything needed
    to re-admit it token-identically on another replica."""

    __slots__ = (
        "id", "prompt", "max_new_tokens", "eos_id", "temperature",
        "top_k", "seed", "replica", "tokens", "done", "readmissions",
        "base", "pin",
    )

    def __init__(
        self, spec: Dict[str, Any], replica: str,
        pin: Optional[int] = None,
    ):
        self.id = spec["id"]
        self.prompt = list(spec["prompt"])
        self.max_new_tokens = int(spec["max_new_tokens"])
        self.eos_id = spec.get("eos_id")
        self.temperature = float(spec.get("temperature", 0.0))
        self.top_k = int(spec.get("top_k", 0))
        self.seed = spec.get("seed")
        self.replica = replica
        # version pin (A/B serving): admission and every re-admission
        # stay on replicas serving exactly this model generation
        self.pin = None if pin is None else int(pin)
        self.tokens: List[int] = []  # the accepted-token journal
        self.done = False
        self.readmissions = 0
        # journal length when the CURRENT assignment started: the
        # replica-side request only generates the remainder, so poll
        # cursors into its output are journal-relative minus this base
        self.base = 0

    def journal_complete(self) -> bool:
        """The accepted journal already ends the stream (budget met or
        eos accepted) — nothing left to re-admit."""
        return (
            len(self.tokens) >= self.max_new_tokens
            or (self.eos_id is not None and self.eos_id in self.tokens)
        )

    def resubmit_spec(self) -> Dict[str, Any]:
        """The re-admission request: prompt + accepted prefix replayed
        through the ordinary prefill path, budget = what remains,
        ``token_index0`` = how many picks already happened (sampled
        streams keep their per-index keys)."""
        return {
            "id": self.id,
            "prompt": self.prompt + self.tokens,
            "max_new_tokens": self.max_new_tokens - len(self.tokens),
            "eos_id": self.eos_id,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "seed": self.seed,
            "token_index0": len(self.tokens),
        }


class _ReplicaState:
    __slots__ = (
        "name", "target", "block_size", "summary", "shed", "draining",
        "left", "dead", "active", "shed_events", "shed_since",
        "shed_seconds", "tokens_out", "headroom", "backpressure",
        "drain_refusals", "generation",
    )

    def __init__(self, name: str, target):
        self.name = name
        self.target = target  # ServeReplica-like (has .handle) or (host, port)
        self.block_size = 0
        self.summary: List[str] = []
        self.headroom = 0  # free pool blocks from the last poll reply
        self.shed = False  # health-red: no new admissions until green
        self.draining = False
        self.left = False  # clean leave — out of the fleet for good
        self.dead = False  # evicted
        self.active = 0  # streams currently assigned here
        self.shed_events = 0
        self.shed_since: Optional[float] = None
        self.shed_seconds = 0.0
        self.tokens_out = 0
        self.backpressure = 0  # replica-side backpressure_events
        self.drain_refusals = 0  # replica-side drain_refusals
        self.generation = 0  # serving generation from the last poll

    @property
    def admitting(self) -> bool:
        return not (self.dead or self.left or self.draining or self.shed)


class FleetRouter:
    """The admission front door over N replicas.

    One router thread of control: callers ``submit()`` requests and
    drive ``pump()`` (or ``run()``), which polls every live replica,
    journals accepted tokens, heartbeats the roster from the replies,
    sweeps for evictions, and re-admits orphaned streams.  The router
    is the ONLY caller of its own state (no internal threads), so a
    supervisor can compose it with whatever loop it already runs.

    ``affinity=False`` degrades routing to least-loaded/round-robin —
    the bench's control arm for measuring the prefix-affinity win.
    """

    def __init__(
        self,
        evict_after_s: float = 2.0,
        join_grace_s: Optional[float] = None,
        rpc_deadline_s: float = 5.0,
        affinity: bool = True,
        metrics=None,
        clock=time.monotonic,
        on_alert=None,
    ):
        self.clock = clock
        self.metrics = metrics
        self.affinity = bool(affinity)
        self.rpc_deadline_s = float(rpc_deadline_s)
        self._on_alert = on_alert
        self.roster = Roster(
            "serve",
            evict_after_s=evict_after_s,
            join_grace_s=join_grace_s,
            clock=clock,
            on_event=self._roster_event,
        )
        self._replicas: Dict[str, _ReplicaState] = {}
        self._streams: Dict[str, _Stream] = {}
        self._rr = 0  # round-robin tiebreak cursor
        self._pending_evictions: List[str] = []
        self.stats = {
            "submitted": 0,
            "finished": 0,
            "routed_affine": 0,
            "routed_fallback": 0,
            "affine_hit_tokens": 0,
            "evictions": 0,
            "readmissions": 0,
            "shed_events": 0,
            "drain_reroutes": 0,
            "poll_failures": 0,
            "requests_lost": 0,
        }

    # ---- membership ---------------------------------------------------
    def add_replica(self, name: str, target) -> None:
        """Register one replica (in-process object or ``(host, port)``)
        and join it to the roster.  The hello round-trip proves the
        endpoint is alive before it can ever be routed to."""
        name = str(name)
        if name in self._replicas and not (
            self._replicas[name].dead or self._replicas[name].left
        ):
            raise ValueError(f"replica {name!r} already registered")
        state = _ReplicaState(name, target)
        hello = self._call(state, ("hello",))
        state.block_size = int(hello["block_size"])
        self._replicas[name] = state
        self.roster.join(name)

    def _roster_event(self, kind: str, member, generation: int) -> None:
        if kind == "evict":
            # defer the re-admission work to pump(): the hook runs
            # inside sweep() and must stay cheap/non-reentrant
            self._pending_evictions.append(str(member))

    def _call(self, state: _ReplicaState, msg) -> Any:
        if isinstance(state.target, tuple):
            return transport.request(
                tuple(state.target), msg, timeout=self.rpc_deadline_s,
                deadline_s=self.rpc_deadline_s,
            )
        return state.target.handle(msg)

    # ---- routing ------------------------------------------------------
    def _eligible(self) -> List[_ReplicaState]:
        return [s for s in self._replicas.values() if s.admitting]

    def _score(
        self, state: _ReplicaState, prompt: Sequence[int]
    ) -> Tuple[float, int]:
        """(depth × recency weight, match depth in blocks) for one
        replica's MRU-first summary — radix.score_prompt_weighted."""
        if not self.affinity or not state.summary or not state.block_size:
            return 0.0, 0
        from theanompi_tpu.serving.radix import score_prompt_weighted

        return score_prompt_weighted(
            prompt, state.block_size, state.summary
        )

    def route(
        self, prompt: Sequence[int], generation: Optional[int] = None
    ) -> Tuple[str, int]:
        """(replica name, affinity match depth in blocks) for one
        prompt: highest depth × recency weight wins (a replica whose
        matching chain is warm outranks one holding the same depth in
        entries about to be LRU-evicted); weight ties break on
        advertised pool headroom, then round-robin.  No match falls
        back to least-loaded, headroom-then-round-robin tiebroken.
        ``generation`` (A/B pinning) restricts candidates to replicas
        last seen serving exactly that model generation."""
        elig = self._eligible()
        if generation is not None:
            elig = [s for s in elig if s.generation == int(generation)]
            if not elig:
                raise FleetError(
                    f"no admitting replica serves generation "
                    f"{int(generation)} (pinned cohort)"
                )
        if not elig:
            raise FleetError("no replica is admitting (fleet down, "
                             "draining, or fully shed)")
        scored = [(*self._score(s, prompt), s) for s in elig]
        best = max(sc for sc, _d, _s in scored)
        if best > 0:
            cands = [(d, s) for sc, d, s in scored if sc == best]
            depth = max(d for d, _ in cands)
            cands = [s for d, s in cands if d == depth]
        else:
            depth = 0
            load = min(s.active for s in elig)
            cands = [s for s in elig if s.active == load]
        if len(cands) > 1:
            # placement tiebreak: the most free KV blocks — reuse being
            # equal, spend the request where capacity is
            room = max(s.headroom for s in cands)
            cands = [s for s in cands if s.headroom == room]
        pick = cands[self._rr % len(cands)]
        self._rr += 1
        return pick.name, depth

    def submit(
        self,
        request: Union[Request, Dict[str, Any]],
        generation: Optional[int] = None,
    ) -> str:
        """Admit one request to the fleet; returns the replica name it
        landed on.  A refusing replica (drain race, just-died) is
        skipped and the request re-routes — ``FleetError`` only when
        every replica refused.  ``generation`` pins this request's
        cohort to replicas serving that model generation — admission
        AND any re-admission stay on the pinned version, so cohort
        timelines compare cleanly (``publish.ab``)."""
        spec = (
            {
                "id": request.id,
                "prompt": list(request.prompt),
                "max_new_tokens": request.max_new_tokens,
                "eos_id": request.eos_id,
                "temperature": request.temperature,
                "top_k": request.top_k,
                "seed": request.seed,
            }
            if isinstance(request, Request) else dict(request)
        )
        if spec["id"] in self._streams:
            raise ValueError(f"stream id {spec['id']!r} already submitted")
        rid = str(spec["id"])
        # the request's story starts HERE: open its retention buffer
        # (no-op unless request tracking is on) and emit the arrow tail
        # the accepting replica's _handle_submit pairs with
        obs.request_begin(rid, prompt_len=len(spec["prompt"]))
        try:
            with obs.span("fleet_submit", rid=rid):
                obs.flow_begin("req", f"req:{rid}", {"rid": rid})
                name, score = self.route(
                    spec["prompt"], generation=generation
                )
                stream = _Stream(spec, name, pin=generation)
                placed = self._place(stream, spec, first_choice=name)
        except FleetError:
            obs.request_end(rid, status="rejected")
            raise
        if self.metrics is not None:
            gen = (
                stream.pin if stream.pin is not None
                else self._replicas[placed].generation
            )
            self.metrics.admitted(
                stream.id, len(stream.prompt), generation=gen
            )
        self._streams[stream.id] = stream
        self.stats["submitted"] += 1
        if score > 0 and placed == name:
            self.stats["routed_affine"] += 1
            self.stats["affine_hit_tokens"] += (
                score * self._replicas[name].block_size
            )
            smetrics.FLEET_ROUTED.inc(policy="affine")
        else:
            self.stats["routed_fallback"] += 1
            smetrics.FLEET_ROUTED.inc(policy="fallback")
        return placed

    def _place(self, stream: _Stream, spec: Dict[str, Any],
               first_choice: str) -> str:
        """Try the routed replica, then every other admitting one (a
        pinned stream only ever tries replicas on its generation)."""
        order = [first_choice] + [
            s.name for s in self._eligible()
            if s.name != first_choice
            and (stream.pin is None or s.generation == stream.pin)
        ]
        for name in order:
            state = self._replicas[name]
            try:
                reply = self._call(state, ("submit", spec))
            except (ConnectionError, OSError, TimeoutError):
                continue  # dead/dying: the sweep will evict it
            if reply.get("ok"):
                if name != first_choice:
                    self.stats["drain_reroutes"] += 1
                    smetrics.FLEET_DRAIN_REROUTES.inc()
                stream.replica = name
                state.active += 1
                self.roster.beat(name, step=reply.get("ticks"))
                return name
            if reply.get("reason") == "draining":
                state.draining = True
        raise FleetError(
            f"request {spec['id']!r}: every replica refused or failed"
        )

    # ---- the pump -----------------------------------------------------
    def pump(self) -> int:
        """One router round: poll every replica that owns streams (or
        could), journal tokens, heartbeat + sweep the roster, re-admit
        orphans.  Returns the number of still-open streams."""
        with obs.span("fleet_pump", streams=len(self._streams)):
            by_replica: Dict[str, Dict[str, int]] = {}
            for st in self._streams.values():
                if not st.done:
                    by_replica.setdefault(st.replica, {})[st.id] = (
                        len(st.tokens) - st.base
                    )
            for name, state in list(self._replicas.items()):
                if state.dead or state.left:
                    continue
                cursors = by_replica.get(name, {})
                try:
                    reply = self._call(state, ("poll", cursors))
                except (ConnectionError, OSError, TimeoutError):
                    self.stats["poll_failures"] += 1
                    continue  # no beat: silence is how eviction starts
                self._absorb_poll(state, reply)
            self.roster.sweep()
            while self._pending_evictions:
                self._handle_eviction(self._pending_evictions.pop(0))
        return sum(1 for s in self._streams.values() if not s.done)

    def _absorb_poll(self, state: _ReplicaState, reply: Dict) -> None:
        self.roster.beat(state.name, step=reply.get("ticks"))
        state.summary = list(reply.get("summary") or ())
        state.headroom = int(reply.get("headroom") or 0)
        state.backpressure = int(reply.get("backpressure") or 0)
        state.drain_refusals = int(reply.get("drain_refusals") or 0)
        state.generation = int(reply.get("generation") or 0)
        state.draining = bool(reply.get("draining"))
        now = self.clock()
        healthy = bool(reply.get("healthy", True))
        if not healthy and not state.shed:
            state.shed = True
            state.shed_events += 1
            state.shed_since = now
            self.stats["shed_events"] += 1
            smetrics.FLEET_SHED.inc(replica=state.name)
            self._alert(
                "replica_shed",
                f"replica {state.name!r} health went red — shed from "
                "admission rotation until green",
            )
        elif healthy and state.shed:
            state.shed = False
            if state.shed_since is not None:
                state.shed_seconds += now - state.shed_since
                state.shed_since = None
        for rid, row in (reply.get("streams") or {}).items():
            st = self._streams.get(rid)
            if st is None or st.done or st.replica != state.name:
                continue
            toks = [int(t) for t in row.get("toks") or ()]
            if toks:
                if self.metrics is not None and not st.tokens:
                    self.metrics.first_token(st.id)
                st.tokens.extend(toks)
                state.tokens_out += len(toks)
            if row.get("done") or st.journal_complete():
                st.done = True
                state.active = max(0, state.active - 1)
                self.stats["finished"] += 1
                if self.metrics is not None:
                    self.metrics.finished(st.id, len(st.tokens))
                # the router owns the stream's retention buffer
                # (replica schedulers run with owns_request_buffers
                # off) — the story ends when the ROUTER sees the
                # stream complete, so a mid-flight kill can still
                # flag-and-retain the whole trace
                obs.request_end(st.id, n_tokens=len(st.tokens))

    def _handle_eviction(self, name: str) -> None:
        state = self._replicas.get(name)
        if state is None or state.dead:
            return
        state.dead = True
        self.stats["evictions"] += 1
        self._alert(
            "replica_evicted",
            f"replica {name!r} evicted after missed heartbeats — "
            "re-admitting its in-flight streams",
        )
        for st in list(self._streams.values()):
            if st.replica != name or st.done:
                continue
            state.active = max(0, state.active - 1)
            if st.journal_complete():
                st.done = True  # journal already complete
                self.stats["finished"] += 1
                if self.metrics is not None:
                    self.metrics.finished(st.id, len(st.tokens))
                # the dead replica's scheduler never closed this
                # request's retention buffer — close it here (no-op
                # when the replica-side finish already did)
                obs.request_end(st.id, n_tokens=len(st.tokens))
                continue
            spec = st.resubmit_spec()
            st.readmissions += 1
            self.stats["readmissions"] += 1
            smetrics.FLEET_READMISSIONS.inc(replica=name)
            # a killed/readmitted stream is retained UNCONDITIONALLY —
            # failovers are exactly the tails worth explaining
            obs.request_flag(st.id, "readmitted")
            self._alert(
                "request_readmitted",
                f"stream {st.id!r} re-admitted off dead replica "
                f"{name!r} with {len(st.tokens)} accepted token(s) "
                "journaled",
            )
            try:
                # a pinned stream re-admits only onto its generation —
                # losing it when that generation vanished is honest.
                # The hop gets its own phase span + a fresh flow arrow
                # (id suffixed with the journal length = the spec's
                # token_index0, which the accepting replica's flow_end
                # reconstructs without a side channel)
                with obs.span("req_readmit", rid=st.id, off_replica=name,
                              journaled=len(st.tokens)):
                    obs.flow_begin(
                        "req", f"req:{st.id}:r{len(st.tokens)}",
                        {"rid": st.id},
                    )
                    placed = self._place(
                        st, spec, first_choice=self.route(
                            spec["prompt"], generation=st.pin
                        )[0],
                    )
            except FleetError:
                st.done = True  # surfaced as a violation by the drill
                self.stats["requests_lost"] += 1
                obs.request_flag(st.id, "lost")
                obs.request_end(st.id, status="lost",
                                n_tokens=len(st.tokens))
                self._alert(
                    "request_lost",
                    f"stream {st.id!r} could not re-admit anywhere",
                )
                continue
            st.replica = placed
            st.base = len(st.tokens)

    def _alert(self, rule: str, message: str) -> None:
        if self._on_alert is not None:
            try:
                self._on_alert(rule, message)
            except Exception:
                pass
        obs.instant(f"fleet_{rule}", {"message": message})

    # ---- drain / run --------------------------------------------------
    def drain_replica(self, name: str, timeout_s: float = 60.0,
                      poll_interval_s: float = 0.01) -> None:
        """Drain-on-leave: tell ``name`` to stop admitting, pump until
        its in-flight streams complete, then ``leave()`` it from the
        roster (clean — no eviction alert) and drop it from rotation."""
        state = self._replicas[name]
        self._call(state, ("drain",))
        state.draining = True
        deadline = self.clock() + timeout_s
        while any(
            not st.done and st.replica == name
            for st in self._streams.values()
        ):
            if self.clock() > deadline:
                raise FleetError(
                    f"drain of {name!r} did not finish within {timeout_s}s"
                )
            self.pump()
            time.sleep(poll_interval_s)
        self.roster.leave(name)
        state.left = True

    def run(self, timeout_s: float = 300.0,
            poll_interval_s: float = 0.005) -> Dict[str, List[int]]:
        """Pump until every submitted stream is done; returns
        ``{id: tokens}`` (the journals — what the fleet actually
        accepted, not what any one replica believes)."""
        deadline = self.clock() + timeout_s
        while self.pump():
            if self.clock() > deadline:
                open_ids = [
                    s.id for s in self._streams.values() if not s.done
                ]
                raise FleetError(
                    f"fleet did not drain within {timeout_s}s; open "
                    f"streams: {open_ids[:8]}"
                )
            time.sleep(poll_interval_s)
        return self.outputs()

    def outputs(self) -> Dict[str, List[int]]:
        return {s.id: list(s.tokens) for s in self._streams.values()}

    def scaling_signals(self) -> Dict[str, Any]:
        """One snapshot of the demand-vs-capacity picture — the feed the
        tuning driver's ``fleet_replicas`` knob judges against.

        Everything here is already maintained by ``pump()``; this method
        only assembles it (and exports the gauges), so it is safe to
        call at any cadence.  ``queue_depth`` counts streams the router
        has accepted but not finished — the fleet's actual backlog, not
        any one replica's."""
        queue_depth = sum(
            1 for s in self._streams.values() if not s.done
        )
        headroom: Dict[str, int] = {}
        live = admitting = shedding = 0
        backpressure = drain_refusals = 0
        for name, s in self._replicas.items():
            if s.dead or s.left:
                continue
            live += 1
            headroom[name] = s.headroom
            backpressure += s.backpressure
            drain_refusals += s.drain_refusals
            if s.admitting:
                admitting += 1
            if s.shed:
                shedding += 1
        sig = {
            "queue_depth": queue_depth,
            "replicas_total": len(self._replicas),
            "replicas_live": live,
            "replicas_admitting": admitting,
            "replicas_shedding": shedding,
            "backpressure_refusals": backpressure,
            "drain_refusals": drain_refusals,
            "drain_reroutes": self.stats["drain_reroutes"],
            "shed_events": self.stats["shed_events"],
            "requests_lost": self.stats["requests_lost"],
            "headroom": headroom,
            "headroom_total": sum(headroom.values()),
            "headroom_min": min(headroom.values()) if headroom else 0,
        }
        smetrics.FLEET_QUEUE_DEPTH.set(queue_depth)
        smetrics.FLEET_ADMITTING.set(admitting)
        smetrics.FLEET_BACKPRESSURE.set(backpressure)
        for name, free in headroom.items():
            smetrics.FLEET_HEADROOM.set(free, replica=name)
        return sig

    def fleet_stats(self) -> Dict[str, Any]:
        """The ``detail.fleet`` feed: router stats + per-replica rows."""
        total_routed = (
            self.stats["routed_affine"] + self.stats["routed_fallback"]
        )
        per_replica = {}
        for name, s in self._replicas.items():
            per_replica[name] = {
                "tokens_out": s.tokens_out,
                "dead": s.dead,
                "left": s.left,
                "shed_events": s.shed_events,
                "shed_seconds": round(s.shed_seconds, 4),
                "generation": s.generation,
            }
        return {
            **self.stats,
            "affinity_enabled": self.affinity,
            "affinity_hit_rate": (
                round(self.stats["routed_affine"] / total_routed, 4)
                if total_routed else 0.0
            ),
            "replicas": per_replica,
        }
