"""EASGD and GOSGD — the asynchronous training rules.

Reference analogs (SURVEY.md §3.2, §4.3, §4.4):

- ``EASGD_Worker`` / ``EASGD_Server`` (upstream ``easgd_worker.py`` /
  ``easgd_server.py``): a dedicated server rank holds center variables;
  each worker trains τ local iterations then does a serialized pairwise
  elastic exchange — worker ``x_i ← x_i − α(x_i − x̃)``, center
  ``x̃ ← x̃ + α(x_i − x̃)`` (Zhang, Choromanska & LeCun 2015).
- ``GOSGD_Worker`` (upstream ``gosgd_worker.py``): no server; after each
  local step, with probability p a worker pushes ``(params, weight/2)``
  to a random peer and halves its own weight; receivers merge by weight
  (Blot et al. 2016).

TPU-native redesign (SURVEY.md §8.1): each async worker is an
**independent jitted program on its own disjoint device subset** (a
per-worker ``Mesh``), driven by a thread of the single controller; the
server is a host object; exchanges move host pytrees through
``transport.Mailbox``.  Asynchrony semantics (staleness, elastic math,
gossip weights) are preserved exactly at the host level — XLA has no
dynamic p2p, and τ hides host-transfer latency just as it hid MPI latency
in the reference.  Device subsets of size >1 run BSP *within* a worker
(hierarchical: in-graph psum inside, elastic averaging outside).
"""

from __future__ import annotations

import importlib
import os
import threading
from typing import Any, List, Optional

import jax
import numpy as np

from theanompi_tpu import observability as obs
from theanompi_tpu.parallel.transport import Mailbox
from theanompi_tpu.runtime.mesh import make_mesh, replicate
from theanompi_tpu.runtime.recorder import Recorder

Pytree = Any

_REG = obs.get_registry()
_EXCHANGES = _REG.counter(
    "easgd_exchanges_total", "elastic worker<->center exchanges"
)
_PUSHES = _REG.counter("gosgd_pushes_total", "gossip pushes sent")
_MERGES = _REG.counter("gosgd_merges_total", "gossip messages merged in")
_WEIGHT = _REG.gauge(
    "gosgd_consensus_weight", "per-worker gossip consensus weight"
)


def _to_host(tree: Pytree) -> Pytree:
    """Device→host COPY of every leaf.

    ``np.array``, not ``np.asarray``: on CPU ``asarray`` of a jax array
    is a zero-copy VIEW of the device buffer (graftlint GL-D004).  The
    trees this produces cross threads — GOSGD pushes them through the
    in-process Mailbox to peers, EASGD seeds the server's center and
    the epoch-boundary ``host_net_state`` from them — and they are read
    there long after this worker's next jitted step has DONATED (and
    XLA reused) the underlying buffers.  A view would silently read
    reused memory; a copy is immutable history (same contract as
    ``utils.checkpoint.host_snapshot``).
    """
    return jax.tree.map(lambda x: np.array(x), tree)


def _split_devices(devices, n_workers: int):
    per, rem = divmod(len(devices), n_workers)
    if per < 1:
        raise ValueError(
            f"{n_workers} workers need ≥{n_workers} devices, have {len(devices)}"
        )
    # spread the remainder so no chip idles (first `rem` workers get +1)
    out, i = [], 0
    for w in range(n_workers):
        n = per + (1 if w < rem else 0)
        out.append(devices[i : i + n])
        i += n
    return out


class EASGD_Server:
    """Center-variable holder (reference ``EASGD_Server``).

    The reference dedicates an MPI rank + GPU to this; here it is a host
    object whose ``exchange`` serializes workers with a lock exactly as
    the MPI recv-loop serialized them (SURVEY.md §4.3 'serialization
    bottleneck by design').

    ``roster``/``tau_ctrl`` (optional, installed by an adaptive-τ
    driver) give the in-process server the same straggler-adaptive τ
    hints the cross-process ``EasgdServerCore`` serves: exchanges beat
    the roster, ``suggest_tau`` reads the controller.
    """

    def __init__(self, center: Pytree, alpha: float,
                 roster=None, tau_ctrl=None):
        self.center = center
        self.alpha = alpha
        self._lock = threading.Lock()
        self.n_exchanges = 0
        self.roster = roster
        self.tau_ctrl = tau_ctrl

    def exchange(self, worker_params: Pytree, rank=None, step=None) -> Pytree:
        a = self.alpha
        with self._lock:
            if self.roster is not None and rank is not None:
                if not self.roster.beat(rank, step):
                    self.roster.join(rank)
                    self.roster.beat(rank, step)
            diff = jax.tree.map(lambda w, c: w - c, worker_params, self.center)
            self.center = jax.tree.map(
                lambda c, d: c + a * d, self.center, diff
            )
            self.n_exchanges += 1
            _EXCHANGES.inc()
            return jax.tree.map(lambda w, d: w - a * d, worker_params, diff)

    def suggest_tau(self, rank=None, default=None):
        if self.tau_ctrl is None or rank is None:
            return default
        return self.tau_ctrl.tau_for(rank)


class _AsyncWorkerBase:
    """Common thread body: local model + train loop + exchange hook."""

    def __init__(self, rank, devices, modelfile, modelclass, model_config, n_epochs,
                 recorder: Recorder, n_workers: Optional[int] = None):
        self.rank = rank
        self.devices = devices
        self.recorder = recorder
        # stall watchdog slot, assigned by the owning driver/entrypoint
        # after construction (the threaded driver shares ONE across
        # workers — any worker's progress ticks it, detecting whole-job
        # hangs; the per-process entrypoints assign one each)
        self.watchdog = None
        # fault-injection slot (runtime.fault.FaultInjector) — the
        # chaos drills' hook; ``fault_rank`` is the rank the PLAN
        # addresses (global process rank for the distributed
        # entrypoints, which differs from the EASGD data-shard index)
        self.fault = None
        self.fault_rank = rank
        cfg = dict(model_config or {})
        cls = getattr(importlib.import_module(modelfile), modelclass)
        self.model = cls(
            config=cfg, mesh=cls.build_mesh(devices=devices, config=cfg)
        )
        # Disjoint per-worker example streams (reference: per-rank batch
        # division, SURVEY.md §3.6). All workers share the dataset and the
        # epoch-seeded permutation; each takes its rank::n slice — real
        # data diversity, not just a shifted seed (round-1 VERDICT bug:
        # identical streams across async workers on real datasets).
        # Custom duck-typed providers without shard_for_worker keep
        # working via the old behavior — rebuild the model with a
        # per-rank seed shift — loudly, since on a real dataset a seed
        # shift alone does NOT diversify the stream.
        if n_workers and n_workers > 1:
            shard = getattr(self.model.data, "shard_for_worker", None)
            if shard is not None:
                shard(rank, n_workers)
            else:
                import warnings

                warnings.warn(
                    f"{type(self.model.data).__name__} lacks shard_for_worker; "
                    f"falling back to a per-rank seed shift. If the provider "
                    f"ignores its seed (real on-disk data), all async workers "
                    f"will train on the SAME batch stream — implement "
                    f"shard_for_worker(rank, n_workers) to fix this",
                    RuntimeWarning,
                    stacklevel=2,
                )
                cfg["seed"] = int(cfg.get("seed", 0)) + rank
                self.model = cls(
                    config=cfg, mesh=cls.build_mesh(devices=devices, config=cfg)
                )
        # per-worker rng stream (dropout masks, device aug) — data order
        # is handled by sharding above, but the in-step rng must differ
        # per worker too or single-device workers draw identical masks
        self.model.rng = jax.random.fold_in(self.model.rng, rank)
        if n_epochs is not None:
            self.model.n_epochs = n_epochs
        self.error: Optional[BaseException] = None
        # host-side snapshot of BN/running state taken by the worker
        # thread at each epoch boundary: the server's center validation
        # reads THIS, never the live training state (whose buffers the
        # donating jitted step invalidates concurrently)
        self.host_net_state: Optional[Pytree] = None
        # driver-installed hooks (epoch-completion protocol: the EASGD
        # server thread validates/saves the center once all live workers
        # pass an epoch boundary — reference server duties, SURVEY.md §4.3)
        self.on_epoch_end = None  # fn(rank, epoch)
        self.on_exit = None  # fn(rank)

    def set_params(self, host_params: Pytree) -> None:
        self.model.params = replicate(self.model.mesh, host_params)

    def get_params(self) -> Pytree:
        return _to_host(self.model.params)

    def run(self):
        try:
            self._run()
        except BaseException as e:  # joined + re-raised by the driver
            self.error = e
            # the driver re-raises this LATER, after every thread
            # joins — by then this thread's live state is gone, so the
            # flight recorder dumps the post-mortem NOW (recent spans/
            # events per thread + all-thread stacks); diagnostics must
            # never mask the original failure
            try:
                obs.get_flight_recorder().dump(
                    reason=f"{type(self).__name__} rank {self.rank} "
                    "raised",
                    exc=e,
                )
            except Exception as de:
                print(
                    f"flight dump failed for worker {self.rank}: "
                    f"{type(de).__name__}: {de}",
                    flush=True,
                )
        finally:
            if self.on_exit is not None:
                self.on_exit(self.rank)

    def _epoch_end(self, epoch: int) -> None:
        self.model.current_epoch = epoch + 1
        if self.on_epoch_end is not None:
            # worker thread owns the state between steps — snapshot here,
            # so the server thread never touches donated buffers
            self.host_net_state = _to_host(self.model.net_state)
            self.on_epoch_end(self.rank, epoch)

    def _run(self):
        raise NotImplementedError


class EASGD_Worker(_AsyncWorkerBase):
    def __init__(self, *args, server: EASGD_Server, tau: int,
                 adaptive_tau: bool = False, **kw):
        super().__init__(*args, **kw)
        self.server = server
        self.tau = tau
        self.adaptive_tau = adaptive_tau
        # degraded mode (docs/elasticity.md): an unreachable server
        # turns exchanges into counted local SGD steps — never an
        # exception into this loop.  The proxy's bounded retry already
        # ran by the time we count a failure here.
        self._degraded = False
        self.n_degraded_steps = 0
        self.n_exchange_failures = 0

    def _exchange(self, count: int) -> None:
        """One elastic exchange, failure-isolated.  A server that is
        down (or evicting/re-admitting us) costs a counted failure and
        flips this worker into degraded local-SGD mode; the next τ
        boundary retries, and a ``readmitted`` reply hands back the
        center (the proxy resets the EF residuals) so recovery needs no
        checkpoint."""
        rec = self.recorder
        try:
            # step-tagged exchange leg: the span carries the iteration
            # count, so one parameter exchange is traceable end-to-end
            # (this span ⊃ the transport's tcp_request/tcp_send spans ⊃
            # the flow arrow) and the trace doctor can attribute comm
            # time to steps
            with obs.span("easgd_exchange", step=count, tau=self.tau):
                rec.start("comm")
                try:
                    new_w = self.server.exchange(
                        self.get_params(), rank=self.rank, step=count
                    )
                finally:
                    rec.end("comm")
            self.set_params(new_w)
        except (ConnectionError, OSError, TimeoutError) as e:
            self.n_exchange_failures += 1
            if not self._degraded:
                self._degraded = True
                print(
                    f"EASGD worker {self.rank}: exchange failed "
                    f"({type(e).__name__}: {e}) — degrading to local "
                    "SGD until the server returns",
                    flush=True,
                )
            return
        if self._degraded:
            self._degraded = False
            print(
                f"EASGD worker {self.rank}: server reachable again — "
                "elastic exchanges resumed",
                flush=True,
            )
        if self.adaptive_tau:
            hint = self.server.suggest_tau(self.rank, self.tau)
            if hint:
                self.tau = max(1, int(hint))

    def _run(self):
        model, rec = self.model, self.recorder
        model.compile_train()
        count = model.current_epoch * model.data.n_batch_train
        since_exchange = 0
        for epoch in range(model.current_epoch, model.n_epochs):
            model.adjust_hyperp(epoch)
            model.reset_train_iter(epoch)
            for _ in range(model.data.n_batch_train):
                count += 1
                if self.fault is not None:
                    self.fault.maybe_fail(self.fault_rank, count)
                model.train_iter(count, rec)
                rec.print_train_info(count)
                if self.watchdog is not None:
                    self.watchdog.tick()
                if self._degraded:
                    self.n_degraded_steps += 1
                    from theanompi_tpu.parallel import membership as _ms

                    _ms.count_degraded_step("easgd", self.rank)
                since_exchange += 1
                if since_exchange >= self.tau:
                    since_exchange = 0
                    self._exchange(count)
            self._epoch_end(epoch)


class GOSGD_Worker(_AsyncWorkerBase):
    def __init__(self, *args, mailbox: Mailbox, p_push: float, rng: np.random.RandomState, **kw):
        super().__init__(*args, **kw)
        self.mailbox = mailbox
        self.p_push = p_push
        self.weight = 1.0 / mailbox.n_ranks  # gossip consensus weights
        self._np_rng = rng
        self.n_pushes = 0  # observability: tests/operators can assert
        self.n_merges = 0  # gossip actually happened
        self.n_push_failures = 0  # pushes rolled back (peer unreachable)

    def _membership_duties(self, step: Optional[int] = None):
        """Elastic-membership housekeeping piggybacked on the merge
        cadence (every hook is duck-typed: the in-process Mailbox has
        none of them and behaves exactly as before):

        - ``sweep`` evicts silent peers from the push table,
        - ``maybe_hello`` beacons our own liveness (a low-``p_push``
          peer must not look dead between lucky pushes),
        - queued snapshot requests from (re)joining peers are granted
          as directed, mass-conserving pushes.
        """
        mb = self.mailbox
        sweep = getattr(mb, "sweep", None)
        if sweep is not None:
            sweep()
        hello = getattr(mb, "maybe_hello", None)
        if hello is not None:
            hello(step)
        take = getattr(mb, "take_snapshot_requests", None)
        if take is not None:
            for dst in take():
                if self.weight <= 0.0:
                    break  # nothing to donate; another peer will grant
                print(
                    f"GOSGD worker {self.rank}: granting snapshot to "
                    f"(re)joining peer {dst}",
                    flush=True,
                )
                self._push_to(int(dst), step=step)

    def _merge_inbox(self, step: Optional[int] = None):
        # drain BEFORE the membership sweep: beats are recorded at
        # drain time, so judging silence first would misattribute THIS
        # worker's own stall (compile, slow merge) to its peers and
        # evict ranks whose frames were sitting in the queue
        msgs = self.mailbox.drain(self.rank)
        self._membership_duties(step)
        # cross-process transports expose reclaim_expired (app-level ack
        # protocol, distributed_async._GossipAdapter): weight whose push
        # was never acked folds back into this worker so a dead receiver
        # can't silently shrink total consensus mass.  The in-process
        # Mailbox is a lossless queue and has no such hook.
        reclaim = getattr(self.mailbox, "reclaim_expired", None)
        if reclaim is not None:
            restored = reclaim()
            if restored:
                self.weight += restored
        if not msgs:
            return
        # step-tagged merge leg (see easgd_exchange): the step number
        # connects a merged gossip frame's flow arrow to the iteration
        # that consumed it (None on the post-training settle drains)
        with obs.span("gosgd_merge", step=step, n_msgs=len(msgs)):
            self.recorder.start("comm")
            w_i = self.get_params()
            a_i = self.weight
            for (w_j, a_j) in msgs:
                tot = a_i + a_j
                w_i = jax.tree.map(
                    lambda wi, wj: (a_i * wi + a_j * wj) / tot, w_i, w_j
                )
                a_i = tot
            self.weight = a_i
            self.set_params(w_i)
            self.n_merges += len(msgs)
            _MERGES.inc(len(msgs), rank=str(self.rank))
            _WEIGHT.set(self.weight, rank=str(self.rank))
            self.recorder.end("comm")

    def _pick_peer(self) -> Optional[int]:
        """Push destination: uniform over all other ranks (the
        reference behavior) unless the mailbox keeps a live peer table
        — then only KNOWN-LIVE peers are candidates (a dead or not-yet-
        joined rank is never a push target, so membership churn stops
        costing failed-send weight restores), weighted away from
        stragglers (``peer_weights``)."""
        live = getattr(self.mailbox, "live_peers", None)
        if live is None:
            peers = [r for r in range(self.mailbox.n_ranks) if r != self.rank]
            return int(self._np_rng.choice(peers)) if peers else None
        peers = [r for r in live() if r != self.rank]
        if not peers:
            return None  # nobody known-alive yet (joiner warming up)
        weigh = getattr(self.mailbox, "peer_weights", None)
        if weigh is None:
            return int(self._np_rng.choice(peers))
        w = np.asarray(weigh(peers), dtype=np.float64)
        tot = float(w.sum())
        if tot <= 0:
            return int(self._np_rng.choice(peers))
        return int(self._np_rng.choice(peers, p=w / tot))

    def _push_to(self, dst: int, step: Optional[int] = None) -> None:
        """One directed gossip push (half this worker's mass to
        ``dst``) — the regular random push AND the snapshot grant a
        (re)joining peer pulls its state through."""
        self.recorder.start("comm")
        self.weight /= 2.0
        try:
            # step-tagged push leg: this span ⊃ the mailbox's send span
            # ⊃ the flow-begin, so the arrow's tail is attributable to
            # the iteration that pushed
            with obs.span("gosgd_push", step=step, dst=dst):
                self.mailbox.send(dst, (self.get_params(), self.weight))
            self.n_pushes += 1
            _PUSHES.inc(rank=str(self.rank))
            _WEIGHT.set(self.weight, rank=str(self.rank))
        except (ConnectionError, OSError):
            # peer unreachable (cross-process: exited/crashed) — undo
            # the halving so the consensus weight mass isn't lost, and
            # keep training: gossip tolerates dead peers by design
            self.weight *= 2.0
            self.n_push_failures += 1
            print(f"GOSGD worker {self.rank}: push to {dst} failed "
                  f"(peer gone); weight restored", flush=True)
        finally:
            self.recorder.end("comm")

    def _maybe_push(self, step: Optional[int] = None):
        if self._np_rng.rand() >= self.p_push or self.mailbox.n_ranks < 2:
            return
        dst = self._pick_peer()
        if dst is None:
            return
        self._push_to(dst, step=step)

    def _run(self):
        model, rec = self.model, self.recorder
        model.compile_train()
        count = model.current_epoch * model.data.n_batch_train
        for epoch in range(model.current_epoch, model.n_epochs):
            model.adjust_hyperp(epoch)
            model.reset_train_iter(epoch)
            for _ in range(model.data.n_batch_train):
                count += 1
                if self.fault is not None:
                    self.fault.maybe_fail(self.fault_rank, count)
                model.train_iter(count, rec)
                rec.print_train_info(count)
                if self.watchdog is not None:
                    self.watchdog.tick()
                self._merge_inbox(step=count)
                self._maybe_push(step=count)
            self._epoch_end(epoch)
        # final drain so in-flight pushes aren't lost at shutdown
        self._merge_inbox()


def coalesce_duties_window(epoch, n_epochs, need, enabled):
    """``(newest, skipped)``: the newest fully-completed epoch server
    duties should service, plus the 0-based boundaries coalesced past to
    reach it.  Shared by the threaded EASGD driver and the
    multi-process server (distributed_async.run_easgd_server) so the
    two sibling implementations cannot drift."""
    newest = epoch
    while enabled and newest + 1 < n_epochs and need(newest + 1):
        newest += 1
    return newest, list(range(epoch, newest))


def duties_val_due(val_freq, newest, skipped):
    """A validation is due if the serviced boundary OR any boundary
    coalesced past was val_freq-aligned — coalescing must never
    silently drop a due validation."""
    return bool(val_freq) and any(
        (e + 1) % val_freq == 0 for e in list(skipped) + [newest]
    )


def duties_provenance(newest, skipped, n_exchanges):
    """The center-val row's provenance stamp (VERDICT r3 #1): with
    these fields a frozen curve is self-diagnosing — identical costs
    with growing n_exchanges mean a real exchange bug; identical costs
    with frozen n_exchanges mean the validations outlived the workers.
    All epoch numbers are 1-based, matching the row's ``epoch``."""
    import time as _time

    return {
        "epoch": newest + 1,
        "n_exchanges": n_exchanges,
        "t_wall": round(_time.time(), 3),
        **(
            {"coalesced_epochs": [e + 1 for e in skipped]}
            if skipped
            else {}
        ),
    }


class _AsyncDriverBase:
    """Spawns worker threads over disjoint device subsets and joins them."""

    def __init__(
        self,
        modelfile: str,
        modelclass: str,
        model_config: Optional[dict],
        devices,
        n_workers: Optional[int] = None,
        n_epochs: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        verbose: bool = True,
        val_freq: int = 1,  # 0 = skip final validation of the result model
        tensorboard_dir: Optional[str] = None,  # rank-0 TB mirror
        keep_last: Optional[int] = None,  # EASGD: prune per-epoch center
        # snapshots to the newest N (None = keep all). No-op for GOSGD,
        # which only writes one final consensus file.
        watchdog_timeout: Optional[float] = None,  # shared job-stall
        # watchdog: fires when NO worker completes an iteration within
        # the timeout (whole-job hang, e.g. a device that stopped
        # answering); armed at the first completed iteration so per-thread
        # compiles never count
        watchdog_action: str = "dump",
    ):
        from theanompi_tpu.runtime.fault import Watchdog

        Watchdog.validate_action(watchdog_action)
        self.modelfile = modelfile
        self.modelclass = modelclass
        self.model_config = model_config
        self.devices = list(devices)
        self.n_workers = n_workers or len(self.devices)
        self.n_epochs = n_epochs
        self.checkpoint_dir = checkpoint_dir
        self.verbose = verbose
        self.val_freq = val_freq
        self.tensorboard_dir = tensorboard_dir
        self.keep_last = keep_last
        self._watchdog_cfg = (
            (float(watchdog_timeout), watchdog_action)
            if watchdog_timeout
            else None
        )
        self._wd = None
        self._telemetry = None
        self.workers: List[_AsyncWorkerBase] = []
        self.result_model = None

    def _make_recorder(self, rank):
        pf = int((self.model_config or {}).get("print_freq", 40))
        return Recorder(
            print_freq=pf,
            rank=rank,
            verbose=self.verbose and rank == 0,
            save_dir=self.checkpoint_dir,
            tensorboard_dir=self.tensorboard_dir if rank == 0 else None,
        )

    def _build_workers(self):
        raise NotImplementedError

    def _finalize(self):
        raise NotImplementedError

    def _start_aux(self):
        """Hook: driver-side background duties (EASGD server thread)."""

    def _stop_aux(self):
        """Hook: join background duties after workers exit."""

    def run(self):
        # live telemetry (observability/live.py): the threaded drivers
        # are one process sharing one tracer, so ONE shipper covers
        # every worker thread (per-thread tracks ride the span digests).
        # Inert unless THEANOMPI_LIVE=1 / THEANOMPI_LIVE_AGG is set
        # (AGG accepts "host:port,host:port" — the HA aggregator
        # ladder; ship failover is counted, never raised into workers).
        from theanompi_tpu.observability import live as obs_live

        self._telemetry = obs_live.maybe_start_from_env(
            f"{type(self).__name__.replace('_Driver', '').lower()}_driver"
        )
        self._build_workers()
        if self._watchdog_cfg is not None:
            from theanompi_tpu.runtime.fault import Watchdog

            timeout, action = self._watchdog_cfg
            self._wd = Watchdog.maybe(timeout, action)
            for w in self.workers:
                w.watchdog = self._wd
        try:
            threads = [
                threading.Thread(target=w.run, name=f"{type(w).__name__}-{w.rank}")
                for w in self.workers
            ]
            self._start_aux()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            # reap even when start/join raises (Ctrl-C in a notebook):
            # a leaked exit-mode watchdog would kill the process later.
            # The consensus/validation tail below is not
            # iteration-cadenced, so the success path reaps here too.
            if self._wd is not None:
                self._wd.close()
                self._wd = None
        self._stop_aux()
        try:
            errs = [w.error for w in self.workers if w.error is not None]
            if errs:
                raise errs[0]
            self._finalize()
            if self.val_freq and self.result_model is not None:
                # validate the consensus/center model (reference: the EASGD
                # server owns validation of the center params; SURVEY.md §4.3)
                rec = self.workers[0].recorder
                self.result_model.run_validation(0, rec)
            if self.checkpoint_dir:
                for w in self.workers:
                    w.recorder.save()
        finally:
            # release TB writers even when a worker raised — an unclosed
            # SummaryWriter loses its last flush window and leaks its
            # daemon thread in the still-running process
            for w in self.workers:
                w.recorder.close()
            srv_rec = getattr(self, "server_recorder", None)
            if srv_rec is not None:
                srv_rec.close()
            if self._telemetry is not None:
                try:
                    summary = self._telemetry.stop()
                    alerts = summary.get("alerts_total")
                    if alerts is not None and self.verbose:
                        print(
                            f"[live] {summary.get('windows', 0)} "
                            f"window(s), {alerts} watchdog alert(s)",
                            flush=True,
                        )
                except Exception as te:  # telemetry never masks the run
                    print(
                        f"telemetry stop failed: "
                        f"{type(te).__name__}: {te}",
                        flush=True,
                    )


class EASGD_Driver(_AsyncDriverBase):
    """Server + N elastic-averaging workers (reference ``async_rule.EASGD``
    spawning N workers + 1 server rank; SURVEY.md §3.1).

    The server's *in-training* duties match the reference
    ``easgd_server.py`` loop (SURVEY.md §4.3): when every live worker
    passes an epoch boundary, the server validates the CENTER params,
    checkpoints them (``ckpt_center_{epoch:04d}.npz``), and records the
    result — so a long run produces mid-run signal and mid-run restart
    points of the model that matters.  ``resume=True`` restarts from the
    latest center checkpoint.  (lr scheduling stays in the workers'
    ``adjust_hyperp`` — our schedule is epoch-deterministic, so the
    reference's server-pushed lr adjustments need no central authority.)
    """

    def __init__(self, *args, tau: int = 10, alpha: float = 0.5,
                 resume: bool = False, duties_coalesce: bool = True,
                 adaptive_tau: bool = False, **kw):
        super().__init__(*args, **kw)
        self.tau = tau
        self.alpha = alpha
        self.resume = resume
        # straggler-adaptive per-worker tau (membership.TauController):
        # exchange wall cadence equalized across unequal device subsets
        self.adaptive_tau = adaptive_tau
        # True (default): duties jump to the newest completed epoch when
        # validation is slower than training, so every recorded center
        # row is fresh (see _server_duties).  False: strictly one
        # validate+checkpoint per epoch boundary — deterministic row
        # count, at the cost of re-validating a finished center when
        # workers outpace the duties thread.
        self.duties_coalesce = duties_coalesce
        self.server: Optional[EASGD_Server] = None
        self.server_recorder: Optional[Recorder] = None
        self.start_epoch = 0
        self._cv = threading.Condition()
        self._epoch_counts: dict = {}
        self._n_running = 0
        self._n_failed = 0  # workers that exited WITH an error: they will
        # never report further epoch boundaries, so the duties predicate
        # must stop expecting them — but a worker that finished normally
        # already reported every epoch and keeps counting toward it
        self._duties_thread: Optional[threading.Thread] = None

    def _build_workers(self):
        groups = _split_devices(self.devices, self.n_workers)
        self.workers = [
            EASGD_Worker(
                rank,
                groups[rank],
                self.modelfile,
                self.modelclass,
                self.model_config,
                self.n_epochs,
                self._make_recorder(rank),
                n_workers=self.n_workers,
                server=None,  # set below once center exists
                tau=self.tau,
                adaptive_tau=self.adaptive_tau,
            )
            for rank in range(self.n_workers)
        ]
        # center = worker 0's init (reference: server rank initializes and
        # broadcasts); all workers start at the center
        center = self.workers[0].get_params()
        if self.resume and self.checkpoint_dir:
            from theanompi_tpu.utils import checkpoint as ckpt

            path = ckpt.latest(self.checkpoint_dir, prefix="ckpt_center_")
            if path:
                blob = ckpt.restore(path)
                center = blob["params"]
                self.start_epoch = int(blob["epoch"])
                print(f"EASGD: resumed center from {path} "
                      f"at epoch {self.start_epoch}", flush=True)
        if self.adaptive_tau:
            from theanompi_tpu.parallel import membership as _ms

            roster = _ms.Roster("easgd", evict_after_s=float("inf"))
            self.server = EASGD_Server(
                center, self.alpha, roster=roster,
                tau_ctrl=_ms.TauController(self.tau, roster),
            )
        else:
            self.server = EASGD_Server(center, self.alpha)
        self.server_recorder = Recorder(
            print_freq=1, rank=0, verbose=self.verbose,
            save_dir=self.checkpoint_dir,
            # the center's per-epoch validation curve is THE metric of
            # an EASGD run — mirror it under its own TB run dir
            tensorboard_dir=(
                os.path.join(self.tensorboard_dir, "center")
                if self.tensorboard_dir
                else None
            ),
        )
        for w in self.workers:
            w.server = self.server
            w.set_params(center)
            w.model.current_epoch = self.start_epoch
            w.on_epoch_end = self._epoch_done
            w.on_exit = self._worker_exit
        if self.val_freq:
            # compile the center-validation fn BEFORE training starts:
            # compile_val's state placement must not run concurrently
            # with the donating train step
            self.workers[0].model.compile_val()

    # --- epoch-completion protocol (worker threads → server thread) ----
    def _epoch_done(self, rank: int, epoch: int) -> None:
        with self._cv:
            self._epoch_counts[epoch] = self._epoch_counts.get(epoch, 0) + 1
            self._cv.notify_all()

    def _worker_exit(self, rank: int) -> None:
        with self._cv:
            self._n_running -= 1
            if self.workers[rank].error is not None:
                self._n_failed += 1
            self._cv.notify_all()

    def _start_aux(self):
        self._n_running = len(self.workers)
        self._duties_thread = threading.Thread(
            target=self._server_duties, name="EASGD-server", daemon=True
        )
        self._duties_thread.start()

    def _stop_aux(self):
        if self._duties_thread is not None:
            self._duties_thread.join(timeout=600)

    def _server_duties(self):
        """Reference ``EASGD_Server.run()`` periodic branch: validate +
        checkpoint the center at epoch boundaries.

        Duties COALESCE lagging epochs (VERDICT r3 #1): a full-set
        validation can take longer than a worker epoch, and validating
        every boundary sequentially lets workers finish the whole run
        while the duties thread grinds through a backlog — the committed
        round-3 curve's last 6 rows were 6 re-validations of the SAME
        final center, which demonstrated nothing about elastic dynamics.
        Instead, after epoch ``e`` completes, duties jump to the NEWEST
        fully-completed epoch: every validated row then reflects a fresh
        center (exchanges happened since the previous row), and the
        skipped boundaries are recorded on the row itself."""
        n_epochs = self.workers[0].model.n_epochs
        epoch = self.start_epoch
        while epoch < n_epochs:
            with self._cv:
                # every worker that has not FAILED must report epoch
                # `epoch` before center duties run — a fast worker that
                # exited normally already reported all its epochs, so it
                # keeps counting toward the expectation (a predicate on
                # `_n_running` alone would fire epochs early once any
                # worker finishes, checkpointing centers the slow
                # workers never trained toward)
                need = lambda e: (self._epoch_counts.get(e, 0)
                                  >= len(self.workers) - self._n_failed)
                self._cv.wait_for(lambda: need(epoch))
                if self._epoch_counts.get(epoch, 0) == 0:
                    return  # every worker failed before this boundary
                newest, skipped = coalesce_duties_window(
                    epoch, n_epochs, need, self.duties_coalesce
                )
            try:
                self._center_duties(newest, skipped=skipped)
            except Exception as e:  # duties must never kill training
                print(f"EASGD server duties failed at epoch {newest}: "
                      f"{type(e).__name__}: {e}", flush=True)
            epoch = newest + 1

    def _center_duties(self, epoch: int, skipped=()) -> None:
        m = self.workers[0].model
        with self.server._lock:
            center = jax.tree.map(np.copy, self.server.center)
            # snapshot atomically with the center: the row must say how
            # many elastic exchanges produced EXACTLY these params
            n_exchanges = self.server.n_exchanges
        if self.checkpoint_dir:
            from theanompi_tpu.utils import checkpoint as ckpt

            ckpt.save(
                os.path.join(
                    self.checkpoint_dir, f"ckpt_center_{epoch + 1:04d}.npz"
                ),
                {"params": center, "epoch": epoch + 1, "alpha": self.alpha,
                 "tau": self.tau},
            )
            if self.keep_last:
                ckpt.prune(
                    self.checkpoint_dir, self.keep_last,
                    prefix="ckpt_center_",
                )
        if duties_val_due(self.val_freq, epoch, skipped):
            w0 = self.workers[0]
            loss, err, _ = m.run_validation(
                (epoch + 1) * m.data.n_batch_train,
                self.server_recorder,
                params=replicate(m.mesh, center),
                # epoch-boundary snapshot taken by the worker thread —
                # never the live (donation-churned) training state
                net_state=w0.host_net_state
                if w0.host_net_state is not None
                else _to_host(m.net_state),
                extra=duties_provenance(epoch, skipped, n_exchanges),
            )
            if self.verbose:
                print(
                    f"[EASGD center] epoch {epoch}: val cost {loss:.4f} "
                    f"err {err:.4f} (n_exchanges {n_exchanges})", flush=True,
                )

    def _finalize(self):
        # the server owns the final model (reference: server saves center)
        self.result_model = self.workers[0].model
        self.result_model.params = replicate(
            self.result_model.mesh, self.server.center
        )
        if self.checkpoint_dir:
            path = os.path.join(self.checkpoint_dir, "ckpt_center.npz")
            self.result_model.save_model(path)
            if self.server_recorder is not None:
                self.server_recorder.save(
                    os.path.join(self.checkpoint_dir, "record_server.jsonl")
                )


class GOSGD_Driver(_AsyncDriverBase):
    """N gossip workers over a shared mailbox (reference
    ``async_rule.GOSGD``)."""

    def __init__(self, *args, p_push: float = 0.25, **kw):
        super().__init__(*args, **kw)
        self.p_push = p_push

    def _build_workers(self):
        groups = _split_devices(self.devices, self.n_workers)
        mailbox = self.mailbox = Mailbox(self.n_workers)
        seed0 = int((self.model_config or {}).get("seed", 0))
        self.workers = [
            GOSGD_Worker(
                rank,
                groups[rank],
                self.modelfile,
                self.modelclass,
                self.model_config,
                self.n_epochs,
                self._make_recorder(rank),
                n_workers=self.n_workers,
                mailbox=mailbox,
                p_push=self.p_push,
                rng=np.random.RandomState(10_000 + seed0 + rank),
            )
            for rank in range(self.n_workers)
        ]
        # common init point (reference workers all load the same init)
        w0 = self.workers[0].get_params()
        for w in self.workers[1:]:
            w.set_params(w0)

    def _finalize(self):
        # drain pushes still in flight when their target exited (a worker's
        # final drain races with peers' last sends) — without this, their
        # weight mass is lost and the consensus denominator drifts from 1
        for w in self.workers:
            for (w_j, a_j) in self.mailbox.drain(w.rank):
                w_i, a_i = w.get_params(), w.weight
                tot = a_i + a_j
                merged = jax.tree.map(
                    lambda wi, wj: (a_i * wi + a_j * wj) / tot, w_i, w_j
                )
                w.weight = tot
                w.set_params(merged)
        # gossip consensus: weighted average of worker params
        tot = sum(w.weight for w in self.workers)
        acc = None
        for w in self.workers:
            part = jax.tree.map(
                lambda x: np.asarray(x) * (w.weight / tot), w.model.params
            )
            acc = part if acc is None else jax.tree.map(np.add, acc, part)
        self.result_model = self.workers[0].model
        self.result_model.params = replicate(self.result_model.mesh, acc)
        if self.checkpoint_dir:
            path = os.path.join(self.checkpoint_dir, "ckpt_consensus.npz")
            self.result_model.save_model(path)
