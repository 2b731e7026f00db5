"""Cross-process EASGD / GOSGD — async rules over the TCP transport.

Reference analog (SURVEY.md §4.3/§4.4, §8.1): upstream
``easgd_server.py`` is a dedicated MPI rank serving elastic exchanges
one worker at a time, and ``gosgd_worker.py`` pushes (params, weight) to
random peers over MPI p2p.  Here each rank is an OS process driving its
own local devices; exchanges ride ``transport.TcpMailbox`` /
``TcpServerChannel`` (host RPC + device_put — XLA has no dynamic p2p).
The in-process worker classes are reused verbatim: a worker cannot tell
whether ``server.exchange`` crosses a thread or a datacenter.

Topology (matches the reference):

- EASGD: rank 0 = server process (owns the center, validates and
  checkpoints it per epoch, serves ``join``/``exchange``/``epoch``/
  ``done`` requests serialized); ranks 1..N-1 = workers.
- GOSGD: every rank is a peer worker; rank 0 additionally collects the
  final (params, weight) pairs and writes the consensus checkpoint.

**Elastic membership** (docs/elasticity.md): both planes keep a live
roster (``parallel/membership.py``).  EASGD workers register on
``join``, heartbeat implicitly through every exchange/epoch frame, and
are EVICTED after ``evict_after_s`` of silence — eviction frees the
server's per-worker reply-leg EF residual and stops the epoch/done
predicates waiting on the dead rank.  A (re)joining worker is
re-admitted CHECKPOINTLESSLY: its first exchange after eviction gets
the center back (never folded with its stale params) under a bumped
generation, and both sides reset their compression residuals.  GOSGD
peers gossip ``hello``/``bye`` beacons beside the mass frames; silent
peers drop out of everyone's push tables, and a rejoining peer pulls a
peer snapshot as directed, mass-conserving pushes.  Worker-side, every
exchange leg runs under bounded retry with jittered backoff and
degrades to counted local SGD steps — membership failures never raise
into a surviving worker's train loop.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from theanompi_tpu.parallel import membership as ms
from theanompi_tpu.parallel.async_workers import (
    EASGD_Worker,
    GOSGD_Worker,
    _to_host,
    coalesce_duties_window,
    duties_provenance,
    duties_val_due,
)
from theanompi_tpu.parallel.transport import (
    TcpMailbox,
    TcpServerChannel,
    request,
)
from theanompi_tpu.runtime.mesh import replicate
from theanompi_tpu.runtime.recorder import Recorder

Address = Tuple[str, int]


def default_addresses(n: int, hosts: Optional[Sequence[str]], port_base: int) -> List[Address]:
    """Rank r listens on (hosts[r], port_base + r); single-host default."""
    if hosts is None or len(hosts) == 0:
        hosts = ["127.0.0.1"]
    if len(hosts) == 1:
        hosts = [hosts[0]] * n
    if len(hosts) != n:
        raise ValueError(f"{len(hosts)} hosts for {n} ranks")
    return [(hosts[r], port_base + r) for r in range(n)]


def _cast_wire(tree: Any, dtype) -> Any:
    """Cast fp32 array leaves to ``dtype`` (everything else untouched) —
    the compressed-wire half of the reference's fp16 exchange story
    (SURVEY.md §3.3 ``Exch_asa16``) applied to the async TCP path: the
    parameter payload is ~2× fewer bytes per exchange, and quantization
    noise rides the same channel asynchrony already makes noisy."""
    def leaf(a):
        if isinstance(a, np.ndarray) and a.dtype == np.float32:
            return a.astype(dtype)
        return a

    return jax.tree.map(leaf, tree)


def _uncast_wire(tree: Any) -> Any:
    """fp16 leaves back to fp32 after decode (training math never runs
    in the wire dtype)."""
    def leaf(a):
        if isinstance(a, np.ndarray) and a.dtype == np.float16:
            return a.astype(np.float32)
        return a

    return jax.tree.map(leaf, tree)


def _pack_wire(tree: Any, mode, residual: Any = None):
    """One compressed-wire entry point for every async TCP leg:
    ``mode`` is ``None`` (fp32), a numpy dtype (the cast wire above),
    or ``'q8'`` — int8 + per-block fp32 scales via ``wire.q8_pack``
    (~4× fewer frame bytes than fp32, the same block recipe as the
    BSP exchanger's in-graph wire).  Returns ``(packed,
    new_residual)``; only the q8 wire produces a residual (EF on the
    push leg — pass it back in on the next send of the same payload)."""
    if mode is None:
        return tree, None
    if mode == "q8":
        from theanompi_tpu.parallel import wire

        return wire.q8_pack(tree, residual)
    return _cast_wire(tree, mode), None


def _unpack_wire(tree: Any) -> Any:
    """Receiver side, mode-agnostic by design: undo q8 packing AND the
    fp16 cast (both self-describing), so a mixed fleet — or a sender
    whose compression config differs — still decodes correctly."""
    from theanompi_tpu.parallel import wire

    return _uncast_wire(wire.q8_unpack(tree))


class _RemoteServer:
    """Client proxy with the in-process EASGD_Server's exchange surface.

    ``wire_dtype`` (``np.float16`` or ``'q8'``) compresses the
    parameter payload both ways; elastic math always runs fp32 at the
    server.  The q8 wire keeps the EF residual on the PUSH leg: what
    one exchange's quantization dropped is re-sent with the next, so
    the center integrates the true worker trajectory.  (The reply leg
    is EF'd server-side per worker — the membership roster is exactly
    the per-worker state that used to be missing.)

    Every exchange runs under a bounded retry budget with jittered
    backoff (``retries``/``timeout_s``); the final failure re-raises so
    the worker can degrade to local SGD — never die.  A reply flagged
    ``readmitted`` means the server evicted this worker's previous
    incarnation: the proxy resets its push-leg EF residual (stale error
    feedback must not be replayed into a fresh connection) and hands
    the worker the CENTER to pull — checkpointless recovery."""

    def __init__(self, address: Address, wire_dtype=None,
                 rank: Optional[int] = None,
                 retries: int = 2, timeout_s: float = 120.0):
        self.address = address
        self.wire_dtype = wire_dtype
        self.rank = rank
        self.retries = int(retries)
        self.timeout_s = float(timeout_s)
        self._residual = None  # q8 push-leg EF state
        self._last_tau: Optional[int] = None
        self.readmissions = 0
        self.generation: Optional[int] = None

    def join(self, rank: Optional[int] = None):
        # a long connect ladder for THIS request only: ranks start
        # together and the server builds its model before it listens,
        # so a worker that is ready first meets a refused connection —
        # with the default 3 attempts in ~0.2 s that was a start-up race
        # lost about one run in six (backoff doubles to 2 s: ~1 min)
        reply = request(
            self.address,
            {"kind": "join", "rank": self.rank if rank is None else rank},
            timeout=self.timeout_s,
            connect_retries=30,
        )
        self.generation = reply.get("generation", self.generation)
        self._last_tau = reply.get("tau", self._last_tau)
        self._residual = None  # fresh incarnation, fresh EF history
        return reply

    def exchange(self, worker_params, rank=None, step=None):
        w, residual = _pack_wire(
            worker_params, self.wire_dtype, self._residual
        )
        msg = {"kind": "exchange", "params": w}
        if self.rank is not None:
            msg["rank"] = self.rank
            if step is not None:
                msg["step"] = int(step)
        reply = ms.retry_with_backoff(
            lambda: request(self.address, msg, timeout=self.timeout_s),
            attempts=self.retries + 1,
            counter_labels={"rule": "easgd"},
        )
        # commit the EF residual only after the push actually landed: a
        # failed send's quantization error was never on the wire, so it
        # must not be subtracted from the next attempt
        self._residual = residual
        self._last_tau = reply.get("tau", self._last_tau)
        if reply.get("readmitted"):
            self.readmissions += 1
            self.generation = reply.get("generation", self.generation)
            self._residual = None
            print(
                f"EASGD worker (rank {self.rank}): re-admitted by the "
                f"server under generation {self.generation} — pulling "
                "the center (checkpointless recovery)",
                flush=True,
            )
        return _unpack_wire(reply["params"])

    def suggest_tau(self, rank=None, default: Optional[int] = None):
        """The server's adaptive-τ hint from the latest reply (None →
        keep the caller's static τ)."""
        return self._last_tau if self._last_tau else default


class _CompressedMailbox:
    """Mailbox decorator: fp32 leaves ride the TCP frames in
    ``wire_dtype`` (fp16 cast or ``'q8'`` int8+scales); receives
    reconstruct fp32. The GOSGD analog of the EASGD proxy's compressed
    exchange.

    q8 push-leg EF: the residual is keyed by the payload's shape
    fingerprint (``wire.q8_fingerprint``) because one mailbox
    interleaves params pushes with acks/finals — a residual must only
    roll into the NEXT frame of the same payload shape, whichever peer
    it goes to (the EF recurrence is about this sender's quantization
    error, not about any one destination)."""

    def __init__(self, inner, wire_dtype):
        self._inner = inner
        self._dt = wire_dtype
        self._residuals: dict = {}
        self.n_ranks = inner.n_ranks

    def send(self, dst: int, msg: Any) -> None:
        if self._dt == "q8":
            from theanompi_tpu.parallel import wire

            fp = wire.q8_fingerprint(msg)
            if fp:
                packed, res = _pack_wire(msg, "q8", self._residuals.get(fp))
                self._residuals[fp] = res
                self._inner.send(dst, packed)
                return
            # no quantizable leaves (ack frames): ship as-is
            self._inner.send(dst, msg)
            return
        self._inner.send(dst, _cast_wire(msg, self._dt))

    def drain(self, rank=None):
        return [_unpack_wire(m) for m in self._inner.drain(rank)]

    def recv(self, rank=None, timeout=None):
        return _unpack_wire(self._inner.recv(rank, timeout))

    def reset_residuals(self) -> None:
        """Drop every push-leg EF residual — called on membership churn
        (a peer evicted or re-admitted): error feedback accumulated
        against a dead incarnation's stream must never be replayed into
        a fresh one."""
        self._residuals.clear()

    def close(self) -> None:
        self._inner.close()


# ---------------------------------------------------------------------------
# EASGD
# ---------------------------------------------------------------------------

class EasgdServerCore:
    """The EASGD server's elastic math + membership, transport-free.

    Extracted from ``run_easgd_server`` so the protocol is testable
    with plain numpy pytrees (no model, no sockets): ``handler`` is
    what a ``TcpServerChannel`` serves, ``cv``/predicates are what the
    duties loop waits on.  The roster turns the old static
    ``n_workers - failed`` accounting into LIVE membership:

    - ``join`` registers (or re-admits) a rank; the reply carries the
      center, the server's CURRENT wait epoch (a mid-run joiner starts
      there — checkpointless), the member's generation, and the
      adaptive-τ hint when enabled.
    - ``exchange`` heartbeats the member.  An exchange from an
      UNKNOWN/EVICTED rank is the re-admission path: its stale params
      are NOT folded into the center — the reply hands back the center
      under a fresh generation with ``readmitted: True``, and the
      per-worker reply-leg EF residual starts from zero (the old one
      died with the eviction).
    - ``epoch``/``done`` update the boundary bookkeeping; ``done``
      leaves the roster cleanly (no eviction alert).
    - ``sweep`` evicts members silent past ``evict_after_s`` — called
      from the duties loop's wait so a dead worker can never wedge an
      epoch boundary.
    - ``weights`` (with ``publish_every > 0``) serves the latest
      published center snapshot to serving-tier subscribers — the
      online learning loop's pull RPC (``theanompi_tpu.publish``).
      Publication fires every ``publish_every`` exchanges; the
      ``(generation, digest)`` announcement piggybacks on join and
      exchange replies under the ``"publish"`` key.  Snapshot payloads
      always ride the wire fp32, never ``wire_dtype``-compressed: the
      subscriber verifies the digest byte-for-byte before install, and
      a lossy wire would turn every pull into a refusal.

    With ``wire_dtype='q8'`` the reply leg is EF-compensated PER WORKER
    (residual in the member's roster state — the server-side state PR 6
    noted was missing), freed on evict and fresh on rejoin.
    """

    def __init__(
        self,
        center: Any,
        alpha: float,
        start_epoch: int = 0,
        wire_dtype=None,
        evict_after_s: float = 60.0,
        base_tau: Optional[int] = None,
        adaptive_tau: bool = False,
        on_event=None,
        clock=time.monotonic,
        publish_every: int = 0,
    ):
        self.alpha = float(alpha)
        self.wire_dtype = wire_dtype
        self.cv = threading.Condition()
        self.center = center
        self.epoch = int(start_epoch)  # the boundary duties wait on
        self.n_exchanges = 0
        self.epoch_counts: dict = {}
        self.net_state = None  # latest worker BN-state snapshot
        self.wire_seen: Optional[str] = None
        self.done_ok: set = set()
        self.failed: set = set()
        self.any_joined = False
        self.readmissions = 0
        self._on_event = on_event
        self.roster = ms.Roster(
            "easgd", evict_after_s=evict_after_s,
            on_event=self._membership_event, clock=clock,
        )
        self.tau_ctrl = (
            ms.TauController(base_tau, self.roster)
            if (adaptive_tau and base_tau) else None
        )
        if int(publish_every) > 0:
            from theanompi_tpu.publish.publisher import CenterPublisher

            # the center attr is re-BOUND every exchange, so the
            # publisher must read through the getter, not capture a tree
            self.publisher = CenterPublisher(
                lambda: self.center, publish_every
            )
        else:
            self.publisher = None

    def _membership_event(self, kind, member, generation) -> None:
        print(
            f"EASGD server: membership {kind} rank {member} "
            f"(generation {generation})",
            flush=True,
        )
        if self._on_event is not None:
            self._on_event(kind, member, generation)

    # ---- duties-loop predicates (call with ``cv`` held) --------------
    def expected_reports(self) -> int:
        """Ranks that must report the current boundary: live members
        (they train toward it) plus clean finishers (they already
        reported every epoch — the original fast-worker rationale).
        Failed and evicted ranks are expected to report nothing."""
        return len(self.roster.members()) + len(self.done_ok)

    def boundary_ready(self, epoch: int) -> bool:
        n = self.expected_reports()
        return n > 0 and self.epoch_counts.get(epoch, 0) >= n

    def all_gone(self) -> bool:
        """Every rank that ever joined has left (done/failed/evicted)."""
        return self.any_joined and not self.roster.members()

    def sweep(self) -> List[Any]:
        return self.roster.sweep()

    def _tau_hint(self, reply: dict, rank) -> dict:
        if self.tau_ctrl is not None and rank is not None:
            reply["tau"] = self.tau_ctrl.tau_for(rank)
        return self._announce(reply)

    def _announce(self, reply: dict) -> dict:
        """Piggyback the latest publish announcement — generation +
        digest, a few dozen bytes — on a reply already going out."""
        if self.publisher is not None:
            ann = self.publisher.announcement()
            if ann is not None:
                reply["publish"] = ann
        return reply

    # ---- the served protocol -----------------------------------------
    def handler(self, msg: Any) -> Any:
        kind = msg["kind"]
        with self.cv:
            if kind == "join":
                rank = msg.get("rank")
                gen = 0
                if rank is not None:
                    gen = self.roster.join(rank)
                    self.any_joined = True
                    self.done_ok.discard(rank)
                    self.failed.discard(rank)
                self.cv.notify_all()
                return self._tau_hint(
                    {"params": self.center, "epoch": self.epoch,
                     "generation": gen},
                    rank,
                )
            if kind == "exchange":
                if self.wire_seen is None:
                    # observability: what dtype ACTUALLY rode the wire —
                    # the e2e compression tests assert this, so a
                    # refactor that silently drops the compression
                    # cannot stay green ('int8+scales' for q8 frames)
                    from theanompi_tpu.parallel import wire as _w

                    self.wire_seen = _w.wire_dtype_seen(msg["params"])
                rank = msg.get("rank")
                if rank is not None and not self.roster.beat(
                    rank, msg.get("step")
                ):
                    # unknown/evicted incarnation → re-admission: the
                    # worker's params went stale while it was out of the
                    # roster, so they must NOT move the center; hand it
                    # the center to pull under a fresh generation
                    gen = self.roster.join(rank)
                    self.any_joined = True
                    self.done_ok.discard(rank)
                    self.failed.discard(rank)
                    self.readmissions += 1
                    out = jax.tree.map(np.copy, self.center)
                    if self.wire_dtype:
                        out = _pack_wire(out, self.wire_dtype)[0]
                    self.cv.notify_all()
                    return self._tau_hint(
                        {"params": out, "readmitted": True,
                         "generation": gen, "epoch": self.epoch},
                        rank,
                    )
                w = _unpack_wire(msg["params"])  # math always fp32
                c = self.center
                diff = jax.tree.map(lambda a, b: a - b, w, c)
                self.center = jax.tree.map(
                    lambda b, d: b + self.alpha * d, c, diff
                )
                self.n_exchanges += 1
                if self.publisher is not None:
                    # cadence hook: every publish_every-th exchange
                    # snapshots the center just updated above
                    self.publisher.maybe_publish(self.n_exchanges)
                out = jax.tree.map(lambda a, d: a - self.alpha * d, w, diff)
                if self.wire_dtype:
                    st = (
                        self.roster.state(rank) if rank is not None else None
                    )
                    if st is not None:
                        # reply leg EF per worker: the residual lives in
                        # the member's roster state, so eviction frees it
                        # and a rejoin starts from zero by construction
                        out, st["reply_ef"] = _pack_wire(
                            out, self.wire_dtype, st.get("reply_ef")
                        )
                    else:
                        # anonymous (rank-less) client: plain RN, the
                        # pre-membership behavior
                        out = _pack_wire(out, self.wire_dtype)[0]
                return self._tau_hint({"params": out}, rank)
            if kind == "epoch":
                rank = msg.get("rank")
                if rank is not None:
                    self.roster.beat(rank)
                e = int(msg["epoch"])
                self.epoch_counts[e] = self.epoch_counts.get(e, 0) + 1
                if msg.get("net_state") is not None:
                    self.net_state = msg["net_state"]
                self.cv.notify_all()
                return {"ok": True}
            if kind == "done":
                rank = msg.get("rank")
                if rank is not None:
                    if bool(msg.get("failed", False)):
                        self.failed.add(rank)
                    else:
                        self.done_ok.add(rank)
                    self.roster.leave(rank)
                self.cv.notify_all()
                return {"ok": True}
            if kind == "weights":
                # online learning loop: a serving-tier subscriber pulls
                # the published center snapshot (fp32, never
                # wire-compressed — the digest must verify byte-exact)
                snap = (
                    self.publisher.snapshot(msg.get("generation"))
                    if self.publisher is not None
                    else None
                )
                if snap is None:
                    return {
                        "ok": False,
                        "error": "no published snapshot for the "
                                 "requested generation",
                    }
                snap["ok"] = True
                return snap
        raise ValueError(f"unknown request kind {kind!r}")


def run_easgd_server(
    size: int,
    address: Address,
    modelfile: str,
    modelclass: str,
    model_config: Optional[dict],
    n_epochs: Optional[int],
    alpha: float,
    checkpoint_dir: Optional[str],
    val_freq: int = 1,
    resume: bool = False,
    verbose: bool = True,
    timeout: float = 3600.0,
    keep_last: Optional[int] = None,  # prune center snapshots to newest N
    wire_dtype=None,  # e.g. np.float16: compressed exchange replies
    duties_coalesce: bool = True,  # jump to the newest completed epoch
    # when validation is slower than a worker epoch (same semantics and
    # rationale as EASGD_Driver.duties_coalesce, async_workers.py)
    evict_after_s: float = 60.0,  # membership: a worker silent past
    # this window is evicted (its exchange cadence is its heartbeat —
    # size it well above tau * step_time)
    adaptive_tau: bool = False,  # straggler-adaptive per-worker tau
    # hints in every exchange/join reply (membership.TauController)
    tau: Optional[int] = None,  # the workers' base tau (adaptive mode
    # needs it to scale from; ignored otherwise)
    publish_every: int = 0,  # online learning loop: snapshot + announce
    # the center every N exchanges for serving-tier subscribers
    # (theanompi_tpu.publish); 0 disables publication entirely
):
    """Rank 0: the reference ``EASGD_Server.run()`` loop, TCP-served.

    Builds its own model instance on this process's devices (the
    reference dedicated a rank + GPU to the server) purely for center
    init + validation; it never trains.  Membership lives in
    :class:`EasgdServerCore`: dead workers are evicted instead of
    wedging epoch boundaries, and killed-then-respawned workers
    re-admit checkpointlessly (docs/elasticity.md)."""
    import importlib

    cfg = dict(model_config or {})
    cls = getattr(importlib.import_module(modelfile), modelclass)
    model = cls(config=cfg, mesh=cls.build_mesh(devices=jax.local_devices(), config=cfg))
    if n_epochs is not None:
        model.n_epochs = n_epochs
    start_epoch = 0
    center = _to_host(model.params)
    if resume and checkpoint_dir:
        from theanompi_tpu.utils import checkpoint as ckpt

        path = ckpt.latest(checkpoint_dir, prefix="ckpt_center_")
        if path:
            blob = ckpt.restore(path)
            center = blob["params"]
            start_epoch = int(blob["epoch"])
            print(f"EASGD server: resumed center from {path} at epoch "
                  f"{start_epoch}", flush=True)

    # live telemetry (observability/live.py): inert unless
    # THEANOMPI_LIVE/THEANOMPI_LIVE_AGG is set.  The server's
    # membership_evictions_total deltas ride the frames, so the live
    # watchdog's worker_evicted rule pages on real fleet churn.
    from theanompi_tpu.observability import live as obs_live

    telemetry = obs_live.maybe_start_from_env("easgd_server")
    rec = Recorder(print_freq=1, rank=0, verbose=verbose,
                   save_dir=checkpoint_dir)
    # adaptive τ prefers the live doctor's SPAN-LEVEL straggler index
    # (shipped in the workers' telemetry frames) over the roster's
    # beat-rate proxy — installed only when this process hosts the
    # aggregator (THEANOMPI_LIVE=1); the controller falls back to the
    # proxy whenever the live plane is off or has no window yet
    live_tau_source = (
        ms.live_straggler_source(telemetry.aggregator)
        if telemetry is not None and hasattr(telemetry, "aggregator")
        else None
    )
    core = EasgdServerCore(
        center,
        alpha,
        start_epoch=start_epoch,
        wire_dtype=wire_dtype,
        evict_after_s=evict_after_s,
        base_tau=tau,
        adaptive_tau=adaptive_tau,
        publish_every=publish_every,
        on_event=lambda kind, member, gen: rec.log_event(
            "membership", plane="easgd", event=kind, rank=member,
            generation=gen,
        ),
    )
    if core.tau_ctrl is not None and live_tau_source is not None:
        core.tau_ctrl.live_source = live_tau_source
    cv = core.cv

    channel = TcpServerChannel(address[1], core.handler)
    deadline = time.monotonic() + timeout

    def _wait_for(pred) -> None:
        """cv.wait_for with eviction sweeps folded in: a dead worker
        must unblock the predicate by being evicted, not by the job
        timeout.  Raises TimeoutError at the overall deadline."""
        with cv:
            while not pred():
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"EASGD server: boundary/drain predicate unmet "
                        f"within {timeout}s"
                    )
                cv.wait(timeout=min(1.0, max(0.1, evict_after_s / 4)))
                core.sweep()

    try:
        epoch = start_epoch
        while epoch < model.n_epochs:
            core.epoch = epoch
            _wait_for(
                lambda: core.boundary_ready(epoch) or core.all_gone()
            )
            with cv:
                if core.epoch_counts.get(epoch, 0) == 0:
                    break  # all workers gone before this boundary
                # coalesce lagging duties to the NEWEST completed epoch
                # so every validated row reflects a fresh center — same
                # helper as the threaded driver (frozen-curve fix,
                # VERDICT r3 #1)
                newest, skipped = coalesce_duties_window(
                    epoch, model.n_epochs, core.boundary_ready,
                    duties_coalesce,
                )
                center = jax.tree.map(np.copy, core.center)
                # snapshot with the center: the provenance must say how
                # many exchanges produced exactly these params
                n_ex = core.n_exchanges
                net_state = core.net_state
                core.epoch = newest + 1  # joiners start at the new boundary
            if checkpoint_dir:
                from theanompi_tpu.utils import checkpoint as ckpt

                ckpt.save(
                    os.path.join(checkpoint_dir, f"ckpt_center_{newest + 1:04d}.npz"),
                    {"params": center, "epoch": newest + 1, "alpha": alpha},
                )
                if keep_last:
                    ckpt.prune(checkpoint_dir, keep_last,
                               prefix="ckpt_center_")
            if duties_val_due(val_freq, newest, skipped):
                loss, err, _ = model.run_validation(
                    (newest + 1) * model.data.n_batch_train,
                    rec,
                    params=replicate(model.mesh, center),
                    net_state=net_state,  # workers' trained BN stats
                    extra=duties_provenance(newest, skipped, n_ex),
                )
                if verbose:
                    print(f"[EASGD center] epoch {newest}: val cost "
                          f"{loss:.4f} err {err:.4f} (n_exchanges {n_ex})",
                          flush=True)
            epoch = newest + 1
        # drain: every rank that ever joined must leave (done) or be
        # evicted — the roster replaces the static done >= n_workers
        # count, so a killed-and-never-respawned worker cannot wedge
        # the shutdown past its eviction window
        _wait_for(core.all_gone)
        with cv:
            center = jax.tree.map(np.copy, core.center)
    finally:
        channel.close()
        if telemetry is not None:
            try:
                telemetry.stop()
            except Exception as te:  # telemetry never masks the run
                print(f"telemetry stop failed: {type(te).__name__}: {te}",
                      flush=True)
    model.params = replicate(model.mesh, center)
    rec.log_event(
        "async_wire",
        dtype=core.wire_seen or "none",
        n_exchanges=core.n_exchanges,
    )
    rec.log_event(
        "membership_summary",
        plane="easgd",
        evictions=core.roster.n_evictions,
        rejoins=core.roster.n_rejoins,
        readmissions=core.readmissions,
    )
    if checkpoint_dir:
        model.save_model(os.path.join(checkpoint_dir, "ckpt_center.npz"))
        rec.save(os.path.join(checkpoint_dir, "record_server.jsonl"))
    return model


def run_easgd_worker(
    rank: int,
    size: int,
    server_address: Address,
    modelfile: str,
    modelclass: str,
    model_config: Optional[dict],
    n_epochs: Optional[int],
    tau: int,
    checkpoint_dir: Optional[str] = None,
    verbose: bool = False,
    wire_dtype=None,  # e.g. np.float16: compressed exchange payloads
    watchdog_timeout: Optional[float] = None,  # per-process stall
    # watchdog (armed at the first completed iteration)
    watchdog_action: str = "dump",
    adaptive_tau: bool = False,  # apply the server's per-worker tau hints
    exchange_retries: int = 2,  # bounded retry per exchange leg before
    # degrading to local SGD (membership.retry_with_backoff)
    exchange_timeout_s: float = 120.0,
):
    """Ranks 1..N-1: the reference ``EASGD_Worker`` loop, one process."""
    widx = rank - 1  # data-shard index among the N-1 workers
    rec = Recorder(
        print_freq=int((model_config or {}).get("print_freq", 40)),
        rank=rank,
        verbose=verbose,
        save_dir=checkpoint_dir,
    )
    server = _RemoteServer(
        server_address, wire_dtype=wire_dtype, rank=rank,
        retries=exchange_retries, timeout_s=exchange_timeout_s,
    )
    worker = EASGD_Worker(
        widx,
        jax.local_devices(),
        modelfile,
        modelclass,
        model_config,
        n_epochs,
        rec,
        n_workers=size - 1,
        server=server,
        tau=tau,
        adaptive_tau=adaptive_tau,
    )
    from theanompi_tpu.observability import live as obs_live
    from theanompi_tpu.runtime.fault import FaultInjector

    telemetry = obs_live.maybe_start_from_env(f"easgd_rank{rank}")
    # chaos plans address processes by GLOBAL rank (the supervisor's
    # view), while the worker indexes data shards by widx
    worker.fault = FaultInjector.from_env(rank=rank)
    worker.fault_rank = rank
    joined = server.join()
    worker.set_params(joined["params"])
    worker.model.current_epoch = int(joined["epoch"])
    # the epoch report carries this worker's host BN-state snapshot
    # (taken at the boundary by _epoch_end): the server's own model
    # never trains, so validating the center with ITS init running
    # stats would make every mid-run val row garbage on BN models
    def _report_epoch(r, e):
        try:
            ms.retry_with_backoff(
                lambda: request(
                    server_address,
                    {"kind": "epoch", "rank": rank, "epoch": e,
                     "net_state": worker.host_net_state},
                    timeout=exchange_timeout_s,
                ),
                attempts=exchange_retries + 1,
                counter_labels={"rule": "easgd"},
            )
        except (ConnectionError, OSError, TimeoutError) as err:
            # a down server must not kill a surviving worker at an
            # epoch boundary: training continues, the next exchange's
            # re-admission path resyncs the membership state
            print(
                f"EASGD worker {rank}: epoch-{e} report failed "
                f"({type(err).__name__}) — continuing locally",
                flush=True,
            )

    worker.on_epoch_end = _report_epoch
    from theanompi_tpu.runtime.fault import Watchdog

    worker.watchdog = Watchdog.maybe(watchdog_timeout, watchdog_action)
    failed = True
    try:
        worker._run()
        failed = False
    finally:
        if worker.watchdog is not None:
            worker.watchdog.close()
        if telemetry is not None:
            try:
                telemetry.stop()
            except Exception as te:  # telemetry never masks the run
                print(f"telemetry stop failed: {type(te).__name__}: {te}",
                      flush=True)
        try:
            request(
                server_address, {"kind": "done", "rank": rank, "failed": failed}
            )
        except OSError:
            pass  # server already gone; never mask the original error
        rec.log_event(
            "membership_client",
            plane="easgd",
            degraded_steps=worker.n_degraded_steps,
            exchange_failures=worker.n_exchange_failures,
            readmissions=server.readmissions,
        )
        if checkpoint_dir:
            rec.save()
    return worker.model


# ---------------------------------------------------------------------------
# GOSGD
# ---------------------------------------------------------------------------

class _GossipAdapter:
    """Mailbox view for one GOSGD peer: frames mass-carrying messages
    with ``(kind, src, seq, ...)`` and runs the app-level ack protocol
    (VERDICT r3 #6) the raw transport cannot provide.

    The TCP transport is at-most-once: a frame that landed in a dying
    receiver's kernel buffer is lost with no error anywhere, silently
    shrinking total consensus mass by the in-flight weight
    (transport.py's delivery-model note).  Here every push/final is
    acked by the receiver AT DECODE TIME (once it's in this process's
    queue the mass is owned); a sender whose push is never acked
    reclaims the halved weight via ``reclaim_expired`` — called from
    the worker's merge step — and a peer whose final is never acked
    resends it.

    Trade-off, stated honestly: restore-on-timeout converts silent mass
    LOSS (dead receiver) into possible mass DUPLICATION (receiver alive
    but stalled past ``ack_timeout``: it may still merge the push the
    sender already reclaimed).  Both are bounded by the in-flight
    weight; loss was invisible, duplication is logged by both ends.  A
    receiver that can no longer merge (post-final lingering) does NOT
    ack, so the sender's reclaim is the correct outcome there.
    """

    def __init__(self, mailbox: TcpMailbox, rank: int,
                 ack_timeout: float = 120.0,
                 evict_after_s: float = 60.0,
                 hello_every_s: float = 2.0,
                 on_event=None):
        self.mailbox = mailbox
        self.rank = int(rank)
        self.n_ranks = mailbox.n_ranks
        self.ack_timeout = float(ack_timeout)
        self.finals: List[Tuple[Any, float]] = []
        self.accept_gossip = True  # False once this peer shipped its final
        self._seq = 0
        # seq -> (kind, dst, weight, deadline, payload-for-resend|None)
        self._pending: dict = {}
        self._finals_seen: set = set()
        self.n_dropped = 0  # post-final pushes dropped unacked (observability)
        # ---- elastic membership (docs/elasticity.md) -----------------
        # the peer table: who is alive and pushable.  Beats come from
        # the gossip frames themselves plus periodic hello beacons (a
        # quiet peer with low p_push still proves life); silent peers
        # are evicted from THIS peer's table only — membership is a
        # local view, consistent because everyone runs the same rules.
        self.on_event = on_event
        self.roster = ms.Roster(
            "gosgd", evict_after_s=evict_after_s,
            on_event=self._membership_event,
        )
        self.hello_every_s = float(hello_every_s)
        self._last_hello = 0.0
        self._snapshot_requests: List[int] = []
        self._final_srcs: set = set()
        self.any_joined = False

    # ---- membership --------------------------------------------------
    def _membership_event(self, kind, member, generation) -> None:
        print(
            f"GOSGD peer {self.rank}: membership {kind} rank {member} "
            f"(generation {generation})",
            flush=True,
        )
        if kind in ("evict", "rejoin"):
            # fresh incarnation / dead stream: push-leg EF residuals
            # accumulated against the old connection must not replay
            reset = getattr(self.mailbox, "reset_residuals", None)
            if reset is not None:
                reset()
        if self.on_event is not None:
            try:
                self.on_event(kind, member, generation)
            except Exception as e:
                print(f"GOSGD membership event hook failed: "
                      f"{type(e).__name__}: {e}", flush=True)

    def _beat(self, src: int, step: Optional[int] = None) -> None:
        """Any frame from ``src`` proves life: auto-join unknowns (the
        gossip fabric has no central admission — hearing a peer IS the
        join), then heartbeat."""
        src = int(src)
        if not self.roster.beat(src, step):
            self.roster.join(src)
            self.any_joined = True
            self.roster.beat(src, step)

    def live_peers(self) -> List[int]:
        """Pushable peers.  Until ANY peer has spoken the membership
        protocol, every configured rank is assumed live (mixed-fleet /
        pre-hello compatibility: a sender must not go mute just because
        its peers never beacon — the weight-restore path still covers
        their deaths).  Once the fabric is heard from, only known-live
        members are targets."""
        if not self.any_joined:
            return [r for r in range(self.n_ranks) if r != self.rank]
        return [int(r) for r in self.roster.members()]

    def peer_weights(self, peers: Sequence[int]) -> List[float]:
        """Push-target selection weights, biased AWAY from stragglers:
        a peer whose beat-measured step rate lags the fastest gets
        proportionally less gossip (its inbox is already its
        bottleneck), floored at 0.25 so no live peer starves of
        updates."""
        out = []
        for r in peers:
            idx = self.roster.straggler_index(int(r))
            out.append(1.0 if idx is None else max(0.25, 1.0 - idx))
        return out

    def sweep(self) -> List[int]:
        return [int(r) for r in self.roster.sweep()]

    def maybe_hello(self, step: Optional[int] = None) -> None:
        """Periodic liveness beacon to every configured address — the
        heartbeat for peers the random pushes would leave silent."""
        now = time.monotonic()
        if now - self._last_hello < self.hello_every_s:
            return
        self._last_hello = now
        self.send_hello(step=step)

    def send_hello(self, step: Optional[int] = None,
                   need_snapshot: bool = False,
                   ranks: Optional[Sequence[int]] = None) -> None:
        targets = (
            list(ranks) if ranks is not None
            else [r for r in range(self.n_ranks) if r != self.rank]
        )
        for dst in targets:
            try:
                self.mailbox.send(
                    dst,
                    ("hello", self.rank, int(step or 0),
                     1 if need_snapshot else 0),
                )
            except (ConnectionError, OSError):
                pass  # unreachable peers learn of us from later beacons

    def send_bye(self) -> None:
        """Best-effort clean-leave announcement (peers drop us from
        their tables immediately instead of waiting out the eviction
        window)."""
        for dst in range(self.n_ranks):
            if dst == self.rank:
                continue
            try:
                self.mailbox.send(dst, ("bye", self.rank))
            except (ConnectionError, OSError):
                pass

    def take_snapshot_requests(self) -> List[int]:
        out, self._snapshot_requests = self._snapshot_requests, []
        return out

    def pending_final_ranks(self) -> List[int]:
        """Live members whose final has not arrived — what rank 0's
        consensus gather waits on (an evicted member drops out, so a
        dead peer cannot wedge the consensus past its eviction
        window)."""
        return [
            r for r in self.live_peers()
            if r != self.rank and r not in self._final_srcs
        ]

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _ack(self, src: int, seq: int) -> None:
        try:
            self.mailbox.send(src, ("ack", seq))
        except (ConnectionError, OSError):
            pass  # acker's best effort: a dead sender needs no ack

    def send(self, dst: int, msg: Any) -> None:
        """Gossip push ``(params, weight)`` — framed, tracked, acked."""
        p, w = msg
        seq = self._next_seq()
        self._pending[seq] = (
            "push", dst, float(w), time.monotonic() + self.ack_timeout, None
        )
        try:
            self.mailbox.send(dst, ("push", self.rank, seq, p, w))
        except BaseException:
            # a send that RAISED is compensated by the caller's own
            # restore (_maybe_push) — leaving the pending entry would
            # reclaim the same mass a second time at the ack deadline
            del self._pending[seq]
            raise

    def send_final(self, dst: int, params: Any, weight: float) -> int:
        seq = self._next_seq()
        payload = ("final", self.rank, seq, params, weight)
        # finals RESEND on timeout rather than restoring (the mass has
        # nowhere else to go; consensus cannot complete without it)
        self._pending[seq] = (
            "final", dst, float(weight),
            time.monotonic() + self.ack_timeout, payload,
        )
        try:
            self.mailbox.send(dst, payload)
        except (ConnectionError, OSError):
            pass  # keep pending: resend_overdue_finals retries it
        return seq

    def is_acked(self, seq: int) -> bool:
        return seq not in self._pending

    def resend_overdue_finals(self) -> None:
        now = time.monotonic()
        for seq, (kind, dst, w, deadline, payload) in list(self._pending.items()):
            if kind == "final" and now > deadline:
                self._pending[seq] = (
                    kind, dst, w, now + self.ack_timeout, payload
                )
                try:
                    self.mailbox.send(dst, payload)
                    print(f"GOSGD peer {self.rank}: resent unacked final "
                          f"(seq {seq})", flush=True)
                except (ConnectionError, OSError):
                    pass  # receiver gone; keep trying until job timeout

    def has_pending_pushes(self) -> bool:
        return any(k == "push" for k, *_ in self._pending.values())

    def reclaim_expired(self) -> float:
        """Total push weight whose ack never arrived — the sender folds
        this back into its own consensus weight."""
        now = time.monotonic()
        total = 0.0
        for seq, (kind, dst, w, deadline, _) in list(self._pending.items()):
            if kind == "push" and now > deadline:
                del self._pending[seq]
                total += w
                print(f"GOSGD peer {self.rank}: push seq {seq} to {dst} "
                      f"unacked after {self.ack_timeout:.0f}s — reclaiming "
                      f"weight {w:.4f}", flush=True)
        return total

    def drain(self, rank: Optional[int] = None) -> List[Any]:
        gossip = []
        for m in self.mailbox.drain():
            if not isinstance(m, tuple):
                gossip.append(m)
            elif m[0] == "ack" and len(m) == 2:
                self._pending.pop(m[1], None)
            elif m[0] == "push" and len(m) == 5:
                _, src, seq, p, w = m
                self._beat(src)
                if self.accept_gossip:
                    self._ack(src, seq)
                    gossip.append((p, w))
                else:
                    # can't merge any more (final shipped): no ack, so
                    # the sender reclaims the mass — dropping silently
                    # here was the pre-r4 behavior the ack closes
                    self.n_dropped += 1
                    print(f"GOSGD peer {self.rank}: dropping post-final "
                          f"push from {src} (sender will reclaim)",
                          flush=True)
            elif m[0] == "final" and len(m) == 5:
                _, src, seq, p, w = m
                self._ack(src, seq)
                # a RESENT final may arrive twice: dedupe by (src, seq)
                key = (src, seq)
                if key not in self._finals_seen:
                    self._finals_seen.add(key)
                    self.finals.append((p, float(np.asarray(w))))
                # a final is a clean leave: its sender can merge nothing
                # further, so it must drop out of the push table now
                # instead of collecting post-final pushes to reclaim
                self._final_srcs.add(int(src))
                if self.roster.is_member(int(src)):
                    self.roster.leave(int(src))
            elif m[0] == "hello" and len(m) == 4:
                _, src, step, need = m
                self._beat(src, int(step))
                if need and int(src) not in self._snapshot_requests:
                    # a (re)joining peer asked for state: queue a
                    # directed, mass-conserving push grant for the
                    # worker's next merge step (docs/elasticity.md —
                    # a snapshot IS a push, so consensus mass stays 1)
                    self._snapshot_requests.append(int(src))
            elif m[0] == "bye" and len(m) == 2:
                if self.roster.is_member(int(m[1])):
                    self.roster.leave(int(m[1]))
            else:
                gossip.append(m)
        return gossip


def run_gosgd_peer(
    rank: int,
    size: int,
    addresses: Sequence[Address],
    modelfile: str,
    modelclass: str,
    model_config: Optional[dict],
    n_epochs: Optional[int],
    p_push: float,
    checkpoint_dir: Optional[str] = None,
    val_freq: int = 1,
    verbose: bool = False,
    timeout: float = 3600.0,
    wire_dtype=None,  # e.g. np.float16: compressed gossip payloads
    watchdog_timeout: Optional[float] = None,  # per-process stall
    # watchdog (armed at the first completed iteration)
    watchdog_action: str = "dump",
    ack_timeout: float = 120.0,  # mass-frame ack window (see
    # _GossipAdapter: reclaim pushes / resend finals past this)
    evict_after_s: float = 60.0,  # membership: silent peers leave the
    # push table after this window
    hello_every_s: float = 2.0,  # liveness beacon cadence
    rejoin: Optional[bool] = None,  # None → THEANOMPI_ELASTIC_REJOIN
    # env (set by the elastic supervisor on respawned ranks): start
    # with zero consensus weight and pull a peer snapshot instead of
    # training from init — checkpointless recovery
    snapshot_wait_s: float = 30.0,
):
    """One GOSGD peer process; rank 0 also aggregates the consensus."""
    mailbox = TcpMailbox(rank, addresses)
    if wire_dtype:
        mailbox = _CompressedMailbox(mailbox, wire_dtype)
    seed0 = int((model_config or {}).get("seed", 0))
    rec = Recorder(
        print_freq=int((model_config or {}).get("print_freq", 40)),
        rank=rank,
        verbose=verbose and rank == 0,
        save_dir=checkpoint_dir,
    )
    adapter = _GossipAdapter(
        mailbox, rank, ack_timeout=ack_timeout,
        evict_after_s=evict_after_s,
        # at least 3 beacons per eviction window: the cadence must
        # leave headroom for a slow iteration between beacons, or a
        # merely-slow peer reads as dead under a tight window
        hello_every_s=min(hello_every_s, evict_after_s / 3.0),
        on_event=lambda kind, member, gen: rec.log_event(
            "membership", plane="gosgd", event=kind, rank=member,
            generation=gen,
        ),
    )
    worker = GOSGD_Worker(
        rank,
        jax.local_devices(),
        modelfile,
        modelclass,
        model_config,
        n_epochs,
        rec,
        n_workers=size,
        mailbox=adapter,
        p_push=p_push,
        rng=np.random.RandomState(10_000 + seed0 + rank),
    )
    from theanompi_tpu.observability import live as obs_live
    from theanompi_tpu.runtime.fault import FaultInjector, Watchdog

    telemetry = obs_live.maybe_start_from_env(f"gosgd_rank{rank}")
    worker.fault = FaultInjector.from_env(rank=rank)
    worker.watchdog = Watchdog.maybe(watchdog_timeout, watchdog_action)
    if rejoin is None:
        rejoin = os.environ.get("THEANOMPI_ELASTIC_REJOIN") == "1"
    if rejoin:
        # checkpointless re-admission: this incarnation holds NO
        # consensus mass (the dead one's share renormalizes away) and
        # pulls its params from the fabric — every live peer grants a
        # directed half-weight push, so the joiner starts at a
        # mass-weighted average of its peers
        worker.weight = 0.0
        adapter.send_hello(step=0, need_snapshot=True)
        deadline = time.monotonic() + snapshot_wait_s
        while worker.weight <= 0.0 and time.monotonic() < deadline:
            worker._merge_inbox()
            if worker.weight <= 0.0:
                time.sleep(0.05)
        if worker.weight > 0.0:
            print(f"GOSGD peer {rank}: re-admitted with snapshot "
                  f"weight {worker.weight:.4f}", flush=True)
        else:
            print(f"GOSGD peer {rank}: no snapshot within "
                  f"{snapshot_wait_s:.0f}s — training from init at "
                  "zero weight (mass arrives with the first merge)",
                  flush=True)
    else:
        # announce ourselves so peers add us to their push tables (a
        # mid-run late joiner becomes a push target only once heard)
        adapter.send_hello(step=0)
    try:
        worker._run()  # ends with a final inbox drain
        # training is done: the consensus/lingering phases below are
        # not iteration-cadenced — reap the watchdog now
        if worker.watchdog is not None:
            worker.watchdog.close()
            worker.watchdog = None
        # settle outstanding pushes BEFORE the mass leaves this process:
        # wait (bounded by the pushes' own ack deadlines) for acks,
        # merging inbound gossip meanwhile; whatever never gets acked is
        # reclaimed by _merge_inbox into worker.weight — otherwise a
        # push still in flight when training ends ships a final that is
        # light by the unacked half, the exact mass hole the ack
        # protocol exists to close
        settle_deadline = time.monotonic() + ack_timeout + 5.0
        while (adapter.has_pending_pushes()
               and time.monotonic() < settle_deadline):
            worker._merge_inbox()
            if adapter.has_pending_pushes():
                time.sleep(0.05)
        worker._merge_inbox()  # final reclaim pass

        if rank != 0:
            # final is mass-carrying: ship it through the adapter so it
            # is acked by rank 0 and resent if the ack never comes — a
            # final eaten by the at-most-once transport used to hang the
            # whole consensus until the job timeout
            adapter.accept_gossip = False  # can't merge any more
            adapter.send_final(0, worker.get_params(), worker.weight)
            # announce the clean leave fabric-wide: the final only goes
            # to rank 0, and without a bye the other peers would time
            # this rank out as an EVICTION while it lingers serving
            # acks (per-sender FIFO: the final precedes the bye at 0)
            adapter.send_bye()
            # keep the listener open until rank 0 finishes the consensus:
            # slower peers may still push gossip at this port, and a dead
            # port would crash their training (their push rolls back on
            # failure, but staying reachable avoids the churn entirely —
            # their unacked pushes are reclaimed, see _GossipAdapter)
            deadline = time.monotonic() + timeout
            stop = False
            while time.monotonic() < deadline and not stop:
                for m in adapter.drain():  # acks processed; gossip dropped
                    if isinstance(m, tuple) and len(m) == 1 and m[0] == "stop":
                        stop = True
                adapter.resend_overdue_finals()
                if not stop:
                    time.sleep(0.2)
            return worker.model
        # rank 0: gather the finals, weight-average.  Membership-aware:
        # the gather waits on LIVE members' finals, so a dead peer
        # blocks the consensus only until its eviction window elapses —
        # its mass renormalizes away (the weighted average divides by
        # the received total).  Peers that never spoke the hello
        # protocol fall back to the static count (mixed fleets decode).
        deadline = time.monotonic() + timeout
        while len(adapter.finals) < size - 1:
            if adapter.any_joined and not adapter.pending_final_ranks():
                print(
                    f"GOSGD consensus: proceeding with "
                    f"{len(adapter.finals)}/{size - 1} finals — every "
                    "remaining peer left or was evicted; mass "
                    "renormalizes over the received entries",
                    flush=True,
                )
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"GOSGD consensus: only {len(adapter.finals)}/{size - 1} "
                    f"finals within {timeout}s"
                )
            worker._merge_inbox()  # late gossip folds into rank 0's mass
            adapter.sweep()
            time.sleep(0.05)
        # one defensive drain after the last final: per-sender FIFO on
        # the persistent-connection transport already guarantees a
        # peer's gossip precedes its final, but consensus mass must not
        # depend on that subtlety — any straggler gossip folds in here
        worker._merge_inbox()
        entries = [(worker.get_params(), worker.weight)] + adapter.finals
        tot = sum(w for _, w in entries)
        acc = None
        for p, w in entries:
            part = jax.tree.map(lambda x: np.asarray(x) * (w / tot), p)
            acc = part if acc is None else jax.tree.map(np.add, acc, part)
        model = worker.model
        model.params = replicate(model.mesh, acc)
        if val_freq:
            model.run_validation(0, rec)
        rec.log_event(
            "membership_summary",
            plane="gosgd",
            evictions=adapter.roster.n_evictions,
            rejoins=adapter.roster.n_rejoins,
            finals=len(adapter.finals),
            total_mass=round(float(tot), 6),
        )
        if checkpoint_dir:
            model.save_model(os.path.join(checkpoint_dir, "ckpt_consensus.npz"))
            rec.save()
        # release the peers lingering for shutdown
        for r in range(1, size):
            try:
                mailbox.send(r, ("stop",))
            except (ConnectionError, OSError):
                pass  # peer already gone
        return model
    finally:
        if worker.watchdog is not None:  # crash path: _run raised
            worker.watchdog.close()
        if telemetry is not None:
            try:
                telemetry.stop()
            except Exception as te:  # telemetry never masks the run
                print(f"telemetry stop failed: {type(te).__name__}: {te}",
                      flush=True)
        mailbox.close()
