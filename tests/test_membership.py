"""Elastic membership (ISSUE 10): join/leave mid-run, heartbeat
eviction, checkpointless re-admission, degraded mode, and the chaos
drill.

Layered like the implementation: pure ``Roster``/``TauController``
units, the transport-free ``EasgdServerCore`` protocol, the gossip
adapter over real localhost TCP, the live-plane ``worker_evicted``
golden (exactly one alert per kill), and — under the ``distributed``
marker — the real kill→evict→respawn→re-admit drill on OS processes.
"""

import threading
import time

import numpy as np
import pytest

from theanompi_tpu.parallel import membership as ms

# ---------------------------------------------------------------------------
# Roster
# ---------------------------------------------------------------------------


def test_roster_join_beat_evict_rejoin_generations():
    t = [0.0]
    events = []
    r = ms.Roster("t", evict_after_s=1.0, clock=lambda: t[0],
                  on_event=lambda k, m, g: events.append((k, m, g)))
    assert r.join("w1") == 1
    assert r.beat("w1", step=1)
    t[0] = 0.5
    assert r.sweep() == []  # inside the window
    t[0] = 2.0
    assert r.sweep() == ["w1"]  # silent past the window: evicted
    assert not r.is_member("w1")
    assert r.sweep() == []  # exactly once
    assert r.n_evictions == 1
    # rejoin bumps the generation — both sides know history reset
    assert r.join("w1") == 2
    assert r.n_rejoins == 1
    assert [e[0] for e in events] == ["join", "evict", "rejoin"]


def test_roster_clean_leave_is_not_an_eviction():
    r = ms.Roster("t", evict_after_s=0.01)
    r.join("w1")
    r.leave("w1")
    assert not r.is_member("w1")
    time.sleep(0.05)
    assert r.sweep() == []
    assert r.n_evictions == 0
    # and coming back after a clean leave still counts as a rejoin
    assert r.join("w1") == 2


def test_roster_join_grace_covers_warmup():
    """A member that has never proven progress (no step >= 1 beat) gets
    the long join grace, not the tight eviction window — arbitrarily
    long compiles must not read as death.  Once armed, the tight window
    applies."""
    t = [0.0]
    r = ms.Roster("t", evict_after_s=1.0, join_grace_s=10.0,
                  clock=lambda: t[0])
    r.join("compiling")
    r.join("armed")
    r.beat("armed", step=3)
    t[0] = 2.0
    assert r.sweep() == ["armed"]  # armed + silent past 1s
    assert r.is_member("compiling")  # still inside the grace
    t[0] = 11.0
    assert r.sweep() == ["compiling"]  # grace bounds the warmup too


def test_roster_state_freed_on_evict_and_fresh_on_rejoin():
    """The per-member state dict is where EF residuals live: eviction
    clears it and a rejoin starts empty — stale error feedback can
    never be replayed against a fresh incarnation."""
    t = [0.0]
    r = ms.Roster("t", evict_after_s=1.0, clock=lambda: t[0])
    r.join("w")
    r.beat("w", step=1)
    st = r.state("w")
    st["reply_ef"] = np.ones(4)
    t[0] = 5.0
    r.sweep()
    assert r.state("w") is None  # non-members have no state
    assert len(st) == 0  # the dict itself was cleared at eviction
    r.join("w")
    assert r.state("w") == {}


def test_roster_straggler_index_from_step_rates():
    t = [0.0]
    r = ms.Roster("t", evict_after_s=100.0, clock=lambda: t[0])
    for w in ("fast", "slow"):
        r.join(w)
    r.beat("fast", step=0)
    r.beat("slow", step=0)
    t[0] = 10.0
    r.beat("fast", step=100)  # 10 steps/s
    r.beat("slow", step=50)   # 5 steps/s
    assert r.straggler_index("fast") == 0.0
    assert r.straggler_index("slow") == pytest.approx(0.5)
    assert r.straggler_index("unknown") is None


def test_roster_concurrent_leave_join_consistency():
    """Satellite: peer-table consistency under concurrent leave+join —
    threads hammering join/leave/sweep/beat leave the table coherent
    (no exceptions, every surviving member actually joined last)."""
    r = ms.Roster("t", evict_after_s=0.01, join_grace_s=0.05)
    errors = []

    def churn(rank):
        try:
            for i in range(200):
                r.join(rank)
                r.beat(rank, step=i + 1)
                if i % 3 == 0:
                    r.leave(rank)
                if i % 7 == 0:
                    r.sweep()
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    threads = [
        threading.Thread(target=churn, args=(f"w{i}",)) for i in range(6)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    members = r.members()
    assert len(members) == len(set(members))
    for m in members:
        assert r.generation(m) >= 1
    time.sleep(0.06)
    r.sweep()  # drains the survivors; nothing raises


# ---------------------------------------------------------------------------
# roster churn under resize (ISSUE 13 satellite) — plane "bsp"
# ---------------------------------------------------------------------------


def test_bsp_roster_eviction_exactly_once_under_racing_sweeps():
    """N threads racing sweep() over the same silent member: exactly
    ONE of them observes the eviction — the elastic-BSP 'one eviction
    per kill fleet-wide' invariant at the roster layer."""
    t = [0.0]
    events = []
    lock = threading.Lock()

    def on_event(kind, member, gen):
        with lock:
            events.append((kind, member, gen))

    r = ms.Roster("bsp", evict_after_s=1.0, clock=lambda: t[0],
                  on_event=on_event)
    r.join("w1")
    r.beat("w1", step=3)  # armed
    t[0] = 5.0
    evicted = []
    barrier = threading.Barrier(8)

    def race():
        barrier.wait()
        out = r.sweep()
        with lock:
            evicted.extend(out)

    threads = [threading.Thread(target=race) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert evicted == ["w1"]  # one sweep won; the rest saw nothing
    assert [e for e in events if e[0] == "evict"] == [("evict", "w1", 1)]
    assert r.n_evictions == 1


def test_bsp_roster_generation_monotone_across_shrink_expand_shrink():
    """The generation a member carries is strictly increasing across a
    full shrink → expand → shrink episode — both sides always know
    which incarnation's history they hold."""
    t = [0.0]
    r = ms.Roster("bsp", evict_after_s=1.0, clock=lambda: t[0])
    gens = [r.join("w1")]
    r.beat("w1", step=2)
    t[0] += 5.0
    assert r.sweep() == ["w1"]  # shrink
    gens.append(r.join("w1"))  # expand: re-admission
    r.beat("w1", step=9)
    t[0] += 5.0
    assert r.sweep() == ["w1"]  # shrink again
    gens.append(r.join("w1"))
    assert gens == [1, 2, 3]
    assert all(b > a for a, b in zip(gens, gens[1:]))


def test_bsp_roster_concurrent_sweep_and_rejoin_hammer():
    """Sweeps racing rejoins on plane 'bsp': the table stays coherent,
    every eviction pairs with the member being absent at that instant,
    and generations never move backwards."""
    r = ms.Roster("bsp", evict_after_s=0.01, join_grace_s=0.02)
    errors = []
    stop = time.monotonic() + 0.5
    seen_gens = {f"w{i}": 0 for i in range(4)}
    glock = threading.Lock()

    def rejoiner(rank):
        try:
            step = 0
            while time.monotonic() < stop:
                gen = r.join(rank)
                with glock:
                    assert gen > seen_gens[rank] or gen == 1
                    seen_gens[rank] = max(seen_gens[rank], gen)
                step += 1
                r.beat(rank, step=step)
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    def sweeper():
        try:
            while time.monotonic() < stop:
                # each swept rank was atomically removed inside sweep();
                # it may already be BACK by now (a racing rejoin — the
                # very churn under test), so only coherence is asserted
                for m in r.sweep():
                    gen = r.generation(m)
                    assert gen is None or gen >= 1
                time.sleep(0.002)
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    threads = [
        threading.Thread(target=rejoiner, args=(f"w{i}",))
        for i in range(4)
    ] + [threading.Thread(target=sweeper) for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    members = r.members()
    assert len(members) == len(set(members))
    for rank, gen in seen_gens.items():
        cur = r.generation(rank)
        if cur is not None:
            assert cur >= gen  # never backwards


# ---------------------------------------------------------------------------
# TauController — straggler-adaptive tau
# ---------------------------------------------------------------------------


def _rated_roster(rates):
    """Roster with planted step rates (rate = steps per 10 fake secs)."""
    t = [0.0]
    r = ms.Roster("t", evict_after_s=1e9, clock=lambda: t[0])
    for w in rates:
        r.join(w)
        r.beat(w, step=0)
    t[0] = 10.0
    for w, rate in rates.items():
        r.beat(w, step=int(rate * 10))
    return r


def test_tau_controller_equalizes_wall_cadence():
    r = _rated_roster({"fast": 20.0, "mid": 10.0, "slow": 5.0})
    ctrl = ms.TauController(8, r)
    # tau scales with relative step rate: the straggler exchanges after
    # FEWER local steps, the fast rank after more — same wall cadence
    assert ctrl.tau_for("mid") == 8
    assert ctrl.tau_for("fast") == 16
    assert ctrl.tau_for("slow") == 4
    assert ctrl.tau_for("unknown") == 8  # no signal: static tau


def test_tau_controller_bounds():
    r = _rated_roster({"fast": 1000.0, "mid": 10.0, "slow": 0.5})
    ctrl = ms.TauController(8, r, tau_min=2, tau_max=32)
    assert ctrl.tau_for("fast") == 32
    assert ctrl.tau_for("slow") == 2


def test_tau_controller_prefers_live_doctor_straggler_index():
    """ISSUE 13 satellite: with a live source installed, τ scales from
    the doctor's span-level per-rank straggler index (rate ∝ 1−index),
    not the roster's beat-rate proxy — the roster here would say the
    OPPOSITE (it rates 'rank1' fast), so a wrong source is visible."""
    r = _rated_roster({1: 20.0, 2: 10.0, 3: 5.0})
    live = {"easgd_rank1": 0.5, "easgd_rank2": 0.0, "easgd_rank3": 0.75}
    ctrl = ms.TauController(8, r, live_source=lambda: live)
    # speeds (1-idx): rank1 0.5, rank2 1.0, rank3 0.25; median 0.5
    assert ctrl.tau_for(1) == 8    # at the median
    assert ctrl.tau_for(2) == 16   # the fast rank earns a longer τ
    assert ctrl.tau_for(3) == 4    # the straggler exchanges sooner
    # a member the live window does not cover falls back to the proxy
    r.join(4)


def test_tau_controller_falls_back_to_proxy_when_live_plane_off():
    r = _rated_roster({1: 20.0, 2: 10.0, 3: 5.0})
    # source returning None (no closed window yet), a single-rank
    # window (no relative signal), and a RAISING source all fall back
    for src in (lambda: None, lambda: {"rank1": 0.5},
                lambda: (_ for _ in ()).throw(RuntimeError("down"))):
        ctrl = ms.TauController(8, r, live_source=src)
        assert ctrl.tau_for(1) == 16  # the beat-rate proxy's answer
        assert ctrl.tau_for(3) == 4


def test_live_straggler_source_reads_latest_window_with_stragglers():
    class FakeAgg:
        def __init__(self, windows):
            self._w = windows

        def recent_windows(self):
            return self._w

    win = {
        "window": 3,
        "stragglers": {"per_rank": {
            "rank1": {"straggler_index": 0.0},
            "rank2": {"straggler_index": 0.6},
        }},
    }
    empty = {"window": 4}  # newest window closed without span data
    src = ms.live_straggler_source(FakeAgg([win, empty]))
    assert src() == {"rank1": 0.0, "rank2": 0.6}
    assert ms.live_straggler_source(FakeAgg([empty]))() is None
    assert ms.live_straggler_source(FakeAgg([]))() is None


# ---------------------------------------------------------------------------
# retry_with_backoff — the exchange-leg discipline
# ---------------------------------------------------------------------------


def test_retry_with_backoff_retries_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("down")
        return "ok"

    out = ms.retry_with_backoff(flaky, attempts=4, base_backoff_s=0.001)
    assert out == "ok"
    assert len(calls) == 3


def test_retry_with_backoff_exhausts_and_reraises():
    calls = []

    def dead():
        calls.append(1)
        raise TimeoutError("never")

    with pytest.raises(TimeoutError):
        ms.retry_with_backoff(dead, attempts=3, base_backoff_s=0.001)
    assert len(calls) == 3  # bounded, not infinite


# ---------------------------------------------------------------------------
# EasgdServerCore — the membership-aware exchange protocol
# ---------------------------------------------------------------------------


def _core(**kw):
    from theanompi_tpu.parallel.distributed_async import EasgdServerCore

    kw.setdefault("evict_after_s", 1.0)
    return EasgdServerCore({"w": np.ones(8, np.float32)}, 0.5, **kw)


def test_easgd_core_eviction_unblocks_boundary():
    t = [0.0]
    core = _core(clock=lambda: t[0])
    core.handler({"kind": "join", "rank": 1})
    core.handler({"kind": "join", "rank": 2})
    w = {"w": np.zeros(8, np.float32)}
    core.handler({"kind": "exchange", "rank": 1, "step": 2, "params": w})
    core.handler({"kind": "exchange", "rank": 2, "step": 2, "params": w})
    core.handler({"kind": "epoch", "rank": 1, "epoch": 0})
    assert core.expected_reports() == 2
    assert not core.boundary_ready(0)  # rank 2 hasn't reported
    t[0] = 5.0
    core.handler({"kind": "exchange", "rank": 1, "step": 4, "params": w})
    assert core.sweep() == [2]
    assert core.expected_reports() == 1
    assert core.boundary_ready(0)  # the dead rank no longer blocks


def test_easgd_core_readmission_pulls_center_without_pollution():
    t = [0.0]
    core = _core(clock=lambda: t[0])
    core.handler({"kind": "join", "rank": 1})
    w = {"w": np.zeros(8, np.float32)}
    core.handler({"kind": "exchange", "rank": 1, "step": 2, "params": w})
    t[0] = 5.0
    assert core.sweep() == [1]
    c_before = core.center["w"].copy()
    n_ex = core.n_exchanges
    stale = {"w": np.full(8, 99.0, np.float32)}
    rep = core.handler(
        {"kind": "exchange", "rank": 1, "step": 3, "params": stale}
    )
    assert rep["readmitted"] is True
    assert rep["generation"] == 2
    np.testing.assert_allclose(rep["params"]["w"], c_before)
    np.testing.assert_allclose(core.center["w"], c_before)  # untouched
    assert core.n_exchanges == n_ex  # a re-admission is not an exchange
    assert core.readmissions == 1
    # the NEXT exchange is elastic again
    rep2 = core.handler(
        {"kind": "exchange", "rank": 1, "step": 4, "params": w}
    )
    assert "readmitted" not in rep2
    assert core.n_exchanges == n_ex + 1


def test_easgd_core_done_and_failed_accounting():
    core = _core()
    core.handler({"kind": "join", "rank": 1})
    core.handler({"kind": "join", "rank": 2})
    core.handler({"kind": "done", "rank": 1})
    assert not core.all_gone()
    assert core.expected_reports() == 2  # finisher still counts (it
    # already reported every boundary)
    core.handler({"kind": "done", "rank": 2, "failed": True})
    assert core.all_gone()
    assert core.expected_reports() == 1  # the failure expects nothing


def test_easgd_core_q8_reply_residual_reset_on_rejoin():
    """Satellite: EF/mailbox residual reset on rejoin, numpy oracle.

    The q8 reply leg is EF-compensated per worker with the residual in
    the member's roster state.  After evict + rejoin, the reply
    sequence must be BIT-IDENTICAL to a fresh server given the same
    exchanges — any surviving residual (stale-residual corruption)
    breaks the equality."""
    rng = np.random.RandomState(0)
    center = {"w": rng.randn(256).astype(np.float32)}
    pushes = [
        {"w": rng.randn(256).astype(np.float32)} for _ in range(3)
    ]

    def replies(core):
        out = []
        for i, p in enumerate(pushes):
            rep = core.handler(
                {"kind": "exchange", "rank": 1, "step": i + 1,
                 "params": {"w": p["w"].copy()}}
            )
            if not rep.get("readmitted"):
                out.append(rep["params"])
        return out

    from theanompi_tpu.parallel.distributed_async import EasgdServerCore

    t = [0.0]
    a = EasgdServerCore(
        {"w": center["w"].copy()}, 0.5, wire_dtype="q8",
        evict_after_s=1.0, clock=lambda: t[0],
    )
    a.handler({"kind": "join", "rank": 1})
    replies(a)  # accumulate reply-leg EF residual
    st = a.roster.state(1)
    assert st.get("reply_ef") is not None  # the residual exists...
    t[0] = 10.0
    assert a.sweep() == [1]
    # ...and died with the eviction
    assert not st

    # re-admitted worker's view == a FRESH server's view, bit for bit
    center_now = {"w": a.center["w"].copy()}
    rep = a.handler(
        {"kind": "exchange", "rank": 1, "step": 4,
         "params": {"w": pushes[0]["w"].copy()}}
    )
    assert rep["readmitted"] is True
    a_replies = replies(a)

    b = EasgdServerCore({"w": center_now["w"].copy()}, 0.5,
                        wire_dtype="q8")
    b.handler({"kind": "join", "rank": 1})
    b_replies = replies(b)
    assert len(a_replies) == len(b_replies) == 3
    for ra, rb in zip(a_replies, b_replies):
        np.testing.assert_array_equal(ra["w"]["q"], rb["w"]["q"])
        np.testing.assert_array_equal(ra["w"]["s"], rb["w"]["s"])


def test_easgd_core_adaptive_tau_hints():
    t = [0.0]
    core = _core(base_tau=8, adaptive_tau=True, clock=lambda: t[0])
    for r in (1, 2):
        core.handler({"kind": "join", "rank": r})
    w = {"w": np.zeros(8, np.float32)}
    core.handler({"kind": "exchange", "rank": 1, "step": 0, "params": w})
    core.handler({"kind": "exchange", "rank": 2, "step": 0, "params": w})
    t[0] = 10.0
    rep_fast = core.handler(
        {"kind": "exchange", "rank": 1, "step": 200, "params": w}
    )
    rep_slow = core.handler(
        {"kind": "exchange", "rank": 2, "step": 50, "params": w}
    )
    assert rep_fast["tau"] > rep_slow["tau"]  # cadence equalized


# ---------------------------------------------------------------------------
# EASGD worker degraded mode (no server, no model — loop logic only)
# ---------------------------------------------------------------------------


class _FlakyServer:
    def __init__(self, fail_times):
        self.fail_times = fail_times
        self.calls = 0
        self.tau_hint = None

    def exchange(self, params, rank=None, step=None):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise ConnectionError("server down")
        return {"w": np.zeros(2, np.float32)}

    def suggest_tau(self, rank=None, default=None):
        return self.tau_hint or default


def _worker_stub(server, tau=2, adaptive_tau=False):
    from theanompi_tpu.parallel.async_workers import EASGD_Worker
    from theanompi_tpu.runtime.recorder import Recorder

    w = object.__new__(EASGD_Worker)
    w.rank = 0
    w.recorder = Recorder(verbose=False)
    w.server = server
    w.tau = tau
    w.adaptive_tau = adaptive_tau
    w._degraded = False
    w.n_degraded_steps = 0
    w.n_exchange_failures = 0
    w.get_params = lambda: {"w": np.ones(2, np.float32)}
    w.applied = []
    w.set_params = w.applied.append
    return w


def test_easgd_worker_degrades_and_recovers_without_raising():
    srv = _FlakyServer(fail_times=2)
    w = _worker_stub(srv)
    w._exchange(2)  # fails → degraded, NOT raised
    assert w._degraded and w.n_exchange_failures == 1
    assert w.applied == []  # params untouched on failure
    w._exchange(4)  # still down
    assert w.n_exchange_failures == 2
    w._exchange(6)  # server back → recovered
    assert not w._degraded
    assert len(w.applied) == 1


def test_easgd_worker_applies_adaptive_tau_hint():
    srv = _FlakyServer(fail_times=0)
    srv.tau_hint = 7
    w = _worker_stub(srv, tau=2, adaptive_tau=True)
    w._exchange(2)
    assert w.tau == 7


# ---------------------------------------------------------------------------
# GOSGD: biased peer selection + snapshot grant mass conservation
# ---------------------------------------------------------------------------


class _TableMailbox:
    """Mailbox stub with a membership table (the adapter surface)."""

    def __init__(self, live, weights=None, n_ranks=4):
        self.n_ranks = n_ranks
        self._live = live
        self._weights = weights
        self.sent = []

    def live_peers(self):
        return list(self._live)

    def peer_weights(self, peers):
        return [self._weights[p] for p in peers]

    def send(self, dst, msg):
        self.sent.append((dst, msg))

    def drain(self, rank=None):
        return []


def _gosgd_stub(mailbox, weight=0.5, p_push=1.0):
    from theanompi_tpu.parallel.async_workers import GOSGD_Worker
    from theanompi_tpu.runtime.recorder import Recorder

    w = object.__new__(GOSGD_Worker)
    w.rank = 0
    w.recorder = Recorder(verbose=False)
    w.mailbox = mailbox
    w.p_push = p_push
    w.weight = weight
    w._np_rng = np.random.RandomState(0)
    w.n_pushes = 0
    w.n_merges = 0
    w.n_push_failures = 0
    w.get_params = lambda: {"w": np.ones(2, np.float32)}
    return w


def test_gosgd_pick_peer_only_targets_live_members():
    mb = _TableMailbox(live=[2], weights={2: 1.0})
    w = _gosgd_stub(mb)
    for _ in range(20):
        assert w._pick_peer() == 2  # rank 1 and 3 are not live
    mb._live = []
    assert w._pick_peer() is None  # nobody known-alive: no push


def test_gosgd_pick_peer_biased_away_from_straggler():
    mb = _TableMailbox(live=[1, 2], weights={1: 1.0, 2: 0.25})
    w = _gosgd_stub(mb)
    picks = [w._pick_peer() for _ in range(400)]
    # 4:1 weights → the straggler gets roughly 20% of the pushes
    frac_straggler = picks.count(2) / len(picks)
    assert 0.1 < frac_straggler < 0.35
    assert picks.count(1) > picks.count(2)


def test_gosgd_snapshot_grant_conserves_mass():
    """A snapshot grant IS a directed push: donor halves its weight, so
    total consensus mass is unchanged by a re-admission."""
    mb = _TableMailbox(live=[3], weights={3: 1.0})
    mb.take_snapshot_requests = lambda: [3]
    mb.sweep = lambda: []
    mb.maybe_hello = lambda step=None: None
    w = _gosgd_stub(mb, weight=0.5)
    w._membership_duties(step=7)
    assert w.weight == 0.25
    (dst, (params, sent_w)), = mb.sent
    assert dst == 3 and sent_w == 0.25  # donor half rides the wire


def test_gossip_adapter_membership_over_tcp():
    """hello/bye/evict/snapshot over real localhost TCP mailboxes:
    silent peers are evicted exactly once, a bye leaves cleanly, and a
    need_snapshot hello queues exactly one grant."""
    from theanompi_tpu.parallel.distributed_async import _GossipAdapter
    from theanompi_tpu.parallel.transport import TcpMailbox
    from theanompi_tpu.runtime.multiprocess import find_free_port

    ports = [find_free_port() for _ in range(3)]
    addrs = [("127.0.0.1", p) for p in ports]
    events = []
    a = _GossipAdapter(
        TcpMailbox(0, addrs), 0, evict_after_s=0.4, hello_every_s=0.05,
        on_event=lambda k, m, g: events.append((k, m, g)),
    )
    b = _GossipAdapter(TcpMailbox(1, addrs), 1, evict_after_s=0.4)
    c = _GossipAdapter(TcpMailbox(2, addrs), 2, evict_after_s=0.4)
    try:
        for ad in (a, b, c):
            ad.send_hello(step=1)  # step >= 1 arms eviction
        deadline = time.time() + 15
        while len(a.live_peers()) < 2 and time.time() < deadline:
            a.drain()
            time.sleep(0.02)
        assert sorted(a.live_peers()) == [1, 2]

        # b leaves cleanly; c goes silent
        b.send_bye()
        deadline = time.time() + 15
        while 1 in a.live_peers() and time.time() < deadline:
            a.drain()
            time.sleep(0.02)
        assert 1 not in a.live_peers()
        time.sleep(0.5)
        a.drain()
        assert a.sweep() == [2]
        assert a.sweep() == []  # exactly once
        assert a.roster.n_evictions == 1  # the bye was NOT an eviction

        # c rejoins asking for a snapshot: exactly one queued grant
        c.send_hello(step=0, need_snapshot=True, ranks=[0])
        c.send_hello(step=0, need_snapshot=True, ranks=[0])  # duplicate
        deadline = time.time() + 15
        while 2 not in a.live_peers() and time.time() < deadline:
            a.drain()
            time.sleep(0.02)
        assert a.take_snapshot_requests() == [2]
        assert a.take_snapshot_requests() == []
        kinds = [k for k, m, _ in events if m == 2]
        assert kinds == ["join", "evict", "rejoin"]
    finally:
        for ad in (a, b, c):
            ad.mailbox.close()


def test_compressed_mailbox_residuals_reset_on_membership_churn():
    """Satellite (numpy oracle): the q8 push-leg EF residuals die on
    evict/rejoin — the next frame is packed exactly like a fresh
    sender's (no stale-residual corruption)."""
    from theanompi_tpu.parallel import wire
    from theanompi_tpu.parallel.distributed_async import _CompressedMailbox

    class _Sink:
        n_ranks = 2

        def __init__(self):
            self.frames = []

        def send(self, dst, msg):
            self.frames.append(msg)

    rng = np.random.RandomState(1)
    payloads = [
        {"w": rng.randn(512).astype(np.float32)} for _ in range(3)
    ]
    sink = _CompressedMailbox(_Sink(), "q8")
    for p in payloads:
        sink.send(1, {"w": p["w"].copy()})
    assert sink._residuals  # EF state accumulated
    sink.reset_residuals()
    assert not sink._residuals
    sink.send(1, {"w": payloads[0]["w"].copy()})

    fresh = _CompressedMailbox(_Sink(), "q8")
    fresh.send(1, {"w": payloads[0]["w"].copy()})
    a = sink._inner.frames[-1]["w"]
    b = fresh._inner.frames[-1]["w"]
    np.testing.assert_array_equal(a["q"], b["q"])
    np.testing.assert_array_equal(np.asarray(a["s"]), np.asarray(b["s"]))
    # oracle: both decode to the plain RN quantization of the payload
    np.testing.assert_allclose(
        wire.q8_unpack(a), wire.q8_pack({"w": payloads[0]["w"]})[0] and
        wire.q8_unpack(wire.q8_pack({"w": payloads[0]["w"].copy()})[0])["w"],
    )


# ---------------------------------------------------------------------------
# live plane: exactly one worker_evicted alert per kill (golden)
# ---------------------------------------------------------------------------


def _frame(rank, seq, counters):
    from theanompi_tpu.observability import live

    return {
        "kind": live.FRAME_KIND, "v": live.FRAME_VERSION, "rank": rank,
        "seq": seq, "t_wall": 0.0, "sample_rate": 1, "dropped": 0,
        "spans": {"names": [], "idx": [], "ts": [], "dur": []},
        "ctrs": {"ts": [], "key": [], "val": []},
        "flows": {"b_id": [], "b_ts": [], "f_id": [], "f_ts": []},
        "counters": counters, "hist": {},
    }


def test_worker_evicted_alert_exactly_once_per_kill():
    from theanompi_tpu.observability import live

    agg = live.Aggregator(log=lambda line: None)
    key = 'membership_evictions_total{plane="easgd",rank="1"}'
    agg.ingest(_frame("server", 1, {key: 1.0}))
    v1 = agg.close_window()
    ev = [a for a in v1["alerts"] if a["rule"] == "worker_evicted"]
    assert len(ev) == 1
    assert ev[0]["rank"] == "1"
    assert "easgd" in ev[0]["message"]
    # the counter is cumulative: re-shipping the same total (no new
    # delta) must not re-alert
    v2 = agg.close_window()
    assert not [a for a in v2["alerts"] if a["rule"] == "worker_evicted"]
    # a second kill (fresh delta) alerts exactly once more, and a
    # different rank's eviction carries its own rank label
    key2 = 'membership_evictions_total{plane="gosgd",rank="2"}'
    agg.ingest(_frame("server", 2, {key: 1.0, key2: 1.0}))
    v3 = agg.close_window()
    ev3 = [a for a in v3["alerts"] if a["rule"] == "worker_evicted"]
    assert sorted(a["rank"] for a in ev3) == ["1", "2"]


# ---------------------------------------------------------------------------
# the real drill: kill → evict → respawn → re-admit, cross-process
# ---------------------------------------------------------------------------

@pytest.mark.distributed
def test_easgd_chaos_drill_kill_evict_respawn_readmit(tmp_path):
    """The acceptance drill (ISSUE 10): SIGKILL an EASGD worker
    mid-run.  The server must evict it exactly once, the elastic
    supervisor respawns it, the fresh incarnation re-admits
    checkpointlessly (center pull), no surviving rank sees an
    exception, and the final loss stays within tolerance of the
    uninterrupted baseline."""
    from theanompi_tpu.runtime import chaos

    verdict = chaos.run_drill(
        rule="EASGD",
        n_procs=3,
        kill_rank=1,
        kill_iter=6,
        n_epochs=3,
        tau=1,
        workdir=str(tmp_path),
        timeout=600,
    )
    assert verdict["ok"], verdict["violations"]
    assert verdict["kills_observed"] == 1
    assert verdict["evictions"] == 1  # exactly one eviction per kill
    assert verdict["rejoins"] + verdict["readmissions"] >= 1
    assert verdict["restarts"] == {1: 1}
    assert verdict["loss_delta"] <= verdict["loss_tolerance"]


@pytest.mark.distributed
def test_gosgd_chaos_drill_kill_evict_respawn_readmit(tmp_path):
    """The GOSGD half of the acceptance drill: kill a gossip peer —
    peers evict it from their push tables, the respawn re-admits via a
    peer-snapshot pull at zero weight, and the consensus still lands
    within tolerance."""
    from theanompi_tpu.runtime import chaos

    verdict = chaos.run_drill(
        rule="GOSGD",
        n_procs=3,
        kill_rank=1,
        kill_iter=6,
        n_epochs=3,
        p_push=0.5,
        workdir=str(tmp_path),
        timeout=600,
    )
    assert verdict["ok"], verdict["violations"]
    assert verdict["kills_observed"] == 1
    assert verdict["evictions"] == 1
    assert verdict["rejoins"] + verdict["readmissions"] >= 1
