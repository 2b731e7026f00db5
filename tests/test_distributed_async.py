"""Cross-PROCESS EASGD / GOSGD over the TCP transport (VERDICT round-1
#2; SURVEY.md §4.3/§4.4, §8.1).

The reference ran its async rules as MPI processes; round 1 only ever
exchanged through an in-process queue.  These tests spawn real OS
processes: EASGD's server rank serves elastic exchanges over TCP and
checkpoints/validates the center per epoch; GOSGD peers gossip over
their TCP mailboxes and rank 0 writes the consensus.
"""

import json

import numpy as np
import pytest

from theanompi_tpu.runtime.multiprocess import find_free_port, spawn_local

CFG = (
    '{"batch_size": 16, "n_epochs": 2, "n_synth_train": 128, '
    '"n_synth_val": 64, "dropout_rate": 0.0, "print_freq": 1000, '
    '"comm_probe": false, "seed": 5}'
)

# no cache variables: the spawned ranks place their compile cache by the
# repo's one rule (theanompi_tpu/cachedir.py)


@pytest.mark.distributed
def test_easgd_across_processes(tmp_path):
    """1 server + 2 worker processes: exchanges cross the process
    boundary, the center is checkpointed + validated per epoch, and the
    final center model is saved by the server."""
    port = find_free_port()
    spawn_local(
        3,
        [
            "--rule", "EASGD", "--config", CFG,
            "--checkpoint-dir", str(tmp_path),
            "--tau", "2",
            "--async-port-base", str(port),
            # strict per-epoch duties: this test pins one row/checkpoint
            # per epoch; coalescing (the default) is timing-dependent
            "--duties-coalesce", "0",
        ],
        local_device_count=1,
        timeout=600,
        stream_output=False,
    )
    names = sorted(f.name for f in tmp_path.iterdir())
    assert "ckpt_center_0001.npz" in names
    assert "ckpt_center_0002.npz" in names
    assert "ckpt_center.npz" in names
    # the server validated the center DURING training
    rows = [
        json.loads(l)
        for l in (tmp_path / "record_server.jsonl").read_text().splitlines()
    ]
    assert len([r for r in rows if r["kind"] == "val"]) == 2
    # the two epoch snapshots differ: exchanges actually moved the center
    from theanompi_tpu.utils import checkpoint as ckpt

    c1 = ckpt.restore(str(tmp_path / "ckpt_center_0001.npz"))["params"]
    c2 = ckpt.restore(str(tmp_path / "ckpt_center_0002.npz"))["params"]
    diffs = [
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(
            [x for x in _leaves(c1)], [x for x in _leaves(c2)]
        )
    ]
    assert max(diffs) > 0


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


@pytest.mark.distributed
def test_gosgd_across_processes(tmp_path):
    """2 peer processes gossiping over TCP; rank 0 writes the consensus
    checkpoint after collecting every peer's final (params, weight)."""
    port = find_free_port()
    spawn_local(
        2,
        [
            "--rule", "GOSGD", "--config", CFG,
            "--checkpoint-dir", str(tmp_path),
            "--p-push", "0.5",
            "--async-port-base", str(port),
        ],
        local_device_count=1,
        timeout=600,
        stream_output=False,
    )
    assert (tmp_path / "ckpt_consensus.npz").exists()
    from theanompi_tpu.utils import checkpoint as ckpt

    blob = ckpt.restore(str(tmp_path / "ckpt_consensus.npz"))
    for leaf in _leaves(blob["params"]):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.distributed
def test_easgd_fp16_wire_across_processes(tmp_path):
    """--wire-dtype float16: exchanges carry fp16 payloads (reference's
    fp16 exchange story on the async path) and the run still trains,
    validates, and checkpoints the center."""
    port = find_free_port()
    spawn_local(
        3,
        [
            "--rule", "EASGD", "--config", CFG,
            "--checkpoint-dir", str(tmp_path),
            "--tau", "2",
            "--async-port-base", str(port),
            "--wire-dtype", "float16",
        ],
        local_device_count=1,
        timeout=600,
        stream_output=False,
    )
    assert (tmp_path / "ckpt_center.npz").exists()
    from theanompi_tpu.utils import checkpoint as ckpt

    blob = ckpt.restore(str(tmp_path / "ckpt_center.npz"))
    for leaf in _leaves(blob["params"]):
        a = np.asarray(leaf)
        assert np.isfinite(a).all()
        if a.dtype.kind == "f":
            assert a.dtype == np.float32  # wire dtype never leaks into state
    # the server RECORDS what dtype actually rode the wire — a refactor
    # that silently drops the compression turns this row float32
    rows = [
        json.loads(l)
        for l in (tmp_path / "record_server.jsonl").read_text().splitlines()
    ]
    wire_rows = [r for r in rows if r["kind"] == "async_wire"]
    assert wire_rows and wire_rows[0]["dtype"] == "float16"
    assert wire_rows[0]["n_exchanges"] > 0


# ---------------------------------------------------------------------------
# GOSGD mass-frame ack protocol (VERDICT r3 #6)
# ---------------------------------------------------------------------------


def test_gossip_ack_protocol_unit():
    """Adapter-level ack flow over real localhost TCP: an acked push is
    not reclaimed; an unacked one is reclaimed exactly once; a resent
    final is deduped by (src, seq)."""
    import time

    from theanompi_tpu.parallel.distributed_async import _GossipAdapter
    from theanompi_tpu.parallel.transport import TcpMailbox

    ports = [find_free_port(), find_free_port()]
    addrs = [("127.0.0.1", p) for p in ports]
    a = _GossipAdapter(TcpMailbox(0, addrs), 0, ack_timeout=1.0)
    b = _GossipAdapter(TcpMailbox(1, addrs), 1, ack_timeout=1.0)
    try:
        # acked push: b drains (acks), a sees the ack -> nothing pending
        a.send(1, ({"w": np.ones(3, np.float32)}, 0.25))
        deadline = time.time() + 15
        got = []
        while not got and time.time() < deadline:
            got = b.drain()
            time.sleep(0.02)
        assert len(got) == 1 and float(got[0][1]) == 0.25
        while a._pending and time.time() < deadline:
            a.drain()  # processes b's ack
            time.sleep(0.02)
        assert a.reclaim_expired() == 0.0
        assert not a._pending

        # unacked push: b stops accepting (post-final) -> no ack -> a
        # reclaims the exact weight, once
        b.accept_gossip = False
        a.send(1, ({"w": np.ones(3, np.float32)}, 0.125))
        while b.n_dropped < 1 and time.time() < deadline:
            b.drain()  # decodes + drops the push, sends NO ack
            time.sleep(0.02)
        assert b.n_dropped == 1
        time.sleep(1.1)  # past ack_timeout
        a.drain()
        assert a.reclaim_expired() == 0.125
        assert a.reclaim_expired() == 0.0  # exactly once

        # final resend dedupe: b never acks until the second copy
        deadline = time.time() + 15
        seq = a.send_final(1, {"w": np.zeros(2, np.float32)}, 0.5)
        time.sleep(1.1)
        a.resend_overdue_finals()  # second copy on the wire
        while len(b.finals) < 1 and time.time() < deadline:
            b.drain()
            time.sleep(0.02)
        time.sleep(0.3)
        b.drain()  # the duplicate arrives; (src, seq) dedupe eats it
        assert len(b.finals) == 1
        while not a.is_acked(seq) and time.time() < deadline:
            a.drain()
            time.sleep(0.02)
        assert a.is_acked(seq)
    finally:
        a.mailbox.close()
        b.mailbox.close()


@pytest.mark.distributed
def test_gossip_receiver_killed_mid_push_mass_restored(tmp_path):
    """Chaos (VERDICT r3 #6): SIGKILL a receiver PROCESS after a push
    landed on its side of the wire but before it acked — the at-most-
    once window transport.py documents.  The sender's reclaim must
    return total consensus mass to exactly 1.0; before the ack protocol
    this mass silently vanished."""
    import os
    import signal
    import subprocess
    import sys
    import time

    import jax

    from theanompi_tpu.parallel.async_workers import GOSGD_Worker
    from theanompi_tpu.parallel.distributed_async import _GossipAdapter
    from theanompi_tpu.parallel.transport import TcpMailbox
    from theanompi_tpu.runtime.recorder import Recorder

    ports = [find_free_port(), find_free_port()]
    # victim process: binds its mailbox (accepts + decodes frames into
    # its queue) but never acks; killed mid-flight below
    victim = subprocess.Popen(
        [sys.executable, "-c", f"""
import time
from theanompi_tpu.parallel.transport import TcpMailbox
mb = TcpMailbox(1, [("127.0.0.1", {ports[0]}), ("127.0.0.1", {ports[1]})])
print("ready", flush=True)
time.sleep(60)
"""],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.PIPE,
        text=True,
    )
    try:
        assert victim.stdout.readline().strip() == "ready"
        addrs = [("127.0.0.1", p) for p in ports]
        adapter = _GossipAdapter(TcpMailbox(0, addrs), 0, ack_timeout=1.5)
        worker = GOSGD_Worker(
            0,
            jax.devices()[:1],
            "theanompi_tpu.models.cifar10",
            "Cifar10_model",
            dict(batch_size=8, n_synth_train=32, n_synth_val=16,
                 print_freq=1000, comm_probe=False),
            1,
            Recorder(verbose=False),
            mailbox=adapter,
            p_push=1.0,  # push deterministically
            rng=np.random.RandomState(0),
        )
        # the victim is a stub holding no mass: this worker owns all of it
        worker.weight = 1.0
        worker._maybe_push()  # halves to 0.5, frame reaches the victim
        assert worker.weight == 0.5
        assert worker.n_pushes == 1
        # kill the receiver AFTER the push landed on its side
        time.sleep(0.3)
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=30)
        # before the ack deadline: nothing to reclaim yet
        worker._merge_inbox()
        assert worker.weight == 0.5
        time.sleep(1.6)  # past ack_timeout
        worker._merge_inbox()
        assert worker.weight == 1.0, (
            "in-flight mass to a killed receiver was not reclaimed"
        )
        adapter.mailbox.close()
    finally:
        if victim.poll() is None:
            victim.kill()
