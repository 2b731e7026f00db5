import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.runtime import (
    Config,
    Recorder,
    batch_sharding,
    make_mesh,
    num_devices,
    replicated_sharding,
)
from theanompi_tpu.runtime.mesh import replicate, shard_batch


def test_eight_fake_devices():
    assert num_devices() == 8


def test_make_mesh_default():
    mesh = make_mesh()
    assert mesh.axis_names == ("dp",)
    assert mesh.devices.shape == (8,)


def test_make_mesh_2d():
    mesh = make_mesh(shape=(4, 2), axis_names=("dp", "mp"))
    assert mesh.devices.shape == (4, 2)


def test_make_mesh_subset():
    mesh = make_mesh(devices=jax.devices()[:4])
    assert mesh.devices.shape == (4,)


def test_make_mesh_bad_shape():
    with pytest.raises(ValueError):
        make_mesh(shape=(3,))


def test_shard_and_replicate():
    mesh = make_mesh()
    x = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    xs = shard_batch(mesh, x)
    assert xs.sharding == batch_sharding(mesh)
    p = replicate(mesh, {"w": np.ones((4,), np.float32)})
    assert p["w"].sharding == replicated_sharding(mesh)
    # psum over the sharded batch equals the host sum
    np.testing.assert_allclose(np.asarray(jnp.sum(xs)), x.sum())


def test_config_merge_and_typo():
    c = Config({"lr": 0.1, "batch_size": 128}, lr=0.01)
    assert c.lr == 0.01
    assert c.batch_size == 128
    c.momentum = 0.9
    assert c["momentum"] == 0.9
    assert "momentum" in c
    with pytest.raises(AttributeError):
        _ = c.battch_size
    d = c.asdict()
    assert d["lr"] == 0.01


def test_recorder_phases_and_save(tmp_path):
    r = Recorder(print_freq=2, verbose=False, save_dir=str(tmp_path))
    for i in range(1, 5):
        r.start("calc")
        r.end("calc")
        r.start("comm")
        r.end("comm")
        r.train_error(i, cost=1.0 / i, error=0.5)
        r.print_train_info(i)
    assert len(r.history) == 2
    r.val_error(4, 0.3, 0.1, 0.05)
    path = r.save()
    rows = Recorder.load(path)
    kinds = {row["kind"] for row in rows}
    assert kinds == {"train", "val"}
    assert all("calc" in row for row in rows if row["kind"] == "train")


def test_recorder_unmatched_end_is_zero():
    r = Recorder(verbose=False)
    assert r.end("comm") == 0.0


def test_config_pickle_roundtrip():
    import copy
    import pickle

    c = Config({"lr": 0.1, "bs": 64})
    c2 = pickle.loads(pickle.dumps(c))
    assert c2.lr == 0.1 and c2.bs == 64
    c3 = copy.deepcopy(c)
    assert c3.asdict() == c.asdict()


def test_recorder_save_flushes_partial_window(tmp_path):
    r = Recorder(print_freq=40, verbose=False, save_dir=str(tmp_path))
    for i in range(1, 6):  # fewer than print_freq iterations
        r.train_error(i, cost=2.0, error=1.0)
        r.print_train_info(i)
    rows = Recorder.load(r.save())
    train = [x for x in rows if x["kind"] == "train"]
    assert len(train) == 1 and train[0]["cost"] == 2.0


def test_init_distributed_single_host_noop(monkeypatch):
    from theanompi_tpu.runtime import mesh as mesh_mod

    for k in (*mesh_mod._MULTIHOST_ENV_MARKERS, "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(k, raising=False)
    assert mesh_mod.init_distributed() is False


def test_single_entry_hostnames_is_single_host(monkeypatch):
    from theanompi_tpu.runtime import mesh as mesh_mod

    for k in mesh_mod._MULTIHOST_ENV_MARKERS:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert mesh_mod._env_says_multihost() is False
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h1,h2")
    assert mesh_mod._env_says_multihost() is True


def test_model_describe():
    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.runtime.mesh import make_mesh as mk

    m = Cifar10_model(
        config=dict(batch_size=4, n_synth_train=64, n_synth_val=32,
                    grad_accum=2, zero1=True),
        mesh=mk(),
    )
    text = m.describe()
    assert "Cifar10_model" in text and "dp=8" in text
    assert "zero1" in text and "grad_accum=2" in text
    assert f"{m.n_params:,}" in text


def test_multihost_env_with_failed_autodetect_hard_fails(monkeypatch):
    """Pod-looking env + no coordinator must raise, not silently train N
    unsynced replicas (the override env var restores the old degrade)."""
    from theanompi_tpu.runtime import mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "_distributed_initialized", False)
    monkeypatch.setattr(mesh_mod, "_distributed_gave_up", False)
    monkeypatch.setenv("CLOUD_TPU_TASK_ID", "0")
    monkeypatch.delenv("THEANOMPI_TPU_ALLOW_DEGRADED", raising=False)

    def boom(**kw):
        raise ValueError("no coordinator found")

    monkeypatch.setattr(mesh_mod.jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="UNSYNCED"):
        mesh_mod.init_distributed()

    monkeypatch.setenv("THEANOMPI_TPU_ALLOW_DEGRADED", "1")
    with pytest.warns(RuntimeWarning, match="SINGLE-HOST"):
        assert mesh_mod.init_distributed() is False


def test_recorder_tensorboard_mirror(tmp_path):
    """tensorboard_dir mirrors the record to TB event files (SURVEY §6
    metrics row: JSONL + optional TensorBoard writer)."""
    pytest.importorskip("torch.utils.tensorboard")
    from theanompi_tpu.runtime.recorder import Recorder

    tb = tmp_path / "tb"
    rec = Recorder(print_freq=2, verbose=False, save_dir=str(tmp_path),
                   tensorboard_dir=str(tb))
    for i in range(1, 5):
        rec.train_error(i, 1.0, 0.5)
        rec.print_train_info(i)
    rec.val_error(4, 0.9, 0.4, 0.1)
    rec.log_event("comm_fraction", frac=0.25)
    rec.start_epoch()
    rec.end_epoch(4, 0)
    rec.save()
    rec.close()
    events = [f for f in tb.iterdir() if "tfevents" in f.name]
    assert events and events[0].stat().st_size > 0
    # JSONL record still written alongside
    assert (tmp_path / "record_rank0.jsonl").exists()


def test_recorder_without_tensorboard_unchanged(tmp_path):
    from theanompi_tpu.runtime.recorder import Recorder

    rec = Recorder(print_freq=1, verbose=False, save_dir=str(tmp_path))
    rec.train_error(1, 2.0, 1.0)
    rec.print_train_info(1)
    rec.save()
    rec.close()  # no-op without a writer
    assert (tmp_path / "record_rank0.jsonl").exists()
