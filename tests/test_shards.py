"""Raw shard format + native C++ ring loader."""

import numpy as np
import pytest

from theanompi_tpu.data import shards
from theanompi_tpu.data.providers import ImageNetData


def _make_batches(n=4, bs=8, hw=16, seed=0):
    rng = np.random.RandomState(seed)
    return [
        (
            rng.rand(bs, hw, hw, 3).astype(np.float32),
            rng.randint(0, 10, bs).astype(np.int32),
        )
        for _ in range(n)
    ]


def test_native_lib_builds():
    # g++ is baked into this environment; the build must succeed
    assert shards.native_available()


def test_native_lib_never_loads_a_stale_binary(monkeypatch):
    """``native/libtnploader.so`` is git-ignored, so the file on disk may
    be anything.  The library comes from ``shard_loader.cpp`` or the
    failure is loud: a build that fails raises even with a ``.so`` lying
    there, and a machine without a toolchain warns and reads with NumPy
    — in neither case is the stray file handed to ``dlopen``."""
    import ctypes
    import subprocess

    assert shards.native_available()  # a built .so now exists on disk
    loaded = []
    monkeypatch.setattr(ctypes, "CDLL", lambda p: loaded.append(p))
    monkeypatch.setattr(shards, "_lib", None)

    monkeypatch.setattr(shards, "_lib_tried", False)
    monkeypatch.setattr(
        shards.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 2, "", "g++: boom"),
    )
    with pytest.raises(RuntimeError, match="failed to build"):
        shards._load_lib()

    monkeypatch.setattr(shards, "_lib_tried", False)
    monkeypatch.setattr(shards.shutil, "which", lambda name: None)
    with pytest.warns(RuntimeWarning, match="no C\\+\\+ toolchain"):
        assert shards._load_lib() is None
    assert loaded == []


def test_roundtrip_native(tmp_path):
    batches = _make_batches()
    paths = shards.write_shard_dir(str(tmp_path), batches)
    meta = shards.read_meta(str(tmp_path))
    reader = shards.RawShardReader(paths, meta["x_shape"], meta["y_shape"])
    out = list(reader)
    assert len(out) == len(batches)
    for (x0, y0), (x1, y1) in zip(batches, out):
        np.testing.assert_array_equal(x0, x1)
        np.testing.assert_array_equal(y0, y1)


def test_roundtrip_python_fallback(tmp_path, monkeypatch):
    batches = _make_batches(n=2)
    paths = shards.write_shard_dir(str(tmp_path), batches)
    meta = shards.read_meta(str(tmp_path))
    monkeypatch.setattr(shards, "_load_lib", lambda: None)
    reader = shards.RawShardReader(paths, meta["x_shape"], meta["y_shape"])
    assert reader._h is None  # really on the fallback path
    out = list(reader)
    np.testing.assert_array_equal(out[1][0], batches[1][0])


def test_native_reports_missing_file(tmp_path):
    if not shards.native_available():
        pytest.skip("no native toolchain")
    reader = shards.RawShardReader(
        [str(tmp_path / "nope.raw")], (2, 4, 4, 3), (2,)
    )
    with pytest.raises(IOError):
        next(reader)


def test_truncated_shard_rejected(tmp_path, monkeypatch):
    p = str(tmp_path / "bad.raw")
    with open(p, "wb") as f:
        f.write(b"\x00" * 10)
    monkeypatch.setattr(shards, "_load_lib", lambda: None)
    reader = shards.RawShardReader([p], (2, 4, 4, 3), (2,))
    with pytest.raises(IOError):
        next(reader)


def test_native_aug_available():
    assert shards.native_aug_available()  # v2 lib with the aug entry points


def test_aug_native_matches_numpy_fallback(tmp_path, monkeypatch):
    """The C++ reader-thread aug and the numpy fallback draw the SAME
    keyed splitmix64 stream — batches must be bit-identical."""
    batches = _make_batches(n=3, bs=8, hw=16)
    paths = shards.write_shard_dir(str(tmp_path), batches)
    meta = shards.read_meta(str(tmp_path))
    kw = dict(crop_size=12, mirror=True, aug_seed=42, return_meta=True)
    native = list(
        shards.RawShardReader(paths, meta["x_shape"], meta["y_shape"], **kw)
    )
    monkeypatch.setattr(shards, "_load_lib", lambda: None)
    fallback_reader = shards.RawShardReader(
        paths, meta["x_shape"], meta["y_shape"], **kw
    )
    assert fallback_reader._h is None
    fallback = list(fallback_reader)
    assert len(native) == len(fallback) == 3
    for (xn, yn, mn), (xf, yf, mf) in zip(native, fallback):
        np.testing.assert_array_equal(mn, mf)
        np.testing.assert_array_equal(xn, xf)
        np.testing.assert_array_equal(yn, yf)


def test_aug_output_is_the_declared_crop(tmp_path):
    """Each augmented image must equal the (oh, ow) window of its source
    (mirrored when flip=1) — verified against the returned meta."""
    batches = _make_batches(n=2, bs=4, hw=16)
    paths = shards.write_shard_dir(str(tmp_path), batches)
    meta = shards.read_meta(str(tmp_path))
    reader = shards.RawShardReader(
        paths, meta["x_shape"], meta["y_shape"],
        crop_size=10, mirror=True, aug_seed=7, return_meta=True,
    )
    flips_seen = set()
    for (x_src, y_src), (x, y, m) in zip(batches, reader):
        assert x.shape == (4, 10, 10, 3)
        np.testing.assert_array_equal(y, y_src)
        for i in range(4):
            oh, ow, flip = (int(v) for v in m[i])
            assert 0 <= oh <= 6 and 0 <= ow <= 6
            flips_seen.add(flip)
            win = x_src[i, oh : oh + 10, ow : ow + 10]
            if flip:
                win = win[:, ::-1]
            np.testing.assert_array_equal(x[i], win)
    assert flips_seen == {0, 1}  # both mirror outcomes occur


def test_aug_deterministic_per_seed(tmp_path):
    batches = _make_batches(n=1, bs=8, hw=16)
    paths = shards.write_shard_dir(str(tmp_path), batches)
    meta = shards.read_meta(str(tmp_path))

    def run(seed):
        r = shards.RawShardReader(
            paths, meta["x_shape"], meta["y_shape"],
            crop_size=12, mirror=True, aug_seed=seed,
        )
        return next(iter(r))[0]

    np.testing.assert_array_equal(run(5), run(5))
    assert (run(5) != run(6)).any()


def test_aug_per_image_offsets_differ(tmp_path):
    """Per-IMAGE augmentation (VERDICT round-1 #7): offsets must vary
    within one batch, not one draw for the whole batch."""
    batches = _make_batches(n=1, bs=16, hw=16)
    paths = shards.write_shard_dir(str(tmp_path), batches)
    meta = shards.read_meta(str(tmp_path))
    reader = shards.RawShardReader(
        paths, meta["x_shape"], meta["y_shape"],
        crop_size=8, mirror=True, aug_seed=3, return_meta=True,
    )
    _, _, m = next(iter(reader))
    assert len(np.unique(m[:, 0])) > 1 or len(np.unique(m[:, 1])) > 1


def test_provider_raw_train_aug_in_loader(tmp_path):
    """ImageNetData raw mode with crop configured: train batches arrive
    pre-cropped from the loader; val keeps the deterministic center
    crop; epochs draw different augmentations."""
    bs, hw, crop = 8, 16, 12
    shards.write_shard_dir(str(tmp_path / "train"), _make_batches(2, bs, hw, 1))
    shards.write_shard_dir(str(tmp_path / "val"), _make_batches(1, bs, hw, 2))
    data = ImageNetData(
        batch_size=bs, data_dir=str(tmp_path), image_size=hw, crop_size=crop
    )
    e0 = [x for x, _ in data.train_batches()]
    e1 = [x for x, _ in data.train_batches()]
    assert all(x.shape == (bs, crop, crop, 3) for x in e0)
    assert any((a != b).any() for a, b in zip(e0, e1))  # fresh seed per pass
    (xv, _), = list(data.val_batches())
    assert xv.shape == (bs, crop, crop, 3)


def test_imagenet_provider_raw_mode(tmp_path):
    bs, hw = 8, 16
    shards.write_shard_dir(str(tmp_path / "train"), _make_batches(3, bs, hw, 1))
    shards.write_shard_dir(str(tmp_path / "val"), _make_batches(1, bs, hw, 2))
    data = ImageNetData(batch_size=bs, data_dir=str(tmp_path), image_size=hw)
    assert not data.synthetic
    assert data.raw_meta is not None
    assert data.n_batch_train == 3
    data.shuffle(epoch=0)
    xs = list(data.train_batches())
    assert len(xs) == 3
    assert xs[0][0].shape == (bs, hw, hw, 3)
    vs = list(data.val_batches())
    assert len(vs) == 1


def test_imagenet_provider_train_only_raw_dir(tmp_path):
    bs, hw = 8, 16
    shards.write_shard_dir(str(tmp_path / "train"), _make_batches(2, bs, hw, 1))
    data = ImageNetData(batch_size=bs, data_dir=str(tmp_path), image_size=hw)
    assert data.n_batch_train == 2
    assert data.n_batch_val == 0
    assert list(data.val_batches()) == []
    assert len(list(data.train_batches())) == 2


# -- property-based bounds on the shared aug RNG stream ----------------------

try:
    from hypothesis import given, settings, strategies as st  # noqa: E402
except ModuleNotFoundError:  # noqa: E402 — container without hypothesis:
    # the property tests skip; the rest of the module still collects
    import pytest as _pytest

    class _StrategyStub:
        """Chainable stand-in so module-level strategy expressions
        (st.one_of(...).map(...) etc.) still evaluate."""

        def __call__(self, *a, **k):
            return self

        def __getattr__(self, name):
            return self

    st = _StrategyStub()

    def given(*a, **k):
        return _pytest.mark.skip(reason="hypothesis not installed")

    def settings(*a, **k):
        return lambda f: f


@settings(max_examples=60, deadline=None)
@given(
    st.integers(0, 2**63 - 1),  # seed
    st.integers(0, 2**31 - 1),  # file index
    st.integers(1, 64),         # images per shard
    st.integers(0, 32),         # max_oh
    st.integers(0, 32),         # max_ow
    st.booleans(),              # mirror
)
def test_aug_draws_bounds_property(seed, file_idx, n, max_oh, max_ow, mirror):
    """The splitmix64 stream the C++ loader and numpy fallback SHARE:
    offsets always in range, flips binary (zero when mirror is off),
    deterministic per (seed, file)."""
    oh, ow, flip = shards.aug_draws(seed, file_idx, n, max_oh, max_ow, mirror)
    assert oh.shape == ow.shape == flip.shape == (n,)
    assert (0 <= oh).all() and (oh <= max_oh).all()
    assert (0 <= ow).all() and (ow <= max_ow).all()
    if mirror:
        assert set(np.unique(flip)) <= {0, 1}
    else:
        assert (flip == 0).all()
    oh2, ow2, flip2 = shards.aug_draws(seed, file_idx, n, max_oh, max_ow, mirror)
    np.testing.assert_array_equal(oh, oh2)
    np.testing.assert_array_equal(ow, ow2)
    np.testing.assert_array_equal(flip, flip2)


def test_aug_draws_vary_across_files_and_seeds():
    a = shards.aug_draws(1, 0, 64, 20, 20, True)
    b = shards.aug_draws(1, 1, 64, 20, 20, True)  # next file: new draws
    c = shards.aug_draws(2, 0, 64, 20, 20, True)  # new seed: new draws
    assert any((x != y).any() for x, y in zip(a, b))
    assert any((x != y).any() for x, y in zip(a, c))
