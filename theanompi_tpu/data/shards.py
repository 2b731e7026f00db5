"""Raw shard files + the native C++ ring loader binding.

The reference stored pre-processed ImageNet as hickle/HDF5 ``.hkl`` batch
files read by a spawned loader process (SURVEY.md §3.6).  Our equivalents:

- **raw shards**: ``[x float32 | y int32]`` flat binary per batch —
  written by :func:`write_raw_shard`, shapes carried in a ``meta.json``
  sidecar per directory (no HDF5 C dependency).
- **native ring loader**: ``native/shard_loader.cpp`` (C++ reader thread
  + pre-allocated ring, ctypes ABI). Built with ``make`` from that
  source on first use — a stray prebuilt ``.so`` is never trusted;
  :class:`RawShardReader` uses NumPy reads (with a warning) only when
  the machine has no toolchain at all.
"""

from __future__ import annotations

import ctypes
import json
import os
import shutil
import subprocess
import warnings
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libtnploader.so")

_lib = None
_lib_tried = False


def _load_lib():
    """Build the native loader from ``native/shard_loader.cpp`` and
    load it; None only when the machine has no C++ toolchain (then
    :class:`RawShardReader` uses its NumPy reader, and says so).

    The library always comes from the source git holds: ``make`` runs
    unconditionally (a no-op when the ``.so`` is newer than the
    ``.cpp``), and a ``.so`` that happens to lie in ``native/`` is NEVER
    loaded when the build did not run or did not succeed — the file is
    git-ignored, so it may be anything.  A toolchain that is present
    but fails to build is a bug and raises."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    cxx = os.environ.get("CXX", "g++")
    if shutil.which("make") is None or shutil.which(cxx) is None:
        warnings.warn(
            f"no C++ toolchain (make + {cxx}) to build "
            "native/shard_loader.cpp: raw shards are read by the NumPy "
            "reader",
            RuntimeWarning,
            stacklevel=2,
        )
        return None
    # Serialize the (re)build across processes: N worker ranks start
    # together, and an unlocked `make` race could dlopen a partially
    # written .so. Every process takes the lock before its make; any
    # process that reaches CDLL has therefore waited out all writers.
    import fcntl

    with open(_LIB_PATH + ".lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        built = subprocess.run(
            ["make", "-C", _NATIVE_DIR, "-s"],
            capture_output=True, text=True, timeout=120,
        )
    if built.returncode != 0:
        raise RuntimeError(
            "native/shard_loader.cpp failed to build:\n"
            + (built.stderr or built.stdout)[-2000:]
        )
    lib = ctypes.CDLL(_LIB_PATH)
    lib.tnp_version.restype = ctypes.c_int
    lib.tnp_loader_open.restype = ctypes.c_void_p
    lib.tnp_loader_open.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int,
        ctypes.c_long,
        ctypes.c_long,
        ctypes.c_int,
    ]
    lib.tnp_loader_next.restype = ctypes.c_int
    lib.tnp_loader_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.tnp_loader_error.restype = ctypes.c_char_p
    lib.tnp_loader_error.argtypes = [ctypes.c_void_p]
    lib.tnp_loader_close.argtypes = [ctypes.c_void_p]
    if lib.tnp_version() >= 2:
        lib.tnp_loader_open_aug.restype = ctypes.c_void_p
        lib.tnp_loader_open_aug.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_long,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_ulonglong,
            ctypes.c_int,
        ]
        lib.tnp_loader_next_aug.restype = ctypes.c_int
        lib.tnp_loader_next_aug.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load_lib() is not None


def native_aug_available() -> bool:
    lib = _load_lib()
    return lib is not None and lib.tnp_version() >= 2


# -- splitmix64 twin of the C++ aug RNG (shard_loader.cpp) -------------------
# Keyed on (seed, file index, image index); the numpy fallback draws the
# SAME (oh, ow, flip) stream, so native and fallback batches are
# bit-identical — the property the tests pin.

_PHI_FILE = np.uint64(0x9E3779B97F4A7C15)
_PHI_IMG = np.uint64(0xBF58476D1CE4E5B9)
_PHI_DRAW = np.uint64(0x94D049BB133111EB)


def _mix64(z: np.ndarray) -> np.ndarray:
    z = z.astype(np.uint64, copy=True)
    z ^= z >> np.uint64(30)
    z *= np.uint64(0xBF58476D1CE4E5B9)
    z ^= z >> np.uint64(27)
    z *= np.uint64(0x94D049BB133111EB)
    z ^= z >> np.uint64(31)
    return z


def aug_draws(
    seed: int, file_idx: int, n: int, max_oh: int, max_ow: int, mirror: bool
):
    """(oh, ow, flip) int32 arrays of length n — the keyed splitmix64
    stream both the C++ reader and the numpy fallback use."""
    with np.errstate(over="ignore"):
        base = (
            np.uint64(seed)
            + np.uint64(file_idx) * _PHI_FILE
            + np.arange(n, dtype=np.uint64) * _PHI_IMG
        )
        oh = (_mix64(base) % np.uint64(max_oh + 1)).astype(np.int32)
        ow = (_mix64(base + _PHI_DRAW) % np.uint64(max_ow + 1)).astype(np.int32)
        if mirror:
            flip = (_mix64(base + np.uint64(2) * _PHI_DRAW)
                    & np.uint64(1)).astype(np.int32)
        else:
            flip = np.zeros(n, np.int32)
    return oh, ow, flip


def write_raw_shard(path: str, x: np.ndarray, y: np.ndarray) -> None:
    x = np.ascontiguousarray(x, np.float32)
    y = np.ascontiguousarray(y, np.int32)
    with open(path, "wb") as f:
        f.write(x.tobytes())
        f.write(y.tobytes())


def write_shard_dir(
    dir_path: str, batches: Sequence[Tuple[np.ndarray, np.ndarray]]
) -> List[str]:
    """Write batches as raw shards + meta.json (shapes/dtypes)."""
    os.makedirs(dir_path, exist_ok=True)
    first_x, first_y = batches[0]
    meta = {
        "x_shape": list(first_x.shape),
        "y_shape": list(first_y.shape),
        "x_dtype": "float32",
        "y_dtype": "int32",
        "n_shards": len(batches),
    }
    with open(os.path.join(dir_path, "meta.json"), "w") as f:
        json.dump(meta, f)
    paths = []
    for i, (x, y) in enumerate(batches):
        if x.shape != first_x.shape or y.shape != first_y.shape:
            raise ValueError("all shards must share one batch shape")
        p = os.path.join(dir_path, f"shard_{i:05d}.raw")
        write_raw_shard(p, x, y)
        paths.append(p)
    return paths


def read_meta(dir_path: str) -> dict:
    with open(os.path.join(dir_path, "meta.json")) as f:
        return json.load(f)


class RawShardReader:
    """Iterate (x, y) batches from raw shard files in a given order.

    Uses the C++ ring loader when available (reads run in a native thread
    ahead of consumption), NumPy otherwise. One pass per instance — make
    a new reader per epoch with the shuffled file order, exactly like the
    reference re-listed ``.hkl`` files each epoch.

    **Aug mode** (``crop_size``/``mirror`` with an ``aug_seed``): the
    reference's loader process cropped and mirrored while the GPU
    computed (SURVEY.md §3.6 parallel loading); here the C++ reader
    thread does the same — per-image random crop + horizontal mirror
    fused into the slot fill, so the consumer receives train-ready
    crops. The numpy fallback draws the identical splitmix64
    (oh, ow, flip) stream, so both paths yield bit-identical batches.
    x_shape must be (N, H, W, C) in aug mode.
    """

    def __init__(
        self,
        paths: Sequence[str],
        x_shape: Tuple[int, ...],
        y_shape: Tuple[int, ...],
        depth: int = 3,
        crop_size: Optional[int] = None,
        mirror: bool = False,
        aug_seed: Optional[int] = None,
        return_meta: bool = False,
    ):
        self.paths = list(paths)
        self.x_shape = tuple(x_shape)
        self.y_shape = tuple(y_shape)
        self.x_bytes = int(np.prod(self.x_shape)) * 4
        self.y_bytes = int(np.prod(self.y_shape)) * 4
        self.aug = aug_seed is not None and (bool(crop_size) or mirror)
        self.return_meta = return_meta
        if self.aug:
            if len(self.x_shape) != 4:
                raise ValueError("aug mode needs (N, H, W, C) shards")
            n, h, w, _c = self.x_shape
            ch = int(crop_size) if crop_size and crop_size < h else h
            cw = int(crop_size) if crop_size and crop_size < w else w
            self.out_shape = (n, ch, cw, _c)
            self.crop_h, self.crop_w = ch, cw
            self.mirror = bool(mirror)
            self.aug_seed = int(aug_seed) & 0xFFFFFFFFFFFFFFFF
        else:
            self.out_shape = self.x_shape
        self._lib = _load_lib()
        if self.aug and self._lib is not None and self._lib.tnp_version() < 2:
            self._lib = None  # stale prebuilt lib: numpy fallback
        self._h = None
        if self._lib is not None and self.paths:
            arr = (ctypes.c_char_p * len(self.paths))(
                *[p.encode() for p in self.paths]
            )
            if self.aug:
                n, h, w, _c = self.x_shape
                self._h = self._lib.tnp_loader_open_aug(
                    arr, len(self.paths), n, h, w, _c, self.y_bytes,
                    int(crop_size or 0), int(self.mirror), self.aug_seed,
                    depth,
                )
            else:
                self._h = self._lib.tnp_loader_open(
                    arr, len(self.paths), self.x_bytes, self.y_bytes, depth
                )
        self._i = 0

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        return self

    def _result(self, x, y, meta):
        return (x, y, meta) if self.return_meta else (x, y)

    def __next__(self):
        if self._h:
            x = np.empty(self.out_shape, np.float32)
            y = np.empty(self.y_shape, np.int32)
            if self.aug:
                meta = np.empty((self.x_shape[0], 3), np.int32)
                rc = self._lib.tnp_loader_next_aug(
                    self._h,
                    x.ctypes.data_as(ctypes.c_void_p),
                    y.ctypes.data_as(ctypes.c_void_p),
                    meta.ctypes.data_as(ctypes.c_void_p),
                )
            else:
                meta = None
                rc = self._lib.tnp_loader_next(
                    self._h,
                    x.ctypes.data_as(ctypes.c_void_p),
                    y.ctypes.data_as(ctypes.c_void_p),
                )
            if rc == 1:
                return self._result(x, y, meta)
            err = self._lib.tnp_loader_error(self._h).decode()
            self.close()
            self._i = len(self.paths)  # stay exhausted (no fallback re-read)
            if rc < 0:
                raise IOError(err or "native shard loader failed")
            raise StopIteration
        # NumPy fallback
        if self._i >= len(self.paths):
            raise StopIteration
        file_idx = self._i
        p = self.paths[file_idx]
        self._i += 1
        buf = np.fromfile(p, dtype=np.uint8)
        if buf.nbytes != self.x_bytes + self.y_bytes:
            raise IOError(f"shard {p} has {buf.nbytes} bytes, "
                          f"expected {self.x_bytes + self.y_bytes}")
        x = buf[: self.x_bytes].view(np.float32).reshape(self.x_shape)
        y = buf[self.x_bytes :].view(np.int32).reshape(self.y_shape)
        meta = None
        if self.aug:
            from theanompi_tpu.ops.augment import apply_crop_mirror

            n, h, w, _c = self.x_shape
            oh, ow, flip = aug_draws(
                self.aug_seed, file_idx, n, h - self.crop_h, w - self.crop_w,
                self.mirror,
            )
            x = np.ascontiguousarray(
                apply_crop_mirror(x, oh, ow, flip, self.crop_h, self.crop_w)
            )
            meta = np.stack([oh, ow, flip], axis=1)
        return self._result(x, y, meta)

    def close(self):
        if self._h:
            self._lib.tnp_loader_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
