"""Prefetching device loader.

Re-creation of the reference's "parallel loading" subsystem (upstream
``proc_load_mpi.py``: a spawned process per worker that loads + augments
the next ``.hkl`` batch and hands GPU buffers over while the current batch
computes; SURVEY.md §3.6 / §8.3 "hidden loading").

TPU-first design: a background **thread** (NumPy loading releases the GIL;
a process would force an extra copy through shared memory) pulls host
batches from the provider, shards them onto the mesh with ``device_put``
(async under JAX dispatch), and keeps ``depth`` batches in flight so the
ICI/MXU step, not input, bounds iteration time.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator, Optional

from theanompi_tpu import observability as obs

_REG = obs.get_registry()
_BATCHES = _REG.counter(
    "data_batches_placed_total", "host batches placed onto the mesh"
)
_DEPTH = _REG.gauge(
    "data_prefetch_depth", "device batches queued ahead of the consumer"
)


class PrefetchLoader:
    """Wrap a host batch iterator; yield device-placed batches.

    ``place`` maps a host batch -> device arrays (e.g. a closure over
    ``mesh.shard_batch``). Exceptions in the worker thread propagate to
    the consumer on the next ``__next__``.
    """

    _SENTINEL = object()

    def __init__(
        self,
        batches: Iterator,
        place: Callable,
        depth: int = 2,
    ):
        self._place = place
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, args=(iter(batches),), daemon=True
        )
        self._thread.start()

    def _run(self, it):
        try:
            for batch in it:
                with obs.span("data_load_place"):
                    placed = self._place(batch)
                self._q.put(placed)
                _BATCHES.inc(mode="prefetch")
                _DEPTH.set(self._q.qsize())
        except BaseException as e:  # surfaced to consumer
            self._err = e
        finally:
            self._q.put(self._SENTINEL)

    def __iter__(self):
        return self

    def __next__(self):
        # 'data_wait' is the consumer-visible stall: ~0 while the
        # prefetch pipeline keeps up, one load-time wide when it starves
        with obs.span("data_wait"):
            item = self._q.get()
        _DEPTH.set(self._q.qsize())
        if item is self._SENTINEL:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item


def prefetch_to_mesh(batches, mesh, depth: int = 2, spec=None):
    """Convenience: shard each (x, y) host batch over the mesh.

    Default places the leading dim over ``dp``; pass an explicit
    ``PartitionSpec`` (e.g. ``P('dp','sp')``) for other layouts such as
    the sequence-parallel transformer's token batches.
    """
    from theanompi_tpu.runtime.mesh import shard_batch

    return PrefetchLoader(
        batches, lambda b: shard_batch(mesh, b, spec=spec), depth=depth
    )
