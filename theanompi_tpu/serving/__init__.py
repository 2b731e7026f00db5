"""theanompi_tpu.serving — TPU-native inference for the transformer LM.

The training side of the train→serve gap is closed by the rest of the
framework (BSP over a mesh, ZeRO, checkpoints); this package closes the
serving side with the same sharded-parameter machinery:

- ``paging``    — the one engine, ``PagedServingEngine``, over the
  paged KV cache: a refcounted fixed-size block pool (``BlockPool``),
  hash-consed prefix reuse (``PrefixCache``), jit-compiled batched,
  chunked multi-slot prefill and single-token decode through block
  tables, on the model's own ``build_mesh()`` mesh.  It holds no
  model's body: it picks the programs of the model's block family.
- ``dense``, ``latent`` — the two program families (``DensePrograms``:
  the pre-LN stack over a learned position table; ``LatentPrograms``:
  latent attention and routed experts): a pool's layout and the bodies
  of the prefill/verify and decode programs.
- ``engine``    — what every program shares: ``host_input``, the trash
  block, the prefill bucket ladder.
- ``scheduler`` — continuous batching: an admission queue feeding a fixed
  set of decode slots, join-on-finish slot and block recycling, no
  recompiles as requests come and go.
- ``loader``    — restore a *training* checkpoint
  (``utils/checkpoint.restore``) and re-lay the params into inference
  sharding (reusing ``TransformerLM._build_param_specs``).
- ``metrics``   — per-request TTFT / TPOT / throughput counters emitted
  through ``runtime.recorder.Recorder.log_event`` (and, via the
  observability bus, into the process-wide metrics registry /
  trace timeline) so serving shares the training observability
  pipeline.
- ``sampling``  — temperature / top-k stochastic sampling on the decode
  path: seeded per-request PRNG keys, ``temperature=0`` preserved as
  exact greedy, zero recompiles across sampling-config changes.
- ``spec``      — speculative decoding: a draft ``TransformerLM``
  (``models.transformer.make_draft``) proposes k tokens per round and
  the target verifies all of them in ONE batched paged dispatch
  (``PagedServingEngine.verify_chunks``); greedy and sampled streams
  are token-identical to the non-speculative path by construction.
- ``radix``     — the prefix cache generalized to a radix tree: LRU
  leaf-first partial eviction (shared trunks survive pool pressure)
  and compact digest summaries for prefix-affinity routing.
- ``fleet``     — the fault-tolerant serving fleet: ``ServeReplica``
  (one engine behind the request/reply protocol) and ``FleetRouter``
  (prefix-affine admission, roster heartbeats piggybacked on poll
  replies, kill→evict→re-admit with token-identical journaled
  replay, drain-on-leave, 503 shedding).  See ``docs/fleet.md``.

Bench entry point: ``bench_serve.py`` at the repo root (hooked from
``bench.py`` via ``THEANOMPI_BENCH_SERVE=1``) produces the
``BENCH_serve`` JSON under a synthetic Poisson workload.
"""

from theanompi_tpu.serving.fleet import FleetRouter, ServeReplica
from theanompi_tpu.serving.loader import load_engine, restore_params_for_serving
from theanompi_tpu.serving.metrics import ServingMetrics
from theanompi_tpu.serving.paging import (
    BlockPool,
    PagedServingEngine,
    PrefixCache,
)
from theanompi_tpu.serving.radix import RadixPrefixCache
from theanompi_tpu.serving.sampling import Sampler
from theanompi_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    SchedulerDraining,
)
from theanompi_tpu.serving.spec import SpecDecoder

__all__ = [
    "PagedServingEngine",
    "BlockPool",
    "PrefixCache",
    "RadixPrefixCache",
    "ContinuousBatchingScheduler",
    "Request",
    "SchedulerDraining",
    "Sampler",
    "ServingMetrics",
    "SpecDecoder",
    "FleetRouter",
    "ServeReplica",
    "load_engine",
    "restore_params_for_serving",
]
