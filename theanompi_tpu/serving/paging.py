"""Paged KV cache: fixed-size blocks, block tables, prefix reuse.

The PR 1 engine preallocates a worst-case contiguous region per slot
(``init_cache`` reserves ``max_len`` rows for every slot), so cache
memory scales with the *longest imaginable* sequence times the slot
count while real traffic is long-tail: most sequences are short, a few
are huge.  This module decouples a sequence's logical positions from
their physical placement — the same move the Theano-MPI lineage makes
for training (preallocated exchanged buffers, arXiv:1605.08325) and
arXiv:2112.01075 makes for redistribution: only *live* blocks occupy
memory.

Three pieces:

- **BlockPool** — host-side allocator over a device-side flat row pool:
  ``k``/``v``, one array a layer of shape ``(n_blocks * block_size,
  row_width)``, a row holding a token's heads side by side
  (``heads * head_dim`` numbers, rounded up to 128 lanes — see
  ``PagedServingEngine.init_state``).  The layers' arrays are separate
  leaves of the state (never stacked), each donated and updated in
  place by its program.  Block 0 is reserved as the *trash block*: masked or
  inactive lanes scatter their garbage there, so a freed (reallocated)
  block can never be corrupted by a stale lane.  Refcounted — a block
  shared by N sequences (prefix reuse) frees only when the last
  reference drops.
- **PrefixCache** — hash-consed chains of *full, immutable* blocks:
  the digest of (parent digest, block tokens) names a block's exact
  content and position, so two requests sharing a system prompt map
  their shared full blocks to the SAME physical block — prefilled
  once, refcounted across requests.  The final prompt token is never
  served from cache (its logits must be computed), so a match is
  capped at ``(len(prompt) - 1) // block_size`` blocks.
- **PagedServingEngine** — the contiguous engine's forward math
  re-expressed over block tables: prefill and decode gather/scatter
  K/V rows by ``table[block] * block_size + offset`` instead of
  slot-major slicing.  Tables/positions enter the jitted programs as
  *data* (device arrays), never as shapes, so admission, retirement
  and table growth cause ZERO recompiles — one decode program ever,
  one prefill program per chunk bucket.  Prefill is **batched and
  chunked**: the program has a narrow fixed shape (``prefill_rows`` =
  ``PREFILL_ROWS`` lanes × the chunk bucket) and a tick feeds every
  lane that holds unfed prompt tokens, up to ``prefill_chunk`` tokens
  each, in as many calls of that shape as they need — a trickle of
  arrivals pays for the rows it feeds, and a giant prompt cannot hide
  the TTFT of everyone queued behind it.

Decode-speed layers on top (ISSUE 11):

- **kv_dtype='int8'** — K/V live in the pool as int8 with per-row /
  per-head fp32 scales (the ``quantize.quantize_blocks`` codec over
  ``head_dim``, applied once on write).  Quantization is per row, so a
  block's bytes depend only on the tokens it holds — hash-consed
  prefix blocks stay shareable, and chunked prefill remains
  bit-identical to whole-prompt prefill (queries always attend the
  quantized image, never a fresher fp32 copy).  Dequant fuses into the
  attention gather (or runs in-kernel on the Pallas path).  Capacity:
  ``kv_block_bytes()``/``blocks_at_budget()`` turn a byte budget into
  a block count — int8 fits ~4× the fp32 blocks per chip at head_dim
  64 (the ``detail.kv_quant`` probe in bench_serve measures it).
- **verify_chunks** — the chunked-prefill body with logits at EVERY
  chunk position instead of only the last: the speculative-decoding
  verify dispatch (``serving/spec.py``) scores a draft's k proposals
  plus the bonus token in ONE batched call.  Same jitted program for
  every acceptance outcome — rejected tails roll lengths back
  host-side, so acceptance churn recompiles nothing.
- **paged_attn='pallas'** — the decode tick's attention runs the
  fused ``ops.pallas_paged`` kernel: block tables scalar-prefetched
  into the kernel, K/V blocks gathered inside it (int8 dequant
  in-VMEM), online softmax over the block stream.  A pool the kernel
  cannot serve (multi-device mesh — see ``pallas_paged.supported``)
  is refused at construction; ``'auto'`` picks by that same rule.
  Numerics are pinned allclose between the two paths.

Correctness contract (tests/test_serving_paged.py): greedy decode
through block tables is token-identical to the contiguous engine and
to the no-cache recompute baseline; prefix hits change which physical
rows are read, never the values read from them.
"""

from __future__ import annotations

import functools
import hashlib
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu import observability as obs
from theanompi_tpu.runtime.mesh import DATA_AXIS, TP_AXIS
from theanompi_tpu.serving import metrics as smetrics
from theanompi_tpu.serving.engine import (
    _NEG_INF, ServingEngine, host_input,
)

TRASH_BLOCK = 0  # reserved physical block: masked/inactive writes land here

KV_DTYPES = ("fp32", "int8")

# Lanes of the prefill program.  A row is dear (per layer a key and a
# value image of t_pad positions, float32 scores over them, a scatter
# of its chunk into the pool), and in steady traffic a tick has one to
# three lanes to feed, so the program is narrow and a tick with more
# pending lanes calls it again (``scheduler._prefill_pending``).  One
# width, not a ladder: every (rows, bucket) pair is a program to build
# and to warm, and a pair first met under load would compile there.
# What a burst pays for it is one call's fixed cost per
# ``PREFILL_ROWS`` arrivals; that fixed cost is the decode program's
# too: the float32 weights read and cast (what holding them in the
# compute dtype takes away), and until PR 30 a copy of the whole pool.
# Read on a v5e with GPT-2 XL (PERF.md, PR 26): a call of 2 / 4 / 8 /
# 32 rows took 68 / 75 / 106 / 277 ms beside a decode tick of 74; 2
# served a trickle 2 % faster than 4 and doubled what a burst pays.
# With the pool updated in place (PERF.md, PR 30) a call of 4 rows
# takes 31 ms beside a decode tick of 19.
PREFILL_ROWS = 4


class BlockPool:
    """Host-side accounting for the device block pool.

    The pool owns block *identities* (free list + refcounts); the
    device arrays live in the engine state and are threaded through
    the jitted programs.  One pool per scheduler — two schedulers
    sharing an engine each run their own allocation world, exactly
    like two schedulers each calling ``init_cache`` today.
    """

    def __init__(self, n_blocks: int, block_size: int):
        if int(n_blocks) < 2:
            raise ValueError(
                f"n_blocks={n_blocks}: need at least 2 (block 0 is the "
                "reserved trash block)"
            )
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        # block 0 reserved; allocatable ids are 1..n_blocks-1
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self.peak_used = 0
        self._publish()

    # ---- accounting --------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return (self.n_blocks - 1) - len(self._free)

    def ref(self, block: int) -> int:
        return self._ref.get(block, 0)

    def _publish(self) -> None:
        smetrics.BLOCKS_FREE.set(self.n_free)
        smetrics.BLOCKS_USED.set(self.n_used)
        self.peak_used = max(self.peak_used, self.n_used)

    # ---- alloc / retain / release ------------------------------------
    def alloc(self, n: int, rid=None) -> Optional[List[int]]:
        """``n`` fresh blocks (ref 1 each), or None — never a partial
        grant, so a failed admission has nothing to roll back.  ``rid``
        labels the span with the requesting stream (trace-only)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        extra = {"rid": rid} if rid is not None else {}
        with obs.span("block_alloc", n=n, free=len(self._free), **extra):
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._ref[b] = 1
        self._publish()
        return out

    def retain(self, block: int) -> None:
        if self._ref.get(block, 0) < 1:
            raise ValueError(f"retain of unallocated block {block}")
        self._ref[block] += 1

    def release(self, block: int) -> None:
        r = self._ref.get(block, 0)
        if r < 1:
            raise ValueError(f"release of unallocated block {block}")
        if r == 1:
            del self._ref[block]
            self._free.append(block)
            self._publish()
        else:
            self._ref[block] = r - 1

    def release_all(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self.release(b)


class PrefixCache:
    """Hash-consed chains of immutable full blocks.

    A cache entry maps ``digest(parent_digest, block_tokens)`` to a
    physical block id whose K/V rows hold exactly those tokens at
    exactly those positions.  The cache holds one reference per entry,
    so a cached block survives its originating request; ``evict_unused``
    drops every entry nothing else references (the pool-exhaustion
    pressure valve).  Digests are sha1 over token bytes — content
    addressing must not depend on Python's salted ``hash``.
    """

    def __init__(self, pool: BlockPool):
        self.pool = pool
        self.block_size = pool.block_size
        self._entries: Dict[bytes, int] = {}
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _digest(self, parent: bytes, tokens: Sequence[int]) -> bytes:
        h = hashlib.sha1(parent)
        h.update(np.asarray(tokens, dtype=np.int64).tobytes())
        return h.digest()

    def match(self, prompt: Sequence[int], rid=None) -> Tuple[List[int], int]:
        """Longest cached chain of full blocks covering a PREFIX of
        ``prompt``; each matched block is retained for the caller.
        Capped so at least the final prompt token is always prefilled
        (its logits are the request's first decode input).  ``rid``
        labels the span with the matching stream (trace-only)."""
        bs = self.block_size
        limit = (len(prompt) - 1) // bs
        out: List[int] = []
        parent = b""
        extra = {"rid": rid} if rid is not None else {}
        with obs.span("prefix_match", n_prompt=len(prompt), **extra):
            for j in range(limit):
                parent = self._digest(parent, prompt[j * bs:(j + 1) * bs])
                block = self._entries.get(parent)
                if block is None:
                    break
                out.append(block)
        for b in out:
            self.pool.retain(b)
        if out:
            self.hits += 1
            self.hit_tokens += len(out) * bs
            smetrics.PREFIX_HITS.inc()
            smetrics.PREFIX_HIT_TOKENS.inc(len(out) * bs)
        else:
            self.misses += 1
            smetrics.PREFIX_MISSES.inc()
        return out, len(out) * bs

    def insert(self, prompt: Sequence[int], blocks: Sequence[int]) -> int:
        """Register every full block of a just-prefilled prompt.  The
        first ``k`` chain links may already exist (they were the hit);
        new entries retain their block on behalf of the cache.  Returns
        the number of entries added."""
        bs = self.block_size
        added = 0
        parent = b""
        for j in range(len(prompt) // bs):
            parent = self._digest(parent, prompt[j * bs:(j + 1) * bs])
            if parent in self._entries:
                continue  # identical content already cached; keep it
            self._entries[parent] = blocks[j]
            self.pool.retain(blocks[j])
            added += 1
        return added

    def evict_unused(self, need: Optional[int] = None) -> int:
        """Free every cached block whose ONLY reference is the cache
        itself.  Called when allocation fails — cached-but-idle prefix
        memory yields to live sequences before admission backpressures.
        Evicting a parent strands its children unreachable; they have
        ref 1 too, so the same sweep collects them.

        ``need`` (how many blocks the failed allocation wanted) is
        accepted for signature parity with ``radix.RadixPrefixCache``
        and ignored: the flat chain dict cannot tell a hot shared
        trunk from a cold tail, so its only safe pressure valve is the
        full sweep — exactly the behavior the radix tree improves on
        (``docs/fleet.md``)."""
        dropped = 0
        with obs.span("prefix_evict", entries=len(self._entries)):
            for digest in list(self._entries):
                block = self._entries[digest]
                if self.pool.ref(block) == 1:
                    self.pool.release(block)
                    del self._entries[digest]
                    dropped += 1
        return dropped


class PagedServingEngine(ServingEngine):
    """The serving engine over a paged KV cache.

    Shares every weight-math helper with ``ServingEngine`` (identical
    LayerNorm/projection/softmax numerics); replaces slot-major cache
    slicing with block-table gather/scatter.

    Geometry:

    - ``block_size`` — KV rows per block (the allocation granule).
    - ``n_blocks`` — pool capacity *including* the reserved trash
      block; defaults to contiguous parity
      (``n_slots * blocks_per_seq + 1``) so the default engine serves
      exactly what the contiguous one could, and operators shrink it
      (or raise ``n_slots``) to bank the long-tail savings.
    - ``prefill_rows`` — lanes per batched prefill call (fixed shape;
      default ``min(n_slots, PREFILL_ROWS)``).  Nothing an operator
      tunes: the scheduler feeds every pending lane each tick in
      ``ceil(pending / prefill_rows)`` calls, so the width only says
      what one call costs (see ``PREFILL_ROWS``).
    - ``prefill_chunk`` — max prompt tokens one prefill call advances
      a sequence by (None = whole prompt in one chunk).  Chunks pad to
      the ``chunk_buckets`` ladder, one compiled program per bucket.
    - ``kv_dtype`` — ``'fp32'`` (compatibility path: the pool holds
      the compute dtype, bit-identical to PR 8) or ``'int8'``
      (quantized blocks + per-row/head scales; ~4× the blocks per
      byte, greedy drift bounded by the bench probe).
    - ``paged_attn`` — ``'xla'`` (gathered-image attention, the
      GSPMD-partitionable default), ``'pallas'`` (fused in-kernel
      gather; a multi-device pool is refused at construction), or
      ``'auto'`` (the kernel on a single-device pool, XLA on a sharded
      one) — ``paged_attn_effective`` records which runs.
    """

    is_paged = True

    def __init__(
        self,
        model,
        n_slots: int = 4,
        max_len: Optional[int] = None,
        buckets: Optional[Sequence[int]] = None,
        block_size: int = 16,
        n_blocks: Optional[int] = None,
        prefill_rows: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        prefix_cache: bool = True,
        prefix_impl: str = "chain",
        kv_dtype: str = "fp32",
        paged_attn: str = "xla",
    ):
        super().__init__(model, n_slots=n_slots, max_len=max_len,
                         buckets=buckets)
        if int(block_size) < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = int(block_size)
        self.blocks_per_seq = math.ceil(self.max_len / self.block_size)
        # gathered-attention width: every sequence attends over its
        # full table image; equals max_len when block_size divides it
        self.t_pad = self.blocks_per_seq * self.block_size
        if n_blocks is None:
            n_blocks = self.n_slots * self.blocks_per_seq + 1
        self.n_blocks = int(n_blocks)
        if self.n_blocks < 2:
            raise ValueError(
                f"n_blocks={self.n_blocks}: need at least one usable "
                "block plus the reserved trash block.  A pool smaller "
                "than max_len rows is fine — requests that could never "
                "fit are refused at submit()"
            )
        self.prefill_rows = int(
            prefill_rows or min(self.n_slots, PREFILL_ROWS)
        )
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}"
                )
        self.prefill_chunk = prefill_chunk
        cap = prefill_chunk if prefill_chunk is not None else self.buckets[-1]
        self.chunk_buckets = tuple(sorted(
            {b for b in self.buckets if b <= cap} | {cap}
        ))
        self.prefix_cache_enabled = bool(prefix_cache)
        if prefix_impl not in ("chain", "radix"):
            raise ValueError(
                f"prefix_impl must be 'chain' or 'radix', got "
                f"{prefix_impl!r}"
            )
        # 'chain' = the PR 8 flat hash-consed dict (all-or-nothing
        # eviction); 'radix' = serving/radix.py's tree (LRU leaf-first
        # partial eviction + routing summaries — the fleet default).
        # Both serve identical tokens; only eviction/summaries differ.
        self.prefix_impl = prefix_impl
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}"
            )
        self.kv_dtype = kv_dtype
        if paged_attn not in ("xla", "pallas", "auto"):
            raise ValueError(
                f"paged_attn must be 'xla', 'pallas' or 'auto', got "
                f"{paged_attn!r}"
            )
        from theanompi_tpu.ops import pallas_paged

        self.paged_attn = paged_attn
        kernel_ok = pallas_paged.supported(self.mesh)
        if paged_attn == "pallas" and not kernel_ok:
            # a demand the pool cannot meet is an error at engine
            # build, never a quiet swap of kernels; 'auto' is the
            # spelling that lets the pool's layout choose
            raise ValueError(
                "paged_attn='pallas' needs a single-device pool (the "
                f"kernel is a single-shard program); this mesh has "
                f"{self.mesh.devices.size} devices — use 'auto' or 'xla'"
            )
        # the ONE selection rule: fused kernel on a single-device pool,
        # GSPMD-partitionable XLA gather on a sharded one
        self.paged_attn_effective = (
            "pallas" if paged_attn != "xla" and kernel_ok else "xla"
        )
        # pool rows shard over dp only when every per-device shard is a
        # whole number of blocks (a split block would tear the
        # gather/scatter row arithmetic across devices)
        row_ax = (
            DATA_AXIS
            if DATA_AXIS in self.mesh.shape
            and int(self.mesh.shape[DATA_AXIS]) > 1
            and self.n_blocks % int(self.mesh.shape[DATA_AXIS]) == 0
            else None
        )
        head_ax = (
            TP_AXIS
            if TP_AXIS in self.mesh.shape and int(self.mesh.shape[TP_AXIS]) > 1
            else None
        )
        # one layer's pool is (rows, row_width) and its int8 scale plane
        # (rows, heads); one spec for both: rows over dp, a row's heads
        # over tp
        self.pool_spec = P(row_ax, head_ax)
        # a row holds its heads side by side, rounded up to 128 lanes
        # (``init_state``); heads split over tp keep their exact width,
        # so that a shard is a whole number of heads
        width = self.n_heads * self.head_dim
        self.row_width = width if head_ax else -(-width // 128) * 128
        # a latent model's pool, programs and counters (serving/latent.py).
        # `last_counters` is what the newest
        # program call returned beside its logits (None: no such model),
        # `last_span` the newest `prefill_chunk_dispatch` span: the
        # scheduler completes the span once the program has run.
        self._latent = None
        self.last_counters = None
        self.last_span = None
        if self.latent:
            if kv_dtype != "fp32":
                raise ValueError("a latent pool holds the compute dtype "
                                 "(kv_dtype='fp32')")
            from theanompi_tpu.serving.latent import LatentPrograms

            self._latent = LatentPrograms(self)
        # trace counter for the spec-decode verify program (one compile
        # ever per chunk width — acceptance churn must retrace nothing)
        self._n_verify_traces = 0
        self._paged_prefill_jit = jax.jit(
            functools.partial(self._paged_chunk_fn, all_logits=False),
            donate_argnums=(1,),
        )
        self._paged_verify_jit = jax.jit(
            functools.partial(self._paged_chunk_fn, all_logits=True),
            donate_argnums=(1,),
        )
        self._paged_decode_jit = jax.jit(
            self._paged_decode_fn, donate_argnums=(1,)
        )

    # ------------------------------------------------------------------
    # state + pool construction
    # ------------------------------------------------------------------
    def _kv_compute_dtype(self):
        return self.compute_dtype or jnp.float32

    def init_state(self):
        """Device block pool: ``k``/``v``, each one array a layer,
        ``(n_blocks · block_size, row_width)``, allocated already
        sharded.  A resident token holds one row a layer and side: its
        heads side by side, ``heads · head_dim`` numbers.  ``row_width``
        is that rounded up to 128 lanes: the device would pad a narrower
        row to as much anyway, and it lays a tall array whose rows are
        no multiple of 128 out column-major, which every program would
        then copy to row-major and back (a ``(…, heads, head_dim)`` pool
        is worse still: its two minor dimensions are tiled, 2.6 times
        the bytes at 25 heads of 64).  The layers' arrays are separate
        leaves of the state (never stacked), each donated and updated
        in place by its program.  ``kv_dtype='int8'`` adds the
        per-row/per-head scale planes ``ks``/``vs``, likewise one
        ``(rows, heads)`` array a layer.  Lengths and block tables stay
        host-side (tiny ints shipped per call — they are *data*, so
        shipping them can never recompile anything).  A latent model:
        ``kv``, one (rows, kv_rank + rope) array a layer
        (``serving/latent.py``)."""
        if self._latent is not None:
            return self._latent.init_state()
        dt = (
            jnp.int8 if self.kv_dtype == "int8" else self._kv_compute_dtype()
        )
        rows = self.n_blocks * self.block_size

        sh = NamedSharding(self.mesh, self.pool_spec)

        def leaves(width, dtype):
            return [jnp.zeros((rows, width), dtype, device=sh)
                    for _ in range(self.n_layers)]

        state = {side: leaves(self.row_width, dt) for side in ("k", "v")}
        if self.kv_dtype == "int8":
            for side in ("ks", "vs"):
                state[side] = leaves(self.n_heads, jnp.float32)
        return state

    def kv_block_bytes(self) -> int:
        """Device bytes ONE pool block occupies across all layers
        (K + V payload, plus the int8 scale planes) — the equal-byte
        currency of the ``detail.kv_quant`` capacity probe."""
        if self._latent is not None:
            return self._latent.block_bytes()
        payload = (
            1 if self.kv_dtype == "int8"
            else jnp.dtype(self._kv_compute_dtype()).itemsize
        )
        row = self.row_width * payload  # the stored width, padding and all
        if self.kv_dtype == "int8":
            row += self.n_heads * 4  # fp32 scale per (row, head)
        return 2 * self.n_layers * self.block_size * row

    def blocks_at_budget(self, budget_bytes: int) -> int:
        """How many pool blocks fit in ``budget_bytes`` of cache HBM at
        this engine's kv_dtype (the trash block counts like any other)."""
        return max(0, int(budget_bytes) // self.kv_block_bytes())

    def make_pool(self, n_blocks: Optional[int] = None) -> BlockPool:
        """A fresh allocator over (a prefix of) the device pool.  An
        ``n_blocks`` below the engine's capacity caps the *accounted*
        pool — how the bench pins "equal cache memory" comparisons."""
        n = int(n_blocks) if n_blocks is not None else self.n_blocks
        if n > self.n_blocks:
            raise ValueError(
                f"pool of {n} blocks exceeds the device pool "
                f"({self.n_blocks})"
            )
        return BlockPool(n, self.block_size)

    def pick_chunk_bucket(self, n: int) -> int:
        for b in self.chunk_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"chunk of {n} tokens exceeds the largest chunk bucket "
            f"{self.chunk_buckets[-1]}"
        )

    def max_seq_blocks(self, total_tokens: int) -> int:
        return math.ceil(total_tokens / self.block_size)

    # ------------------------------------------------------------------
    # jitted programs (tables/positions are DATA, never shapes)
    # ------------------------------------------------------------------
    def _gather_rows(self, tables):
        """(N, blocks_per_seq) block ids → (N, t_pad) physical rows:
        row j of a sequence's image is logical position j."""
        bs = self.block_size
        rows = tables[:, :, None] * bs + jnp.arange(bs)[None, None, :]
        return rows.reshape(tables.shape[0], -1)

    def _pool_leaves(self, state):
        """The state's leaves as lists that a program replaces layer by
        layer (``_kv_write``; a float pool has no scale planes: ``None``
        a layer), and the dtype in which attention images are gathered."""
        pool = {side: list(leaves) for side, leaves in state.items()}
        for side in ("ks", "vs"):
            pool.setdefault(side, [None] * self.n_layers)
        img_dt = (
            self._kv_compute_dtype() if self.kv_dtype == "int8"
            else pool["k"][0].dtype
        )
        return pool, img_dt

    def _kv_write(self, pool, side, i, rows, wr):
        """Scatter freshly-computed K or V ``rows`` (N, H, hd) (``side``
        ``'k'`` or ``'v'``) into layer ``i``'s ``(rows, row_width)`` pool
        at row indices ``wr``: each row's heads laid side by side and
        padded with zeros to ``row_width``, written into the layer's own
        donated leaf (in place), which takes the old one's place in
        ``pool``.  Returns the layer's new pool and scale plane.  fp32
        path: a cast + scatter, the values bit-identical to PR 8.  int8
        path: the ``quantize_blocks`` codec over head_dim
        (per-row/per-head amax scale, into the layer's ``(rows, heads)``
        scale plane) — quantized ONCE on write, so every later reader
        (XLA gather, Pallas kernel, a prefix-sharing sibling) sees the
        same bytes."""
        pool_l, scale_l = pool[side][i], pool[side + "s"][i]
        if self.kv_dtype == "int8":
            from theanompi_tpu.parallel.quantize import quantize_blocks

            rows, s = quantize_blocks(rows.astype(jnp.float32))
            scale_l = scale_l.at[wr].set(s)
        flat = rows.astype(pool_l.dtype).reshape(rows.shape[0], -1)
        flat = jnp.pad(flat, ((0, 0), (0, self.row_width - flat.shape[1])))
        pool[side][i], pool[side + "s"][i] = pool_l.at[wr].set(flat), scale_l
        return pool[side][i], scale_l

    def _kv_image(self, pool_l, scale_l, gr_flat, n, dtype):
        """Gather the attention image for one layer from its ``(rows,
        row_width)`` pool, and view it as (n, t_pad, H, hd) only after
        the gather (the lanes' rows, not the pool) — dequantizing int8
        payloads against their gathered scales."""
        h, hd = self.n_heads, self.head_dim
        img = jnp.take(pool_l, gr_flat, axis=0)[:, :h * hd]
        img = img.reshape(n, self.t_pad, h, hd)
        if self.kv_dtype == "int8":
            sc = jnp.take(scale_l, gr_flat, axis=0)
            img = img.astype(jnp.float32) * sc.reshape(
                n, self.t_pad, h)[..., None]
        return img.astype(dtype)

    def _paged_chunk_fn(
        self, params, state, tokens, tables, p0, true_len, active,
        all_logits,
    ):
        """One batched, chunked multi-token pass: ``tokens`` (P, C)
        int32 — chunk c of each lane, entering logical positions
        ``p0[i] + [0, C)``; ``true_len`` (P,) real tokens per lane
        (pad and inactive lanes scatter to the trash block).  Writes
        each lane's chunk K/V into its table's blocks and returns
        logits at each lane's last real chunk token (prefill,
        ``all_logits=False``) or at EVERY chunk position (the
        speculative-decoding verify dispatch, ``all_logits=True`` —
        (P, C, V), so a draft's k proposals and the bonus token are
        scored in this ONE call)."""
        if all_logits:  # runs at trace time only
            self._n_verify_traces += 1
        else:
            self._n_prefill_traces += 1
        if self._latent is not None:
            return self._latent.chunk_fn(params, state, tokens, tables, p0,
                                         true_len, active, all_logits)
        emb, pos, blocks, lnf, head = self._weights(params)
        p_, c_ = tokens.shape
        bs = self.block_size
        h, hd = self.n_heads, self.head_dim
        positions = p0[:, None] + jnp.arange(c_)[None, :]  # (P, C)
        with jax.named_scope("embed"):
            x = self._embed(
                emb, pos, tokens, jnp.minimum(positions, self.max_len - 1)
            )  # (P, C, D)
        blk_idx = jnp.minimum(positions // bs, self.blocks_per_seq - 1)
        blk = jnp.take_along_axis(tables, blk_idx, axis=1)  # (P, C)
        valid = active[:, None] & (
            jnp.arange(c_)[None, :] < true_len[:, None]
        )
        wr = jnp.where(valid, blk * bs + positions % bs, TRASH_BLOCK)
        wr = wr.reshape(-1)  # (P·C,) — collisions only inside trash
        gr = self._gather_rows(tables).reshape(-1)  # (P·t_pad,)
        # causal over ABSOLUTE positions: chunk queries see the whole
        # cached history (earlier chunks / prefix-hit blocks) plus the
        # intra-chunk triangle, exactly like one full-prompt pass
        mask = jnp.arange(self.t_pad)[None, None, :] <= positions[:, :, None]
        pool, img_dt = self._pool_leaves(state)
        # named scopes are metadata on the same operations: a profile
        # groups by them (layer<i>/qkv, .../cast_weights inside it, ...)
        for i, bp in enumerate(blocks):
            with jax.named_scope(f"layer{i}"):
                with jax.named_scope("qkv"):
                    y = self._ln(bp["ln1"], x)
                    q = self._proj(y, bp["attn"]["wq"]).reshape(p_, c_, h, hd)
                    k = self._proj(y, bp["attn"]["wk"]).reshape(p_, c_, h, hd)
                    v = self._proj(y, bp["attn"]["wv"]).reshape(p_, c_, h, hd)
                with jax.named_scope("pool_update"):
                    pk_l, pks_l = self._kv_write(
                        pool, "k", i, k.reshape(p_ * c_, h, hd), wr)
                    pv_l, pvs_l = self._kv_write(
                        pool, "v", i, v.reshape(p_ * c_, h, hd), wr)
                with jax.named_scope("paged_attn"):
                    kc = self._kv_image(pk_l, pks_l, gr, p_, img_dt)
                    vc = self._kv_image(pv_l, pvs_l, gr, p_, img_dt)
                    s = jnp.einsum(
                        "pchd,pthd->phct", q, kc,
                        preferred_element_type=jnp.float32,
                    ) * self.scale
                    s = jnp.where(mask[:, None, :, :], s, _NEG_INF)
                    prob = jax.nn.softmax(s, axis=-1)
                    o = jnp.einsum(
                        "phct,pthd->pchd", prob.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32,
                    ).astype(y.dtype)
                with jax.named_scope("attn_out"):
                    x = x + self._proj(
                        o.reshape(p_, c_, h * hd), bp["attn"]["wo"])
                with jax.named_scope("mlp"):
                    x = x + self._mlp(bp, self._ln(bp["ln2"], x))
        out = {side: pool[side] for side in state}
        with jax.named_scope("head"):
            if all_logits:
                logits = self._head(lnf, head, x)  # (P, C, V)
            else:
                last = jnp.take_along_axis(
                    x, jnp.maximum(true_len - 1, 0)[:, None, None], axis=1
                )[:, 0]  # (P, D)
                logits = self._head(lnf, head, last)
        return out, logits

    def _paged_decode_fn(
        self, params, state, tokens, tables, lengths, active
    ):
        """One decode tick for every lane: identical math to the
        contiguous ``_decode_fn`` with the per-slot cache image
        gathered through the block table.  Inactive lanes scatter to
        the trash block — a recycled block can never be corrupted by a
        lane that no longer owns it.  ``paged_attn='pallas'`` swaps
        the gather+softmax for the fused kernel (same scatter, same
        mask semantics — allclose-pinned)."""
        self._n_decode_traces += 1  # runs at trace time only
        if self._latent is not None:
            return self._latent.decode_fn(params, state, tokens, tables,
                                          lengths, active)
        emb, pos, blocks, lnf, head = self._weights(params)
        s_ = tokens.shape[0]
        bs = self.block_size
        h, hd = self.n_heads, self.head_dim
        pos_idx = lengths  # (S,) position of the incoming token
        with jax.named_scope("embed"):
            x = self._embed(
                emb, pos, tokens, jnp.minimum(pos_idx, self.max_len - 1)
            )  # (S, D)
        blk = jnp.take_along_axis(
            tables,
            jnp.minimum(pos_idx // bs, self.blocks_per_seq - 1)[:, None],
            axis=1,
        )[:, 0]
        wr = jnp.where(active, blk * bs + pos_idx % bs, TRASH_BLOCK)
        gr = self._gather_rows(tables).reshape(-1)  # (S·t_pad,)
        att_mask = jnp.arange(self.t_pad)[None, :] <= pos_idx[:, None]
        pool, img_dt = self._pool_leaves(state)
        use_pallas = self.paged_attn_effective == "pallas"
        if use_pallas:
            from theanompi_tpu.ops import pallas_paged
        for i, bp in enumerate(blocks):  # scopes as in _paged_chunk_fn
            with jax.named_scope(f"layer{i}"):
                with jax.named_scope("qkv"):
                    y = self._ln(bp["ln1"], x)
                    q = self._proj(y, bp["attn"]["wq"]).reshape(s_, h, hd)
                    k = self._proj(y, bp["attn"]["wk"]).reshape(s_, h, hd)
                    v = self._proj(y, bp["attn"]["wv"]).reshape(s_, h, hd)
                with jax.named_scope("pool_update"):
                    pk_l, pks_l = self._kv_write(pool, "k", i, k, wr)
                    pv_l, pvs_l = self._kv_write(pool, "v", i, v, wr)
                with jax.named_scope("paged_attn"):
                    if use_pallas:
                        o = pallas_paged.paged_decode_attention(
                            q, pk_l, pv_l, tables, pos_idx,
                            block_size=bs, scale=self.scale,
                            k_scale=pks_l, v_scale=pvs_l,
                        ).astype(y.dtype)
                    else:
                        kc = self._kv_image(pk_l, pks_l, gr, s_, img_dt)
                        vc = self._kv_image(pv_l, pvs_l, gr, s_, img_dt)
                        s = jnp.einsum(
                            "shd,sthd->sht", q, kc,
                            preferred_element_type=jnp.float32,
                        ) * self.scale
                        s = jnp.where(att_mask[:, None, :], s, _NEG_INF)
                        prob = jax.nn.softmax(s, axis=-1)
                        o = jnp.einsum(
                            "sht,sthd->shd", prob.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32,
                        ).astype(y.dtype)
                with jax.named_scope("attn_out"):
                    x = x + self._proj(
                        o.reshape(s_, h * hd), bp["attn"]["wo"])
                with jax.named_scope("mlp"):
                    x = x + self._mlp(bp, self._ln(bp["ln2"], x))
        out = {side: pool[side] for side in state}
        with jax.named_scope("head"):
            logits = self._head(lnf, head, x)
        return out, logits

    # ------------------------------------------------------------------
    # host entries
    # ------------------------------------------------------------------
    def prefill_chunks(self, params, state, rows):
        """One batched chunked-prefill dispatch.

        ``rows`` is a list of up to ``prefill_rows`` dicts with keys
        ``tokens`` (this lane's chunk, 1..prefill_chunk ints), ``p0``
        (its absolute start position) and ``table`` (the lane's block
        ids).  Returns ``(state, logits)`` — logits row i belongs to
        rows[i] (meaningful only for the lane's FINAL chunk)."""
        if not rows or len(rows) > self.prefill_rows:
            raise ValueError(
                f"prefill_chunks wants 1..{self.prefill_rows} rows, "
                f"got {len(rows)}"
            )
        c = self.pick_chunk_bucket(max(len(r["tokens"]) for r in rows))
        p_ = self.prefill_rows
        # boundary span: the host arrays and the jitted call; its counts
        # say how much of what the program computes is padding
        with obs.span("prefill_chunk_dispatch", boundary=True,
                      rows=len(rows), bucket=c, rows_computed=p_,
                      computed_tokens=p_ * c) as span:
            tokens = np.zeros((p_, c), np.int32)
            tables = np.zeros((p_, self.blocks_per_seq), np.int32)
            p0 = np.zeros((p_,), np.int32)
            true_len = np.zeros((p_,), np.int32)
            active = np.zeros((p_,), bool)
            for i, r in enumerate(rows):
                n = len(r["tokens"])
                tokens[i, :n] = r["tokens"]
                tables[i, :len(r["table"])] = r["table"]
                p0[i] = int(r["p0"])
                true_len[i] = n
                active[i] = True
            useful = int(true_len.sum())
            span.set(useful_tokens=useful)
            smetrics.PREFILL_CHUNKS.inc(bucket=str(c))
            smetrics.PREFILL_TOKENS.inc(useful)
            state, logits = self._call(
                self._paged_prefill_jit, params, state,
                host_input(tokens), host_input(tables),
                host_input(p0), host_input(true_len),
                host_input(active),
            )
            self.last_span = span
        return state, logits

    def verify_chunks(self, params, state, tokens, tables, p0, true_len,
                      active):
        """One batched speculative-VERIFY dispatch: ``tokens`` (S, C)
        int32 — each active lane's [last emitted token, draft
        proposals…] chunk entering positions ``p0[i] + [0, C)``;
        ``true_len`` (S,) how many of the C are real for this lane
        (budget-clamped lanes pad — the pad writes go to the trash
        block and their logits are never picked).  Returns ``(state,
        logits (S, C, V))``: row i column j scores the token FOLLOWING
        chunk position j, so greedy acceptance is an argmax compare and
        sampled acceptance draws with the request's own per-index keys.
        C is pinned by the caller (spec_k + 1) — ONE compiled program
        across every acceptance/rollback outcome."""
        smetrics.SPEC_VERIFY_DISPATCHES.inc()
        with obs.span("spec_verify_dispatch", rows=int(np.sum(active)),
                      width=int(np.asarray(tokens).shape[1])):
            state, logits = self._call(
                self._paged_verify_jit, params, state,
                host_input(tokens, jnp.int32),
                host_input(tables, jnp.int32),
                host_input(p0, jnp.int32),
                host_input(true_len, jnp.int32),
                host_input(active, bool),
            )
        return state, logits

    def _call(self, program, *args):
        """``(state, logits)`` of a jitted program; what a latent
        model's program returns beside them (its experts' counters, a
        device array nobody has waited for) goes to ``last_counters``."""
        out = program(*args)
        self.last_counters = out[2] if len(out) > 2 else None
        return out[0], out[1]

    def decode_step_paged(self, params, state, tokens, tables, lengths,
                          active):
        """One decode tick; host arrays in, ``(state, logits)`` out."""
        return self._call(
            self._paged_decode_jit, params, state,
            host_input(tokens, jnp.int32),
            host_input(tables, jnp.int32),
            host_input(lengths, jnp.int32),
            host_input(active, bool),
        )

    # ------------------------------------------------------------------
    # convenience: single-sequence greedy decode (tests / smoke)
    # ------------------------------------------------------------------
    def greedy(self, prompt, n_new: int, params=None, **sched_kwargs) -> List[int]:
        """Greedy-decode through the full paged scheduler path (block
        allocation, chunked prefill, table-threaded decode).
        ``sched_kwargs`` reach the scheduler — e.g. ``spec_k=4,
        draft_engine=...`` runs the speculative path."""
        from theanompi_tpu.serving.scheduler import (
            ContinuousBatchingScheduler, Request,
        )

        sched = ContinuousBatchingScheduler(self, params=params,
                                            **sched_kwargs)
        sched.submit(
            Request(id="greedy", prompt=list(prompt), max_new_tokens=n_new)
        )
        return sched.run()["greedy"]
