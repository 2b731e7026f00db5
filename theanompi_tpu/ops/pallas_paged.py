"""Fused paged-attention decode kernel (Pallas TPU).

One decode tick's attention for every serving lane: a single query
token per sequence against K/V gathered **block by block from the
paged pool inside the kernel**.  The lane's block table is a
scalar-prefetch argument, so the BlockSpec ``index_map`` reads
``table[lane, j]`` and a grid step DMAs one pool block into VMEM
(``mla_paged_decode``: a group of 16; the grouped-query form: of 32) —
the XLA path instead materializes the whole gathered ``(S, t_pad, H,
hd)`` image in HBM first (and, on a dp-sharded pool, pays a GSPMD
cross-shard gather for it).  Softmax runs as the online recurrence
over the block stream (same max/denominator carry as
``pallas_flash``), so nothing quadratic in the table length ever leaves
VMEM.

**The grid is the lanes' resident blocks, not the table's width**
(``lane_steps``): one flat list of (lane, block group) pairs, lanes in
order and each lane's groups in order, ``length // span + 1`` of them
a lane, and a one-dimensional grid whose bound is the list's length
(a traced scalar: the same compiled program whatever the lanes hold).
A step past a lane's length does not exist, so a table 35 groups wide
over contexts of three costs three steps a lane, and a full table
walks what a rectangular grid would.  Every lane has one step at
least (an idle lane's, on the trash block), so every output row is
written.

int8 pool payloads (``serving.paging`` ``kv_dtype='int8'``)
dequantize **in-kernel**: the per-row/per-head fp32 scales ride a
parallel scale pool gathered through the same table, and the int8
rows never round-trip through an fp32 HBM image — the capacity win of
the quantized cache is also a bandwidth win on the decode hot path.

Within a lane's boundary block, rows past the length mask to ``-inf``
exactly like the XLA path's ``att_mask``.

``interpret=True`` on the CPU (the ``ops.platform.on_tpu`` gate)
so CPU CI exercises the same kernel code — the tier-1 contract is
allclose against the XLA gather path on both fp32 and int8 pools.

The pool is read as the engine stores it: one ``(rows, width)`` array
a layer, a row holding a token's heads side by side (``width`` =
heads · head_dim rounded up to 128 lanes), so a block is ``(block_size,
width)`` dense — no ``(heads, head_dim)`` minor dimensions for the
device to tile and pad, and no reshape of the pool in front of the
kernel (which would be a copy of it).  Per-head scores come from the
flat rows by spreading the query block-diagonally (``_paged_kernel``),
or, where key/value heads are shared by a group of query heads or a
window bounds a lane's reach, by a product of the group's queries and
each key/value head's columns (``_grouped_kernel``; ``kv_heads=``,
``window=``): one kernel, one name, ``paged_decode_attn``.

Scope: the kernel is a SINGLE-SHARD program.  ``supported()`` gates on
one device — a dp-sharded pool or tp-sharded heads would need a
shard_map wrapper that is not built, so the engine selects the XLA
path there (see docs/serving.md for the selection matrix).  Both pool
dtypes compile under Mosaic for a v5e at the serving widths (block 32,
head 64, at 8 heads and at GPT-2 XL's 25: 1,600 numbers stored 1,664
wide; ``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops import platform
from theanompi_tpu.ops.pallas_flash import _NEG_INF, resolve_scale


def supported(mesh=None) -> bool:
    """Whether the fused kernel can serve this pool.

    Single-device only: ``pallas_call`` under jit has no partitioning
    rule, so a pool sharded over dp rows or tp heads takes the XLA
    gather (GSPMD partitions that one for free).
    """
    n = mesh.devices.size if mesh is not None else len(jax.devices())
    return int(n) == 1


# ---------------------------------------------------------------------------
# the grid: one step for each (lane, block group) that holds rows
# ---------------------------------------------------------------------------

def lane_steps(lengths, span: int, steps_per_lane: int, starts=None):
    """The decode kernels' grid as a list: ``(lane, j, total)``.

    A lane attends to positions ``<= length``, so it takes ``n =
    length // span + 1`` steps of ``span`` rows (at most
    ``steps_per_lane``, the table's width; an idle lane at length 0
    takes one, on the trash block); with ``starts`` (S,), the first
    position each lane attends to (a window's), its steps begin at
    ``start // span`` and ``n`` is that many fewer.  ``lane`` and ``j``
    are flat int32 arrays of the static length ``S * steps_per_lane``:
    step ``t`` of the grid is group ``j[t]`` of lane ``lane[t]``, lanes
    in order and each lane's groups in order; ``total = sum(n)`` is the
    grid's bound and the tail past it is never visited.  Plain XLA on S
    integers: a decode program builds it once and hands it to every
    layer's call.
    """
    lengths = jnp.asarray(lengths, jnp.int32)
    s = lengths.shape[0]
    n = jnp.minimum(lengths // span + 1, steps_per_lane)
    if starts is not None:
        first = jnp.asarray(starts, jnp.int32) // span
        n = n - first
    t = jnp.arange(s * steps_per_lane, dtype=jnp.int32)
    # (T, S): lane i lies wholly before step t.  One compare and two
    # sums, no search: a search is a loop on the device
    before = t[:, None] >= jnp.cumsum(n)[None, :]
    lane = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), s - 1)
    j = t - jnp.sum(jnp.where(before, n[None, :], 0), axis=1)
    if starts is not None:
        j = j + jnp.take(first, lane)
    return lane, j, jnp.sum(n)


def window_starts(lengths, window: Optional[int]):
    """The first position each lane attends to: ``length - window + 1``
    (not below 0) under a window, None without one."""
    if window is None:
        return None
    return jnp.maximum(jnp.asarray(lengths, jnp.int32) - window + 1, 0)


MLA_GROUP = 16  # blocks a grid step of ``mla_paged_decode``
GQA_GROUP = 32  # blocks a grid step of the grouped ``paged_decode_attn``


def mla_grid(block_size: int, table_blocks: int, group: int = MLA_GROUP):
    """``(span, steps_per_lane)`` of ``mla_paged_decode`` over a table
    of ``table_blocks`` columns: rows a grid step, and a full lane's
    steps (what ``lane_steps`` takes)."""
    g = max(1, min(int(group), int(table_blocks)))
    return g * int(block_size), -(-int(table_blocks) // g)


def gqa_grid(block_size: int, table_blocks: int):
    """The same of ``paged_decode_attention`` with ``kv_heads`` or a
    ``window`` (``GQA_GROUP`` blocks a step, fewer under a narrower
    table)."""
    return mla_grid(block_size, table_blocks, GQA_GROUP)


def _grid(tables, lengths, span, steps_per_lane, steps, window=None):
    """``(grid, scalar-prefetch operands)`` of a decode kernel: the
    list's length, and ``lane, j, tables, lengths``.  ``steps`` is the
    caller's ``lane_steps`` (a decode program's one list for all its
    layers of a kind) or None to build it here."""
    lengths = jnp.minimum(
        jnp.asarray(lengths, jnp.int32), steps_per_lane * span - 1)
    lane, j, total = (
        lane_steps(lengths, span, steps_per_lane,
                   window_starts(lengths, window))
        if steps is None else steps)
    if lane.shape != (tables.shape[0] * steps_per_lane,) \
            or j.shape != lane.shape:
        raise ValueError(
            f"steps are lane_steps(lengths, {span}, {steps_per_lane}) of "
            f"{tables.shape[0]} lanes, got arrays of {lane.shape}, {j.shape}")
    return (total,), (lane, j, jnp.asarray(tables, jnp.int32), lengths)


def _step_blocks(lane, j, tables, lengths, group, block_size):
    """The pool block of each of a step's ``group`` blocks, one flat
    int32 array (step ``t``'s block ``k`` at ``t · group + k``): column
    ``j · group + k`` of the lane's table, or past the lane's last block
    its last (whose positions past the length are masked).  Plain XLA
    once a call, so a step's index maps (two a block) read one scalar
    each where they would read four: reading the table themselves, they
    held a step longer than its bytes did (``PERF.md``, the grouped
    kernel alone)."""
    last = jnp.take(lengths, lane) // block_size
    col = jnp.minimum(
        j[:, None] * group + jnp.arange(group, dtype=jnp.int32)[None, :],
        last[:, None])
    return jnp.take(tables.reshape(-1),
                    lane[:, None] * tables.shape[1] + col).reshape(-1)


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------

def _head_columns(h, hd, w):
    """(H, W) bool: column ``c`` of a flat row belongs to head ``h``
    (``h·hd <= c < (h+1)·hd``); the padding columns belong to none."""
    col = lax.broadcasted_iota(jnp.int32, (h, w), 1)
    lo = lax.broadcasted_iota(jnp.int32, (h, w), 0) * hd
    return (col >= lo) & (col < lo + hd)


def _grouped_kernel(lane_ref, j_ref, ids_ref, len_ref, q_ref, *refs, span,
                    scale, hd, window):
    """One lane's online softmax over its stream of block groups, a
    group of query heads to each key/value head: the step's blocks
    (``refs``: the key blocks, then the value blocks) side by side as
    ``span`` rows, and for key/value head ``k`` the group's queries
    (G, hd) against those rows' ``hd`` columns of that head, ``(G, hd)
    × (hd, span)``, and the probabilities against the same columns of
    the values, ``(G, span) × (span, hd)``; operands in the pool's
    dtype, float32 accumulation.  ``window``: the lane's steps begin at
    the group that holds its first position (``lane_steps``' start) and
    positions before it are masked."""
    n = (len(refs) - 4) // 2
    k_refs, v_refs = refs[:n], refs[n:2 * n]
    o_ref, m_ref, d_ref, acc_ref = refs[2 * n:]
    t = pl.program_id(0)
    s_idx, j = lane_ref[t], j_ref[t]
    length = len_ref[s_idx]
    first = (0 if window is None
             else jnp.maximum(length - window + 1, 0))

    @pl.when(j == first // span)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv, g = m_ref.shape[0], m_ref.shape[1]
    pos = j * span + lax.broadcasted_iota(jnp.int32, (g, span), 1)
    seen = pos <= length
    if window is not None:
        seen &= pos >= first
    for k in range(kv):
        cols = slice(k * hd, (k + 1) * hd)
        kb = jnp.concatenate([r[:, cols] for r in k_refs], axis=0)
        vb = jnp.concatenate([r[:, cols] for r in v_refs], axis=0)
        s = lax.dot_general(
            q_ref[0, k].astype(kb.dtype), kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, span)
        s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_ref[k]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        d_ref[k] = d_ref[k] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[k] = acc_ref[k] * corr + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        m_ref[k] = m_new

    @pl.when(j == length // span)  # the lane's last step
    def _fin():
        o_ref[0] = (acc_ref[...] / d_ref[...]).astype(o_ref.dtype)


def _paged_kernel(lane_ref, j_ref, tbl_ref, len_ref, q_ref, k_ref, v_ref,
                  *refs, bs, scale, hd, quant):
    """One lane's online softmax over its block stream, on flat rows,
    every head its own key/value head.

    A pool row holds the heads side by side, so per-head scores come
    from two plain products a block and no reshape that splits lanes:
    the lane's query is spread block-diagonally (``qh`` (H, W): ``q_h``
    in head ``h``'s ``hd`` columns, zeros elsewhere), ``qh · Kᵀ`` is the
    (H, bs) scores, and ``p · V`` is (H, W), of which head ``h``'s own
    columns are kept at the end: one product of H rows, where a product
    a head would be H products of one row over ``hd``-column slices
    (64 at GPT-2 XL's heads, half a lane tile).  int8 payloads: the
    per-row/per-head scales multiply the scores (K) and the
    probabilities (V) — the same numbers as dequantizing the block
    first, since row ``h`` of either product only keeps head ``h``'s
    columns.
    """
    if quant:
        ks_ref, vs_ref, o_ref, qh_ref, m_ref, d_ref, acc_ref = refs
    else:
        o_ref, qh_ref, m_ref, d_ref, acc_ref = refs
    t = pl.program_id(0)
    s_idx, j = lane_ref[t], j_ref[t]
    h, w = acc_ref.shape

    @pl.when(j == 0)
    def _init():
        qh_ref[...] = jnp.where(
            _head_columns(h, hd, w), q_ref[0].astype(jnp.float32), 0.0)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the incoming token's position: it attends to itself, like the
    # XLA att_mask
    length = len_ref[s_idx]

    s = lax.dot_general(
        qh_ref[...], k_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale  # (H, bs)
    if quant:
        s = s * ks_ref[...].T
    pos = j * bs + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos <= length, s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    d_ref[...] = d_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        p = p * vs_ref[...].T
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)  # (H, W)
    m_ref[...] = m_new

    @pl.when(j == length // bs)  # the lane's last step
    def _fin():
        own = jnp.where(
            _head_columns(h, hd, w), acc_ref[...] / d_ref[...], 0.0)
        o_ref[0] = jnp.sum(own, axis=0, keepdims=True).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    block_size: int,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    steps=None,
    kv_heads: Optional[int] = None,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """softmax(q·Kᵀ·scale)·V over each lane's paged K/V, one layer.

    - ``q`` (S, H, hd): the decode tick's single query per lane.
    - ``kv_heads``: the pool's key/value heads, each read by a group of
      ``H // kv_heads`` query heads (query head ``h`` by head ``h //
      group``); None: ``H``, a head each.
    - ``k_pool``/``v_pool`` (R, W >= H·hd): the flat row pool for this
      layer (R = n_blocks · block_size), one row a token, ``[head 0 |
      head 1 | … | padding]``, fp32/compute dtype or int8.  (``W`` a
      multiple of 128: the device would pad a narrower row to as much
      anyway, and it lays a tall array whose rows are no multiple of
      128 out column-major, which every call would then copy to
      row-major and back; a ``(R, H, hd)`` pool has its two minor
      dimensions tiled, 2.6 times the bytes at 25 heads of 64.)
    - ``tables`` (S, NT) int32: per-lane block ids (0 = trash block).
    - ``lengths`` (S,) int32: the incoming token's position; rows at
      positions <= length attend (the token was scattered before the
      call, exactly like the XLA path).
    - ``k_scale``/``v_scale`` (R, H) fp32: required when the pools are
      int8 — per-row/per-head dequant scales.
    - ``window``: a lane at ``length`` attends to the positions
      ``length - window < p <= length`` alone, and its steps begin at
      the block that holds the first of them; None: every position.
      The table says where the positions live, so a per-lane ring of
      ``R`` blocks is read through a table whose column ``c`` holds
      ring block ``c mod R`` (``serving/latent.py``).
    - ``steps``: ``lane_steps(lengths, block_size, NT)`` where the
      caller has built it for several calls; built here when None.
      With ``kv_heads`` or a ``window``: ``lane_steps(lengths,
      *gqa_grid(block_size, NT), window_starts(lengths, window))``.

    A grid step fetches one ``(block_size, W)`` block of each pool
    through the table-driven index map, and the grid holds a lane's
    resident blocks alone (``lane_steps``).  With ``kv_heads`` or a
    ``window`` a step attends to ``GQA_GROUP`` blocks (``gqa_grid``),
    each pool handed to the call once for each, as
    ``mla_paged_decode`` reads its pool, and each block's id read from
    one list built beside the steps (``_step_blocks``); a window lane's
    first step may begin before its first position, and those rows are
    read and masked.  Returns fp32 (S, H, hd).
    Numerics contract (tier-1 pinned): allclose to the XLA gather path
    (``paged_decode_xla``) on both pool dtypes; a head a key/value head
    without a window is the ungrouped kernel's arithmetic as it was.
    """
    s, h, hd = q.shape
    w = int(k_pool.shape[1])
    nt = int(tables.shape[1])
    bs = int(block_size)
    kv = h if kv_heads is None else int(kv_heads)
    quant = k_pool.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools need k_scale/v_scale")
    if h % kv:
        raise ValueError(f"{h} query heads do not divide into {kv} "
                         "key/value heads")
    if k_pool.ndim != 2 or w < kv * hd:
        raise ValueError(
            f"pools are (rows, width >= heads * head_dim = {kv * hd}), "
            f"got {k_pool.shape}"
        )
    if (kv != h or window is not None) and quant:
        raise ValueError("an int8 pool is read a head a key/value head "
                         "and without a window")
    sc = resolve_scale(scale, hd)

    def _pool_map(t, lane, j, tbl, ln):
        return (tbl[lane[t], j[t]], 0)

    def _row_map(t, lane, j, tbl, ln):
        return (lane[t], 0, 0)

    if kv != h or window is not None:
        g = h // kv
        span, nj = gqa_grid(bs, nt)
        n = span // bs
        tables = jnp.pad(jnp.asarray(tables, jnp.int32),
                         ((0, 0), (0, nj * n - nt)))

        def _block_map(k):
            def index(t, lane, j, ids, ln):
                return (ids[t * n + k], 0)
            return index

        def _group_map(t, lane, j, ids, ln):
            return (lane[t], 0, 0, 0)

        grid, (lane, j, tables, lengths) = _grid(tables, lengths, span, nj,
                                                 steps, window)
        scalars = (lane, j, _step_blocks(lane, j, tables, lengths, n, bs),
                   lengths)
        blocks = [pl.BlockSpec((bs, w), _block_map(k)) for k in range(n)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # lane, j, the steps' blocks, lengths
            grid=grid,
            in_specs=[pl.BlockSpec((1, kv, g, hd), _group_map)]  # q
            + blocks + blocks,                                   # k, v
            out_specs=pl.BlockSpec((1, kv, g, hd), _group_map),
            scratch_shapes=[
                pltpu.VMEM((kv, g, 1), jnp.float32),   # running max
                pltpu.VMEM((kv, g, 1), jnp.float32),   # running denominator
                pltpu.VMEM((kv, g, hd), jnp.float32),  # output accumulator
            ],
        )
        out = pl.pallas_call(
            functools.partial(_grouped_kernel, span=span, scale=sc, hd=hd,
                              window=window),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s, kv, g, hd), jnp.float32),
            interpret=(not platform.on_tpu()) if interpret is None
            else interpret,
            # one kernel, one name: the profile's paged_decode_attn*
            name="paged_decode_attn",
        )(*scalars, q.reshape(s, kv, g, hd), *([k_pool] * n),
          *([v_pool] * n))
        return out.reshape(s, h, hd)

    # the lane's query as one flat row, like the pool's
    q_flat = jnp.pad(q.reshape(s, 1, h * hd), ((0, 0), (0, 0), (0, w - h * hd)))
    in_specs = [
        pl.BlockSpec((1, 1, w), _row_map),           # q
        pl.BlockSpec((bs, w), _pool_map),            # k block
        pl.BlockSpec((bs, w), _pool_map),            # v block
    ]
    args = [q_flat, k_pool, v_pool]
    if quant:
        in_specs += [
            pl.BlockSpec((bs, h), _pool_map),        # k scales
            pl.BlockSpec((bs, h), _pool_map),        # v scales
        ]
        args += [k_scale, v_scale]
    grid, scalars = _grid(tables, lengths, bs, nt, steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # lane, j, tables, lengths
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, w), _row_map),
        scratch_shapes=[
            pltpu.VMEM((h, w), jnp.float32),   # block-diagonal query
            pltpu.VMEM((h, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1), jnp.float32),   # running denominator
            pltpu.VMEM((h, w), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, scale=sc, hd=hd,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, 1, w), jnp.float32),
        interpret=(not platform.on_tpu()) if interpret is None else interpret,
        # the device operation's name in a profile starts with this
        name="paged_decode_attn_i8" if quant else "paged_decode_attn",
    )(*scalars, *args)
    return out[:, 0, :h * hd].reshape(s, h, hd)


# ---------------------------------------------------------------------------
# latent cache: absorbed decode over rows shared by every head
# ---------------------------------------------------------------------------

def _mla_kernel(lane_ref, j_ref, tbl_ref, len_ref, ql_ref, qr_ref, *refs,
                bs, group, scale, c_dim, r_dim):
    pool_refs, o_ref = refs[:group], refs[group]
    m_ref, d_ref, acc_ref = refs[group + 1:]
    t = pl.program_id(0)
    s_idx, j = lane_ref[t], j_ref[t]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s_idx]
    span = group * bs

    rows = jnp.concatenate([r[...] for r in pool_refs], axis=0)
    c, kr = rows[:, :c_dim], rows[:, c_dim:c_dim + r_dim]
    nt = (((1,), (1,)), ((), ()))
    sc = (
        lax.dot_general(ql_ref[0], c, nt,
                        preferred_element_type=jnp.float32)
        + lax.dot_general(qr_ref[0], kr, nt,
                          preferred_element_type=jnp.float32)
    ) * scale  # (H, span)
    pos = j * span + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    sc = jnp.where(pos <= length, sc, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.exp(sc - m_new)
    corr = jnp.exp(m_prev - m_new)
    d_ref[...] = d_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(c.dtype), c, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == length // span)  # the lane's last step
    def _fin():
        o_ref[0] = (acc_ref[...] / d_ref[...]).astype(o_ref.dtype)


def mla_paged_decode(
    q_lat: jax.Array,
    q_rope: jax.Array,
    pool: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    block_size: int,
    scale: float,
    group: int = MLA_GROUP,
    steps=None,
    interpret: Optional[bool] = None,
):
    """Absorbed latent attention for one decode tick, one layer:
    ``softmax((q_lat · c + q_rope · k_rope) · scale) · c`` over each
    lane's resident rows.

    - ``q_lat`` (S, H, C): the heads' queries carried into the latent
      space (``q_nope W_kvb[k]ᵀ``); ``q_rope`` (S, H, R).
    - ``pool`` (rows, W >= C + R): one row a token, ``[c_kv | k_rope |
      padding]``, shared by every head — a block is read once for all
      of them.  (``W`` a multiple of 128: the device lays a narrower
      tall array out column-major, and every call would then copy the
      whole pool to row-major and back.)
    - ``tables`` (S, NT), ``lengths`` (S,) as ``paged_decode_attention``.
    - ``steps``: ``lane_steps(lengths, *mla_grid(block_size, NT,
      group))`` where the caller has built it for several calls.

    A grid step attends to ``group`` blocks: the pool is handed to the
    call ``group`` times, each with its own table-driven BlockSpec, so
    the blocks arrive by the pipeline's own double-buffered copies; a
    block past the lane's last one maps onto the last (no new copy) and
    its positions are masked.  The grid holds a lane's resident groups
    alone (``lane_steps``).  Returns fp32 (S, H, C): the caller carries
    it out of the latent space (``· W_kvb[v]``)."""
    s, h, c_dim = q_lat.shape
    r_dim = q_rope.shape[-1]
    bs = int(block_size)
    span, nj = mla_grid(bs, int(tables.shape[1]), group)
    g = span // bs
    tables = jnp.asarray(tables, jnp.int32)
    tables = jnp.pad(tables, ((0, 0), (0, nj * g - tables.shape[1])))

    def pool_map(k):
        def index(t, lane, j, tbl, ln):
            si = lane[t]
            return (tbl[si, jnp.minimum(j[t] * g + k, ln[si] // bs)], 0)
        return index

    def row_map(t, lane, j, tbl, ln):
        return (lane[t], 0, 0)

    grid, scalars = _grid(tables, lengths, span, nj, steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # lane, j, tables, lengths
        grid=grid,
        in_specs=[pl.BlockSpec((1, h, c_dim), row_map),
                  pl.BlockSpec((1, h, r_dim), row_map)]
        + [pl.BlockSpec((bs, pool.shape[1]), pool_map(k)) for k in range(g)],
        out_specs=pl.BlockSpec((1, h, c_dim), row_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),      # running max
            pltpu.VMEM((h, 1), jnp.float32),      # running denominator
            pltpu.VMEM((h, c_dim), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, bs=bs, group=g,
                          scale=float(scale), c_dim=c_dim, r_dim=r_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, c_dim), jnp.float32),
        interpret=(not platform.on_tpu()) if interpret is None else interpret,
        name="mla_paged_decode",
    )(*scalars, q_lat.astype(pool.dtype), q_rope.astype(pool.dtype),
      *([pool] * g))


def paged_decode_xla(q, k_pool, v_pool, tables, lengths, *, block_size,
                     scale=None, kv_heads=None, window=None):
    """``paged_decode_attention`` (compute-dtype pools) over the
    gathered image of every lane's table: plain XLA, the kernel's
    oracle and the path of a sharded pool.  fp32 (S, H, hd)."""
    s, h, hd = q.shape
    kv = h if kv_heads is None else int(kv_heads)
    bs = int(block_size)
    rows = (tables[:, :, None] * bs + jnp.arange(bs)[None, None, :])
    rows = rows.reshape(s, -1)

    def image(pool):  # (S, T, kv, hd)
        return jnp.take(pool, rows, axis=0)[..., :kv * hd].reshape(
            s, rows.shape[1], kv, hd)

    kc, vc = image(k_pool), image(v_pool)
    f32 = dict(preferred_element_type=jnp.float32)
    sc = jnp.einsum("skgd,stkd->skgt", q.reshape(s, kv, h // kv, hd).astype(
        kc.dtype), kc, **f32) * resolve_scale(scale, hd)
    at = jnp.arange(rows.shape[1])[None, :]
    seen = at <= lengths[:, None]
    if window is not None:
        seen &= at > lengths[:, None] - window
    prob = jax.nn.softmax(jnp.where(seen[:, None, None, :], sc, _NEG_INF),
                          axis=-1)
    return jnp.einsum("skgt,stkd->skgd", prob.astype(vc.dtype), vc,
                      **f32).reshape(s, h, hd)


def mla_decode_xla(q_lat, q_rope, pool, tables, lengths, *, block_size,
                   scale):
    """The same over the gathered image of every lane's table (plain
    XLA: the kernel's oracle and the path of a sharded pool)."""
    s = q_lat.shape[0]
    c_dim, r_dim = q_lat.shape[-1], q_rope.shape[-1]
    bs = int(block_size)
    rows = (tables[:, :, None] * bs + jnp.arange(bs)[None, None, :])
    img = jnp.take(pool, rows.reshape(s, -1), axis=0)  # (S, T, W)
    c, kr = img[..., :c_dim], img[..., c_dim:c_dim + r_dim]
    sc = (
        jnp.einsum("shc,stc->sht", q_lat.astype(c.dtype), c,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("shr,str->sht", q_rope.astype(c.dtype), kr,
                     preferred_element_type=jnp.float32)
    ) * scale
    mask = jnp.arange(img.shape[1])[None, :] <= lengths[:, None]
    prob = jax.nn.softmax(jnp.where(mask[:, None, :], sc, _NEG_INF), axis=-1)
    return jnp.einsum("sht,stc->shc", prob.astype(c.dtype), c,
                      preferred_element_type=jnp.float32)
