"""The paged programs of a ``block='dense'`` model: the pre-LN stack
over a learned position table that ``ops.attention.TransformerBlock``
trains, re-expressed over block tables with the SAME numerics (fp32
LayerNorm statistics and softmax, fp32 MXU accumulation), so that greedy
decode through the pool is token-identical to recomputing the training
forward a token at a time (``tests/test_serving.py``).

**The pool.**  ``k``/``v``, one array a layer, ``(n_blocks · block_size,
row_width)``: a resident token holds one row a layer and side, its
heads side by side (``heads · head_dim`` numbers).  ``row_width`` is
that rounded up to 128 lanes: the device would pad a narrower row to as
much anyway, and it lays a tall array whose rows are no multiple of 128
out column-major, which every program would then copy to row-major and
back (a ``(…, heads, head_dim)`` pool is worse still: its two minor
dimensions are tiled, 2.6 times the bytes at 25 heads of 64).  The
layers' arrays are separate leaves of the state (never stacked), each
donated and updated in place by its program.  ``kv_dtype='int8'`` adds
the per-row/per-head fp32 scale planes ``ks``/``vs``, likewise one
``(rows, heads)`` array a layer.  Rows shard over ``dp`` when every
shard is a whole number of blocks, a row's heads over the Megatron
``tp`` shards that produce them (tensor parallelism is served through
GSPMD: params stay in their training layout under ``jit``).

**One body.**  ``_run`` embeds, runs every layer (``ln1 → q, k, v →``
write the layer's leaves ``→ attention → wo → ln2 → mlp``, generic over
the leading dimensions) and applies the head; the chunk program
(prefill and the speculative verify) and the decode program supply the
positions, the rows to write and the attention over the pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu.ops import pallas_paged
from theanompi_tpu.ops.pallas_flash import _NEG_INF
from theanompi_tpu.runtime.mesh import DATA_AXIS, TP_AXIS
from theanompi_tpu.serving.engine import TRASH_BLOCK

# The leaves ``_run`` multiplies by or gathers from, by their own names:
# the projections, the feed-forward's and the head's ``w``, the embedding
# and position tables.  LayerNorm scales and every bias enter float32
# arithmetic and stay as they are.
COMPUTE_LEAVES = frozenset(("wq", "wk", "wv", "wo", "w", "table", "pos"))


class DensePrograms:
    """What ``PagedServingEngine`` runs for a ``dense`` model: the
    state's layout and the bodies of its two jitted programs."""

    latent = False  # the scheduler reports no latent_rows_* stats
    lane_state = None  # every layer's past is rows in the pool

    def __init__(self, engine):
        cfg = engine.model.config
        if int(cfg.get("moe_experts", 0) or 0):
            raise ValueError("serving supports the dense FFN stack only "
                             "(moe_experts=0)")
        if engine.max_len > int(cfg.seq_len):
            raise ValueError(
                f"max_len={engine.max_len} exceeds the learned positional "
                f"table ({int(cfg.seq_len)} rows, config seq_len)"
            )
        self.engine = engine
        self.compute_dtype = engine.compute_dtype
        # what a float pool holds and an int8 pool's images are read in
        self.kv_compute_dtype = self.compute_dtype or jnp.float32
        self.n_heads = int(cfg.n_heads)
        self.head_dim = engine.d_model // self.n_heads
        self.scale = self.head_dim ** -0.5
        # rows a grid step of the decode kernel attends to, and a full
        # lane's steps
        self.attn_span, self.attn_steps = (
            engine.block_size, engine.blocks_per_seq)
        mesh = engine.mesh
        # pool rows shard over dp only when every per-device shard is a
        # whole number of blocks (a split block would tear the
        # gather/scatter row arithmetic across devices)
        row_ax = (
            DATA_AXIS
            if DATA_AXIS in mesh.shape
            and int(mesh.shape[DATA_AXIS]) > 1
            and engine.n_blocks % int(mesh.shape[DATA_AXIS]) == 0
            else None
        )
        head_ax = (
            TP_AXIS
            if TP_AXIS in mesh.shape and int(mesh.shape[TP_AXIS]) > 1
            else None
        )
        # one layer's pool is (rows, row_width) and its int8 scale plane
        # (rows, heads); one spec for both: rows over dp, a row's heads
        # over tp
        self.pool_spec = P(row_ax, head_ax)
        # a row holds its heads side by side, rounded up to 128 lanes
        # (module docstring); heads split over tp keep their exact width,
        # so that a shard is a whole number of heads
        width = self.n_heads * self.head_dim
        self.row_width = width if head_ax else -(-width // 128) * 128

    # ---- weights ---------------------------------------------------------
    @staticmethod
    def serving_params(params, compute_dtype):
        """``params`` with its ``COMPUTE_LEAVES`` in ``compute_dtype``
        (module docstring).  A leaf already there comes back as the same
        array, so a serving tree comes back as the arrays it is made of,
        and where ``compute_dtype`` is ``None`` every tree does; a cast
        leaf keeps its sharding (an elementwise operation on a placed
        array)."""
        if compute_dtype is None:
            return params

        def leaf(path, a):
            name = getattr(path[-1], "key", None)
            if name not in COMPUTE_LEAVES or a.dtype == compute_dtype:
                return a
            return jnp.asarray(a).astype(compute_dtype)

        return jax.tree_util.tree_map_with_path(leaf, params)

    # ---- state -----------------------------------------------------------
    def init_state(self):
        e = self.engine
        dt = jnp.int8 if e.kv_dtype == "int8" else self.kv_compute_dtype
        rows = e.n_blocks * e.block_size
        sh = NamedSharding(e.mesh, self.pool_spec)

        def leaves(width, dtype):
            return [jnp.zeros((rows, width), dtype, device=sh)
                    for _ in range(e.n_layers)]

        state = {side: leaves(self.row_width, dt) for side in ("k", "v")}
        if e.kv_dtype == "int8":
            for side in ("ks", "vs"):
                state[side] = leaves(self.n_heads, jnp.float32)
        return state

    def block_bytes(self) -> int:
        e = self.engine
        payload = (
            1 if e.kv_dtype == "int8"
            else jnp.dtype(self.kv_compute_dtype).itemsize
        )
        row = self.row_width * payload  # the stored width, padding and all
        if e.kv_dtype == "int8":
            row += self.n_heads * 4  # fp32 scale per (row, head)
        return 2 * e.n_layers * e.block_size * row

    def decode_rows(self, lengths) -> dict:
        """The key/value rows a decode call has to read for lanes at
        ``lengths`` (the decoding lanes'): ``length + 1`` a lane in
        every layer (as ``serving/latent.py``)."""
        n = np.asarray(lengths, np.int64) + 1
        return {"kv_rows_full": self.engine.n_layers * int(n.sum()),
                "kv_rows_window": 0}

    # ---- forward pieces (numerics mirror ops.attention exactly) ----------
    def _weights(self, params):
        """Split the Sequential params list: embedding, positions, the
        block dicts, final LN, logits head."""
        n = self.engine.n_layers
        emb, pos = params[0], params[1]
        blocks = params[2:2 + n]
        lnf, head = params[2 + n], params[3 + n]
        return emb, pos, blocks, lnf, head

    def _ln(self, p, x):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + 1e-5)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)

    def _proj(self, x, w):
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            with jax.named_scope("cast_weights"):
                w = w.astype(self.compute_dtype)
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        return y

    def _mlp(self, bp, x):
        w1, w2 = bp["mlp_in"]["w"], bp["mlp_out"]["w"]
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            with jax.named_scope("cast_weights"):
                w1 = w1.astype(self.compute_dtype)
                w2 = w2.astype(self.compute_dtype)
        h = jnp.dot(x, w1, preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h + bp["mlp_in"]["b"])
        if self.compute_dtype is not None:
            h = h.astype(self.compute_dtype)
        y = jnp.dot(h, w2, preferred_element_type=jnp.float32)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        return y + bp["mlp_out"]["b"].astype(y.dtype)

    def _embed(self, emb, pos, tokens, positions):
        x = jnp.take(emb["table"], tokens, axis=0)
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
        return x + jnp.take(pos["pos"], positions, axis=0).astype(x.dtype)

    def _head(self, lnf, head, x):
        x = self._ln(lnf, x)
        w = head["w"]
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            with jax.named_scope("cast_weights"):
                w = w.astype(self.compute_dtype)
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return y.astype(jnp.float32) + head["b"]

    # ---- the pool ----------------------------------------------------------
    def _gather_rows(self, tables):
        """(N, blocks_per_seq) block ids → (N, t_pad) physical rows:
        row j of a sequence's image is logical position j."""
        bs = self.engine.block_size
        rows = tables[:, :, None] * bs + jnp.arange(bs)[None, None, :]
        return rows.reshape(tables.shape[0], -1)

    def _pool_leaves(self, state):
        """The state's leaves as lists that a program replaces layer by
        layer (``_kv_write``; a float pool has no scale planes: ``None``
        a layer)."""
        pool = {side: list(leaves) for side, leaves in state.items()}
        for side in ("ks", "vs"):
            pool.setdefault(side, [None] * self.engine.n_layers)
        return pool

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
        if self.engine.kv_dtype == "int8":
            from theanompi_tpu.parallel.quantize import quantize_blocks

            rows, s = quantize_blocks(rows.astype(jnp.float32))
            scale_l = scale_l.at[wr].set(s)
        flat = rows.astype(pool_l.dtype).reshape(rows.shape[0], -1)
        flat = jnp.pad(flat, ((0, 0), (0, self.row_width - flat.shape[1])))
        pool[side][i], pool[side + "s"][i] = pool_l.at[wr].set(flat), scale_l
        return pool[side][i], scale_l

    def _kv_image(self, pool_l, scale_l, gr_flat, n):
        """Gather the attention image for one layer from its ``(rows,
        row_width)`` pool, and view it as (n, t_pad, H, hd) only after
        the gather (the lanes' rows, not the pool) — dequantizing int8
        payloads against their gathered scales."""
        h, hd, t_pad = self.n_heads, self.head_dim, self.engine.t_pad
        img = jnp.take(pool_l, gr_flat, axis=0)[:, :h * hd]
        img = img.reshape(n, t_pad, h, hd)
        if self.engine.kv_dtype == "int8":
            sc = jnp.take(scale_l, gr_flat, axis=0)
            img = img.astype(jnp.float32) * sc.reshape(n, t_pad, h)[..., None]
        return img.astype(self.kv_compute_dtype)

    # ---- the two programs --------------------------------------------------
    def _run(self, params, state, tokens, positions, plan, pick_rows):
        """Embed ``tokens`` (any leading dimensions: ``(P, C)`` for a
        chunk, ``(S,)`` for decode) at ``positions``, every layer over
        the pool it has just written, then the head over the rows
        ``pick_rows`` chooses.  ``plan()`` gives the program's own
        part, ``(wr, attention)``: the pool rows the tokens' K/V go to
        (flat, one a token) and ``attention(q, k_pool, k_scale, v_pool,
        v_scale) -> o``.  It is a function so that its index arithmetic
        is traced after the embedding: the order of operations in the
        lowered programs is pinned (``CHANGES.md``, PR 31)."""
        e = self.engine
        emb, pos, blocks, lnf, head = self._weights(params)
        lead = tokens.shape
        h, hd = self.n_heads, self.head_dim
        with jax.named_scope("embed"):
            x = self._embed(
                emb, pos, tokens, jnp.minimum(positions, e.max_len - 1)
            )  # (..., D)
        wr, attention = plan()
        pool = self._pool_leaves(state)
        # named scopes are metadata on the same operations: a profile
        # groups by them (layer<i>/qkv, .../cast_weights inside it, ...)
        for i, bp in enumerate(blocks):
            with jax.named_scope(f"layer{i}"):
                with jax.named_scope("qkv"):
                    y = self._ln(bp["ln1"], x)
                    q = self._proj(y, bp["attn"]["wq"]).reshape(*lead, h, hd)
                    k = self._proj(y, bp["attn"]["wk"]).reshape(*lead, h, hd)
                    v = self._proj(y, bp["attn"]["wv"]).reshape(*lead, h, hd)
                with jax.named_scope("pool_update"):
                    pk_l, pks_l = self._kv_write(
                        pool, "k", i, k.reshape(-1, h, hd), wr)
                    pv_l, pvs_l = self._kv_write(
                        pool, "v", i, v.reshape(-1, h, hd), wr)
                with jax.named_scope("paged_attn"):
                    o = attention(q, pk_l, pks_l, pv_l, pvs_l).astype(y.dtype)
                with jax.named_scope("attn_out"):
                    x = x + self._proj(
                        o.reshape(*lead, h * hd), bp["attn"]["wo"])
                with jax.named_scope("mlp"):
                    x = x + self._mlp(bp, self._ln(bp["ln2"], x))
        out = {side: pool[side] for side in state}
        with jax.named_scope("head"):
            logits = self._head(lnf, head, pick_rows(x))
        return out, logits

    def chunk_fn(self, params, state, tokens, tables, p0, true_len, active,
                 all_logits, lanes=None):
        e = self.engine
        p_, c_ = tokens.shape
        bs = e.block_size
        positions = p0[:, None] + jnp.arange(c_)[None, :]  # (P, C)

        def plan():
            blk_idx = jnp.minimum(positions // bs, e.blocks_per_seq - 1)
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
            mask = (jnp.arange(e.t_pad)[None, None, :]
                    <= positions[:, :, None])

            def attention(q, k_pool, k_scale, v_pool, v_scale):
                kc = self._kv_image(k_pool, k_scale, gr, p_)
                vc = self._kv_image(v_pool, v_scale, gr, p_)
                s = jnp.einsum(
                    "pchd,pthd->phct", q, kc,
                    preferred_element_type=jnp.float32,
                ) * self.scale
                s = jnp.where(mask[:, None, :, :], s, _NEG_INF)
                prob = jax.nn.softmax(s, axis=-1)
                return jnp.einsum(
                    "phct,pthd->pchd", prob.astype(vc.dtype), vc,
                    preferred_element_type=jnp.float32,
                )

            return wr, attention

        def pick_rows(x):
            if all_logits:
                return x  # (P, C, D)
            return jnp.take_along_axis(
                x, jnp.maximum(true_len - 1, 0)[:, None, None], axis=1
            )[:, 0]  # (P, D)

        return self._run(params, state, tokens, positions, plan, pick_rows)

    def decode_fn(self, params, state, tokens, tables, lengths, active):
        e = self.engine
        s_ = tokens.shape[0]
        bs = e.block_size
        pos_idx = lengths  # (S,) position of the incoming token

        def plan():
            blk = jnp.take_along_axis(
                tables,
                jnp.minimum(pos_idx // bs, e.blocks_per_seq - 1)[:, None],
                axis=1,
            )[:, 0]
            wr = jnp.where(active, blk * bs + pos_idx % bs, TRASH_BLOCK)
            gr = self._gather_rows(tables).reshape(-1)  # (S·t_pad,)
            att_mask = jnp.arange(e.t_pad)[None, :] <= pos_idx[:, None]

            if e.paged_attn_effective == "pallas":
                # the kernel's grid, one list for every layer's call
                steps = pallas_paged.lane_steps(
                    pos_idx, self.attn_span, self.attn_steps)

                def attention(q, k_pool, k_scale, v_pool, v_scale):
                    return pallas_paged.paged_decode_attention(
                        q, k_pool, v_pool, tables, pos_idx,
                        block_size=bs, scale=self.scale,
                        k_scale=k_scale, v_scale=v_scale, steps=steps,
                    )
            else:
                def attention(q, k_pool, k_scale, v_pool, v_scale):
                    kc = self._kv_image(k_pool, k_scale, gr, s_)
                    vc = self._kv_image(v_pool, v_scale, gr, s_)
                    s = jnp.einsum(
                        "shd,sthd->sht", q, kc,
                        preferred_element_type=jnp.float32,
                    ) * self.scale
                    s = jnp.where(att_mask[:, None, :], s, _NEG_INF)
                    prob = jax.nn.softmax(s, axis=-1)
                    return jnp.einsum(
                        "sht,sthd->shd", prob.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32,
                    )

            return wr, attention

        return self._run(params, state, tokens, pos_idx, plan, lambda x: x)
