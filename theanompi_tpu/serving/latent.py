"""The paged programs of a ``block='latent_moe'`` model
(``ops.latent_block``): a **latent block pool** and two attention paths
over it.

**The pool.**  One array a layer, ``(n_blocks · block_size, width)``: a
resident token holds ONE row a layer, ``[c_kv | k_rope]`` (``kv_rank +
rope`` numbers), shared by every head — not ``2 · heads · head_dim``.
``width`` is that rounded up to 128 lanes: the device would pad a
narrower row to as much anyway, and it lays a tall array whose rows are
no multiple of 128 out column-major, which every program would then
copy to row-major and back.  Blocks,
block tables, the trash block, the prefix caches and the scheduler are
those of ``serving.paging``: a block is still ``block_size`` rows, and
what a row holds is the model's business.  The layers' arrays are
separate leaves of the state (never stacked), each donated and updated
in place by its program.

**Two attention paths**, both reading rows the program has just written
(so a chunk attends to itself through the pool, and chunked prefill
equals whole-prompt prefill):

- *prefill* (a chunk of queries a lane): **expanded**, blocked over the
  context.  A loop over spans of ``ctx_block`` positions gathers the
  lanes' rows of that span through their tables, expands keys and
  values from them (``c_kv W_kvb``), and folds the span into a running
  softmax; it stops after the last span any lane of the call reaches,
  so the scores of a 2,048-token chunk against 17,408 positions are
  never whole and a short context pays for its own length.  For a chunk
  of ``C`` queries a row's expansion (2 · kv_rank · heads · (nope + v)
  operations) is shared by ``C`` queries; absorbing instead would spend
  (kv_rank + rope + kv_rank) / (nope + rope + v) = 3.4 times the
  attention operations to save it, which pays only under ~170 queries
  a lane.
- *decode* (one query a lane): **absorbed** — the query is carried into
  the latent space, every head attends to the same 576-wide rows, and
  the result is carried out: the ``mla_paged_decode`` kernel
  (``ops.pallas_paged``) on a single-device pool, its XLA form
  elsewhere.

The block's forward pass is ``LatentMoeBlock.forward``, the one the
model's own ``apply`` runs; these programs only supply its ``attend``.

Each program also returns two counters of its expert layers, summed or
maximised over them: ``experts_hit`` (distinct experts that received a
token) and ``expert_load_max`` (the most tokens one expert received).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu.ops import platform
from theanompi_tpu.ops.pallas_flash import _NEG_INF
from theanompi_tpu.serving.engine import TRASH_BLOCK


def paged_prefill_attention(attn, ap, q_nope, q_rope, pool, tables,
                            positions, *, block_size: int, ctx_block: int):
    """Expanded attention of ``positions`` (P, C) queries a lane over the
    lanes' resident rows, a span of ``ctx_block`` positions at a time
    (module docstring).  Returns (P · C, H, v_dim)."""
    p_, c_ = positions.shape
    h = attn.n_heads
    bs, nb = block_size, ctx_block // block_size
    n_spans = math.ceil(tables.shape[1] / nb)
    tables = jnp.pad(tables, ((0, 0), (0, n_spans * nb - tables.shape[1])))
    qn = q_nope.reshape(p_, c_, h, -1)
    qr = q_rope.reshape(p_, c_, h, -1)
    f32 = dict(preferred_element_type=jnp.float32)

    def span(j, carry):
        m, den, acc = carry
        ids = lax.dynamic_slice_in_dim(tables, j * nb, nb, axis=1)
        rows = (ids[:, :, None] * bs + jnp.arange(bs)).reshape(p_, nb * bs)
        lat = jnp.take(pool, rows, axis=0)  # (P, span, row)
        k_nope, v = attn.expand(ap, lat[..., :attn.kv_rank])
        k_rope = lat[..., attn.kv_rank:attn.row_dim]
        s = (
            jnp.einsum("pchd,pthd->phct", qn, k_nope, **f32)
            + jnp.einsum("pchr,ptr->phct", qr, k_rope, **f32)
        ) * attn.scale
        at = j * nb * bs + jnp.arange(nb * bs)
        mask = at[None, None, :] <= positions[:, :, None]  # causal, absolute
        s = jnp.where(mask[:, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        prob = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        den = den * corr + jnp.sum(prob, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "phct,pthd->phcd", prob.astype(v.dtype), v, **f32)
        return m_new, den, acc

    init = (jnp.full((p_, h, c_), _NEG_INF, jnp.float32),
            jnp.zeros((p_, h, c_), jnp.float32),
            jnp.zeros((p_, h, c_, attn.v_dim), jnp.float32))
    reach = jnp.minimum(jnp.max(positions) // (nb * bs) + 1, n_spans)
    _, den, acc = lax.fori_loop(0, reach, span, init)
    o = (acc / den[..., None]).transpose(0, 2, 1, 3)
    return o.reshape(p_ * c_, h, attn.v_dim).astype(q_nope.dtype)


class LatentPrograms:
    """What ``PagedServingEngine`` runs for a ``latent_moe`` model: the
    state's layout and the bodies of its two jitted programs."""

    latent = True  # the scheduler reports the latent_rows_* stats

    def __init__(self, engine):
        if engine.kv_dtype != "fp32":
            raise ValueError("a latent pool holds the compute dtype "
                             "(kv_dtype='fp32')")
        layers = engine.model.net.layers
        self.engine = engine
        self.embed, self.blocks = layers[0], layers[1:-2]
        self.norm, self.head = layers[-2], layers[-1]
        self.attn = self.blocks[0].attn
        bs = engine.block_size
        # the span of positions the prefill attention folds at a time:
        # 512 (its float32 scores, heads x chunk x span, are what a span
        # costs in memory), and a quarter of a short context so that the
        # tests' small engines loop too
        self.ctx_block = max(bs, min(512, engine.t_pad // 4) // bs * bs)
        self.row_width = -(-self.attn.row_dim // 128) * 128
        # the rows' dtype: the compute dtype where the model names one,
        # else the dtype its weights are held in (activations follow
        # their weights, and the cache its activations)
        self.dtype = jnp.dtype(
            engine.compute_dtype
            or jax.tree.leaves(engine.model.params)[0].dtype)

    @property
    def impl(self) -> str:
        """``'pallas'``: the named kernels (latent decode, grouped
        experts, hyper-connections); ``'xla'``: their plain forms.  The
        engine's one selection rule, except that ``paged_attn='auto'``
        takes the kernels on a TPU only: interpreted on the CPU they are
        for the tests that ask for them (``paged_attn='pallas'``)."""
        e = self.engine
        if e.paged_attn == "auto" and not platform.on_tpu():
            return "xla"
        return e.paged_attn_effective

    # ---- weights ---------------------------------------------------------
    @staticmethod
    def serving_params(params, compute_dtype):
        """The tree as it is: a latent model's weights are held in the
        dtype it computes in (``serving/dense.py`` has the family whose
        are not)."""
        return params

    # ---- state -----------------------------------------------------------
    def init_state(self):
        e = self.engine
        sh = NamedSharding(e.mesh, P())
        shape = (e.n_blocks * e.block_size, self.row_width)
        return {"kv": [jnp.zeros(shape, self.dtype, device=sh)
                       for _ in self.blocks]}

    def block_bytes(self) -> int:
        e = self.engine
        return (len(self.blocks) * e.block_size * self.row_width
                * self.dtype.itemsize)

    # ---- the two programs --------------------------------------------------
    def _run(self, params, state, tokens, positions, valid, wr, attention,
             pick_rows):
        """Embed, every block with ``attention(ap, q…, pool) -> o`` over
        the pool it has just written, then norm and head over the rows
        ``pick_rows`` chooses."""
        x, _ = self.embed.apply(params[0], {}, tokens)
        kv, hit, load = list(state["kv"]), 0, 0
        for i, block in enumerate(self.blocks):
            def attend(ap, q_nope, q_rope, row, i=i):
                with jax.named_scope("pool_update"):
                    row = jnp.pad(row.astype(kv[i].dtype), (
                        (0, 0), (0, self.row_width - row.shape[1])))
                    kv[i] = kv[i].at[wr].set(row)
                return attention(ap, q_nope, q_rope, kv[i])

            with jax.named_scope(f"layer{i}"):
                x, counts = block.forward(
                    params[1 + i], x, positions, attend, valid=valid,
                    impl=self.impl)
            if counts is not None:
                hit = hit + jnp.sum(counts > 0)
                load = jnp.maximum(load, jnp.max(counts))
        with jax.named_scope("head"):
            x, _ = self.norm.apply(params[-2], {}, pick_rows(x))
            logits, _ = self.head.apply(params[-1], {}, x)
        counters = jnp.stack([jnp.asarray(hit, jnp.int32),
                              jnp.asarray(load, jnp.int32)])
        return {"kv": kv}, logits, counters

    def chunk_fn(self, params, state, tokens, tables, p0, true_len, active,
                 all_logits):
        e = self.engine
        p_, c_ = tokens.shape
        bs = e.block_size
        positions = p0[:, None] + jnp.arange(c_)[None, :]
        blk = jnp.take_along_axis(
            tables, jnp.minimum(positions // bs, e.blocks_per_seq - 1), axis=1)
        valid = active[:, None] & (jnp.arange(c_)[None, :] < true_len[:, None])
        wr = jnp.where(valid, blk * bs + positions % bs, TRASH_BLOCK)

        def attention(ap, q_nope, q_rope, pool):
            return paged_prefill_attention(
                self.attn, ap, q_nope, q_rope, pool, tables, positions,
                block_size=bs, ctx_block=self.ctx_block)

        def pick_rows(x):
            x = x.reshape(p_, c_, -1)
            if all_logits:
                return x
            return jnp.take_along_axis(
                x, jnp.maximum(true_len - 1, 0)[:, None, None], axis=1)[:, 0]

        return self._run(params, state, tokens.reshape(-1),
                         positions.reshape(-1), valid.reshape(-1),
                         wr.reshape(-1), attention, pick_rows)

    def decode_fn(self, params, state, tokens, tables, lengths, active):
        from theanompi_tpu.ops import pallas_paged

        e = self.engine
        bs = e.block_size
        blk = jnp.take_along_axis(
            tables, jnp.minimum(lengths // bs, e.blocks_per_seq - 1)[:, None],
            axis=1)[:, 0]
        wr = jnp.where(active, blk * bs + lengths % bs, TRASH_BLOCK)
        decode = (pallas_paged.mla_paged_decode if self.impl == "pallas"
                  else pallas_paged.mla_decode_xla)

        def attention(ap, q_nope, q_rope, pool):
            o_lat = decode(self.attn.absorb(ap, q_nope), q_rope, pool, tables,
                           lengths, block_size=bs, scale=self.attn.scale)
            return self.attn.unabsorb(ap, o_lat, q_nope.dtype)

        return self._run(params, state, tokens, lengths, active, wr,
                         attention, lambda x: x)
