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

**Recurrent layers.**  A block whose mixer is Kimi delta attention
(``ops.kda``) holds no rows: the pool has an array for each *latent*
layer only, and beside it the state holds, for each recurrent layer,
``kda`` (``n_slots``, heads, K, K) float32 — a lane's matrices — and
``conv`` (``n_slots``, 3, 3 · heads · K) — the convolution's last
inputs.  They are addressed by lane and not by block table: a prefill
row says which lane it is (``lanes``), a lane's state is cleared where a
request's first chunk enters (``p0 == 0``), carried from chunk to chunk
and from the last chunk into decode; padding positions (``g = 0, β =
0``), padding rows (written nowhere) and inactive lanes leave it as it
was.  A prefix cannot be rebuilt from blocks alone for such a model:
the engine serves it with prefix reuse off and refuses to verify drafts
(``recurrent``).

Each program also returns three counters of its expert layers, summed
or maximised over them: ``experts_hit`` (distinct held experts that
received a token), ``expert_load_max`` (the most tokens one expert
received) and ``pairs_routed`` (the token-expert pairs computed here:
the picks of useful tokens that fell on a held expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu.ops import kda, pallas_paged, platform
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
        # a block's place among the blocks of its kind: the index of its
        # pool array, or of its recurrent state
        seen = {"mla": 0, "kda": 0}
        self.place = []
        for b in self.blocks:
            self.place.append(seen[b.kind])
            seen[b.kind] += 1
        self.n_latent, self.n_recurrent = seen["mla"], seen["kda"]
        self.kda = next((b.kda for b in self.blocks if b.kda is not None),
                        None)
        bs = engine.block_size
        # the span of positions the prefill attention folds at a time:
        # 512 (its float32 scores, heads x chunk x span, are what a span
        # costs in memory), and a quarter of a short context so that the
        # tests' small engines loop too
        self.ctx_block = max(bs, min(512, engine.t_pad // 4) // bs * bs)
        # rows a grid step of the decode kernel attends to, and a full
        # lane's steps
        self.attn_span, self.attn_steps = pallas_paged.mla_grid(
            bs, engine.blocks_per_seq)
        self.row_width = -(-self.attn.row_dim // 128) * 128
        # the rows' dtype: the compute dtype where the model names one,
        # else the dtype its weights are held in (activations follow
        # their weights, and the cache its activations)
        self.dtype = jnp.dtype(
            engine.compute_dtype
            or jax.tree.leaves(engine.model.params)[0].dtype)

    @property
    def recurrent(self) -> bool:
        """Some layer's past is per-lane state, not rows: no prefix
        reuse, no rolled-back drafts."""
        return self.n_recurrent > 0

    @property
    def impl(self) -> str:
        """``'pallas'``: the named kernels (latent decode, grouped
        experts, hyper-connections, the recurrent step and scan);
        ``'xla'``: their plain forms.  The engine's one selection rule,
        except that ``paged_attn='auto'`` takes the kernels on a TPU
        only: interpreted on the CPU they are for the tests that ask for
        them (``paged_attn='pallas'``)."""
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
        state = {"kv": [jnp.zeros(shape, self.dtype, device=sh)
                        for _ in range(self.n_latent)]}
        if self.recurrent:
            m = self.kda
            state["kda"] = [
                jnp.zeros((e.n_slots, m.n_heads, m.head_dim, m.head_dim),
                          jnp.float32, device=sh)
                for _ in range(self.n_recurrent)]
            state["conv"] = [
                jnp.zeros((e.n_slots, m.conv - 1, 3 * m.width), self.dtype,
                          device=sh)
                for _ in range(self.n_recurrent)]
        return state

    def block_bytes(self) -> int:
        e = self.engine
        return (self.n_latent * e.block_size * self.row_width
                * self.dtype.itemsize)

    def recurrent_state_bytes(self) -> int:
        """Device bytes of the per-lane state over all recurrent layers
        and lanes (0 for a model without such layers)."""
        if not self.recurrent:
            return 0
        m, e = self.kda, self.engine
        lane = (m.n_heads * m.head_dim * m.head_dim * 4
                + (m.conv - 1) * 3 * m.width * self.dtype.itemsize)
        return self.n_recurrent * e.n_slots * lane

    # ---- the two programs --------------------------------------------------
    def _run(self, params, state, tokens, positions, valid, wr, attention,
             recur, pick_rows):
        """Embed, every block with ``attention(ap, q…, pool) -> o`` over
        the pool it has just written or ``recur(mp, u, g, beta, s, conv)
        -> (o, s, conv)`` over the lanes' state, then norm and head over
        the rows ``pick_rows`` chooses."""
        x, _ = self.embed.apply(params[0], {}, tokens)
        kv = list(state["kv"])
        mats, conv = list(state.get("kda", ())), list(state.get("conv", ()))
        hit, load, pairs = 0, 0, 0
        for i, block in enumerate(self.blocks):
            j = self.place[i]

            def attend(ap, q_nope, q_rope, row, j=j):
                with jax.named_scope("pool_update"):
                    row = jnp.pad(row.astype(kv[j].dtype), (
                        (0, 0), (0, self.row_width - row.shape[1])))
                    kv[j] = kv[j].at[wr].set(row)
                return attention(ap, q_nope, q_rope, kv[j])

            def mix(mp, u, g, beta, j=j):
                o, mats[j], conv[j] = recur(mp, u, g, beta, mats[j], conv[j])
                return o

            with jax.named_scope(f"layer{i}"):
                x, counts = block.forward(
                    params[1 + i], x, positions,
                    attend if block.kda is None else mix, valid=valid,
                    impl=self.impl)
            if counts is not None:
                hit = hit + jnp.sum(counts > 0)
                load = jnp.maximum(load, jnp.max(counts))
                pairs = pairs + jnp.sum(counts)
        with jax.named_scope("head"):
            x, _ = self.norm.apply(params[-2], {}, pick_rows(x))
            logits, _ = self.head.apply(params[-1], {}, x)
        counters = jnp.stack([jnp.asarray(c, jnp.int32)
                              for c in (hit, load, pairs)])
        new = {"kv": kv}
        if self.recurrent:
            new.update(kda=mats, conv=conv)
        return new, logits, counters

    def chunk_fn(self, params, state, tokens, tables, p0, true_len, active,
                 all_logits, lanes=None):
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

        def recur(mp, u, g, beta, mats, conv):
            """The rows' chunks through the recurrence, from their lanes'
            state (an empty one where a request's first chunk enters) and
            back into it."""
            m = self.kda
            h, k = m.n_heads, m.head_dim
            src = jnp.minimum(lanes, e.n_slots - 1)
            fresh = active & (p0 == 0)
            s0 = jnp.where(fresh[:, None, None, None], 0.0, mats[src])
            past = jnp.where(fresh[:, None, None], 0, conv[src])
            u = u.reshape(p_, c_, -1)
            g = jnp.where(valid[..., None, None], g.reshape(p_, c_, h, k), 0.0)
            beta = jnp.where(valid[..., None], beta.reshape(p_, c_, h), 0.0)
            q, kk, v = m.convolve(mp, past, u)
            scan = (kda.kda_chunk_prefill if self.impl == "pallas"
                    else kda.kda_chunk_xla)
            o, s1 = scan(s0, q, kk, v, g, beta)
            with jax.named_scope("state_update"):
                # the last inputs behind the row's real tokens; a padding
                # row (lane n_slots) is written nowhere
                seq = jnp.concatenate([past, u], axis=1)
                last = jax.vmap(lambda a, n: lax.dynamic_slice_in_dim(
                    a, n, m.conv - 1, axis=0))(seq, true_len)
                dst = jnp.where(active, lanes, e.n_slots)
                mats = mats.at[dst].set(s1, mode="drop")
                conv = conv.at[dst].set(last, mode="drop")
            return o.reshape(p_ * c_, h, k), mats, conv

        def pick_rows(x):
            x = x.reshape(p_, c_, -1)
            if all_logits:
                return x
            return jnp.take_along_axis(
                x, jnp.maximum(true_len - 1, 0)[:, None, None], axis=1)[:, 0]

        return self._run(params, state, tokens.reshape(-1),
                         positions.reshape(-1), valid.reshape(-1),
                         wr.reshape(-1), attention, recur, pick_rows)

    def decode_fn(self, params, state, tokens, tables, lengths, active):
        e = self.engine
        bs = e.block_size
        blk = jnp.take_along_axis(
            tables, jnp.minimum(lengths // bs, e.blocks_per_seq - 1)[:, None],
            axis=1)[:, 0]
        wr = jnp.where(active, blk * bs + lengths % bs, TRASH_BLOCK)
        if self.impl == "pallas":
            # the kernel's grid, one list for every latent layer's call
            decode = functools.partial(
                pallas_paged.mla_paged_decode, steps=pallas_paged.lane_steps(
                    lengths, self.attn_span, self.attn_steps))
        else:
            decode = pallas_paged.mla_decode_xla

        def attention(ap, q_nope, q_rope, pool):
            o_lat = decode(self.attn.absorb(ap, q_nope), q_rope, pool, tables,
                           lengths, block_size=bs, scale=self.attn.scale)
            return self.attn.unabsorb(ap, o_lat, q_nope.dtype)

        def recur(mp, u, g, beta, mats, conv):
            """One token a lane; an inactive lane (``g = 0, β = 0``, its
            inputs not shifted in) keeps its state."""
            g = jnp.where(active[:, None, None], g, 0.0)
            beta = jnp.where(active[:, None], beta, 0.0)
            q, kk, v = (a[:, 0] for a in
                        self.kda.convolve(mp, conv, u[:, None, :]))
            step = (kda.kda_decode if self.impl == "pallas"
                    else kda.kda_step_xla)
            mats, o = step(mats, q, kk, v, g, beta)
            with jax.named_scope("state_update"):
                shifted = jnp.concatenate(
                    [conv[:, 1:], u[:, None, :].astype(conv.dtype)], axis=1)
                conv = jnp.where(active[:, None, None], shifted, conv)
            return o, mats, conv

        return self._run(params, state, tokens, lengths, active, wr,
                         attention, recur, lambda x: x)
