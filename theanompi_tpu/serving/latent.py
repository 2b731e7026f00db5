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
(``lane_state``).

**Grouped-query layers** (``ops.latent_block.GroupedAttention``) keep
keys and values, one ``(rows, kv_heads · head_dim)`` array of each a
layer (the width rounded up to 128 lanes, as the latent rows'), of two
kinds by the layer's reach:

- a *global* layer's arrays are pool arrays like the latent ones:
  ``n_blocks · block_size`` rows behind the shared block table, the
  same rows of every layer holding the same token;
- a *window* layer (its query at ``p`` sees ``p - W < j <= p``) keeps
  a **ring** a lane instead, addressed by lane like the recurrent
  state: ``R`` blocks of its own, position ``p`` at ring block ``(p //
  block_size) mod R``.  ``R`` blocks hold ``W + C - 1`` rows (``C`` the
  engine's prefill chunk; at most the engine's positions): a chunk
  writes its ``C`` rows before it attends, and its first query still
  needs the ``W - 1`` before it, so no row a query reads has been
  overwritten.  A query reads a row only at a position inside its
  window and at or before itself, all of them written by its own
  request, so a ring needs no clearing when a lane changes hands.

Both read through a table: the lane's block table, or for a ring the
*column map* ``lane · R + (column mod R)``; prefill folds spans of the
context as the latent path does (a window layer from the span that
holds its first query's first key), decode runs
``pallas_paged.paged_decode_attention`` with ``kv_heads`` and, for a
ring, ``window`` (its XLA form ``paged_decode_xla`` elsewhere).  A ring
cannot be rebuilt from blocks either: a model with window layers is
served with prefix reuse off and refuses drafts as a recurrent one
does.

Each program also returns three counters of its expert layers, summed
or maximised over them: ``experts_hit`` (distinct held experts that
received a token), ``expert_load_max`` (the most tokens one expert
received) and ``pairs_routed`` (the token-expert pairs computed here:
the picks of useful tokens that fell on a held expert).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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


def paged_gqa_prefill(attn, q, k_pool, v_pool, tables, positions, *,
                      block_size: int, ctx_block: int):
    """Grouped-query attention of ``positions`` (P, C) queries a lane
    over keys and values read through ``tables`` (P, NT) (a ring's
    column map for a window layer), a span of ``ctx_block`` positions
    at a time with a running softmax, as ``paged_prefill_attention``;
    a window layer starts at the span that holds the call's first
    query's first key.  ``q`` (P · C, H, hd); returns the same shape."""
    p_, c_ = positions.shape
    kv, g, hd = attn.kv_heads, attn.group, attn.head_dim
    bs, nb = block_size, ctx_block // block_size
    n_spans = math.ceil(tables.shape[1] / nb)
    tables = jnp.pad(tables, ((0, 0), (0, n_spans * nb - tables.shape[1])))
    qg = q.reshape(p_, c_, kv, g, hd)
    f32 = dict(preferred_element_type=jnp.float32)

    def span(j, carry):
        m, den, acc = carry
        ids = lax.dynamic_slice_in_dim(tables, j * nb, nb, axis=1)
        rows = (ids[:, :, None] * bs + jnp.arange(bs)).reshape(p_, nb * bs)
        k = jnp.take(k_pool, rows, axis=0)[..., :kv * hd].reshape(
            p_, nb * bs, kv, hd)
        v = jnp.take(v_pool, rows, axis=0)[..., :kv * hd].reshape(
            p_, nb * bs, kv, hd)
        s = jnp.einsum("pckgd,ptkd->pkgct", qg, k, **f32) * attn.scale
        at = j * nb * bs + jnp.arange(nb * bs)
        seen = attn.mask(positions[:, :, None], at[None, None, :])
        s = jnp.where(seen[:, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        prob = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        den = den * corr + jnp.sum(prob, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "pkgct,ptkd->pkgcd", prob.astype(v.dtype), v, **f32)
        return m_new, den, acc

    init = (jnp.full((p_, kv, g, c_), _NEG_INF, jnp.float32),
            jnp.zeros((p_, kv, g, c_), jnp.float32),
            jnp.zeros((p_, kv, g, c_, hd), jnp.float32))
    reach = jnp.minimum(jnp.max(positions) // (nb * bs) + 1, n_spans)
    first = 0
    if attn.window is not None:
        first = jnp.maximum(jnp.min(positions) - attn.window + 1, 0) // (
            nb * bs)
    _, den, acc = lax.fori_loop(first, reach, span, init)
    o = (acc / den[..., None]).transpose(0, 3, 1, 2, 4)
    return o.reshape(p_ * c_, kv * g, hd).astype(q.dtype)


def _kind(block) -> str:
    """A block's kind of past: ``'mla'`` latent rows, ``'gqa'`` keys and
    values behind the table, ``'swa'`` the same in a window ring,
    ``'kda'`` recurrent state."""
    if block.kind == "gqa" and block.attn.window is not None:
        return "swa"
    return block.kind


def _lanes(n: int) -> int:
    """``n`` numbers a row rounded up to whole 128-lane tiles."""
    return -(-n // 128) * 128


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
        # a block's place among the blocks of its kind: the index of its
        # pool array, ring, or recurrent state
        self.kinds = [_kind(b) for b in self.blocks]
        seen = dict.fromkeys(("mla", "gqa", "swa", "kda"), 0)
        self.place = []
        for k in self.kinds:
            self.place.append(seen[k])
            seen[k] += 1
        self.n_latent, self.n_recurrent = seen["mla"], seen["kda"]
        self.n_full, self.n_window = seen["gqa"], seen["swa"]
        self.attn = next((b.attn for b in self.blocks if b.kind == "mla"),
                         None)
        self.grouped = next(
            (b.attn for b in self.blocks if b.kind == "gqa"), None)
        self.kda = next((b.kda for b in self.blocks if b.kda is not None),
                        None)
        bs = engine.block_size
        windows = {b.attn.window for b, k in zip(self.blocks, self.kinds)
                   if k == "swa"}
        if len(windows) > 1:
            raise ValueError(f"window layers of several widths {windows}")
        self.window: Optional[int] = windows.pop() if windows else None
        self.ring_blocks = 0
        if self.window is not None:
            # W + C - 1 rows (module docstring), whole blocks, never more
            # than a lane's table holds
            chunk = engine.prefill_chunk or engine.chunk_buckets[-1]
            rows = min(self.window + chunk - 1, engine.t_pad)
            self.ring_blocks = -(-rows // bs)
        self.ring_rows = self.ring_blocks * bs
        # the span of positions the prefill attention folds at a time:
        # 512 (its float32 scores, heads x chunk x span, are what a span
        # costs in memory), and a quarter of a short context so that the
        # tests' small engines loop too
        self.ctx_block = max(bs, min(512, engine.t_pad // 4) // bs * bs)
        # rows a grid step of the decode kernel attends to, and a full
        # lane's steps
        self.attn_span, self.attn_steps = (
            pallas_paged.mla_grid(bs, engine.blocks_per_seq) if self.n_latent
            else pallas_paged.gqa_grid(bs, engine.blocks_per_seq))
        self.row_width = _lanes(self.attn.row_dim) if self.attn else 0
        self.kv_width = _lanes(self.grouped.width) if self.grouped else 0
        # the rows' dtype: the compute dtype where the model names one,
        # else the dtype its weights are held in (activations follow
        # their weights, and the cache its activations)
        self.dtype = jnp.dtype(
            engine.compute_dtype
            or jax.tree.leaves(engine.model.params)[0].dtype)

    @property
    def recurrent(self) -> bool:
        """Some layer's past is a recurrent state a lane."""
        return self.n_recurrent > 0

    @property
    def lane_state(self) -> Optional[str]:
        """What of the layers' past lives a lane and not in blocks, so
        that a prefix cannot be rebuilt from blocks alone (no prefix
        reuse, no rolled-back drafts; a prefill row says its lane), or
        None."""
        parts = [name for n, name in ((self.n_recurrent, "recurrent state"),
                                      (self.n_window, "window rings")) if n]
        return " and ".join(parts) or None

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
        rows = e.n_blocks * e.block_size

        def arrays(n, shape):
            return [jnp.zeros(shape, self.dtype, device=sh) for _ in range(n)]

        state = {"kv": arrays(self.n_latent, (rows, self.row_width))}
        if self.n_full:
            state["k"] = arrays(self.n_full, (rows, self.kv_width))
            state["v"] = arrays(self.n_full, (rows, self.kv_width))
        if self.n_window:
            ring = (e.n_slots * self.ring_rows, self.kv_width)
            state["ring_k"] = arrays(self.n_window, ring)
            state["ring_v"] = arrays(self.n_window, ring)
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
        """Device bytes of one pool block over the layers that keep
        rows behind the table (latent rows; global keys and values)."""
        e = self.engine
        return e.block_size * self.dtype.itemsize * (
            self.n_latent * self.row_width + 2 * self.n_full * self.kv_width)

    def ring_bytes(self) -> int:
        """Device bytes of the window layers' rings over all lanes (0
        for a model without window layers)."""
        return (2 * self.n_window * self.engine.n_slots * self.ring_rows
                * self.kv_width * self.dtype.itemsize)

    def decode_rows(self, lengths) -> dict:
        """The key/value rows a decode call has to read for lanes at
        ``lengths`` (host ints, the decoding lanes'), summed over the
        layers of each reach: ``length + 1`` a lane in a layer that
        attends to the whole context (latent or global), ``min(length +
        1, W)`` in a window layer."""
        n = np.asarray(lengths, np.int64) + 1
        window = 0
        if self.n_window:
            window = self.n_window * int(np.minimum(n, self.window).sum())
        return {"kv_rows_full": (self.n_latent + self.n_full) * int(n.sum()),
                "kv_rows_window": window}

    def prefill_window_rows(self, p0: int, n: int) -> int:
        """Rows the window layers read for a chunk of ``n`` tokens at
        ``p0``: the keys from its first query's first to its last."""
        if not self.n_window:
            return 0
        return self.n_window * (p0 + n - max(0, p0 - self.window + 1))

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
             recur, pick_rows, ring_wr=None):
        """Embed, every block with ``attention[kind](…)`` over the pool
        or ring it has just written (the tokens' rows: ``wr`` in the
        pool, ``ring_wr`` in the rings) or ``recur(mp, u, g, beta, s,
        conv) -> (o, s, conv)`` over the lanes' state, then norm and head
        over the rows ``pick_rows`` chooses."""
        writes = {"mla": wr, "gqa": wr, "swa": ring_wr}
        x, _ = self.embed.apply(params[0], {}, tokens)
        arrays = {k: list(v) for k, v in state.items()}
        kv = arrays["kv"]
        mats, conv = arrays.get("kda", []), arrays.get("conv", [])
        hit, load, pairs = 0, 0, 0
        for i, block in enumerate(self.blocks):
            j, kind = self.place[i], self.kinds[i]

            def latent(ap, q_nope, q_rope, row, j=j):
                with jax.named_scope("pool_update"):
                    row = jnp.pad(row.astype(kv[j].dtype), (
                        (0, 0), (0, self.row_width - row.shape[1])))
                    kv[j] = kv[j].at[writes["mla"]].set(row)
                return attention["mla"](ap, q_nope, q_rope, kv[j])

            def grouped(ap, q, k, v, j=j, kind=kind, attn=block.attn):
                ks, vs = arrays["k" if kind == "gqa" else "ring_k"], arrays[
                    "v" if kind == "gqa" else "ring_v"]
                with jax.named_scope("pool_update"):
                    for pool, new in ((ks, k), (vs, v)):
                        new = jnp.pad(new.astype(pool[j].dtype), (
                            (0, 0), (0, self.kv_width - new.shape[1])))
                        pool[j] = pool[j].at[writes[kind]].set(
                            new, mode="drop")
                return attention[kind](attn, q, ks[j], vs[j]).astype(q.dtype)

            def mix(mp, u, g, beta, j=j):
                o, mats[j], conv[j] = recur(mp, u, g, beta, mats[j], conv[j])
                return o

            attend = {"mla": latent, "gqa": grouped, "swa": grouped,
                      "kda": mix}[kind]
            with jax.named_scope(f"layer{i}"):
                x, counts = block.forward(
                    params[1 + i], x, positions, attend, valid=valid,
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
        return arrays, logits, counters

    def _ring_tables(self, lanes):
        """The rings' column map: column ``c`` of lane ``l`` is ring
        block ``l · R + c mod R`` (``lanes`` (P,) held to real lanes)."""
        cols = jnp.arange(self.engine.blocks_per_seq) % self.ring_blocks
        return lanes[:, None] * self.ring_blocks + cols[None, :]

    def _ring_rows(self, lanes, positions, valid):
        """The ring rows that ``positions`` of ``lanes`` go to; past the
        rings (dropped) where not ``valid``."""
        e = self.engine
        return jnp.where(valid, lanes * self.ring_rows
                         + positions % self.ring_rows,
                         e.n_slots * self.ring_rows)

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

        def latent(ap, q_nope, q_rope, pool):
            return paged_prefill_attention(
                self.attn, ap, q_nope, q_rope, pool, tables, positions,
                block_size=bs, ctx_block=self.ctx_block)

        def grouped(table):
            return lambda attn, q, k_pool, v_pool: paged_gqa_prefill(
                attn, q, k_pool, v_pool, table, positions, block_size=bs,
                ctx_block=self.ctx_block)

        attention = {"mla": latent, "gqa": grouped(tables)}
        ring_wr = None
        if self.n_window:
            src = jnp.minimum(lanes, e.n_slots - 1)
            ring_wr = self._ring_rows(src[:, None], positions,
                                      valid).reshape(-1)
            attention["swa"] = grouped(self._ring_tables(src))

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
                         wr.reshape(-1), attention, recur, pick_rows, ring_wr)

    def decode_fn(self, params, state, tokens, tables, lengths, active):
        e = self.engine
        bs = e.block_size
        blk = jnp.take_along_axis(
            tables, jnp.minimum(lengths // bs, e.blocks_per_seq - 1)[:, None],
            axis=1)[:, 0]
        wr = jnp.where(active, blk * bs + lengths % bs, TRASH_BLOCK)
        kernel = self.impl == "pallas"
        if kernel:
            # the kernels' grids, one list for every call of a kind
            decode = functools.partial(
                pallas_paged.mla_paged_decode, steps=pallas_paged.lane_steps(
                    lengths, self.attn_span, self.attn_steps))
        else:
            decode = pallas_paged.mla_decode_xla

        def latent(ap, q_nope, q_rope, pool):
            o_lat = decode(self.attn.absorb(ap, q_nope), q_rope, pool, tables,
                           lengths, block_size=bs, scale=self.attn.scale)
            return self.attn.unabsorb(ap, o_lat, q_nope.dtype)

        def grouped(table, window):
            if kernel:
                starts = pallas_paged.window_starts(lengths, window)
                attend = functools.partial(
                    pallas_paged.paged_decode_attention,
                    steps=pallas_paged.lane_steps(
                        lengths, *pallas_paged.gqa_grid(bs, e.blocks_per_seq),
                        starts))
            else:
                attend = pallas_paged.paged_decode_xla
            return lambda attn, q, k_pool, v_pool: attend(
                q, k_pool, v_pool, table, lengths, block_size=bs,
                scale=attn.scale, kv_heads=attn.kv_heads, window=window)

        attention = {"mla": latent}
        if self.n_full:
            attention["gqa"] = grouped(tables, None)
        ring_wr = None
        if self.n_window:
            lanes = jnp.arange(e.n_slots, dtype=jnp.int32)
            ring_wr = self._ring_rows(lanes, lengths, active)
            attention["swa"] = grouped(self._ring_tables(lanes), self.window)

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
                         attention, recur, lambda x: x, ring_wr)
