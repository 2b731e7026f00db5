"""A decoder block with latent attention, sparse experts and a
multi-stream residual — written once, for training's ``apply`` and for
the server's prefill and decode alike.

What differs between the three is **where the keys and values come
from**, and that is the one thing the block does not decide: its
``forward`` computes the queries and the token's cache row and hands
them to an ``attend`` function of the caller's —

- ``apply`` (a whole sequence, no cache): ``causal_attend`` below,
  which expands keys and values from the rows of the sequence itself;
- the paged prefill and decode (``serving/latent.py``): functions that
  write the rows into the block pool and attend through a block table,
  expanded over a blocked context for a chunk, absorbed for one token.

**The mixer** is one of three kinds, a block's ``kind``: latent
attention (``'mla'``), grouped-query attention (``'gqa'``,
``GroupedAttention``: keys and values of ``kv_heads`` heads, each shared
by a group of query heads; windowed or global, rotary or not, layer by
layer; its ``attend`` takes the queries, keys and values) or Kimi delta
attention (``'kda'``, ``ops.kda``: no rows in a cache but a matrix a
head and the convolution's last inputs; its ``attend`` takes the
convolution's inputs, the decay and the step and returns the heads'
outputs, and owns that state).

**Latent attention** (as DeepSeek-V3; with ``q_rank`` ``None`` the
queries come from one matrix, with ``rotary=False`` no position enters
and the ``rope`` dimensions are plain ones).  A token's
cache row is ``[c_kv | k_rope]``: the normed compression of its keys and
values and one rotary key shared by every head.  Two orders of the same
products: *expanded*, ``[k_nope | v] = c_kv W_kvb`` per head and
attention as usual; *absorbed*, the query carried into the latent space
(``q_nope W_kvb[k]ᵀ``), attention over the rows themselves, and the
result carried out (``· W_kvb[v]``) — ``LatentAttention.absorb`` /
``unabsorb``.

**The residual** is ``n_streams`` streams mixed by manifold-constrained
hyper-connections (``ops.pallas_mhc``); the state is ``(tokens,
n_streams · d)``.  One stream is the plain residual ``x + F(norm(x))``
and has no hyper-connection parameters.

**The feed-forward** is a gated dense one or ``parallel.moe.MoeMlp``,
whose router reads the feed-forward's normed input or, with
``route_from_attn``, the attention's (SmallThinker's router placed
before attention).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from theanompi_tpu.ops import pallas_mhc
from theanompi_tpu.ops.attention import (
    gated_ffn, rms_norm, rope_half, rope_interleaved, yarn_inv_freq,
    yarn_mscale,
)
from theanompi_tpu.ops.layers import Layer, normal_init
from theanompi_tpu.ops.pallas_flash import _NEG_INF


def _rotate(layout: str):
    """The rotary function of a layout, looked up when it is called."""
    return {"interleaved": rope_interleaved, "half": rope_half}[layout]


def _mm(x, w):
    """``x @ w`` with operands in ``x``'s dtype, fp32 accumulation."""
    with jax.named_scope("cast_weights"):
        w = w.astype(x.dtype)
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


class LatentAttention:
    """Sizes and products of the latent attention; no state."""

    kind = "mla"

    def __init__(self, d_model, n_heads, q_rank, kv_rank, nope, rope, v_dim,
                 norm_eps, rope_cfg, rotary: bool = True,
                 rope_layout: str = "interleaved"):
        self.d_model, self.n_heads = d_model, n_heads
        self.rotary, self.rope_layout = rotary, rope_layout
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope, self.rope, self.v_dim = nope, rope, v_dim
        self.norm_eps = norm_eps
        factor = float(rope_cfg.get("factor", 1.0))
        self.inv_freq = yarn_inv_freq(
            rope, float(rope_cfg.get("theta", 10000.0)), factor,
            int(rope_cfg.get("original_max_position", 4096)),
            float(rope_cfg.get("beta_fast", 32.0)),
            float(rope_cfg.get("beta_slow", 1.0)))
        mscale = float(rope_cfg.get("mscale", 1.0))
        all_dim = float(rope_cfg.get("mscale_all_dim", 0.0))
        # what multiplies cos and sin, and what the softmax scale takes
        self.rope_scale = (yarn_mscale(factor, mscale)
                           / yarn_mscale(factor, all_dim))
        self.scale = (nope + rope) ** -0.5 * (
            yarn_mscale(factor, all_dim) ** 2 if all_dim else 1.0)
        self.row_dim = kv_rank + rope

    def init(self, key, dtype):
        d, h = self.d_model, self.n_heads
        ks = jax.random.split(key, 5)
        w = normal_init(0.02)
        if self.q_rank is None:
            queries = {"wq": w(ks[0], (d, h * (self.nope + self.rope)), d,
                               dtype)}
        else:
            queries = {
                "wq_a": w(ks[0], (d, self.q_rank), d, dtype),
                "q_norm": jnp.ones((self.q_rank,), dtype),
                "wq_b": w(ks[1], (self.q_rank, h * (self.nope + self.rope)),
                          self.q_rank, dtype),
            }
        return {
            **queries,
            "wkv_a": w(ks[2], (d, self.row_dim), d, dtype),
            "kv_norm": jnp.ones((self.kv_rank,), dtype),
            "wkv_b": w(ks[3], (self.kv_rank, h * (self.nope + self.v_dim)),
                       self.kv_rank, dtype),
            "wo": w(ks[4], (h * self.v_dim, d), h * self.v_dim, dtype),
        }

    def project(self, ap, hid, positions):
        """``(q_nope (N, H, nope), q_rope (N, H, rope), row (N, kv_rank
        + rope))`` of the tokens ``hid`` (N, d) at ``positions`` (N,)."""
        n, h = hid.shape[0], self.n_heads
        if self.q_rank is None:
            q = _mm(hid, ap["wq"])
        else:
            cq = rms_norm(_mm(hid, ap["wq_a"]), ap["q_norm"], self.norm_eps)
            q = _mm(cq, ap["wq_b"])
        q = q.reshape(n, h, self.nope + self.rope)
        kv = _mm(hid, ap["wkv_a"])
        c_kv = rms_norm(kv[:, :self.kv_rank], ap["kv_norm"], self.norm_eps)
        q_rope, k_rope = q[..., self.nope:], kv[:, self.kv_rank:]
        if self.rotary:
            rotate = _rotate(self.rope_layout)
            q_rope = rotate(q_rope, positions, self.inv_freq, self.rope_scale)
            k_rope = rotate(k_rope, positions, self.inv_freq, self.rope_scale)
        return q[..., :self.nope], q_rope, jnp.concatenate(
            [c_kv, k_rope], axis=-1)

    def _wkv_b(self, ap, dtype):
        w = ap["wkv_b"].astype(dtype).reshape(
            self.kv_rank, self.n_heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def expand(self, ap, c_kv):
        """``(k_nope (..., H, nope), v (..., H, v_dim))`` of latent rows
        ``c_kv`` (..., kv_rank)."""
        wk, wv = self._wkv_b(ap, c_kv.dtype)
        f32 = dict(preferred_element_type=jnp.float32)
        return (jnp.einsum("...c,chd->...hd", c_kv, wk, **f32).astype(c_kv.dtype),
                jnp.einsum("...c,chd->...hd", c_kv, wv, **f32).astype(c_kv.dtype))

    def absorb(self, ap, q_nope):
        """The queries in the latent space: ``q_nope W_kvb[k]ᵀ``."""
        wk, _ = self._wkv_b(ap, q_nope.dtype)
        return jnp.einsum("nhd,chd->nhc", q_nope, wk,
                          preferred_element_type=jnp.float32).astype(q_nope.dtype)

    def unabsorb(self, ap, o_lat, dtype):
        """Latent outputs (N, H, kv_rank) out: ``· W_kvb[v]``."""
        _, wv = self._wkv_b(ap, dtype)
        return jnp.einsum("nhc,chd->nhd", o_lat.astype(dtype), wv,
                          preferred_element_type=jnp.float32).astype(dtype)

    def out(self, ap, o):
        return _mm(o.reshape(o.shape[0], -1), ap["wo"])


def causal_attend(attn: LatentAttention, batch: int):
    """``attend`` for whole sequences with no cache: ``batch`` rows of
    equal length, every token attending to those before it in its row
    (expanded keys and values; fp32 softmax)."""

    def attend(ap, q_nope, q_rope, row):
        n, h = q_nope.shape[0], attn.n_heads
        t = n // batch
        k_nope, v = attn.expand(ap, row[:, :attn.kv_rank])
        k_rope = row[:, attn.kv_rank:].reshape(batch, t, attn.rope)
        f32 = dict(preferred_element_type=jnp.float32)
        s = (
            jnp.einsum("bqhd,bkhd->bhqk", q_nope.reshape(batch, t, h, -1),
                       k_nope.reshape(batch, t, h, -1), **f32)
            + jnp.einsum("bqhr,bkr->bhqk", q_rope.reshape(batch, t, h, -1),
                         k_rope, **f32)
        ) * attn.scale
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, _NEG_INF), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype),
                       v.reshape(batch, t, h, -1), **f32)
        return o.reshape(n, h, -1).astype(q_nope.dtype)

    return attend


class GroupedAttention:
    """Sizes and products of grouped-query attention; no state.
    ``n_heads`` query heads and ``kv_heads`` key/value heads of
    ``head_dim``, query head ``h`` reading key/value head ``h //
    group``; no biases; scale ``head_dim^-1/2``.  ``window``: a query
    at ``p`` sees the keys at ``p - window < j <= p`` (None: every key
    before it); ``rotary``: positions enter by ``rope_layout`` at
    ``theta``, or not at all (NoPE).  A token's keys and its values are
    one row each, ``kv_heads · head_dim`` wide, heads side by side."""

    kind = "gqa"

    def __init__(self, d_model, n_heads, kv_heads, head_dim, norm_eps, *,
                 window=None, rotary=True, theta=10000.0,
                 rope_layout="half"):
        if n_heads % kv_heads:
            raise ValueError(f"{n_heads} query heads do not divide into "
                             f"{kv_heads} key/value heads")
        self.d_model, self.n_heads = d_model, n_heads
        self.kv_heads, self.head_dim = kv_heads, head_dim
        self.group = n_heads // kv_heads
        self.norm_eps = norm_eps
        self.window = None if window is None else int(window)
        self.rotary, self.rope_layout = rotary, rope_layout
        self.inv_freq = yarn_inv_freq(head_dim, float(theta))
        self.scale = head_dim ** -0.5
        self.width = kv_heads * head_dim

    def init(self, key, dtype):
        d, hd = self.d_model, self.head_dim
        ks = jax.random.split(key, 4)
        w = normal_init(0.02)
        return {
            "wq": w(ks[0], (d, self.n_heads * hd), d, dtype),
            "wk": w(ks[1], (d, self.width), d, dtype),
            "wv": w(ks[2], (d, self.width), d, dtype),
            "wo": w(ks[3], (self.n_heads * hd, d), self.n_heads * hd, dtype),
        }

    def project(self, ap, hid, positions):
        """``(q (N, H, hd), k (N, kv·hd), v (N, kv·hd))`` of the tokens
        ``hid`` (N, d) at ``positions`` (N,)."""
        n, hd = hid.shape[0], self.head_dim
        q = _mm(hid, ap["wq"]).reshape(n, self.n_heads, hd)
        k = _mm(hid, ap["wk"])
        if self.rotary:
            rotate = _rotate(self.rope_layout)
            q = rotate(q, positions, self.inv_freq)
            k = rotate(k.reshape(n, self.kv_heads, hd), positions,
                       self.inv_freq).reshape(n, self.width)
        return q, k, _mm(hid, ap["wv"])

    def mask(self, q_at, k_at):
        """Which keys at ``k_at`` the queries at ``q_at`` see
        (broadcast): causal, and inside the window where there is one."""
        seen = k_at <= q_at
        if self.window is not None:
            seen &= k_at > q_at - self.window
        return seen

    def out(self, ap, o):
        return _mm(o.reshape(o.shape[0], -1), ap["wo"])


def causal_gqa_attend(attn: GroupedAttention, batch: int):
    """``attend`` for whole sequences with no cache, as
    ``causal_attend``: causal, and windowed where the layer is."""

    def attend(ap, q, k, v):
        n, hd, kv = q.shape[0], attn.head_dim, attn.kv_heads
        t = n // batch
        f32 = dict(preferred_element_type=jnp.float32)
        s = jnp.einsum("bqkgd,btkd->bkgqt",
                       q.reshape(batch, t, kv, attn.group, hd),
                       k.reshape(batch, t, kv, hd), **f32) * attn.scale
        at = jnp.arange(t)
        seen = attn.mask(at[:, None], at[None, :])
        p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype),
                       v.reshape(batch, t, kv, hd), **f32)
        return o.reshape(n, attn.n_heads, hd).astype(q.dtype)

    return attend


class LatentMoeBlock(Layer):
    """One block: a hyper-connected mixer (``attn``, latent or grouped,
    or ``kda`` where one is given), then a hyper-connected feed-forward
    (dense gated, or ``moe``).

    ``forward``'s ``impl``: ``'pallas'`` runs the hyper-connection halves
    and the experts as the named kernels (the server's choice on one
    chip); ``'xla'`` their plain, differentiable forms."""

    def __init__(self, attn: LatentAttention, *, ffn_hidden: Optional[int],
                 moe=None, n_streams: int = 4, hc_iters: int = 20,
                 hc_eps: float = 1e-6, hc_clamp: float = 30.0,
                 param_dtype=jnp.float32, kda=None,
                 route_from_attn: bool = False):
        self.attn, self.kda = attn, kda
        self.kind = attn.kind if kda is None else "kda"
        self.route_from_attn = route_from_attn
        self.ffn_hidden, self.moe = ffn_hidden, moe
        self.n_streams = n_streams
        self.hc = dict(n=n_streams, eps=hc_eps, iters=hc_iters, clamp=hc_clamp)
        self.param_dtype = param_dtype

    # ---- parameters ----------------------------------------------------
    def _init_hc(self, key, d):
        n = self.n_streams
        m = 2 * n + n * n
        k1, k2 = jax.random.split(key)
        bias = jax.random.normal(k2, (m,), jnp.float32)
        bias = bias.at[2 * n:].add(3.0 * jnp.eye(n).reshape(-1))
        return {
            # Φ transposed: row c holds the weights of coefficient c
            "phi": normal_init(0.02)(k1, (m, n * d), n * d, self.param_dtype),
            "alpha": jnp.full((3,), 0.1, self.param_dtype),
            "bias": bias.astype(self.param_dtype),
        }

    def init(self, key, in_shape):
        d, dt = self.attn.d_model, self.param_dtype
        ks = jax.random.split(key, 7)
        params = {
            "attn_norm": jnp.ones((d,), dt),
            "ffn_norm": jnp.ones((d,), dt),
        }
        if self.kda is None:
            params["attn"] = self.attn.init(ks[0], dt)
        else:
            params["kda"] = self.kda.init(ks[0], dt)
        if self.n_streams > 1:
            params["hc_attn"] = self._init_hc(ks[1], d)
            params["hc_ffn"] = self._init_hc(ks[2], d)
        if self.moe is not None:
            params["moe"], _, _ = self.moe.init(ks[3], (d,))
            if "route_bias" in params["moe"]:
                # a selection bias that makes choice and weight differ
                params["moe"]["route_bias"] = (0.02 * jax.random.normal(
                    ks[4], (self.moe.n_experts,))).astype(dt)
        else:
            w = normal_init(0.02)
            f = self.ffn_hidden
            params["mlp"] = {"w_gate": w(ks[3], (d, f), d, dt),
                             "w_up": w(ks[4], (d, f), d, dt),
                             "w_down": w(ks[5], (f, d), f, dt)}
        return params, {}, in_shape

    # ---- the forward pass, once ----------------------------------------
    def _mix(self, x, hp, norm_scale, f, impl):
        """One hyper-connected sublayer around ``f`` (d → d); with one
        stream the plain residual."""
        if self.n_streams == 1:
            return x + f(rms_norm(x, norm_scale, self.attn.norm_eps))
        kernel = impl == "pallas"
        with jax.named_scope("mhc"):
            phi_t, ab = pallas_mhc.pack_coefficients(
                hp["phi"], hp["alpha"], hp["bias"], self.n_streams)
            pre = pallas_mhc.mhc_pre if kernel else pallas_mhc.mhc_pre_xla
            u, coef = pre(x, phi_t, ab, **self.hc)
        y = f(rms_norm(u, norm_scale, self.attn.norm_eps))
        with jax.named_scope("mhc"):
            post = pallas_mhc.mhc_post if kernel else pallas_mhc.mhc_post_xla
            return post(x, y, coef, n=self.n_streams)

    def forward(self, params, x, positions, attend: Callable, valid=None,
                impl: str = "xla"):
        """``(x' (N, n·d), counts)``: the state after the block, and the
        tokens each held expert received (``None`` for a dense block).
        ``attend(attention params, q_nope, q_rope, row) -> (N, H,
        v_dim)`` supplies the keys and values (module docstring); in a
        ``gqa`` block it is ``attend(attention params, q, k, v) -> (N,
        H, hd)``; in a ``kda`` block ``attend(mixer params, u, g, beta)
        -> (N, H, K)``, the convolution and the recurrence over the
        caller's state.  ``valid`` (N,) bool marks the rows that are
        tokens, not padding."""
        counts, normed = [], []

        def attention(hid):
            normed.append(hid)
            if self.kda is not None:
                mp = params["kda"]
                u, g, beta = self.kda.project(mp, hid)
                return self.kda.out(mp, attend(mp, u, g, beta), hid)
            if self.kind == "gqa":
                with jax.named_scope("gqa_attn"):
                    ap = params["attn"]
                    q, k, v = self.attn.project(ap, hid, positions)
                    return self.attn.out(ap, attend(ap, q, k, v))
            with jax.named_scope("mla_attn"):
                ap = params["attn"]
                q_nope, q_rope, row = self.attn.project(ap, hid, positions)
                return self.attn.out(ap, attend(ap, q_nope, q_rope, row))

        def feed_forward(hid):
            if self.moe is None:
                mp = params["mlp"]
                with jax.named_scope("mlp"):
                    return gated_ffn(hid, mp["w_gate"], mp["w_up"], mp["w_down"])
            y, c, _ = self.moe.forward(
                params["moe"], hid, valid=valid, impl=impl,
                route_x=normed[0] if self.route_from_attn else None)
            counts.append(c)
            return y

        x = self._mix(x, params.get("hc_attn"), params["attn_norm"],
                      attention, impl)
        x = self._mix(x, params.get("hc_ffn"), params["ffn_norm"],
                      feed_forward, impl)
        return x, (counts[0] if counts else None)

    def apply(self, params, state, x, train=False, rng=None):
        b, t, nd = x.shape
        positions = jnp.tile(jnp.arange(t), b)
        from theanompi_tpu.ops.kda import causal_mix

        if self.kda is not None:
            attend = causal_mix(self.kda, b)
        elif self.kind == "gqa":
            attend = causal_gqa_attend(self.attn, b)
        else:
            attend = causal_attend(self.attn, b)
        y, _ = self.forward(params, x.reshape(b * t, nd), positions, attend)
        return y.reshape(b, t, nd), state


class StreamEmbedding(Layer):
    """Token embedding into ``n_streams`` equal streams: int32 (T,) →
    (T, n_streams · d)."""

    def __init__(self, vocab_size, features, n_streams, compute_dtype=None,
                 param_dtype=jnp.float32):
        self.vocab_size, self.features = vocab_size, features
        self.n_streams = n_streams
        self.compute_dtype, self.param_dtype = compute_dtype, param_dtype

    def init(self, key, in_shape):
        table = normal_init(0.02)(key, (self.vocab_size, self.features),
                                  self.features, self.param_dtype)
        return {"table": table}, {}, (*in_shape, self.n_streams * self.features)

    def apply(self, params, state, x, train=False, rng=None):
        y = jnp.take(params["table"], x, axis=0)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        return jnp.tile(y, (1,) * (y.ndim - 1) + (self.n_streams,)), state


class StreamSumNorm(Layer):
    """The streams summed, then RMSNorm: (..., n_streams · d) → (..., d)."""

    def __init__(self, n_streams, eps=1e-6, param_dtype=jnp.float32):
        self.n_streams, self.eps, self.param_dtype = n_streams, eps, param_dtype

    def init(self, key, in_shape):
        d = in_shape[-1] // self.n_streams
        return {"scale": jnp.ones((d,), self.param_dtype)}, {}, (*in_shape[:-1], d)

    def apply(self, params, state, x, train=False, rng=None):
        s = jnp.sum(
            x.astype(jnp.float32).reshape(*x.shape[:-1], self.n_streams, -1),
            axis=-2)
        return rms_norm(s, params["scale"], self.eps).astype(x.dtype), state
