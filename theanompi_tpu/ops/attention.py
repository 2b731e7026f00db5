"""Attention / transformer layers for the functional layer library.

These extend ``ops.layers`` with the building blocks of a long-context
transformer. The reference has no attention (SURVEY.md §3.4), so there
is no reference analog to cite — the contract and style follow
``layers2``-derived ``ops.layers``, and the sequence-parallel path runs
``parallel.ring_attention`` over the ``sp`` mesh axis when the layer is
applied inside ``shard_map``.

Per the library convention, ``in_shape``/``out_shape`` exclude the batch
dimension: token inputs are ``(T,)`` int32, activations ``(T, D)``.
When sequence parallelism is active, ``T`` here is the *local* shard
length and position-dependent layers recover global positions from
``lax.axis_index(sp_axis)``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops.layers import Layer, normal_init
from theanompi_tpu.parallel.ring_attention import full_attention, ring_attention


class LayerNorm(Layer):
    """Layer normalization over the feature (last) dimension, fp32 stats."""

    def __init__(self, eps: float = 1e-5):
        self.eps = eps

    def init(self, key, in_shape):
        d = in_shape[-1]
        params = {
            "scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32),
        }
        return params, {}, in_shape

    def apply(self, params, state, x, train=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.eps)
        y = y * params["scale"] + params["bias"]
        return y.astype(x.dtype), state


class Embedding(Layer):
    """Token embedding: int32 ``(T,)`` → ``(T, D)``.

    With ``compute_dtype`` set, the looked-up activations enter the
    residual stream in that dtype (master table stays fp32), so the whole
    transformer stack flows in bf16 on TPU.
    """

    def __init__(
        self,
        vocab_size: int,
        features: int,
        w_init=None,
        compute_dtype: Optional[jnp.dtype] = None,
    ):
        self.vocab_size = vocab_size
        self.features = features
        self.w_init = w_init or normal_init(0.02)
        self.compute_dtype = compute_dtype

    def init(self, key, in_shape):
        params = {
            "table": self.w_init(
                key, (self.vocab_size, self.features), self.features
            )
        }
        return params, {}, (*in_shape, self.features)

    def apply(self, params, state, x, train=False, rng=None):
        y = jnp.take(params["table"], x, axis=0)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        return y, state


class PositionalEmbedding(Layer):
    """Learned absolute positions, sequence-parallel aware.

    ``max_len`` is the *global* maximum sequence length. Under sequence
    parallelism (``sp_axis`` given and in scope), the local shard of
    length T covers global rows ``[idx·T, (idx+1)·T)`` of the table.
    """

    def __init__(self, max_len: int, sp_axis: Optional[str] = None):
        self.max_len = max_len
        self.sp_axis = sp_axis

    def init(self, key, in_shape):
        t, d = in_shape
        params = {"pos": normal_init(0.02)(key, (self.max_len, d), d)}
        return params, {}, in_shape

    def apply(self, params, state, x, train=False, rng=None):
        t = x.shape[1]
        offset = 0
        if self.sp_axis is not None:
            offset = lax.axis_index(self.sp_axis) * t
        pos = lax.dynamic_slice_in_dim(params["pos"], offset, t, axis=0)
        return x + pos.astype(x.dtype), state


class MultiHeadAttention(Layer):
    """Multi-head self-attention with optional sequence parallelism.

    ``sp_axis``/``sp_size`` select the path statically at trace time:
    ``sp_size == 1`` (or ``sp_axis=None``) runs dense single-shard
    attention; otherwise the layer must be applied inside a ``shard_map``
    that has ``sp_axis`` in scope with the sequence dim sharded over it,
    and ``sp_mode`` picks the exact-attention layout:

    - ``'ring'`` — K/V circulate the ring (``parallel.ring_attention``).
    - ``'alltoall'`` — head⇄sequence reshuffle (``parallel.ulysses``),
      needs ``n_heads % sp_size == 0``.

    ``tp_axis``/``tp_size`` add Megatron-style tensor parallelism:
    wq/wk/wv are column-parallel (each tp rank owns ``n_heads/tp_size``
    whole heads), wo is row-parallel with a ``psum`` over ``tp_axis``
    restoring the replicated residual stream. The owning model supplies
    the matching ``PartitionSpec`` tree (``TransformerLM.param_specs``)
    so ``shard_map`` hands each rank its weight shards.
    """

    def __init__(
        self,
        n_heads: int,
        causal: bool = True,
        sp_axis: Optional[str] = None,
        sp_size: int = 1,
        sp_mode: str = "ring",
        tp_axis: Optional[str] = None,
        tp_size: int = 1,
        compute_dtype: Optional[jnp.dtype] = None,
        attn_impl: str = "xla",
    ):
        if sp_mode not in ("ring", "alltoall"):
            raise ValueError(f"sp_mode must be 'ring' or 'alltoall', got {sp_mode!r}")
        if attn_impl not in ("xla", "flash"):
            raise ValueError(f"attn_impl must be 'xla' or 'flash', got {attn_impl!r}")
        if tp_size > 1 and n_heads % tp_size:
            raise ValueError(
                f"tensor parallelism needs n_heads % tp == 0, "
                f"got n_heads={n_heads}, tp={tp_size}"
            )
        self.n_heads = n_heads
        self.causal = causal
        self.sp_axis = sp_axis
        self.sp_size = sp_size
        self.sp_mode = sp_mode
        self.tp_axis = tp_axis
        self.tp_size = tp_size
        self.compute_dtype = compute_dtype
        self.attn_impl = attn_impl

    def init(self, key, in_shape):
        t, d = in_shape
        if d % self.n_heads:
            raise ValueError(f"d_model {d} not divisible by n_heads {self.n_heads}")
        keys = jax.random.split(key, 4)
        std = 1.0 / math.sqrt(d)
        init = normal_init(std)
        params = {
            "wq": init(keys[0], (d, d), d),
            "wk": init(keys[1], (d, d), d),
            "wv": init(keys[2], (d, d), d),
            "wo": init(keys[3], (d, d), d),
        }
        return params, {}, in_shape

    def _proj(self, x, w):
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            w = w.astype(self.compute_dtype)
        # fp32 MXU accumulation, narrowed back to the flowing dtype
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        return y

    def apply(self, params, state, x, train=False, rng=None):
        b, t, d = x.shape  # d = full model dim (residual stream replicated)
        tp = self.tp_axis is not None and self.tp_size > 1
        h = self.n_heads // (self.tp_size if tp else 1)
        hd = d // self.n_heads
        if tp:
            from theanompi_tpu.parallel.tensor import copy_to_tp

            x = copy_to_tp(x, self.tp_axis)  # Megatron f: bwd psums cotangents
        # column-parallel projections: local wq is (d, d/tp) → local heads
        q = self._proj(x, params["wq"]).reshape(b, t, h, hd)
        k = self._proj(x, params["wk"]).reshape(b, t, h, hd)
        v = self._proj(x, params["wv"]).reshape(b, t, h, hd)
        if self.sp_axis is not None and self.sp_size > 1:
            if self.sp_mode == "alltoall":
                from theanompi_tpu.parallel.ulysses import ulysses_attention

                sp_fn = ulysses_attention
            else:
                sp_fn = ring_attention
            o = sp_fn(
                q, k, v,
                axis_name=self.sp_axis,
                axis_size=self.sp_size,
                causal=self.causal,
                attn_impl=self.attn_impl,
            )
        else:
            from theanompi_tpu.parallel.ring_attention import local_attention

            o = local_attention(
                q, k, v, causal=self.causal, attn_impl=self.attn_impl
            )
        # output keeps the flowing activation dtype (softmax statistics
        # inside ring/ulysses/full attention are fp32 regardless).
        # Row-parallel wo: local (d/tp, d) partial products summed over tp
        # restore the replicated residual stream (Megatron g: bwd identity).
        y = self._proj(o.reshape(b, t, h * hd), params["wo"])
        if tp:
            from theanompi_tpu.parallel.tensor import reduce_from_tp

            y = reduce_from_tp(y, self.tp_axis)
        return y, state


class TransformerBlock(Layer):
    """Pre-LN decoder block: LN→MHA→residual, LN→FFN→residual.

    The FFN is a dense GELU MLP by default; pass ``moe`` (a
    ``parallel.moe.MoeMlp``) to make this a mixture-of-experts block —
    tokens flatten to ``(b·t, d)`` for routing and the expert weights
    shard over the MoE layer's ``ep_axis`` (GShard-style, the model
    reuses its data axis). Composes with sequence parallelism and,
    via 2-D expert sharding (the MoE's ``tp_axis``: every expert's
    hidden dim Megatron-split), with tensor parallelism.
    """

    def __init__(
        self,
        n_heads: int,
        mlp_ratio: int = 4,
        causal: bool = True,
        sp_axis: Optional[str] = None,
        sp_size: int = 1,
        sp_mode: str = "ring",
        tp_axis: Optional[str] = None,
        tp_size: int = 1,
        compute_dtype: Optional[jnp.dtype] = None,
        moe=None,
        attn_impl: str = "xla",
    ):
        self.ln1 = LayerNorm()
        self.ln2 = LayerNorm()
        self.attn = MultiHeadAttention(
            n_heads, causal=causal, sp_axis=sp_axis, sp_size=sp_size,
            sp_mode=sp_mode, tp_axis=tp_axis, tp_size=tp_size,
            compute_dtype=compute_dtype, attn_impl=attn_impl,
        )
        self.mlp_ratio = mlp_ratio
        self.tp_axis = tp_axis
        self.tp_size = tp_size
        self.compute_dtype = compute_dtype
        self.moe = moe

    def init(self, key, in_shape):
        t, d = in_shape
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        p1, _, _ = self.ln1.init(k1, in_shape)
        pa, _, _ = self.attn.init(k2, in_shape)
        p2, _, _ = self.ln2.init(k3, in_shape)
        params = {"ln1": p1, "attn": pa, "ln2": p2}
        if self.moe is not None:
            pm, ms, _ = self.moe.init(k4, (d,))
            params["moe"] = pm
            return params, {"moe": ms}, in_shape
        dm = d * self.mlp_ratio
        params["mlp_in"] = {
            "w": normal_init(1.0 / math.sqrt(d))(k4, (d, dm), d),
            "b": jnp.zeros((dm,), jnp.float32),
        }
        params["mlp_out"] = {
            "w": normal_init(1.0 / math.sqrt(dm))(k5, (dm, d), dm),
            "b": jnp.zeros((d,), jnp.float32),
        }
        return params, {}, in_shape

    def _mlp(self, params, x):
        # tp: w1/b1 column-parallel (local (d, dm/tp) / (dm/tp,)), the
        # gelu runs on the local slice, w2 row-parallel with the Megatron
        # f/g pair restoring the replicated stream; b2 is added AFTER the
        # reduce so it isn't counted tp times
        tp = self.tp_axis is not None and self.tp_size > 1
        if tp:
            from theanompi_tpu.parallel.tensor import copy_to_tp

            x = copy_to_tp(x, self.tp_axis)
        w1, w2 = params["mlp_in"]["w"], params["mlp_out"]["w"]
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            w1 = w1.astype(self.compute_dtype)
            w2 = w2.astype(self.compute_dtype)
        hmid = jnp.dot(x, w1, preferred_element_type=jnp.float32)
        hmid = jax.nn.gelu(hmid + params["mlp_in"]["b"])
        if self.compute_dtype is not None:
            hmid = hmid.astype(self.compute_dtype)
        y = jnp.dot(hmid, w2, preferred_element_type=jnp.float32)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        if tp:
            from theanompi_tpu.parallel.tensor import reduce_from_tp

            y = reduce_from_tp(y, self.tp_axis)
        return y + params["mlp_out"]["b"].astype(y.dtype)

    def apply(self, params, state, x, train=False, rng=None):
        h1, _ = self.ln1.apply(params["ln1"], {}, x)
        a, _ = self.attn.apply(params["attn"], {}, h1, train=train, rng=rng)
        x = x + a
        h2, _ = self.ln2.apply(params["ln2"], {}, x)
        if self.moe is not None:
            b, t, d = h2.shape
            y, ms = self.moe.apply(params["moe"], state["moe"], h2.reshape(b * t, d))
            x = x + y.reshape(b, t, d)
            return x, {"moe": ms}
        x = x + self._mlp(params, h2)
        return x, state


# ---------------------------------------------------------------------------
# pieces of the rotary / RMSNorm / gated family (ops.latent_block)
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    """``x / sqrt(mean(x²) + eps) · scale`` over the last dim, fp32
    statistics, result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature term ``0.1 · mscale · ln(factor) + 1``
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float = 10000.0, factor: float = 1.0,
                  original_max_position: int = 4096,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """The ``dim // 2`` rotary frequencies under YaRN scaling, as
    DeepSeek-V2/V3's ``YarnRotaryEmbedding`` computes them: a frequency
    that turns more than ``beta_fast`` times in the original context is
    kept (extrapolated), one that turns less than ``beta_slow`` times is
    divided by ``factor`` (interpolated), and a linear ramp over the
    dimensions between joins the two.  ``factor <= 1``: plain RoPE."""
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if factor <= 1.0:
        return extra

    def correction_dim(rotations):
        return (dim * math.log(original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0, 1.0)
    keep = 1.0 - ramp  # 1 where the frequency is extrapolated
    return extra / factor * (1.0 - keep) + extra * keep


def rope_interleaved(x, positions, inv_freq, scale: float = 1.0):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last dim by
    ``positions · inv_freq[i]``.  ``x`` (..., T, [H,] dim) with
    ``positions`` (..., T) broadcast over a head axis if there is one.
    The result is laid out de-interleaved (rotated evens, then rotated
    odds), as the DeepSeek code leaves it: queries and keys go through
    the same permutation, so their products do not see it."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., T, dim/2)
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    xe, xo = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate(
        [xe * cos - xo * sin, xe * sin + xo * cos], axis=-1).astype(x.dtype)


def rope_half(x, positions, inv_freq, scale: float = 1.0):
    """Rotate the pairs ``(x[i], x[i + dim/2])`` of the last dim by
    ``positions · inv_freq[i]``: the rotate-half layout of Llama and
    Qwen (``x·cos + rotate_half(x)·sin``).  Shapes as
    ``rope_interleaved``; the result keeps the layout."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    half = xf.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)



GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def gated_ffn(x, w_gate, w_up, w_down, activation: str = "silu"):
    """``(act(x W_gate) ⊙ (x W_up)) W_down`` (``act`` SiLU, or ReLU:
    ReGLU): operands as they come, fp32 accumulation, the activation in
    fp32, result in ``x``'s dtype."""
    g = jnp.dot(x, w_gate.astype(x.dtype), preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up.astype(x.dtype), preferred_element_type=jnp.float32)
    h = (GATES[activation](g) * u).astype(x.dtype)
    return jnp.dot(h, w_down.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
