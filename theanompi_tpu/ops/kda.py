"""Kimi Delta Attention (arXiv:2510.26692): a linear-attention layer
whose past is a matrix a head and not rows in a cache.

Per head, with a state ``S`` (K, K) float32 (rows: key channels,
columns: value channels), zero where a sequence starts::

    S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

``α_t = exp(g_t) ∈ (0, 1)^K`` decays every key channel by itself, ``β_t
∈ (0, 1)`` is the step of the delta rule, ``q`` and ``k`` are L2-normed
(``q`` carries the ``K^{−1/2}`` scale).  Before it a causal depthwise
convolution of width ``conv`` over the three projections, whose last
``conv − 1`` inputs are state too.

**Two forms of the one recurrence**, each as a Pallas kernel named in
the device trace and as plain XLA (the kernels' oracle, the CPU path):

- *a token at a time* (``kda_decode`` / ``kda_step_xla``): every lane's
  state is read, decayed, corrected by the rank-one delta, read out and
  written back — bound by memory, 2 · H · K² · 4 bytes a lane a layer.
- *chunked* (``kda_chunk_prefill`` / ``kda_chunk_xla``): with ``Γ_t`` the
  decay from the chunk's start, ``k⁺ = Γ ⊙ k``, ``k⁻ = k / Γ``, ``q⁺ = Γ ⊙
  q``, the deltas ``w_t`` of a chunk solve the unit lower-triangular
  ``(I + tril(β k⁺ k⁻ᵀ, −1)) W = β ⊙ (V − K⁺ S_0)``; then ``O = Q⁺ S_0 +
  tril(Q⁺ K⁻ᵀ) W`` and ``S_c = Diag(Γ_c) S_0 + (Γ_c / Γ ⊙ K)ᵀ W``.  What
  does not depend on ``S_0`` (the system's two right-hand sides, the two
  triangles) is computed for all chunks at once in XLA
  (``chunk_operands``, the inverse by products: ``unit_lower_inverse``);
  the scan over chunks, four small products a
  step with the state in fast memory, is the kernel.  ``1 / Γ`` grows
  with the chunk: at 64 tokens a chunk it stays inside float32 for
  decays down to ``e^{−1.3}`` a token (the configuration's are above
  ``e^{−0.14}``).

Padding tokens take ``g = 0, β = 0``: they leave the state as it was.
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
from theanompi_tpu.ops.attention import rms_norm
from theanompi_tpu.ops.layers import normal_init

HI = lax.Precision.HIGHEST
CHUNK = 64  # tokens a step of the chunked scan


def _mm(x, w):
    """``x @ w`` with operands in ``x``'s dtype, fp32 accumulation."""
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


class KdaMixer:
    """Sizes, parameters and the XLA parts of the layer (projections,
    convolution, activations, output gate); the recurrence is the
    caller's (``LatentMoeBlock.forward``'s ``attend``), which owns the
    state."""

    def __init__(self, d_model: int, n_heads: int, head_dim: int,
                 conv: int, norm_eps: float):
        self.d_model, self.n_heads, self.head_dim = d_model, n_heads, head_dim
        self.conv, self.norm_eps = conv, norm_eps
        self.width = n_heads * head_dim  # of q, of k, of v
        self.scale = head_dim ** -0.5

    def init(self, key, dtype):
        d, h, k, w = self.d_model, self.n_heads, self.head_dim, self.width
        ks = jax.random.split(key, 9)
        small = normal_init(0.02)
        return {
            # so that silu sees inputs of order one
            "wqkv": normal_init(d ** -0.5)(ks[0], (d, 3 * w), d, dtype),
            "conv_w": normal_init(0.5)(ks[1], (self.conv, 3 * w), 1, dtype),
            "wf_a": small(ks[2], (d, k), d, dtype),
            "wf_b": small(ks[3], (k, w), k, dtype),
            "a_log": jnp.zeros((h,), dtype),
            "dt_bias": jax.random.uniform(
                ks[4], (w,), jnp.float32, -7.0, -2.0).astype(dtype),
            "wb": small(ks[5], (d, h), d, dtype),
            "wg_a": small(ks[6], (d, k), d, dtype),
            "wg_b": small(ks[7], (k, w), k, dtype),
            "o_norm": jnp.ones((k,), dtype),
            "wo": small(ks[8], (w, d), w, dtype),
        }

    # ---- before the recurrence ---------------------------------------------
    def project(self, mp, hid):
        """``(u (N, 3·H·K), g (N, H, K) fp32 ≤ 0, beta (N, H) fp32)`` of
        the tokens ``hid`` (N, d): the convolution's inputs ``[u_q | u_k |
        u_v]``, the log-decay and the step."""
        n, h, k = hid.shape[0], self.n_heads, self.head_dim
        with jax.named_scope("kda_proj"):
            u = _mm(hid, mp["wqkv"])
            f = _mm(_mm(hid, mp["wf_a"]), mp["wf_b"]).astype(jnp.float32)
            g = -jnp.exp(mp["a_log"].astype(jnp.float32))[:, None] * (
                jax.nn.softplus(f + mp["dt_bias"].astype(jnp.float32))
                .reshape(n, h, k))
            beta = jax.nn.sigmoid(jnp.dot(
                hid, mp["wb"].astype(hid.dtype),
                preferred_element_type=jnp.float32))
        return u, g, beta

    def convolve(self, mp, past, u):
        """The causal convolution of ``u`` (..., T, W) behind its last
        ``conv − 1`` inputs ``past`` (..., conv − 1, W): ``(q, k, v)``
        (..., T, H, K) fp32, activated and normed, ``q`` scaled."""
        with jax.named_scope("kda_conv"):
            t, m = u.shape[-2], self.conv - 1
            seq = jnp.concatenate([past.astype(u.dtype), u], axis=-2)
            w = mp["conv_w"].astype(jnp.float32)
            c = sum(w[j] * lax.slice_in_dim(seq, j, j + t, axis=-2)
                    .astype(jnp.float32) for j in range(m + 1))
            c = jax.nn.silu(c).reshape(*c.shape[:-1], 3, self.n_heads,
                                       self.head_dim)
            q, k, v = c[..., 0, :, :], c[..., 1, :, :], c[..., 2, :, :]

            def l2(z):
                return z * lax.rsqrt(
                    jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)

            return l2(q) * self.scale, l2(k), v

    # ---- after it ----------------------------------------------------------
    def out(self, mp, o, hid):
        """``[RMSNorm_head(o) ⊙ sigmoid(hid W_g↓ W_g↑)] W_o``: ``o`` (N,
        H, K) fp32 → (N, d)."""
        with jax.named_scope("kda_gate"):
            n = o.shape[0]
            gate = jax.nn.sigmoid(
                _mm(_mm(hid, mp["wg_a"]), mp["wg_b"]).astype(jnp.float32))
            y = rms_norm(o, mp["o_norm"], self.norm_eps).reshape(n, -1) * gate
            return _mm(y.astype(hid.dtype), mp["wo"])


# ---------------------------------------------------------------------------
# a token at a time
# ---------------------------------------------------------------------------

def kda_step_xla(s, q, k, v, g, beta):
    """One token: ``s`` (..., H, K, K) fp32, ``q k v g`` (..., H, K),
    ``beta`` (..., H) → ``(s', o (..., H, K))``."""
    s = jnp.exp(g)[..., :, None] * s
    r = jnp.einsum("...d,...de->...e", k, s, precision=HI)
    u = beta[..., None] * (v - r)
    s = s + k[..., :, None] * u[..., None, :]
    return s, jnp.einsum("...d,...de->...e", q, s, precision=HI)


def kda_scan_xla(s0, q, k, v, g, beta):
    """A sequence token by token (``lax.scan``): ``s0`` (P, H, K, K),
    the rest (P, T, H, ·) → ``(o (P, T, H, K), s_T)``.  The tests' form
    of the recurrence as it is written."""
    def step(s, x):
        s, o = kda_step_xla(s, *x)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    s, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), s


def _column(row, eye):
    """A (1, K) row as a (K, 1) column: the diagonal matrix's row sums
    (a relayout the compiler always has)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _decode_kernel(s_ref, q_ref, k_ref, v_ref, g_ref, b_ref, so_ref, o_ref,
                   *, heads):
    kk = s_ref.shape[-1]
    eye = (lax.broadcasted_iota(jnp.int32, (kk, kk), 0)
           == lax.broadcasted_iota(jnp.int32, (kk, kk), 1))
    for j in range(heads):
        row = lambda ref: ref[0, j:j + 1, :]
        k = _column(row(k_ref), eye)
        s = _column(jnp.exp(row(g_ref)), eye) * s_ref[0, j]
        r = jnp.sum(k * s, axis=0, keepdims=True)
        s = s + k * (row(b_ref) * (row(v_ref) - r))
        so_ref[0, j] = s
        o_ref[0, j:j + 1, :] = jnp.sum(_column(row(q_ref), eye) * s, axis=0,
                                       keepdims=True)


def kda_decode(s, q, k, v, g, beta, *, interpret: Optional[bool] = None):
    """``kda_step_xla`` over the lanes ``s`` (N, H, K, K) as one kernel
    that reads and writes each state once, in place."""
    n, h, kk, _ = s.shape
    hb = 8 if h % 8 == 0 else h
    b = jnp.broadcast_to(beta[..., None], (n, h, kk)).astype(jnp.float32)
    vec = pl.BlockSpec((1, hb, kk), lambda i, j: (i, j, 0))
    mat = pl.BlockSpec((1, hb, kk, kk), lambda i, j: (i, j, 0, 0))
    s, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        grid=(n, h // hb),
        in_specs=[mat, vec, vec, vec, vec, vec],
        out_specs=[mat, vec],
        out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct((n, h, kk), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=(not platform.on_tpu()) if interpret is None else interpret,
        name="kda_decode",
    )(s, *(a.astype(jnp.float32) for a in (q, k, v, g)), b)
    return s, o


# ---------------------------------------------------------------------------
# chunked
# ---------------------------------------------------------------------------

def unit_lower_inverse(a):
    """``(I + a)⁻¹`` for strictly lower-triangular ``a`` (..., c, c), by
    products alone (no library solve: the matrix unit's work on the
    chip, and nothing that spins threads on a shared CPU).  With ``a =
    a_d + a_off`` (the diagonal blocks of 16, and what lies below them),
    ``I + a = (I + a_d)(I + D a_off)``, ``D = (I + a_d)⁻¹``.  Both
    factors are ``I`` plus a nilpotent ``N`` and are inverted by the
    finite product ``(I − N)(I + N²)(I + N⁴)…``: ``a_d¹⁶ = 0``, and ``D
    a_off`` is strictly lower by blocks, so its ``(c / 16)``-th power
    is 0.  Block forward substitution: with L2-normed keys and ``β < 1``
    the entries of ``a`` are under 1 and the few powers taken stay
    small."""
    c = a.shape[-1]
    size = max(16, 1 << (c - 1).bit_length())
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, size - c)] * 2)
    at = jnp.arange(size) // 16
    diagonal = at[:, None] == at[None, :]
    eye = jnp.eye(size, dtype=a.dtype)
    mm = functools.partial(jnp.matmul, precision=HI)

    def inverse(n, order):
        """``(I − n)⁻¹`` for ``n`` with ``n^order = 0``: ``Π_j (I +
        n^(2^j))`` while ``2^j < order``."""
        t, power, reach = eye + n, n, 2
        while reach < order:
            power = mm(power, power)
            t, reach = mm(t, eye + power), 2 * reach
        return t

    d = inverse(-jnp.where(diagonal, a, 0.0), 16)
    t = inverse(-mm(d, jnp.where(diagonal, 0.0, a)), size // 16)
    return mm(t, d)[..., :c, :c]


def chunk_operands(q, k, v, g, beta, c: int):
    """What the scan over chunks of ``c`` tokens reads, none of it
    depending on the state: ``q k v g`` (P, T, H, K) fp32, ``beta`` (P,
    T, H) → ``(u, wk, qp (P, H, n, c, K), m (P, H, n, c, c), kd (P, H,
    n, c, K), gam (P, H, n, 1, K))`` with ``W = u − wk S_0``, ``O = qp
    S_0 + m W``, ``S_c = gam ⊙ S_0 + kdᵀ W`` (module docstring)."""
    p, t, h, d = q.shape
    n = t // c

    def chunks(x):
        return x.reshape(p, n, c, h, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g, b = (chunks(a) for a in (q, k, v, g, beta[..., None]))
    cum = jnp.cumsum(g, axis=3)
    kp, km, qp = k * jnp.exp(cum), k * jnp.exp(-cum), q * jnp.exp(cum)
    kd = k * jnp.exp(cum[..., -1:, :] - cum)
    gam = jnp.exp(cum[..., -1:, :])
    at = jnp.arange(c)
    below = (at[:, None] > at[None, :]).astype(jnp.float32)
    a = jnp.einsum("...td,...sd->...ts", kp, km, precision=HI)
    sol = jnp.matmul(unit_lower_inverse(b * a * below),
                     jnp.concatenate([b * v, b * kp], axis=-1), precision=HI)
    m = jnp.einsum("...td,...sd->...ts", qp, km, precision=HI) * (
        at[:, None] >= at[None, :])
    return sol[..., :d], sol[..., d:], qp, m, kd, gam


def _chunk_size(t: int) -> int:
    return CHUNK if t % CHUNK == 0 else t


def kda_chunk_xla(s0, q, k, v, g, beta, chunk: Optional[int] = None):
    """The chunked form in plain XLA: ``s0`` (P, H, K, K), the rest (P,
    T, H, ·) with ``T`` a multiple of the chunk → ``(o (P, T, H, K),
    s_T)``."""
    p, t, h, d = q.shape
    c = chunk or _chunk_size(t)
    ops = chunk_operands(q, k, v, g, beta, c)

    def step(s, x):
        u, wk, qp, m, kd, gam = x
        mm = functools.partial(jnp.einsum, precision=HI)
        w = u - mm("phcd,phde->phce", wk, s)
        o = mm("phcd,phde->phce", qp, s) + mm("phts,phse->phte", m, w)
        s = gam[..., 0, :, None] * s + mm("phcd,phce->phde", kd, w)
        return s, o

    s, o = lax.scan(step, s0, tuple(jnp.moveaxis(a, 2, 0) for a in ops))
    # (n, P, H, c, K) -> (P, T, H, K)
    return o.transpose(1, 0, 3, 2, 4).reshape(p, t, h, d), s


def _chunk_kernel(s0_ref, u_ref, wk_ref, qp_ref, m_ref, kdt_ref, gam_ref,
                  o_ref, s_ref, acc):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[...] = s0_ref[...]

    kk = acc.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (kk, kk), 0)
           == lax.broadcasted_iota(jnp.int32, (kk, kk), 1))
    mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                           precision=HI)
    s = acc[...]
    w = u_ref[...] - mm(wk_ref[...], s)
    o_ref[...] = mm(qp_ref[...], s) + mm(m_ref[...], w)
    acc[...] = _column(gam_ref[...], eye) * s + mm(kdt_ref[...], w)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        s_ref[...] = acc[...]


def kda_chunk_prefill(s0, q, k, v, g, beta, chunk: Optional[int] = None,
                      *, interpret: Optional[bool] = None):
    """``kda_chunk_xla`` with the scan over chunks as one kernel: a grid
    of (lanes, heads, chunks), the state of a head in fast memory from
    its first chunk to its last."""
    p, t, h, d = q.shape
    c = chunk or _chunk_size(t)
    n = t // c
    with jax.named_scope("kda_state"):
        u, wk, qp, m, kd, gam = chunk_operands(q, k, v, g, beta, c)
        kdt = jnp.swapaxes(kd, -1, -2)

    def per_chunk(rows, cols):
        return pl.BlockSpec((None, None, None, rows, cols),
                            lambda i, hh, j: (i, hh, j, 0, 0))

    state = pl.BlockSpec((None, None, d, d), lambda i, hh, j: (i, hh, 0, 0))
    o, s = pl.pallas_call(
        _chunk_kernel,
        grid=(p, h, n),
        in_specs=[state, per_chunk(c, d), per_chunk(c, d), per_chunk(c, d),
                  per_chunk(c, c), per_chunk(d, c), per_chunk(1, d)],
        out_specs=[per_chunk(c, d), state],
        out_shape=[jax.ShapeDtypeStruct((p, h, n, c, d), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=(not platform.on_tpu()) if interpret is None else interpret,
        name="kda_chunk_prefill",
    )(s0, u, wk, qp, m, kdt, gam)
    return o.transpose(0, 2, 3, 1, 4).reshape(p, t, h, d), s


def causal_mix(mixer: KdaMixer, batch: int):
    """``attend`` for whole sequences from an empty state (the model's
    own ``apply``): ``batch`` rows of equal length, the chunked XLA form
    (padded to a whole chunk)."""

    def attend(mp, u, g, beta):
        n, h, kk = u.shape[0], mixer.n_heads, mixer.head_dim
        t = n // batch
        past = jnp.zeros((batch, mixer.conv - 1, u.shape[-1]), u.dtype)
        q, k, v = mixer.convolve(mp, past, u.reshape(batch, t, -1))
        pad = -t % CHUNK

        def fit(a):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

        s0 = jnp.zeros((batch, h, kk, kk), jnp.float32)
        o, _ = kda_chunk_xla(
            s0, fit(q), fit(k), fit(v), fit(g.reshape(batch, t, h, kk)),
            fit(beta.reshape(batch, t, h)))
        return o[:, :t].reshape(n, h, kk)

    return attend
