"""Fused flash-attention (Pallas TPU) — forward AND backward kernels.

The dense attention path (``parallel.ring_attention.full_attention``)
materializes the (B, H, Tq, Tk) score matrix in HBM — the classic
O(T²) memory wall. These kernels compute the same softmax(QKᵀ)V with
the online-softmax recurrence entirely in VMEM:

- **forward**: one grid step owns one (batch·head, q-block) tile,
  streams K/V blocks through registers, writes the (BLOCK_Q, D) output
  tile plus the per-row log-sum-exp (the only residual the backward
  needs beyond q/k/v/out).
- **backward** (FlashAttention-2 schedule): probabilities are
  *recomputed* blockwise from q/k/lse — never stored — in two kernels
  with no cross-tile accumulation hazards: a dq pass gridded over
  q-blocks and a dk/dv pass gridded over k-blocks, each streaming the
  opposite operand. ``Δ = rowsum(dout·out)`` is precomputed in XLA
  (cheap elementwise) and prefetched per tile.

Causal masking skips fully-masked blocks in all three kernels (the
forward bounds its K loop at the diagonal; dq starts its K loop at 0
and ends at the diagonal; dk/dv starts its Q loop at the diagonal).

HBM traffic: O(T·D) per pass instead of O(T²). Head dim and sequence
enter VMEM whole per (b, h): fine through T ≈ 8k at D=64/128 on
v5e-class VMEM; beyond that, shard sequence over ``sp`` first — ring
attention composes (``attn_impl`` applies to the local dense paths).

``interpret=True`` on the CPU so CI exercises the same kernel code
(the gate is ``ops.platform.on_tpu``).

Reference lineage: the reference framework has no attention at all
(SURVEY.md §3.4); its only native-kernel component was the fp16
pack/unpack CUDA pair (§3.3) — this is the same "hot op → native
kernel" tier applied to the op that dominates transformer step time.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from theanompi_tpu.ops import platform

_NEG_INF = -1e30

BLOCK_Q = 128  # MXU/VPU-friendly tile; shapes must divide (or T < block)
BLOCK_K = 128


def _pick_block(t: int, pref: int) -> int:
    if t <= pref:
        return t
    for b in (pref, 64, 32, 16, 8):
        if t % b == 0:
            return b
    return t  # fall back to one block (still correct, more VMEM)


def _dot(a, b, dims, precision=None):
    """f32-accumulating block matmul.  ``precision`` matters on real
    MXUs: the TPU default multiplies f32 operands in bf16 passes
    (~3e-3 abs error on unit-scale data — measured on the first r4
    chip run), which is the right trade for training throughput;
    ``lax.Precision.HIGHEST`` buys exact-f32 multiplies at ~3× the
    MXU passes for callers that need oracle-grade numerics."""
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, bq, bk, t,
                precision=None):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    d = q.shape[-1]
    nk = t // bk

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    den0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(kc, carry):
        m, den, acc = carry
        k_blk = k_ref[0, pl.dslice(kc * bk, bk)].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(kc * bk, bk)].astype(jnp.float32)
        s = _dot(q, k_blk, ((1,), (1,)), precision) * scale  # (bq, bk)
        if causal:
            k_pos = kc * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        corr = jnp.exp(m - m_new)
        den = den * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + _dot(p, v_blk, ((1,), (0,)), precision)
        return m_new, den, acc

    if causal:
        # skip K blocks entirely above the diagonal: q-block qi covers
        # rows < (qi+1)·bq — without this the causal forward does ~2×
        # the necessary block matmuls
        nk_eff = jnp.minimum(nk, ((qi + 1) * bq + bk - 1) // bk)
    else:
        nk_eff = nk
    m, den, acc = lax.fori_loop(0, nk_eff, body, (m0, den0, acc0))
    o_ref[0] = (acc / den[:, None]).astype(o_ref.dtype)
    # stats ride a trailing singleton dim: Mosaic requires the last two
    # block dims to be (8,128)-divisible or full, which a rank-2 (1, bq)
    # block violates (found on the first real-chip run, r4) — (bq, 1)
    # satisfies it as (8-divisible, equal-to-array)
    lse_ref[0] = (m + jnp.log(den))[:, None]


def _flash_forward(q, k, v, causal, scale, precision=None):
    b, t, h, d = q.shape
    bq = _pick_block(t, BLOCK_Q)
    bk = _pick_block(t, BLOCK_K)
    # (B, T, H, D) -> (B*H, T, D): one grid row per (batch, head)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, t=t,
        precision=precision,
    )
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32),
        ),
        grid=(b * h, t // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
        ),
        interpret=not platform.on_tpu(),
        name="flash_attn_fwd",
    )(qr, kr, vr)
    return out, lse[..., 0]  # both in (B*H, ...) layout


# ---------------------------------------------------------------------------
# backward — FlashAttention-2 two-pass schedule
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
               *, scale, causal, bq, bk, t, precision=None):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0]  # (bq,) — stats carry a trailing unit dim
    dlt = dlt_ref[0][:, 0]  # (see _fwd_kernel: Mosaic block-shape rule)
    d = q.shape[-1]
    nk = t // bk
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(kc, dq):
        k_blk = k_ref[0, pl.dslice(kc * bk, bk)].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(kc * bk, bk)].astype(jnp.float32)
        s = _dot(q, k_blk, ((1,), (1,)), precision) * scale
        if causal:
            k_pos = kc * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])  # normalized probabilities
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = _dot(do, v_blk, ((1,), (1,)), precision)  # (bq, bk)
        ds = p * (dp - dlt[:, None]) * scale
        return dq + _dot(ds, k_blk, ((1,), (0,)), precision)

    nk_eff = jnp.minimum(nk, ((qi + 1) * bq + bk - 1) // bk) if causal else nk
    dq = lax.fori_loop(0, nk_eff, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref, dv_ref,
                *, scale, causal, bq, bk, t, precision=None):
    kc = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)  # (bk, d)
    v_blk = v_ref[0].astype(jnp.float32)
    d = k_blk.shape[-1]
    nq = t // bq
    k_pos = kc * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def body(qi, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.dslice(qi * bq, bq)].astype(jnp.float32)
        do_blk = do_ref[0, pl.dslice(qi * bq, bq)].astype(jnp.float32)
        lse = lse_ref[0, pl.dslice(qi * bq, bq), 0]
        dlt = dlt_ref[0, pl.dslice(qi * bq, bq), 0]
        s = _dot(q_blk, k_blk, ((1,), (1,)), precision) * scale  # (bq, bk)
        if causal:
            q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dv = dv + _dot(p, do_blk, ((0,), (0,)), precision)  # (bk, d)
        dp = _dot(do_blk, v_blk, ((1,), (1,)), precision)  # (bq, bk)
        ds = p * (dp - dlt[:, None]) * scale
        dk = dk + _dot(ds, q_blk, ((0,), (0,)), precision)  # (bk, d)
        return dk, dv

    # causal: q-blocks strictly above the diagonal see only masked rows
    qi_min = (kc * bk) // bq if causal else 0
    zeros = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(qi_min, nq, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(causal, scale, precision, res, ct):
    qr, kr, vr, out, lse = res  # all (B*H, T, D) / (B*H, T)
    do = ct  # (B*H, T, D) fp32-or-input-dtype cotangent
    # Δ_i = Σ_d dout·out — XLA elementwise, prefetched per tile
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (B*H, T)
    return flash_backward_rows(qr, kr, vr, do, lse, delta, causal, scale,
                               precision=precision)


def flash_backward_rows(qr, kr, vr, do, lse, delta, causal, scale,
                        precision=None):
    """FA-2 backward kernels on row-layout operands with a precomputed
    Δ — the entry the ring backward drives per block, so that the
    loop-invariant pieces (Q/dO transposes, lse reshape, Δ) are
    computed ONCE outside the ring scan instead of per hop.

    The enabler for the ring backward: because the FA-2 recomputation
    normalizes probabilities by ``p = exp(s − lse)``, feeding the
    *global* (all-ring-steps) lse makes each (Q-shard, KV-block) pair's
    ``dq += ds·K``, ``dk += dsᵀ·Q``, ``dv += pᵀ·dO`` exact additive
    partials of the full-sequence gradient — no re-weighting or second
    online pass needed. ``delta`` must come from the global output
    (Δ = rowsum(dO·O) is only meaningful globally).

    qr/kr/vr/do (B·H, T, D) with equal Tq == Tk; lse/delta (B·H, T);
    ``scale`` must already be resolved (a float). Returns (dq, dk, dv)
    in rows layout.
    """
    bh, t, d = qr.shape
    bq = _pick_block(t, BLOCK_Q)
    bk = _pick_block(t, BLOCK_K)

    # stats enter the kernels with a trailing unit dim (Mosaic block-
    # shape rule — see _fwd_kernel); same bytes, legal (… , bq, 1) tiles
    lse3 = lse[..., None]
    dlt3 = delta[..., None]

    row = lambda bhi, i: (bhi, 0, 0)  # noqa: E731 — whole-row spec

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, t=t,
            precision=precision,
        ),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qr.dtype),
        grid=(bh, t // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
            pl.BlockSpec((1, t, d), row),
            pl.BlockSpec((1, t, d), row),
            pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bhi, qi: (bhi, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bhi, qi: (bhi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bhi, qi: (bhi, qi, 0)),
        interpret=not platform.on_tpu(),
        name="flash_attn_bwd_dq",
    )(qr, kr, vr, do, lse3, dlt3)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, t=t,
            precision=precision,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), kr.dtype),
            jax.ShapeDtypeStruct((bh, t, d), vr.dtype),
        ),
        grid=(bh, t // bk),
        in_specs=[
            pl.BlockSpec((1, t, d), row),
            pl.BlockSpec((1, bk, d), lambda bhi, kc: (bhi, kc, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, kc: (bhi, kc, 0)),
            pl.BlockSpec((1, t, d), row),
            pl.BlockSpec((1, t, 1), row),
            pl.BlockSpec((1, t, 1), row),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, d), lambda bhi, kc: (bhi, kc, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, kc: (bhi, kc, 0)),
        ),
        interpret=not platform.on_tpu(),
        name="flash_attn_bwd_dkv",
    )(qr, kr, vr, do, lse3, dlt3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom VJP over the kernels)
# ---------------------------------------------------------------------------

def to_rows(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def from_rows(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def flash_forward_with_lse(q, k, v, causal=False, scale=None, precision=None):
    """Forward-only kernel entry returning ``(out, lse)`` with
    lse shaped (B, H, T). NO AD rule — callers (the ring-flash path)
    wrap it in their own custom_vjp; differentiating this directly
    raises at trace time (pallas_call has no autodiff registration).
    """
    s = resolve_scale(scale, q.shape[-1])
    out, lse = _flash_forward(q, k, v, causal, s, precision)
    b, h = q.shape[0], q.shape[2]
    return from_rows(out, b, h), lse.reshape(b, h, -1)


def resolve_scale(scale, d: int) -> float:
    """THE default-scale policy, resolved once — fwd and bwd must agree."""
    return float(scale) if scale is not None else d ** -0.5


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    precision=None,
):
    """softmax(QKᵀ·scale)V, fused fwd+bwd. Shapes (B, T, H, D) like
    ``full_attention``; same numerics (fp32 statistics) by test.

    ``precision``: forwarded to every block matmul (see ``_dot``).
    None = backend default (bf16 multiply passes on TPU — the training
    configuration); ``lax.Precision.HIGHEST`` = exact-f32 multiplies
    (oracle-grade, ~3× MXU passes; what the chip-vs-oracle tests use).
    """
    out, _ = _flash_forward(
        q, k, v, causal, resolve_scale(scale, q.shape[-1]), precision
    )
    return from_rows(out, q.shape[0], q.shape[2])


def _vjp_fwd(q, k, v, causal, scale, precision):
    s = resolve_scale(scale, q.shape[-1])
    out, lse = _flash_forward(q, k, v, causal, s, precision)
    b, h = q.shape[0], q.shape[2]
    res = (to_rows(q), to_rows(k), to_rows(v), out, lse, b, h, s)
    return from_rows(out, b, h), res


def _vjp_bwd(causal, scale, precision, res, ct):
    qr, kr, vr, out, lse, b, h, s = res  # s: the scale the fwd ran with
    dq, dk, dv = _flash_backward(
        causal, s, precision, (qr, kr, vr, out, lse), to_rows(ct)
    )
    return from_rows(dq, b, h), from_rows(dk, b, h), from_rows(dv, b, h)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
