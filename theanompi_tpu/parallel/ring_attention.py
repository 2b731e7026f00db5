"""Ring attention — sequence/context parallelism over a mesh axis.

The reference framework has no attention anywhere (2016 CNN/GAN zoo;
SURVEY.md §3.4 / §6 "long-context: ABSENT"), but long-context sequence
parallelism is a first-class requirement of this framework, so it is
built into the parallel layer rather than bolted onto a model.

Design (TPU-first, after Liu et al., "Ring Attention with Blockwise
Transformers", and the blockwise-parallel-transformer lineage in
PAPERS.md):

- The sequence dimension is sharded over a named mesh axis (``sp``).
  Each device holds a query block Q_i and starts with its own K_i/V_i.
- ``n_sp`` ring steps: compute blockwise attention of Q_i against the
  resident K/V block, then rotate K/V one hop around the ring with
  ``lax.ppermute`` — on TPU this rides ICI neighbor links, overlapping
  the transfer with the next block's compute under XLA's scheduler.
- Numerically exact (not approximate): blocks combine with the online
  softmax recurrence (running max ``m``, normalizer ``den``, numerator
  ``num``), so the result is bit-comparable to full attention up to
  float association.
- Causal masking uses global positions reconstructed from
  ``lax.axis_index``: query block ``i`` holds rows ``[i·T, (i+1)·T)``,
  and after ``s`` rotations the resident K/V block originated on device
  ``(i − s) mod n``.

Everything here runs *inside* ``shard_map`` (the functions take the
local shards). ``ring_self_attention`` is a convenience wrapper that
builds the shard_map for standalone use and tests; models embed
``ring_attention`` directly in their own step functions via
``ops.attention.MultiHeadAttention(sp_axis=...)``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

SEQ_AXIS = "sp"  # canonical sequence-parallel mesh axis name

_NEG_INF = -1e30  # finite mask value: keeps exp() NaN-free on all-masked rows


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain softmax attention; the single-device reference semantics.

    Shapes: q (B, Tq, H, D), k/v (B, Tk, H, D) → (B, Tq, H, D).
    Softmax statistics are computed in fp32 regardless of input dtype.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def local_attention(q, k, v, causal=False, scale=None, attn_impl="xla"):
    """THE local dense-attention dispatch (XLA fused vs Pallas flash) —
    shared by the non-SP path, the Ulysses local phase, and the sp=1
    degenerations, so impl/scale policy lives in one place."""
    if attn_impl == "flash":
        from theanompi_tpu.ops.pallas_flash import flash_attention

        return flash_attention(q, k, v, causal, scale)
    return full_attention(q, k, v, causal=causal, scale=scale)


def _block_update(q, k_blk, v_blk, m, den, num, scale, mask):
    """One online-softmax accumulation step against a K/V block.

    q (B,Tq,H,D); k_blk/v_blk (B,Tk,H,D); m/den (B,H,Tq); num (B,H,Tq,D).
    ``mask`` is (Tq, Tk) boolean or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        # zero masked probabilities explicitly: on a fully-masked row
        # m_new stays at _NEG_INF and exp(s - m_new) = 1, which must not
        # count toward the normalizer
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    den = den * corr + jnp.sum(p, axis=-1)
    num = num * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32,
    )
    return m_new, den, num


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQ_AXIS,
    axis_size: Optional[int] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    attn_impl: str = "xla",
) -> jax.Array:
    """Exact blockwise attention over sequence shards on a ring.

    Call inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``. Local shapes: q/k/v (B, T_local, H, D); returns the
    local output shard (B, T_local, H, D) in q's dtype.

    ``axis_size`` is the static size of the ring (``mesh.shape[axis]``);
    it must be supplied because the loop bound has to be a Python int
    for XLA unrolling/scan. With ``axis_size=1`` this degrades to
    ``full_attention`` (no collectives traced — the single-shard path
    costs nothing extra).
    """
    if axis_size is None:
        raise ValueError("ring_attention needs static axis_size (mesh.shape[axis])")
    if axis_size == 1:
        return local_attention(q, k, v, causal, scale, attn_impl)
    if attn_impl == "flash":
        return ring_attention_flash(
            q, k, v, axis_name, axis_size, causal, scale
        )

    b, t, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    m0 = jnp.full((b, h, t), _NEG_INF, jnp.float32)
    den0 = jnp.zeros((b, h, t), jnp.float32)
    num0 = jnp.zeros((b, h, t, d), jnp.float32)

    def step(carry, s):
        k_blk, v_blk, m, den, num = carry
        if causal:
            src = (my - s) % axis_size  # origin device of the resident block
            qpos = my * t + jnp.arange(t)
            kpos = src * t + jnp.arange(t)
            mask = qpos[:, None] >= kpos[None, :]
        else:
            mask = None
        m, den, num = _block_update(q, k_blk, v_blk, m, den, num, scale, mask)
        # rotate K/V one hop; neighbor transfer over ICI. The final
        # rotation returns the block home — keeping it unconditional
        # trades one redundant hop for a branch-free scan body.
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m, den, num), None

    (k, v, m, den, num), _ = lax.scan(
        step, (k, v, m0, den0, num0), jnp.arange(axis_size)
    )
    out = num / den[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _merge_blocks(o1, lse1, o2, lse2):
    """Online-softmax combination of two attention partials.

    o: (B, T, H, D); lse: (B, H, T). Numerically safe for one side
    being all-masked (lse = -inf ⇒ weight 0)."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    den = w1 + w2
    c1 = jnp.transpose(w1 / den, (0, 2, 1))[..., None]  # (B, T, H, 1)
    c2 = jnp.transpose(w2 / den, (0, 2, 1))[..., None]
    return o1 * c1 + o2 * c2, m + jnp.log(den)


def _ring_flash_forward_impl(q, k, v, axis_name, axis_size, causal, scale):
    """The flash ring forward, returning ``(out, lse)`` — lse is the
    GLOBAL log-sum-exp over every ring step, the residual that makes
    the blockwise FA-2 backward exact (see ``_ring_flash_bwd``)."""
    from theanompi_tpu.ops.pallas_flash import flash_forward_with_lse

    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # s = 0: the diagonal block (own K/V). The merge carry runs fp32
    # (partials are re-weighted each step; bf16 inputs would also
    # break the scan/cond carry dtype contract) — cast back at the end.
    o, lse = flash_forward_with_lse(q, k, v, causal=causal, scale=scale)
    o = o.astype(jnp.float32)

    def step(carry, s):
        k_blk, v_blk, o, lse = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (my - s) % axis_size

        def visible(args):
            o, lse = args
            o_s, lse_s = flash_forward_with_lse(
                q, k_blk, v_blk, causal=False, scale=scale
            )
            return _merge_blocks(o, lse, o_s.astype(jnp.float32), lse_s)

        if causal:
            o, lse = lax.cond(src < my, visible, lambda a: a, (o, lse))
        else:
            o, lse = visible((o, lse))
        return (k_blk, v_blk, o, lse), None

    (_, _, o, lse), _ = lax.scan(
        step, (k, v, o, lse), jnp.arange(1, axis_size)
    )
    return o.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention_flash(q, k, v, axis_name, axis_size, causal, scale):
    """Ring attention whose per-step block attention runs the fused
    Pallas flash kernel, partials merged by log-sum-exp.

    Causal structure on the ring is block-triangular: the resident
    (s=0) block is the diagonal (standard causal flash); a rotated-in
    block from source device ``src`` is either fully visible
    (``src < my`` — dense flash) or fully masked (skip, no kernel
    launch). Backward: blockwise FA-2 ring (same block-triangular
    skips) — the global lse saved from the forward makes every
    per-block kernel contribution an exact additive partial, and dk/dv
    accumulators travel the ring *with* their K/V block, arriving home
    after the final hop.
    """
    return _ring_flash_forward_impl(
        q, k, v, axis_name, axis_size, causal, scale
    )[0]


def _ring_flash_fwd(q, k, v, axis_name, axis_size, causal, scale):
    out, lse = _ring_flash_forward_impl(
        q, k, v, axis_name, axis_size, causal, scale
    )
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, axis_size, causal, scale, res, ct):
    """FA-2 backward on the ring — no O(T²) rematerialization, no
    second forward. Each ring step feeds the resident K/V block plus
    the global lse to the blockwise flash backward kernels:

    - dq accumulates locally on the query owner (every visible block
      contributes ``ds·K``).
    - dk/dv partials are accumulated into carries that ``ppermute``
      around the ring in lockstep with their K/V block; after the ring
      closes (axis_size hops total) each block's gradient lands back
      on the device that owns it.

    Causality mirrors the forward exactly: the s=0 diagonal block runs
    the causal kernels; rotated-in blocks run dense kernels when
    ``src < my`` and are skipped (carry passthrough, no kernel launch)
    when fully masked.

    The whole ring runs in the kernels' row layout (B·H, T, D): the
    loop-invariant operands (Q, dO, lse, Δ) are converted/computed once
    up front, the traveling K/V blocks and their accumulators rotate in
    row layout, and only the three outputs convert back at the end.
    """
    from theanompi_tpu.ops.pallas_flash import (
        flash_backward_rows, from_rows, resolve_scale, to_rows,
    )

    q, k, v, o, lse = res
    b, h = q.shape[0], q.shape[2]
    s_resolved = resolve_scale(scale, q.shape[-1])
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    qr = to_rows(q)
    kr = to_rows(k)
    vr = to_rows(v)
    dor = to_rows(ct)
    lser = lse.reshape(b * h, -1)
    # Δ = rowsum(dO·O) over the GLOBAL output — loop-invariant
    delta = jnp.sum(
        dor.astype(jnp.float32) * to_rows(o).astype(jnp.float32), axis=-1
    )

    def block_bwd(k_rows, v_rows, blk_causal):
        return flash_backward_rows(
            qr, k_rows, v_rows, dor, lser, delta, blk_causal, s_resolved
        )

    # s = 0: the diagonal block. Accumulators run fp32 — dk/dv partials
    # are summed across up to axis_size devices' contributions.
    dq0, dk0, dv0 = block_bwd(kr, vr, causal)
    dq0 = dq0.astype(jnp.float32)
    dk0 = dk0.astype(jnp.float32)
    dv0 = dv0.astype(jnp.float32)

    def step(carry, s):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        # rotate the K/V block and ITS gradient accumulators together —
        # the pairing is what routes each block's dk/dv home
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        dk_blk = lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = lax.ppermute(dv_blk, axis_name, perm)
        src = (my - s) % axis_size

        def visible(args):
            dk_blk, dv_blk, dq = args
            dq_c, dk_c, dv_c = block_bwd(k_blk, v_blk, False)
            return (
                dk_blk + dk_c.astype(jnp.float32),
                dv_blk + dv_c.astype(jnp.float32),
                dq + dq_c.astype(jnp.float32),
            )

        if causal:
            dk_blk, dv_blk, dq = lax.cond(
                src < my, visible, lambda a: a, (dk_blk, dv_blk, dq)
            )
        else:
            dk_blk, dv_blk, dq = visible((dk_blk, dv_blk, dq))
        return (k_blk, v_blk, dk_blk, dv_blk, dq), None

    (_, _, dk_blk, dv_blk, dq), _ = lax.scan(
        step, (kr, vr, dk0, dv0, dq0), jnp.arange(1, axis_size)
    )
    # the scan made axis_size−1 hops; one more closes the ring and
    # returns each block's accumulated gradient to its owner
    dk_blk = lax.ppermute(dk_blk, axis_name, perm)
    dv_blk = lax.ppermute(dv_blk, axis_name, perm)
    return (
        from_rows(dq, b, h).astype(q.dtype),
        from_rows(dk_blk, b, h).astype(k.dtype),
        from_rows(dv_blk, b, h).astype(v.dtype),
    )


ring_attention_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_self_attention(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = SEQ_AXIS,
    causal: bool = False,
):
    """Standalone sharded entry point (tests / direct use).

    Takes *global* (B, T, H, D) arrays, shard_maps the ring over
    ``mesh`` axis ``axis`` (T must divide by its size), returns the
    global result.
    """
    n = int(mesh.shape[axis])
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis, axis_size=n, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)(q, k, v)
