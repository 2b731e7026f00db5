"""Manifold-constrained hyper-connections (arXiv:2512.24880, on
arXiv:2409.19606): a residual of ``n`` streams that every sublayer reads
through a learned, input-dependent mix and writes back through a doubly
stochastic ``n × n`` matrix.

Per token, with the state ``X`` (n, d) and a sublayer ``F``::

    x̃      = vec(X) / sqrt(mean(vec(X)²) + eps)              (n·d, fp32)
    z      = alpha ⊙ (x̃ Φ) + b                               (2n + n²)
    H_pre  = sigmoid(z[:n])          H_post = 2 · sigmoid(z[n:2n])
    H_res  = sinkhorn(exp(clip(z[2n:], ±clamp)))              (n, n)
    u      = Σ_i H_pre[i] · X[i]                              (d)
    X'[i]  = Σ_j H_res[i, j] · X[j] + H_post[i] · F(norm(u))

``sinkhorn`` divides the rows by their sums ``+ eps``, then the columns,
``iters`` times.  ``alpha`` is one scalar for each of the three groups.

Two halves around the sublayer, each in two forms:

- ``mhc_pre`` (coefficients and the read mix) and ``mhc_post`` (the
  write-back) as Pallas kernels over tiles of tokens, named so in a
  device trace.  The coefficients of a tile live in the lanes of one
  (tile, 128) array (``[H_pre | H_post | H_res row-major | 0…]``); the
  Sinkhorn sums over a row or a column are products with a 0/1 matrix
  over those lanes, fed in three bfloat16 terms so that the sum is
  float32's whatever precision the matrix unit multiplies at.
- ``mhc_pre_xla`` / ``mhc_post_xla``: the same in plain XLA — the
  kernels' oracle and the CPU path.

Shapes: ``x`` (T, n·d) (stream ``i`` in lanes ``[i·d, (i+1)·d)``),
``phi_t`` (128, n·d) (``Φ`` transposed, rows past ``2n + n²`` zero),
``ab`` (2, 128) fp32 (``alpha`` spread over its group's lanes; ``b``).
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

LANES = 128
_VMEM_LIMIT = 96 * 1024 * 1024


def pack_coefficients(phi, alpha, bias, n: int):
    """``(phi_t (128, n·d), ab (2, 128) fp32)`` from the layer's
    parameters ``phi`` (2n + n², n·d: ``Φ`` transposed), ``alpha`` (3,),
    ``bias`` (2n + n²,)."""
    m = 2 * n + n * n
    phi_t = jnp.pad(phi, ((0, LANES - m), (0, 0)))
    group = jnp.repeat(jnp.arange(3), jnp.array([n, n, n * n]),
                       total_repeat_length=m)
    a = jnp.zeros((LANES,), jnp.float32).at[:m].set(
        alpha.astype(jnp.float32)[group])
    b = jnp.zeros((LANES,), jnp.float32).at[:m].set(bias.astype(jnp.float32))
    return phi_t, jnp.stack([a, b])


# ---------------------------------------------------------------------------
# plain XLA
# ---------------------------------------------------------------------------

def coefficients_xla(x, phi_t, ab, *, n, eps, iters, clamp):
    """(T, 128) fp32 coefficient lanes, as the kernel lays them out."""
    t = x.shape[0]
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    raw = lax.dot_general(x, phi_t.astype(x.dtype), (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    z = raw * inv * ab[0] + ab[1]
    pre = jax.nn.sigmoid(z[:, :n])
    post = 2.0 * jax.nn.sigmoid(z[:, n:2 * n])
    r = jnp.exp(jnp.clip(z[:, 2 * n:2 * n + n * n], -clamp, clamp))
    r = r.reshape(t, n, n)
    for _ in range(iters):
        r = r / (jnp.sum(r, axis=2, keepdims=True) + eps)
        r = r / (jnp.sum(r, axis=1, keepdims=True) + eps)
    out = jnp.concatenate([pre, post, r.reshape(t, n * n)], axis=-1)
    return jnp.pad(out, ((0, 0), (0, LANES - out.shape[-1])))


def mhc_pre_xla(x, phi_t, ab, *, n, eps, iters, clamp):
    t, nd = x.shape
    coef = coefficients_xla(x, phi_t, ab, n=n, eps=eps, iters=iters,
                            clamp=clamp)
    u = jnp.einsum("tn,tnd->td", coef[:, :n],
                   x.astype(jnp.float32).reshape(t, n, nd // n))
    return u.astype(x.dtype), coef


def mhc_post_xla(x, y, coef, *, n):
    t, nd = x.shape
    xs = x.astype(jnp.float32).reshape(t, n, nd // n)
    res = coef[:, 2 * n:2 * n + n * n].reshape(t, n, n)
    out = (jnp.einsum("tij,tjd->tid", res, xs)
           + coef[:, n:2 * n, None] * y.astype(jnp.float32)[:, None, :])
    return out.reshape(t, nd).astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas
# ---------------------------------------------------------------------------

def _group_sum(r, ones):
    """``r @ ones`` for a 0/1 ``ones`` with float32's accuracy: ``r`` in
    three bfloat16 terms, each product exact, accumulated in fp32."""
    total = None
    for _ in range(3):
        part = r.astype(jnp.bfloat16)
        s = jnp.dot(part, ones, preferred_element_type=jnp.float32)
        total = s if total is None else total + s
        r = r - part.astype(jnp.float32)
    return total


def _pre_kernel(x_ref, phi_ref, ab_ref, u_ref, coef_ref, *, n, d, eps, iters,
                clamp):
    tt = x_ref.shape[0]
    ssq = jnp.zeros((tt, 1), jnp.float32)
    raw = jnp.zeros((tt, LANES), jnp.float32)
    for i in range(n):
        xi = x_ref[:, i * d:(i + 1) * d]
        xf = xi.astype(jnp.float32)
        ssq = ssq + jnp.sum(xf * xf, axis=1, keepdims=True)
        raw = raw + lax.dot_general(
            xi, phi_ref[:, i * d:(i + 1) * d], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    inv = lax.rsqrt(ssq / (n * d) + eps)
    z = raw * inv * ab_ref[0:1, :] + ab_ref[1:2, :]
    lane = lax.broadcasted_iota(jnp.int32, (tt, LANES), 1)
    sig = jax.nn.sigmoid(z)
    gate = jnp.where(lane < n, sig, 2.0 * sig)
    in_res = (lane >= 2 * n) & (lane < 2 * n + n * n)
    r = jnp.where(in_res, jnp.exp(jnp.clip(z, -clamp, clamp)), 0.0)
    # 0/1 matrices over the lanes: same row / same column of the n × n
    a = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) - 2 * n
    b = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1) - 2 * n
    both = (a >= 0) & (a < n * n) & (b >= 0) & (b < n * n)
    same_row = (both & (a // n == b // n)).astype(jnp.bfloat16)
    same_col = (both & (a % n == b % n)).astype(jnp.bfloat16)
    # the other lanes divide by one: a compiler that folds the chain of
    # divisions into one would otherwise underflow eps ** (2 · iters)
    for _ in range(iters):
        r = r / jnp.where(in_res, _group_sum(r, same_row) + eps, 1.0)
        r = r / jnp.where(in_res, _group_sum(r, same_col) + eps, 1.0)
    coef = jnp.where(lane < 2 * n, gate, r)
    coef_ref[...] = coef
    u = jnp.zeros((tt, d), jnp.float32)
    for i in range(n):
        u = u + coef[:, i:i + 1] * x_ref[:, i * d:(i + 1) * d].astype(jnp.float32)
    u_ref[...] = u.astype(u_ref.dtype)


def _post_kernel(x_ref, y_ref, coef_ref, o_ref, *, n, d):
    coef = coef_ref[...]
    yf = y_ref[...].astype(jnp.float32)
    xs = [x_ref[:, j * d:(j + 1) * d].astype(jnp.float32) for j in range(n)]
    for i in range(n):
        acc = coef[:, n + i:n + i + 1] * yf
        for j in range(n):
            c = 2 * n + i * n + j
            acc = acc + coef[:, c:c + 1] * xs[j]
        o_ref[:, i * d:(i + 1) * d] = acc.astype(o_ref.dtype)


def _token_tile(t: int, nd: int) -> int:
    """Tokens a grid step holds: 128 at most, fewer for a wide state
    (the state's tile, its fp32 copies and the output's share VMEM)."""
    cap = 128 if nd <= 8192 else 64
    return t if t <= cap else cap


def _pad_tokens(tt, *arrays):
    t = arrays[0].shape[0]
    pad = -t % tt
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, pad), (0, 0))) for a in arrays)


def mhc_pre(x, phi_t, ab, *, n, eps, iters, clamp,
            interpret: Optional[bool] = None):
    """``(u (T, d), coef (T, 128) fp32)``: the coefficients of every
    token and the read mix ``u = Σ_i H_pre[i] · X[i]``."""
    t, nd = x.shape
    d = nd // n
    tt = _token_tile(t, nd)
    (xp,) = _pad_tokens(tt, x)
    tp = xp.shape[0]
    u, coef = pl.pallas_call(
        functools.partial(_pre_kernel, n=n, d=d, eps=eps, iters=iters,
                          clamp=clamp),
        grid=(tp // tt,),
        in_specs=[pl.BlockSpec((tt, nd), lambda i: (i, 0)),
                  pl.BlockSpec((LANES, nd), lambda i: (0, 0)),
                  pl.BlockSpec((2, LANES), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((tt, d), lambda i: (i, 0)),
                   pl.BlockSpec((tt, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((tp, d), x.dtype),
                   jax.ShapeDtypeStruct((tp, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=(not platform.on_tpu()) if interpret is None else interpret,
        name="mhc_pre",
    )(xp, phi_t.astype(x.dtype), ab)
    return u[:t], coef[:t]


def mhc_post(x, y, coef, *, n, interpret: Optional[bool] = None):
    """``X'[i] = Σ_j H_res[i, j] · X[j] + H_post[i] · y`` (T, n·d)."""
    t, nd = x.shape
    d = nd // n
    tt = _token_tile(t, nd)
    xp, yp, cp = _pad_tokens(tt, x, y, coef)
    tp = xp.shape[0]
    out = pl.pallas_call(
        functools.partial(_post_kernel, n=n, d=d),
        grid=(tp // tt,),
        in_specs=[pl.BlockSpec((tt, nd), lambda i: (i, 0)),
                  pl.BlockSpec((tt, d), lambda i: (i, 0)),
                  pl.BlockSpec((tt, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tt, nd), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, nd), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=(not platform.on_tpu()) if interpret is None else interpret,
        name="mhc_post",
    )(xp, yp.astype(x.dtype), cp)
    return out[:t]
