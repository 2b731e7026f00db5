"""Grouped matrix product for sparse experts (Pallas TPU).

The rows of ``x`` are the tokens of a step sorted by the expert they
were routed to and laid out in row tiles of ``tm`` so that **a tile
holds one expert's tokens** (``parallel.moe.dispatch_plan`` pads each
expert's group up to a whole tile).  ``tile_expert[i]`` names tile
``i``'s expert and rides as a scalar-prefetch argument, so the weight
BlockSpec's ``index_map`` reads it and every grid step streams one
``(tk, tn)`` block of exactly that expert's matrix: an expert nobody
chose is never read, and one that holds two tiles is read twice.  Tiles
past ``n_valid`` (the static tile count is the worst case of the
routing) run no product, and their index maps stay on the last valid
tile's blocks, so they fetch nothing either.

``grouped_mm_xla`` is the same product in plain XLA (a gather of each
tile's matrix and one batched product): the oracle of the kernel, the
differentiable form that training uses, and what a pool sharded over
several devices falls back to.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops import platform


def _largest_divisor(n: int, candidates) -> int:
    """The first of ``candidates`` that divides ``n``; ``n`` itself (one
    block) where none does or ``n`` is no multiple of a lane tile."""
    if n % 128:
        return n
    return next((c for c in candidates if n % c == 0), n)


def _gmm_kernel(te_ref, nv_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(i < nv_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[0], preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _fin():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_mm(x, w, tile_expert, n_valid, *, tm: int,
               name: str = "moe_grouped_mm",
               interpret: Optional[bool] = None):
    """``out[r] = x[r] @ w[tile_expert[r // tm]]`` for the rows of the
    first ``n_valid`` tiles; rows of later tiles are left unwritten.

    ``x`` (n_tiles·tm, K), ``w`` (E, K, N), ``tile_expert`` (n_tiles,)
    int32, ``n_valid`` (1,) int32.  Returns (n_tiles·tm, N) in ``x``'s
    dtype, accumulated in fp32."""
    m, kdim = x.shape
    _, _, n = w.shape
    n_tiles = m // tm
    tk = _largest_divisor(kdim, (512, 256, 128))
    tn = _largest_divisor(n, (1024, 896, 512, 256, 128))
    nk, nn = kdim // tk, n // tn

    def tile(i, nv):  # a tile past the valid ones stays on the last
        return jnp.minimum(i, jnp.maximum(nv[0] - 1, 0))

    def x_map(i, j, k, te, nv):
        return (tile(i, nv), jnp.where(i < nv[0], k, nk - 1))

    def w_map(i, j, k, te, nv):
        live = i < nv[0]
        return (te[tile(i, nv)], jnp.where(live, k, nk - 1),
                jnp.where(live, j, nn - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nn, nk),
        in_specs=[pl.BlockSpec((tm, tk), x_map),
                  pl.BlockSpec((1, tk, tn), w_map)],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k, te, nv: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=(not platform.on_tpu()) if interpret is None else interpret,
        name=name,
    )(jnp.asarray(tile_expert, jnp.int32), jnp.asarray(n_valid, jnp.int32),
      x, w.astype(x.dtype))


def grouped_mm_xla(x, w, tile_expert, n_valid=None, *, tm: int):
    """The plain form: every tile times its expert's matrix (tiles past
    ``n_valid`` too: their rows are padding and nobody reads them)."""
    m, kdim = x.shape
    wt = jnp.take(w, tile_expert, axis=0).astype(x.dtype)  # (n_tiles, K, N)
    out = jnp.einsum("tmk,tkn->tmn", x.reshape(m // tm, tm, kdim), wt,
                     preferred_element_type=jnp.float32)
    return out.reshape(m, -1).astype(x.dtype)
