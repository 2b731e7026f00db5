"""Fused Pallas TPU kernel for local response normalization.

LRN is the hot non-matmul op of the AlexNet/GoogLeNet era models
(reference ``LRN`` layer in ``theanompi/models/layers2.py``): its XLA
chain (square → pad → reduce_window → power → divide) accounts for ~1/3
of the whole AlexNet-128 training step. These kernels fuse the entire op
— forward AND backward — into one read + one write of the activation,
with all window math done in VMEM registers.

Measured verdict (v5e, AlexNet-128 bs512): the kernel wins in isolation
(e.g. 2.9ms → 1.1ms fwd+bwd on the 256-channel LRN), but inserting it
into the full model *loses* ~3% end-to-end because ``pallas_call`` is a
fusion barrier — XLA can no longer fuse LRN with its neighboring
ReLU/pool. The ``LRN`` layer therefore defaults to the XLA path
(``impl='auto'``); this kernel stays as ``impl='pallas'`` — the
native-kernel seam where formats XLA can't express (int8 + per-block
scale, stochastic rounding) would land.

Math (cross-channel window W(c) of ``size`` channels centered at c):

    D_c = k + α · Σ_{j∈W(c)} x_j²           (fp32 in-register)
    y_c = x_c · D_c^{-β}

Backward, with u_c = dy_c · x_c · D_c^{-β-1}:

    dx_i = dy_i · D_i^{-β} − 2αβ · x_i · Σ_{c : i∈W(c)} u_c

(the reverse-window sum = matmul with the transposed band; B ≠ Bᵀ for
even window sizes).

D is recomputed in the backward kernel instead of saved: one extra
in-register window pass is far cheaper than an activation-sized HBM
round trip.

Layout: activations (B,H,W,C) are flattened to (M, C) rows; the grid
walks row-blocks with the full channel dim resident per block (C is at
most a few hundred in the LRN-era nets, well under the lane budget).
On CPU (the test rig) the kernels run in interpreter mode; numerical
equivalence against the plain-XLA path is covered by tests/test_ops.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from theanompi_tpu.ops import platform

_ROWS = 512  # rows (= B·H·W elements) per grid step; VMEM ~ ROWS·C·4B·few


def _win_sum(a: jnp.ndarray, size: int, transpose: bool = False) -> jnp.ndarray:
    """Sum over the LRN channel window along the last (lane) axis.

    Implemented as a matmul with a banded 0/1 matrix: cross-lane shifts
    are slow on the VPU's register layout, while a (rows,C)×(C,C) matmul
    rides the MXU at full rate (the band matrix is built by iota in
    registers, never touching HBM). The band is shared with the XLA
    banded-matmul path (``layers.lrn_band_matrix``) so impls can't
    diverge. ``transpose=True`` sums over the REVERSE relation
    ``{c : i ∈ W(c)}`` — needed by the backward pass; for even window
    sizes the band is asymmetric, so B and Bᵀ differ.
    """
    from theanompi_tpu.ops.layers import lrn_band_matrix

    band = lrn_band_matrix(a.shape[-1], size, a.dtype)
    if transpose:
        band = band.T
    return jnp.dot(a, band, preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, y_ref, *, size, alpha, beta, k):
    x = x_ref[...].astype(jnp.float32)
    d = k + alpha * _win_sum(x * x, size)
    y_ref[...] = (x * jnp.exp(-beta * jnp.log(d))).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, dx_ref, *, size, alpha, beta, k):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    d = k + alpha * _win_sum(x * x, size)  # recomputed, stays in VMEM
    d_mb = jnp.exp(-beta * jnp.log(d))  # D^-β
    u = dy * x * d_mb / d  # dy·x·D^(-β-1)
    dx = dy * d_mb - (2.0 * alpha * beta) * x * _win_sum(u, size, transpose=True)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _rowblock_call(name, kernel, out_dtype, size, alpha, beta, k, *arrays):
    """Run a (rows, C)-blocked kernel over flattened (M, C) activations;
    ``name`` is the kernel's name in a profile."""
    x = arrays[0]
    c = x.shape[-1]
    m = x.size // c
    flats = [a.reshape(m, c) for a in arrays]
    pad = (-m) % _ROWS
    if pad:
        flats = [jnp.pad(a, ((0, pad), (0, 0))) for a in flats]
    mp = m + pad
    spec = pl.BlockSpec((_ROWS, c), lambda i: (i, 0))
    out = pl.pallas_call(
        partial(kernel, size=size, alpha=alpha, beta=beta, k=k),
        out_shape=jax.ShapeDtypeStruct((mp, c), out_dtype),
        grid=(mp // _ROWS,),
        in_specs=[spec] * len(flats),
        out_specs=spec,
        interpret=not platform.on_tpu(),
        name=name,
    )(*flats)
    return out[:m].reshape(x.shape)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lrn(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    """Fused cross-channel LRN over the last axis of ``x`` (NHWC)."""
    return _rowblock_call("lrn_fwd", _fwd_kernel, x.dtype, size, alpha, beta, k, x)


def _lrn_fwd(x, size, alpha, beta, k):
    return lrn(x, size, alpha, beta, k), x


def _lrn_bwd(size, alpha, beta, k, x, dy):
    return (_rowblock_call("lrn_bwd", _bwd_kernel, x.dtype, size, alpha, beta, k, x, dy),)


lrn.defvjp(_lrn_fwd, _lrn_bwd)
