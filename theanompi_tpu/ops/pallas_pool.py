"""Single-pass Pallas TPU kernel for the max-pool backward.

XLA lowers the max-pool VJP to ``select-and-scatter`` — a sequential
window scan measured at ~7% of the AlexNet-128 step (docs/perf/NOTES.md
op budget, select-and-scatter.{1,2}).  The pure-XLA alternative
(``layers._maxpool_mask_bwd``) measured 2.2× slower END-TO-END because
its kh·kw interior-padded overlap-adds at distinct offsets cannot fuse:
each one is a full input-sized HBM read-modify-write plus stride-2
slice relayouts (the r5 layout diagnosis in NOTES.md).

This kernel runs the SAME shifted-mask math but entirely in VMEM per
(batch, channel) block: one HBM read of x, one of (y, dy) at output
resolution, one HBM write of dx.  Each window offset (di, dj) is one
STRIDED view of the VMEM-resident plane — ``ref[:, ds(di, oh, sh),
ds(dj, ow, sw), :]`` — read to compare against ``y`` and read-modify-
written to scatter the cotangent.  H is an untiled leading dim (a
stride there is address arithmetic), W rides the sublanes (Mosaic's
``strided_load``/``strided_store``), C rides the lanes untouched.  The
compare is an exact fp32 equality, so no precision knob is involved.

What Mosaic demands of that shape (found by compiling for a described
v5e, PR 21 — the earlier band-matmul formulation never finished
compiling): strided access needs a 32-bit plane whose lane dim is one
128-lane tile, so x is widened into an fp32 VMEM scratch first and the
grid walks channels in blocks of 128 (C ≤ 128 is one block; a wider C
that is not a multiple of 128 is zero-padded up to one).  The plane
itself is never split: a block holds the FULL (h, w) extent, so no
halo exchange is needed — and a plane too large for the budget is an
error here (:func:`plane_fits_vmem`), not a Mosaic refusal.

Tie semantics match ``_maxpool_mask_bwd``: the cotangent is split
EQUALLY across tied window maxima (select-and-scatter routes to the
first max; both are valid subgradients, the equal split conserves
per-window cotangent mass and keeps the kernel order-free).  VALID
padding only, like the mask path.

On CPU (the test rig) the kernel runs in interpreter mode; numerical
equivalence against the native backward is covered by tests/test_ops.py.
Reference analog: the maxpool gradient op of the reference's
``theanompi/models/layers2.py`` pool layer (cuDNN there; SURVEY.md
§3.5) — re-designed as a TPU kernel rather than translated.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops import platform

_LANES = 128  # channel block: strided VMEM access wants one lane tile
# VMEM the blocks of one grid step may take, out of the 16 MiB a kernel
# is scoped to by default on v5e.  Per image of a block, in fp32-plane
# units: the two fp32 scratch planes plus the double-buffered x and dx
# blocks (counted at 4 B even when they are bf16 — headroom for the
# (y, dy, count) values at output resolution).
_VMEM_BUDGET = 8 << 20
_PLANES_PER_IMAGE = 6


def _image_bytes(h: int, w: int) -> int:
    """VMEM one image of a block takes: ``_PLANES_PER_IMAGE`` fp32
    (h, w, 128-lane) planes, W padded to the 8-sublane tile."""
    return _PLANES_PER_IMAGE * h * (-(-w // 8) * 8) * _LANES * 4


def plane_fits_vmem(h: int, w: int) -> bool:
    """Whether one (h, w) spatial plane fits the kernel's VMEM budget.
    The grid walks batch and channels only, so even a one-image block
    keeps the whole plane resident (ADVICE r5 item 1; in-repo pools at
    128 px are <= 32x32 and comfortably inside)."""
    return _image_bytes(h, w) <= _VMEM_BUDGET


def _pool_bwd_kernel(x_ref, y_ref, dy_ref, dx_ref, xf_ref, acc_ref,
                     *, window, stride):
    kh, kw = window
    sh, sw = stride
    oh, ow = y_ref.shape[1:3]
    xf_ref[...] = x_ref[...].astype(jnp.float32)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    y = y_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    # VALID pooling: every (di, dj) tap of every window is in bounds
    taps = [
        (slice(None), pl.ds(di, oh, stride=sh), pl.ds(dj, ow, stride=sw),
         slice(None))
        for di in range(kh) for dj in range(kw)
    ]
    # pass 1: ties per window, for the mass-conserving equal split
    cnt = jnp.zeros(y.shape, jnp.float32)
    for tap in taps:
        cnt = cnt + (xf_ref[tap] == y).astype(jnp.float32)
    dyc = dy / cnt  # every window has >= 1 max
    # pass 2: scatter-add through the same strided views
    for tap in taps:
        acc_ref[tap] = acc_ref[tap] + jnp.where(xf_ref[tap] == y, dyc, 0.0)
    dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def maxpool_bwd(x, y, dy, window, stride) -> jnp.ndarray:
    """dx for a VALID max pool, via the (batch, channel)-blocked kernel."""
    n, h, w, c = x.shape
    oh, ow = y.shape[1:3]
    if not plane_fits_vmem(h, w):
        raise ValueError(
            f"maxpool_bwd: a {h}x{w} spatial plane needs "
            f"{_image_bytes(h, w)} bytes of VMEM per "
            f"image, over the kernel's budget ({_VMEM_BUDGET}); the grid "
            "blocks over batch and channels only, so a plane this large "
            "cannot be VMEM-resident — use grad_impl='native' for this "
            "pool"
        )
    cb = c if c <= _LANES else _LANES
    # clamp to n: without it a small batch pads UP to the budget
    nb = max(1, min(n, _VMEM_BUDGET // _image_bytes(h, w)))
    pad_n, pad_c = (-n) % nb, (-c) % cb
    if pad_n or pad_c:
        # padded rows/channels: y=0 matches x=0 at every tap and dy=0,
        # so their dx is exactly 0 — no masking needed
        zx = ((0, pad_n), (0, 0), (0, 0), (0, pad_c))
        x, y, dy = (jnp.pad(a, zx) for a in (x, y, dy))
    np_, cp = n + pad_n, c + pad_c
    spec_x = pl.BlockSpec((nb, h, w, cb), lambda i, j: (i, 0, 0, j))
    spec_y = pl.BlockSpec((nb, oh, ow, cb), lambda i, j: (i, 0, 0, j))
    out = pl.pallas_call(
        partial(_pool_bwd_kernel, window=window, stride=stride),
        out_shape=jax.ShapeDtypeStruct((np_, h, w, cp), x.dtype),
        grid=(np_ // nb, cp // cb),
        in_specs=[spec_x, spec_y, spec_y],
        out_specs=spec_x,
        scratch_shapes=[
            pltpu.VMEM((nb, h, w, cb), jnp.float32),  # x widened to fp32
            pltpu.VMEM((nb, h, w, cb), jnp.float32),  # dx accumulator
        ],
        interpret=not platform.on_tpu(),
        name="maxpool_bwd",
    )(x, y, dy)
    return out[:n, :, :, :c]


def _require_valid(padding):
    # guard HERE, not only in the MaxPool constructor: a direct call
    # with SAME would run the SAME forward while the backward's offset
    # filter silently drops padded-region window taps — wrong dx, no
    # error (review r5)
    if padding != "VALID":
        raise ValueError(
            f"maxpool_pallas supports VALID padding only, got {padding!r}"
        )


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def maxpool_pallas(x, window, stride, padding):
    """MaxPool whose backward is the single-pass Pallas kernel (forward
    stays XLA's reduce_window — it fuses fine)."""
    from theanompi_tpu.ops.layers import _maxpool_fwd_raw

    _require_valid(padding)
    return _maxpool_fwd_raw(x, window, stride, padding)


def _fwd(x, window, stride, padding):
    from theanompi_tpu.ops.layers import _maxpool_fwd_raw

    _require_valid(padding)
    y = _maxpool_fwd_raw(x, window, stride, padding)
    return y, (x, y)


def _bwd(window, stride, padding, res, dy):
    x, y = res
    return (maxpool_bwd(x, y, dy, window, stride).astype(x.dtype),)


maxpool_pallas.defvjp(_fwd, _bwd)
