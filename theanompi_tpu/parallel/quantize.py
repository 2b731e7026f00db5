"""Block-quantized int8 wire format for gradient exchange.

The reference's native-kernel capability was fp16 pack/unpack CUDA
kernels that halved exchange bytes (upstream ``Exch_asa16``; SURVEY.md
§3.3 native #1).  This module goes past parity: **int8 + per-block fp32
scale**, quartering the wire vs fp32 — the modern gradient-compression
recipe (per-block max-abs scaling keeps the quantization error bounded
per 256-element block instead of per whole tensor).

Two equivalent implementations:

- :func:`quantize_blocks` / :func:`dequantize_blocks` — XLA ops; these
  fuse into the surrounding step (measured on this rig: ``pallas_call``
  is a fusion barrier, so the XLA path is the perf default).
- :func:`pallas_quantize_blocks` / :func:`pallas_dequantize_blocks` —
  explicit Pallas TPU kernels (interpret-mode on CPU), the native-tier
  seam.  Tiles are (32, lanes) so the int8 operand respects the TPU's
  (32, 128) int8 tiling (pallas_guide.md).  Passing a ``key`` selects
  the stochastic-rounding kernel, whose U[0,1) dither is a counter hash
  computed in VMEM — the XLA SR path materializes a payload-sized
  random tensor as a fusion input; the kernel never touches HBM for it.

The exchange algebra lives in ``exchanger.BSP_Exchanger`` (strategies
``int8`` / ``pallas_int8``): quantize → all_to_all (int8 shards + fp32
scales) → dequantize → fp32 shard-sum → requantize → all_gather →
dequantize.  Summation always happens in fp32 — int8 is a WIRE format
only, never an accumulator (a sum of int8 values overflows at world
size 2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from theanompi_tpu.ops import platform

BLOCK = 256  # elements per quantization block (fp32 scale each)


# ---------------------------------------------------------------------------
# XLA path
# ---------------------------------------------------------------------------

def quantize_blocks(x: jnp.ndarray, key=None):
    """(…, BLOCK) fp32 → ((…, BLOCK) int8, (…,) fp32 scales).

    ``key`` enables **stochastic rounding**: ``floor(y + U[0,1))`` is
    unbiased (``E[q·scale] = x``), unlike round-to-nearest whose
    per-element bias accumulates over thousands of gradient steps —
    the reason int8 training recipes pair block scaling with SR.
    """
    scale = jnp.max(jnp.abs(x), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    y = x / safe[..., None]
    if key is None:
        q = jnp.round(y)
    else:
        q = jnp.floor(y + jax.random.uniform(key, y.shape, jnp.float32))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_blocks(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """fp32 reconstruction; works for any wire payload dtype (int8, fp16)."""
    return q.astype(jnp.float32) * scale[..., None]


# fp16 block-scale target: amax maps to 256, keeping every block value
# in fp16's normal range — overflow-proof (fp16 max 65504) and small
# values stay normal down to ~2.4e-7 of the block amax (fp16 subnormal
# threshold 6.1e-5 / 256). A plain fp16 CAST (the reference's CUDA
# kernels, our 'fp16' strategy) can overflow to inf on large-magnitude
# gradient blocks and flush small ones to zero; the fused scale removes
# both hazards for the same wire bytes.
FP16_CAP = 256.0


def quantize_blocks_fp16(x: jnp.ndarray, key=None):
    """(…, BLOCK) fp32 → ((…, BLOCK) fp16, (…,) fp32 scales).

    Round-to-nearest only (``key`` accepted for interface compatibility,
    ignored): at 11 significand bits the rounding error floor is ~2^-11
    relative per element — three orders below int8's, and far below SGD
    gradient noise — so stochastic rounding buys nothing measurable at
    this precision."""
    scale = jnp.max(jnp.abs(x), axis=-1) / FP16_CAP
    safe = jnp.where(scale > 0, scale, 1.0)
    q = (x / safe[..., None]).astype(jnp.float16)
    return q, scale.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Pallas path (native-tier kernels)
# ---------------------------------------------------------------------------

_ROWS = 32  # int8 TPU tile: (32, 128); 32 is also a legal f32 sublane count
_LANES = 256  # = BLOCK: one quant block per row segment


def _block_scale(x, cap):
    """Per-row amax scale (keepdims) + divide-safe variant — the shared
    head of every quant kernel."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / cap
    return s, jnp.where(s > 0, s, 1.0)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]  # (_ROWS, _LANES) fp32 — one quant block per row
    s, safe = _block_scale(x, 127.0)
    q_ref[...] = jnp.round(x / safe).astype(jnp.int8)
    s_ref[...] = s.astype(jnp.float32)


def _hash_uniform(counter: jnp.ndarray) -> jnp.ndarray:
    """Counter-based U[0,1) from a uint32 lattice — lowmc-style integer
    avalanche (xor-shift/multiply mix), all VPU 32-bit int ops so it
    runs identically under Mosaic and interpret mode. Statistical grade
    is plenty for rounding dither; this is NOT a crypto or jax.random
    replacement."""
    x = counter
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # top 24 bits → exactly representable fp32 in [0, 1). Mosaic has no
    # uint32→f32 convert (first-chip-run finding, r4); after the >>8 the
    # top byte is zero, so the value is int32-exact — bitcast to i32
    # (identical bits, now non-negative) and convert from there.
    x24 = jax.lax.bitcast_convert_type(x >> 8, jnp.int32)
    return x24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _quant_sr_kernel(x_ref, seed_ref, q_ref, s_ref):
    """Stochastic-rounding variant: ``floor(y + u)`` with per-element
    dither derived in-kernel from (seed, global element index) — no
    random tensor ever crosses HBM, unlike the XLA path where the
    U[0,1) array is a full payload-sized input to the fusion.

    Bound: the global element index is a single uint32, so the dither
    sequence repeats after 2**32 elements — a leaf fused beyond ~4.3B
    elements (16 GiB fp32, beyond one chip's HBM for a gradient leaf)
    would see correlated (never biased) dither across distant rows in
    one step. Widen ``idx`` to two uint32 words if that regime ever
    becomes real."""
    i = pl.program_id(0)
    x = x_ref[...]
    s, safe = _block_scale(x, 127.0)
    y = x / safe
    row = jax.lax.broadcasted_iota(jnp.uint32, (_ROWS, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, (_ROWS, _LANES), 1)
    idx = (jnp.uint32(i * _ROWS) + row) * jnp.uint32(_LANES) + lane
    # Weyl step decorrelates the seed from the lattice before the mix
    u = _hash_uniform(idx * jnp.uint32(0x9E3779B9) + seed_ref[0, 0])
    q = jnp.floor(y + u)
    q_ref[...] = jnp.clip(q, -127, 127).astype(jnp.int8)
    s_ref[...] = s.astype(jnp.float32)


# Mosaic for v5e has no float16 vector type (compiling for a described
# v5e refuses the narrowing store with "failed to legalize operation
# 'tpu.pack_subelements'" and the load with "Invalid vector type", PR
# 21; r4 met the same on the chip).  It does have int16.  So the fp16s
# kernels do the IEEE conversion themselves in 32-bit integer ops and
# move int16 BIT PATTERNS through VMEM; the wrappers bitcast to/from
# float16 outside the kernel, where XLA handles the type fine.  The
# same code runs in interpret mode, so the CPU tests pin it bit-exact
# against XLA's own convert.

def _f32_to_f16_bits(x):
    """float32 → IEEE float16 (round-to-nearest-even) as int32 bit
    patterns in [0, 0xFFFF] — subnormal, overflow→inf and NaN included."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    sign = (b >> 16) & 0x8000
    a = b & 0x7FFFFFFF  # magnitude bits: non-negative, so >> is logical
    # normal halves: rebias the exponent (127 → 15) and round the
    # mantissa to 10 bits, ties to even; a carry out of the mantissa
    # bumps the exponent, up to 0x7C00 = inf past 65504
    norm = (a - (112 << 23) + 0xFFF + ((a >> 13) & 1)) >> 13
    # subnormal halves (|x| < 2^-14): adding 0.5 lets the fp32 adder do
    # the shift-and-round; the low bits of the sum ARE the half's bits
    half = jnp.float32(0.5)
    sub = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(a, jnp.float32) + half, jnp.int32
    ) - 0x3F000000
    big = jnp.where(a > 0x7F800000, 0x7E00, 0x7C00)  # NaN : inf
    h = jnp.where(
        a >= (143 << 23), big, jnp.where(a < (113 << 23), sub, norm)
    )
    return h | sign


def _f16_bits_to_f32(h):
    """int32 bit patterns of IEEE float16 (low 16 bits) → float32, exact."""
    h = h & 0xFFFF
    e = (h >> 10) & 0x1F
    m = h & 0x3FF
    normal = ((e + 112) << 23) | (m << 13)
    # subnormal: m · 2^-24, built by an int→float convert so no fp32
    # subnormal (which the VPU would flush) is ever formed
    sub = jax.lax.bitcast_convert_type(
        m.astype(jnp.float32) * jnp.float32(2.0 ** -24), jnp.int32
    )
    mag = jnp.where(
        e == 0, sub, jnp.where(e == 31, 0x7F800000 | (m << 13), normal)
    )
    return jax.lax.bitcast_convert_type(
        mag | ((h & 0x8000) << 16), jnp.float32
    )


def _quant_fp16_kernel(x_ref, q_ref, s_ref):
    """Fused cast+scale (the reason the fp16s Pallas tier exists — a
    cast-ONLY kernel adds nothing over XLA's own convert, which is why
    the former ``pallas_bf16`` strategy was retired): one VMEM pass
    computes the block amax, normalizes, and narrows to fp16 bits."""
    x = x_ref[...]  # (_ROWS, _LANES) fp32 — one quant block per row
    s, safe = _block_scale(x, FP16_CAP)
    q_ref[...] = _f32_to_f16_bits(x / safe).astype(jnp.int16)
    s_ref[...] = s.astype(jnp.float32)


def _dequant_fp16_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = _f16_bits_to_f32(q_ref[...].astype(jnp.int32)) * s_ref[...]


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _run_quant_kernel(x, kernel, out_dtype, seed=None):
    """Shared pallas_call scaffolding for all block-quant kernels:
    flatten (…, BLOCK) → (rows, BLOCK), tile (32, BLOCK) per grid step,
    return (payload, scales) reshaped back. ``rows`` must be a multiple
    of 32 (the exchanger pads to this)."""
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    x2 = x.reshape(rows, BLOCK)
    in_specs = [pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0))]
    args = [x2]
    if seed is not None:
        in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))
        args.append(seed)
    q2, s2 = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, BLOCK), out_dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        grid=(rows // _ROWS,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, 1), lambda i: (i, 0)),
        ),
        interpret=not platform.on_tpu(),
        name=kernel.__name__.lstrip("_"),  # quant_kernel, quant_sr_kernel, ...
    )(*args)
    return q2.reshape(*lead, BLOCK), s2.reshape(lead)


def pallas_quantize_blocks(x: jnp.ndarray, key=None):
    """Same contract as :func:`quantize_blocks` (``key`` selects the
    stochastic-rounding kernel), for (…, BLOCK) inputs whose leading
    dims multiply to a multiple of 32 (the exchanger pads to this).

    SR dither comes from an in-kernel counter hash seeded by ``key``
    (not the jax.random bit stream), so outputs are deterministic per
    key but NOT bit-identical to ``quantize_blocks(x, key)`` — both are
    valid unbiased rounding dither."""
    if key is None:
        return _run_quant_kernel(x, _quant_kernel, jnp.int8)
    seed = jax.random.bits(key, (1, 1), jnp.uint32)
    return _run_quant_kernel(x, _quant_sr_kernel, jnp.int8, seed=seed)


def pallas_quantize_blocks_fp16(x: jnp.ndarray, key=None):
    """Same contract as :func:`quantize_blocks_fp16` (``key`` ignored —
    see there), input rows padded to a multiple of 32 by the exchanger.
    The 16-bit TPU tile is (16, 128); 32 rows is a legal multiple for
    both the fp32 input and the 16-bit output.  The kernel emits int16
    bit patterns (see ``_f32_to_f16_bits``); the bitcast to float16 is
    free in XLA."""
    q, s = _run_quant_kernel(x, _quant_fp16_kernel, jnp.int16)
    return jax.lax.bitcast_convert_type(q, jnp.float16), s


def pallas_dequantize_blocks(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    kernel = _dequant_kernel
    if q.dtype == jnp.float16:
        kernel = _dequant_fp16_kernel
        q = jax.lax.bitcast_convert_type(q, jnp.int16)
    lead = q.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    q2 = q.reshape(rows, BLOCK)
    s2 = scale.reshape(rows, 1)
    grid = rows // _ROWS
    o2 = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0)),
        interpret=not platform.on_tpu(),
        name=kernel.__name__.lstrip("_"),  # dequant_kernel, dequant_fp16_kernel
    )(q2, s2)
    return o2.reshape(*lead, BLOCK)
