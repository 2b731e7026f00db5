"""Every Pallas kernel the package ships, paired with its XLA oracle.

ONE list with three readers, so that they cannot drift apart:

- ``chip_smoke.py`` (*kernels* phase) runs every case compiled by
  Mosaic on the chip, at the ``"real"`` shapes;
- ``tests/test_tpu_kernels.py`` runs the same cases one by one — on the
  chip under ``THEANOMPI_TPU_TESTS=1``, and at the ``"tiny"`` shapes in
  interpret mode in the CPU suite;
- ``tests/test_tpu_compile.py`` compiles the ``"real"`` shapes for a
  *described* v5e without running anything.

A case is ``make_args(key) -> args`` (arrays from the seed),
``kernel(*args)`` (the Pallas path) and ``oracle(*args)`` (plain XLA,
same pytree out).  ``"real"`` shapes are what the chip paths use:
flash at T 1024 / 8 heads / head 64, paged decode at the serving knobs
of ``bench_serve.py`` (block 32, 32 lanes, 1024 rows), the wire kernels
on one 4 MB bucket, LRN and pool backward at AlexNet-128's batch-512
planes.

Precision: the MXU multiplies fp32 operands in bf16 passes by default,
in the kernel AND in the oracle but with different groupings (~3e-3 on
unit-scale data, r4 chip run).  So fp32 cases trace both sides under
``jax.default_matmul_precision("highest")`` — the setting reaches the
dots inside a kernel body too — which proves the kernel MATH to ~1e-4,
and bf16 cases run the kernel at the backend default (the training
configuration) against an fp32 oracle inside bf16's envelope.
"""

from __future__ import annotations

import contextlib
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.ops import platform


class KernelCase(NamedTuple):
    name: str
    make_args: Callable  # key -> tuple of arrays
    kernel: Callable  # the Pallas path
    oracle: Callable  # plain XLA, same output pytree
    atol: float
    rtol: float
    # each side is traced under jax.default_matmul_precision(...);
    # None = the backend default
    kernel_precision: Optional[str] = None
    oracle_precision: Optional[str] = None
    # integer payloads: at most this share of elements may differ, by 1
    # (a rounding tie resolved the other way by a 1-ulp different divide)
    off_by_one_share: float = 0.0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _flash_cases(size):
    from theanompi_tpu.ops.pallas_flash import flash_attention
    from theanompi_tpu.parallel.ring_attention import full_attention

    b, t, h, d = (2, 1024, 8, 64) if size == "real" else (2, 96, 4, 16)

    def qkv(dtype):
        def make(key):
            return tuple(
                jax.random.normal(k, (b, t, h, d), dtype)
                for k in jax.random.split(key, 3)
            )
        return make

    def f32(x):
        return x.astype(jnp.float32)

    def grads(attend):
        return jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(f32(attend(q, k, v)))),
            argnums=(0, 1, 2),
        )

    def kern(q, k, v):
        return flash_attention(q, k, v, True)

    def dense(q, k, v):  # fp32 oracle whatever the input dtype
        return full_attention(f32(q), f32(k), f32(v), causal=True)

    return [
        KernelCase("flash_fwd_f32", qkv(jnp.float32), kern, dense,
                   atol=1e-4, rtol=1e-4, kernel_precision="highest",
                   oracle_precision="highest"),
        # sum-of-squares gradients span magnitudes; atol catches the
        # near-zero elements, rtol the rest (r4 chip run)
        KernelCase("flash_bwd_f32", qkv(jnp.float32), grads(kern),
                   grads(dense), atol=5e-4, rtol=1e-3,
                   kernel_precision="highest", oracle_precision="highest"),
        KernelCase("flash_fwd_bf16", qkv(jnp.bfloat16), kern, dense,
                   atol=3e-2, rtol=3e-2, oracle_precision="highest"),
        KernelCase("flash_bwd_bf16", qkv(jnp.bfloat16), grads(kern),
                   grads(dense), atol=1e-1, rtol=5e-2,
                   oracle_precision="highest"),
    ]


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def _paged_oracle(q, kp, vp, tables, lengths, *, bs, ks=None, vs=None):
    """The XLA gather path of ``serving.paging._paged_decode_fn``:
    materialize each lane's (t_pad, H, hd) image through its block
    table from the flat ``(rows, width)`` pool, dequantize, mask rows
    past the length, softmax."""
    s, h, hd = q.shape
    rows = (tables[:, :, None] * bs + jnp.arange(bs)[None, None, :])
    rows = rows.reshape(s, -1)

    def image(pool):
        img = jnp.take(pool, rows, axis=0)[..., :h * hd]
        return img.reshape(s, -1, h, hd).astype(jnp.float32)

    kc, vc = image(kp), image(vp)
    if ks is not None:
        kc = kc * jnp.take(ks, rows, axis=0)[..., None]
        vc = vc * jnp.take(vs, rows, axis=0)[..., None]
    sc = jnp.einsum("shd,sthd->sht", q, kc) * hd ** -0.5
    mask = jnp.arange(rows.shape[1])[None, :] <= lengths[:, None]
    prob = jax.nn.softmax(jnp.where(mask[:, None, :], sc, -1e30), axis=-1)
    return jnp.einsum("sht,sthd->shd", prob, vc)


def ragged_lengths(kind: str, s: int, span: int, positions: int):
    """Lengths (S,) that put the paged decode kernels' grid at its
    edges (``pallas_paged.lane_steps``; ``span`` rows a grid step,
    ``positions`` the table's): ``edges`` an idle lane, lanes a row
    short of, at and past a step's boundary and one in the table's last
    column; ``full`` every lane full; ``one_long`` a full lane among
    short ones."""
    last = positions - 1
    if kind == "edges":
        edges = [0, span - 1, span, span + 1, last]
        out = (edges * -(-s // len(edges)))[:s]
    elif kind == "full":
        out = [last] * s
    elif kind == "one_long":
        out = [span // 2] * s
        out[s // 2] = last
    else:
        raise ValueError(f"unknown kind of lengths {kind!r}")
    return np.asarray(out, np.int32)


def _paged_cases(size):
    from theanompi_tpu.ops.pallas_paged import paged_decode_attention
    from theanompi_tpu.parallel.quantize import quantize_blocks

    s, hd, bs, nt, nb = (
        (32, 64, 32, 32, 257) if size == "real" else (3, 8, 4, 5, 10)
    )

    def flat(x):
        """(rows, H, hd) -> the pool's (rows, width): heads side by
        side, padded with zeros to a multiple of 128 lanes."""
        x = x.reshape(x.shape[0], -1)
        return jnp.pad(x, ((0, 0), (0, -x.shape[1] % 128)))

    def make(quant, h):
        def make_args(key):
            kq, kk, kv, kt, kl = jax.random.split(key, 5)
            q = jax.random.normal(kq, (s, h, hd), jnp.float32)
            kp = jax.random.normal(kk, (nb * bs, h, hd), jnp.float32)
            vp = jax.random.normal(kv, (nb * bs, h, hd), jnp.float32)
            # block 0 is the trash block; lane 0 sits at length 0 and the
            # last lane fills its table (a lane's shortest and longest
            # run of grid steps)
            tables = jax.random.randint(kt, (s, nt), 1, nb, jnp.int32)
            lengths = jax.random.randint(kl, (s,), 0, nt * bs, jnp.int32)
            lengths = lengths.at[0].set(0).at[-1].set(nt * bs - 1)
            if not quant:
                return q, flat(kp), flat(vp), tables, lengths
            (kq8, ks), (vq8, vs) = quantize_blocks(kp), quantize_blocks(vp)
            return q, flat(kq8), flat(vq8), tables, lengths, ks, vs
        return make_args

    def kernel(q, kp, vp, tables, lengths, ks=None, vs=None):
        return paged_decode_attention(
            q, kp, vp, tables, lengths, block_size=bs,
            k_scale=ks, v_scale=vs,
        )

    def oracle(q, kp, vp, tables, lengths, ks=None, vs=None):
        return _paged_oracle(q, kp, vp, tables, lengths, bs=bs, ks=ks, vs=vs)

    # 8 heads: the width is a multiple of 128 as it stands (the tiny
    # size pads 32 to 128); 25 heads of 64 are GPT-2 XL's 1,600, which
    # pads to 1,664 (tiny: 5 heads of 8, 40 to 128)
    h_wide, h_odd = (8, 25) if size == "real" else (4, 5)
    return [
        KernelCase(name, make(quant, h), kernel, oracle,
                   atol=1e-4, rtol=1e-4, kernel_precision="highest",
                   oracle_precision="highest")
        for name, quant, h in (
            ("paged_decode_f32", False, h_wide),
            ("paged_decode_int8", True, h_wide),
            ("paged_decode_f32_h25", False, h_odd),
            ("paged_decode_int8_h25", True, h_odd),
        )
    ]


# ---------------------------------------------------------------------------
# wire (block-quantization) kernels
# ---------------------------------------------------------------------------

def _wire_cases(size):
    from theanompi_tpu.parallel import quantize as Q

    # one 4 MB fp32 bucket (the exchanger's default) = 4096 blocks
    rows = (4 << 20) // 4 // Q.BLOCK if size == "real" else 64

    def x_only(key):
        # per-block magnitudes over several decades, like gradients
        kx, ks = jax.random.split(key)
        x = jax.random.normal(kx, (rows, Q.BLOCK), jnp.float32)
        return (x * jnp.exp(3.0 * jax.random.normal(ks, (rows, 1))),)

    def x_and_key(key):
        return x_only(key) + (jax.random.fold_in(key, 1),)

    def bits(q16):  # fp16 payloads compare as bit patterns
        return jax.lax.bitcast_convert_type(q16, jnp.int16)

    def fp16_pack(pack):
        def run(x):
            q, s = pack(x)
            return bits(q), s
        return run

    def packed(pack):
        # the payload is made HERE, outside the jitted unpack: inside one
        # program XLA folds convert(f32→f16→f32) away (excess precision
        # is allowed by default on the TPU) and the oracle would dequantize
        # values that never were fp16 — seen on the chip as 99 % of a
        # bucket off by up to 2^-11 (PR 21)
        return lambda key: pack(*x_only(key))

    def sr_kernel(x, key):
        q, s = Q.pallas_quantize_blocks(x, key)
        q2, _ = Q.pallas_quantize_blocks(x, key)  # deterministic per key
        q3, _ = Q.pallas_quantize_blocks(x, jax.random.fold_in(key, 7))
        return q, s, jnp.all(q == q2), jnp.any(q != q3)

    def sr_oracle(x, key):
        # the kernel's dither is its own counter hash, so its XLA oracle
        # is round-to-nearest: floor(y + u) lies within one step of it
        q, s = Q.quantize_blocks(x)
        return q, s, jnp.bool_(True), jnp.bool_(True)

    tie = 1e-4  # share of elements allowed to round the other way
    return [
        KernelCase("wire_int8_pack", x_only, Q.pallas_quantize_blocks,
                   Q.quantize_blocks, atol=0, rtol=1e-6,
                   off_by_one_share=tie),
        KernelCase("wire_int8_sr_pack", x_and_key, sr_kernel, sr_oracle,
                   atol=0, rtol=1e-6, off_by_one_share=1.0),
        KernelCase("wire_fp16s_pack", x_only,
                   fp16_pack(Q.pallas_quantize_blocks_fp16),
                   fp16_pack(Q.quantize_blocks_fp16), atol=0, rtol=1e-6,
                   off_by_one_share=tie),
        KernelCase("wire_int8_unpack", packed(Q.quantize_blocks),
                   Q.pallas_dequantize_blocks, Q.dequantize_blocks,
                   atol=0, rtol=1e-6),
        KernelCase("wire_fp16s_unpack", packed(Q.quantize_blocks_fp16),
                   Q.pallas_dequantize_blocks, Q.dequantize_blocks,
                   atol=0, rtol=1e-6),
    ]


# ---------------------------------------------------------------------------
# LRN and max-pool backward (AlexNet-128 planes)
# ---------------------------------------------------------------------------

def _lrn_cases(size):
    from theanompi_tpu.ops import layers as L

    # AlexNet-128's two LRN planes at batch 512
    shapes = (
        [(512, 32, 32, 96), (512, 15, 15, 256)] if size == "real"
        else [(4, 8, 8, 96), (2, 5, 5, 256)]
    )
    lp, lw = L.LRN(impl="pallas"), L.LRN(impl="window")

    def both(layer):
        def run(x, w):
            y, vjp = jax.vjp(lambda a: layer.apply({}, {}, a)[0], x)
            return y, vjp(w)[0]
        return run

    def make(shape):
        def make_args(key):
            kx, kw = jax.random.split(key)
            return (jax.random.normal(kx, shape, jnp.float32),
                    jax.random.normal(kw, shape, jnp.float32))
        return make_args

    return [
        KernelCase(f"lrn{i}", make(s), both(lp), both(lw),
                   atol=5e-5, rtol=5e-5)
        for i, s in enumerate(shapes, 1)
    ]


def _pool_cases(size):
    from theanompi_tpu.ops.layers import _maxpool_fwd_raw
    from theanompi_tpu.ops.pallas_pool import maxpool_bwd

    # AlexNet-128's three pool planes at batch 512; the tiny planes
    # keep one channel count over a lane tile (130 pads to two blocks)
    shapes = (
        [(512, 32, 32, 96), (512, 15, 15, 256), (512, 7, 7, 256)]
        if size == "real" else [(3, 9, 9, 5), (2, 7, 7, 130), (1, 15, 15, 3)]
    )
    win, st = (3, 3), (2, 2)

    def fwd(x):
        return _maxpool_fwd_raw(x, win, st, "VALID")

    def make(shape):
        n, h, w, c = shape

        def make_args(key):
            # tie-free by construction: within one (n, c) plane every
            # position holds a distinct value (a bijection mod a prime
            # above h·w), so select-and-scatter's first-max and the
            # kernel's equal split agree exactly
            m = next(p for p in range(h * w + 1, 2 * h * w + 3)
                     if all(p % q for q in range(2, int(p ** 0.5) + 1)))
            pos = (jnp.arange(h)[:, None] * w + jnp.arange(w)[None, :])
            v = (pos[None, :, :, None] * 7
                 + jnp.arange(c)[None, None, None, :] * 13
                 + jnp.arange(n)[:, None, None, None] * 5) % m
            x = v.astype(jnp.float32) / m - 0.5
            dy = jax.random.normal(key, fwd(x).shape, jnp.float32)
            return x, dy
        return make_args

    def kernel(x, dy):
        return maxpool_bwd(x, fwd(x), dy, win, st)

    def oracle(x, dy):
        return jax.vjp(fwd, x)[1](dy)[0]

    return [
        KernelCase(f"pool{i}_bwd", make(s), kernel, oracle,
                   atol=1e-6, rtol=1e-6)
        for i, s in enumerate(shapes, 1)
    ]


# ---------------------------------------------------------------------------
# latent decode attention, grouped expert products, hyper-connections
# ---------------------------------------------------------------------------

def _mla_cases(size):
    from theanompi_tpu.ops.pallas_paged import mla_decode_xla, mla_paged_decode

    # real: the serving widths of the latent models (32 heads, rows of
    # 512 + 64 stored 640 wide, blocks of 32, 16 a grid step): 32 lanes
    # over 2,048 positions, and the Kimi cell's 64 lanes under a
    # 560-column table, lengths of 100-9,000 (the grid's dynamic bound at
    # that width: 35 steps a lane, three or four of them resident)
    h, c, r, w, bs, g = (
        (32, 512, 64, 640, 32, 16) if size == "real" else (4, 16, 8, 128, 4, 3)
    )
    kw = dict(block_size=bs, scale=(c + r) ** -0.5)

    def make(dtype, s, nt, nb, lo, hi):
        def make_args(key):
            kq, kr, kp, kt, kl = jax.random.split(key, 5)
            q_lat = jax.random.normal(kq, (s, h, c), dtype)
            q_rope = jax.random.normal(kr, (s, h, r), dtype)
            pool = jax.random.normal(kp, (nb * bs, w), dtype)
            tables = jax.random.randint(kt, (s, nt), 1, nb, jnp.int32)
            lengths = jax.random.randint(kl, (s,), lo, hi, jnp.int32)
            lengths = lengths.at[0].set(0).at[-1].set(nt * bs - 1)
            return q_lat, q_rope, pool, tables, lengths
        return make_args

    def kernel(*args):  # several groups a lane, and a ragged last one
        return mla_paged_decode(*args, group=g, **kw)

    def oracle(q_lat, q_rope, pool, tables, lengths):
        f32 = jnp.float32
        return mla_decode_xla(q_lat.astype(f32), q_rope.astype(f32),
                              pool.astype(f32), tables, lengths, **kw)

    s, nt, nb = (32, 64, 2049) if size == "real" else (3, 7, 12)
    wide = ((64, 560, 8193, 100, 9000) if size == "real"
            else (5, 16, 12, 1, 9))
    return [
        KernelCase("mla_decode_f32", make(jnp.float32, s, nt, nb, 0, nt * bs),
                   kernel, oracle, atol=1e-4, rtol=1e-4,
                   kernel_precision="highest", oracle_precision="highest"),
        KernelCase("mla_decode_bf16",
                   make(jnp.bfloat16, s, nt, nb, 0, nt * bs), kernel, oracle,
                   atol=3e-2, rtol=3e-2, oracle_precision="highest"),
        KernelCase("mla_decode_bf16_wide_table", make(jnp.bfloat16, *wide),
                   kernel, oracle, atol=3e-2, rtol=3e-2,
                   oracle_precision="highest"),
    ]


def _gmm_cases(size):
    from theanompi_tpu.ops.pallas_gmm import grouped_mm, grouped_mm_xla

    def case(name, dtype, e, k, n, tm, n_tiles, atol, precision):
        def make_args(key):
            kx, kw, kt = jax.random.split(key, 3)
            x = jax.random.normal(kx, (n_tiles * tm, k), dtype)
            w = (jax.random.normal(kw, (e, k, n), jnp.float32)
                 * k ** -0.5).astype(dtype)
            # tiles sorted by expert, some experts absent, a tail of dead tiles
            te = jnp.sort(jax.random.randint(kt, (n_tiles,), 0, e, jnp.int32))
            return x, w, te, jnp.array([max(1, (4 * n_tiles) // 5)], jnp.int32)

        def live(out, nv):  # rows of dead tiles are never written
            rows = jnp.arange(out.shape[0])[:, None] < nv[0] * tm
            return jnp.where(rows, out, 0)

        def kernel(x, w, te, nv):
            return live(grouped_mm(x, w, te, nv, tm=tm), nv)

        def oracle(x, w, te, nv):
            f32 = jnp.float32
            return live(grouped_mm_xla(x.astype(f32), w.astype(f32), te, tm=tm), nv)

        return KernelCase(name, make_args, kernel, oracle, atol=atol, rtol=atol,
                          kernel_precision=precision, oracle_precision="highest")

    if size == "real":  # the expert widths: a decode tick's and a prefill call's tiles
        return [
            case("gmm_decode_bf16", jnp.bfloat16, 64, 3584, 1024, 16, 68, 5e-2, None),
            case("gmm_prefill_bf16", jnp.bfloat16, 64, 1024, 3584, 256, 48, 5e-2, None),
        ]
    return [
        case("gmm_decode_bf16", jnp.float32, 5, 24, 16, 8, 9, 1e-4, "highest"),
        case("gmm_prefill_bf16", jnp.float32, 5, 16, 24, 16, 6, 1e-4, "highest"),
    ]


def _mhc_cases(size):
    from theanompi_tpu.ops import pallas_mhc as M

    n, iters = 4, 20
    t, d, dtype = (256, 3584, jnp.bfloat16) if size == "real" else (24, 64, jnp.float32)
    m = 2 * n + n * n
    kw = dict(n=n, eps=1e-6, iters=iters, clamp=30.0)

    def make_args(key):
        kx, kp, kb, ky = jax.random.split(key, 4)
        x = jax.random.normal(kx, (t, n * d), dtype)
        phi = (0.02 * jax.random.normal(kp, (m, n * d))).astype(dtype)
        bias = jax.random.normal(kb, (m,)).at[2 * n:].add(3.0 * jnp.eye(n).reshape(-1))
        # one token at the clamp's extremes
        bias_hot = bias.at[2 * n].set(40.0).at[2 * n + 5].set(-40.0)
        phi_t, ab = M.pack_coefficients(phi, jnp.full((3,), 0.1), bias_hot, n)
        y = jax.random.normal(ky, (t, d), dtype)
        return x, phi_t, ab, y

    def both(pre, post):
        def run(x, phi_t, ab, y):
            u, coef = pre(x, phi_t, ab, **kw)
            return u, coef, post(x, y, coef, n=n)
        return run

    tol = 3e-2 if size == "real" else 1e-5
    return [
        KernelCase("mhc_pre_post", make_args, both(M.mhc_pre, M.mhc_post),
                   both(M.mhc_pre_xla, M.mhc_post_xla), atol=tol, rtol=tol,
                   kernel_precision=None if size == "real" else "highest",
                   oracle_precision="highest"),
    ]


# ---------------------------------------------------------------------------
# Kimi delta attention: the recurrent step and the chunked scan
# ---------------------------------------------------------------------------

def _kda_cases(size):
    from theanompi_tpu.ops import kda

    # real: the benchmark's stage (64 lanes of 32 heads; one lane's
    # 2,048-token chunk)
    n, h, t = (64, 32, 2048) if size == "real" else (3, 2, 128)
    d = 128

    def draw(key, *lead):
        """Activated and normed as ``KdaMixer.convolve`` leaves them, the
        decay over the configuration's range."""
        ks = jax.random.split(key, 5)

        def l2(z):
            return z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)

        q, k, v = (jax.nn.silu(jax.random.normal(kk, (*lead, h, d)))
                   for kk in ks[:3])
        g = -jax.nn.softplus(
            jax.random.uniform(ks[3], (*lead, h, d), minval=-7.0, maxval=-2.0))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (*lead, h)))
        return l2(q) * d ** -0.5, l2(k), v, g, beta

    def decode_args(key):
        k0, k1 = jax.random.split(key)
        return (0.1 * jax.random.normal(k0, (n, h, d, d)), *draw(k1, n))

    def chunk_args(key):
        k0, k1 = jax.random.split(key)
        return (0.1 * jax.random.normal(k0, (1, h, d, d)), *draw(k1, 1, t))

    return [
        KernelCase("kda_decode", decode_args, kda.kda_decode,
                   kda.kda_step_xla, atol=1e-5, rtol=1e-5,
                   oracle_precision="highest"),
        # the inverse and the triangles are shared (XLA, at `highest` by
        # their own argument); the kernel's four products ask for it too
        KernelCase("kda_chunk_prefill", chunk_args, kda.kda_chunk_prefill,
                   kda.kda_chunk_xla, atol=1e-4, rtol=1e-4,
                   oracle_precision="highest"),
    ]


_FAMILIES = (_flash_cases, _paged_cases, _wire_cases, _lrn_cases, _pool_cases,
             _mla_cases, _gmm_cases, _mhc_cases, _kda_cases)


def cases(size: str = "real"):
    """All cases at ``size`` ('real' = the chip shapes, 'tiny' = the CPU
    suite's interpret-mode shapes) — the same names either way."""
    if size not in ("real", "tiny"):
        raise ValueError(f"size must be 'real' or 'tiny', got {size!r}")
    return [c for family in _FAMILIES for c in family(size)]


# ---------------------------------------------------------------------------
# running one case
# ---------------------------------------------------------------------------

def matmul_precision(name: Optional[str]):
    """``jax.default_matmul_precision(name)``, or nothing for None."""
    return (
        jax.default_matmul_precision(name) if name is not None
        else contextlib.nullcontext()
    )


def check_case(case: KernelCase, seed: int = 0) -> dict:
    """Run ``case`` against its oracle; raises AssertionError on any
    disagreement.  On a TPU the kernel must reach the compiler as a
    Mosaic custom call (``tpu_custom_call`` in the lowered text) — an
    interpreted or reference-substituted kernel fails here."""
    args = case.make_args(jax.random.PRNGKey(seed))
    compiled = platform.on_tpu()
    with matmul_precision(case.kernel_precision):
        jitted = jax.jit(case.kernel)
        if compiled:
            assert "tpu_custom_call" in jitted.lower(*args).as_text(), (
                f"{case.name}: no tpu_custom_call in the lowered text — "
                "the kernel did not reach Mosaic"
            )
        got = jax.block_until_ready(jitted(*args))
    with matmul_precision(case.oracle_precision):
        want = jax.jit(case.oracle)(*args)
    # compared ON the device: a case's outputs run to hundreds of MB,
    # and only these few scalars need to cross to the host
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert g.shape == w.shape, (case.name, g.shape, w.shape)
        if jnp.issubdtype(g.dtype, jnp.integer):
            diff = jnp.abs(g.astype(jnp.int32) - w.astype(jnp.int32))
            off = int(diff.max())
            assert off <= (1 if case.off_by_one_share else 0), (
                f"{case.name}: integer payload off by {off}")
            share = float(jnp.mean(diff != 0))
            assert share <= case.off_by_one_share, (
                f"{case.name}: {share:.2e} of the payload differs "
                f"(allowed {case.off_by_one_share:.0e})")
        elif g.dtype == jnp.bool_:
            assert bool(jnp.all(g == w)), f"{case.name}: flag {g} != {w}"
        else:
            # a bf16 kernel output meets its fp32 oracle in fp32
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            assert bool(jnp.isfinite(g).all()), (
                f"{case.name}: non-finite output")
            err = jnp.abs(g - w)
            over = int(jnp.sum(err > case.atol + case.rtol * jnp.abs(w)))
            assert over == 0, (
                f"{case.name}: {over} of {g.size} elements outside "
                f"atol={case.atol} rtol={case.rtol}; max abs error "
                f"{float(err.max()):.3g}")
            worst = max(worst, float(err.max()))
    return {"case": case.name, "compiled": compiled, "max_abs_err": worst}
