"""Functional layer library.

Re-creation of the reference's layer lib (upstream
``theanompi/models/layers2.py``: ``Weight``, ``Conv``, ``Pool``, ``LRN``,
``FC``, ``Dropout``, ``Softmax`` classes wrapping Theano ops; SURVEY.md
§3.5) — redesigned for JAX:

- Layers are **stateless descriptor objects** (hyperparameters only).
  Trainable variables live in a separate ``params`` pytree, non-trainable
  state (BatchNorm running stats) in a ``state`` pytree, so optimizers and
  exchangers operate on pure pytrees — the TPU analog of the reference's
  list of Theano shared variables (``model.params``).
- Contract: ``init(key, in_shape) -> (params, state, out_shape)`` and
  ``apply(params, state, x, train=False, rng=None) -> (y, new_state)``.
  ``in_shape``/``out_shape`` exclude the batch dimension.
- Layout is NHWC (TPU-native).
- Mixed precision: with ``compute_dtype=bfloat16`` activations FLOW in
  bf16 between layers (halves HBM traffic — the usual TPU bottleneck);
  master params stay fp32 and statistics (BatchNorm moments, global
  pooling) are computed in fp32 inside the fused op. Dense matmuls
  request fp32 accumulation explicitly (``preferred_element_type``);
  convs rely on the TPU MXU's native fp32 accumulation of bf16 inputs
  (the conv VJP rejects a widened output dtype, see ``Conv2d.apply``).
  Pass ``output_dtype=float32`` on a final logits layer to leave mixed
  precision at the head.
- There is no ``Weight`` save/load here: checkpointing serializes whole
  pytrees (``theanompi_tpu.utils.checkpoint``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops import platform

Params = Any
State = Any
Shape = Tuple[int, ...]


def static_bool(flag, what: str = "flag") -> bool:
    """Coerce a mode flag to a trace-time-static Python bool.

    Layers whose train/eval branch changes the COLLECTIVE sequence
    (sync-BatchNorm's pmean pair) must take the branch identically on
    every worker, which is only guaranteed when the flag is a concrete
    host value baked into the trace.  A traced value gets a targeted
    TypeError here — at the call site, naming the flag — instead of a
    TracerBoolConversionError from somewhere inside the layer (or, if
    it ever reached ``shard_map`` per-worker, a silent hang).
    """
    if isinstance(flag, jax.core.Tracer):
        raise TypeError(
            f"{what} must be a trace-time-static Python bool, got a "
            f"traced value ({type(flag).__name__}) — pass a concrete "
            "True/False (mark the argument static under jit)"
        )
    return bool(flag)


# ---------------------------------------------------------------------------
# initializers (the reference's `Weight` init modes)
# ---------------------------------------------------------------------------

def he_normal(key, shape, fan_in, dtype=jnp.float32):
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(key, shape, dtype) * std


def xavier_uniform(key, shape, fan_in, fan_out, dtype=jnp.float32):
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -limit, limit)


def normal_init(std):
    def f(key, shape, fan_in, dtype=jnp.float32):
        return jax.random.normal(key, shape, dtype) * std

    return f


# ---------------------------------------------------------------------------
# base
# ---------------------------------------------------------------------------

class Layer:
    """Descriptor base. Subclasses override init/apply."""

    _scope: Optional[str] = None  # see ``named``

    def named(self, name: str) -> "Layer":
        """Name this layer's operations in a profile: ``Sequential``
        applies it under ``jax.named_scope(name)`` (metadata only; the
        backward pass inherits it).  Returns ``self`` for chaining."""
        self._scope = name
        return self

    def init(self, key, in_shape: Shape):
        return {}, {}, in_shape

    def apply(self, params, state, x, train: bool = False, rng=None):
        return x, state

    def __repr__(self):
        fields = ", ".join(
            f"{k}={v!r}" for k, v in vars(self).items() if not k.startswith("_")
        )
        return f"{type(self).__name__}({fields})"


def _explicit_padding(padding, kernel, stride, hw):
    """Resolve a padding spec to explicit ((lo,hi),(lo,hi)) pairs.
    SAME uses XLA's convention: lo = total//2 (hi gets the odd pixel)."""
    if isinstance(padding, str):
        if padding.upper() == "SAME":
            pads = []
            for d in range(2):
                out = -(-hw[d] // stride[d])
                total = max(0, (out - 1) * stride[d] + kernel[d] - hw[d])
                pads.append((total // 2, total - total // 2))
            return tuple(pads)
        if padding.upper() == "VALID":
            return ((0, 0), (0, 0))
        # loud, not VALID-by-default: lax accepts strings this helper
        # doesn't model (SAME_LOWER), and silently computing VALID for
        # them would make the s2d path diverge from the plain conv
        raise ValueError(f"unsupported padding spec {padding!r}")
    return tuple((int(p[0]), int(p[1])) for p in padding)


def _conv_s2d(x, w, stride, padding):
    """Strided conv via space-to-depth: fold the (bh, bw) stride into
    channels so the MXU sees a stride-1 conv with a bh·bw·Cin contraction.

    Why: a stem like AlexNet's 11×11/stride-4 over 3 channels runs the
    MXU at ~27% efficiency (contraction dim 3, pad-heavy strided im2col —
    measured in docs/perf/trace_r2). Folding gives contraction dim 48 and
    no stride. The canonical HWIO kernel stays the parameter (checkpoint-
    and init-compatible); it is zero-front-padded so every tap lands at a
    fixed (block, phase) pair, then reshaped to blocks — tap u maps to
    block (u+f)//b, phase (u+f)%b with f ≡ -pad_lo (mod b), so the padded
    taps are zeros and the result is the SAME dot products re-ordered.
    """
    bh, bw = stride
    n, h, wid, cin = x.shape
    kh, kw, _, cout = w.shape
    if h % bh or wid % bw:
        raise ValueError(
            f"s2d conv needs input {h}x{wid} divisible by stride {stride}"
        )
    pads = _explicit_padding(padding, (kh, kw), stride, (h, wid))
    f = ((-pads[0][0]) % bh, (-pads[1][0]) % bw)  # kernel front zeros
    kbh, kbw = -(-(kh + f[0]) // bh), -(-(kw + f[1]) // bw)  # kernel blocks
    wp = jnp.pad(
        w,
        (
            (f[0], kbh * bh - kh - f[0]),
            (f[1], kbw * bw - kw - f[1]),
            (0, 0),
            (0, 0),
        ),
    )
    # (kbh, bh, kbw, bw, cin, cout) -> blocks spatial, phases into channels;
    # channel order (phase_h, phase_w, cin) must match the input fold below
    wp = wp.reshape(kbh, bh, kbw, bw, cin, cout)
    wp = wp.transpose(0, 2, 1, 3, 4, 5).reshape(kbh, kbw, bh * bw * cin, cout)
    xs = x.reshape(n, h // bh, bh, wid // bw, bw, cin)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // bh, wid // bw, bh * bw * cin)
    blo = ((pads[0][0] + f[0]) // bh, (pads[1][0] + f[1]) // bw)
    # hi-side block pad chosen so the stride-1 block conv yields exactly
    # the plain conv's output count (may be negative = trim, which XLA
    # supports); over-covered padding pixels multiply the kernel's zero
    # back-padding, under-coverage cannot happen (padding is zeros on
    # both sides of the equivalence)
    oh, ow = _conv_out_hw((h, wid), (kh, kw), (bh, bw), pads)
    bhi = (oh + kbh - 1 - blo[0] - h // bh, ow + kbw - 1 - blo[1] - wid // bw)
    return lax.conv_general_dilated(
        xs,
        wp,
        window_strides=(1, 1),
        padding=((blo[0], bhi[0]), (blo[1], bhi[1])),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


class Conv2d(Layer):
    """2-D convolution, NHWC / HWIO, fp32 MXU accumulation.

    Reference analog: ``Conv`` in layers2.py (cuDNN NCHW). NHWC is the
    TPU-preferred layout; ``compute_dtype=bfloat16`` casts inputs/weights
    for the MXU while keeping master params fp32.

    ``s2d=True`` computes the strided conv through space-to-depth
    (``_conv_s2d``) — same parameters, same math, MXU-friendly layout for
    few-channel strided stems. Requires stride > 1 dividing the input.
    """

    def __init__(
        self,
        filters: int,
        kernel: Tuple[int, int] | int,
        stride: Tuple[int, int] | int = 1,
        padding: str | Sequence[Tuple[int, int]] = "SAME",
        use_bias: bool = True,
        w_init: Optional[Callable] = None,
        compute_dtype: Optional[jnp.dtype] = None,
        output_dtype: Optional[jnp.dtype] = None,
        s2d: bool = False,
    ):
        self.filters = filters
        self.kernel = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        self.padding = padding
        self.use_bias = use_bias
        self.w_init = w_init or he_normal
        self.compute_dtype = compute_dtype
        self.output_dtype = output_dtype
        if s2d and (self.stride[0] < 2 and self.stride[1] < 2):
            raise ValueError("s2d=True only makes sense for strided convs")
        self.s2d = s2d

    def init(self, key, in_shape):
        h, w, cin = in_shape
        kh, kw = self.kernel
        if self.s2d and (h % self.stride[0] or w % self.stride[1]):
            # refuse at init where the architecture mistake is visible,
            # not at jit trace time (same convention as MaxPool.init)
            raise ValueError(
                f"s2d conv needs input {h}x{w} divisible by stride "
                f"{self.stride}"
            )
        fan_in = kh * kw * cin
        wkey, _ = jax.random.split(key)
        params = {"w": self.w_init(wkey, (kh, kw, cin, self.filters), fan_in)}
        if self.use_bias:
            params["b"] = jnp.zeros((self.filters,), jnp.float32)
        out_h, out_w = _conv_out_hw((h, w), self.kernel, self.stride, self.padding)
        return params, {}, (out_h, out_w, self.filters)

    def apply(self, params, state, x, train=False, rng=None):
        x, w, narrow_to = _conv_operand_dtypes(
            x, params["w"], self.compute_dtype
        )
        if self.s2d:
            y = _conv_s2d(x, w, self.stride, self.padding)
        else:
            y = lax.conv_general_dilated(
                x,
                w,
                window_strides=self.stride,
                padding=self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        if narrow_to is not None:
            y = y.astype(narrow_to)
        if self.output_dtype is not None:
            y = y.astype(self.output_dtype)
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y, state


def _conv_operand_dtypes(x, w, compute_dtype):
    """Pick conv operand dtypes for the current backend.

    On TPU, narrow (bf16) operands are the right call: the MXU
    accumulates in fp32 in hardware and the narrow activation halves HBM
    traffic.  (``preferred_element_type=fp32`` is not used because a
    widened conv output makes the VJP's cotangent dtype mismatch its
    bf16 operands, which ``lax.conv`` rejects.)  On other backends a
    narrow conv accumulates in the operand dtype — silently degrading
    deep nets like VGG16/ResNet50 — so there we keep fp32 operands and
    narrow the *output* instead: same activation dtype flows downstream,
    accumulation stays fp32.

    Returns ``(x, w, narrow_to)`` where ``narrow_to`` is a dtype to cast
    the conv result to, or None."""
    if compute_dtype is None:
        return x, w, None
    if platform.on_tpu():
        return x.astype(compute_dtype), w.astype(compute_dtype), None
    return x.astype(jnp.float32), w.astype(jnp.float32), compute_dtype


class Dense(Layer):
    """Fully-connected layer (reference ``FC``)."""

    def __init__(
        self,
        features: int,
        use_bias: bool = True,
        w_init: Optional[Callable] = None,
        compute_dtype: Optional[jnp.dtype] = None,
        output_dtype: Optional[jnp.dtype] = None,
    ):
        self.features = features
        self.use_bias = use_bias
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.output_dtype = output_dtype

    def init(self, key, in_shape):
        # acts on the last dim; leading per-example dims (e.g. the
        # transformer's sequence axis) pass through untouched
        d = in_shape[-1]
        init = self.w_init or (
            lambda k, s, fi, dtype=jnp.float32: xavier_uniform(
                k, s, fi, self.features, dtype
            )
        )
        params = {"w": init(key, (d, self.features), d)}
        if self.use_bias:
            params["b"] = jnp.zeros((self.features,), jnp.float32)
        return params, {}, (*in_shape[:-1], self.features)

    def apply(self, params, state, x, train=False, rng=None):
        w = params["w"]
        out_dtype = self.output_dtype
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            w = w.astype(self.compute_dtype)
            if out_dtype is None:
                out_dtype = self.compute_dtype
        # fp32 MXU accumulation regardless of operand dtype; the result is
        # then narrowed to the flowing activation dtype (or kept fp32 for
        # a logits head via output_dtype=float32)
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        if out_dtype is not None:
            y = y.astype(out_dtype)
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y, state


def _maxpool_fwd_raw(x, window, stride, padding):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, *window, 1), (1, *stride, 1), padding
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _maxpool_mask(x, window, stride, padding):
    """MaxPool whose BACKWARD avoids XLA's ``select-and-scatter`` (a
    measured ~5-8% of the AlexNet step on v5e — sequential window scan
    that doesn't fuse). Instead: for each of the kh·kw window offsets,
    compare the strided input slice against the pooled max and
    interior-pad the masked cotangent back onto the input grid — kh·kw
    elementwise ops XLA fuses into neighboring work.

    Tie semantics differ deliberately: select-and-scatter routes the
    cotangent to the FIRST max per window; this SPLITS it equally
    across tied maxima (both are valid subgradients; equal split keeps
    the per-window cotangent mass exactly conserved). VALID padding
    only.
    """
    return _maxpool_fwd_raw(x, window, stride, padding)


def _maxpool_mask_fwd(x, window, stride, padding):
    y = _maxpool_fwd_raw(x, window, stride, padding)
    return y, (x, y)


def _maxpool_mask_bwd(window, stride, padding, res, dy):
    x, y = res
    kh, kw = window
    sh, sw = stride
    n, h, w, c = x.shape
    oh, ow = y.shape[1:3]
    dy = dy.astype(jnp.float32)
    dx = jnp.zeros(x.shape, jnp.float32)
    span_h = (oh - 1) * sh + 1
    span_w = (ow - 1) * sw + 1

    def window_slices():
        for di in range(kh):
            for dj in range(kw):
                if di + span_h > h or dj + span_w > w:
                    continue  # offset falls off the (VALID) input entirely
                xs = lax.slice(
                    x,
                    (0, di, dj, 0),
                    (n, di + span_h, dj + span_w, c),
                    (1, sh, sw, 1),
                )  # (n, oh, ow, c): input sample each window reads at (di,dj)
                yield di, dj, xs

    # pass 1: ties per window, so the split conserves cotangent mass
    cnt = jnp.zeros(y.shape, jnp.float32)
    for _, _, xs in window_slices():
        cnt = cnt + (xs == y).astype(jnp.float32)
    dy = dy / cnt  # every window has >= 1 max, cnt >= 1
    for di, dj, xs in window_slices():
        contrib = jnp.where(xs == y, dy, 0.0)
        # scatter back = interior-dilate by the stride, offset by (di,dj);
        # dilated length along H is exactly span_h = (oh-1)·sh + 1, so
        # lo=di / hi=h-di-span_h reconstructs h
        dx = dx + lax.pad(
            contrib,
            jnp.float32(0),
            (
                (0, 0, 0),
                (di, h - di - span_h, sh - 1),
                (dj, w - dj - span_w, sw - 1),
                (0, 0, 0),
            ),
        )
    return (dx.astype(x.dtype),)


_maxpool_mask.defvjp(_maxpool_mask_fwd, _maxpool_mask_bwd)


class MaxPool(Layer):
    """Max pooling. ``grad_impl``: 'native' = XLA select-and-scatter
    backward; 'mask' = the fused shifted-mask backward (VALID only; see
    ``_maxpool_mask``); 'pallas' = the single-pass VMEM-resident kernel
    backward (VALID only; see ``ops.pallas_pool`` — the r5 answer to the
    mask path's unfusable overlap-add)."""

    def __init__(self, window=2, stride=None, padding="VALID", grad_impl="native"):
        self.window = (window, window) if isinstance(window, int) else tuple(window)
        stride = stride if stride is not None else self.window
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        self.padding = padding
        if grad_impl not in ("native", "mask", "pallas"):
            raise ValueError(
                f"grad_impl must be native|mask|pallas, got {grad_impl!r}"
            )
        if grad_impl in ("mask", "pallas") and padding != "VALID":
            raise ValueError(
                f"grad_impl={grad_impl!r} supports VALID padding only"
            )
        self.grad_impl = grad_impl

    def init(self, key, in_shape):
        h, w, c = in_shape
        oh, ow = _conv_out_hw((h, w), self.window, self.stride, self.padding)
        if oh <= 0 or ow <= 0:
            # a zero-size feature map silently trains on biases alone in
            # the native path and crashes the mask backward — refuse at
            # init where the architecture mistake is visible
            raise ValueError(
                f"MaxPool window {self.window} on {h}x{w} input produces "
                f"an empty {oh}x{ow} output — input image too small for "
                "this architecture"
            )
        return {}, {}, (oh, ow, c)

    def apply(self, params, state, x, train=False, rng=None):
        if self.grad_impl == "mask":
            return _maxpool_mask(x, self.window, self.stride, self.padding), state
        if self.grad_impl == "pallas":
            from theanompi_tpu.ops.pallas_pool import maxpool_pallas

            # a plane past the kernel's VMEM budget raises in its
            # backward (pallas_pool.maxpool_bwd) — never a silent swap
            # to the native path
            return maxpool_pallas(x, self.window, self.stride, self.padding), state
        return _maxpool_fwd_raw(x, self.window, self.stride, self.padding), state


class AvgPool(Layer):
    def __init__(self, window=2, stride=None, padding="VALID"):
        self.window = (window, window) if isinstance(window, int) else tuple(window)
        stride = stride if stride is not None else self.window
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        self.padding = padding

    def init(self, key, in_shape):
        h, w, c = in_shape
        oh, ow = _conv_out_hw((h, w), self.window, self.stride, self.padding)
        return {}, {}, (oh, ow, c)

    def apply(self, params, state, x, train=False, rng=None):
        ones = jnp.ones_like(x)
        s = lax.reduce_window(
            x, 0.0, lax.add, (1, *self.window, 1), (1, *self.stride, 1), self.padding
        )
        n = lax.reduce_window(
            ones, 0.0, lax.add, (1, *self.window, 1), (1, *self.stride, 1), self.padding
        )
        return s / n, state


class GlobalAvgPool(Layer):
    def init(self, key, in_shape):
        h, w, c = in_shape
        return {}, {}, (c,)

    def apply(self, params, state, x, train=False, rng=None):
        # fp32 accumulation for the spatial mean (49+ bf16 adds would drift)
        return jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(x.dtype), state


def lrn_band_matrix(c: int, size: int, dtype) -> jnp.ndarray:
    """(C, C) 0/1 matrix B with B[j, c] = 1 iff source channel j lies in
    the LRN window of output channel c: ``j - c ∈ [-size//2, size-1-size//2]``
    (matches the pad + reduce_window baseline for even AND odd sizes).
    Built from iotas in registers — shared by the XLA banded-matmul path
    and the Pallas kernel so the two cannot diverge."""
    pad = size // 2
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)  # source channel j
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)  # output channel
    d = row - col
    return ((d >= -pad) & (d <= size - 1 - pad)).astype(dtype)


class LRN(Layer):
    """Local response normalization (AlexNet/GoogLeNet-era; reference
    ``LRN`` layer). Cross-channel normalization in NHWC.

    ``impl`` (all numerically equivalent; tests check this):

    - ``'auto'`` (= ``'xla'``): banded-matmul window sum — the C-channel
      window sum is a (…,C)×(C,C) contraction with a 0/1 band matrix, so
      it rides the MXU and XLA fuses square/power/divide around it.
      Fastest measured path on v5e: 44.7k vs 39.7k (reduce_window chain)
      vs 38.5k (standalone Pallas kernel) AlexNet-128 img/s.
    - ``'pallas'``: fused Pallas TPU kernel (``ops.pallas_lrn``, one HBM
      read + one write for fwd AND bwd) — wins in isolation, loses
      in-model because ``pallas_call`` is a fusion barrier; kept as the
      seam for wire formats XLA can't express.
    - ``'window'``: the literal pad+reduce_window chain (the reference's
      op-for-op shape, kept as the numeric baseline).
    """

    def __init__(self, size=5, alpha=1e-4, beta=0.75, k=1.0, impl="auto",
                 remat=False, stats_dtype=None):
        if impl not in ("auto", "xla", "pallas", "window", "shift"):
            raise ValueError(
                f"impl must be auto|xla|pallas|window|shift, got {impl!r}"
            )
        if impl == "pallas" and (remat or stats_dtype):
            # the Pallas kernel path returns before _normalize, so these
            # knobs would be silently discarded — refuse loudly instead
            raise ValueError(
                "impl='pallas' supports neither remat nor stats_dtype"
            )
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.impl = impl
        # remat: recompute the window sum in the backward pass instead of
        # saving the fp32 denominator activation — trades a second cheap
        # window sum for a [N,H,W,C] fp32 HBM round-trip
        self.remat = remat
        # stats_dtype (e.g. bf16): narrow the window sum AFTER its fp32
        # accumulation, so the power/divide chain AND the autodiff
        # residuals that cross the fwd/bwd boundary are narrow — the r2
        # trace shows the saved f32 [N,H,W,C] denominator is a top-10 HBM
        # cost of the AlexNet step. Denominator relative error is ~bf16
        # eps (0.4%), amplified by ~beta; fp32 (None) stays the default.
        self.stats_dtype = jnp.dtype(stats_dtype) if stats_dtype else None

    def apply(self, params, state, x, train=False, rng=None):
        if self.impl == "pallas":
            from theanompi_tpu.ops.pallas_lrn import lrn as pallas_lrn

            return (
                pallas_lrn(x, self.size, float(self.alpha), float(self.beta),
                           float(self.k)),
                state,
            )
        fn = self._normalize
        if self.remat:
            fn = jax.checkpoint(fn)
        return fn(x), state

    def _normalize(self, x):
        pad = self.size // 2
        if self.impl == "window":
            # literal pad + reduce_window chain (numeric baseline)
            sq = jnp.square(x)
            sq = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (pad, self.size - 1 - pad)))
            win = lax.reduce_window(
                sq, 0.0, lax.add, (1, 1, 1, self.size), (1, 1, 1, 1), "VALID"
            )
        elif self.impl == "shift":
            # explicit shifted adds along the lane (channel) axis: O(size)
            # elementwise work instead of the O(C) MXU contraction — the
            # window sum becomes size slices + adds that XLA fuses into
            # the surrounding square/power/divide chain
            sq = jnp.square(x.astype(jnp.float32))
            sq = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (pad, self.size - 1 - pad)))
            c = x.shape[-1]
            win = sq[..., :c]
            for i in range(1, self.size):
                win = win + sq[..., i : i + c]
        else:
            # banded-matmul window sum: rides the MXU with fp32
            # accumulation, and XLA fuses the square into the contraction
            # input and power/divide into its epilogue
            band = lrn_band_matrix(x.shape[-1], self.size, x.dtype)
            win = jnp.einsum(
                "bhwc,cd->bhwd", jnp.square(x), band,
                preferred_element_type=jnp.float32,
            )
        if self.stats_dtype is not None:
            win = win.astype(self.stats_dtype)
            denom = jnp.power(
                jnp.asarray(self.k, win.dtype) + jnp.asarray(self.alpha, win.dtype) * win,
                jnp.asarray(self.beta, win.dtype),
            )
            return (x.astype(denom.dtype) / denom).astype(x.dtype)
        denom = jnp.power(self.k + self.alpha * win, self.beta)
        return (x.astype(jnp.float32) / denom).astype(x.dtype)


class BatchNorm(Layer):
    """Batch normalization with running statistics in ``state``.

    Per-shard statistics by default (matches per-GPU BN in reference-era
    data parallelism). ``axis_name`` enables cross-replica sync-BN via
    ``lax.pmean`` when applied inside ``shard_map``.
    """

    def __init__(
        self,
        momentum=0.9,
        eps=1e-5,
        axis_name: Optional[str] = None,
        scale_init: float = 1.0,
    ):
        self.momentum = momentum
        self.eps = eps
        self.axis_name = axis_name
        # scale_init=0 is the "zero-gamma" residual trick: a freshly-init
        # deep ResNet starts as (near-)identity, keeping early gradients
        # bounded through dozens of stacked blocks
        self.scale_init = scale_init

    def init(self, key, in_shape):
        c = in_shape[-1]
        params = {
            "scale": jnp.full((c,), self.scale_init, jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
        }
        state = {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}
        return params, state, in_shape

    def apply(self, params, state, x, train=False, rng=None):
        # The branch below changes the COLLECTIVE sequence (sync-BN
        # issues a pmean pair in train mode only), so the flag must be
        # a trace-time constant, identical on every worker — never a
        # traced value that could steer workers into different arms
        # (graftlint GL-C002).  static_bool proves that: it rejects
        # tracers with a targeted TypeError instead of letting jit's
        # TracerBoolConversionError surface from deep inside the step.
        training = static_bool(train, "BatchNorm 'train'")
        reduce_axes = tuple(range(x.ndim - 1))
        xf = x.astype(jnp.float32)  # fp32 moments even for bf16 activations
        if training:
            mean = jnp.mean(xf, axis=reduce_axes)
            var = jnp.mean(jnp.square(xf), axis=reduce_axes) - jnp.square(mean)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                var = lax.pmean(var, self.axis_name)
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean,
                "var": m * state["var"] + (1 - m) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        y = (xf - mean) * inv * params["scale"] + params["bias"]
        return y.astype(x.dtype), new_state


class Dropout(Layer):
    """Inverted dropout (reference ``Dropout``). Needs an rng in train."""

    def __init__(self, rate=0.5):
        self.rate = rate

    def apply(self, params, state, x, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError("Dropout in train mode requires an rng key")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0), state


class Activation(Layer):
    def __init__(self, fn: Callable = jax.nn.relu):
        self.fn = fn

    def apply(self, params, state, x, train=False, rng=None):
        return self.fn(x), state


def Relu():
    return Activation(jax.nn.relu)


class Reshape(Layer):
    """Reshape the per-example feature shape (batch dim untouched)."""

    def __init__(self, shape: Shape):
        self.shape = tuple(shape)

    def init(self, key, in_shape):
        import numpy as _np

        if int(_np.prod(in_shape)) != int(_np.prod(self.shape)):
            raise ValueError(f"cannot reshape {in_shape} -> {self.shape}")
        return {}, {}, self.shape

    def apply(self, params, state, x, train=False, rng=None):
        return x.reshape(x.shape[0], *self.shape), state


class Flatten(Layer):
    def init(self, key, in_shape):
        return {}, {}, (int(jnp.prod(jnp.array(in_shape))),)

    def apply(self, params, state, x, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


# ---------------------------------------------------------------------------
# combinators
# ---------------------------------------------------------------------------

class Sequential(Layer):
    """Chain of layers; threads params/state lists and splits dropout rngs."""

    def __init__(self, layers: Sequence[Layer]):
        self.layers = list(layers)

    def init(self, key, in_shape):
        params, state = [], []
        shape = in_shape
        for layer in self.layers:
            key, sub = jax.random.split(key)
            p, s, shape = layer.init(sub, shape)
            params.append(p)
            state.append(s)
        return params, state, shape

    def apply(self, params, state, x, train=False, rng=None):
        new_state = []
        for i, layer in enumerate(self.layers):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            scope = layer._scope or f"{type(layer).__name__.lower()}{i}"
            with jax.named_scope(scope):
                x, s = layer.apply(
                    params[i], state[i], x, train=train, rng=sub)
            new_state.append(s)
        return x, new_state


class Parallel(Layer):
    """Apply branches to the same input, concat outputs on channels.

    The inception-block combinator (GoogLeNet's reference implementation
    builds these by hand in Theano; SURVEY.md §3.5).
    """

    def __init__(self, branches: Sequence[Layer]):
        self.branches = list(branches)

    def init(self, key, in_shape):
        params, state, out_shapes = [], [], []
        for br in self.branches:
            key, sub = jax.random.split(key)
            p, s, o = br.init(sub, in_shape)
            params.append(p)
            state.append(s)
            out_shapes.append(o)
        base = out_shapes[0][:-1]
        for o in out_shapes:
            if o[:-1] != base:
                raise ValueError(f"branch spatial shapes differ: {out_shapes}")
        c = sum(o[-1] for o in out_shapes)
        return params, state, (*base, c)

    def apply(self, params, state, x, train=False, rng=None):
        ys, new_state = [], []
        for i, br in enumerate(self.branches):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            y, s = br.apply(params[i], state[i], x, train=train, rng=sub)
            ys.append(y)
            new_state.append(s)
        return jnp.concatenate(ys, axis=-1), new_state


class Remat(Layer):
    """Gradient checkpointing (rematerialization) around ``inner``.

    The backward pass recomputes ``inner``'s forward instead of saving
    its internal activations — the standard HBM-for-FLOPs trade that
    makes long-context transformer training fit (activation memory per
    block drops from O(layers) tensors to the block boundary only).
    Thin wrapper over ``jax.checkpoint``; composes with the sp/tp
    collectives inside the block (they replay in the recompute).
    """

    def __init__(self, inner: Layer):
        self.inner = inner

    def init(self, key, in_shape):
        return self.inner.init(key, in_shape)

    def apply(self, params, state, x, train=False, rng=None):
        def fn(p, xx):
            return self.inner.apply(p, state, xx, train=train, rng=rng)

        return jax.checkpoint(fn)(params, x)


class AuxTapped(Layer):
    """Sequential trunk with auxiliary classifier heads tapped off
    intermediate outputs (GoogLeNet's aux classifiers — the reference
    builds the two heads by hand off inception 4a/4d; SURVEY.md §3.5).

    ``segments`` run in sequence; ``aux_heads[i]`` (if not None) is
    applied to segment i's output. In train mode ``apply`` returns
    ``(main_out, [aux_out, ...])``; in eval mode just ``main_out`` —
    the heads exist only to inject gradient mid-trunk, so inference
    never pays for them. Models using this override ``loss_and_metrics``
    to weight the aux losses (classically 0.3×).
    """

    def __init__(self, segments: Sequence[Layer], aux_heads: Sequence[Optional[Layer]]):
        if len(aux_heads) != len(segments):
            raise ValueError(
                f"aux_heads must align with segments: "
                f"{len(aux_heads)} vs {len(segments)}"
            )
        self.segments = list(segments)
        self.aux_heads = list(aux_heads)

    def init(self, key, in_shape):
        seg_params, seg_state, aux_params, aux_state = [], [], [], []
        shape = in_shape
        for seg, aux in zip(self.segments, self.aux_heads):
            key, sub = jax.random.split(key)
            p, s, shape = seg.init(sub, shape)
            seg_params.append(p)
            seg_state.append(s)
            if aux is None:
                aux_params.append({})
                aux_state.append({})
            else:
                key, sub = jax.random.split(key)
                ap, as_, _ = aux.init(sub, shape)
                aux_params.append(ap)
                aux_state.append(as_)
        params = {"trunk": seg_params, "aux": aux_params}
        state = {"trunk": seg_state, "aux": aux_state}
        return params, state, shape

    def apply(self, params, state, x, train=False, rng=None):
        new_trunk, new_aux, aux_outs = [], [], []
        for i, (seg, aux) in enumerate(zip(self.segments, self.aux_heads)):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            x, s = seg.apply(
                params["trunk"][i], state["trunk"][i], x, train=train, rng=sub
            )
            new_trunk.append(s)
            if aux is not None and train:
                asub = None
                if rng is not None:
                    rng, asub = jax.random.split(rng)
                y, as_ = aux.apply(
                    params["aux"][i], state["aux"][i], x, train=train, rng=asub
                )
                aux_outs.append(y)
                new_aux.append(as_)
            else:
                # eval: heads untouched; their state passes through
                new_aux.append(state["aux"][i])
        new_state = {"trunk": new_trunk, "aux": new_aux}
        if train:
            return (x, aux_outs), new_state
        return x, new_state


class Residual(Layer):
    """Residual connection: ``y = body(x) + shortcut(x)``.

    The ResNet/Wide-ResNet combinator (the reference's Lasagne model zoo
    builds these with Lasagne ElemwiseSumLayer; SURVEY.md §3.5).
    ``shortcut=None`` is identity; pass a projection (1×1 conv, possibly
    strided) when shapes change.
    """

    def __init__(self, body: Layer, shortcut: Optional[Layer] = None):
        self.body = body
        self.shortcut = shortcut

    def init(self, key, in_shape):
        k1, k2 = jax.random.split(key)
        bp, bs, out_shape = self.body.init(k1, in_shape)
        if self.shortcut is not None:
            sp, ss, s_out = self.shortcut.init(k2, in_shape)
            if s_out != out_shape:
                raise ValueError(
                    f"shortcut out {s_out} != body out {out_shape}"
                )
        else:
            if out_shape != in_shape:
                raise ValueError(
                    f"identity shortcut needs body out {out_shape} == in {in_shape}"
                )
            sp, ss = {}, {}
        return {"body": bp, "shortcut": sp}, {"body": bs, "shortcut": ss}, out_shape

    def apply(self, params, state, x, train=False, rng=None):
        r1 = r2 = None
        if rng is not None:
            rng, r1 = jax.random.split(rng)
            rng, r2 = jax.random.split(rng)
        y, new_bs = self.body.apply(
            params["body"], state["body"], x, train=train, rng=r1
        )
        if self.shortcut is not None:
            sc, new_ss = self.shortcut.apply(
                params["shortcut"], state["shortcut"], x, train=train, rng=r2
            )
        else:
            sc, new_ss = x, state["shortcut"]
        return y + sc, {"body": new_bs, "shortcut": new_ss}


class ConvTranspose2d(Layer):
    """Transposed convolution (the LS-GAN generator's upsampling op)."""

    def __init__(
        self,
        filters: int,
        kernel: Tuple[int, int] | int,
        stride: Tuple[int, int] | int = 2,
        padding: str = "SAME",
        use_bias: bool = True,
        w_init: Optional[Callable] = None,
        compute_dtype: Optional[jnp.dtype] = None,
        output_dtype: Optional[jnp.dtype] = None,
    ):
        self.filters = filters
        self.kernel = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        self.padding = padding
        self.use_bias = use_bias
        self.w_init = w_init or he_normal
        self.compute_dtype = compute_dtype
        self.output_dtype = output_dtype

    def init(self, key, in_shape):
        h, w, cin = in_shape
        kh, kw = self.kernel
        params = {"w": self.w_init(key, (kh, kw, cin, self.filters), kh * kw * cin)}
        if self.use_bias:
            params["b"] = jnp.zeros((self.filters,), jnp.float32)
        if self.padding.upper() == "SAME":
            oh, ow = h * self.stride[0], w * self.stride[1]
        else:
            oh = (h - 1) * self.stride[0] + kh
            ow = (w - 1) * self.stride[1] + kw
        return params, {}, (oh, ow, self.filters)

    def apply(self, params, state, x, train=False, rng=None):
        x, w, narrow_to = _conv_operand_dtypes(
            x, params["w"], self.compute_dtype
        )
        y = lax.conv_transpose(
            x,
            w,
            strides=self.stride,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if narrow_to is not None:
            y = y.astype(narrow_to)
        if self.output_dtype is not None:
            y = y.astype(self.output_dtype)
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y, state


# ---------------------------------------------------------------------------

def _conv_out_hw(hw, window, stride, padding):
    # delegate string resolution to _explicit_padding so an unmodeled
    # spec (SAME_LOWER) is refused HERE, at init time, instead of
    # init reporting a silently-VALID shape that apply then contradicts
    h, w = hw
    pads = _explicit_padding(padding, window, stride, hw)
    oh = (h + pads[0][0] + pads[0][1] - window[0]) // stride[0] + 1
    ow = (w + pads[1][0] + pads[1][1] - window[1]) // stride[1] + 1
    return oh, ow


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
