"""Plain reference for the ``alexnet128`` configuration.

AlexNet (Krizhevsky et al. 2012), single tower as the paper's framework
ran it (arXiv:1605.08325), at 128 px: forward, mean softmax
cross-entropy, backward and momentum SGD with L2 weight decay, in
straightforward ``jax.numpy`` at float32 with every contraction at
``highest`` precision.  No kernel, no mesh, no program code: this file
imports nothing of ``theanompi_tpu`` and takes nothing the program made.
The weights and the batches come from the seed through the functions
below, and the driver hands the *same* arrays to the program.

Departures from the publication, also listed in the configuration file:
dropout is off (its masks come from the program's own key stream, which
an independent reference cannot draw), the input is 128 px and not
224/227, the data are seeded noise.

``precision="int8"`` is the control of "How correct is decided": the
same arithmetic with both operands of every convolution and matrix
product, and the gradient that comes into each in the backward pass,
rounded to 8-bit integers on a per-tensor scale (the step below the
bfloat16 the configuration states).  ``rows`` plants the batch
faults: the reference then sees only that slice of every batch and takes
its mean over it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST

# (name, kernel, stride, filters) in order; pools and LRNs are fixed by
# the architecture and applied in forward() below
CONVS = (
    ("conv1", 11, 4, 96),
    ("conv2", 5, 1, 256),
    ("conv3", 3, 1, 384),
    ("conv4", 3, 1, 384),
    ("conv5", 3, 1, 256),
)
DENSE = ("fc6", "fc7", "fc8")


def layer_shapes(cfg: dict):
    """[(name, weight shape, fan_in, fan_out)] at the configuration's
    sizes; biases are (shape[-1],)."""
    out = []
    cin, hw = 3, int(cfg["image_size"])
    for name, k, s, c in CONVS:
        out.append((name, (k, k, cin, c), k * k * cin, c))
        cin = c
        hw = -(-hw // s)  # SAME
        if name in ("conv1", "conv2", "conv5"):
            hw = (hw - 3) // 2 + 1  # 3x3 stride-2 VALID pool
    d = hw * hw * cin
    for name, width in zip(DENSE, (4096, 4096, int(cfg["n_classes"]))):
        out.append((name, (d, width), d, width))
        d = width
    return out


def seed_key(seed: int):
    """Any whole number (the driver's seeds pass 2**31) to one key."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def make_weights(cfg: dict, seed: int):
    """``[{"w", "b"}, ...]`` in layer order, float32, made on the device in
    one jitted call: He-normal convolutions, Xavier-uniform dense layers,
    zero biases (the initialisers the program's layers default to)."""
    shapes = layer_shapes(cfg)

    @jax.jit
    def make(key):
        layers = []
        for i, (name, shape, fan_in, fan_out) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            if len(shape) == 4:
                w = jax.random.normal(k, shape, jnp.float32) * math.sqrt(
                    2.0 / fan_in
                )
            else:
                lim = math.sqrt(6.0 / (fan_in + fan_out))
                w = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
            layers.append({"w": w, "b": jnp.zeros((shape[-1],), jnp.float32)})
        return layers

    return make(jax.random.fold_in(seed_key(seed), 1))


def batches_fn(cfg: dict, rows: int, n: int):
    """``f(key) -> [(images, labels)] * n``, batches of ``rows``.  One
    draw of 8-bit pixels, brought to zero mean and unit variance in
    float32 (what a loader hands the step); batch ``i`` is that draw
    rolled by ``i`` pixels down and ``2 i`` across, with labels of its
    own, so all rows of all batches differ and batch ``i`` is the same
    whatever ``n`` is.  Every element depends on the key and its position
    alone, so a sharded call and a one-device call agree.  (One draw and
    not ``n``: the generator's work is most of what making a batch costs,
    and every run pays it.)"""
    size, classes = int(cfg["image_size"]), int(cfg["n_classes"])
    std = math.sqrt((256.0**2 - 1.0) / 12.0)

    def make(key):
        kx, ky = jax.random.split(key)
        px = jax.random.bits(kx, (rows, size, size, 3), jnp.uint8)
        base = (px.astype(jnp.float32) - 127.5) / std
        out = []
        for i in range(n):
            y = jax.random.randint(jax.random.fold_in(ky, i), (rows,), 0,
                                   classes, jnp.int32)
            out.append((jnp.roll(base, (i, 2 * i), axis=(1, 2)), y))
        return out

    return make


def data_key(seed: int):
    return jax.random.fold_in(seed_key(seed), 2)


# ---------------------------------------------------------------------------
# the network
# ---------------------------------------------------------------------------

def _int8(a):
    """Round to 255 levels on a per-tensor scale."""
    scale = jnp.max(jnp.abs(a)) / 127.0 + 1e-30
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _lowered(f, q):
    """``f(x, w)``, a convolution or a matrix product, as a step in a
    lower precision computes it: both operands rounded by ``q`` in the
    forward pass, and in the backward pass the incoming gradient rounded
    too, so that all three products of a training step are low."""

    @jax.custom_vjp
    def g(x, w):
        return f(q(x), q(w))

    def fwd(x, w):
        return jax.vjp(f, q(x), q(w))

    def bwd(vjp, ct):
        return vjp(q(ct))

    g.defvjp(fwd, bwd)
    return g


def _lrn(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    lo = size // 2
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (lo, size - 1 - lo)))
    c = x.shape[-1]
    win = sum(sq[..., i : i + c] for i in range(size))
    return x / jnp.power(k + alpha * win, beta)


def _pool(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "VALID"
    )


def forward(layers, x, precision: str = "float32"):
    def conv(stride):
        return lambda a, w: lax.conv_general_dilated(
            a, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        )

    def dot(a, w):
        return jnp.dot(a, w, precision=HI)

    if precision != "float32":
        q = {"int8": _int8}[precision]
        plain_conv, plain_dot = conv, dot
        conv = lambda stride: _lowered(plain_conv(stride), q)
        dot = _lowered(plain_dot, q)
    for (name, _, stride, _), p in zip(CONVS, layers):
        x = jax.nn.relu(conv(stride)(x, p["w"]) + p["b"])
        if name in ("conv1", "conv2"):
            x = _lrn(x)
        if name in ("conv1", "conv2", "conv5"):
            x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    for i, p in enumerate(layers[len(CONVS):]):
        x = dot(x, p["w"]) + p["b"]
        if i < 2:
            x = jax.nn.relu(x)
    return x


def _loss_sum(layers, x, y, precision):
    logp = jax.nn.log_softmax(forward(layers, x, precision), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, y[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("precision",))
def _block_grad(layers, x, y, precision):
    return jax.value_and_grad(_loss_sum)(layers, x, y, precision)


def loss_and_grad(layers, x, y, precision="float32", block=256):
    """Mean loss and its gradient over the batch, in blocks of rows so
    that the float32 activations fit beside whatever else is resident."""
    n = x.shape[0]
    if n % block:
        block = n
    xs = x.reshape(n // block, block, *x.shape[1:])
    ys = y.reshape(n // block, block)
    total, grads = 0.0, None
    for i in range(n // block):
        loss, g = _block_grad(layers, xs[i], ys[i], precision)
        total = total + loss
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / n, jax.tree.map(lambda g: g / n, grads)


@jax.jit
def _sgd(layers, grads, velocity, lr, momentum, weight_decay):
    def upd(p, g, v):
        v = momentum * v - lr * (g + weight_decay * p)
        return p + v, v

    out = jax.tree.map(upd, layers, grads, velocity)
    is_pair = lambda t: isinstance(t, tuple)
    return (
        jax.tree.map(lambda t: t[0], out, is_leaf=is_pair),
        jax.tree.map(lambda t: t[1], out, is_leaf=is_pair),
    )


def _norms(tree):
    return [float(jnp.sqrt(jnp.sum(jnp.square(a))))
            for a in jax.tree.leaves(tree)]


def first_steps(cfg: dict, seed: int, global_batch: int, lr: float,
                steps: int = 3, precision: str = "float32", rows=None,
                block: int = 256, frozen: bool = False):
    """Follow the first ``steps`` training steps from the seed.

    Returns ``losses`` (one per step), ``grad_norms`` (per leaf, of the
    first step's gradient as the optimizer gets it, before weight decay)
    and ``change_norms`` (per leaf, of parameters after the last step
    minus the initial ones), leaves in ``jax.tree.leaves`` order of
    ``make_weights``.  ``rows=(lo, hi)`` is the planted fault: only that
    slice of every batch is seen; ``frozen`` is another: every step
    returns its state unchanged.  ``first_grads`` are the first step's
    gradient leaves themselves, left on the device."""
    layers = make_weights(cfg, seed)
    start = layers
    velocity = jax.tree.map(jnp.zeros_like, layers)
    batches = jax.jit(batches_fn(cfg, global_batch, steps))(data_key(seed))
    losses, grad_norms, first_grads = [], None, None
    for i in range(steps):
        x, y = batches[i]
        batches[i] = None
        if rows is not None:
            x, y = x[rows[0] : rows[1]], y[rows[0] : rows[1]]
        loss, grads = loss_and_grad(layers, x, y, precision, block)
        del x, y
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms, first_grads = _norms(grads), jax.tree.leaves(grads)
        if frozen:
            continue
        layers, velocity = _sgd(
            layers, grads, velocity, jnp.float32(lr),
            jnp.float32(cfg["momentum"]), jnp.float32(cfg["weight_decay"]),
        )
    change = jax.tree.map(jnp.subtract, layers, start)
    return dict(losses=losses, grad_norms=grad_norms,
                change_norms=_norms(change), first_grads=first_grads)
