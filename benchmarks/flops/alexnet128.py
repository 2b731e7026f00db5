"""Operations and bytes of the ``alexnet128`` step, from its shapes.

Counted: the multiply-adds of the five convolutions and three dense
layers (2 operations each), forward once and backward twice (gradient
to the input and to the weights), except that the first convolution
needs no gradient to its input.  Not counted: LRN, pooling, ReLU, the
loss, the optimizer (elementwise, under 1 % of the whole), and
anything an implementation recomputes or adds (a band-matrix LRN, a
space-to-depth stem)."""

from __future__ import annotations

CONVS = ((11, 4, 96, False), (5, 1, 256, True), (3, 1, 384, False),
         (3, 1, 384, False), (3, 1, 256, True))  # kernel, stride, out, pool


def conv_flops(out_hw: int, kernel: int, cin: int, cout: int) -> int:
    """Forward operations of one SAME convolution for one image."""
    return 2 * out_hw * out_hw * cout * kernel * kernel * cin


def dense_flops(d_in: int, d_out: int) -> int:
    return 2 * d_in * d_out


def layers(cfg: dict):
    """[(name, forward operations per image, needs input gradient)]."""
    out, cin, hw = [], 3, int(cfg["image_size"])
    for i, (k, s, c, pool) in enumerate(CONVS):
        hw = -(-hw // s)
        out.append((f"conv{i + 1}", conv_flops(hw, k, cin, c), i > 0))
        cin = c
        if i in (0, 1, 4):
            hw = (hw - 3) // 2 + 1
    d = hw * hw * cin
    for name, width in (("fc6", 4096), ("fc7", 4096),
                        ("fc8", int(cfg["n_classes"]))):
        out.append((name, dense_flops(d, width), True))
        d = width
    return out


def train_flops_per_sample(cfg: dict) -> int:
    """Forward and backward operations one image needs."""
    return sum(f * (3 if dx else 2) for _, f, dx in layers(cfg))


def n_params(cfg: dict) -> int:
    from_shapes = 0
    cin, hw = 3, int(cfg["image_size"])
    for i, (k, s, c, _) in enumerate(CONVS):
        from_shapes += k * k * cin * c + c
        cin, hw = c, -(-hw // s)
        if i in (0, 1, 4):
            hw = (hw - 3) // 2 + 1
    d = hw * hw * cin
    for width in (4096, 4096, int(cfg["n_classes"])):
        from_shapes += d * width + width
        d = width
    return from_shapes
