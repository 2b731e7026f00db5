"""Operations of the ``gpt2-xl`` forward pass, from its shapes.

Per token: 2 operations for every parameter of a matrix the token goes
through (the four attention projections and the two feed-forward
matrices of each layer, and the head), plus attention over the tokens
resident before it: 2 x 2 x n_embd operations a layer for each (scores
and values).  Embedding lookups, norms, GELU and softmax count nothing.
Padding rows and padded positions are no useful work and count nothing:
the driver passes only real tokens."""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, n = int(cfg["n_embd"]), int(cfg["n_layer"])
    ff = int(cfg.get("n_inner") or 4 * d)
    return n * (4 * d * d + 2 * d * ff) + d * int(cfg["vocab_size"])


def forward_flops(cfg: dict, tokens: int, attended: int) -> int:
    """``tokens`` real tokens through the whole model, which between
    them attend to ``attended`` resident tokens (the sum over tokens of
    the positions each sees)."""
    d, n = int(cfg["n_embd"]), int(cfg["n_layer"])
    return 2 * matmul_params(cfg) * tokens + 4 * d * n * attended
