"""Operations and bytes of the ``smallthinker-21b-a3b`` forward pass,
from its shapes.

Per processed token: 2 operations for every parameter of a matrix the
token multiplies: in every layer ``W_q``, ``W_k``, ``W_v``, ``W_o``
(21.0 M), the router (2,560 x 64) and the ``moe_num_active_primary_experts``
experts it picks (6 x 3 x 2,560 x 768; every expert is held here);
plus attention, ``2 x 2 x heads x head_dim`` = 14,336 operations a layer
for each position a query attends to.

Attention is counted from the one fact the driver gives, the sum of the
positions the stage's tokens attend to (a prompt of L tokens, L (L + 1)
/ 2; a decoded token, its lane's length): in full in the global layers,
and in a window layer as that sum x ``W / n_positions``.  A token at
``p`` attends to ``min(p, W)`` positions there, and ``min(p, W) >= p W
/ P`` for every ``p <= P``, so the count never passes what was done; it
reads low by what the window keeps of a short context (``PERF.md``).

The head (2,560 x 151,936) is counted for ``logit_rows`` rows only;
``readers/serve_mfu.py`` passes none.  Embedding lookups, norms,
activations and softmax count nothing.  Padding counts nothing: the
driver passes only real tokens."""

from __future__ import annotations


def _layers(cfg: dict):
    """``(global layers, window layers)`` among the layers run."""
    n = int(cfg["num_hidden_layers"])
    window = sum(1 for f in cfg["sliding_window_layout"][:n] if f)
    return n - window, window


def per_token_params(cfg: dict) -> int:
    """Parameters of the matrices one token multiplies through all the
    layers, the head apart."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv, hd = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    attention = 2 * d * h * hd + 2 * d * kv * hd
    experts = (d * int(cfg["moe_num_primary_experts"])
               + int(cfg["moe_num_active_primary_experts"])
               * 3 * d * int(cfg["moe_ffn_hidden_size"]))
    return int(cfg["num_hidden_layers"]) * (attention + experts)


def attention_flops_per_position(cfg: dict) -> float:
    """Operations of one query against one attended position of the
    driver's count, over the layers (window layers at ``W / P``)."""
    full, window = _layers(cfg)
    share = int(cfg["sliding_window_size"]) / int(cfg["n_positions"])
    per_layer = 4 * int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
    return per_layer * (full + window * min(1.0, share))


def forward_flops(cfg: dict, tokens: int, attended: int, logit_rows: int = 0) -> float:
    """``tokens`` real tokens through the whole stage, which between
    them attend to ``attended`` resident tokens, ``logit_rows`` of them
    through the head."""
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return (2 * per_token_params(cfg) * tokens
            + attention_flops_per_position(cfg) * attended
            + 2 * head * logit_rows)


def expert_pair_flops(cfg: dict) -> int:
    """Operations of ONE routed expert for one token (a token-expert
    pair of the grouped products)."""
    return 6 * int(cfg["hidden_size"]) * int(cfg["moe_ffn_hidden_size"])


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of one routed expert's three matrices."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_ffn_hidden_size"]) * itemsize


def kv_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of one token's keys and values in one layer."""
    return 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) * itemsize
