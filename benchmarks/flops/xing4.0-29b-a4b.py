"""Operations of the ``xing4.0-29b-a4b`` forward pass, from its shapes.

Per processed token: 2 operations for every parameter of a matrix the
token multiplies — in every layer the five attention projections
(``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``: 28.41 M) and the two
hyper-connection maps ``Φ`` (2 × 24 × 14,336); in the leading dense
layers the gated feed-forward (3 × 3,584 × 9,216); in the expert layers
the router, the ``num_experts_per_tok`` routed experts the token chose
and the shared one (5 × 3 × 3,584 × 1,024) — plus attention over the
tokens resident before it: ``2 · heads · ((nope + rope) + v)`` = 20,480
operations a layer for each attended position (scores and values, the
expanded count: the same whichever path, expanded or absorbed, computes
it; the expansion of keys and values from the latent rows and the
absorption of the queries are the path's own cost and count nothing).

The head (3,584 × 131,072) is counted for ``logit_rows`` rows only: the
program computes logits for one row a decode token and one a prompt, not
for every prompt token.  ``readers/serve_mfu.py`` knows tokens and
attended positions and no such count, so through it the head counts
nothing: at this cell's mean of 182 answer tokens to 7,590 prompt tokens
that leaves out 2 % of the operations, on the low side.

Embedding lookups, norms, the Sinkhorn iterations, SiLU and softmax
count nothing.  Padding rows and padded positions are no useful work and
count nothing: the driver passes only real tokens."""

from __future__ import annotations


def per_token_params(cfg: dict) -> int:
    """Parameters of the matrices one token multiplies through all the
    layers, the head apart."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope, v = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                     int(cfg["v_head_dim"]))
    n = int(cfg["hc_mult"])
    layers, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    attention = (d * qr + qr * h * (nope + rope) + d * (kr + rope)
                 + kr * h * (nope + v) + h * v * d)
    hyper = 2 * (2 * n + n * n) * n * d
    dense_ffn = 3 * d * int(cfg["intermediate_size"])
    experts = (d * int(cfg["n_routed_experts"])
               + 3 * d * int(cfg["moe_intermediate_size"])
               * (int(cfg["num_experts_per_tok"]) + int(cfg["n_shared_experts"])))
    return (layers * (attention + hyper) + dense * dense_ffn
            + (layers - dense) * experts)


def attention_flops_per_position(cfg: dict) -> int:
    """Operations of one query against one resident position, all layers."""
    h = int(cfg["num_attention_heads"])
    width = (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
             + int(cfg["v_head_dim"]))
    return 2 * h * width * int(cfg["num_hidden_layers"])


def forward_flops(cfg: dict, tokens: int, attended: int, logit_rows: int = 0) -> int:
    """``tokens`` real tokens through the whole stage, which between
    them attend to ``attended`` resident tokens (the sum over tokens of
    the positions each sees), ``logit_rows`` of them through the head."""
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return (2 * per_token_params(cfg) * tokens
            + attention_flops_per_position(cfg) * attended
            + 2 * head * logit_rows)


def expert_flops_per_token(cfg: dict) -> int:
    """Operations of the ROUTED experts (the grouped products) for one
    token through every expert layer."""
    layers = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    return (layers * int(cfg["num_experts_per_tok"]) * 6
            * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"]))


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of one routed expert's three matrices."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"]) * itemsize


def latent_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a resident token holds in the latent cache over all layers."""
    return ((int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))
            * int(cfg["num_hidden_layers"]) * itemsize)


def hyper_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """Bytes the two hyper-connection kernels have to move for one token
    through every sublayer: the state read twice and written once, the
    sublayer's input written and its output read."""
    n, d = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    sublayers = 2 * int(cfg["num_hidden_layers"])
    return sublayers * (3 * n * d + 2 * d) * itemsize
