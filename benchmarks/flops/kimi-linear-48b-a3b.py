"""Operations and bytes of the ``kimi-linear-48b-a3b`` forward pass, from
its shapes.

Per processed token: 2 operations for every parameter of a matrix the
token multiplies — in a linear layer the three projections, the two
low-rank pairs, ``W_β``, ``W_o`` and the four filter taps (39.5 M); in a
latent layer ``W_q``, ``W_kva``, ``W_kvb``, ``W_o`` (29.1 M); in the
leading dense layers the gated feed-forward (3 × 2,304 × 9,216); in an
expert layer the router (all 256 outputs), the shared expert and **the
expected held picks**: a token picks ``num_experts_per_token`` of the
router's experts and this chip computes those that fall on the
``num_experts`` it holds, 8 × 64 / 256 = 2 a token *in expectation*
(the program's own count of a call is ``pairs_routed``) — plus, in a
linear layer, ``6 · heads · K · K`` for the state's decay, delta and
read (decay 1, the key's read 2, the rank-one write 2, the query's read
2, less the one the decay shares: six a state element), plus attention
over the tokens resident before it in the **latent layers only**: ``2 ·
heads · ((nope + rope) + v)`` = 20,480 operations a latent layer for
each attended position.

The head (2,304 × 40,960) is counted for ``logit_rows`` rows only;
``readers/serve_mfu.py`` passes none, which leaves out what one row a
decoded token costs (189 M of 1,350 M operations a decoded token: the
share reads low by that).

Embedding lookups, norms, activations, the chunked form's own products
inside a chunk and softmax count nothing.  Padding counts nothing: the
driver passes only real tokens."""

from __future__ import annotations


def _kinds(cfg: dict):
    linear = set(cfg["linear_attn_config"]["kda_layers"])
    layers = int(cfg["num_hidden_layers"])
    n_linear = sum(1 for i in range(layers) if i + 1 in linear)
    return n_linear, layers - n_linear


def _linear_sizes(cfg: dict):
    lin = cfg["linear_attn_config"]
    return int(lin["num_heads"]), int(lin["head_dim"]), int(lin["short_conv_kernel_size"])


def held_picks_per_token(cfg: dict) -> float:
    """The expected picks of a token that fall on an expert held here."""
    routed = int(cfg["published"]["num_experts"])
    return int(cfg["num_experts_per_token"]) * int(cfg["num_experts"]) / routed


def per_token_params(cfg: dict) -> float:
    """Parameters of the matrices one token multiplies through all the
    layers, the head apart (the held picks in expectation)."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    lh, lk, conv = _linear_sizes(cfg)
    w = lh * lk
    kr = int(cfg["kv_lora_rank"])
    nope, rope, v = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                     int(cfg["v_head_dim"]))
    n_linear, n_latent = _kinds(cfg)
    layers, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    linear = d * 3 * w + conv * 3 * w + 2 * (d * lk + lk * w) + d * lh + w * d
    latent = (d * h * (nope + rope) + d * (kr + rope) + kr * h * (nope + v)
              + h * v * d)
    dense_ffn = 3 * d * int(cfg["intermediate_size"])
    expert = 3 * d * int(cfg["moe_intermediate_size"])
    experts = (d * int(cfg["published"]["num_experts"])
               + expert * (held_picks_per_token(cfg)
                           + int(cfg["num_shared_experts"])))
    return (n_linear * linear + n_latent * latent + dense * dense_ffn
            + (layers - dense) * experts)


def state_flops_per_token(cfg: dict) -> int:
    """Decay, delta and read of the recurrent state, all linear layers."""
    lh, lk, _ = _linear_sizes(cfg)
    return _kinds(cfg)[0] * 6 * lh * lk * lk


def attention_flops_per_position(cfg: dict) -> int:
    """Operations of one query against one resident position, over the
    latent layers (the linear ones attend to nothing)."""
    h = int(cfg["num_attention_heads"])
    width = (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
             + int(cfg["v_head_dim"]))
    return 2 * h * width * _kinds(cfg)[1]


def forward_flops(cfg: dict, tokens: int, attended: int, logit_rows: int = 0) -> float:
    """``tokens`` real tokens through the whole stage, which between
    them attend to ``attended`` resident tokens, ``logit_rows`` of them
    through the head."""
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return ((2 * per_token_params(cfg) + state_flops_per_token(cfg)) * tokens
            + attention_flops_per_position(cfg) * attended
            + 2 * head * logit_rows)


def expert_pair_flops(cfg: dict) -> int:
    """Operations of ONE routed expert for one token (a token-expert
    pair of the grouped products)."""
    return 6 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of one routed expert's three matrices."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"]) * itemsize


def latent_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a resident token holds in the latent cache over the latent
    layers (the linear ones keep no rows)."""
    return ((int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))
            * _kinds(cfg)[1] * itemsize)


def kda_state_bytes(cfg: dict) -> int:
    """Bytes of one lane's recurrent matrices over the linear layers
    (float32; the convolution's three inputs apart)."""
    lh, lk, _ = _linear_sizes(cfg)
    return _kinds(cfg)[0] * lh * lk * lk * 4


def kda_token_bytes(cfg: dict) -> int:
    """Bytes the recurrence has to move for one token over the linear
    layers: its ``q``, ``k``, ``v`` and ``g`` read and its ``o``
    written, float32."""
    lh, lk, _ = _linear_sizes(cfg)
    return _kinds(cfg)[0] * 5 * lh * lk * 4
