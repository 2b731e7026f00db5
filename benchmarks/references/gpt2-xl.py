"""Plain reference for the ``gpt2-xl`` configuration.

GPT-2 (Radford et al. 2019) at the 1558M sizes: token and learned
position embeddings, 48 pre-LayerNorm blocks (causal self-attention with
25 heads of 64, then a 4x feed-forward with tanh GELU), a final
LayerNorm and the vocabulary head.  Straightforward ``jax.numpy`` at
float32 with every contraction at ``highest`` precision, one whole
sequence at a time, no cache, no batching, no kernel; layer by layer, so
that it fits beside the weights.  Imports nothing of ``theanompi_tpu``
and takes nothing the program made: the weights come from the seed
through ``make_weights`` and the driver hands the same arrays to the
program.

Departures of the program's block from GPT-2, which this reference
follows because the configuration file states them: the query, key,
value and output projections have no bias; the head is not tied to the
embedding and has a bias.

``precision="int8"`` is the control: both operands of every matrix
product (projections, attention scores and values, feed-forward, head)
rounded to 8-bit integers on a per-tensor scale, the step below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(cfg: dict, seed: int):
    """The weights in the program's layout (a list: embedding, positions,
    the blocks, final norm, head), float32, made on the device in one
    jitted call.  GPT-2's initialisation: normal(0, 0.02) matrices, the
    two residual projections scaled by 1/sqrt(2 n_layer), positions
    normal(0, 0.01), unit norms, zero biases."""
    d, v = int(cfg["n_embd"]), int(cfg["vocab_size"])
    n, t = int(cfg["n_layer"]), int(cfg["n_positions"])
    ff = int(cfg.get("n_inner") or 4 * d)
    resid = 0.02 / math.sqrt(2 * n)

    @jax.jit
    def make(key):
        def normal(i, shape, std):
            return jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std

        def norm():
            return {"bias": jnp.zeros((d,), jnp.float32),
                    "scale": jnp.ones((d,), jnp.float32)}

        out = [{"table": normal(0, (v, d), 0.02)},
               {"pos": normal(1, (t, d), 0.01)}]
        for l in range(n):
            b = 10 * (l + 1)
            out.append({
                "attn": {"wk": normal(b + 1, (d, d), 0.02),
                         "wo": normal(b + 3, (d, d), resid),
                         "wq": normal(b, (d, d), 0.02),
                         "wv": normal(b + 2, (d, d), 0.02)},
                "ln1": norm(), "ln2": norm(),
                "mlp_in": {"b": jnp.zeros((ff,), jnp.float32),
                           "w": normal(b + 4, (d, ff), 0.02)},
                "mlp_out": {"b": jnp.zeros((d,), jnp.float32),
                            "w": normal(b + 5, (ff, d), resid)},
            })
        out.append(norm())
        out.append({"b": jnp.zeros((v,), jnp.float32),
                    "w": normal(2, (d, v), 0.02)})
        return out

    return make(jax.random.fold_in(seed_key(seed), 1))


def _int8(a):
    scale = jnp.max(jnp.abs(a)) / 127.0 + 1e-30
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _q(precision):
    return {"float32": lambda a: a, "int8": _int8}[precision]


def _ln(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def _block(bp, x, n_head, eps, precision):
    q_ = _q(precision)
    mm = lambda a, b: jnp.matmul(q_(a), q_(b), precision=HI)
    t, d = x.shape
    hd = d // n_head
    y = _ln(bp["ln1"], x, eps)
    q = mm(y, bp["attn"]["wq"]).reshape(t, n_head, hd).transpose(1, 0, 2)
    k = mm(y, bp["attn"]["wk"]).reshape(t, n_head, hd).transpose(1, 0, 2)
    v = mm(y, bp["attn"]["wv"]).reshape(t, n_head, hd).transpose(1, 0, 2)
    s = mm(q, k.transpose(0, 2, 1)) / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), v).transpose(1, 0, 2).reshape(t, d)
    x = x + mm(o, bp["attn"]["wo"])
    y = _ln(bp["ln2"], x, eps)
    h = jax.nn.gelu(mm(y, bp["mlp_in"]["w"]) + bp["mlp_in"]["b"],
                    approximate=True)
    return x + mm(h, bp["mlp_out"]["w"]) + bp["mlp_out"]["b"]


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(lnf, head, x, eps, precision):
    q_ = _q(precision)
    return jnp.matmul(q_(_ln(lnf, x, eps)), q_(head["w"]),
                      precision=HI) + head["b"]


@jax.jit
def _embed(emb, pos, tokens):
    return emb["table"][tokens] + pos["pos"][: tokens.shape[0]]


def _padded(cfg: dict, t: int) -> int:
    """Every sequence is padded to the next 256 positions: a program a
    layer for each of at most four lengths whatever the requests' own (a
    causal pass: padding changes nothing before it).  Nothing on the
    device has a request's own length for a shape, so no small program
    is built per request: slices are taken on the host."""
    return min(int(cfg["n_positions"]), 256 * -(-t // 256))


def _logits_padded(cfg: dict, weights, tokens, precision: str):
    n, eps = int(cfg["n_layer"]), float(cfg["layer_norm_epsilon"])
    toks = np.zeros((_padded(cfg, len(tokens)),), np.int32)
    toks[: len(tokens)] = tokens
    x = _embed(weights[0], weights[1], toks)
    for bp in weights[2:2 + n]:
        x = _block(bp, x, int(cfg["n_head"]), eps, precision)
    return _head(weights[2 + n], weights[3 + n], x, eps, precision)


def logits(cfg: dict, weights, tokens, precision: str = "float32"):
    """(len(tokens), vocabulary) float32: row ``i`` scores the token that
    follows ``tokens[i]``.  One sequence, one plain forward pass."""
    return np.asarray(
        _logits_padded(cfg, weights, tokens, precision))[: len(tokens)]


@jax.jit
def _gaps_below_best(rows, chosen):
    best = jnp.max(rows, axis=-1)
    return best - jnp.take_along_axis(rows, chosen[:, None], axis=-1)[:, 0]


@jax.jit
def _first(rows):
    return jnp.argmax(rows, axis=-1).astype(jnp.int32)


def served_gaps(cfg: dict, weights, prompt, served, precision="float32"):
    """For each served token, how far its float32-reference logit lies
    below the reference's best at that position (0 where the served token
    is the reference's own greedy choice).  With ``precision`` lower, the
    "served" tokens are instead the ones that precision puts first at
    each position of the same prompt and tokens (the control: it need not
    decode).  Returns the gaps as a list."""
    seq = list(prompt) + list(served)
    p, m = len(prompt), len(served)
    rows = _logits_padded(cfg, weights, seq[:-1], "float32")  # row i scores seq[i + 1]
    if precision == "float32":
        chosen = np.zeros((rows.shape[0],), np.int32)
        chosen[: len(seq) - 1] = seq[1:]
    else:
        chosen = _first(_logits_padded(cfg, weights, seq[:-1], precision))
    gaps = np.asarray(_gaps_below_best(rows, chosen))
    return gaps[p - 1 : p - 1 + m].tolist()
