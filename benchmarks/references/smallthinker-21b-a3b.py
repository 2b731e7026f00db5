"""Plain reference for the ``smallthinker-21b-a3b`` configuration.

The forward pass of ``PowerInfer/SmallThinker-21BA3B-Instruct`` from the
keys of its ``config.json``, in straightforward ``jax.numpy`` at float32
with every contraction at ``highest`` precision: one whole sequence at a
time, no cache, no ring, no batching, no kernel; the keys and values
repeated to every query head, the queries a block of ``Q_BLOCK`` at a
time against the whole sequence with the window as a mask, a loop over
the experts.  Imports nothing of ``theanompi_tpu`` and takes nothing the
program made: the weights come from the seed through ``make_weights``
(bfloat16 leaves in the program's tree, which the driver hands to the
program as they are) and are upcast here one layer, and inside the
expert loop one expert, at a time.

The equations (``x`` the residual, one stream; sizes by their config
keys):

- block: ``a = norm(x)·g₁``; ``x ← x + Attn(a)``; ``h = norm(x)·g₂``;
  ``x ← x + MoE(h; route on a)``; norm ``x / sqrt(mean(x²) +
  rms_norm_eps)``;
- attention: ``num_attention_heads`` query heads and
  ``num_key_value_heads`` key/value heads of ``head_dim``, query head
  ``i`` reading key/value head ``i // (heads / kv heads)``, no biases,
  scale ``head_dim^-1/2``.  Layer ``l`` (0-based) takes rotary
  positions where ``rope_layout[l]`` is 1 (rotate-half: the pairs ``(z_i,
  z_{i + hd/2})`` turned by ``p · rope_theta^(−2i/hd)``), none where it
  is 0; its query at ``p`` sees the keys at ``p − sliding_window_size <
  j ≤ p`` where ``sliding_window_layout[l]`` is 1, every ``j ≤ p`` where
  it is 0;
- experts: logits ``a W_r`` over ``moe_num_primary_experts``; the
  ``moe_num_active_primary_experts`` largest; weights the softmax of the
  logits renormalised over the chosen (``norm_topk_prob``); an expert is
  ``W_down(relu(h W_gate) ⊙ (h W_up))`` (ReGLU); no shared expert;
- head: ``norm(x) W_head`` over ``vocab_size`` ids.

``precision="int8"`` is the control: both operands of every matrix
product rounded to 8-bit integers on a per-tensor scale, the step below
the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


def seed_key(seed: int):
    """The key of a seed (any whole number up to a little over 2**31);
    ``rbg`` keys: the chip's own generator."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def sized(cfg: dict) -> dict:
    """The configuration with the sizes a run really has.  They are the
    published keys; the harness's CPU rehearsal of a serve cell shrinks
    a model through the keys of ``program_config`` it knows (``d_model``,
    ``n_heads``, ``n_layers``), so where those are given they win over
    ``hidden_size``, ``num_attention_heads`` and ``num_hidden_layers``
    (in the configuration's file they agree), and the per-layer lists
    are cut to the layers run; every other key as published."""
    pc = cfg.get("program_config", {})
    out = dict(cfg)
    for ours, theirs in (("hidden_size", "d_model"),
                         ("num_attention_heads", "n_heads"),
                         ("num_hidden_layers", "n_layers")):
        out[ours] = int(pc.get(theirs, cfg[ours]))
    n = out["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        out[key] = list(cfg[key])[:n]
    return out


def leaf_dtype(cfg: dict):
    """bfloat16, what the configuration states; a model that a rehearsal
    shrank gets the same bfloat16 values in float32 leaves (on the CPU at
    toy sizes the check then holds the served tokens to the mathematics
    and not to bfloat16's ties)."""
    shrunk = any(sized(cfg)[k] != int(cfg[k]) for k in
                 ("hidden_size", "num_attention_heads", "num_hidden_layers"))
    return jnp.float32 if shrunk else jnp.bfloat16


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    """Values of bfloat16 whatever the leaf's dtype."""
    draw = jax.random.normal(key, shape, jnp.float32) * std
    return draw.astype(jnp.bfloat16).astype(dtype)


def make_weights(cfg: dict, seed: int):
    """The weights in the program's layout (a list: embedding, the
    blocks, final norm, head), bfloat16 (``leaf_dtype``), made on the
    device one leaf a call: matrices N(0, 0.02²), norms 1."""
    dtype = leaf_dtype(cfg)
    cfg = sized(cfg)
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    h, kv, hd = (int(cfg["num_attention_heads"]),
                 int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    e, fe = int(cfg["moe_num_primary_experts"]), int(cfg["moe_ffn_hidden_size"])
    root = jax.random.fold_in(seed_key(seed), 1)
    count = [0]

    def w(*shape):
        count[0] += 1
        return _normal(jax.random.fold_in(root, count[0]), tuple(shape),
                       0.02, dtype)

    def ones(k):
        return jnp.ones((k,), dtype)

    out = [{"table": w(v, d)}]
    for _ in range(int(cfg["num_hidden_layers"])):
        out.append({
            "attn": {"wq": w(d, h * hd), "wk": w(d, kv * hd),
                     "wv": w(d, kv * hd), "wo": w(h * hd, d)},
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "moe": {"wg": w(d, e), "w_gate": w(e, d, fe), "w_up": w(e, d, fe),
                    "w_down": w(e, fe, d)},
        })
    out.append({"scale": ones(d)})
    out.append({"w": w(d, v)})
    return out


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def _int8(a):
    scale = jnp.max(jnp.abs(a)) / 127.0 + 1e-30
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _mm(precision):
    q = {"float32": lambda a: a, "int8": _int8}[precision]
    return lambda a, b: jnp.matmul(q(a), q(b), precision=HI)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rotate_half(z, pos, theta):
    """Rotary positions, the rotate-half layout: ``z`` (T, heads, hd)."""
    hd = z.shape[-1]
    freq = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    turned = jnp.concatenate([-z[..., hd // 2:], z[..., :hd // 2]], axis=-1)
    return z * cos + turned * sin


def _attention(ap, a, sizes, mm, q_block, rotary, window):
    """Grouped-query attention of one sequence: the keys and values
    repeated to every query head, the queries a block at a time against
    the whole sequence, causal and (``window`` > 0) windowed."""
    h, kv, hd, theta = sizes
    t = a.shape[0]
    pos = jnp.arange(t)
    q = mm(a, ap["wq"]).reshape(t, h, hd)
    k = mm(a, ap["wk"]).reshape(t, kv, hd)
    v = mm(a, ap["wv"]).reshape(t, kv, hd)
    if rotary:
        q, k = _rotate_half(q, pos, theta), _rotate_half(k, pos, theta)
    k = jnp.repeat(k, h // kv, axis=1).transpose(1, 2, 0)  # (H, hd, T)
    v = jnp.repeat(v, h // kv, axis=1).transpose(1, 0, 2)  # (H, T, hd)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=0)
        s = mm(qb.transpose(1, 0, 2), k) * hd ** -0.5  # (H, q, T)
        at = (i * q_block + jnp.arange(q_block))[:, None]
        seen = pos[None, :] <= at
        if window:
            seen &= pos[None, :] > at - window
        o = mm(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1), v)
        return o.transpose(1, 0, 2).reshape(q_block, h * hd)

    o = lax.map(block, jnp.arange(t // q_block)).reshape(t, h * hd)
    return mm(o, ap["wo"])


def _route(wg, a, top_k):
    """``(idx (T, k), weights (T, k))``: the top-k of the softmax over
    every expert, renormalised over the chosen."""
    p = jax.nn.softmax(jnp.matmul(a, wg, precision=HI), axis=-1)
    w, idx = lax.top_k(p, top_k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def _experts(mp, hid, idx, w, mm, cap):
    """``Σ_e w_e FFN_e(h)``: a loop over the experts, each over the (at
    most ``cap``) tokens that chose it."""
    t, d = hid.shape
    padded = jnp.concatenate([hid, jnp.zeros((1, d), hid.dtype)])

    def one(e, y):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)  # (T,)
        (rows,) = jnp.nonzero(jnp.any(idx == e, axis=-1), size=cap, fill_value=t)
        p = {k: mp[k][e].astype(jnp.float32) for k in ("w_gate", "w_up", "w_down")}
        x = padded[rows]
        ye = mm(jax.nn.relu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])
        gate = jnp.concatenate([we, jnp.zeros((1,))])[rows]
        return y.at[rows].add(gate[:, None] * ye, mode="drop")

    return lax.fori_loop(0, mp["w_gate"].shape[0], one, jnp.zeros_like(hid))


@functools.partial(jax.jit, static_argnames=("sizes", "top_k", "eps", "precision",
                                             "q_block", "cap", "rotary",
                                             "window"))
def _block(bp, x, sizes, top_k, eps, precision, q_block, cap, rotary, window):
    """One block over one sequence: ``x`` (T, d) → ``(x' (T, d), the
    most tokens one expert was chosen by)``; where that exceeds ``cap``
    the caller runs the block again with a loop over every token."""
    mm = _mm(precision)
    small = _f32({k: v for k, v in bp.items() if k != "moe"})
    a = _norm(x, small["attn_norm"], eps)
    x = x + _attention(small["attn"], a, sizes, mm, q_block, rotary, window)
    hid = _norm(x, small["ffn_norm"], eps)
    mp = bp["moe"]
    idx, w = _route(mp["wg"].astype(jnp.float32), a, top_k)
    e = mp["w_gate"].shape[0]
    load = jnp.max(jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(e), axis=0))
    return x + _experts(mp, hid, idx, w, mm, cap), load


@jax.jit
def _embed(emb, tokens):
    return emb["table"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "precision", "rows"))
def _head(norm, head, x, start, eps, precision, rows):
    """Logits of ``rows`` positions from ``start``."""
    mm = _mm(precision)
    x = lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    hid = _norm(x, norm["scale"].astype(jnp.float32), eps)
    return mm(hid, head["w"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# whole sequences
# ---------------------------------------------------------------------------

LADDER = (256, 1024, 2048, 4096, 8192, 12288, 16384)
Q_BLOCK = 256
HEAD_ROWS = 1024  # the longest answer of the mix


def _padded(t: int) -> int:
    """Every sequence is padded to the next length of a short ladder (a
    causal pass: padding changes nothing before it), so that a program a
    layer exists for ``len(LADDER)`` lengths whatever the requests' own."""
    return next(length for length in LADDER if t <= length)


def _states(cfg: dict, weights, tokens, precision: str):
    """The residual after the last block, (T_padded, d)."""
    cfg = sized(cfg)
    eps = float(cfg["rms_norm_eps"])
    sizes = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
             int(cfg["head_dim"]), float(cfg["rope_theta"]))
    top_k = int(cfg["moe_num_active_primary_experts"])
    n_experts = int(cfg["moe_num_primary_experts"])
    t_pad = _padded(len(tokens))
    toks = np.zeros((t_pad,), np.int32)
    toks[: len(tokens)] = tokens
    x = _embed(weights[0], toks)
    q_block = min(Q_BLOCK, t_pad)
    # the expert loop's size: twice an expert's mean load unless the
    # routing of this very input proves skewed past it, then every token
    cap = min(t_pad, max(2 * t_pad * top_k // n_experts, 1))
    for layer in range(int(cfg["num_hidden_layers"])):
        bp = weights[1 + layer]
        window = (int(cfg["sliding_window_size"])
                  if cfg["sliding_window_layout"][layer] else 0)
        args = (sizes, top_k, eps, precision, q_block)
        kw = dict(rotary=bool(cfg["rope_layout"][layer]), window=window)
        y, load = _block(bp, x, *args, cap, **kw)
        if int(load) > cap:
            y, _ = _block(bp, x, *args, t_pad, **kw)
        x = y
    return x


def logits(cfg: dict, weights, tokens, precision: str = "float32", start: int = 0):
    """(rows, vocabulary) float32 from position ``start``: row ``i``
    scores the token that follows ``tokens[start + i]``.  One sequence,
    one plain forward pass; at most ``HEAD_ROWS`` rows."""
    x = _states(cfg, weights, tokens, precision)
    rows = min(HEAD_ROWS, x.shape[0])
    start = max(0, min(int(start), x.shape[0] - rows))
    n_layers = int(sized(cfg)["num_hidden_layers"])
    out = _head(weights[1 + n_layers], weights[2 + n_layers], x, start,
                float(cfg["rms_norm_eps"]), precision, rows)
    return out, start


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
    rows, start = logits(cfg, weights, seq[:-1], "float32", start=p - 1)
    if precision == "float32":
        chosen = np.zeros((rows.shape[0],), np.int32)
        chosen[p - 1 - start: p - 1 - start + m] = seq[p:]
    else:
        low, _ = logits(cfg, weights, seq[:-1], precision, start=p - 1)
        chosen = _first(low)
    gaps = np.asarray(_gaps_below_best(rows, chosen))
    return gaps[p - 1 - start: p - 1 - start + m].tolist()
