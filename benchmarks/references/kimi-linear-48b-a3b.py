"""Plain reference for the ``kimi-linear-48b-a3b`` configuration.

The forward pass of ``moonshotai/Kimi-Linear-48B-A3B-Instruct``
(``model_type: kimi_linear``; the layer is arXiv:2510.26692's) from the
keys of its ``config.json``, in straightforward ``jax.numpy`` at float32
with every contraction at ``highest`` precision: one whole sequence at a
time, no cache, no state carried, no batching, no kernel; the recurrence
a ``lax.scan`` over tokens, the convolution four shifted sums, the
attention not absorbed, a loop over the held experts.  Imports nothing
of ``theanompi_tpu`` and takes nothing the program made: the weights
come from the seed through ``make_weights`` (bfloat16 leaves in the
program's tree, which the driver hands to the program as they are) and
are upcast here one layer, and inside the expert loop one expert, at a
time.

The equations (``h`` a token's hidden row; sizes by their config keys):

- block: ``x ← x + Mix(norm(x))``, ``x ← x + FFN(norm(x))``, one stream;
  norm: ``x / sqrt(mean(x²) + rms_norm_eps) · g``;
- linear layer (``linear_attn_config.kda_layers``, counted from 1):
  ``[u_q | u_k | u_v] = h W_qkv``; a causal depthwise convolution of
  width ``short_conv_kernel_size``, no bias, a filter a channel, zeros
  before the sequence; per head ``q = l2(silu(c_q)) · K^{−1/2}``, ``k =
  l2(silu(c_k))``, ``v = silu(c_v)`` (``l2(z) = z / sqrt(Σz² + 1e-6)``);
  ``g = −exp(A_log) · softplus(h W_f↓ W_f↑ + dt_bias)``, ``α = exp(g)``;
  ``β = sigmoid(h W_β)``; ``S_t = (I − β k kᵀ) Diag(α) S_{t−1} + β k vᵀ``
  from ``S = 0``, ``o = Sᵀ q``; ``y = [norm_head(o) ⊙ sigmoid(h W_g↓
  W_g↑)] W_o``;
- latent layer (``full_attn_layers``; as ``deepseek_v3`` with
  ``q_lora_rank`` null and ``mla_use_nope``): ``q = h W_q`` → per head
  ``[q_a | q_b]``; ``[c_kv | k_b] = h W_kva``, ``c_kv ← norm(c_kv)``; no
  rotation; ``[k_a | v] = c_kv W_kvb`` per head; scores ``(q_a·k_a +
  q_b·k_b) · (nope + rope)^{−1/2}``, causal, softmax; ``concat_h(softmax ·
  v) W_o``;
- dense feed-forward (the first ``first_k_dense_replace`` layers):
  ``W_down(silu(h W_gate) ⊙ (h W_up))``;
- experts: ``s = sigmoid(h W_r)`` over all ``published.num_experts``;
  the ``num_experts_per_token`` largest of ``s + b``; weights
  ``routed_scaling_factor · s_e / (Σ_chosen s + 1e-20)``; **the share**:
  ``Σ_{e chosen, e < num_experts} w_e FFN_e(h) + FFN_shared(h)``, the
  held experts being the first ``num_experts`` (what the absent ones
  would add is left out, here as in the program); no capacity;
- head: ``norm(x) W_head`` over the ``vocab_size`` ids held here.

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
    ``hidden_size``, the two head counts and ``num_hidden_layers`` (in
    the configuration's file they agree), every other key as published."""
    pc = cfg.get("program_config", {})
    out = dict(cfg)
    for ours, theirs in (("hidden_size", "d_model"),
                         ("num_attention_heads", "n_heads"),
                         ("num_hidden_layers", "n_layers")):
        out[ours] = int(pc.get(theirs, cfg[ours]))
    out["linear_attn_config"] = dict(cfg["linear_attn_config"],
                                     num_heads=out["num_attention_heads"])
    return out


def leaf_dtype(cfg: dict):
    """bfloat16, what the configuration states; a model that a rehearsal
    shrank gets the same bfloat16 values in float32 leaves (on the CPU at
    toy sizes the check then holds the served tokens to the mathematics
    and not to bfloat16's ties)."""
    shrunk = any(sized(cfg)[k] != int(cfg[k]) for k in
                 ("hidden_size", "num_attention_heads", "num_hidden_layers"))
    return jnp.float32 if shrunk else jnp.bfloat16


def layer_kinds(cfg: dict):
    """``'kda'`` or ``'mla'`` for each of the layers run."""
    linear = set(cfg["linear_attn_config"]["kda_layers"])
    return ["kda" if i + 1 in linear else "mla"
            for i in range(int(cfg["num_hidden_layers"]))]


def router_width(cfg: dict) -> int:
    """The router's outputs: all the model's experts, held here or not."""
    return int(cfg.get("published", {}).get("num_experts", cfg["num_experts"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    """Values of bfloat16 whatever the leaf's dtype."""
    draw = jax.random.normal(key, shape, jnp.float32) * std
    return draw.astype(jnp.bfloat16).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi", "dtype"))
def _uniform(key, shape, lo, hi, dtype):
    draw = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    return draw.astype(jnp.bfloat16).astype(dtype)


def make_weights(cfg: dict, seed: int):
    """The weights in the program's layout (a list: embedding, the
    blocks, final norm, head), bfloat16 (``leaf_dtype``), made on the
    device one leaf a call.  Matrices N(0, 0.02²) but ``W_qkv`` N(0, 1 /
    hidden) (so that ``silu`` sees inputs of order one); filters N(0,
    0.5²); ``A_log`` 0; ``dt_bias`` uniform in (−7, −2), so that a
    channel's decay lies between about 0.88 and 0.999 a token; norms 1;
    the selection bias N(0, 0.02²)."""
    dtype = leaf_dtype(cfg)
    cfg = sized(cfg)
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    h = int(cfg["num_attention_heads"])
    lin = cfg["linear_attn_config"]
    lh, lk, conv = (int(lin["num_heads"]), int(lin["head_dim"]),
                    int(lin["short_conv_kernel_size"]))
    kr = int(cfg["kv_lora_rank"])
    nope, rope, vd = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                      int(cfg["v_head_dim"]))
    ff, fe = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    held, routed = int(cfg["num_experts"]), router_width(cfg)
    fs = fe * int(cfg["num_shared_experts"])
    root = jax.random.fold_in(seed_key(seed), 1)
    count = [0]

    def key():
        count[0] += 1
        return jax.random.fold_in(root, count[0])

    def w(*shape, std=0.02):
        return _normal(key(), tuple(shape), std, dtype)

    def ones(k):
        return jnp.ones((k,), dtype)

    out = [{"table": w(v, d)}]
    for layer, kind in enumerate(layer_kinds(cfg)):
        block = {"attn_norm": ones(d), "ffn_norm": ones(d)}
        if kind == "kda":
            wd = lh * lk
            block["kda"] = {
                "a_log": jnp.zeros((lh,), dtype),
                "conv_w": w(conv, 3 * wd, std=0.5),
                "dt_bias": _uniform(key(), (wd,), -7.0, -2.0, dtype),
                "o_norm": ones(lk), "wb": w(d, lh),
                "wf_a": w(d, lk), "wf_b": w(lk, wd),
                "wg_a": w(d, lk), "wg_b": w(lk, wd),
                "wo": w(wd, d), "wqkv": w(d, 3 * wd, std=d ** -0.5),
            }
        else:
            block["attn"] = {
                "kv_norm": ones(kr), "wkv_a": w(d, kr + rope),
                "wkv_b": w(kr, h * (nope + vd)), "wo": w(h * vd, d),
                "wq": w(d, h * (nope + rope)),
            }
        if layer < int(cfg["first_k_dense_replace"]):
            block["mlp"] = {"w_down": w(ff, d), "w_gate": w(d, ff), "w_up": w(d, ff)}
        else:
            block["moe"] = {
                "route_bias": w(routed), "wg": w(d, routed),
                "w_down": w(held, fe, d), "w_gate": w(held, d, fe),
                "w_up": w(held, d, fe),
                "shared": {"w_down": w(fs, d), "w_gate": w(d, fs), "w_up": w(d, fs)},
            }
        out.append(block)
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


def _l2(z):
    return z / jnp.sqrt(jnp.sum(jnp.square(z), axis=-1, keepdims=True) + 1e-6)


def _delta_attention(mp, hid, sizes, mm):
    """The linear layer over one sequence from an empty state, a token
    at a time."""
    h, k, conv, eps = sizes
    t = hid.shape[0]
    u = mm(hid, mp["wqkv"])  # (T, 3·H·K)
    before = jnp.concatenate([jnp.zeros((conv - 1, u.shape[1])), u])
    c = sum(mp["conv_w"][j] * before[j:j + t] for j in range(conv))
    c = jax.nn.silu(c).reshape(t, 3, h, k)
    q, key, v = _l2(c[:, 0]) * k ** -0.5, _l2(c[:, 1]), c[:, 2]
    f = mm(mm(hid, mp["wf_a"]), mp["wf_b"]) + mp["dt_bias"]
    g = -jnp.exp(mp["a_log"])[:, None] * jax.nn.softplus(f).reshape(t, h, k)
    beta = jax.nn.sigmoid(mm(hid, mp["wb"]))  # (T, H)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        seen = jnp.einsum("hd,hde->he", k_t, s, precision=HI)
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - seen))[:, None, :]
        return s, jnp.einsum("hd,hde->he", q_t, s, precision=HI)

    _, o = lax.scan(step, jnp.zeros((h, k, k)), (q, key, v, g, beta))
    gate = jax.nn.sigmoid(mm(mm(hid, mp["wg_a"]), mp["wg_b"]))
    return mm(_norm(o, mp["o_norm"], eps).reshape(t, h * k) * gate, mp["wo"])


def _attention(ap, hid, sizes, mm, q_block):
    """Causal latent attention of one sequence, not absorbed, no
    positions, the queries a block of ``q_block`` at a time."""
    h, nope, rdim, vd, kr, eps = sizes
    t = hid.shape[0]
    pos = jnp.arange(t)
    q = mm(hid, ap["wq"]).reshape(t, h, nope + rdim)
    q_a, q_b = q[..., :nope], q[..., nope:]
    kv = mm(hid, ap["wkv_a"])
    c_kv = _norm(kv[:, :kr], ap["kv_norm"], eps)
    k_b = kv[:, kr:]  # (T, rope): one for all heads
    kvb = mm(c_kv, ap["wkv_b"]).reshape(t, h, nope + vd)
    k_a, v = kvb[..., :nope], kvb[..., nope:]
    scale = (nope + rdim) ** -0.5

    def block(i):
        qa = lax.dynamic_slice_in_dim(q_a, i * q_block, q_block, axis=0)
        qb = lax.dynamic_slice_in_dim(q_b, i * q_block, q_block, axis=0)
        s = (mm(qa.transpose(1, 0, 2), k_a.transpose(1, 2, 0))
             + mm(qb.transpose(1, 0, 2), k_b.T[None])) * scale  # (H, q, T)
        at = i * q_block + jnp.arange(q_block)
        s = jnp.where(at[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2))  # (H, q, v)
        return o.transpose(1, 0, 2).reshape(q_block, h * vd)

    o = lax.map(block, jnp.arange(t // q_block)).reshape(t, h * vd)
    return mm(o, ap["wo"])


def _ffn(p, hid, mm):
    return mm(jax.nn.silu(mm(hid, p["w_gate"])) * mm(hid, p["w_up"]), p["w_down"])


def _route(mp, hid, top_k, route_scale):
    """``(idx (T, k), weights (T, k))`` over ALL the router's experts:
    the top-k of sigmoid scores plus the bias; weights from the scores
    alone."""
    s = jax.nn.sigmoid(jnp.matmul(hid, mp["wg"], precision=HI))
    _, idx = lax.top_k(s + mp["route_bias"], top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, route_scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def _experts(mp, hid, idx, w, mm, cap):
    """``Σ_{e held} w_e FFN_e(h)``: a loop over the held experts (the
    first ones), each over the (at most ``cap``) tokens that chose it; a
    pick that fell on an expert not held here adds nothing."""
    t, d = hid.shape
    padded = jnp.concatenate([hid, jnp.zeros((1, d), hid.dtype)])

    def one(e, y):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)  # (T,)
        (rows,) = jnp.nonzero(jnp.any(idx == e, axis=-1), size=cap, fill_value=t)
        p = {k: mp[k][e].astype(jnp.float32) for k in ("w_gate", "w_up", "w_down")}
        ye = _ffn(p, padded[rows], mm)
        gate = jnp.concatenate([we, jnp.zeros((1,))])[rows]
        return y.at[rows].add(gate[:, None] * ye, mode="drop")

    return lax.fori_loop(0, mp["w_gate"].shape[0], one, jnp.zeros_like(hid))


def _largest_load(idx, n_held):
    return jnp.max(jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(n_held), axis=0))


@functools.partial(jax.jit, static_argnames=("kind", "sizes", "moe", "precision",
                                             "q_block", "cap"))
def _block(bp, x, kind, sizes, moe, precision, q_block, cap):
    """One block over one sequence: ``x`` (T, d) → ``(x' (T, d), the
    most tokens one held expert was chosen by)`` (0 for a dense block);
    where that exceeds ``cap`` the caller runs the block again with a
    loop over every token."""
    mm = _mm(precision)
    eps = sizes[-1]
    small = _f32({k: v for k, v in bp.items() if k != "moe"})
    hid = _norm(x, small["attn_norm"], eps)
    if kind == "kda":
        x = x + _delta_attention(small["kda"], hid, sizes, mm)
    else:
        x = x + _attention(small["attn"], hid, sizes, mm, q_block)
    hid = _norm(x, small["ffn_norm"], eps)
    if moe is None:
        return x + _ffn(small["mlp"], hid, mm), jnp.zeros((), jnp.int32)
    mp = bp["moe"]
    router = _f32({k: mp[k] for k in ("wg", "route_bias")})
    idx, w = _route(router, hid, *moe)
    load = _largest_load(idx, mp["w_gate"].shape[0])
    y = _experts(mp, hid, idx, w, mm, cap) + _ffn(_f32(mp["shared"]), hid, mm)
    return x + y, load


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

LADDER = (256, 1024, 2048, 4096, 8192, 12288, 17920)
Q_BLOCK = 256
HEAD_ROWS = 1536  # the longest answer of the mix


def _padded(t: int) -> int:
    """Every sequence is padded to the next length of a short ladder (a
    causal pass: padding changes nothing before it), so that a program a
    layer exists for ``len(LADDER)`` lengths whatever the requests' own."""
    return next(length for length in LADDER if t <= length)


def _static(cfg: dict):
    """``{kind: sizes}`` and the routing's ``(top-k, scale)``."""
    cfg = sized(cfg)
    eps = float(cfg["rms_norm_eps"])
    lin = cfg["linear_attn_config"]
    sizes = {
        "kda": (int(lin["num_heads"]), int(lin["head_dim"]),
                int(lin["short_conv_kernel_size"]), eps),
        "mla": (int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
                int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
                int(cfg["kv_lora_rank"]), eps),
    }
    return sizes, (int(cfg["num_experts_per_token"]),
                   float(cfg["routed_scaling_factor"]))


def _states(cfg: dict, weights, tokens, precision: str):
    """The residual after the last block, (T_padded, d)."""
    sizes, moe = _static(cfg)
    cfg = sized(cfg)
    t_pad = _padded(len(tokens))
    toks = np.zeros((t_pad,), np.int32)
    toks[: len(tokens)] = tokens
    x = _embed(weights[0], toks)
    dense = int(cfg["first_k_dense_replace"])
    q_block = min(Q_BLOCK, t_pad)
    for layer, kind in enumerate(layer_kinds(cfg)):
        bp = weights[1 + layer]
        is_moe = layer >= dense
        # the expert loop's size: twice an expert's mean load (the picks
        # spread over all the router's experts) unless the routing of
        # this very input proves skewed past it, then every token
        mean_load = t_pad * moe[0] // router_width(cfg)
        cap = min(t_pad, max(2 * mean_load, 1)) if is_moe else 0
        y, load = _block(bp, x, kind, sizes[kind], moe if is_moe else None,
                         precision, q_block, cap)
        if int(load) > cap:
            y, _ = _block(bp, x, kind, sizes[kind], moe, precision, q_block,
                          t_pad)
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
