"""Plain reference for the ``xing4.0-29b-a4b`` configuration.

The forward pass of ``XingChen-AGI/Xing4.0-29B-A4B`` (``model_type:
xing4_0``) from the keys of its ``config.json``, in straightforward
``jax.numpy`` at float32 with every contraction at ``highest``
precision: one whole sequence at a time, no cache, no batching, no
kernel, the attention not absorbed, a loop over the experts, the
Sinkhorn loop written out.  Imports nothing of ``theanompi_tpu`` and
takes nothing the program made: the weights come from the seed through
``make_weights`` (bfloat16 leaves in the program's tree, which the driver
hands to the program as they are) and are upcast here one layer, and
inside the expert loop one expert, at a time.

The equations (``h`` a token's hidden row, ``X`` its four residual
streams; sizes by their config keys):

- norm: ``x / sqrt(mean(x²) + rms_norm_eps) · g``;
- latent attention (as ``deepseek_v3`` with ``q_lora_rank``): ``c_q =
  norm(h W_qa)``, ``q = c_q W_qb`` → per head ``[q_nope | q_rope]``;
  ``[c_kv | k_rope] = h W_kva``, ``c_kv ← norm(c_kv)``; rotary on
  ``q_rope`` and on the one ``k_rope`` all heads share; ``[k_nope | v] =
  c_kv W_kvb`` per head; scores ``(q_nope·k_nope + q_rope·k_rope) · s``,
  causal, softmax; output ``concat_h(softmax · v) W_o``;
- rotary positions with YaRN (DeepSeek-V2/V3's ``YarnRotaryEmbedding``):
  over the ``qk_rope_head_dim / 2`` frequencies ``θ^(−2i/dim)``, those
  that turn more than ``beta_fast`` times in ``original_max_position_
  embeddings`` positions are kept, those under ``beta_slow`` turns are
  divided by ``factor``, a linear ramp between; cos and sin times
  ``m(factor, mscale) / m(factor, mscale_all_dim)``, the softmax scale
  ``(nope + rope)^(−1/2) · m(factor, mscale_all_dim)²`` with ``m(f, a) =
  0.1 a ln f + 1``; pairs ``(x[2i], x[2i+1])``, left de-interleaved;
- dense feed-forward: ``W_down(silu(h W_gate) ⊙ (h W_up))``;
- experts: ``s = sigmoid(h W_r)``; the ``num_experts_per_tok`` largest of
  ``s + b``; weights ``routed_scaling_factor · s_e / (Σ_chosen s +
  1e-20)``; ``Σ_e w_e FFN_e(h) + FFN_shared(h)``; no capacity;
- manifold-constrained hyper-connections around each of the two
  sublayers ``F`` of a block: ``x̃ = vec(X) / sqrt(mean(vec(X)²) +
  hc_eps)``; ``z = α ⊙ (Φ x̃) + b``; ``H_pre = sigmoid(z[:4])``, ``H_post
  = 2 sigmoid(z[4:8])``, ``H_res`` = 20 times (rows, then columns,
  divided by their sums + ``hc_eps``) of ``exp(clip(z[8:], ∓30))`` as 4 ×
  4; ``u = Σ_i H_pre[i] X[i]``; ``X'[i] = Σ_j H_res[i, j] X[j] +
  H_post[i] F(norm(u))``; the streams start as four copies of the
  embedding and are summed before the final norm;
- head: ``norm(Σ_i X[i]) W_head``.

``precision="int8"`` is the control: both operands of every matrix
product rounded to 8-bit integers on a per-tensor scale, the step below
the bfloat16 the configuration states.
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
    """The key of a seed (any whole number up to a little over 2**31).
    ``rbg`` keys: 4.8 G normal draws take the chip's own generator a few
    seconds and the default one more than a minute (69 s, PR 29)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    """Values of bfloat16 whatever the leaf's dtype."""
    draw = jax.random.normal(key, shape, jnp.float32) * std
    return draw.astype(jnp.bfloat16).astype(dtype)


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _hc_bias(key, n, dtype):
    b = jax.random.normal(key, ((2 * n + n * n),), jnp.float32)
    b = b.at[2 * n:].add(3.0 * jnp.eye(n).reshape(-1))
    return b.astype(jnp.bfloat16).astype(dtype)


def sized(cfg: dict) -> dict:
    """The configuration with the sizes a run really has.  They are the
    published keys; the harness's CPU rehearsal of a serve cell shrinks
    a model through the keys of ``program_config`` it knows (``d_model``,
    ``n_heads``, ``n_layers``), so where those are given they win over
    ``hidden_size``, ``num_attention_heads`` and ``num_hidden_layers``
    (in the configuration's file the two agree), every other key as
    published."""
    pc = cfg.get("program_config", {})
    out = dict(cfg)
    for ours, theirs in (("hidden_size", "d_model"),
                         ("num_attention_heads", "n_heads"),
                         ("num_hidden_layers", "n_layers")):
        out[ours] = int(pc.get(theirs, cfg[ours]))
    return out


def leaf_dtype(cfg: dict):
    """The dtype the weights are made in, and with them the program's
    activations and cache, which follow their weights: bfloat16, what
    the configuration states.  A model that a rehearsal shrank (``sized``)
    gets the same bfloat16 values in float32 leaves: on the CPU at toy
    sizes the check then holds the served tokens to the mathematics,
    which float32 repeats to the last tie, and not to bfloat16's
    rounding, which flips a nearly tied token in one toy run of six."""
    shrunk = any(sized(cfg)[k] != int(cfg[k]) for k in
                 ("hidden_size", "num_attention_heads", "num_hidden_layers"))
    return jnp.float32 if shrunk else jnp.bfloat16


def make_weights(cfg: dict, seed: int):
    """The weights in the program's layout (a list: embedding, the
    blocks, final norm, head), bfloat16 (``leaf_dtype``), made on the
    device one leaf a call (a leaf's float32 draw is the most that is ever beside the
    weights).  Matrices N(0, 0.02²); norms 1; the selection bias N(0,
    0.02²); hyper-connections: Φ N(0, 0.02²), α 0.1, b N(0, 1) with 3 on
    the diagonal of its 4 × 4 part."""
    dtype = leaf_dtype(cfg)
    cfg = sized(cfg)
    d, v, h = int(cfg["hidden_size"]), int(cfg["vocab_size"]), int(cfg["num_attention_heads"])
    qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope, vd = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                      int(cfg["v_head_dim"]))
    ff, fe = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    e, n = int(cfg["n_routed_experts"]), int(cfg["hc_mult"])
    fs = fe * int(cfg["n_shared_experts"])
    root = jax.random.fold_in(seed_key(seed), 1)
    count = [0]

    def w(*shape, std=0.02):
        count[0] += 1
        return _normal(jax.random.fold_in(root, count[0]), tuple(shape), std,
                       dtype)

    def ones(k):
        return jnp.ones((k,), dtype)

    def hc():
        count[0] += 1
        return {"alpha": jnp.full((3,), 0.1, jnp.bfloat16).astype(dtype),
                "bias": _hc_bias(jax.random.fold_in(root, count[0]), n, dtype),
                "phi": w((2 * n + n * n), n * d)}

    out = [{"table": w(v, d)}]
    for layer in range(int(cfg["num_hidden_layers"])):
        block = {
            "attn": {"kv_norm": ones(kr), "q_norm": ones(qr),
                     "wkv_a": w(d, kr + rope), "wkv_b": w(kr, h * (nope + vd)),
                     "wo": w(h * vd, d), "wq_a": w(d, qr),
                     "wq_b": w(qr, h * (nope + rope))},
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "hc_attn": hc(), "hc_ffn": hc(),
        }
        if layer < int(cfg["first_k_dense_replace"]):
            block["mlp"] = {"w_down": w(ff, d), "w_gate": w(d, ff), "w_up": w(d, ff)}
        else:
            block["moe"] = {
                "route_bias": w(e), "wg": w(d, e),
                "w_down": w(e, fe, d), "w_gate": w(e, d, fe), "w_up": w(e, d, fe),
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


def yarn(cfg: dict):
    """``(inv_freq (dim/2,), cos/sin multiplier, softmax scale)``."""
    dim, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    factor, orig = float(rs["factor"]), int(rs["original_max_position_embeddings"])
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / factor * ramp + extra * (1 - ramp)

    def m(a):
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0

    mult = m(float(rs["mscale"])) / m(float(rs["mscale_all_dim"]))
    head = int(cfg["qk_nope_head_dim"]) + dim
    scale = head ** -0.5 * m(float(rs["mscale_all_dim"])) ** 2
    return np.asarray(inv, np.float32), float(mult), float(scale)


def _rope(x, positions, inv, mult):
    """Pairs (x[2i], x[2i+1]) turned by positions · inv[i]; evens then
    odds in the result."""
    ang = positions[:, None].astype(jnp.float32) * inv  # (T, dim/2)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([xe * cos - xo * sin, xe * sin + xo * cos], axis=-1)


def _hyper(hp, x, n, eps, iters, clamp):
    """The coefficients of every token from its state ``x`` (T, n, d):
    ``(H_pre (T, n), H_post (T, n), H_res (T, n, n))``."""
    t = x.shape[0]
    flat = x.reshape(t, -1)
    xt = flat / jnp.sqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    c = jnp.matmul(xt, hp["phi"].T, precision=HI)  # (T, 2n + n²)
    a = hp["alpha"]
    pre = jax.nn.sigmoid(a[0] * c[:, :n] + hp["bias"][:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * c[:, n:2 * n] + hp["bias"][n:2 * n])
    r = jnp.exp(jnp.clip(a[2] * c[:, 2 * n:] + hp["bias"][2 * n:], -clamp, clamp))
    r = r.reshape(t, n, n)
    for _ in range(iters):  # Sinkhorn: rows, then columns
        r = r / (jnp.sum(r, axis=2, keepdims=True) + eps)
        r = r / (jnp.sum(r, axis=1, keepdims=True) + eps)
    return pre, post, r


def _attention(ap, hid, sizes, rope, mm, q_block):
    """Causal latent attention of one sequence, not absorbed, the
    queries a block of ``q_block`` at a time."""
    h, nope, rdim, vd, kr, eps = sizes
    inv, mult, scale = rope
    t = hid.shape[0]
    pos = jnp.arange(t)
    cq = _norm(mm(hid, ap["wq_a"]), ap["q_norm"], eps)
    q = mm(cq, ap["wq_b"]).reshape(t, h, nope + rdim)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, inv, mult)
    kv = mm(hid, ap["wkv_a"])
    c_kv = _norm(kv[:, :kr], ap["kv_norm"], eps)
    k_rope = _rope(kv[:, kr:], pos, inv, mult)  # (T, rope): one for all heads
    kvb = mm(c_kv, ap["wkv_b"]).reshape(t, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]

    def block(i):
        qn = lax.dynamic_slice_in_dim(q_nope, i * q_block, q_block, axis=0)
        qr = lax.dynamic_slice_in_dim(q_rope, i * q_block, q_block, axis=0)
        s = (mm(qn.transpose(1, 0, 2), k_nope.transpose(1, 2, 0))
             + mm(qr.transpose(1, 0, 2), k_rope.T[None])) * scale  # (H, q, T)
        at = i * q_block + jnp.arange(q_block)
        s = jnp.where(at[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2))  # (H, q, v)
        return o.transpose(1, 0, 2).reshape(q_block, h * vd)

    o = lax.map(block, jnp.arange(t // q_block)).reshape(t, h * vd)
    return mm(o, ap["wo"])


def _ffn(p, hid, mm):
    return mm(jax.nn.silu(mm(hid, p["w_gate"])) * mm(hid, p["w_up"]), p["w_down"])


def _route(mp, hid, top_k, route_scale):
    """``(idx (T, k), weights (T, k))``: the top-k of sigmoid scores
    plus the bias; weights from the scores alone."""
    s = jax.nn.sigmoid(jnp.matmul(hid, mp["wg"], precision=HI))
    _, idx = lax.top_k(s + mp["route_bias"], top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, route_scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def _experts(mp, hid, idx, w, mm, cap):
    """``Σ_e w_e FFN_e(h)``: a loop over the experts, each over the
    (at most ``cap``) tokens that chose it."""
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


def _largest_load(idx, n_experts):
    return jnp.max(jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(n_experts), axis=0))


@functools.partial(jax.jit, static_argnames=("sizes", "hc", "moe", "rope", "precision",
                                             "q_block", "cap"))
def _block(bp, x, sizes, hc, moe, rope, precision, q_block, cap):
    """One block over one sequence: ``x`` (T, n, d) → ``(x' (T, n, d),
    the most tokens one expert was chosen by)`` (0 for a dense block);
    where that exceeds ``cap`` the caller runs the block again with a
    loop over every token."""
    mm = _mm(precision)
    eps = sizes[-1]
    small = _f32({k: v for k, v in bp.items() if k != "moe"})
    inv = jnp.asarray(rope[0], jnp.float32)

    def sublayer(hp, g, x, f):
        pre, post, res = _hyper(hp, x, *hc)
        u = jnp.sum(pre[:, :, None] * x, axis=1)
        y = f(_norm(u, g, eps))
        return (jnp.einsum("tij,tjd->tid", res, x, precision=HI)
                + post[:, :, None] * y[:, None, :])

    x = sublayer(small["hc_attn"], small["attn_norm"], x,
                 lambda hid: _attention(small["attn"], hid, sizes,
                                        (inv, rope[1], rope[2]), mm, q_block))

    load = [jnp.zeros((), jnp.int32)]

    def feed_forward(hid):
        if moe is None:
            return _ffn(small["mlp"], hid, mm)
        mp = bp["moe"]
        router = _f32({k: mp[k] for k in ("wg", "route_bias")})
        idx, w = _route(router, hid, *moe)
        load[0] = _largest_load(idx, mp["wg"].shape[1])
        return _experts(mp, hid, idx, w, mm, cap) + _ffn(_f32(mp["shared"]), hid, mm)

    return sublayer(small["hc_ffn"], small["ffn_norm"], x, feed_forward), load[0]


@functools.partial(jax.jit, static_argnames=("n",))
def _embed(emb, tokens, n):
    e = emb["table"][tokens].astype(jnp.float32)
    return jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))


@functools.partial(jax.jit, static_argnames=("eps", "precision", "rows"))
def _head(norm, head, x, start, eps, precision, rows):
    """Logits of ``rows`` positions from ``start``: the streams summed,
    normed, times the head."""
    mm = _mm(precision)
    x = lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    hid = _norm(jnp.sum(x, axis=1), norm["scale"].astype(jnp.float32), eps)
    return mm(hid, head["w"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# whole sequences
# ---------------------------------------------------------------------------

LADDER = (256, 1024, 2048, 4096, 8192, 12288, 17408)
Q_BLOCK = 256
HEAD_ROWS = 1024


def _padded(cfg: dict, t: int) -> int:
    """Every sequence is padded to the next length of a short ladder (a
    causal pass: padding changes nothing before it), so that a program a
    layer exists for ``len(LADDER)`` lengths whatever the requests' own.
    Nothing on the device has a request's own length for a shape."""
    return next(length for length in LADDER if t <= length)


def _static(cfg: dict):
    cfg = sized(cfg)
    h = int(cfg["num_attention_heads"])
    sizes = (h, int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
             int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]),
             float(cfg["rms_norm_eps"]))
    hc = (int(cfg["hc_mult"]), float(cfg["hc_eps"]), int(cfg["hc_sinkhorn_iters"]),
          float(cfg["mhc_h_res_clamp_max"]))
    inv, mult, scale = yarn(cfg)
    moe = (int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"]))
    return sizes, hc, (tuple(inv.tolist()), mult, scale), moe


def _states(cfg: dict, weights, tokens, precision: str):
    """The residual state after the last block, (T_padded, n, d)."""
    sizes, hc, rope, moe = _static(cfg)
    cfg = sized(cfg)
    t_pad = _padded(cfg, len(tokens))
    toks = np.zeros((t_pad,), np.int32)
    toks[: len(tokens)] = tokens
    x = _embed(weights[0], toks, hc[0])
    n_layers, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    q_block = min(Q_BLOCK, t_pad)
    for layer, bp in enumerate(weights[1:1 + n_layers]):
        is_moe = layer >= dense
        # the expert loop's size: twice an expert's mean load (an eighth
        # of the tokens at top-4 of 64) unless the routing of this very
        # input proves skewed past it, then every token
        mean_load = t_pad * moe[0] // int(cfg["n_routed_experts"])
        cap = min(t_pad, max(2 * mean_load, 1)) if is_moe else 0
        y, load = _block(bp, x, sizes, hc, moe if is_moe else None, rope,
                         precision, q_block, cap)
        if int(load) > cap:
            y, _ = _block(bp, x, sizes, hc, moe, rope, precision, q_block, t_pad)
        x = y
    return x


def logits(cfg: dict, weights, tokens, precision: str = "float32", start: int = 0):
    """(rows, vocabulary) float32 from position ``start``: row ``i``
    scores the token that follows ``tokens[start + i]``.  One sequence,
    one plain forward pass; at most ``HEAD_ROWS`` rows (the head is the
    largest matrix, and only the served positions are compared)."""
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
