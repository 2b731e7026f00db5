"""Sparse experts without dropped tokens — one routing algorithm for
training and serving.

Beyond-reference (Theano-MPI is data-parallel only; SURVEY.md §3.4).

- **Routing** (``route``): scores over the experts (softmax, or sigmoid
  with a per-expert selection bias that enters the choice and not the
  weight), the ``top_k`` best of each token, the chosen scores
  renormalised and scaled.
- **Dispatch** (``dispatch_plan``): every (token, choice) pair is given
  a row in a layout sorted by expert, each expert's group padded to a
  whole row tile, from a running count per expert — no capacity, no
  overflow, nothing dropped.  The layout's static size is the worst
  case of the routing; tiles past the live ones cost nothing
  (``ops.pallas_gmm``).
- **Experts**: grouped matrix products over that layout — the Pallas
  kernel where the caller says so (serving on one chip), its plain XLA
  form otherwise (training: differentiable; the tests' oracle).
- **Combine**: each token gathers its ``top_k`` rows back, weighted.

**Which experts a layer holds.**  A layer computes the part of the
output that the experts it holds contribute, and the parts of all
holders sum to the whole layer.  Two ways to say which:

- ``ep_axis`` (training, under ``shard_map``): tokens and expert
  weights are sharded over the axis; each device gathers everybody's
  tokens with their routing, computes its own experts' part for all of
  them, and a reduce-scatter hands every device the sum for its own
  tokens.  Autodiff transposes the pair; the expert leaves' cotangents
  are scaled by ``1/ep`` (``_grad_scale``) so that "mean over dp, skip
  ep" stays exact.
- ``experts_held=(first, count)`` (serving, no collective in the
  layer): the expert leaves hold ``count`` experts starting at
  ``first``; whoever owns the replicas sums their outputs.  The shared
  expert belongs to the holder of expert 0, so it is counted once.

``ep_axis=None`` with every expert held is the unsharded oracle.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops.layers import Layer, he_normal
from theanompi_tpu.runtime.mesh import EP_AXIS


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_scale(w, c):
    """Identity forward; cotangent × c backward.

    Why: ``ep`` shards the BATCH (unlike ``tp``, where every rank sees
    the same loss), so the transposed reduce-scatter hands an expert
    shard the summed cotangents of all ep peers' local losses — ep× the
    per-shard mean the exchanger contract expects.  Scaling the WEIGHT
    cotangent by 1/ep (activations untouched: upstream replicated layers
    still need unscaled cotangents) makes `pmean over dp, skip ep` exact
    for expert-sharded leaves.
    """
    return w


_grad_scale.defvjp(lambda w, c: (w, None), lambda c, _, ct: (ct * c,))


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def route(x, w_router, *, top_k: int, scoring: str = "softmax",
          bias=None, scale: float = 1.0):
    """``(idx (T, k) int32, weights (T, k) fp32, scores (T, E) fp32)``.

    ``softmax``: the ``top_k`` largest probabilities; a single choice
    keeps its probability as the weight (Switch), several are
    renormalised to sum to one (GShard).
    ``sigmoid``: scores ``s = sigmoid(x W)``; the choice is the
    ``top_k`` of ``s + bias`` (the bias steers load and never enters a
    weight); weights ``scale · s / (Σ_chosen s + 1e-20)``."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        choice = scores
        renormalise = top_k > 1
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        renormalise = True
    else:
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got {scoring!r}")
    _, idx = lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale, scores


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

class Plan(NamedTuple):
    dest: jax.Array  # (T, k) int32: the row of each (token, choice); rows = dropped
    row_token: jax.Array  # (rows,) int32: the token a row holds (0 for padding)
    tile_expert: jax.Array  # (rows // tm,) int32: the held expert of each tile
    n_valid: jax.Array  # (1,) int32: tiles that hold a token
    counts: jax.Array  # (n_held,) int32: tokens each held expert received
    tm: int


def tile_rows(n_assign: int, n_held: int, least: int = 16) -> int:
    """Rows of a tile: near an expert's mean load, a power of two in
    ``least``..256 (the kernel's ``least`` is 16, bfloat16's sublane
    tile; the plain form's 8: every tile costs it a gathered matrix and
    its padding rows, so it wants them few; at 256 a tile's product
    fills the matrix unit and a second tile of one expert re-reads its
    weights)."""
    mean = max(1, n_assign // max(n_held, 1))
    return min(256, max(least, 1 << (mean - 1).bit_length()))


def n_tiles_for(n_assign: int, n_held: int, tm: int) -> int:
    """The most tiles ``n_assign`` rows can take in groups padded to
    ``tm``: every group wastes at most ``tm - 1`` rows, and no more
    groups than rows are live."""
    return max(1, min(n_assign, (n_assign + n_held * (tm - 1)) // tm))


def dispatch_plan(idx, first, n_held: int, tm: int, valid=None) -> Plan:
    """Rows for the (token, choice) pairs whose expert lies in
    ``[first, first + n_held)`` (``first`` may be traced), in token order
    within each expert.  ``valid`` (T,) bool leaves padding tokens out."""
    t, k = idx.shape
    a = t * k
    n_tiles = n_tiles_for(a, n_held, tm)
    rows = n_tiles * tm
    e = idx.reshape(a) - first
    held = (e >= 0) & (e < n_held)
    if valid is not None:
        held &= jnp.repeat(valid, k)
    e = jnp.where(held, e, n_held)
    hot = (e[:, None] == jnp.arange(n_held)[None, :]).astype(jnp.int32)
    counts = jnp.sum(hot, axis=0)
    rank = jnp.sum((jnp.cumsum(hot, axis=0) - hot) * hot, axis=1)
    tile_end = jnp.cumsum((counts + tm - 1) // tm)
    tile_start = tile_end - (counts + tm - 1) // tm
    dest = jnp.where(
        held, jnp.take(tile_start, jnp.minimum(e, n_held - 1)) * tm + rank, rows)
    row_token = jnp.zeros((rows,), jnp.int32).at[dest].set(
        jnp.arange(a, dtype=jnp.int32) // k, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        n_held - 1).astype(jnp.int32)
    return Plan(dest.reshape(t, k), row_token, tile_expert,
                tile_end[-1:].astype(jnp.int32), counts, tm)


def combine(plan: Plan, rows_out, weights):
    """``y[t] = Σ_k weights[t, k] · rows_out[dest[t, k]]`` (fp32); a
    choice that was not dispatched here adds nothing."""
    n_rows = rows_out.shape[0]
    here = plan.dest < n_rows
    picked = jnp.take(rows_out, jnp.minimum(plan.dest, n_rows - 1), axis=0)
    # a row nobody wrote (a tile past the live ones) may hold anything
    picked = jnp.where(here[..., None], picked, 0)
    w = jnp.where(here, weights, 0.0)
    return jnp.einsum("tk,tkd->td", w, picked.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _gmm(impl: str, x, w, plan: Plan, name: str):
    from theanompi_tpu.ops import pallas_gmm

    if impl == "pallas":
        return pallas_gmm.grouped_mm(x, w, plan.tile_expert, plan.n_valid,
                                     tm=plan.tm, name=name)
    return pallas_gmm.grouped_mm_xla(x, w, plan.tile_expert, tm=plan.tm)


def _tile_bias(h, b, plan: Plan):
    """``h`` (rows, n) plus each tile's expert's bias (E, n), fp32."""
    n = h.shape[-1]
    hb = h.astype(jnp.float32).reshape(-1, plan.tm, n)
    return (hb + jnp.take(b, plan.tile_expert, axis=0)[:, None, :]).reshape(-1, n)


class MoeMlp(Layer):
    """Mixture-of-experts FFN: ``y[token] = Σ_k w_k · FFN_{e_k}(x)
    (+ FFN_shared(x))``, every token served by every expert it chose.

    ``gated=False``: ``relu(x W_in + b_in) W_out + b_out`` per expert
    (the training models).  ``gated=True``: ``(act(x W_gate) ⊙ (x
    W_up)) W_down`` without biases (``activation`` ``'silu'``, or
    ``'relu'``: ReGLU), with ``n_shared`` shared experts of the same
    width fused into one always-on FFN.
    """

    def __init__(
        self,
        n_experts: int,
        d_hidden: int,
        top_k: int = 1,
        ep_axis: Optional[str] = EP_AXIS,
        ep_size: int = 1,
        compute_dtype=None,
        tp_axis: Optional[str] = None,
        tp_size: int = 1,
        emit_aux: bool = True,
        scoring: str = "softmax",
        route_scale: float = 1.0,
        gated: bool = False,
        n_shared: int = 0,
        experts_held: Optional[Tuple[int, int]] = None,
        param_dtype=jnp.float32,
        w_init=he_normal,
        activation: str = "silu",
    ):
        if not 1 <= top_k <= n_experts:
            raise ValueError(
                f"top_k must be in 1..n_experts={n_experts}, got {top_k}")
        if n_experts % max(ep_size, 1):
            raise ValueError(
                f"n_experts={n_experts} not divisible by ep={ep_size}"
            )
        if tp_size > 1 and d_hidden % tp_size:
            raise ValueError(
                f"d_hidden={d_hidden} not divisible by tp={tp_size}"
            )
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring must be 'softmax' or 'sigmoid', got {scoring!r}")
        if n_shared and not gated:
            raise ValueError("a shared expert needs gated=True")
        if activation not in ("silu", "relu"):
            raise ValueError(
                f"activation must be 'silu' or 'relu', got {activation!r}")
        self.n_experts = n_experts
        self.d_hidden = d_hidden
        self.top_k = top_k
        self.ep_axis = ep_axis if ep_size > 1 else None
        self.ep_size = ep_size if ep_size > 1 else 1
        if experts_held is not None and self.ep_axis is not None:
            raise ValueError("experts_held and ep_axis both say which "
                             "experts a layer holds: give one")
        # told so, the expert leaves hold `count` experts and not all
        self._leaf_experts = n_experts if experts_held is None else int(
            experts_held[1])
        first, count = experts_held or (0, n_experts // self.ep_size)
        if not (0 <= first and count >= 1 and first + count <= n_experts):
            raise ValueError(
                f"experts_held={experts_held} outside 0..{n_experts}")
        self.experts_held = (int(first), int(count))
        # expert matmul dtype (routing scores stay fp32 regardless):
        # bf16 here matches the dense-MLP path's MXU behavior
        self.compute_dtype = compute_dtype
        # 2-D expert sharding: hidden dim of every expert Megatron-split
        # over tp (w_in column-parallel, w_out row-parallel, f/g pair)
        self.tp_axis = tp_axis if tp_size > 1 else None
        self.tp_size = tp_size if tp_size > 1 else 1
        # emit_aux=False: STATELESS layer (empty state, no aux_loss
        # output) — required inside scanned schedules that carry
        # activations only (the pipelined LM)
        self.emit_aux = bool(emit_aux)
        self.scoring = scoring
        self.route_scale = float(route_scale)
        self.gated = bool(gated)
        self.n_shared = int(n_shared)
        self.activation = activation
        self.param_dtype = param_dtype
        self.w_init = w_init

    def init(self, key, in_shape):
        (d,) = in_shape
        E, h, dt = self.n_experts, self.d_hidden, self.param_dtype
        he_normal = self.w_init
        kg, ki, ko, ku, ks = jax.random.split(key, 5)
        params = {"wg": he_normal(kg, (d, E), d, dt)}
        if self.scoring == "sigmoid":
            params["route_bias"] = jnp.zeros((E,), dt)
        held = self._leaf_experts
        if self.gated:
            params["w_gate"] = he_normal(ki, (held, d, h), d, dt)
            params["w_up"] = he_normal(ku, (held, d, h), d, dt)
            params["w_down"] = he_normal(ko, (held, h, d), h, dt)
            if self.n_shared:
                hs = h * self.n_shared
                k1, k2, k3 = jax.random.split(ks, 3)
                params["shared"] = {
                    "w_gate": he_normal(k1, (d, hs), d, dt),
                    "w_up": he_normal(k2, (d, hs), d, dt),
                    "w_down": he_normal(k3, (hs, d), hs, dt),
                }
        else:
            params.update(
                w_in=he_normal(ki, (held, d, h), d, dt),
                b_in=jnp.zeros((held, h), dt),
                w_out=he_normal(ko, (held, h, d), h, dt),
                b_out=jnp.zeros((held, d), dt),
            )
        # aux_loss rides the STATE tree: apply emits the differentiable
        # Switch load-balance scalar there, and the owning model adds
        # coef·aux to its task loss (gradients flow — state is a live
        # output of the same apply call)
        if not self.emit_aux:
            return params, {}, in_shape
        return params, {"aux_loss": jnp.zeros((), jnp.float32)}, in_shape

    # ------------------------------------------------------------------
    def _experts(self, params, plan: Plan, x_rows, impl: str):
        """The held experts' FFN over the dispatched rows."""
        gs = 1.0 / self.ep_size  # see _grad_scale: batch shards on ep
        leaf = (
            (lambda n: _grad_scale(params[n], gs)) if self.ep_axis
            else (lambda n: params[n])
        )
        tp = self.tp_axis is not None
        if tp:
            from theanompi_tpu.parallel.tensor import copy_to_tp, reduce_from_tp

            x_rows = copy_to_tp(x_rows, self.tp_axis)  # f: bwd psums over tp
        cd = x_rows.dtype
        with jax.named_scope("moe_experts"):
            if self.gated:
                g = _gmm(impl, x_rows, leaf("w_gate"), plan,
                         "moe_grouped_mm_gate")
                u = _gmm(impl, x_rows, leaf("w_up"), plan,
                         "moe_grouped_mm_up")
                from theanompi_tpu.ops.attention import GATES

                h = (GATES[self.activation](g.astype(jnp.float32))
                     * u.astype(jnp.float32)).astype(cd)
                out = _gmm(impl, h, leaf("w_down"), plan,
                           "moe_grouped_mm_down")
            else:
                h = _gmm(impl, x_rows, leaf("w_in"), plan,
                         "moe_grouped_mm_in")
                h = jax.nn.relu(_tile_bias(h, leaf("b_in"), plan)).astype(cd)
                out = _gmm(impl, h, leaf("w_out"), plan,
                           "moe_grouped_mm_out")
            if tp:
                out = reduce_from_tp(out, self.tp_axis)  # g: fwd psum
            if not self.gated:
                # after the tp reduce, so it is not counted tp times
                out = _tile_bias(out, leaf("b_out"), plan).astype(cd)
        return out

    def forward(self, params, x, valid=None, impl: str = "xla",
                route_x=None):
        """``(y (T, d), counts, scores)``: the part of the layer's output
        that the held experts contribute for the tokens ``x`` (T, d)
        (with ``ep_axis``: the whole output for this device's tokens),
        the tokens each held expert received, and the routing scores.
        ``valid`` (T,) bool leaves padding rows unrouted; ``impl``:
        ``'pallas'`` runs the grouped products as the kernel (serving on
        one chip), ``'xla'`` as their plain, differentiable form.
        ``route_x`` (T, d): the rows the router reads, where they are
        not ``x`` (a block that routes on its attention's input)."""
        if impl not in ("xla", "pallas"):
            raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
        cd = jnp.dtype(self.compute_dtype) if self.compute_dtype else x.dtype
        xc = x.astype(cd)
        with jax.named_scope("moe_router"):
            idx, w, scores = route(
                xc if route_x is None else route_x.astype(cd), params["wg"], top_k=self.top_k, scoring=self.scoring,
                bias=params.get("route_bias"), scale=self.route_scale)
        first, count = self.experts_held
        x_all, v_all = xc, valid
        if self.ep_axis is not None:
            gather = partial(lax.all_gather, axis_name=self.ep_axis,
                             axis=0, tiled=True)
            x_all, idx, w = gather(xc), gather(idx), gather(w)
            v_all = None if valid is None else gather(valid)
            first = lax.axis_index(self.ep_axis) * count
        with jax.named_scope("moe_router"):
            plan = dispatch_plan(
                idx, first, count,
                tile_rows(idx.size, count, 16 if impl == "pallas" else 8),
                valid=v_all)
        x_rows = jnp.take(x_all, plan.row_token, axis=0)
        y = combine(plan, self._experts(params, plan, x_rows, impl), w)
        if self.ep_axis is not None:
            y = lax.psum_scatter(y, self.ep_axis, scatter_dimension=0,
                                 tiled=True)
        if self.n_shared and (self.ep_axis is not None or first == 0):
            from theanompi_tpu.ops.attention import gated_ffn

            sp = params["shared"]
            y = y + gated_ffn(xc, sp["w_gate"], sp["w_up"], sp["w_down"],
                              self.activation).astype(jnp.float32)
        return y.astype(x.dtype), plan.counts, scores

    def _aux(self, scores):
        """Switch load-balance aux (E·Σ frac_e·prob̄_e, =1 at uniform):
        differentiable through prob̄ only, exactly as in the paper."""
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        hot1 = jax.nn.one_hot(jnp.argmax(scores, axis=-1), self.n_experts,
                              dtype=jnp.float32)
        return self.n_experts * jnp.sum(
            jnp.mean(hot1, axis=0) * jnp.mean(probs, axis=0))

    def apply(self, params, state, x, train=False, rng=None):
        y, _, scores = self.forward(params, x)
        if not self.emit_aux:
            return y, {}
        return y, {"aux_loss": self._aux(scores)}

    @staticmethod
    def param_specs(axis, tp_axis=None):
        """PartitionSpec dict matching ``init``'s param keys (the
        ``gated=False`` layout the training models use): expert leaves
        shard their leading expert dim over ``axis``; with ``tp_axis``,
        each expert's hidden dim additionally shards Megatron-style
        (w_in column, w_out row; b_out replicated over tp — it is added
        after the tp reduce). The gate is replicated.  The ONE place the
        key set lives — models and tests build their spec trees from
        this."""
        from jax.sharding import PartitionSpec as P

        if tp_axis is None:
            e = P(axis)
            return {"wg": P(), "w_in": e, "b_in": e, "w_out": e, "b_out": e}
        return {
            "wg": P(),
            "w_in": P(axis, None, tp_axis),  # (E, d, h): column-parallel
            "b_in": P(axis, tp_axis),  # (E, h)
            "w_out": P(axis, tp_axis, None),  # (E, h, d): row-parallel
            "b_out": P(axis),  # (E, d): added post-reduce, tp-replicated
        }

    @staticmethod
    def add_aux_loss(loss, state_tree, coef, train: bool):
        """``loss + coef·Σ aux`` during training — THE way models engage
        the load-balance aux (both MoE models call this; keep the logic
        in one place)."""
        if not (train and coef):
            return loss
        return loss + float(coef) * sum(MoeMlp.collect_aux_losses(state_tree))

    @staticmethod
    def collect_aux_losses(state_tree):
        """Every ``aux_loss`` leaf in a (nested) state tree — the model
        adds ``coef · sum(...)`` to its task loss."""
        out = []

        def walk(node):
            if isinstance(node, dict):
                for k, v in node.items():
                    if k == "aux_loss":
                        out.append(v)
                    else:
                        walk(v)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v)

        walk(state_tree)
        return out

    def aux_load_balance_loss(self, params, x):
        """Switch load-balancing auxiliary: E · Σ_e fraction_e · prob_e.
        Minimized (=1) at uniform routing; add ``coef·aux`` to the task
        loss when training real MoE models."""
        _, _, scores = route(
            x, params["wg"], top_k=self.top_k, scoring=self.scoring,
            bias=params.get("route_bias"))
        return self._aux(scores)
