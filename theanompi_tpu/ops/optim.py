"""Native SGD-family optimizers and learning-rate schedules.

Re-creation of the reference's update-rule builders (upstream
``theanompi/lib/opt.py``: vanilla / momentum / Nesterov SGD with weight
decay, building Theano update pairs over shared variables; SURVEY.md
§3.5) — redesigned as pure ``init``/``update`` functions over pytrees.

The learning rate is a **leaf of the optimizer state** (a jnp scalar), not
a Python constant baked into the jit: the reference kept lr in a Theano
shared variable so ``adjust_hyperp(epoch)`` could change it without
recompiling, and storing it in opt state gives the same property under
``jax.jit`` (it is an array argument, not a static).  Host code mutates it
via ``set_lr``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Any
Grads = Any
OptState = Any


class Optimizer(NamedTuple):
    init: Callable[[Params], OptState]
    update: Callable[[Params, Grads, OptState], Tuple[Params, OptState]]


def sgd(
    lr: float,
    momentum: float = 0.0,
    nesterov: bool = False,
    weight_decay: float = 0.0,
) -> Optimizer:
    """SGD with optional (Nesterov) momentum and decoupled-from-loss L2.

    Weight decay is applied as ``g += wd * p`` (classic L2, as the
    reference's update builders did), not AdamW-style decoupled decay.
    """

    def init(params: Params) -> OptState:
        return {
            "velocity": jax.tree.map(jnp.zeros_like, params),
            "lr": jnp.asarray(lr, jnp.float32),
            "step": jnp.zeros((), jnp.int32),
        }

    def update(params: Params, grads: Grads, state: OptState):
        lr_t = state["lr"]

        def upd(p, g, v):
            g = g.astype(jnp.float32)
            if weight_decay:
                g = g + weight_decay * p
            if momentum:
                v_new = momentum * v - lr_t * g
                if nesterov:
                    step = momentum * v_new - lr_t * g
                else:
                    step = v_new
            else:
                v_new = v
                step = -lr_t * g
            return p + step, v_new

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_v = treedef.flatten_up_to(state["velocity"])
        out = [upd(p, g, v) for p, g, v in zip(flat_p, flat_g, flat_v)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_vel = treedef.unflatten([o[1] for o in out])
        return new_params, {
            "velocity": new_vel,
            "lr": lr_t,
            "step": state["step"] + 1,
        }

    return Optimizer(init, update)


def adam(
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    decoupled: bool = True,
) -> Optimizer:
    """Adam / AdamW (beyond-reference: the 2016 upstream had only the
    SGD family, but the transformer/MoE models this framework adds are
    conventionally trained with it).  Same design rules as :func:`sgd`:
    lr lives in the state, moments are param-shaped top-level entries so
    ``TpuModel._opt_state_specs`` shards them automatically for tp/ep/pp
    models.  ``decoupled=True`` = AdamW (decay applied to params, not
    grads); ``False`` = classic L2-in-gradient.
    """

    def init(params: Params) -> OptState:
        return {
            "mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "lr": jnp.asarray(lr, jnp.float32),
            "step": jnp.zeros((), jnp.int32),
        }

    def update(params: Params, grads: Grads, state: OptState):
        lr_t = state["lr"]
        t = state["step"] + 1
        # bias correction folded into a step-dependent scale (fp32)
        c1 = 1.0 - jnp.power(b1, t.astype(jnp.float32))
        c2 = 1.0 - jnp.power(b2, t.astype(jnp.float32))
        scale = lr_t * jnp.sqrt(c2) / c1

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            if weight_decay and not decoupled:
                g = g + weight_decay * p
            m_new = b1 * m + (1.0 - b1) * g
            v_new = b2 * v + (1.0 - b2) * jnp.square(g)
            step = -scale * m_new / (jnp.sqrt(v_new) + eps)
            if weight_decay and decoupled:
                step = step - lr_t * weight_decay * p
            return p + step, m_new, v_new

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["mu"])
        flat_v = treedef.flatten_up_to(state["nu"])
        out = [upd(*a) for a in zip(flat_p, flat_g, flat_m, flat_v)]
        return treedef.unflatten([o[0] for o in out]), {
            "mu": treedef.unflatten([o[1] for o in out]),
            "nu": treedef.unflatten([o[2] for o in out]),
            "lr": lr_t,
            "step": t,
        }

    return Optimizer(init, update)


def _trust_ratio(p_norm, u_norm, trust_coefficient, eps):
    """LARS/LAMB layer-adaptive scale: η·||p||/||u||, defined as 1 when
    either norm is 0 (fresh zero-init params or vanished updates must
    not freeze/explode the layer)."""
    ratio = trust_coefficient * p_norm / (u_norm + eps)
    return jnp.where((p_norm > 0.0) & (u_norm > 0.0), ratio, 1.0)


def lars(
    lr: float,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    trust_coefficient: float = 0.001,
    eps: float = 1e-9,
) -> Optimizer:
    """LARS (You et al. 2017, arXiv:1708.03888) — layer-wise adaptive
    rate scaling for LARGE-batch data parallelism.  Beyond-reference but
    squarely in its theme: the BASELINE scaling-efficiency metric at 32
    chips implies global batches (16k+) where plain momentum SGD stops
    converging; LARS is the standard fix for exactly the AlexNet/ResNet
    ImageNet configs this framework benchmarks.

    Per-TENSOR trust ratio η·||p||/||g + wd·p|| scales the lr before the
    momentum update (decay folded into the gradient BEFORE the norm — a
    standard variant; the paper's additive form ||g||+wd·||p|| differs
    whenever g and p aren't parallel).  1-D tensors (biases, BN scales)
    take the plain momentum path, per the paper's practice.  Same design
    rules as :func:`sgd`: lr in state, param-shaped `velocity` entry.
    """

    def init(params: Params) -> OptState:
        return {
            "velocity": jax.tree.map(jnp.zeros_like, params),
            "lr": jnp.asarray(lr, jnp.float32),
            "step": jnp.zeros((), jnp.int32),
        }

    def update(params: Params, grads: Grads, state: OptState):
        lr_t = state["lr"]

        def upd(p, g, v):
            g = g.astype(jnp.float32)
            if weight_decay:
                g = g + weight_decay * p
            if p.ndim >= 2:
                local_lr = lr_t * _trust_ratio(
                    jnp.linalg.norm(p), jnp.linalg.norm(g),
                    trust_coefficient, eps,
                )
            else:
                local_lr = lr_t
            v_new = momentum * v - local_lr * g
            return p + v_new, v_new

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_v = treedef.flatten_up_to(state["velocity"])
        out = [upd(p, g, v) for p, g, v in zip(flat_p, flat_g, flat_v)]
        return treedef.unflatten([o[0] for o in out]), {
            "velocity": treedef.unflatten([o[1] for o in out]),
            "lr": lr_t,
            "step": state["step"] + 1,
        }

    return Optimizer(init, update)


def lamb(
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.0,
) -> Optimizer:
    """LAMB (You et al. 2019, arXiv:1904.00962) — the Adam-family
    counterpart of :func:`lars` (large-batch transformer training).
    Bias-corrected Adam direction r = m̂/(√v̂+ε), decoupled decay folded
    into the update (r + wd·p), then the per-tensor trust ratio
    ||p||/||update|| (trust coefficient 1, as in the paper); 1-D tensors
    skip the ratio."""

    def init(params: Params) -> OptState:
        return {
            "mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "lr": jnp.asarray(lr, jnp.float32),
            "step": jnp.zeros((), jnp.int32),
        }

    def update(params: Params, grads: Grads, state: OptState):
        lr_t = state["lr"]
        t = state["step"] + 1
        c1 = 1.0 - jnp.power(b1, t.astype(jnp.float32))
        c2 = 1.0 - jnp.power(b2, t.astype(jnp.float32))

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m_new = b1 * m + (1.0 - b1) * g
            v_new = b2 * v + (1.0 - b2) * jnp.square(g)
            r = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
            if weight_decay:
                r = r + weight_decay * p
            if p.ndim >= 2:
                scale = _trust_ratio(
                    jnp.linalg.norm(p), jnp.linalg.norm(r), 1.0, 1e-9
                )
            else:
                scale = jnp.asarray(1.0, jnp.float32)
            return p - lr_t * scale * r, m_new, v_new

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["mu"])
        flat_v = treedef.flatten_up_to(state["nu"])
        out = [upd(*a) for a in zip(flat_p, flat_g, flat_m, flat_v)]
        return treedef.unflatten([o[0] for o in out]), {
            "mu": treedef.unflatten([o[1] for o in out]),
            "nu": treedef.unflatten([o[2] for o in out]),
            "lr": lr_t,
            "step": t,
        }

    return Optimizer(init, update)


def from_config(cfg) -> Optimizer:
    """Build the optimizer a model config names (``optimizer`` key:
    'sgd' default, 'adam', 'adamw', 'lars', 'lamb')."""
    name = str(cfg.get("optimizer", "sgd")).lower()
    if name == "sgd":
        return sgd(
            lr=float(cfg.lr),
            momentum=float(cfg.momentum),
            nesterov=bool(cfg.nesterov),
            weight_decay=float(cfg.weight_decay),
        )
    if name in ("adam", "adamw"):
        return adam(
            lr=float(cfg.lr),
            b1=float(cfg.get("adam_b1", 0.9)),
            b2=float(cfg.get("adam_b2", 0.999)),
            eps=float(cfg.get("adam_eps", 1e-8)),
            weight_decay=float(cfg.weight_decay),
            decoupled=(name == "adamw"),
        )
    if name == "lars":
        return lars(
            lr=float(cfg.lr),
            momentum=float(cfg.momentum),
            weight_decay=float(cfg.weight_decay),
            trust_coefficient=float(cfg.get("lars_trust", 0.001)),
        )
    if name == "lamb":
        return lamb(
            lr=float(cfg.lr),
            b1=float(cfg.get("adam_b1", 0.9)),
            b2=float(cfg.get("adam_b2", 0.999)),
            eps=float(cfg.get("adam_eps", 1e-6)),
            weight_decay=float(cfg.weight_decay),
        )
    raise ValueError(
        f"unknown optimizer {name!r} (sgd|adam|adamw|lars|lamb)"
    )


def param_shaped_entries(state: OptState, params_treedef) -> tuple:
    """Top-level state keys whose value mirrors the params pytree
    (velocity, Adam moments, …) — THE discriminator for 'shard/sync this
    entry like a parameter' used by opt-state placement, avg-mode moment
    sync, and ZeRO; keep the rule in one place.

    ``ef_wire`` is excluded by name: its TREE structure matches params
    (it is built by tree_map over them) but its leaves carry a leading
    per-device axis and its values are deliberately different on every
    device — syncing or param-sharding it would destroy the error-
    feedback residuals (models/base.py owns its placement)."""
    return tuple(
        k for k, v in state.items()
        if k != "ef_wire" and jax.tree.structure(v) == params_treedef
    )


def set_lr(state: OptState, lr: float) -> OptState:
    """Host-side lr mutation between steps (reference: shared-var set).

    The new scalar goes where the old one lived: a fresh uncommitted
    scalar among committed step outputs is a second argument signature,
    and the train step then compiles twice — once for the first
    iteration after every ``set_lr``, once for the rest (seen as a second
    ``jit(shard_step)`` compile in PR 21's bring-up)."""
    new = dict(state)
    lr = jnp.asarray(lr, jnp.float32)
    old = state.get("lr")
    if isinstance(old, jax.Array) and old.committed:
        lr = jax.device_put(lr, old.sharding)
    new["lr"] = lr
    return new


def get_lr(state: OptState) -> float:
    return float(state["lr"])


# ---------------------------------------------------------------------------
# learning-rate schedules — host-side functions epoch -> lr, driven by
# model.adjust_hyperp(epoch) exactly like the reference's per-model
# schedules (e.g. AlexNet: /10 at fixed epochs).
# ---------------------------------------------------------------------------

def step_decay(base_lr: float, boundaries, factor: float = 0.1):
    """lr = base * factor^(number of boundaries passed)."""

    boundaries = sorted(boundaries)

    def schedule(epoch: int) -> float:
        n = sum(1 for b in boundaries if epoch >= b)
        return base_lr * (factor**n)

    return schedule


def exp_decay(base_lr: float, rate: float):
    def schedule(epoch: int) -> float:
        return base_lr * (rate**epoch)

    return schedule


def constant(base_lr: float):
    def schedule(epoch: int) -> float:
        return base_lr

    return schedule


def linear_warmup_step(base_lr: float, warmup_epochs: int, boundaries, factor=0.1):
    """Warmup then step decay — used when scaling batch size with workers
    (the reference's `scale_lr` heritage: lr scaled by N workers)."""
    step = step_decay(base_lr, boundaries, factor)

    def schedule(epoch: int) -> float:
        if warmup_epochs and epoch < warmup_epochs:
            return base_lr * float(epoch + 1) / warmup_epochs
        return step(epoch)

    return schedule
