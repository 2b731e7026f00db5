"""Expert parallelism (MoE over the ``ep`` mesh axis).

Acceptance: the ep-sharded path (expert weights sharded, an all-gather
and a reduce-scatter around each device's own experts) is numerically
EQUIVALENT to the unsharded oracle (``ep_axis=None`` — identical routing
math, no collectives), the full model's training trajectory matches a
dense-oracle SGD run, and under any routing, however skewed, every token
is served by every expert it chose (a plain loop over the experts is the
oracle): the layer has no capacity and drops nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from theanompi_tpu.models.moe_mlp import MoeMlpModel
from theanompi_tpu.ops import losses, optim
from theanompi_tpu.parallel.moe import MoeMlp
from theanompi_tpu.runtime.mesh import EP_AXIS, make_mesh
from theanompi_tpu.runtime.recorder import Recorder


def _expert_specs():
    return MoeMlp.param_specs(EP_AXIS)


def _loop_over_experts(layer, params, x):
    """The oracle: route, then every expert in turn over every token,
    weighted by what the token gave it (0 where it was not chosen)."""
    from theanompi_tpu.parallel.moe import route

    idx, w, _ = route(x, params["wg"], top_k=layer.top_k,
                      scoring=layer.scoring, bias=params.get("route_bias"),
                      scale=layer.route_scale)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(layer.n_experts):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)[:, None]
        if layer.gated:
            h = jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e])
            y = y + we * (h @ params["w_down"][e])
        else:
            h = jax.nn.relu(x @ params["w_in"][e] + params["b_in"][e])
            y = y + we * (h @ params["w_out"][e] + params["b_out"][e])
    if layer.n_shared:
        sp = params["shared"]
        y = y + (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    return y, idx


@pytest.mark.parametrize("top_k", [1, 2, 3])
def test_moe_sharded_matches_dense(top_k):
    E, d, h, n = 4, 8, 16, 32
    dense = MoeMlp(E, h, top_k=top_k, ep_axis=None)
    params, _, _ = dense.init(jax.random.PRNGKey(0), (d,))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    y_ref, _ = dense.apply(params, {}, x)

    ep = 4
    mesh = make_mesh(
        shape=(ep,), axis_names=(EP_AXIS,), devices=jax.devices()[:ep]
    )
    sharded = MoeMlp(E, h, top_k=top_k, ep_axis=EP_AXIS, ep_size=ep)

    def f(p, xs):
        y, _ = sharded.apply(p, {}, xs)
        return y

    y = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(_expert_specs(), P(EP_AXIS)),
            out_specs=P(EP_AXIS), check_vma=False,
        )
    )(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)


CFG = dict(
    batch_size=4,  # per (dp, ep) shard; dp=2 × ep=4 -> global 32
    d_model=16,
    d_hidden=32,
    n_experts=4,
    ep=4,
    n_synth_train=64,
    n_synth_val=32,
    print_freq=10_000,
    weight_decay=0.0,
    comm_probe=False,
    moe_aux_coef=0.0,  # the dense oracle models the task loss only
)


def _dense_oracle(model):
    """Forward with the same global params, no collectives."""
    moe_dense = MoeMlp(
        int(model.config.n_experts), int(model.config.d_hidden),
        top_k=int(model.config.top_k), ep_axis=None,
    )

    def forward(params, x):
        from theanompi_tpu.ops import layers as L

        for layer, p in zip(model.net.layers, params):
            if isinstance(layer, L.Residual):
                y, _ = moe_dense.apply(p["body"], {}, x)
                x = x + y
            else:
                x, _ = layer.apply(p, {}, x, train=False, rng=None)
        return x

    return forward


def test_moe_model_matches_dense_training():
    model = MoeMlpModel(config=CFG)
    assert model.ep_size == 4 and model.n_workers == 8
    params0 = jax.device_get(model.params)
    opt = optim.sgd(lr=float(model.config.lr), momentum=float(model.config.momentum))
    opt_state = opt.init(params0)
    forward = _dense_oracle(model)

    model.compile_train()
    rec = Recorder(verbose=False)
    model.reset_train_iter(0)  # shuffles epoch 0
    batches = list(model.data.train_batches())

    p_ref = params0
    for i in range(1, 3):
        loss_pipe, _ = model.train_iter(i, rec)
        x, y = batches[i - 1]

        def loss_fn(p):
            return losses.softmax_cross_entropy(
                forward(p, jnp.asarray(x)), jnp.asarray(y)
            )

        loss_ref, grads = jax.value_and_grad(loss_fn)(p_ref)
        p_ref, opt_state = opt.update(p_ref, grads, opt_state)
        np.testing.assert_allclose(float(loss_pipe), float(loss_ref), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(model.params), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_moe_model_learns():
    model = MoeMlpModel(config=dict(CFG, n_synth_train=512))
    model.compile_train()
    rec = Recorder(verbose=False)
    model.reset_train_iter(0)
    ls = [model.train_iter(i, rec)[0] for i in range(1, 5)]
    assert np.isfinite(ls).all() and float(ls[-1]) < float(ls[0])


def _skewed(layer, params, d, n, favourite=2):
    """Inputs and a router under which ``favourite`` takes most tokens
    and some experts take none."""
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    wg = jnp.zeros_like(params["wg"]).at[:, favourite].set(4.0 * x[0])
    wg = wg.at[:, 0].set(jax.random.normal(jax.random.PRNGKey(5), (d,)))
    x = x.at[: (3 * n) // 4].set(x[0] + 0.05 * x[: (3 * n) // 4])
    return x, dict(params, wg=wg.astype(params["wg"].dtype))


@pytest.mark.parametrize("gated,scoring,top_k", [
    (False, "softmax", 1), (False, "softmax", 2), (True, "sigmoid", 2),
])
def test_skewed_routing_drops_no_token(gated, scoring, top_k):
    """One expert takes most tokens and some take none: the layer's
    output is the plain loop's for EVERY token, and the counts say what
    each expert received."""
    E, d, h, n = 8, 8, 16, 48
    moe = MoeMlp(E, h, top_k=top_k, ep_axis=None, gated=gated,
                 scoring=scoring, n_shared=1 if gated else 0,
                 route_scale=2.0 if gated else 1.0)
    params, _, _ = moe.init(jax.random.PRNGKey(0), (d,))
    x, params = _skewed(moe, params, d, n)
    y, counts, _ = jax.jit(moe.forward)(params, x)
    y_ref, idx = _loop_over_experts(moe, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    want = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    np.testing.assert_array_equal(np.asarray(counts), want)
    assert want.max() >= n // 2 and (want == 0).any()  # skewed indeed
    assert int(counts.sum()) == n * top_k  # nothing dropped


def test_padding_rows_are_not_routed():
    E, d, h, n = 4, 8, 16, 12
    moe = MoeMlp(E, h, top_k=2, ep_axis=None)
    params, _, _ = moe.init(jax.random.PRNGKey(0), (d,))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    valid = jnp.arange(n) < 7
    y, counts, _ = moe.forward(params, x, valid=valid)
    y_ref, _ = _loop_over_experts(moe, params, x)
    assert int(counts.sum()) == 7 * 2
    np.testing.assert_allclose(np.asarray(y[:7]), np.asarray(y_ref[:7]),
                               atol=2e-5)


def test_holders_of_disjoint_experts_sum_to_the_whole_layer():
    """The share test: two layers told they hold experts 0-3 and 4-7,
    each given its own slice of the expert leaves, sum to the layer that
    holds all eight; the shared expert is counted once (by the holder of
    expert 0)."""
    E, d, h, n = 8, 8, 16, 40
    kw = dict(top_k=2, ep_axis=None, gated=True, scoring="sigmoid",
              n_shared=1, route_scale=2.0)
    whole = MoeMlp(E, h, **kw)
    params, _, _ = whole.init(jax.random.PRNGKey(0), (d,))
    params["route_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (E,))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    y, counts, _ = whole.forward(params, x)

    def part(first):
        sl = {k: v[first:first + 4] if k in ("w_gate", "w_up", "w_down")
              else v for k, v in params.items()}
        return MoeMlp(E, h, experts_held=(first, 4), **kw).forward(sl, x)

    (y0, c0, _), (y1, c1, _) = part(0), part(4)
    np.testing.assert_allclose(np.asarray(y0 + y1), np.asarray(y), atol=2e-5)
    np.testing.assert_array_equal(np.concatenate([c0, c1]), np.asarray(counts))
    # the second holder adds no shared expert: with no routed token of
    # its own (all weights to experts 0-3) its part is zero
    only_low = dict(params, route_bias=jnp.where(jnp.arange(E) < 4, 10.0, -10.0))
    sl = {k: v[4:] if k in ("w_gate", "w_up", "w_down") else v
          for k, v in only_low.items()}
    y_hi, c_hi, _ = MoeMlp(E, h, experts_held=(4, 4), **kw).forward(sl, x)
    assert int(c_hi.sum()) == 0 and not np.asarray(y_hi).any()


def test_selection_bias_steers_the_choice_and_not_the_weight():
    E, d, h, n = 8, 8, 16, 32
    moe = MoeMlp(E, h, top_k=2, ep_axis=None, gated=True, scoring="sigmoid",
                 route_scale=2.0)
    params, _, _ = moe.init(jax.random.PRNGKey(0), (d,))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    from theanompi_tpu.parallel.moe import route

    kw = dict(top_k=2, scoring="sigmoid", scale=2.0)
    idx0, w0, s0 = route(x, params["wg"], bias=None, **kw)
    bias = jnp.zeros((E,)).at[5].set(10.0)
    idx1, w1, _ = route(x, params["wg"], bias=bias, **kw)
    assert (np.asarray(idx1) == 5).any(axis=1).all()  # 5 is always chosen
    assert not (np.asarray(idx0) == 5).any(axis=1).all()
    # weights: the chosen scores WITHOUT the bias, renormalised, times 2
    chosen = np.take_along_axis(np.asarray(s0), np.asarray(idx1), axis=1)
    np.testing.assert_allclose(
        np.asarray(w1), 2.0 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w0).sum(1), 2.0, rtol=1e-5)


def test_pallas_experts_match_the_xla_form():
    """The grouped-product kernel (interpret mode here) against its XLA
    form through the whole layer, skewed routing included."""
    E, d, h, n = 8, 8, 16, 40
    kw = dict(top_k=2, ep_axis=None, gated=True, scoring="sigmoid",
              n_shared=1)
    moe = MoeMlp(E, h, **kw)
    params, _, _ = moe.init(jax.random.PRNGKey(0), (d,))
    x, params = _skewed(moe, params, d, n)
    y, c, _ = moe.forward(params, x)
    yk, ck, _ = moe.forward(params, x, impl="pallas")
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(c))


def test_aux_loss_engaged_in_training():
    """With moe_aux_coef > 0 the train loss includes the load-balance
    term (≥1 by Cauchy-Schwarz), and it rides the state tree."""
    one = jax.devices()[:1]  # outside shard_map -> unsharded (ep=1) path
    m0 = MoeMlpModel(
        config=dict(CFG, seed=11, ep=1),
        mesh=MoeMlpModel.build_mesh(devices=one, config=dict(ep=1)),
    )
    m1 = MoeMlpModel(
        config=dict(CFG, seed=11, ep=1, moe_aux_coef=0.5),
        mesh=MoeMlpModel.build_mesh(devices=one, config=dict(ep=1)),
    )
    x, y = next(iter(m0.data.train_batches()))
    import jax.numpy as jnp

    args = (jnp.asarray(x)[:8], jnp.asarray(y)[:8], True, jax.random.PRNGKey(0))
    l0, (_, _, st) = m0.loss_and_metrics(m0.params, m0.net_state, *args)
    l1, _ = m1.loss_and_metrics(m1.params, m1.net_state, *args)
    aux = MoeMlp.collect_aux_losses(st)
    assert len(aux) == 1 and float(aux[0]) >= 0.99
    np.testing.assert_allclose(
        float(l1), float(l0) + 0.5 * float(aux[0]), rtol=1e-5
    )


def test_aux_load_balance_loss():
    E, d, h = 4, 8, 16
    moe = MoeMlp(E, h, ep_axis=None)
    params, _, _ = moe.init(jax.random.PRNGKey(0), (d,))
    x = jax.random.normal(jax.random.PRNGKey(1), (256, d))
    aux = float(moe.aux_load_balance_loss(params, x))
    assert np.isfinite(aux) and aux >= 0.9  # =1 at perfectly uniform routing


def test_moe_validation_errors():
    with pytest.raises(ValueError, match="top_k"):
        MoeMlp(4, 8, top_k=5)
    with pytest.raises(ValueError, match="experts_held"):
        MoeMlp(4, 8, ep_axis=None, experts_held=(2, 4))
    with pytest.raises(ValueError, match="scoring"):
        MoeMlp(4, 8, scoring="tanh")
    with pytest.raises(ValueError, match="divisible"):
        MoeMlp(3, 8, ep_axis=EP_AXIS, ep_size=2)
    with pytest.raises(ValueError, match="ep="):
        MoeMlpModel(config=dict(CFG), mesh=make_mesh())  # dp-only mesh
