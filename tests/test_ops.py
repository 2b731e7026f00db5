import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import losses, optim


KEY = jax.random.PRNGKey(0)


def test_conv2d_shapes_and_mixed_precision_flow():
    layer = L.Conv2d(8, 3, stride=2, padding="SAME", compute_dtype=jnp.bfloat16)
    p, s, out = layer.init(KEY, (16, 16, 3))
    assert out == (8, 8, 8)
    x = jnp.ones((2, 16, 16, 3))
    y, _ = layer.apply(p, s, x)
    assert y.shape == (2, 8, 8, 8)
    # activations FLOW in compute_dtype (half the HBM bytes downstream);
    # master params stay fp32
    assert y.dtype == jnp.bfloat16
    assert p["w"].dtype == jnp.float32
    # a logits head opts back into fp32
    head = L.Conv2d(8, 3, compute_dtype=jnp.bfloat16, output_dtype=jnp.float32)
    hp, hs, _ = head.init(KEY, (16, 16, 3))
    hy, _ = head.apply(hp, hs, x)
    assert hy.dtype == jnp.float32


def test_conv2d_valid_padding_shape():
    layer = L.Conv2d(4, 5, stride=1, padding="VALID")
    p, s, out = layer.init(KEY, (12, 12, 3))
    assert out == (8, 8, 4)
    y, _ = layer.apply(p, s, jnp.zeros((1, 12, 12, 3)))
    assert y.shape[1:] == out


def test_dense():
    layer = L.Dense(10)
    p, s, out = layer.init(KEY, (32,))
    assert out == (10,)
    y, _ = layer.apply(p, s, jnp.ones((4, 32)))
    assert y.shape == (4, 10)


def test_pools():
    mp = L.MaxPool(2)
    p, s, out = mp.init(KEY, (8, 8, 3))
    assert out == (4, 4, 3)
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    y, _ = mp.apply({}, {}, x)
    np.testing.assert_allclose(np.asarray(y)[0, :, :, 0], [[5, 7], [13, 15]])
    ap = L.AvgPool(2)
    y2, _ = ap.apply({}, {}, x)
    np.testing.assert_allclose(np.asarray(y2)[0, :, :, 0], [[2.5, 4.5], [10.5, 12.5]])


def test_global_avg_pool():
    g = L.GlobalAvgPool()
    _, _, out = g.init(KEY, (7, 7, 64))
    assert out == (64,)
    y, _ = g.apply({}, {}, jnp.ones((2, 7, 7, 64)) * 3.0)
    np.testing.assert_allclose(np.asarray(y), 3.0)


def test_lrn_matches_manual():
    lrn = L.LRN(size=3, alpha=1e-4, beta=0.75, k=2.0)
    x = jax.random.normal(KEY, (2, 4, 4, 6))
    y, _ = lrn.apply({}, {}, x)
    xn = np.asarray(x)
    # manual cross-channel window sum
    sq = xn**2
    out = np.zeros_like(xn)
    C = xn.shape[-1]
    for c in range(C):
        lo, hi = max(0, c - 1), min(C, c + 2)
        denom = (2.0 + 1e-4 * sq[..., lo:hi].sum(-1)) ** 0.75
        out[..., c] = xn[..., c] / denom
    np.testing.assert_allclose(np.asarray(y), out, rtol=1e-5)


@pytest.mark.parametrize("size", [3, 4])  # even size: asymmetric window
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_lrn_impls_match_window_baseline(impl, size):
    """Every LRN implementation must reproduce the literal
    pad+reduce_window baseline — forward and gradients, odd AND even
    window sizes. M = B·H·W = 32 rows exercises the Pallas kernel's
    pad-to-512-rows-and-slice path."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 4, 6), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    li = L.LRN(size=size, k=2.0, impl=impl)
    lw = L.LRN(size=size, k=2.0, impl="window")
    yi, _ = li.apply({}, {}, x)
    yw, _ = lw.apply({}, {}, x)
    np.testing.assert_allclose(np.asarray(yi), np.asarray(yw), atol=5e-5, rtol=5e-5)
    gi = jax.grad(lambda a: jnp.sum(li.apply({}, {}, a)[0] * w))(x)
    gw = jax.grad(lambda a: jnp.sum(lw.apply({}, {}, a)[0] * w))(x)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(gw), atol=5e-5, rtol=5e-5)


def test_lrn_bad_impl_raises():
    with pytest.raises(ValueError, match="impl"):
        L.LRN(impl="cuda")


def test_batchnorm_train_and_eval():
    bn = L.BatchNorm(momentum=0.5)
    p, s, _ = bn.init(KEY, (4,))
    x = jax.random.normal(KEY, (64, 4)) * 3.0 + 1.0
    y, s1 = bn.apply(p, s, x, train=True)
    # normalized output: ~zero mean, unit var
    np.testing.assert_allclose(np.asarray(y.mean(0)), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y.var(0)), 1.0, atol=1e-2)
    # running stats moved toward batch stats
    assert not np.allclose(np.asarray(s1["mean"]), 0.0)
    # eval mode uses running stats and does not change state
    y2, s2 = bn.apply(p, s1, x, train=False)
    assert s2 is s1


def test_batchnorm_train_flag_is_trace_time_static():
    """Baseline burn-down regression (graftlint GL-C002): BatchNorm's
    train/eval branch changes the collective sequence (sync-BN pmean
    pair), so the flag is now validated as a trace-time static.
    Concrete truthy values behave exactly as before; a traced flag
    fails fast with a targeted TypeError."""
    bn = L.BatchNorm(momentum=0.5)
    p, s, _ = bn.init(KEY, (4,))
    x = jax.random.normal(KEY, (16, 4)) * 2.0 + 0.5
    y_bool, s_bool = bn.apply(p, s, x, train=True)
    # numpy bools / ints coerce like they always did
    y_np, s_np = bn.apply(p, s, x, train=np.bool_(True))
    np.testing.assert_array_equal(np.asarray(y_bool), np.asarray(y_np))
    for k in s_bool:
        np.testing.assert_array_equal(
            np.asarray(s_bool[k]), np.asarray(s_np[k])
        )
    y_eval0, _ = bn.apply(p, s_bool, x, train=0)
    y_evalF, _ = bn.apply(p, s_bool, x, train=False)
    np.testing.assert_array_equal(np.asarray(y_eval0), np.asarray(y_evalF))
    # a TRACED flag is rejected at trace time, naming the flag —
    # before this fix it died as TracerBoolConversionError (or, through
    # shard_map, a per-worker divergent pmean: a hang)
    with pytest.raises(TypeError, match="trace-time-static"):
        jax.jit(lambda t: bn.apply(p, s, x, train=t))(jnp.asarray(True))
    # under jit with the flag baked in, output is unchanged
    f = jax.jit(lambda xx: bn.apply(p, s, xx, train=True)[0])
    np.testing.assert_allclose(
        np.asarray(f(x)), np.asarray(y_bool), rtol=1e-6, atol=1e-6
    )


def test_static_bool_helper():
    assert L.static_bool(np.bool_(False)) is False
    assert L.static_bool(1) is True
    with pytest.raises(TypeError, match="my_flag"):
        jax.jit(lambda t: L.static_bool(t, "my_flag"))(jnp.asarray(True))


def test_dropout():
    d = L.Dropout(0.5)
    x = jnp.ones((1000,))
    y, _ = d.apply({}, {}, x, train=True, rng=KEY)
    kept = float((np.asarray(y) > 0).mean())
    assert 0.4 < kept < 0.6
    np.testing.assert_allclose(np.asarray(y).max(), 2.0)  # inverted scaling
    y_eval, _ = d.apply({}, {}, x, train=False)
    np.testing.assert_allclose(np.asarray(y_eval), np.asarray(x))
    with pytest.raises(ValueError):
        d.apply({}, {}, x, train=True, rng=None)


def test_sequential_and_flatten():
    net = L.Sequential(
        [
            L.Conv2d(4, 3),
            L.Relu(),
            L.MaxPool(2),
            L.Flatten(),
            L.Dense(10),
        ]
    )
    p, s, out = net.init(KEY, (8, 8, 3))
    assert out == (10,)
    y, s1 = net.apply(p, s, jnp.ones((2, 8, 8, 3)), train=True, rng=KEY)
    assert y.shape == (2, 10)
    assert len(s1) == len(net.layers)


def test_parallel_concat():
    block = L.Parallel(
        [
            L.Conv2d(4, 1),
            L.Sequential([L.Conv2d(2, 1), L.Relu(), L.Conv2d(6, 3)]),
        ]
    )
    p, s, out = block.init(KEY, (8, 8, 3))
    assert out == (8, 8, 10)
    y, _ = block.apply(p, s, jnp.ones((2, 8, 8, 3)))
    assert y.shape == (2, 8, 8, 10)


def test_parallel_shape_mismatch():
    block = L.Parallel([L.Conv2d(4, 1), L.MaxPool(2)])
    with pytest.raises(ValueError):
        block.init(KEY, (8, 8, 3))


def test_losses_match_manual():
    logits = jnp.array([[2.0, 0.0, -1.0], [0.0, 3.0, 0.0]])
    labels = jnp.array([0, 2])
    ce = losses.softmax_cross_entropy(logits, labels)
    lp = np.log(np.exp(np.asarray(logits)) / np.exp(np.asarray(logits)).sum(-1, keepdims=True))
    np.testing.assert_allclose(float(ce), -(lp[0, 0] + lp[1, 2]) / 2, rtol=1e-6)
    err = losses.classification_error(logits, labels)
    assert float(err) == 0.5
    err5 = losses.topk_error(logits, labels, k=2)
    assert float(err5) == 0.5  # label 2 not in top-2 of row 1


def test_sgd_momentum_matches_numpy():
    opt = optim.sgd(lr=0.1, momentum=0.9, weight_decay=0.01)
    params = {"w": jnp.ones((3,))}
    state = opt.init(params)
    grads = {"w": jnp.full((3,), 0.5)}
    # numpy reference
    w, v = np.ones(3), np.zeros(3)
    for _ in range(3):
        g = 0.5 + 0.01 * w
        v = 0.9 * v - 0.1 * g
        w = w + v
    p = params
    for _ in range(3):
        g = {"w": jnp.full((3,), 0.5)}
        p, state = opt.update(p, g, state)
    np.testing.assert_allclose(np.asarray(p["w"]), w, rtol=1e-5)
    assert int(state["step"]) == 3


def test_sgd_nesterov_runs_and_lr_set():
    opt = optim.sgd(lr=0.1, momentum=0.9, nesterov=True)
    params = {"w": jnp.ones((2,))}
    state = opt.init(params)
    p, state = opt.update(params, {"w": jnp.ones((2,))}, state)
    assert not np.allclose(np.asarray(p["w"]), 1.0)
    state = optim.set_lr(state, 0.001)
    assert optim.get_lr(state) == pytest.approx(0.001)


def test_sgd_update_is_jittable():
    opt = optim.sgd(lr=0.05, momentum=0.9)
    params = {"w": jnp.ones((4, 4))}
    state = opt.init(params)

    @jax.jit
    def step(p, g, s):
        return opt.update(p, g, s)

    p1, s1 = step(params, {"w": jnp.ones((4, 4))}, state)
    # lr change must NOT retrigger compile-sensitive behavior (it's a leaf)
    s1 = optim.set_lr(s1, 0.01)
    p2, s2 = step(p1, {"w": jnp.ones((4, 4))}, s1)
    assert float(s2["lr"]) == pytest.approx(0.01)


@pytest.mark.parametrize("window,stride", [(2, 2), (3, 2), (3, 1)])
def test_maxpool_mask_grad_matches_native(window, stride):
    """Mask-based maxpool backward == select-and-scatter backward on
    tie-free inputs (random floats; ties measure-zero)."""
    from theanompi_tpu.ops.layers import MaxPool

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 9, 3))

    def loss(x, impl):
        pool = MaxPool(window, stride=stride, grad_impl=impl)
        y, _ = pool.apply({}, {}, x)
        return jnp.sum(jnp.square(y)), y

    (l_m, y_m), g_m = jax.value_and_grad(loss, has_aux=True)(x, "mask")
    (l_n, y_n), g_n = jax.value_and_grad(loss, has_aux=True)(x, "native")
    np.testing.assert_array_equal(np.asarray(y_m), np.asarray(y_n))
    np.testing.assert_allclose(np.asarray(g_m), np.asarray(g_n), atol=1e-6)


def test_maxpool_mask_tie_conserves_cotangent():
    """On ties the mask impl splits the cotangent across tied maxima —
    a valid subgradient; per-window cotangent mass is conserved."""
    from theanompi_tpu.ops.layers import MaxPool

    x = jnp.zeros((1, 4, 4, 1))  # all tied

    def loss(x):
        y, _ = MaxPool(2, stride=2, grad_impl="mask").apply({}, {}, x)
        return jnp.sum(y)

    g = jax.grad(loss)(x)
    # 4 windows, each distributing cotangent 1 over its 4 tied entries
    np.testing.assert_allclose(float(jnp.sum(g)), 4.0)


def test_maxpool_mask_rejects_same_padding():
    from theanompi_tpu.ops.layers import MaxPool

    with pytest.raises(ValueError, match="VALID"):
        MaxPool(3, stride=2, padding="SAME", grad_impl="mask")
    with pytest.raises(ValueError, match="VALID"):
        MaxPool(3, stride=2, padding="SAME", grad_impl="pallas")


@pytest.mark.parametrize("window,stride", [(2, 2), (3, 2), (3, 1)])
def test_maxpool_pallas_grad_matches_native(window, stride):
    """Single-pass Pallas backward (ops/pallas_pool.py, interpret mode
    on CPU) == select-and-scatter backward on tie-free inputs — the r5
    kernel answer to the 7% pool-bwd budget line."""
    from theanompi_tpu.ops.layers import MaxPool

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 9, 3))

    def loss(x, impl):
        pool = MaxPool(window, stride=stride, grad_impl=impl)
        y, _ = pool.apply({}, {}, x)
        return jnp.sum(jnp.square(y)), y

    (l_p, y_p), g_p = jax.value_and_grad(loss, has_aux=True)(x, "pallas")
    (l_n, y_n), g_n = jax.value_and_grad(loss, has_aux=True)(x, "native")
    np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_n))
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_n), atol=1e-6)


def test_maxpool_pallas_tie_split_and_batch_padding():
    """Equal tie split conserves cotangent mass (mask semantics), and a
    batch that doesn't divide the kernel's block size exercises the
    zero-padded grid rows."""
    from theanompi_tpu.ops.layers import MaxPool
    from theanompi_tpu.ops import pallas_pool

    x = jnp.zeros((1, 4, 4, 1))  # all tied

    def loss(x):
        y, _ = MaxPool(2, stride=2, grad_impl="pallas").apply({}, {}, x)
        return jnp.sum(y)

    g = jax.grad(loss)(x)
    np.testing.assert_allclose(float(jnp.sum(g)), 4.0)
    # agreement with the mask impl on ties (same equal-split semantics)
    def loss_m(x):
        y, _ = MaxPool(2, stride=2, grad_impl="mask").apply({}, {}, x)
        return jnp.sum(y)

    np.testing.assert_allclose(np.asarray(jax.grad(loss_m)(x)), np.asarray(g))

    # force multiple grid blocks + padding: a budget of two images'
    # planes makes nb=2 < n=3
    old = pallas_pool._VMEM_BUDGET
    pallas_pool._VMEM_BUDGET = 2 * pallas_pool._image_bytes(9, 9)
    try:
        xr = jax.random.normal(jax.random.PRNGKey(3), (3, 9, 9, 2))

        def loss_r(x, impl):
            y, _ = MaxPool(3, stride=2, grad_impl=impl).apply({}, {}, x)
            return jnp.sum(jnp.square(y))

        g_p = jax.grad(lambda x: loss_r(x, "pallas"))(xr)
        g_n = jax.grad(lambda x: loss_r(x, "native"))(xr)
        np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_n), atol=1e-6)
    finally:
        pallas_pool._VMEM_BUDGET = old


def test_adam_matches_numpy():
    opt = optim.adam(lr=0.01, b1=0.9, b2=0.999, eps=1e-8)
    params = {"w": jnp.ones((3,))}
    state = opt.init(params)
    g = np.full(3, 0.5)
    p = params
    for _ in range(3):
        p, state = opt.update(p, {"w": jnp.full((3,), 0.5)}, state)
    # folded-correction form: step = -lr*sqrt(c2)/c1 * m/(sqrt(v)+eps)
    # (standard Adam up to eps placement) — replay it exactly in numpy
    w2 = np.ones(3)
    m2 = np.zeros(3)
    v2 = np.zeros(3)
    for t in range(1, 4):
        m2 = 0.9 * m2 + 0.1 * g
        v2 = 0.999 * v2 + 0.001 * g * g
        scale = 0.01 * np.sqrt(1 - 0.999**t) / (1 - 0.9**t)
        w2 = w2 - scale * m2 / (np.sqrt(v2) + 1e-8)
    np.testing.assert_allclose(np.asarray(p["w"]), w2, rtol=1e-6)
    assert int(state["step"]) == 3


def test_set_lr_keeps_the_old_scalars_placement():
    """A fresh uncommitted lr among committed step outputs is a second
    argument signature: the train step then compiled twice, once for the
    first iteration after every ``set_lr`` (PR 21 bring-up)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.runtime.mesh import make_mesh

    sh = NamedSharding(make_mesh(), P())
    state = {"lr": jax.device_put(jnp.float32(0.1), sh), "step": 0}
    new = optim.set_lr(state, 0.01)
    assert new["lr"].committed and new["lr"].sharding == sh
    np.testing.assert_allclose(float(new["lr"]), 0.01)
    assert state["lr"] is not new["lr"] and float(state["lr"]) == \
        pytest.approx(0.1)
    # a host-side state (no placement to keep) still works
    assert float(optim.set_lr({"lr": 0.1}, 0.5)["lr"]) == 0.5


def test_adamw_decoupled_decay():
    """AdamW: decay scales with lr and params, independent of the moments."""
    opt = optim.adam(lr=0.1, weight_decay=0.1, decoupled=True)
    params = {"w": jnp.full((2,), 2.0)}
    state = opt.init(params)
    p, _ = opt.update(params, {"w": jnp.zeros((2,))}, state)
    # zero grads: the only movement is -lr*wd*p = -0.1*0.1*2 = -0.02
    np.testing.assert_allclose(np.asarray(p["w"]), 2.0 - 0.02, rtol=1e-6)

    classic = optim.adam(lr=0.1, weight_decay=0.1, decoupled=False)
    state_c = classic.init(params)
    p_c, _ = classic.update(params, {"w": jnp.zeros((2,))}, state_c)
    # classic L2 feeds wd*p through the moments (different trajectory)
    assert not np.allclose(np.asarray(p_c["w"]), np.asarray(p["w"]))


def test_optimizer_from_config_in_model():
    """optimizer='adamw' flows through the model contract: compile,
    step, lr scheduling via adjust_hyperp, checkpoint roundtrip."""
    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.runtime.mesh import make_mesh
    from theanompi_tpu.runtime.recorder import Recorder

    model = Cifar10_model(
        config=dict(
            batch_size=8, n_synth_train=256, n_synth_val=64,
            optimizer="adamw", lr=1e-3, print_freq=1000, comm_probe=False,
        ),
        mesh=make_mesh(),
    )
    model.compile_train()
    model.reset_train_iter(0)
    rec = Recorder(verbose=False)
    losses = [model.train_iter(i, rec)[0] for i in range(1, 5)]
    assert np.isfinite(losses).all() and "mu" in model.opt_state
    model.adjust_hyperp(0)
    assert float(model.opt_state["lr"]) == pytest.approx(1e-3)


def test_schedules():
    sch = optim.step_decay(0.1, [2, 4], 0.1)
    assert sch(0) == pytest.approx(0.1)
    assert sch(2) == pytest.approx(0.01)
    assert sch(4) == pytest.approx(0.001)
    w = optim.linear_warmup_step(0.8, 4, [10])
    assert w(0) == pytest.approx(0.2)
    assert w(3) == pytest.approx(0.8)
    assert w(10) == pytest.approx(0.08)
    assert optim.exp_decay(1.0, 0.5)(2) == pytest.approx(0.25)
    assert optim.constant(0.3)(99) == pytest.approx(0.3)


def test_count_params():
    net = L.Sequential([L.Dense(4), L.Dense(2)])
    p, _, _ = net.init(KEY, (3,))
    assert L.count_params(p) == (3 * 4 + 4) + (4 * 2 + 2)


def test_bf16_compute_backward_is_well_typed():
    net = L.Sequential(
        [
            L.Conv2d(4, 3, compute_dtype=jnp.bfloat16),
            L.Relu(),
            L.Flatten(),
            L.Dense(2, compute_dtype=jnp.bfloat16),
        ]
    )
    p, s, _ = net.init(KEY, (8, 8, 3))

    def loss(p):
        y, _ = net.apply(p, s, jnp.ones((2, 8, 8, 3)))
        return jnp.sum(y**2)

    g = jax.grad(loss)(p)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf, dtype=np.float32)).all()


def test_convtranspose_bf16_backward():
    net = L.ConvTranspose2d(3, 4, stride=2, compute_dtype=jnp.bfloat16)
    p, s, out = net.init(KEY, (4, 4, 8))
    assert out == (8, 8, 3)

    def loss(p):
        y, _ = net.apply(p, s, jnp.ones((2, 4, 4, 8)))
        return jnp.mean(y**2)

    g = jax.grad(loss)(p)
    assert np.isfinite(np.asarray(jax.tree.leaves(g)[0], np.float32)).all()


# ---------------------------------------------------------------------------
# space-to-depth conv (r4 perf path: MXU-friendly strided stems)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "kernel,stride,padding,hw",
    [
        (11, 4, "SAME", (32, 32)),   # the AlexNet-128 stem (pad 3/4)
        (7, 2, "SAME", (16, 16)),    # ResNet-style stem
        (4, 4, "VALID", (16, 16)),   # patchify (ViT-style), zero pad
        (5, (2, 4), "SAME", (12, 16)),  # anisotropic stride
        (3, 2, ((2, 2), (1, 1)), (8, 8)),  # explicit padding
    ],
)
def test_conv_s2d_matches_plain_conv(kernel, stride, padding, hw):
    """s2d computes the SAME dot products as the strided conv (fwd and
    both grads) — only the accumulation order differs, so fp32 agreement
    is to float-roundoff."""
    plain = L.Conv2d(8, kernel, stride=stride, padding=padding)
    s2d = L.Conv2d(8, kernel, stride=stride, padding=padding, s2d=True)
    p, st, out_shape = plain.init(KEY, (*hw, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, *hw, 3))

    y_plain, _ = plain.apply(p, st, x)
    y_s2d, _ = s2d.apply(p, st, x)
    assert y_s2d.shape == y_plain.shape == (2, *out_shape)
    np.testing.assert_allclose(y_s2d, y_plain, rtol=2e-5, atol=2e-5)

    def loss(layer, p, x):
        y, _ = layer.apply(p, st, x)
        return jnp.sum(jnp.sin(y))  # nonuniform cotangent

    gp, gx = jax.grad(lambda p, x: loss(plain, p, x), argnums=(0, 1))(p, x)
    sp, sx = jax.grad(lambda p, x: loss(s2d, p, x), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(sp["w"], gp["w"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(sp["b"], gp["b"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(sx, gx, rtol=2e-4, atol=2e-5)


def test_conv_s2d_bf16_flow_matches_plain_bf16():
    plain = L.Conv2d(8, 11, stride=4, compute_dtype=jnp.bfloat16)
    s2d = L.Conv2d(8, 11, stride=4, compute_dtype=jnp.bfloat16, s2d=True)
    p, st, _ = plain.init(KEY, (32, 32, 3))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3))
    y_plain, _ = plain.apply(p, st, x)
    y_s2d, _ = s2d.apply(p, st, x)
    assert y_s2d.dtype == y_plain.dtype
    np.testing.assert_allclose(
        np.asarray(y_s2d, np.float32), np.asarray(y_plain, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_conv_s2d_rejects_indivisible_input_and_unit_stride():
    with pytest.raises(ValueError, match="strided"):
        L.Conv2d(8, 3, stride=1, s2d=True)
    layer = L.Conv2d(8, 11, stride=4, s2d=True)
    with pytest.raises(ValueError, match="divisible"):
        layer.init(KEY, (30, 30, 3))  # at init, not at jit trace time


def test_lrn_pallas_rejects_narrow_stats_and_remat():
    with pytest.raises(ValueError, match="pallas"):
        L.LRN(impl="pallas", stats_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="pallas"):
        L.LRN(impl="pallas", remat=True)


def test_lrn_bf16_stats_close_to_f32():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 4, 16)) * 2.0
    ref = L.LRN(size=5, k=2.0)
    narrow = L.LRN(size=5, k=2.0, stats_dtype=jnp.bfloat16)
    y_ref, _ = ref.apply({}, {}, x)
    y_n, _ = narrow.apply({}, {}, x)
    assert y_n.dtype == x.dtype  # flowing dtype unchanged
    # denominator carries bf16 relative error (~0.4%), amplified by ~beta
    np.testing.assert_allclose(y_n, y_ref, rtol=2e-2, atol=2e-2)
    # and the narrow path must also hold under bf16 activations
    xb = x.astype(jnp.bfloat16)
    yb, _ = narrow.apply({}, {}, xb)
    assert yb.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(yb, np.float32), y_ref, rtol=5e-2, atol=5e-2
    )


def test_conv_rejects_unmodeled_padding_strings_at_init():
    """_conv_out_hw resolves strings through _explicit_padding, so an
    unmodeled spec (SAME_LOWER) is refused when the architecture is
    built — for the plain path too, where init used to silently report
    a VALID shape that lax's apply would then contradict."""
    for s2d in (False, True):
        layer = L.Conv2d(4, 3, stride=2, padding="SAME_LOWER", s2d=s2d)
        with pytest.raises(ValueError, match="padding"):
            layer.init(KEY, (8, 8, 3))


def test_lars_matches_numpy_and_skips_1d():
    """LARS oracle: trust ratio η||p||/||g+wd·p|| scales the lr for
    matrices; 1-D tensors take the plain momentum path."""
    opt = optim.lars(lr=0.1, momentum=0.9, weight_decay=0.01,
                     trust_coefficient=0.001)
    params = {"w": jnp.full((2, 2), 2.0), "b": jnp.full((2,), 2.0)}
    state = opt.init(params)
    grads = {"w": jnp.full((2, 2), 0.5), "b": jnp.full((2,), 0.5)}
    p, state = opt.update(params, grads, state)
    p, state = opt.update(p, grads, state)

    w, b = np.full((2, 2), 2.0), np.full(2, 2.0)
    vw, vb = np.zeros((2, 2)), np.zeros(2)
    for _ in range(2):
        gw = 0.5 + 0.01 * w
        ratio = 0.001 * np.linalg.norm(w) / (np.linalg.norm(gw) + 1e-9)
        vw = 0.9 * vw - 0.1 * ratio * gw
        w = w + vw
        gb = 0.5 + 0.01 * b
        vb = 0.9 * vb - 0.1 * gb  # no ratio on 1-D
        b = b + vb
    np.testing.assert_allclose(np.asarray(p["w"]), w, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p["b"]), b, rtol=1e-6)
    assert int(state["step"]) == 2


def test_lamb_matches_numpy():
    opt = optim.lamb(lr=0.01, b1=0.9, b2=0.999, eps=1e-6, weight_decay=0.1)
    params = {"w": jnp.full((2, 3), 1.0)}
    state = opt.init(params)
    g = np.full((2, 3), 0.25)
    p = params
    for _ in range(3):
        p, state = opt.update(p, {"w": jnp.full((2, 3), 0.25)}, state)

    w = np.full((2, 3), 1.0)
    m = np.zeros((2, 3))
    v = np.zeros((2, 3))
    for t in range(1, 4):
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        r = (m / (1 - 0.9**t)) / (np.sqrt(v / (1 - 0.999**t)) + 1e-6)
        r = r + 0.1 * w
        scale = np.linalg.norm(w) / (np.linalg.norm(r) + 1e-9)
        w = w - 0.01 * scale * r
    np.testing.assert_allclose(np.asarray(p["w"]), w, rtol=1e-5)


def test_lars_lamb_zero_norm_guard_and_from_config():
    """Zero-init params / zero updates must not freeze or NaN the layer
    (ratio defined as 1), and the config names resolve."""
    from theanompi_tpu.runtime.config import Config

    for name in ("lars", "lamb"):
        opt = optim.from_config(Config(dict(
            optimizer=name, lr=0.1, momentum=0.9, nesterov=False,
            weight_decay=0.0,
        )))
        params = {"w": jnp.zeros((2, 2))}
        state = opt.init(params)
        p, _ = opt.update(params, {"w": jnp.ones((2, 2))}, state)
        assert np.isfinite(np.asarray(p["w"])).all(), name
        assert not np.array_equal(np.asarray(p["w"]), 0.0), name
    with pytest.raises(ValueError, match="lamb"):
        optim.from_config(Config(dict(optimizer="lion", lr=0.1)))
