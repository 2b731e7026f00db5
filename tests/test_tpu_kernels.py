"""Every Pallas kernel against its XLA oracle — interpreted here,
Mosaic-compiled on the chip.

The cases live in ``theanompi_tpu/ops/kernel_cases.py``; ``chip_smoke.py``
runs the same list in its *kernels* phase, so there is one copy.

- In the CPU suite each case runs at its ``"tiny"`` shapes with the
  kernel in interpret mode: it proves kernel *math*.
- On a chip the same test runs the ``"real"`` shapes compiled, and
  ``check_case`` insists on ``tpu_custom_call`` in the lowered text.
  Send it through the chip tool as ONE process (a chip belongs to one
  process at a time):

      THEANOMPI_TPU_TESTS=1 python -m pytest tests/test_tpu_kernels.py -m tpu -q

  ``THEANOMPI_TPU_TESTS=1`` stops conftest.py from pinning the CPU
  platform and runs only ``tpu``-marked tests.

Whether Mosaic *lowers* each kernel is also asked without a chip, in
``tests/test_tpu_compile.py``.  The tests below the cases need the chip
itself (and, for the wire assertions, at least two); they skip from a
fixture — nothing here touches a backend while the module is imported.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import kernel_cases, platform

pytestmark = pytest.mark.tpu

# names only: building the list imports modules, touches no backend
CASE_NAMES = [c.name for c in kernel_cases.cases("tiny")]


@pytest.fixture(scope="module")
def size():
    """'real' shapes compiled on a chip, 'tiny' interpreted on the CPU."""
    return "real" if platform.on_tpu() else "tiny"


@pytest.fixture(scope="module")
def chip():
    if not platform.on_tpu():
        pytest.skip("needs a TPU (THEANOMPI_TPU_TESTS=1; see module docstring)")


@pytest.fixture(scope="module")
def two_chips(chip):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 chips")


def _rand_qkv(key, b=2, t=64, h=4, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, t, h, d), dtype)  # noqa: E731
    return mk(kq), mk(kk), mk(kv)


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_matches_its_xla_oracle(name, size):
    case = {c.name: c for c in kernel_cases.cases(size)}[name]
    out = kernel_cases.check_case(case)
    assert out["compiled"] == platform.on_tpu()


# -- ring-SP flash backward on a real multi-chip mesh ------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_grads_compiled_multichip(causal, two_chips):
    """The blockwise FA-2 ring backward (traveling dk/dv accumulators)
    over a REAL sp axis — the CPU suite proves this in interpret mode
    only (test_flash.py::test_ring_flash_grads_match_dense)."""
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel.ring_attention import (
        SEQ_AXIS, full_attention, ring_attention,
    )
    from theanompi_tpu.runtime.mesh import make_mesh

    sp = 2
    mesh = make_mesh(
        shape=(sp,), axis_names=(SEQ_AXIS,), devices=jax.devices()[:sp]
    )
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), t=64)

    def sharded_loss(a, b, c):
        def inner(aa, bb, cc):
            return jnp.sum(
                jnp.square(
                    ring_attention(
                        aa, bb, cc, axis_name=SEQ_AXIS, axis_size=sp,
                        causal=causal, attn_impl="flash",
                    )
                )
            )

        per = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(None, SEQ_AXIS), P(None, SEQ_AXIS), P(None, SEQ_AXIS)),
            out_specs=P(),
            check_vma=False,
        )(a, b, c)
        return per

    g1 = jax.jit(jax.grad(sharded_loss, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(
        lambda a, b, c: jnp.sum(jnp.square(full_attention(a, b, c, causal=causal))),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# -- wire honesty on real ICI (VERDICT r2 weak #4, open half) ----------------

def test_int8_wire_rides_s8_on_tpu(two_chips):
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel import quantize as Q
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger
    from theanompi_tpu.runtime.mesh import DATA_AXIS, make_mesh

    mesh = make_mesh()
    world = jax.device_count()
    n = world * Q.BLOCK * 32 * 2
    ex = BSP_Exchanger(strategy="pallas_int8", axis=DATA_AXIS, mesh=mesh)

    hlo = (
        jax.jit(
            jax.shard_map(
                lambda g: ex.reduce_grads({"g": g})["g"], mesh=mesh,
                in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
                check_vma=False,
            )
        )
        .lower(jax.ShapeDtypeStruct((world, n), jnp.float32))
        .compile()
        .as_text()
    )
    coll = [l for l in hlo.splitlines() if "all-to-all" in l or "all-gather" in l]
    assert any("s8[" in l for l in coll), "s8 payload missing on TPU wire"


def test_bf16_allreduce_not_promoted_on_tpu(two_chips):
    """On CPU, XLA folds the casts around the bf16 strategy's all-reduce
    and promotes it back to f32 (discovered by collective_wire_bytes).
    The claim 'bf16 halves exchange bytes' is only honest if the TPU
    backend keeps the all-reduce in bf16 — assert exactly that."""
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel.exchanger import BSP_Exchanger
    from theanompi_tpu.runtime.mesh import DATA_AXIS, make_mesh

    mesh = make_mesh()
    world = jax.device_count()
    n = 1 << 16
    ex = BSP_Exchanger(strategy="bf16", axis=DATA_AXIS, mesh=mesh)

    hlo = (
        jax.jit(
            jax.shard_map(
                lambda g: ex.reduce_grads({"g": g})["g"], mesh=mesh,
                in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
                check_vma=False,
            )
        )
        .lower(jax.ShapeDtypeStruct((world, n), jnp.float32))
        .compile()
        .as_text()
    )
    ar = [
        l for l in hlo.splitlines()
        if " = " in l and ("all-reduce(" in l or "all-reduce-start(" in l)
    ]
    assert ar, "bf16 strategy lost its all-reduce"
    assert any("bf16[" in l for l in ar), (
        "bf16 all-reduce was promoted to f32 on TPU too — scope the "
        "strategy's docstring claim:\n" + "\n".join(ar)
    )


# -- s2d stem: compiled equivalence on the real chip -------------------------

def test_conv_s2d_compiled_matches_plain_on_chip(chip):
    """The space-to-depth stem (r4 perf candidate) must agree with the
    plain strided conv WHEN COMPILED on the chip — the CPU suite proves
    the math, this proves the TPU lowering (layout/tiling) didn't bend
    it. AlexNet-128 stem geometry, fwd + dW."""
    from theanompi_tpu.ops import layers as L

    plain = L.Conv2d(96, 11, stride=4, padding="SAME")
    s2d = L.Conv2d(96, 11, stride=4, padding="SAME", s2d=True)
    p, st, _ = plain.init(jax.random.PRNGKey(0), (128, 128, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 128, 128, 3))

    def make_run(layer):
        @jax.jit
        def run(p, x):
            def loss(p):
                y, _ = layer.apply(p, st, x)
                return jnp.sum(jnp.sin(y)), y
            (_, y), g = jax.value_and_grad(loss, has_aux=True)(p)
            return y, g["w"]
        return run

    with jax.default_matmul_precision("highest"):
        y0, g0 = make_run(plain)(p, x)
        y1, g1 = make_run(s2d)(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=2e-3, atol=2e-3)
