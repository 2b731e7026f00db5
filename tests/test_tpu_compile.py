"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a chip that is
*described*, not attached (``topologies.get_topology_desc``).  Nothing
runs, so nothing here says a word about results or speed; what it does
say is what interpret mode cannot: whether Mosaic lowers each kernel at
the real widths (PR 21 found the fp16s wire kernels refused and the pool
backward never finishing), whether the AlexNet step fits a 16 GB chip,
and whether the BSP step over four chips holds its all-reduce.

The kernel cases are the ones ``chip_smoke.py`` runs on the chip
(``theanompi_tpu/ops/kernel_cases.py``) — one list, no second copy.

Rules this file keeps (``on-chip-measurement`` guide, section 2):

- the topology is described inside a module-scoped, non-``autouse``
  fixture OF THIS FILE, after a test has started — never at import, in a
  ``skipif``, in a ``parametrize`` argument or in ``conftest.py`` (only
  one process may load the TPU library; the xdist worker that is handed
  this file is the one that does);
- code that asks "am I on a TPU" sees the CPU here, so the test steers
  the one gate (``ops.platform.on_tpu``) — the program has no option for
  it;
- the persistent compile cache is off around these compiles: an entry
  written for a described chip cannot be read back without one.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from theanompi_tpu.ops import kernel_cases, platform

HBM_BYTES = 16 * 1024 ** 3  # one v5e chip

# building the list imports modules and closes over shapes; no device,
# no backend, no topology is touched until a test asks a fixture
CASES = {c.name: c for c in kernel_cases.cases("real")}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Every kernel gate and dtype policy takes its TPU branch."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)


def _described(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


# ---------------------------------------------------------------------------
# every Pallas kernel, at the shapes the chip runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_under_mosaic_for_v5e(name, one_chip, as_tpu):
    case = CASES[name]
    args = _described(
        jax.eval_shape(case.make_args, jax.random.PRNGKey(0)), one_chip
    )
    with kernel_cases.matmul_precision(case.kernel_precision):
        text = jax.jit(case.kernel).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: kernel did not reach Mosaic"


def test_pool_backward_compiles_in_bf16_too(one_chip, as_tpu):
    """AlexNet's activations are bf16 under ``compute_dtype='bfloat16'``;
    the kernel widens into its fp32 scratch, so the narrow input must
    lower as well."""
    from theanompi_tpu.ops.pallas_pool import maxpool_bwd

    x = jax.ShapeDtypeStruct((512, 32, 32, 96), jnp.bfloat16,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct((512, 15, 15, 96), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(
        lambda x, y, dy: maxpool_bwd(x, y, dy, (3, 3), (2, 2))
    ).lower(x, y, y).compile().as_text()
    assert "tpu_custom_call" in text


def test_pool_backward_refuses_a_plane_over_its_vmem_budget():
    """ADVICE r5: above the plane its fast memory can hold the kernel
    raises a clear error of its own instead of meeting Mosaic's."""
    from theanompi_tpu.ops.pallas_pool import maxpool_bwd, plane_fits_vmem

    assert plane_fits_vmem(32, 32) and not plane_fits_vmem(112, 112)
    x = jnp.zeros((1, 112, 112, 8))
    y = jnp.zeros((1, 55, 55, 8))
    with pytest.raises(ValueError, match="VMEM"):
        maxpool_bwd(x, y, y, (3, 3), (2, 2))


# ---------------------------------------------------------------------------
# whole programs: the AlexNet step and the paged engine
# ---------------------------------------------------------------------------

def _alexnet_step(topo, n_devices, batch_size=512):
    """AlexNet's jitted train step over ``n_devices`` described chips,
    and its arguments as shapes.  The model is built on CPU devices (it
    places its own parameters, and nothing can be placed on a described
    device), then handed the described mesh before the step is built."""
    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.runtime.mesh import make_mesh

    model = AlexNet(
        config=dict(batch_size=batch_size, image_size=128, n_classes=1000,
                    compute_dtype="bfloat16", n_synth_batches=1, lr=1e-3),
        mesh=make_mesh(devices=jax.devices()[:n_devices]),
    )
    mesh = make_mesh(devices=topo.devices[:n_devices])
    model.mesh = mesh
    step = model.compile_train()
    rep, batch = NamedSharding(mesh, P()), NamedSharding(mesh, model.batch_spec)
    gb = batch_size * n_devices
    args = (
        _described(model.params, rep), _described(model.net_state, rep),
        _described(model.opt_state, rep),
        jax.ShapeDtypeStruct((gb, 128, 128, 3), jnp.float32, sharding=batch),
        jax.ShapeDtypeStruct((gb,), jnp.int32, sharding=batch),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
    )
    return step, args


# The two serving stages come first among the whole-program compiles and
# the four-chip step last: xdist hands out the files with the most tests
# first, so this file and tests/test_benchmarks_harness.py start together
# on two workers, and the stage compiles (the heaviest on the CPU) then
# fall well before the harness's one-second traced windows, which count
# finished requests and run some three minutes in.

def test_latent_stage_programs_fit_one_v5e_chip(one_chip, as_tpu):
    """The decode program and the widest prefill program of the
    benchmark's ``xing4.0-29b-a4b`` stage, at the sizes of its
    configuration file (published widths, 4.79 G bfloat16 weights, the
    12,289-block latent pool), compile for a v5e with their four kernels
    inside and fit the chip: arguments, temporaries and the outputs that
    are not the donated pool's stay under 16 GiB."""
    import json
    import os

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.serving import PagedServingEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    pc = dict(cfg["program_config"], seed=0)
    model = TransformerLM(
        config=pc,
        mesh=TransformerLM.build_mesh(devices=jax.devices()[:1], config=pc),
    )
    assert 4.79e9 < model.n_params < 4.80e9 and model.opt_state is None
    eng = PagedServingEngine(model, **cfg["engine"])
    assert eng.paged_attn_effective == "pallas" and eng.prefill_rows == 1
    # 393,216 usable rows, stored 640 wide: 3.02 GB over the six layers
    assert eng.kv_block_bytes() * eng.n_blocks == 12289 * 32 * 6 * 640 * 2
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one_chip),
        model.params)
    state = _described(jax.eval_shape(eng.init_state), one_chip)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s, nb, c = eng.n_slots, eng.blocks_per_seq, eng.chunk_buckets[-1]
    r = eng.prefill_rows
    assert (s, nb, c) == (32, 544, 2048)
    prefill = eng._paged_prefill_jit.lower(
        params, state, arg(jnp.int32, r, c), arg(jnp.int32, r, nb),
        arg(jnp.int32, r), arg(jnp.int32, r), arg(jnp.bool_, r),
    ).compile()
    decode = eng._paged_decode_jit.lower(
        params, state, arg(jnp.int32, s), arg(jnp.int32, s, nb),
        arg(jnp.int32, s), arg(jnp.bool_, s),
    ).compile()
    for program in (prefill, decode):
        m = program.memory_analysis()
        held = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        assert held < HBM_BYTES, held
        # the pool is updated in place: no copy of it among the temporaries
        assert m.temp_size_in_bytes < 2 * 1024 ** 3, m.temp_size_in_bytes
    text = decode.as_text()
    for kernel in ("mla_paged_decode", "moe_grouped_mm_gate", "mhc_pre",
                   "mhc_post"):
        assert kernel in text, kernel
    assert "moe_grouped_mm_down" in prefill.as_text()


def test_kimi_linear_stage_programs_fit_one_v5e_chip(one_chip, as_tpu):
    """The decode program and the widest prefill program of the
    benchmark's ``kimi-linear-48b-a3b`` stage, at the sizes of its
    configuration file (published widths, 4.27 G bfloat16 weights with 64
    of 256 experts held, the 35,841-block latent pool over two layers,
    64 lanes of recurrent state over seven), compile for a v5e with
    their kernels inside and fit the chip; pool and state are updated in
    place."""
    import json
    import os

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.serving import PagedServingEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    pc = dict(cfg["program_config"], seed=0)
    model = TransformerLM(
        config=pc,
        mesh=TransformerLM.build_mesh(devices=jax.devices()[:1], config=pc),
    )
    assert 4.26e9 < model.n_params < 4.28e9 and model.opt_state is None
    eng = PagedServingEngine(model, **cfg["engine"])
    assert eng.paged_attn_effective == "pallas" and eng.prefill_rows == 1
    assert eng.programs.recurrent and not eng.prefix_cache_enabled
    # 1,146,880 usable rows, stored 640 wide over the two latent layers
    assert eng.kv_block_bytes() * eng.n_blocks == 35841 * 32 * 2 * 640 * 2
    # 64 lanes x 7 layers x (32 x 128 x 128 x 4 B + 3 x 12,288 x 2 B)
    assert eng.programs.recurrent_state_bytes() == 64 * 7 * (2 ** 21 + 73728)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one_chip),
        model.params)
    state = _described(jax.eval_shape(eng.init_state), one_chip)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s, nb, c = eng.n_slots, eng.blocks_per_seq, eng.chunk_buckets[-1]
    r = eng.prefill_rows
    assert (s, nb, c) == (64, 560, 2048)
    prefill = eng._paged_prefill_jit.lower(
        params, state, arg(jnp.int32, r, c), arg(jnp.int32, r, nb),
        arg(jnp.int32, r), arg(jnp.int32, r), arg(jnp.bool_, r),
        arg(jnp.int32, r),
    ).compile()
    decode = eng._paged_decode_jit.lower(
        params, state, arg(jnp.int32, s), arg(jnp.int32, s, nb),
        arg(jnp.int32, s), arg(jnp.bool_, s),
    ).compile()
    for name, program in (("prefill", prefill), ("decode", decode)):
        m = program.memory_analysis()
        held = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(name, "arguments", m.argument_size_in_bytes, "temporaries",
              m.temp_size_in_bytes, "held", held)
        assert held < HBM_BYTES, held
        # pool and state are updated in place: no copy among the temporaries
        assert m.temp_size_in_bytes < 2 * 1024 ** 3, m.temp_size_in_bytes
    text = decode.as_text()
    for kernel in ("kda_decode", "mla_paged_decode", "moe_grouped_mm_gate"):
        assert kernel in text, kernel
    assert "mhc_pre" not in text  # one stream: no hyper-connections
    text = prefill.as_text()
    assert "kda_chunk_prefill" in text and "moe_grouped_mm_down" in text


def test_smallthinker_stage_programs_fit_one_v5e_chip(one_chip, as_tpu):
    """The decode program and the widest prefill program of the
    benchmark's ``smallthinker-21b-a3b`` stage, at the sizes of its
    configuration file (published widths, 3.97 G bfloat16 weights, two
    global layers' keys and values over 16,385 blocks, six window
    layers' rings of 192 blocks for 32 lanes), compile for a v5e with
    their kernels inside and fit the chip; pool and rings are updated
    in place."""
    import json
    import os

    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.serving import PagedServingEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    pc = dict(cfg["program_config"], seed=0)
    model = TransformerLM(
        config=pc,
        mesh=TransformerLM.build_mesh(devices=jax.devices()[:1], config=pc),
    )
    assert 3.96e9 < model.n_params < 3.98e9 and model.opt_state is None
    eng = PagedServingEngine(model, **cfg["engine"])
    p = eng.programs
    assert eng.paged_attn_effective == "pallas" and eng.prefill_rows == 1
    assert p.lane_state == "window rings" and not eng.prefix_cache_enabled
    assert (p.n_full, p.n_window, p.ring_blocks) == (2, 6, 192)
    # keys and values, 512 wide, in the two global layers
    assert eng.kv_block_bytes() == 2 * 2 * 32 * 512 * 2
    assert p.ring_bytes() == 6 * 2 * 32 * 6144 * 512 * 2
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one_chip),
        model.params)
    state = _described(jax.eval_shape(eng.init_state), one_chip)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s, nb, c = eng.n_slots, eng.blocks_per_seq, eng.chunk_buckets[-1]
    r = eng.prefill_rows
    assert (s, nb, c) == (32, 512, 2048)
    prefill = eng._paged_prefill_jit.lower(
        params, state, arg(jnp.int32, r, c), arg(jnp.int32, r, nb),
        arg(jnp.int32, r), arg(jnp.int32, r), arg(jnp.bool_, r),
        arg(jnp.int32, r),
    ).compile()
    decode = eng._paged_decode_jit.lower(
        params, state, arg(jnp.int32, s), arg(jnp.int32, s, nb),
        arg(jnp.int32, s), arg(jnp.bool_, s),
    ).compile()
    for name, program in (("prefill", prefill), ("decode", decode)):
        m = program.memory_analysis()
        held = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(name, "arguments", m.argument_size_in_bytes, "temporaries",
              m.temp_size_in_bytes, "held", held)
        assert held < HBM_BYTES, held
        # pool and rings are updated in place: no copy among the temporaries
        assert m.temp_size_in_bytes < 2 * 1024 ** 3, m.temp_size_in_bytes
    text = decode.as_text()
    for kernel in ("paged_decode_attn", "moe_grouped_mm_gate"):
        assert kernel in text, kernel
    assert "moe_grouped_mm_down" in prefill.as_text()


def test_alexnet_step_fits_one_v5e_chip(topo, as_tpu):
    """Batch 512, bf16, every layer at its width: the step the *train*
    phase of chip_smoke.py runs."""
    step, args = _alexnet_step(topo, 1)
    mem = step.lower(*args).compile().memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < need < HBM_BYTES, f"step needs {need / 2**30:.2f} GiB"


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_paged_engine_programs_compile_at_serving_widths(
    kv_dtype, one_chip, as_tpu
):
    """The prefill-chunk and decode programs of ``PagedServingEngine``
    at ``bench_serve.py``'s real knobs, Pallas decode kernel inside."""
    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.serving import PagedServingEngine

    cfg = dict(seq_len=1024, vocab_size=4096, d_model=512, n_heads=8,
               n_layers=8, batch_size=1, n_synth_train=2, n_synth_val=1,
               comm_probe=False, print_freq=10_000)
    model = TransformerLM(
        config=cfg,
        mesh=TransformerLM.build_mesh(devices=jax.devices()[:1], config=cfg),
    )
    eng = PagedServingEngine(
        model, n_slots=32, max_len=1024, block_size=32, n_blocks=257,
        prefill_chunk=256, kv_dtype=kv_dtype, paged_attn="pallas",
    )
    params = _described(model.params, one_chip)
    state = _described(jax.eval_shape(eng.init_state), one_chip)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s, nb, c = eng.n_slots, eng.blocks_per_seq, eng.chunk_buckets[-1]
    r = eng.prefill_rows  # the prefill program's own, narrow width
    eng._paged_prefill_jit.lower(
        params, state, arg(jnp.int32, r, c), arg(jnp.int32, r, nb),
        arg(jnp.int32, r), arg(jnp.int32, r), arg(jnp.bool_, r),
    ).compile()
    text = eng._paged_decode_jit.lower(
        params, state, arg(jnp.int32, s), arg(jnp.int32, s, nb),
        arg(jnp.int32, s), arg(jnp.bool_, s),
    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_blocks", [257, 513])
def test_dense_pool_is_updated_in_place_at_25_heads_of_64(
    n_blocks, one_chip, as_tpu
):
    """The decode program and the four-row prefill program of the
    benchmark's ``gpt2-xl`` engine, at full depth and at the width that
    pads (25 heads of 64: 1,600 numbers a row, stored 1,664 wide),
    bfloat16 pool, Pallas decode kernel, the weights as the scheduler
    hands them over (the serving tree: ``jax.eval_shape`` of the engine's
    hook): the pool's 96 arrays are donated and written in place, so a
    program holds no temporary of the pool's size (the stacked ``(48,
    rows, 25, 64)`` pool of before: 6.93 GB of temporaries at 257
    blocks, and 513 did not fit), and no program reads a float32 matrix
    or table to cast it (9.26 GB of arguments at 257 blocks before, the
    whole embedding table converted for 32 rows of it).  At 513 blocks,
    what the benchmark's issue first asked for, weights, pool and
    temporaries stay under 16 GiB."""
    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.serving import PagedServingEngine

    cfg = dict(seq_len=1024, vocab_size=50257, d_model=1600, n_heads=25,
               n_layers=48, compute_dtype="bfloat16", batch_size=1,
               n_synth_train=2, n_synth_val=1, comm_probe=False,
               print_freq=10_000, init_weights=False)
    model = TransformerLM(
        config=cfg,
        mesh=TransformerLM.build_mesh(devices=jax.devices()[:1], config=cfg),
    )
    eng = PagedServingEngine(
        model, n_slots=32, max_len=1024, block_size=32, n_blocks=n_blocks,
        prefill_chunk=256, paged_attn="pallas",
    )
    assert (eng.programs.row_width, eng.prefill_rows) == (1664, 4)
    params = _described(jax.eval_shape(eng.serving_params, model.params),
                        one_chip)
    matrices = {",".join(map(str, a.shape)) for a in jax.tree.leaves(params)
                if a.dtype == jnp.bfloat16}
    assert {"1600,1600", "1600,6400", "50257,1600", "1600,50257"} <= matrices
    state = _described(jax.eval_shape(eng.init_state), one_chip)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert pool_bytes == 2 * 48 * n_blocks * 32 * 1664 * 2
    assert pool_bytes == eng.kv_block_bytes() * n_blocks

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s, nb, c = eng.n_slots, eng.blocks_per_seq, eng.chunk_buckets[-1]
    r = eng.prefill_rows
    prefill = eng._paged_prefill_jit.lower(
        params, state, arg(jnp.int32, r, c), arg(jnp.int32, r, nb),
        arg(jnp.int32, r), arg(jnp.int32, r), arg(jnp.bool_, r),
    ).compile()
    decode = eng._paged_decode_jit.lower(
        params, state, arg(jnp.int32, s), arg(jnp.int32, s, nb),
        arg(jnp.int32, s), arg(jnp.bool_, s),
    ).compile()
    for program in (prefill, decode):
        m = program.memory_analysis()
        assert m.temp_size_in_bytes < 1024 ** 3, m.temp_size_in_bytes
        assert m.alias_size_in_bytes >= pool_bytes, m.alias_size_in_bytes
        # arguments and temporaries, and the outputs that are not the
        # donated pool's
        held = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        assert held < HBM_BYTES, held
        # the 48 layers share one body of code a fusion (49 and 245 MB
        # with every call inlined, which is what the compiler chooses for
        # a program of this size: paging.PagedServingEngine)
        assert m.generated_code_size_in_bytes < 16 * 1024 ** 2
        # 3.28 GB of weights beside the pool (6.63 GB in float32)
        assert m.argument_size_in_bytes - pool_bytes < 3.7e9
        if n_blocks == 257:
            assert m.argument_size_in_bytes < 6.3e9
        # every matrix and table arrives in bfloat16 and none is converted
        # (the float32 leaves left are scales and biases: 50,257 at most)
        text = program.as_text()
        op = r"= (\w+)\[([\d,]+)\]\S* %s\("
        arguments = re.findall(op % "parameter", text[text.index("\nENTRY "):])
        assert len(arguments) > 96 + 48 * 12
        bad = [(dtype, shape) for dtype, shape in arguments
               if dtype == "f32"
               and math.prod(map(int, shape.split(","))) >= 1600 * 1600]
        assert not bad, bad[:4]
        bad = [shape for dtype, shape in re.findall(op % "convert", text)
               if dtype == "bf16" and shape in matrices]
        assert not bad, bad[:4]
    assert decode.as_text().count("tpu_custom_call") >= 48


def test_bsp_step_over_four_chips_holds_an_all_reduce(topo, as_tpu):
    step, args = _alexnet_step(topo, 4)
    text = step.lower(*args).compile().as_text()
    assert re.search(r"all-reduce(-start)?\(", text), (
        "dp=4 BSP step compiled without an all-reduce"
    )
