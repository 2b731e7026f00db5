"""Checkpoint → serving: restore training params into inference layout.

A training checkpoint (``utils/checkpoint.save`` of
``TpuModel.checkpoint_state()``) carries the full state pytree — params,
optimizer moments, BN state, epoch, rng.  Serving needs exactly the
params, laid out for *inference*: replicated over the serving mesh for
plain data parallelism, or Megatron-sharded via the SAME
``TransformerLM._build_param_specs`` tree training shards by when the
serving mesh has a ``tp`` axis.  Optimizer state is deliberately
dropped — a serving process holding Adam moments would waste 2× the
param HBM.

The serving mesh does NOT have to match the training mesh: checkpoints
store full global arrays (``host_snapshot`` gathers), so a model trained
dp=8 restores onto a dp=1, dp×tp, or any other serving topology —
``_place_sharded_state`` re-lays the leaves per the target specs.
"""

from __future__ import annotations

from typing import Optional

import jax

from theanompi_tpu.runtime.mesh import replicate
from theanompi_tpu.utils import checkpoint


def relayout_for_serving(model, params):
    """Train→serve re-lay of an IN-MEMORY params tree — the live-
    publication path (``theanompi_tpu.publish``): same structure check
    and same placement machinery as :func:`restore_params_for_serving`,
    but the source is a published center snapshot, not a checkpoint
    file, and the MODEL IS NEVER MUTATED — the placed tree is returned
    for the subscriber to validate and hand to
    ``ServeReplica.install_params``.  Replication covers plain-dp
    serving; tp leaves move replicated → Megatron-sharded per the same
    ``_build_param_specs`` tree training shards by (a no-op when the
    mesh has no ``tp`` axis or the model declares no specs).  The placed
    tree is handed back as the **serving tree** its programs read
    (``paging.serving_params``: a ``dense`` model's matrices and tables
    in the compute dtype), which is what a scheduler holds, so that
    ``publish.validate_swap`` compares like with like."""
    from theanompi_tpu.serving.paging import serving_params

    if jax.tree.structure(params) != jax.tree.structure(model.params):
        raise ValueError(
            "published snapshot has a different params structure than "
            "the serving model — the center and this replica were built "
            "from different architecture configs"
        )
    placed = replicate(model.mesh, params)
    specs = getattr(model, "param_specs", None)
    if specs is not None:
        from jax.sharding import NamedSharding

        placed = jax.tree.map(
            lambda a, s: jax.device_put(
                a, NamedSharding(model.mesh, s)
            ),
            placed,
            specs,
        )
    return serving_params(model, placed)


def restore_params_for_serving(model, path: str):
    """Load ``path`` and install its params on ``model``'s mesh in
    inference sharding.  Returns the placed params (also set on the
    model).  Raises on a params-structure mismatch — a checkpoint from
    a different architecture config must fail loudly, not serve noise."""
    blob = checkpoint.restore(path)
    if "params" not in blob:
        raise ValueError(f"{path!r} is not a training checkpoint "
                         "(no 'params' entry)")
    if jax.tree.structure(blob["params"]) != jax.tree.structure(model.params):
        raise ValueError(
            f"checkpoint {path!r} has a different params structure than "
            "the serving model — rebuild the model with the config the "
            "checkpoint was trained with"
        )
    model.params = replicate(model.mesh, blob["params"])
    if "net_state" in blob:
        model.net_state = replicate(model.mesh, blob["net_state"])
    # tp leaves move replicated → Megatron-sharded here (no-op for plain
    # dp serving); same machinery training uses before compile_train
    model._place_sharded_state()
    return model.params


def load_engine(
    path: str,
    config: Optional[dict] = None,
    mesh=None,
    n_slots: int = 4,
    max_len: Optional[int] = None,
    buckets=None,
    model_cls=None,
    block_size: int = 16,
    n_blocks: Optional[int] = None,
    prefill_chunk: Optional[int] = None,
    prefix_cache: bool = True,
    prefix_impl: str = "chain",
    kv_dtype: str = "fp32",
    paged_attn: str = "xla",
):
    """One-call checkpoint → ready ``PagedServingEngine``.

    ``config`` must describe the architecture the checkpoint was trained
    with (d_model / n_heads / n_layers / vocab_size / seq_len); serving
    topology (``tp``) may differ from training.  ``mesh`` defaults to
    ``model_cls.build_mesh(config)`` — the same mesh builder training
    rules use, so serving engages tp meshes from config alone.

    KV memory is fixed-size refcounted blocks (``block_size``/
    ``n_blocks``) with prefix reuse and chunked multi-slot prefill
    (``prefill_chunk``).  ``kv_dtype``
    ('fp32'/'int8') and ``paged_attn`` ('xla'/'pallas'/'auto') select
    the quantized-cache and fused-kernel decode tiers — a checkpoint
    loads identically into any combination."""
    from theanompi_tpu.serving.paging import PagedServingEngine

    if model_cls is None:
        from theanompi_tpu.models.transformer import TransformerLM

        model_cls = TransformerLM
    cfg = dict(config or {})
    # serving never touches the training data pipeline beyond the tiny
    # synthetic defaults a model constructor builds; keep it minimal
    cfg.setdefault("n_synth_train", 2)
    cfg.setdefault("n_synth_val", 1)
    cfg.setdefault("comm_probe", False)
    model = (
        model_cls(config=cfg, mesh=mesh)
        if mesh is not None
        else model_cls(config=cfg)
    )
    restore_params_for_serving(model, path)
    engine = PagedServingEngine(
        model, n_slots=n_slots, max_len=max_len, buckets=buckets,
        block_size=block_size, n_blocks=n_blocks,
        prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
        prefix_impl=prefix_impl, kv_dtype=kv_dtype,
        paged_attn=paged_attn,
    )
    # this model exists to be served: it holds the serving tree, and the
    # restored float32 leaves the tree replaces are freed (a scheduler
    # built on the engine then binds the same arrays)
    model.params = engine.serving_params(model.params)
    return engine


def load_replica(
    path: str,
    name: str,
    config: Optional[dict] = None,
    port: Optional[int] = None,
    **engine_kwargs,
):
    """Checkpointless replica spin-up: one call from a training
    checkpoint to a started, fleet-joinable ``ServeReplica`` — what a
    supervisor runs to replace an evicted replica (the serving analog
    of the async rules' re-admission: state is re-derived from the
    durable artifact, never copied from the dead incarnation).  The
    engine's prefix cache is the radix tree (fleet routing wants the
    summaries); ``engine_kwargs`` reach :func:`load_engine`."""
    from theanompi_tpu.serving.fleet import ServeReplica

    engine_kwargs.setdefault("prefix_impl", "radix")
    engine = load_engine(path, config=config, **engine_kwargs)
    return ServeReplica(name, engine, port=port).start()
