"""Model-contract base class.

The reference enforced a duck-typed model API consumed by its workers
(upstream README + worker code; SURVEY.md §3.5 "Model contract"):
``__init__(config)``, ``build_model()``, ``compile_train()``,
``compile_val()``, ``train_iter(count, recorder)``, ``val_iter(count,
recorder)``, ``adjust_hyperp(epoch)``, ``scale_lr(factor)``,
``cleanup()``, attrs ``params``, ``data``, ``batch_size``, ``n_epochs``.

``TpuModel`` implements that contract once, TPU-first:

- ``compile_train`` emits ONE jitted XLA program containing forward,
  backward, the BSP exchange (``lax.psum`` via ``BSP_Exchanger``) and the
  optimizer update, shard_mapped over the mesh's ``dp`` axis.  The
  reference's separate "theano function + exchanger.exchange()" phases
  fuse into a single compiled step (SURVEY.md §4.5 TPU mapping).
- Parameters / optimizer state / BN state are replicated pytrees on the
  mesh; batches are sharded on the leading dim.
- Subclasses define ``build_data()`` (set ``self.data``) and
  ``build_net()`` (return ``(net, input_shape)``), plus per-model config
  defaults and lr schedule.  Models that are not plain classifiers (the
  GAN) override ``compile_train``/``train_iter`` instead.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu import observability as obs
from theanompi_tpu.data.loader import prefetch_to_mesh
from theanompi_tpu.ops import losses
from theanompi_tpu.ops import optim as optim_lib
from theanompi_tpu.ops.layers import Layer, count_params
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.runtime.config import Config
from theanompi_tpu.runtime.mesh import DATA_AXIS, DCN_AXIS, make_mesh, replicate

# the tracer imports no jax: the program's jax-importing modules hand it
# the profiler's annotation, so boundary spans show in any profile
obs.install_annotation_hook(jax.profiler.TraceAnnotation)

_METRICS_SYNC: Optional[bool] = None


def metrics_must_sync() -> bool:
    """True on the XLA:CPU backend only: there, DISPATCHING any new
    program (even the recorder's deferred one-op scalar add) while an
    8-participant collective step is still in flight can deadlock the
    runtime's collective rendezvous — proven by the r5 easgd_sweep
    stall, parked at 0 CPU inside ``recorder.train_error``'s
    ``deferring_binary_op`` with the loader blocked on a full queue
    (SIGUSR1 stack dump; same hazard CLASS as the r4 train→val fence in
    ``run_validation``, at a different dispatch site). Hosting the
    metrics first is a blocking device→host READ, not a program launch,
    so it serializes the hazard away. TPU keeps the lazy device-scalar
    pipeline the r1 perf push introduced."""
    global _METRICS_SYNC
    if _METRICS_SYNC is None:
        _METRICS_SYNC = jax.default_backend() == "cpu"
    return _METRICS_SYNC

COMMON_DEFAULTS = dict(
    seed=0,
    batch_size=128,  # per data-parallel shard, like the reference's per-GPU bs
    n_epochs=10,
    lr=0.01,
    momentum=0.9,
    nesterov=False,
    weight_decay=1e-4,
    sync_mode="cdd",  # 'cdd' = gradient reduce; 'avg' = param averaging
    exch_strategy="ar",  # 'ar' | 'bf16' | 'fp16' (cast wire) |
    # 'fp16s' | 'pallas_fp16s' (block-scaled fp16 wire: overflow-proof,
    # ~2× fewer bytes) | 'int8' | 'pallas_int8' | 'int8_sr' |
    # 'pallas_int8_sr' (int8 + per-block scale wire, ~4× fewer bytes)
    prefetch_depth=2,
    grad_clip_norm=None,  # global-norm clip after exchange (None = off)
    print_freq=40,
    val_top5=True,
    compute_dtype=None,  # e.g. 'bfloat16' for MXU-native compute
    device_aug=False,  # True = per-image random crop/mirror INSIDE the
    # jitted step (ops.augment.random_crop_mirror) instead of on the
    # host; the provider then ships raw full-size train images. Uses
    # model config keys crop_size / mirror when the model defines them.
    comm_probe=True,  # one-shot comm-fraction measurement at BSP train
    # start (logged as a record event; the fused-step analog of the
    # reference's per-window comm column). Costs two extra compiles.
    sync_each_iter=False,  # True = fence every step (honest per-step calc
    # split, reference-style); False = let steps pipeline and only sync at
    # print/validation boundaries (a host↔device fence stalls the
    # pipeline; its cost on the chip host: not measured)
    zero1=False,  # shard optimizer state over dp (parallel.zero.Zero1):
    # reduce-scatter grads -> update own shard -> all-gather params.
    # Same wire bytes as the allreduce it replaces, moments HBM / N.
    grad_accum=1,  # microbatches per step (lax.scan): grads accumulate
    # across K sequential fwd+bwd passes before ONE exchange+update —
    # K× the effective batch at 1/K the activation HBM
    exchange_overlap="bucket",  # how the BSP gradient exchange is issued:
    # 'leaf'   = PR-0 shape, one collective per gradient leaf after the
    #            full backward (legacy escape hatch);
    # 'bucket' = fuse leaves into ~exchange_bucket_mb flat buckets
    #            (parallel.bucketing): one pack/pad/collective per
    #            bucket, sub-chunk leaves quantize as part of a bucket;
    # 'indag'  = bucketed AND issued inside the backward DAG at the
    #            model's grad-sync points (bucketing.GradSyncGroup —
    #            TransformerLM blocks, ResNet50 stages), so reduction
    #            overlaps backprop (arXiv:1802.06949). Models without
    #            sync groups reject it loudly.
    exchange_bucket_mb=4.0,  # bucket size for 'bucket'/'indag'
    dcn_shape=None,  # N = two-level ('dp_dcn', dp...) mesh: intra-slice
    # collectives ride ICI, only the outer reduction crosses DCN
    # (make_mesh(dcn_shape=...)); honored by the DP build_mesh so
    # rule.init / launch.py / direct construction engage it from config
    # alone — on a multi-process run slices align with process
    # boundaries. Models whose build_mesh doesn't support it (the
    # sp/tp/pp/ep overrides) hard-fail at init instead of silently
    # training on a flat mesh.
)


def stem_is_s2d(cfg) -> bool:
    """Validate the shared ``stem`` config knob ('conv' | 's2d') and
    return whether the model should build its strided stem through
    space-to-depth (ops.layers.Conv2d(s2d=True)). One definition for
    every model that exposes the knob."""
    stem = cfg.get("stem", "conv") if hasattr(cfg, "get") else cfg.stem
    if stem not in ("conv", "s2d"):
        raise ValueError(f"stem must be conv|s2d, got {stem!r}")
    return stem == "s2d"


class TpuModel:
    default_config: dict = {}
    # Sharding surface of the step function. Plain data-parallel models
    # keep the defaults (batch over 'dp', exchange over 'dp'); the
    # sequence-parallel transformer overrides both (batch over 'dp',
    # sequence over 'sp', exchange over ('dp','sp')).
    batch_spec = P(DATA_AXIS)
    exchange_axes = DATA_AXIS
    # mesh axes the LEADING (batch) dim of batch_spec shards over — the
    # per-shard batch_size multiplies over these to give global_batch.
    # The MoE model adds 'ep' (tokens shard over dp×ep); the transformer
    # does NOT add 'sp' (sp shards the sequence dim, not the batch dim).
    batch_axes = (DATA_AXIS,)

    def __init__(self, config: Optional[dict] = None, mesh=None, **overrides):
        self.config = Config(COMMON_DEFAULTS)
        self.config.update(self.default_config)
        if config:
            self.config.update(dict(config))
        self.config.update(overrides)
        cfg = self.config

        # default mesh goes through the CLASS's build_mesh so config-
        # driven topology (dcn_shape here; sp/tp/pp/ep in subclasses
        # that override both) is honored on direct construction too,
        # not only via rule.init/launch
        self.mesh = (
            mesh if mesh is not None else type(self).build_mesh(config=cfg.asdict())
        )
        if cfg.get("dcn_shape"):
            # loud, not silent: either this model's build_mesh doesn't
            # support dcn_shape or an explicit mesh was passed with a
            # missing OR differently-sized dcn axis — training would
            # quietly use a different collective layout than the config
            # requested (ADVICE r3: the axis-exists check alone let a
            # size mismatch through)
            if DCN_AXIS not in self.mesh.shape:
                raise ValueError(
                    f"config dcn_shape={cfg.get('dcn_shape')} but the mesh "
                    f"{dict(self.mesh.shape)} has no '{DCN_AXIS}' axis"
                )
            if int(self.mesh.shape[DCN_AXIS]) != int(cfg.get("dcn_shape")):
                raise ValueError(
                    f"config dcn_shape={cfg.get('dcn_shape')} but the mesh "
                    f"has {DCN_AXIS}={int(self.mesh.shape[DCN_AXIS])}"
                )
        self._engage_dcn_axis()
        self.n_workers = 1
        for ax in self.batch_axes:
            if ax in self.mesh.shape:
                self.n_workers *= int(self.mesh.shape[ax])
        if DCN_AXIS in self.mesh.shape:
            self.n_workers *= int(self.mesh.shape[DCN_AXIS])
        self.batch_size = int(cfg.batch_size)
        self.global_batch = self.batch_size * self.n_workers
        self.n_epochs = int(cfg.n_epochs)
        self.rng = jax.random.PRNGKey(int(cfg.seed))

        self.data = None
        self.net: Optional[Layer] = None
        self.input_shape: Optional[Tuple[int, ...]] = None
        self.lr_schedule = optim_lib.constant(float(cfg.lr))
        self._lr_scale = 1.0
        # pytree of PartitionSpec matching ``params`` for tensor-parallel
        # models (None = fully replicated, the plain data-parallel case)
        self.param_specs = None

        self.build_data()
        self.build_model()

        self.train_fn = None
        self.val_fn = None
        self._train_it = None
        self._val_it = None
        self.current_epoch = 0

    def _engage_dcn_axis(self) -> None:
        """On a two-level ICI×DCN mesh, widen the batch spec and exchange
        axes to cover the outer ``dp_dcn`` axis: the batch shards over
        (dcn, dp) jointly and the gradient reduction runs over both — XLA
        lowers it hierarchically (reduce over ICI within a slice, then
        once across DCN per slice-pair), which is exactly the reference's
        intra-node NCCL + inter-node MPI split (SURVEY.md §6 backend row,
        §8.2 step 8)."""
        if DCN_AXIS not in self.mesh.shape:
            return
        ax = self.exchange_axes
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        if DCN_AXIS not in ax_t:
            self.exchange_axes = (DCN_AXIS,) + ax_t
        lead = self.batch_spec[0]
        lead_t = (lead,) if isinstance(lead, str) else tuple(lead)
        if DCN_AXIS not in lead_t:
            self.batch_spec = P((DCN_AXIS,) + lead_t, *self.batch_spec[1:])

    @classmethod
    def _require_mesh_axis(cls, mesh, axis: str, size: int):
        """Validate that ``mesh`` carries model-parallel ``axis`` at
        ``size`` (shared by the pp/ep/tp models' __init__)."""
        if axis not in mesh.axis_names:
            raise ValueError(
                f"config {axis}={size} but mesh has no '{axis}' axis "
                f"({mesh.axis_names}); build it with "
                f"{cls.__name__}.build_mesh(...)"
            )
        if int(mesh.shape[axis]) != size:
            raise ValueError(
                f"config {axis}={size} != mesh {axis} size {mesh.shape[axis]}"
            )

    # ------------------------------------------------------------------
    # subclass hooks
    # ------------------------------------------------------------------
    @classmethod
    def build_mesh(cls, devices=None, config: Optional[dict] = None):
        """Mesh the rules should build for this model class.

        Plain data-parallel models use one ``dp`` axis (two-level
        ``('dp_dcn', 'dp')`` when the config carries ``dcn_shape``);
        models with extra mesh axes (the sequence-parallel transformer)
        override so ``rule.init(...)`` engages them without the caller
        hand-building a mesh."""
        return make_mesh(
            devices=devices, dcn_shape=(config or {}).get("dcn_shape")
        )

    def build_data(self) -> None:
        raise NotImplementedError

    def build_net(self) -> Tuple[Layer, Tuple[int, ...]]:
        raise NotImplementedError

    # ------------------------------------------------------------------
    # contract: build_model
    # ------------------------------------------------------------------
    def build_model(self) -> None:
        cfg = self.config
        self.net, self.input_shape = self.build_net()
        self.rng, init_key = jax.random.split(self.rng)
        params, net_state, out_shape = self.net.init(init_key, self.input_shape)
        self.out_shape = out_shape
        self.optimizer = optim_lib.from_config(cfg)  # sgd | adam | adamw
        self._zero = None
        if bool(cfg.zero1) and self.n_workers > 1:
            from theanompi_tpu.parallel.zero import Zero1

            # the configured exchange strategy selects zero's wire too
            # (r5): block strategies quantize the reduce-scatter and
            # ride the fp16-block param gather with exact fp32 master
            # shards; 'ar' keeps the plain fp32 legs. Cast wires are
            # rejected by Zero1 itself (foldable — see exchanger).
            self._zero = Zero1(
                self.optimizer, world=self.n_workers,
                strategy=str(cfg.exch_strategy),
            )
            opt_state = self._zero.init(params)
        else:
            opt_state = self.optimizer.init(params)
        # replicate across the mesh (reference: each rank holds a copy)
        self.params = replicate(self.mesh, params)
        self.net_state = replicate(self.mesh, net_state)
        self.opt_state = replicate(self.mesh, opt_state)
        self.n_params = count_params(params)

    # ------------------------------------------------------------------
    # loss — default classifier; GAN overrides
    # ------------------------------------------------------------------
    def _cast_input(self, x):
        dtype = self.config.compute_dtype
        return x.astype(jnp.dtype(dtype)) if dtype is not None else x

    def _metrics(self, logits, y):
        """(err, err5) for classifier logits — shared by the base loss
        and model overrides (GoogLeNet aux, the LM) so metric logic has
        one home."""
        err = losses.classification_error(logits, y)
        if self.config.val_top5 and logits.shape[-1] > 5:
            err5 = losses.topk_error(logits, y, k=5)
        else:
            err5 = err
        return err, err5

    def loss_and_metrics(self, params, net_state, x, y, train: bool, rng):
        # named scopes are metadata on the same operations (the backward
        # pass inherits the forward's): a profile groups by them
        with jax.named_scope("forward"):
            logits, new_state = self.net.apply(
                params, net_state, self._cast_input(x), train=train, rng=rng
            )
        with jax.named_scope("loss"):
            loss = losses.softmax_cross_entropy(logits, y)
            err, err5 = self._metrics(logits, y)
        return loss, (err, err5, new_state)

    # ------------------------------------------------------------------
    # contract: compile_train / compile_val  (reference names [DRIVER])
    # ------------------------------------------------------------------
    def _opt_state_specs(self):
        """PartitionSpec tree for the optimizer state, derived from its
        actual structure: any top-level entry shaped like ``params``
        (velocity, Adam moments, …) mirrors ``param_specs``; everything
        else (lr, step counters) is replicated. Keeps the base class
        optimizer-agnostic."""
        ef_spec = P(self.exchange_axes)  # leading per-device axis
        if self.param_specs is None:
            if "ef_wire" not in self.opt_state:
                return P()
            return {
                k: (
                    jax.tree.map(lambda _: ef_spec, v)
                    if k == "ef_wire"
                    else jax.tree.map(lambda _: P(), v)
                )
                for k, v in self.opt_state.items()
            }
        shard_keys = optim_lib.param_shaped_entries(
            self.opt_state, jax.tree.structure(self.params)
        )
        return {
            k: (
                self.param_specs
                if k in shard_keys
                else (
                    jax.tree.map(lambda _: ef_spec, v)
                    if k == "ef_wire"
                    else jax.tree.map(lambda _: P(), v)
                )
            )
            for k, v in self.opt_state.items()
        }

    def _place_sharded_state(self) -> None:
        """Lay params / params-shaped optimizer entries out per
        ``param_specs`` (tensor-parallel leaves land sharded, not
        replicated). Idempotent; no-op for plain DP models."""
        if self.param_specs is None:
            return
        from jax.sharding import NamedSharding

        def put(tree, specs):
            return jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s)),
                tree,
                specs,
            )

        self.params = put(self.params, self.param_specs)
        specs = self._opt_state_specs()  # keyed lookup, not positional zip
        self.opt_state = {
            k: put(v, specs[k]) for k, v in self.opt_state.items()
        }

    def compile_train(self, exchanger: Optional[BSP_Exchanger] = None):
        cfg = self.config
        ef = bool(cfg.get("error_feedback", False))
        if ef:
            # EF keeps a per-device residual of what the lossy wire
            # dropped and re-sends it next step — low-bit exchanges then
            # converge like fp32 instead of silently flooring small
            # gradient components. Scope (same style as zero1 below):
            # plain single-axis DP, cdd, a lossy strategy.
            axes = self.exchange_axes
            axes_t = (
                tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
            )
            unsupported = {
                "exch_strategy 'ar' (lossless wire)": cfg.exch_strategy == "ar",
                "cast wires (XLA can fold their casts — block "
                "strategies only)": cfg.exch_strategy in ("bf16", "fp16"),
                "sync_mode != 'cdd'": cfg.sync_mode != "cdd",
                "sharded params (tp/pp/ep)": self.param_specs is not None,
                # data-parallel axes only — incl. the two-level dp_dcn×dp
                # mesh (the residual chains over the hierarchical wire's
                # per-axis folds; exchanger._chain_with_rt). sp/tp/ep
                # exchanges carry different semantics and stay out.
                "exchange axes beyond dp/dp_dcn": (
                    not set(axes_t) <= {DATA_AXIS, DCN_AXIS}
                ),
                "zero1": self._zero is not None,
            }
            bad = [k for k, v in unsupported.items() if v]
            if bad:
                raise ValueError(
                    f"error_feedback does not support: {', '.join(bad)}"
                )
            if "ef_wire" not in self.opt_state:
                world = 1
                for a in axes_t:
                    world *= int(self.mesh.shape[a])
                sh = NamedSharding(self.mesh, P(axes_t))
                # create ALREADY sharded over the exchange axes — a
                # world×fp32 copy of every param materialized on one
                # device first would spike HBM for nothing
                self.opt_state["ef_wire"] = jax.tree.map(
                    lambda p: jnp.zeros(
                        (world, *p.shape), jnp.float32, device=sh
                    ),
                    self.params,
                )
        elif "ef_wire" in self.opt_state:
            # flag off but residuals present (EF checkpoint resumed with
            # error_feedback=False, or a recompile after flipping the
            # config): the step would drop the entry while out_specs
            # still expect it — remove it here instead
            self.opt_state = {
                k: v for k, v in self.opt_state.items() if k != "ef_wire"
            }
        self._place_sharded_state()
        overlap = str(cfg.get("exchange_overlap", "bucket"))
        if overlap not in ("leaf", "bucket", "indag"):
            raise ValueError(
                f"exchange_overlap must be leaf|bucket|indag, got {overlap!r}"
            )
        bucket_bytes = (
            None
            if overlap == "leaf"
            else int(float(cfg.get("exchange_bucket_mb", 4.0)) * (1 << 20))
        )
        exchanger = exchanger or BSP_Exchanger(
            strategy=cfg.exch_strategy,
            axis=self.exchange_axes,
            mesh=self.mesh,
            bucket_bytes=bucket_bytes,
        )
        axis = exchanger.axis
        opt = self.optimizer
        sync_mode = cfg.sync_mode
        if sync_mode not in ("cdd", "avg"):
            raise ValueError(f"sync_mode must be 'cdd' or 'avg', got {sync_mode!r}")
        if sync_mode == "avg" and self.param_specs is not None:
            raise ValueError(
                "sync_mode='avg' (parameter averaging) is data-parallel "
                "only; tensor-parallel models must use 'cdd'"
            )
        zero = self._zero
        if zero is not None:
            # ZeRO-1 fuses the gradient reduction into the sharded
            # update; scope: plain single-level dp. The wire may be fp32
            # ('ar') or a block strategy (r5: quantized reduce-scatter +
            # fp16-block param gather with exact master shards); cast
            # wires were already rejected at Zero1 construction.
            unsupported = {
                "sync_mode != 'cdd'": sync_mode != "cdd",
                "sharded params (tp/pp/ep)": self.param_specs is not None,
                "exchange axes beyond dp": self.exchange_axes != DATA_AXIS,
                "grad_clip_norm": cfg.grad_clip_norm is not None,
            }
            bad = [k for k, v in unsupported.items() if v]
            if bad:
                raise ValueError(f"zero1 does not support: {', '.join(bad)}")
        clip = cfg.grad_clip_norm

        param_specs = self.param_specs

        def maybe_clip(grads):
            if clip is None:
                return grads
            if param_specs is None:
                sumsq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
            else:
                # tensor-parallel leaves hold disjoint shards: their local
                # sum-of-squares must be summed over the axes they shard
                # on to contribute the full-leaf norm
                from theanompi_tpu.parallel.exchanger import spec_axis_names

                def leaf_sq(g, s):
                    v = jnp.sum(jnp.square(g))
                    ax = spec_axis_names(s) if s is not None else ()
                    return lax.psum(v, ax) if ax else v

                sumsq = sum(
                    jax.tree.leaves(jax.tree.map(leaf_sq, grads, param_specs))
                )
            gnorm = jnp.sqrt(sumsq)
            scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
            return jax.tree.map(lambda g: g * scale, grads)

        device_aug = bool(cfg.get("device_aug", False))
        aug_crop = cfg.get("crop_size", None)
        aug_mirror = bool(cfg.get("mirror", True))
        accum = int(cfg.get("grad_accum", 1) or 1)

        indag_mask = None
        if overlap == "indag":
            from theanompi_tpu.parallel import bucketing as _bucketing

            # in-DAG issue: each GradSyncGroup's backward reduces its
            # own gradients the moment they are complete. Scope (same
            # style as ef/zero1 above): plain cdd over replicated
            # params, no residual recurrence, no microbatch scan (the
            # scan body would issue K reductions per group per step).
            unsupported = {
                "sync_mode != 'cdd'": sync_mode != "cdd",
                "error_feedback": ef,
                "zero1": zero is not None,
                "grad_accum > 1": accum > 1,
                "sharded params (tp/pp/ep)": self.param_specs is not None,
            }
            bad = [k for k, v in unsupported.items() if v]
            if bad:
                raise ValueError(
                    f"exchange_overlap='indag' does not support: "
                    f"{', '.join(bad)}"
                )
            if not _bucketing.has_sync_groups(self.net):
                raise ValueError(
                    "exchange_overlap='indag' needs grad-sync groups, "
                    "and this model's build_net wired none — models opt "
                    "in by wrapping layer groups in "
                    "bucketing.GradSyncGroup when the config asks for "
                    "'indag' (TransformerLM blocks, ResNet50 stages do)"
                )
            indag_mask = _bucketing.sync_group_mask(self.net, self.params)

            def _make_group_reducer(ex_key):
                def reduce_group(gid, gtree):
                    k = (
                        jax.random.fold_in(ex_key, 1_000_000 + int(gid))
                        if ex_key is not None
                        else None
                    )
                    return exchanger.reduce_grads(
                        gtree, rng=k, tag=f"g{int(gid)}"
                    )

                return reduce_group

        def micro_grads(params, net_state, x, y, rng):
            """fwd+bwd on one microbatch (augment inside, so each
            microbatch draws fresh crops)."""
            if device_aug:
                from theanompi_tpu.ops.augment import random_crop_mirror

                rng, aug_key = jax.random.split(rng)
                x = random_crop_mirror(
                    aug_key, x, crop_size=aug_crop, mirror=aug_mirror
                )

            def loss_fn(p):
                return self.loss_and_metrics(p, net_state, x, y, True, rng)

            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        def shard_step(params, net_state, opt_state, x, y, rng):
            rng = jax.random.fold_in(rng, lax.axis_index(axis))
            # ALL keys this step uses come from one split so none can
            # collide: accum microbatch keys + the exchange (int8_sr) key
            if accum == 1:
                k_micro, ex_key = jax.random.split(rng)
                if indag_mask is not None:
                    from theanompi_tpu.parallel import bucketing as _B

                    # trace-time scope: while value_and_grad traces the
                    # backward, each GradSyncGroup's custom-vjp bwd
                    # finds this reducer and issues its bucket's
                    # reduction in place — the exchange is embedded in
                    # the backward DAG, not appended after it
                    with _B.issue_scope(_make_group_reducer(ex_key)):
                        (loss, (err, _, new_state)), grads = micro_grads(
                            params, net_state, x, y, k_micro
                        )
                else:
                    (loss, (err, _, new_state)), grads = micro_grads(
                        params, net_state, x, y, k_micro
                    )
            else:
                # gradient accumulation: scan over K microbatches, only
                # 1/K of the activations live at once — big effective
                # batches without the HBM. Equal microbatch sizes, so
                # mean-of-means == the full local-batch mean; BN stats
                # thread sequentially (per-microbatch stats, as K
                # smaller steps would see).
                # Divisibility is validated HOST-SIDE (_check_grad_accum
                # via train_iter) — a shape branch inside traced code is
                # a recompile axis (graftlint GL-J003); an indivisible
                # batch reaching this reshape directly still fails at
                # trace time, just with a terser message.
                xs = x.reshape(accum, -1, *x.shape[1:])
                ys = y.reshape(accum, -1, *y.shape[1:])
                all_keys = jax.random.split(rng, accum + 1)
                keys, ex_key = all_keys[:accum], all_keys[accum]

                def micro(carry, inp):
                    g_acc, l_acc, e_acc, st = carry
                    xm, ym, k = inp
                    (l, (e, _, st2)), g = micro_grads(params, st, xm, ym, k)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l, e_acc + e, st2), None

                g0 = jax.tree.map(jnp.zeros_like, params)
                (grads, loss, err, new_state), _ = lax.scan(
                    micro, (g0, 0.0, 0.0, net_state), (xs, ys, keys)
                )
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss, err = loss / accum, err / accum
            if zero is not None:
                # reduce-scatter + shard update + params all-gather; the
                # exchanger is bypassed (the reduction IS the scatter)
                params, opt_state = zero.update_shard(
                    params, grads, opt_state, rng=ex_key
                )
            elif sync_mode == "cdd":
                if ef:
                    # error feedback: send grads + residual, keep what
                    # the wire's first quantization leg drops. The
                    # residual leaf carries a leading per-device axis
                    # (size 1 inside this shard) so shard_map can keep
                    # genuinely different values on every device.
                    # reduce_with_residual packs leg 1 ONCE per leaf —
                    # a separate local_roundtrip would double the
                    # Pallas kernel launches.
                    ef_local = jax.tree.map(
                        lambda e: e[0], opt_state["ef_wire"]
                    )
                    send = jax.tree.map(
                        lambda g, e: g.astype(jnp.float32) + e, grads, ef_local
                    )
                    reduced, rt = exchanger.reduce_with_residual(
                        send, param_specs, rng=ex_key
                    )
                    new_ef = jax.tree.map(
                        lambda s, r: (s - r)[None], send, rt
                    )
                    grads = maybe_clip(reduced)
                else:
                    # with in-DAG issue the sync-grouped leaves arrive
                    # already reduced; done_mask passes them through and
                    # this call sweeps up only the leftovers (stem,
                    # embeddings, head, norms)
                    with jax.named_scope("exchange"):
                        grads = exchanger.reduce_grads(
                            grads, param_specs, rng=ex_key,
                            done_mask=indag_mask,
                        )
                    grads = maybe_clip(grads)
                with jax.named_scope("update"):
                    params, opt_state = opt.update(params, grads, opt_state)
                if ef:
                    # AFTER update: optimizers rebuild their state dict
                    # from known keys, which would silently drop ef_wire
                    opt_state = {**opt_state, "ef_wire": new_ef}
            else:  # avg: local step, then parameter averaging (DP-only;
                # TP models are rejected above, so no per-leaf specs here)
                params, opt_state = opt.update(params, maybe_clip(grads), opt_state)
                params = exchanger.average_params(params, rng=ex_key)
                # moments drift per-replica under avg: sync every
                # param-shaped entry (SGD velocity, Adam mu/nu, ...) —
                # through the SAME wire as the params, or a plain fp32
                # pmean here would move more bytes than the compressed
                # param exchange saves
                sync_keys = optim_lib.param_shaped_entries(
                    opt_state, jax.tree.structure(self.params)
                )
                opt_state = {
                    k: (
                        exchanger.average_params(
                            v,
                            rng=(
                                jax.random.fold_in(ex_key, 1_000 + i)
                                if ex_key is not None
                                else None
                            ),
                        )
                        if k in sync_keys
                        else v
                    )
                    for i, (k, v) in enumerate(opt_state.items())
                }
            # BN running stats: sync so the replicated out-spec holds
            new_state = jax.tree.map(lambda s: lax.pmean(s, axis), new_state)
            loss = lax.pmean(loss, axis)
            err = lax.pmean(err, axis)
            return params, new_state, opt_state, loss, err

        pspec = P() if param_specs is None else param_specs
        opt_spec = (
            zero.state_specs(self.opt_state)
            if zero is not None
            else self._opt_state_specs()
        )
        mapped = jax.shard_map(
            shard_step,
            mesh=self.mesh,
            in_specs=(pspec, P(), opt_spec, self.batch_spec, self.batch_spec, P()),
            out_specs=(pspec, P(), opt_spec, P(), P()),
            check_vma=False,
        )
        self.train_fn = jax.jit(mapped, donate_argnums=(0, 1, 2))
        self.exchanger = exchanger
        return self.train_fn

    def compile_val(self):
        axes = self.exchange_axes
        self._place_sharded_state()

        def shard_eval(params, net_state, x, y):
            loss, (err, err5, _) = self.loss_and_metrics(
                params, net_state, x, y, False, None
            )
            return (
                lax.pmean(loss, axes),
                lax.pmean(err, axes),
                lax.pmean(err5, axes),
            )

        pspec = P() if self.param_specs is None else self.param_specs
        mapped = jax.shard_map(
            shard_eval,
            mesh=self.mesh,
            in_specs=(pspec, P(), self.batch_spec, self.batch_spec),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        self.val_fn = jax.jit(mapped)
        return self.val_fn

    # ------------------------------------------------------------------
    # contract: train_iter / val_iter
    # ------------------------------------------------------------------
    def reset_train_iter(self, epoch: int) -> None:
        self.data.shuffle(epoch)
        self._train_it = prefetch_to_mesh(
            self.data.train_batches(),
            self.mesh,
            depth=int(self.config.prefetch_depth),
            spec=self.batch_spec,
        )

    def reset_val_iter(self) -> None:
        self._val_it = prefetch_to_mesh(
            self.data.val_batches(), self.mesh, depth=1, spec=self.batch_spec
        )

    def _check_grad_accum(self, global_batch: int) -> None:
        """Host-side grad_accum divisibility guard (moved out of the
        traced ``shard_step`` — graftlint GL-J003: a shape-dependent
        branch in traced code is a recompile axis).  ``global_batch``
        is the leading dim of the un-sharded batch; each of the
        ``n_workers`` batch shards must split into ``grad_accum`` equal
        microbatches."""
        accum = int(self.config.get("grad_accum", 1) or 1)
        if accum <= 1:
            return
        per_shard = global_batch // max(1, self.n_workers)
        if per_shard % accum:
            raise ValueError(
                f"per-shard batch {per_shard} not divisible by "
                f"grad_accum={accum}"
            )

    def train_iter(self, count: int, recorder) -> Tuple[float, float]:
        # boundary span over the whole step on the host; the recorder's
        # `wait` and `calc` are its children
        with obs.span("train_iter", boundary=True, iter=count):
            return self._train_iter(count, recorder)

    def _train_iter(self, count: int, recorder) -> Tuple[float, float]:
        if self.train_fn is None:
            self.compile_train()
        if self._train_it is None:
            self.reset_train_iter(self.current_epoch)
        recorder.start("wait")
        x, y = next(self._train_it)
        recorder.end("wait")
        self._check_grad_accum(int(x.shape[0]))
        recorder.start("calc")
        self.rng, step_key = jax.random.split(self.rng)
        out = self.train_fn(
            self.params, self.net_state, self.opt_state, x, y, step_key
        )
        self.params, self.net_state, self.opt_state = out[0], out[1], out[2]
        loss, err = out[3], out[4]
        if self.config.sync_each_iter or metrics_must_sync():
            # pulling the scalars fences the step (honest per-step calc
            # timing; the comm is fused in-graph so calc includes exchange)
            loss, err = float(loss), float(err)
        recorder.end("calc")
        recorder.train_error(count, loss, err)
        return loss, err

    def val_iter(self, count: int, recorder) -> Tuple[float, float, float]:
        if self.val_fn is None:
            self.compile_val()
        x, y = next(self._val_it)
        # device scalars; run_validation accumulates on device and syncs once
        return self.val_fn(self.params, self.net_state, x, y)

    def _val_batch(self, p, s, x, y):
        """One validation batch → (loss, err, err5) device scalars.
        The hook models with a different val_fn signature override
        (LSGAN's takes no labels) so run_validation's fence/override/
        recording semantics stay in ONE place."""
        return self.val_fn(p, s, x, y)

    def run_validation(
        self, count: int, recorder, params=None, net_state=None, extra=None
    ) -> Tuple[float, float, float]:
        """Full-set validation.

        ``params``/``net_state`` override the model's own state for
        validating FOREIGN weights (the EASGD server validates the center
        params mid-training this way — reference ``easgd_server.py``
        duties, SURVEY.md §4.3 — without touching the live training
        state, whose buffers the jitted step donates).  ``extra`` rides
        the recorder's val row (provenance stamps)."""
        if not self.data.n_batch_val:
            return float("nan"), float("nan"), float("nan")
        if self.val_fn is None:
            self.compile_val()
        p = self.params if params is None else params
        s = self.net_state if net_state is None else net_state
        # FENCE the train->val boundary: with sync_each_iter=False the
        # last train step is still executing asynchronously on the
        # 8-thread fake-device pool when validation dispatches its own
        # 8-participant program. On the CPU backend that overlap can
        # deadlock the collective rendezvous (r4: a SOLO suite run
        # stalled here with every thread futex-parked and zero CPU; the
        # same stall under the default terminate timeout is the r3/r4
        # intermittent mid-suite abort). Block on the model's OWN params
        # — on the foreign-params path (EASGD center validation) ``p``
        # is a freshly replicated array that is ready immediately while
        # the live training state is the thing still in flight. One
        # blocking sync per validation is noise next to a full val sweep.
        jax.block_until_ready(self.params)
        if params is not None:
            jax.block_until_ready(p)
        self.reset_val_iter()
        sync = metrics_must_sync()
        # XLA:CPU: host each batch's scalars (blocking read) and
        # accumulate on the HOST — zero extra program dispatches (see
        # metrics_must_sync). TPU accumulates on device, one sync at end.
        tot = [0.0, 0.0, 0.0] if sync else jnp.zeros((3,))
        n = 0
        for _ in range(self.data.n_batch_val):
            x, y = next(self._val_it)
            loss, err, err5 = self._val_batch(p, s, x, y)
            if sync:
                tot = [
                    tot[0] + float(loss),
                    tot[1] + float(err),
                    tot[2] + float(err5),
                ]
            else:
                tot = tot + jnp.array([loss, err, err5])
            n += 1
        loss, err, err5 = (float(v) / n for v in tot)
        recorder.val_error(count, loss, err, err5, extra=extra)
        recorder.print_val_info(count)
        return loss, err, err5

    # ------------------------------------------------------------------
    # contract: hyperparameter scheduling
    # ------------------------------------------------------------------
    def adjust_hyperp(self, epoch: int) -> None:
        """Per-epoch lr schedule (reference: shared-var lr set)."""
        self.current_epoch = epoch
        lr = self.lr_schedule(epoch) * self._lr_scale
        self.opt_state = optim_lib.set_lr(self.opt_state, lr)

    def scale_lr(self, factor: float) -> None:
        """Linear-scaling for N workers (reference: `scale_lr`)."""
        self._lr_scale = float(factor)
        self.opt_state = optim_lib.set_lr(
            self.opt_state, self.lr_schedule(self.current_epoch) * self._lr_scale
        )

    # ------------------------------------------------------------------
    # checkpoint + cleanup
    # ------------------------------------------------------------------
    def checkpoint_state(self) -> dict:
        """The full training-state pytree a checkpoint carries."""
        return {
            "params": self.params,
            "net_state": self.net_state,
            "opt_state": self.opt_state,
            "epoch": self.current_epoch,
            "rng": self.rng,
        }

    def save_model(self, path: str, checkpointer=None) -> str:
        """Snapshot to ``path``. With a ``checkpointer``
        (``utils.checkpoint.AsyncCheckpointer``) the device→host copy is
        synchronous but the disk write happens on its worker thread."""
        from theanompi_tpu.utils import checkpoint

        if checkpointer is not None:
            checkpointer.save(path, self.checkpoint_state())
            return path
        return checkpoint.save(path, self.checkpoint_state())

    def load_model(self, path: str) -> None:
        from theanompi_tpu.utils import checkpoint

        blob = checkpoint.restore(path)
        if jax.tree.structure(blob["params"]) != jax.tree.structure(self.params):
            raise ValueError(
                f"checkpoint {path!r} has a different params structure than "
                f"this model — an architecture config changed between save "
                "and load (e.g. GoogLeNet aux_heads, WResNet depth). "
                "Rebuild the model with the config the checkpoint was "
                "trained with."
            )
        ck_opt = blob["opt_state"]
        ck_ef = None
        if isinstance(ck_opt, dict) and "ef_wire" in ck_opt:
            # error-feedback residuals are handled apart from the rest of
            # the state: a fresh model has no ef_wire until compile_train
            # (the layout check below must not trip on it), and the
            # leaves must go back SHARDED over dp — replicate() would put
            # world x params of fp32 on every device (review r4)
            ck_ef = ck_opt["ef_wire"]
            ck_opt = {k: v for k, v in ck_opt.items() if k != "ef_wire"}
        my_opt = (
            {k: v for k, v in self.opt_state.items() if k != "ef_wire"}
            if isinstance(self.opt_state, dict)
            else self.opt_state
        )
        ck_shapes = [jnp.shape(l) for l in jax.tree.leaves(ck_opt)]
        my_shapes = [jnp.shape(l) for l in jax.tree.leaves(my_opt)]
        if ck_shapes != my_shapes:
            raise ValueError(
                f"checkpoint {path!r} has a different optimizer-state "
                "layout than this model — the optimizer or zero1 config "
                "changed between save and load (zero1 stores flat "
                "dp-sharded moments). Rebuild with the saving config."
            )
        had_ef = isinstance(self.opt_state, dict) and "ef_wire" in self.opt_state
        self.params = replicate(self.mesh, blob["params"])
        self.net_state = replicate(self.mesh, blob["net_state"])
        self.opt_state = replicate(self.mesh, ck_opt)
        if ck_ef is not None:
            world = int(self.mesh.shape[DATA_AXIS])
            lead = jax.tree.leaves(ck_ef)[0].shape[0]
            if not bool(self.config.get("error_feedback", False)):
                print(
                    "[load_model] dropping ef_wire residuals: this model "
                    "has error_feedback=False",
                    flush=True,
                )
            elif lead != world:
                # resuming on a different dp size: residuals are an
                # optimization, not training state — reset (compile_train
                # re-creates zeros) rather than guess a re-layout
                print(
                    f"[load_model] dropping ef_wire residuals: checkpoint "
                    f"world {lead} != mesh dp {world}",
                    flush=True,
                )
            else:
                sh = NamedSharding(self.mesh, P(DATA_AXIS))
                self.opt_state["ef_wire"] = jax.tree.map(
                    lambda a: jax.device_put(a, sh), ck_ef
                )
        if ("ef_wire" in self.opt_state) != had_ef:
            # the restored state's EF composition differs from what the
            # compiled step's in/out specs expect — force a recompile
            # (train_iter compiles lazily when train_fn is None)
            self.train_fn = None
        self.current_epoch = int(blob["epoch"])
        self.rng = blob["rng"]
        # tensor-parallel leaves go back to their sharded layout
        # (checkpoints store full global arrays either way)
        self._place_sharded_state()

    def describe(self) -> str:
        """One-paragraph model summary (the reference printed per-rank
        model info at startup; workers print this on rank 0)."""
        cfg = self.config
        mesh_desc = ", ".join(
            f"{a}={int(s)}" for a, s in zip(self.mesh.axis_names, self.mesh.devices.shape)
        )
        # effective lr (post schedule + linear scaling), not the raw
        # config value — this line is what operators copy into reports
        eff_lr = self.lr_schedule(self.current_epoch) * self._lr_scale
        zero_on = getattr(self, "_zero", None) is not None  # GAN models
        # override build_model and never set _zero
        lines = [
            f"{type(self).__name__}: {self.n_params:,} params, "
            f"mesh({mesh_desc}), global_batch={self.global_batch} "
            f"({self.batch_size}/shard x {self.n_workers})",
            f"  optimizer={cfg.get('optimizer', 'sgd')} lr={eff_lr:g} "
            f"exch={cfg.exch_strategy} sync={cfg.sync_mode}"
            + (" zero1" if zero_on else "")
            + (f" grad_accum={cfg.grad_accum}" if int(cfg.get('grad_accum', 1) or 1) > 1 else ""),
        ]
        if cfg.compute_dtype:
            lines.append(f"  compute_dtype={cfg.compute_dtype}")
        return "\n".join(lines)

    def cleanup(self) -> None:
        self._train_it = None
        self._val_it = None
