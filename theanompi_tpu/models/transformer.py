"""Long-context decoder-only transformer LM with ring sequence parallelism.

No reference analog — Theano-MPI's zoo is 2016 CNNs/GAN (SURVEY.md §3.4,
§6: long-context "ABSENT") — but long-context training is first-class in
this framework, so the model demonstrates the full sharding surface:

- batch over the ``dp`` mesh axis (the reference's data parallelism),
- sequence over the ``sp`` mesh axis with exact **ring attention**
  (``parallel.ring_attention``: K/V blocks rotate over ICI neighbor
  links via ``ppermute`` while each device keeps its query shard),
- gradients reduced over *both* axes in-graph through the standard
  ``BSP_Exchanger`` (every device holds a partial batch × sequence
  gradient contribution).

It implements the unchanged model contract, so ``BSP`` drives it like
any CNN::

    from theanompi_tpu import BSP
    rule = BSP()
    rule.init(devices=8,
              modelfile='theanompi_tpu.models.transformer',
              modelclass='TransformerLM',
              model_config=dict(sp=4, seq_len=8192))
    rule.wait()
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from theanompi_tpu.data.providers import LMTextData
from theanompi_tpu.models.base import TpuModel
from theanompi_tpu.ops import attention as A
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import losses, optim
from theanompi_tpu.parallel.ring_attention import SEQ_AXIS
from theanompi_tpu.runtime.mesh import DATA_AXIS, TP_AXIS, make_mesh


class TransformerLM(TpuModel):
    default_config = dict(
        batch_size=8,  # per dp shard
        seq_len=512,  # GLOBAL sequence length (sharded over sp)
        vocab_size=256,
        d_model=256,
        n_heads=8,
        n_layers=4,
        mlp_ratio=4,
        sp=1,  # sequence-parallel degree (mesh sp-axis size)
        sp_mode="ring",  # 'ring' (ppermute K/V ring) | 'alltoall' (Ulysses)
        attn_impl="xla",  # 'xla' (fused dense) | 'flash' (Pallas kernels:
        # dense path, alltoall local attention, and per-ring-step blocks)
        tp=1,  # tensor-parallel degree (Megatron-style column/row sharding)
        pp=1,  # pipeline-parallel depth: n_layers/pp TransformerBlocks per
        # GPipe stage (parallel.pipeline), activations hopping over ICI
        pp_micro=4,  # microbatches per step (bubble = (pp-1)/(m+pp-1))
        lr=0.1,
        momentum=0.9,
        weight_decay=0.0,
        n_epochs=5,
        lr_boundaries=(3,),
        data_dir=None,
        n_synth_train=32,
        n_synth_val=2,
        val_top5=True,
        exch_strategy="int8_sr",  # exchanger.DEFAULT_COMPRESSED_STRATEGY:
        # unbiased SR int8 wire, 4x fewer bytes than ar at the zero1-
        # evidenced convergence floor (docs/convergence README)
        moe_experts=0,  # >0 = MoE FFN blocks (GShard-style: experts
        # shard over the existing dp axis — parallel.moe.MoeMlp)
        moe_top_k=1,
        moe_hidden=None,  # None = d_model * mlp_ratio
        moe_aux_coef=0.01,  # weight of the Switch load-balance aux loss
        remat=False,  # gradient-checkpoint each block (ops.layers.Remat):
        # backward recomputes the block instead of saving activations —
        # the long-context HBM lever alongside sp
        block="dense",  # 'dense': pre-LN blocks over a learned position
        # table (above).  'latent_moe': ops.latent_block — RMSNorm, YaRN
        # rotary positions (no table: seq_len only sizes the data),
        # latent attention, a gated feed-forward in the first
        # `first_k_dense` blocks and `moe_experts` sigmoid-routed experts
        # (+ `n_shared_experts`) after them, a residual of `hc_mult`
        # streams (`hc_mult` 1: the plain residual); its sizes are the
        # keys below.  `q_lora_rank` None: queries from one matrix;
        # `mla_nope`: no rotary positions; `kda_layers`: the layers
        # (counted from 1) whose mixer is Kimi delta attention
        # (ops.kda: `kda_head_dim`, `kda_conv`) and not latent
        # attention; `moe_experts_held`: how many of the `moe_experts`
        # (the first ones) this model holds and computes (parallel.moe)
        q_lora_rank=None, kv_lora_rank=None, qk_nope_head_dim=None,
        qk_rope_head_dim=None, v_head_dim=None,
        ffn_hidden=None, first_k_dense=1, n_shared_experts=0,
        route_scale=1.0, rms_norm_eps=1e-6,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=30.0,
        mla_nope=False, kda_layers=(), kda_head_dim=128, kda_conv=4,
        moe_experts_held=None,
        rope=None,  # dict: theta, factor, original_max_position,
        # beta_fast, beta_slow, mscale, mscale_all_dim (ops.attention)
        param_dtype="float32",  # the dtype the weights are HELD in
        init_weights=True,  # False: the model holds the SHAPES of its
        # weights (and no optimizer state) until the caller installs
        # arrays — a stage too large to draw twice (the benchmark's
        # driver replaces the weights anyway)
    )

    @classmethod
    def build_mesh(cls, devices=None, config=None):
        cfg = dict(cls.default_config)
        cfg.update(dict(config or {}))
        sp = int(cfg.get("sp", 1))
        tp = int(cfg.get("tp", 1))
        pp = int(cfg.get("pp", 1))
        devices = list(devices) if devices is not None else jax.devices()
        if pp > 1:
            if len(devices) % (pp * sp * tp):
                raise ValueError(
                    f"pp={pp}·sp={sp}·tp={tp} does not divide "
                    f"{len(devices)} devices"
                )
            from theanompi_tpu.runtime.mesh import PP_AXIS

            # innermost → outermost: tp (hottest per-microbatch psums),
            # sp (ring/alltoall hops), pp (stage hops), dp. Axes of
            # size 1 are omitted so the simple cases keep simple meshes.
            shape = [len(devices) // (pp * sp * tp), pp]
            names = [DATA_AXIS, PP_AXIS]
            if sp > 1:
                shape.append(sp)
                names.append(SEQ_AXIS)
            if tp > 1:
                shape.append(tp)
                names.append(TP_AXIS)
            return make_mesh(
                shape=tuple(shape), axis_names=tuple(names), devices=devices
            )
        if len(devices) % (sp * tp):
            raise ValueError(
                f"sp={sp}·tp={tp} does not divide {len(devices)} devices"
            )
        if tp > 1:
            # innermost axis = tp so its psums ride nearest-neighbor ICI
            return make_mesh(
                shape=(len(devices) // (sp * tp), sp, tp),
                axis_names=(DATA_AXIS, SEQ_AXIS, TP_AXIS),
                devices=devices,
            )
        return make_mesh(
            shape=(len(devices) // sp, sp),
            axis_names=(DATA_AXIS, SEQ_AXIS),
            devices=devices,
        )

    def __init__(self, config=None, mesh=None, **overrides):
        cfg = dict(self.default_config)
        cfg.update(dict(config or {}))
        cfg.update(overrides)
        sp = int(cfg.get("sp", 1))
        tp = int(cfg.get("tp", 1))
        pp = int(cfg.get("pp", 1))
        if mesh is None:
            mesh = self.build_mesh(config=cfg)
        if pp > 1:
            from theanompi_tpu.runtime.mesh import PP_AXIS

            if int(cfg.get("moe_experts", 0)) and float(
                cfg.get("moe_aux_coef", self.default_config["moe_aux_coef"])
            ):
                raise ValueError(
                    "pp composes with MoE only at moe_aux_coef=0: the "
                    "GPipe scan carries activations only, so the "
                    "load-balance aux (which rides state) is unavailable "
                    "— set moe_aux_coef=0"
                )
            n_layers = int(cfg.get("n_layers", self.default_config["n_layers"]))
            if n_layers % pp:
                raise ValueError(
                    f"n_layers={n_layers} must divide by pp={pp} "
                    f"(homogeneous stages of n_layers/pp blocks)"
                )
            self._require_mesh_axis(mesh, PP_AXIS, pp)
            # mirror the non-pipelined path: a hand-built mesh's sp/tp
            # axes are ADOPTED when the config doesn't name them —
            # otherwise half the devices would silently run duplicate
            # replicated work over an unused axis
            if sp == 1 and SEQ_AXIS in mesh.axis_names:
                sp = int(mesh.shape[SEQ_AXIS])
            if tp == 1 and TP_AXIS in mesh.axis_names:
                tp = int(mesh.shape[TP_AXIS])
            if sp > 1:
                self._require_mesh_axis(mesh, SEQ_AXIS, sp)
            if tp > 1:
                self._require_mesh_axis(mesh, TP_AXIS, tp)
            self.pp_size = pp
            self.sp_size = sp
            self.tp_size = tp
            # batch shards over dp and (when sp) the sequence dim over
            # sp; replicated over pp/tp (stage masking in the GPipe scan
            # selects what each stage consumes). The ring/alltoall sp
            # collectives run inside every pipeline tick, uniformly
            # across pp ranks — SPMD-safe. Stage-stacked leaves skip pp
            # — and their Megatron-split dims skip tp — via param_specs;
            # replicated leaves carry identical grads across pp
            # (entry/exit custom-VJP pair) and tp (the in-block f/g
            # pair); sp shards hold partial token grads, so sp always
            # joins the mean axes.
            self.batch_spec = (
                P(DATA_AXIS, SEQ_AXIS) if sp > 1 else P(DATA_AXIS)
            )
            self.exchange_axes = (
                (DATA_AXIS, PP_AXIS)
                + ((SEQ_AXIS,) if sp > 1 else ())
                + ((TP_AXIS,) if tp > 1 else ())
            )
            super().__init__(cfg, mesh=mesh)
            self.param_specs = self._build_param_specs()
            return
        self.pp_size = 1
        if SEQ_AXIS not in mesh.axis_names:
            if sp > 1:
                # an explicit dp-only mesh must not silently discard the
                # requested sequence parallelism (dense attention at long
                # seq_len would OOM where the user asked for ring)
                raise ValueError(
                    f"config sp={sp} but the given mesh has no "
                    f"'{SEQ_AXIS}' axis ({mesh.axis_names}); build it with "
                    f"{type(self).__name__}.build_mesh(...)"
                )
        elif sp > 1 and int(mesh.shape[SEQ_AXIS]) != sp:
            raise ValueError(
                f"config sp={sp} != mesh {SEQ_AXIS} size {mesh.shape[SEQ_AXIS]}"
            )
        if tp > 1 and TP_AXIS not in mesh.axis_names:
            raise ValueError(
                f"config tp={tp} but the given mesh has no '{TP_AXIS}' axis "
                f"({mesh.axis_names}); build it with "
                f"{type(self).__name__}.build_mesh(...)"
            )
        if TP_AXIS in mesh.axis_names and tp > 1 and int(mesh.shape[TP_AXIS]) != tp:
            raise ValueError(
                f"config tp={tp} != mesh {TP_AXIS} size {mesh.shape[TP_AXIS]}"
            )
        self.tp_size = int(mesh.shape[TP_AXIS]) if TP_AXIS in mesh.axis_names else 1
        if SEQ_AXIS in mesh.axis_names:
            self.sp_size = int(mesh.shape[SEQ_AXIS])
            # tokens: (batch over dp, sequence over sp, replicated over
            # tp); grads contribute from every (dp, sp) shard, so the
            # exchange reduces over both
            self.batch_spec = P(DATA_AXIS, SEQ_AXIS)
            self.exchange_axes = (DATA_AXIS, SEQ_AXIS)
        else:
            self.sp_size = 1
        if self.tp_size > 1:
            # replicated leaves carry identical full gradients across tp
            # (the Megatron f/g pair completes cotangents in-block), so tp
            # joins the mean axes harmlessly; tp-SHARDED leaves skip it
            # via param_specs in the per-leaf exchange
            ex = self.exchange_axes
            self.exchange_axes = (
                ex + (TP_AXIS,) if isinstance(ex, tuple) else (ex, TP_AXIS)
            )
        super().__init__(cfg, mesh=mesh)  # cfg = defaults + config + overrides
        moe_sharded = (
            int(self.config.moe_experts) > 0
            and int(self.mesh.shape[DATA_AXIS]) > 1
        )
        if self.tp_size > 1 or moe_sharded:
            self.param_specs = self._build_param_specs()

    def build_data(self):
        cfg = self.config
        if int(cfg.seq_len) % self.sp_size:
            raise ValueError(
                f"seq_len {cfg.seq_len} not divisible by sp={self.sp_size}"
            )
        self.data = LMTextData(
            batch_size=self.global_batch,
            seq_len=int(cfg.seq_len),
            vocab_size=int(cfg.vocab_size),
            data_dir=cfg.data_dir,
            n_synth_train=int(cfg.n_synth_train),
            n_synth_val=int(cfg.n_synth_val),
            seed=int(cfg.seed),
        )

    def build_model(self) -> None:
        if bool(self.config.init_weights):
            return super().build_model()
        from theanompi_tpu.ops.layers import count_params

        self.net, self.input_shape = self.build_net()
        self.params, self.net_state, self.out_shape = jax.eval_shape(
            lambda k: self.net.init(k, self.input_shape), self.rng)
        self.net_state = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), self.net_state)
        self.optimizer, self._zero, self.opt_state = None, None, None
        self.n_params = count_params(self.params)

    def _build_latent_net(self):
        """The ``block='latent_moe'`` stack (``ops.latent_block``)."""
        from theanompi_tpu.ops import latent_block as LB
        from theanompi_tpu.ops.layers import normal_init
        from theanompi_tpu.parallel.moe import MoeMlp

        cfg = self.config
        if self.sp_size > 1 or self.tp_size > 1 or self.pp_size > 1:
            raise ValueError("block='latent_moe' runs unsharded (sp=tp=pp=1)")
        dt = jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None
        pdt = jnp.dtype(cfg.param_dtype)
        d, n = int(cfg.d_model), int(cfg.hc_mult)
        q_rank = None if cfg.q_lora_rank is None else int(cfg.q_lora_rank)
        attn = LB.LatentAttention(
            d, int(cfg.n_heads), q_rank, int(cfg.kv_lora_rank),
            int(cfg.qk_nope_head_dim), int(cfg.qk_rope_head_dim),
            int(cfg.v_head_dim), float(cfg.rms_norm_eps), dict(cfg.rope or {}),
            rotary=not bool(cfg.mla_nope))
        kda_layers = {int(i) for i in (cfg.kda_layers or ())}
        kda = None
        if kda_layers:
            from theanompi_tpu.ops.kda import KdaMixer

            kda = KdaMixer(d, int(cfg.n_heads), int(cfg.kda_head_dim),
                           int(cfg.kda_conv), float(cfg.rms_norm_eps))
        held = cfg.moe_experts_held
        held = None if held is None else (0, int(held))

        def make_block(i):
            moe = None
            if i >= int(cfg.first_k_dense) and int(cfg.moe_experts):
                moe = MoeMlp(
                    int(cfg.moe_experts), int(cfg.moe_hidden),
                    top_k=int(cfg.moe_top_k), ep_axis=None, compute_dtype=dt,
                    emit_aux=False, scoring="sigmoid",
                    route_scale=float(cfg.route_scale), gated=True,
                    n_shared=int(cfg.n_shared_experts), experts_held=held,
                    param_dtype=pdt, w_init=normal_init(0.02))
            return LB.LatentMoeBlock(
                attn, ffn_hidden=int(cfg.ffn_hidden), moe=moe, n_streams=n,
                hc_iters=int(cfg.hc_sinkhorn_iters), hc_eps=float(cfg.hc_eps),
                hc_clamp=float(cfg.hc_clamp), param_dtype=pdt,
                kda=kda if i + 1 in kda_layers else None)

        net = L.Sequential([
            LB.StreamEmbedding(int(cfg.vocab_size), d, n, compute_dtype=dt,
                               param_dtype=pdt),
            *[make_block(i) for i in range(int(cfg.n_layers))],
            LB.StreamSumNorm(n, float(cfg.rms_norm_eps), param_dtype=pdt),
            L.Dense(int(cfg.vocab_size), use_bias=False,
                    w_init=normal_init(0.02), compute_dtype=dt,
                    output_dtype=jnp.float32),
        ])
        self.lr_schedule = optim.step_decay(
            float(cfg.lr), list(cfg.lr_boundaries), 0.1)
        return net, (int(cfg.seq_len),)

    def build_net(self):
        cfg = self.config
        if str(cfg.block) == "latent_moe":
            return self._build_latent_net()
        if str(cfg.block) != "dense":
            raise ValueError(
                f"block must be 'dense' or 'latent_moe', got {cfg.block!r}")
        dt = jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None
        sp_axis = SEQ_AXIS if self.sp_size > 1 else None
        tp_axis = TP_AXIS if self.tp_size > 1 else None
        t_local = int(cfg.seq_len) // self.sp_size
        d = int(cfg.d_model)
        n_heads = int(cfg.n_heads)
        if self.tp_size > 1 and str(cfg.sp_mode) == "alltoall":
            if (n_heads // self.tp_size) % self.sp_size:
                raise ValueError(
                    f"alltoall SP over tp-local heads needs "
                    f"(n_heads/tp) % sp == 0, got n_heads={n_heads}, "
                    f"tp={self.tp_size}, sp={self.sp_size}"
                )
        n_experts = int(cfg.moe_experts)
        dp = int(self.mesh.shape[DATA_AXIS])
        if n_experts and n_experts % max(dp, 1):
            raise ValueError(
                f"moe_experts={n_experts} must divide by the dp axis "
                f"size {dp} (experts shard over dp, GShard-style)"
            )

        def make_moe():
            if not n_experts:
                return None
            from theanompi_tpu.parallel.moe import MoeMlp

            return MoeMlp(
                n_experts,
                int(cfg.moe_hidden or d * int(cfg.mlp_ratio)),
                top_k=int(cfg.moe_top_k),
                ep_axis=DATA_AXIS if dp > 1 else None,
                ep_size=dp,
                compute_dtype=dt,
                tp_axis=tp_axis,  # 2-D expert sharding when tp > 1
                tp_size=self.tp_size,
                # inside the GPipe scan the layer must be stateless —
                # __init__ enforces moe_aux_coef=0 for pp
                emit_aux=self.pp_size == 1,
            )

        wrap = L.Remat if bool(cfg.remat) else (lambda b: b)

        def make_block():
            return wrap(A.TransformerBlock(
                n_heads,
                mlp_ratio=int(cfg.mlp_ratio),
                causal=True,
                sp_axis=sp_axis,
                sp_size=self.sp_size,
                sp_mode=str(cfg.sp_mode),
                tp_axis=tp_axis,
                tp_size=self.tp_size,
                compute_dtype=dt,
                moe=make_moe(),
                attn_impl=str(cfg.attn_impl),
            ))

        if self.pp_size > 1:
            # GPipe over the block stack: n_layers/pp blocks per stage,
            # stage weights sharded over pp, embeddings and the head
            # replicated on every stage device (parallel.pipeline)
            from theanompi_tpu.parallel.pipeline import PipelineStages

            per_stage = int(cfg.n_layers) // self.pp_size
            body = [PipelineStages(
                lambda _i: L.Sequential([make_block() for _ in range(per_stage)]),
                n_stages=self.pp_size,
                n_micro=int(cfg.pp_micro),
            )]
        else:
            body = [make_block() for _ in range(int(cfg.n_layers))]
            if str(cfg.get("exchange_overlap", "")) == "indag":
                # in-DAG exchange issue points: every transformer block
                # is one grad-sync group whose backward reduces the
                # block's gradients the moment they are complete
                # (parallel.bucketing; delegating wrapper — the params
                # tree structure is unchanged)
                from theanompi_tpu.parallel.bucketing import GradSyncGroup

                body = [
                    GradSyncGroup(b, gid=i, name=f"block{i}")
                    for i, b in enumerate(body)
                ]
        net = L.Sequential(
            [
                A.Embedding(int(cfg.vocab_size), d, compute_dtype=dt),
                A.PositionalEmbedding(int(cfg.seq_len), sp_axis=sp_axis),
                *body,
                A.LayerNorm(),
                L.Dense(int(cfg.vocab_size), compute_dtype=dt, output_dtype=jnp.float32),
            ]
        )
        self.lr_schedule = optim.step_decay(
            float(cfg.lr), list(cfg.lr_boundaries), 0.1
        )
        return net, (t_local,)

    def _build_param_specs(self):
        """PartitionSpec tree mirroring ``self.params`` (a Sequential's
        per-layer list): Megatron column/row sharding for every dense
        TransformerBlock (tp), expert-dim sharding over dp for MoE
        blocks (GShard-style ep≡dp), everything else replicated."""
        from theanompi_tpu.parallel.pipeline import PipelineStages
        from theanompi_tpu.runtime.mesh import PP_AXIS

        col = P(None, TP_AXIS)  # output-dim sharded: wq/wk/wv, mlp_in.w
        row = P(TP_AXIS, None)  # input-dim sharded: wo, mlp_out.w
        rep = P()
        tp_on = self.tp_size > 1
        dp = int(self.mesh.shape[DATA_AXIS])

        def block_spec(layer, layer_params):
            block = {
                "ln1": jax.tree.map(lambda _: rep, layer_params["ln1"]),
                "attn": (
                    {"wq": col, "wk": col, "wv": col, "wo": row}
                    if tp_on
                    else jax.tree.map(lambda _: rep, layer_params["attn"])
                ),
                "ln2": jax.tree.map(lambda _: rep, layer_params["ln2"]),
            }
            if layer.moe is not None:
                from theanompi_tpu.parallel.moe import MoeMlp

                block["moe"] = MoeMlp.param_specs(
                    DATA_AXIS if dp > 1 else None,
                    TP_AXIS if tp_on else None,
                )
            elif tp_on:
                block["mlp_in"] = {"w": col, "b": P(TP_AXIS)}
                block["mlp_out"] = {"w": row, "b": rep}
            else:
                block["mlp_in"] = jax.tree.map(
                    lambda _: rep, layer_params["mlp_in"]
                )
                block["mlp_out"] = jax.tree.map(
                    lambda _: rep, layer_params["mlp_out"]
                )
            return block

        def unwrap(layer):
            return layer.inner if isinstance(layer, L.Remat) else layer

        specs = []
        for layer, layer_params in zip(self.net.layers, self.params):
            layer = unwrap(layer)
            if isinstance(layer, PipelineStages):
                # stage-stacked leaves: leading (stage) dim shards over
                # pp, the block's own Megatron dims (if tp) shift right
                # by one — every stacked leaf skips pp in the exchange;
                # only the Megatron-split ones also skip tp (stacked
                # LN/bias leaves still reduce over tp, required: their
                # tp-rank grads are identical copies)
                template = layer.stages[0]  # Sequential of blocks
                stage = []
                for blk, blk_params in zip(template.layers, layer_params):
                    bs = block_spec(unwrap(blk), blk_params)
                    stage.append(jax.tree.map(
                        lambda s: P(PP_AXIS, *s),
                        bs,
                        is_leaf=lambda x: isinstance(x, P),
                    ))
                specs.append(stage)
                continue
            if not isinstance(layer, A.TransformerBlock):
                specs.append(jax.tree.map(lambda _: rep, layer_params))
                continue
            specs.append(block_spec(layer, layer_params))
        return specs

    def loss_and_metrics(self, params, net_state, x, y, train: bool, rng):
        # x, y: int32 (B, T_local) token shards; flatten tokens so the
        # shared classification losses apply per-token
        logits, new_state = self.net.apply(params, net_state, x, train=train, rng=rng)
        v = logits.shape[-1]
        flat_logits = logits.reshape(-1, v)
        flat_y = y.reshape(-1)
        loss = losses.softmax_cross_entropy(flat_logits, flat_y)
        if int(self.config.moe_experts):
            # Switch load-balance aux: MoE blocks emit it through the
            # state tree (differentiable — same apply call)
            from theanompi_tpu.parallel.moe import MoeMlp

            loss = MoeMlp.add_aux_loss(
                loss, new_state, self.config.moe_aux_coef, train
            )
        err, err5 = self._metrics(flat_logits, flat_y)
        return loss, (err, err5, new_state)


def make_draft(model: TransformerLM, n_layers: int = 1) -> TransformerLM:
    """Zoo entry: the **truncated self-draft** for speculative decoding.

    Builds a ``TransformerLM`` on the target's own mesh with the same
    embedding / positional / final-LN / head weights and the target's
    FIRST ``n_layers`` transformer blocks — a zero-training draft whose
    per-token cost is ~``n_layers / L`` of the target's and whose
    greedy proposals track the target wherever the late blocks refine
    rather than overturn the early residual stream.  The train→serve
    loader applies unchanged (the draft IS a TransformerLM with its own
    params), so a distilled draft checkpoint drops in by loading
    different params into the same shape.

    Serving-side composition: hand the result to
    ``PagedServingEngine(draft, ...)`` and pass that engine as the
    scheduler's ``draft_engine`` (``serving/spec.py``).
    """
    L = int(model.config.n_layers)
    n_layers = int(n_layers)
    if not 1 <= n_layers <= L:
        raise ValueError(
            f"draft n_layers must be in [1, {L}], got {n_layers}"
        )
    cfg = {k: model.config[k] for k in model.config}
    cfg["n_layers"] = n_layers
    draft = TransformerLM(config=cfg, mesh=model.mesh)
    p = list(model.params)
    # Sequential params layout: [embedding, positions, block_0..block_{L-1},
    # final_ln, head] — the same split serving/dense.py _weights makes
    draft.params = p[:2] + p[2:2 + n_layers] + p[2 + L:]
    return draft
