"""Expert-parallel MoE classifier.

No reference analog (Theano-MPI is data-parallel only; SURVEY.md §3.4)
— demonstrator for the beyond-reference ``ep`` mesh axis: tokens shard
over (dp, ep), expert FFN weights shard over ``ep``, and every device computes its
own experts' part for everybody's tokens between an all-gather and a
reduce-scatter (``parallel.moe.MoeMlp``: no capacity, nothing dropped).
Gradients reduce over (dp, ep) with expert-sharded leaves skipping
``ep`` via ``param_specs`` — the same per-leaf mechanism as tensor and
pipeline parallelism.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from theanompi_tpu.data.providers import Cifar10Data
from theanompi_tpu.models.base import TpuModel
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import optim
from theanompi_tpu.parallel.moe import MoeMlp
from theanompi_tpu.runtime.mesh import DATA_AXIS, EP_AXIS, make_dp_axis_mesh


class MoeMlpModel(TpuModel):
    default_config = dict(
        batch_size=32,  # per (dp, ep) shard
        d_model=128,
        d_hidden=256,
        n_experts=8,
        top_k=1,
        moe_aux_coef=0.01,  # weight of the Switch load-balance aux loss
        ep=2,  # expert-parallel degree = mesh ep-axis size
        n_classes=10,
        lr=0.05,
        momentum=0.9,
        weight_decay=0.0,
        n_epochs=5,
        data_dir=None,
        n_synth_train=2048,
        n_synth_val=256,
    )

    batch_axes = (DATA_AXIS, EP_AXIS)

    @classmethod
    def build_mesh(cls, devices=None, config=None):
        cfg = dict(cls.default_config)
        cfg.update(dict(config or {}))
        return make_dp_axis_mesh(EP_AXIS, int(cfg.get("ep", 1)), devices)

    def __init__(self, config=None, mesh=None, **overrides):
        cfg = dict(self.default_config)
        cfg.update(dict(config or {}))
        cfg.update(overrides)
        ep = int(cfg.get("ep", 1))
        if mesh is None:
            mesh = self.build_mesh(config=cfg)
        if ep > 1:
            self._require_mesh_axis(mesh, EP_AXIS, ep)
        self.ep_size = ep
        if ep > 1:
            # tokens shard over both axes; replicated leaves (gate, dense
            # head) carry per-shard grads that mean over (dp, ep); expert
            # leaves skip ep via param_specs
            self.batch_spec = P((DATA_AXIS, EP_AXIS))
            self.exchange_axes = (DATA_AXIS, EP_AXIS)
        super().__init__(cfg, mesh=mesh)
        if ep > 1:
            self.param_specs = self._build_param_specs()

    def build_data(self):
        cfg = self.config
        self.data = Cifar10Data(
            batch_size=self.global_batch,
            data_dir=cfg.data_dir,
            n_synth_train=int(cfg.n_synth_train),
            n_synth_val=int(cfg.n_synth_val),
            seed=int(cfg.seed),
        )

    def build_net(self):
        cfg = self.config
        d = int(cfg.d_model)
        self.moe = MoeMlp(
            n_experts=int(cfg.n_experts),
            d_hidden=int(cfg.d_hidden),
            top_k=int(cfg.top_k),
            ep_axis=EP_AXIS if self.ep_size > 1 else None,
            ep_size=self.ep_size,
            compute_dtype=(
                jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None
            ),
        )
        net = L.Sequential(
            [
                L.Flatten(),
                L.Dense(d),
                L.Relu(),
                L.Residual(self.moe),
                L.Dense(int(cfg.n_classes)),
            ]
        )
        self.lr_schedule = optim.constant(float(cfg.lr))
        return net, Cifar10Data.shape

    def loss_and_metrics(self, params, net_state, x, y, train: bool, rng):
        loss, (err, err5, new_state) = super().loss_and_metrics(
            params, net_state, x, y, train, rng
        )
        loss = MoeMlp.add_aux_loss(
            loss, new_state, self.config.moe_aux_coef, train
        )
        return loss, (err, err5, new_state)

    def _build_param_specs(self):
        expert = MoeMlp.param_specs(EP_AXIS)
        specs = []
        for layer, layer_params in zip(self.net.layers, self.params):
            if isinstance(layer, L.Residual):
                specs.append({"body": expert, "shortcut": {}})
            else:
                specs.append(jax.tree.map(lambda _: P(), layer_params))
        return specs
