"""AlexNet — the ImageNet benchmark model.

Reference analog: ``AlexNet`` in ``theanompi/models/alex_net.py``
(SURVEY.md §3.5), the model behind the paper's headline BSP scaling
numbers, run at 128px ("AlexNet ImageNet-128px" in BASELINE.json).
Single-tower (the reference dropped the original's 2-GPU grouping), with
the classic LRN + overlapping-pool arrangement.
"""

from __future__ import annotations

import jax.numpy as jnp

from theanompi_tpu.data.providers import ImageNetData
from theanompi_tpu.models.base import TpuModel, stem_is_s2d
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import optim


class AlexNet(TpuModel):
    default_config = dict(
        batch_size=128,
        n_epochs=60,
        lr=0.01,
        momentum=0.9,
        weight_decay=5e-4,
        dropout_rate=0.5,
        lr_boundaries=(20, 40, 50),
        image_size=128,
        crop_size=None,  # e.g. 112 for crop aug; None trains full-size
        mirror=True,
        n_classes=1000,
        data_dir=None,
        n_synth_batches=64,
        lrn_impl="auto",  # see ops.layers.LRN: auto|xla|shift|window|pallas
        lrn_remat=False,  # recompute LRN internals in bwd (saves HBM)
        lrn_stats=None,  # 'bf16' narrows the LRN window-sum/residual
        # dtype (halves the saved-denominator HBM round-trip; see LRN)
        pool_grad="native",  # 'mask' = fused maxpool bwd (no
        # select-and-scatter; see ops.layers.MaxPool)
        stem="conv",  # 's2d' folds conv1's stride into channels
        # (space-to-depth: 3ch stride-4 11x11 -> 48ch stride-1 3x3)
    )

    def build_data(self):
        cfg = self.config
        self.data = ImageNetData(
            batch_size=self.global_batch,
            data_dir=cfg.data_dir,
            image_size=int(cfg.image_size),
            n_classes=int(cfg.n_classes),
            n_synth_batches=int(cfg.n_synth_batches),
            n_synth_val_batches=int(cfg.get("n_synth_val_batches", 4)),
            seed=int(cfg.seed),
            crop_size=cfg.crop_size,
            mirror=bool(cfg.mirror),
            # device_aug: the jitted step augments; host ships raw images
            train_aug=not bool(cfg.get("device_aug", False)),
            mean_subtract=bool(cfg.get("mean_subtract", True)),
        )

    def build_net(self):
        cfg = self.config
        dt = jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None
        drop = float(cfg.dropout_rate)
        if cfg.lrn_stats not in (None, "f32", "float32", "bf16", "bfloat16"):
            raise ValueError(f"lrn_stats must be None|f32|bf16, got {cfg.lrn_stats!r}")
        lrn = dict(
            impl=str(cfg.lrn_impl),
            remat=bool(cfg.lrn_remat),
            stats_dtype=(
                jnp.bfloat16 if cfg.lrn_stats in ("bf16", "bfloat16") else None
            ),
        )
        pg = str(cfg.pool_grad)
        s2d_stem = stem_is_s2d(cfg)
        net = L.Sequential(
            [
                L.Conv2d(96, 11, stride=4, padding="SAME", compute_dtype=dt,
                         s2d=s2d_stem).named("conv1"),
                L.Relu().named("relu1"),
                L.LRN(**lrn).named("lrn1"),
                L.MaxPool(3, stride=2, grad_impl=pg).named("pool1"),
                L.Conv2d(256, 5, padding="SAME", compute_dtype=dt).named("conv2"),
                L.Relu().named("relu2"),
                L.LRN(**lrn).named("lrn2"),
                L.MaxPool(3, stride=2, grad_impl=pg).named("pool2"),
                L.Conv2d(384, 3, padding="SAME", compute_dtype=dt).named("conv3"),
                L.Relu().named("relu3"),
                L.Conv2d(384, 3, padding="SAME", compute_dtype=dt).named("conv4"),
                L.Relu().named("relu4"),
                L.Conv2d(256, 3, padding="SAME", compute_dtype=dt).named("conv5"),
                L.Relu().named("relu5"),
                L.MaxPool(3, stride=2, grad_impl=pg).named("pool5"),
                L.Flatten().named("flatten"),
                L.Dense(4096, compute_dtype=dt).named("fc6"),
                L.Relu().named("relu6"),
                L.Dropout(drop).named("drop6"),
                L.Dense(4096, compute_dtype=dt).named("fc7"),
                L.Relu().named("relu7"),
                L.Dropout(drop).named("drop7"),
                L.Dense(int(cfg.n_classes), compute_dtype=dt,
                        output_dtype=jnp.float32).named("fc8"),
            ]
        )
        self.lr_schedule = optim.step_decay(
            float(cfg.lr), list(cfg.lr_boundaries), 0.1
        )
        size = int(cfg.crop_size or cfg.image_size)
        return net, (size, size, 3)
