"""ResNet-50.

Reference analog: ``ResNet50`` in
``theanompi/models/lasagne_model_zoo/resnet50.py`` (SURVEY.md §3.5) —
BASELINE.json config #4 runs it under EASGD.  Standard bottleneck
architecture (stages 3-4-6-3), BatchNorm with per-shard statistics by
default (the reference-era data-parallel BN behavior); pass
``sync_bn=True`` for cross-replica stats.
"""

from __future__ import annotations

import jax.numpy as jnp

from theanompi_tpu.data.providers import ImageNetData
from theanompi_tpu.models.base import TpuModel, stem_is_s2d
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import optim
from theanompi_tpu.runtime.mesh import DATA_AXIS


def _bottleneck(cin, cmid, cout, stride, bn_axis, dt):
    body = L.Sequential(
        [
            L.Conv2d(cmid, 1, use_bias=False, compute_dtype=dt),
            L.BatchNorm(axis_name=bn_axis),
            L.Relu(),
            L.Conv2d(cmid, 3, stride=stride, padding="SAME", use_bias=False, compute_dtype=dt),
            L.BatchNorm(axis_name=bn_axis),
            L.Relu(),
            L.Conv2d(cout, 1, use_bias=False, compute_dtype=dt),
            L.BatchNorm(axis_name=bn_axis, scale_init=0.0),
        ]
    )
    if stride != 1 or cin != cout:
        shortcut = L.Sequential(
            [
                L.Conv2d(cout, 1, stride=stride, use_bias=False, compute_dtype=dt),
                L.BatchNorm(axis_name=bn_axis),
            ]
        )
    else:
        shortcut = None
    return L.Sequential([L.Residual(body, shortcut), L.Relu()])


class ResNet50(TpuModel):
    default_config = dict(
        batch_size=64,
        n_epochs=90,
        lr=0.1,
        momentum=0.9,
        weight_decay=1e-4,
        lr_boundaries=(30, 60, 80),
        image_size=224,
        n_classes=1000,
        data_dir=None,
        n_synth_batches=32,
        sync_bn=False,
        stem="conv",  # 's2d' folds the 7x7/2 stem's stride into
        # channels (space-to-depth; see ops.layers.Conv2d)
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
            mean_subtract=bool(cfg.get("mean_subtract", True)),
        )

    def build_net(self):
        cfg = self.config
        dt = jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None
        bn_axis = DATA_AXIS if cfg.sync_bn else None
        s2d_stem = stem_is_s2d(cfg)
        stages = [  # (n_blocks, cmid, cout, first_stride)
            (3, 64, 256, 1),
            (4, 128, 512, 2),
            (6, 256, 1024, 2),
            (3, 512, 2048, 2),
        ]
        seq = [
            L.Conv2d(64, 7, stride=2, padding="SAME", use_bias=False,
                     compute_dtype=dt, s2d=s2d_stem),
            L.BatchNorm(axis_name=bn_axis),
            L.Relu(),
            L.MaxPool(3, stride=2, padding="SAME"),
        ]
        indag = str(cfg.get("exchange_overlap", "")) == "indag"
        cin = 64
        for si, (n_blocks, cmid, cout, stride) in enumerate(stages):
            blocks = []
            for b in range(n_blocks):
                blocks.append(
                    _bottleneck(cin, cmid, cout, stride if b == 0 else 1, bn_axis, dt)
                )
                cin = cout
            if indag:
                # in-DAG exchange issue points: each residual stage is
                # one grad-sync group — its backward reduces the
                # stage's gradients while earlier stages still
                # differentiate (parallel.bucketing). NOTE: grouping
                # nests the stage's blocks one list level deeper, so
                # indag checkpoints are mode-specific.
                from theanompi_tpu.parallel.bucketing import GradSyncGroup

                seq.append(
                    GradSyncGroup(
                        L.Sequential(blocks), gid=si, name=f"stage{si + 1}"
                    )
                )
            else:
                seq.extend(blocks)
        seq += [L.GlobalAvgPool(), L.Dense(int(cfg.n_classes), compute_dtype=dt, output_dtype=jnp.float32)]
        self.lr_schedule = optim.step_decay(
            float(cfg.lr), list(cfg.lr_boundaries), 0.1
        )
        size = int(cfg.image_size)
        return L.Sequential(seq), (size, size, 3)
