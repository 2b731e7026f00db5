"""VGG-16.

Reference analog: ``VGG16`` (upstream ``theanompi/models/vgg16.py`` /
lasagne zoo vgg; SURVEY.md §3.5) — BASELINE.json config #3 pairs it with
GoogLeNet under the compressed-exchanger path (its 138M params make
exchange bytes the bottleneck, which is exactly what bf16 wire halves).
"""

from __future__ import annotations

import jax.numpy as jnp

from theanompi_tpu.data.providers import ImageNetData
from theanompi_tpu.models.base import TpuModel
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import optim


def _block(n_convs, filters, dt):
    seq = []
    for _ in range(n_convs):
        seq += [L.Conv2d(filters, 3, padding="SAME", compute_dtype=dt), L.Relu()]
    seq.append(L.MaxPool(2))
    return seq


class VGG16(TpuModel):
    default_config = dict(
        batch_size=32,
        n_epochs=60,
        lr=0.01,
        momentum=0.9,
        weight_decay=5e-4,
        dropout_rate=0.5,
        lr_boundaries=(25, 45),
        image_size=224,
        n_classes=1000,
        data_dir=None,
        n_synth_batches=32,
        exch_strategy="int8_sr",  # config #3: compressed exchanger path
        # (default tier = exchanger.DEFAULT_COMPRESSED_STRATEGY)
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
        drop = float(cfg.dropout_rate)
        net = L.Sequential(
            [
                *_block(2, 64, dt),
                *_block(2, 128, dt),
                *_block(3, 256, dt),
                *_block(3, 512, dt),
                *_block(3, 512, dt),
                L.Flatten(),
                L.Dense(4096, compute_dtype=dt),
                L.Relu(),
                L.Dropout(drop),
                L.Dense(4096, compute_dtype=dt),
                L.Relu(),
                L.Dropout(drop),
                L.Dense(int(cfg.n_classes), compute_dtype=dt, output_dtype=jnp.float32),
            ]
        )
        self.lr_schedule = optim.step_decay(
            float(cfg.lr), list(cfg.lr_boundaries), 0.1
        )
        size = int(cfg.image_size)
        return net, (size, size, 3)
