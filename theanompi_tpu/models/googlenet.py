"""GoogLeNet (Inception v1).

Reference analog: ``GoogLeNet`` in ``theanompi/models/googlenet.py``
(SURVEY.md §3.5, ~1000 LoC of hand-built Theano inception blocks).  Here
each inception block is one ``Parallel`` combinator over four branches,
and the two reference-era **auxiliary classifiers** (tapped off
inception 4a and 4d, loss-weighted 0.3, train-only) hang off an
``AuxTapped`` trunk — inference never pays for them.  Set
``aux_heads=False`` to drop them (modern init converges without them,
but the default matches the reference architecture).
"""

from __future__ import annotations

import jax.numpy as jnp

from theanompi_tpu.data.providers import ImageNetData
from theanompi_tpu.models.base import TpuModel, stem_is_s2d
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import losses
from theanompi_tpu.ops import optim


def _conv(filters, kernel, dt, stride=1, s2d=False):
    return L.Sequential(
        [
            L.Conv2d(filters, kernel, stride=stride, padding="SAME",
                     compute_dtype=dt, s2d=s2d),
            L.Relu(),
        ]
    )


def _inception(c1, c3r, c3, c5r, c5, pp, dt):
    return L.Parallel(
        [
            _conv(c1, 1, dt),
            L.Sequential([_conv(c3r, 1, dt), _conv(c3, 3, dt)]),
            L.Sequential([_conv(c5r, 1, dt), _conv(c5, 5, dt)]),
            L.Sequential([L.MaxPool(3, stride=1, padding="SAME"), _conv(pp, 1, dt)]),
        ]
    )


def _aux_head(n_classes, dt):
    """Szegedy-2014 auxiliary classifier: avgpool 5/3 → 1×1×128 conv →
    FC-1024 → dropout 0.7 → FC-n_classes. SAME pooling so the head also
    wires up at the small image sizes the smoke tests use."""
    return L.Sequential(
        [
            L.AvgPool(5, stride=3, padding="SAME"),
            _conv(128, 1, dt),
            L.Flatten(),
            L.Dense(1024, compute_dtype=dt),
            L.Relu(),
            L.Dropout(0.7),
            L.Dense(n_classes, compute_dtype=dt, output_dtype=jnp.float32),
        ]
    )


class GoogLeNet(TpuModel):
    default_config = dict(
        batch_size=64,
        n_epochs=60,
        lr=0.01,
        momentum=0.9,
        weight_decay=2e-4,
        dropout_rate=0.4,
        lr_boundaries=(30, 50),
        image_size=224,
        n_classes=1000,
        data_dir=None,
        n_synth_batches=32,
        exch_strategy="int8_sr",  # BASELINE.json config #3 names "the
        # compressed exchanger path"; the default tier is now the SR
        # int8 wire (exchanger.DEFAULT_COMPRESSED_STRATEGY — see the
        # zero1 convergence evidence), 2x fewer bytes than the bf16 cast
        aux_heads=True,  # reference-parity train-only aux classifiers
        aux_weight=0.3,  # classic 0.3 weighting of each aux loss
        stem="conv",  # 's2d': space-to-depth 7x7/2 stem (ops.layers.Conv2d)
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
        nc = int(cfg.n_classes)
        s2d_stem = stem_is_s2d(cfg)
        stem_to_4a = L.Sequential(
            [
                _conv(64, 7, dt, stride=2, s2d=s2d_stem),
                L.MaxPool(3, stride=2, padding="SAME"),
                L.LRN(),
                _conv(64, 1, dt),
                _conv(192, 3, dt),
                L.LRN(),
                L.MaxPool(3, stride=2, padding="SAME"),
                _inception(64, 96, 128, 16, 32, 32, dt),  # 3a -> 256
                _inception(128, 128, 192, 32, 96, 64, dt),  # 3b -> 480
                L.MaxPool(3, stride=2, padding="SAME"),
                _inception(192, 96, 208, 16, 48, 64, dt),  # 4a -> 512
            ]
        )
        mid_to_4d = L.Sequential(
            [
                _inception(160, 112, 224, 24, 64, 64, dt),  # 4b
                _inception(128, 128, 256, 24, 64, 64, dt),  # 4c
                _inception(112, 144, 288, 32, 64, 64, dt),  # 4d -> 528
            ]
        )
        tail = L.Sequential(
            [
                _inception(256, 160, 320, 32, 128, 128, dt),  # 4e -> 832
                L.MaxPool(3, stride=2, padding="SAME"),
                _inception(256, 160, 320, 32, 128, 128, dt),  # 5a
                _inception(384, 192, 384, 48, 128, 128, dt),  # 5b -> 1024
                L.GlobalAvgPool(),
                L.Dropout(float(cfg.dropout_rate)),
                L.Dense(nc, compute_dtype=dt, output_dtype=jnp.float32),
            ]
        )
        if bool(cfg.aux_heads):
            net = L.AuxTapped(
                [stem_to_4a, mid_to_4d, tail],
                [_aux_head(nc, dt), _aux_head(nc, dt), None],
            )
        else:
            net = L.Sequential([stem_to_4a, mid_to_4d, tail])
        self.lr_schedule = optim.step_decay(
            float(cfg.lr), list(cfg.lr_boundaries), 0.1
        )
        size = int(cfg.image_size)
        return net, (size, size, 3)

    def loss_and_metrics(self, params, net_state, x, y, train: bool, rng):
        if not (train and bool(self.config.aux_heads)):
            return super().loss_and_metrics(params, net_state, x, y, train, rng)
        (logits, aux_logits), new_state = self.net.apply(
            params, net_state, self._cast_input(x), train=True, rng=rng
        )
        loss = losses.softmax_cross_entropy(logits, y)
        w = float(self.config.aux_weight)
        for al in aux_logits:
            loss = loss + w * losses.softmax_cross_entropy(al, y)
        err, err5 = self._metrics(logits, y)
        return loss, (err, err5, new_state)
