"""LS-GAN — least-squares GAN on CIFAR-sized images.

Reference analog: ``LSGAN`` in
``theanompi/models/lasagne_model_zoo/lsgan.py`` (SURVEY.md §3.5) —
BASELINE.json config #5 pairs it with GOSGD gossip exchange.

This model exercises the parts of the contract a classifier doesn't: two
parameter pytrees (G, D), two optimizers, and a custom fused train step —
both adversarial updates execute in ONE shard_mapped XLA program per
iteration, with gradient pmean over ``dp`` for each net (Mao et al. 2017
least-squares objectives: D minimizes ½[(D(x)-1)² + D(G(z))²], G
minimizes ½(D(G(z))-1)²).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from theanompi_tpu.data.providers import Cifar10Data
from theanompi_tpu.models.base import TpuModel
from theanompi_tpu.ops import layers as L
from theanompi_tpu.ops import optim as optim_lib
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.runtime.mesh import DATA_AXIS, replicate


def _leaky():
    return L.Activation(lambda x: jax.nn.leaky_relu(x, 0.2))


class LSGAN(TpuModel):
    default_config = dict(
        batch_size=64,
        n_epochs=50,
        lr=2e-4,
        momentum=0.0,  # reference-era GAN SGD; see also adam note below
        weight_decay=0.0,
        latent_dim=100,
        base_width=64,
        data_dir=None,
        n_synth_train=4096,
        n_synth_val=512,
        val_top5=False,
    )

    # -- nets ------------------------------------------------------------
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
        # satisfied via build_model override; not used
        raise NotImplementedError

    def build_model(self):
        cfg = self.config
        dt = jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None
        w = int(cfg.base_width)
        zdim = int(cfg.latent_dim)
        self.latent_dim = zdim
        self.generator = L.Sequential(
            [
                L.Dense(4 * 4 * 4 * w, compute_dtype=dt),
                L.Reshape((4, 4, 4 * w)),
                L.BatchNorm(),
                L.Relu(),
                L.ConvTranspose2d(2 * w, 4, stride=2, compute_dtype=dt),  # 8
                L.BatchNorm(),
                L.Relu(),
                L.ConvTranspose2d(w, 4, stride=2, compute_dtype=dt),  # 16
                L.BatchNorm(),
                L.Relu(),
                L.ConvTranspose2d(3, 4, stride=2, compute_dtype=dt),  # 32
                L.Activation(jnp.tanh),
            ]
        )
        self.discriminator = L.Sequential(
            [
                L.Conv2d(w, 4, stride=2, padding="SAME", compute_dtype=dt),  # 16
                _leaky(),
                L.Conv2d(2 * w, 4, stride=2, padding="SAME", compute_dtype=dt),  # 8
                L.BatchNorm(),
                _leaky(),
                L.Conv2d(4 * w, 4, stride=2, padding="SAME", compute_dtype=dt),  # 4
                L.BatchNorm(),
                _leaky(),
                L.Flatten(),
                L.Dense(1, compute_dtype=dt, output_dtype=jnp.float32),
            ]
        )
        self.rng, gk, dk = jax.random.split(self.rng, 3)
        g_params, g_state, _ = self.generator.init(gk, (zdim,))
        d_params, d_state, _ = self.discriminator.init(dk, Cifar10Data.shape)
        lr = float(cfg.lr)
        self.g_opt = optim_lib.sgd(lr=lr, momentum=float(cfg.momentum))
        self.d_opt = optim_lib.sgd(lr=lr, momentum=float(cfg.momentum))
        self.params = replicate(
            self.mesh, {"g": g_params, "d": d_params}
        )
        self.net_state = replicate(self.mesh, {"g": g_state, "d": d_state})
        self.opt_state = replicate(
            self.mesh,
            {"g": self.g_opt.init(g_params), "d": self.d_opt.init(d_params)},
        )
        self.lr_schedule = optim_lib.constant(lr)
        from theanompi_tpu.ops.layers import count_params

        self.n_params = count_params(self.params)

    # -- fused adversarial step -----------------------------------------
    def compile_train(self, exchanger: Optional[BSP_Exchanger] = None):
        cfg = self.config
        # COMMON_DEFAULTS features the GAN's bespoke two-player step does
        # not implement — reject loudly rather than silently ignore
        unsupported = {
            "zero1": bool(cfg.get("zero1", False)),
            "grad_accum": int(cfg.get("grad_accum", 1) or 1) != 1,
            "device_aug": bool(cfg.get("device_aug", False)),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"LSGAN does not support: {', '.join(bad)}")
        # the GAN rides the bucketed wire like every TpuModel ('indag'
        # needs grad-sync groups the GAN nets don't define — reject in
        # the same loud style as the knobs above)
        overlap = str(cfg.get("exchange_overlap", "bucket"))
        if overlap == "indag":
            raise ValueError("LSGAN does not support: exchange_overlap='indag'")
        exchanger = exchanger or BSP_Exchanger(
            strategy=cfg.exch_strategy,
            mesh=self.mesh,
            bucket_bytes=(
                None
                if overlap == "leaf"
                else int(float(cfg.get("exchange_bucket_mb", 4.0)) * (1 << 20))
            ),
        )
        axis = exchanger.axis
        G, D = self.generator, self.discriminator
        g_opt, d_opt = self.g_opt, self.d_opt
        zdim = self.latent_dim

        def shard_step(params, net_state, opt_state, x, rng):
            rng = jax.random.fold_in(rng, lax.axis_index(axis))
            rz, rg, rd, rex_d, rex_g = jax.random.split(rng, 5)
            z = jax.random.normal(rz, (x.shape[0], zdim))

            def d_loss_fn(d_params):
                fake, g_state = G.apply(
                    params["g"], net_state["g"], z, train=True, rng=rg
                )
                fake = lax.stop_gradient(fake)
                d_real, d_state = D.apply(
                    d_params, net_state["d"], x, train=True, rng=rd
                )
                d_fake, d_state = D.apply(d_params, d_state, fake, train=True, rng=rd)
                loss = 0.5 * (
                    jnp.mean((d_real - 1.0) ** 2) + jnp.mean(d_fake**2)
                )
                return loss, (g_state, d_state)

            (d_loss, (g_state, d_state)), d_grads = jax.value_and_grad(
                d_loss_fn, has_aux=True
            )(params["d"])
            d_grads = exchanger.reduce_grads(d_grads, rng=rex_d)
            new_d, new_d_opt = d_opt.update(params["d"], d_grads, opt_state["d"])

            def g_loss_fn(g_params):
                fake, g_state2 = G.apply(g_params, g_state, z, train=True, rng=rg)
                d_fake, _ = D.apply(new_d, d_state, fake, train=True, rng=rd)
                return 0.5 * jnp.mean((d_fake - 1.0) ** 2), g_state2

            (g_loss, g_state2), g_grads = jax.value_and_grad(
                g_loss_fn, has_aux=True
            )(params["g"])
            g_grads = exchanger.reduce_grads(g_grads, rng=rex_g)
            new_g, new_g_opt = g_opt.update(params["g"], g_grads, opt_state["g"])

            new_params = {"g": new_g, "d": new_d}
            new_state = jax.tree.map(
                lambda s: lax.pmean(s, axis), {"g": g_state2, "d": d_state}
            )
            new_opt = {"g": new_g_opt, "d": new_d_opt}
            return (
                new_params,
                new_state,
                new_opt,
                lax.pmean(d_loss, axis),
                lax.pmean(g_loss, axis),
            )

        mapped = jax.shard_map(
            shard_step,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(DATA_AXIS), P()),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )
        self.train_fn = jax.jit(mapped, donate_argnums=(0, 1, 2))
        self.exchanger = exchanger
        return self.train_fn

    def compile_val(self):
        D = self.discriminator

        def shard_eval(params, net_state, x):
            d_real, _ = D.apply(params["d"], net_state["d"], x, train=False)
            loss = 0.5 * jnp.mean((d_real - 1.0) ** 2)
            return (lax.pmean(loss, DATA_AXIS),)

        mapped = jax.shard_map(
            shard_eval,
            mesh=self.mesh,
            in_specs=(P(), P(), P(DATA_AXIS)),
            out_specs=(P(),),
            check_vma=False,
        )
        self.val_fn = jax.jit(mapped)
        return self.val_fn

    # -- contract -------------------------------------------------------
    def _train_iter(self, count: int, recorder) -> Tuple[float, float]:
        if self.train_fn is None:
            self.compile_train()
        if self._train_it is None:
            self.reset_train_iter(self.current_epoch)
        recorder.start("wait")
        x, _ = next(self._train_it)
        recorder.end("wait")
        recorder.start("calc")
        self.rng, step_key = jax.random.split(self.rng)
        out = self.train_fn(self.params, self.net_state, self.opt_state, x, step_key)
        self.params, self.net_state, self.opt_state = out[0], out[1], out[2]
        d_loss, g_loss = out[3], out[4]
        from theanompi_tpu.models.base import metrics_must_sync

        if self.config.sync_each_iter or metrics_must_sync():
            d_loss, g_loss = float(d_loss), float(g_loss)
        recorder.end("calc")
        # recorder's (cost, error) slots carry (d_loss, g_loss)
        recorder.train_error(count, d_loss, g_loss)
        return d_loss, g_loss

    def val_iter(self, count: int, recorder):
        if self.val_fn is None:
            self.compile_val()
        x, _ = next(self._val_it)
        (loss,) = self.val_fn(self.params, self.net_state, x)
        return float(loss), 0.0, 0.0

    def _val_batch(self, p, s, x, y):
        """The GAN's val signal is the discriminator's real-vs-one loss
        and takes no labels — err/err5 slots report 0. Overriding this
        hook (not run_validation itself) keeps the base method's
        train→val fence and foreign-params semantics in one place; the
        GOSGD driver validates the CONSENSUS model through exactly that
        path after the join (found by the lsgan-gosgd preset E2E test —
        the convergence artifact ran with val_freq=0 and never hit it)."""
        (loss,) = self.val_fn(p, s, x)
        z = jnp.zeros(())
        return loss, z, z

    def adjust_hyperp(self, epoch: int) -> None:
        self.current_epoch = epoch
        lr = self.lr_schedule(epoch) * self._lr_scale
        self.opt_state = {
            "g": optim_lib.set_lr(self.opt_state["g"], lr),
            "d": optim_lib.set_lr(self.opt_state["d"], lr),
        }

    def scale_lr(self, factor: float) -> None:
        self._lr_scale = float(factor)
        self.adjust_hyperp(self.current_epoch)

    def sample(self, n: int = 16):
        """Generate n images (host-side convenience)."""
        self.rng, k = jax.random.split(self.rng)
        z = jax.random.normal(k, (n, self.latent_dim))
        imgs, _ = self.generator.apply(
            jax.tree.map(lambda x: x, self.params["g"]),
            self.net_state["g"],
            z,
            train=False,
        )
        return imgs
