"""Driver: a model trained through the program's ``BSP`` rule.

Set-up builds ONE object — ``BSP().init(devices=chips, ...)``'s model with
its compiled step and state — gives it the weights the configuration's
reference makes from the seed, drives it through its first steps by
``model.train_iter`` (the call ``BSP_Worker.run`` makes, followed by the
worker's ``print_train_info``) on batches made on the device from the
seed, keeps the readings the check needs, warms on, and hands the same
object to the window.  The window makes the same calls for ``seconds``,
with run-ahead bounded so that it ends within a step or two of its time.

End-to-end metric: ``train_images_per_s_per_chip`` — steps completed in
the window x global batch over (time from the window's start to the last
step's completion x chips).
"""

from __future__ import annotations

import collections
import itertools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

CHECK_STEPS = 3


def stated_lr(cfg: dict, n_workers: int) -> float:
    """The rate the configuration states for ``n_workers`` chips (the
    rule scales it linearly), not what the program holds."""
    scale = n_workers if cfg.get("lr_linear_scaling", True) else 1
    return float(cfg["lr"]) * scale


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.t = ctx.traffic
        self.chips = ctx.chips
        self.per_chip = int(self.cfg["batch_size_per_chip"])
        self.global_batch = self.per_chip * self.chips
        self.model = None
        self._facts = {}
        self._prog = None

    # ------------------------------------------------------------------
    def setup(self) -> None:
        marks = {"start": time.perf_counter() - self.ctx.t0}

        def mark(name):
            marks[name] = time.perf_counter() - self.ctx.t0

        import theanompi_tpu

        mark("imported")
        ref, cfg, seed = self.ctx.reference, self.cfg, self.ctx.seed
        model_config = dict(
            cfg["program_config"], batch_size=self.per_chip,
            seed=seed % (2**31 - 1),
            lr_linear_scaling=bool(cfg.get("lr_linear_scaling", True)),
        )
        rule = theanompi_tpu.BSP()
        rule.init(devices=list(self.ctx.devices), model_config=model_config,
                  val_freq=0, **cfg["program"])
        self.rule, model = rule, rule.model
        mark("rule_init")
        self.model, self.rec = model, rule.worker.recorder
        self.rec.verbose = False
        if model.global_batch != self.global_batch:
            raise RuntimeError(
                f"program's global batch {model.global_batch} is not "
                f"{self.chips} x {self.per_chip}"
            )
        mesh = model.mesh
        replicated = NamedSharding(mesh, P())

        # the benchmark's weights, into the program's tree (same leaf
        # order: layers in sequence, "b" before "w")
        leaves = jax.tree.leaves(ref.make_weights(cfg, seed))
        treedef = jax.tree.structure(model.params)
        mine = jax.tree.leaves(model.params)
        if [a.shape for a in leaves] != [a.shape for a in mine]:
            raise RuntimeError(
                "the reference's weights do not fit the program's tree: "
                f"{[a.shape for a in leaves]} against {[a.shape for a in mine]}"
            )
        del mine
        model.params = jax.tree.unflatten(
            treedef, [jax.device_put(a, replicated) for a in leaves]
        )
        start = jax.tree.map(jnp.copy, model.params)  # the step donates
        del leaves

        # what BSP_Worker.run does before its loop, without the
        # validation program and the probes this cell never uses
        if model_config["lr_linear_scaling"] and model.n_workers > 1:
            model.scale_lr(float(model.n_workers))
        model.compile_train()
        model.adjust_hyperp(0)
        self.lr = stated_lr(cfg, model.n_workers)

        # the resident batches, made on the device, sharded as the step
        # takes them; every row differs
        n_res = int(self.t["resident_batches"])
        sharded = NamedSharding(mesh, model.batch_spec)
        make = jax.jit(ref.batches_fn(cfg, self.global_batch, n_res),
                       out_shardings=[(sharded, sharded)] * n_res)
        self.pool = make(ref.data_key(seed))
        model._train_it = itertools.cycle(self.pool)
        jax.block_until_ready(self.pool)
        mark("weights_and_batches")

        wd = float(cfg["weight_decay"])

        @jax.jit
        def first_grads(velocity, p0, lr):
            # momentum SGD from zero velocity: v1 = -lr (g + wd p0)
            return [-v / lr - wd * p
                    for v, p in zip(jax.tree.leaves(velocity),
                                    jax.tree.leaves(p0))]

        @jax.jit
        def norms(leaves):
            return [jnp.sqrt(jnp.sum(jnp.square(a))) for a in leaves]

        @jax.jit
        def change_norms(p, p0):
            return [jnp.sqrt(jnp.sum(jnp.square(a - b)))
                    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]

        self.count = 0
        losses, grads = [], None
        for i in range(CHECK_STEPS):
            losses.append(self._step())
            if i == 0:
                grads = first_grads(model.opt_state["velocity"], start,
                                    jnp.float32(self.lr))
        g_norms = norms(grads)
        c_norms = change_norms(model.params, start)
        self._prog = dict(
            losses=[float(v) for v in losses],
            grad_norms=[float(v) for v in g_norms],
            change_norms=[float(v) for v in c_norms],
            # kept on one device through the window: 4 bytes a parameter
            first_grads=[g.addressable_shards[0].data for g in grads],
        )
        del start
        mark("first_steps")
        # warm on past the worker's first print boundary, so that every
        # small program of the loop (the recorder's adds) exists
        warm = max(int(self.t["warm_steps"]), self.rec.print_freq + 2)
        self._run_steps(warm - self.count)
        mark("warmed")
        self.ctx.say(setup_marks_s=marks)

    # ------------------------------------------------------------------
    def _step(self):
        """The worker's loop body for one iteration; returns the loss."""
        self.count += 1
        loss, _ = self.model.train_iter(self.count, self.rec)
        self.rec.print_train_info(self.count)
        return loss

    def _run_steps(self, n: int, host_times=None):
        """``n`` steps with bounded run-ahead, then wait for the last."""
        ahead = int(self.t["run_ahead"])
        pending = collections.deque()
        loss = None
        for _ in range(n):
            t0 = time.perf_counter()
            loss = self._step()
            if host_times is not None:
                host_times.append(time.perf_counter() - t0)
            pending.append(loss)
            if len(pending) > ahead:
                jax.block_until_ready(pending.popleft())
        if loss is not None:
            jax.block_until_ready(loss)
        return loss

    def window(self, seconds: float, tracer) -> None:
        ahead = int(self.t["run_ahead"])
        host_times, losses = [], []
        pending = collections.deque()
        traced = tracer is None
        steps = 0
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            if not traced and time.perf_counter() - t0 >= float(self.t["trace_after_s"]):
                # a steady stretch of its own: drain, trace N steps, drain
                while pending:
                    jax.block_until_ready(pending.popleft())
                n = int(self.t["trace_steps"])
                tracer.start()
                ts = time.perf_counter()
                self._run_steps(n, host_times)
                dt = time.perf_counter() - ts
                tracer.stop()
                steps += n
                traced = True
                self._facts["traced_steps"] = n
                self._facts["traced_s"] = dt
                self._facts["traced_samples_per_s"] = n * self.global_batch / dt
                continue
            ts = time.perf_counter()
            loss = self._step()
            host_times.append(time.perf_counter() - ts)
            steps += 1
            if steps % 64 == 0:
                self.ctx.memory.sample()
            losses.append(loss)
            pending.append(loss)
            if len(pending) > ahead:
                jax.block_until_ready(pending.popleft())
        while pending:
            jax.block_until_ready(pending.popleft())
        t_last = time.perf_counter()
        self.steps, self.window_s = steps, t_last - t0
        values = jax.device_get(losses) if losses else []
        self.n_bad = sum(1 for v in values if not float(v) == float(v)
                         or abs(float(v)) == float("inf"))
        self._facts["host_step_s"] = host_times
        self._facts["steps"] = steps
        self._facts["window_s"] = self.window_s

    # ------------------------------------------------------------------
    def end_to_end_values(self) -> dict:
        return {
            "train_images_per_s_per_chip":
                self.steps * self.global_batch / (self.window_s * self.chips),
        }

    def facts(self) -> dict:
        return self._facts

    def attempted_failed(self):
        return self.steps, self.n_bad

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        m = self.model
        m._train_it = None
        m.params = m.opt_state = m.net_state = None
        m.train_fn = None
        self.pool = None
        self.model = self.rule = None

    def check(self) -> dict:
        import compare

        ref = self.ctx.reference.first_steps(
            self.cfg, self.ctx.seed, self.global_batch, self.lr,
            steps=CHECK_STEPS,
            block=int(self.t.get("reference_block", 256)),
        )
        self.ref = ref
        dev = ref["first_grads"][0].devices()
        self._prog["first_grads"] = [
            g if g.devices() == dev else jax.device_put(g, next(iter(dev)))
            for g in self._prog["first_grads"]
        ]
        numbers = compare.training_numbers(self._prog, ref)
        self._prog["first_grads"] = ref["first_grads"] = None
        return numbers


def calibrate(context, seeds, control_seeds, emit) -> None:
    """Readings for the limits (see ``benchmarks/calibrate.py``).  The
    program's first steps need no measured window."""
    import compare

    def strip(numbers):
        return {k: v for k, v in numbers.items() if not k.startswith("_")}

    for seed in seeds:
        d = Driver(context(seed))
        d.setup()
        d.release()
        numbers = d.check()
        emit(kind="program", seed=seed, **strip(numbers),
             where=numbers["_where"], losses=d._prog["losses"],
             ref_losses=d.ref["losses"])
        del d
    for seed in control_seeds:
        d = Driver(context(seed))
        cfg, ref_mod = d.cfg, d.ctx.reference
        lr = stated_lr(cfg, d.chips)
        kw = dict(steps=CHECK_STEPS,
                  block=int(d.t.get("reference_block", 256)))
        ref = ref_mod.first_steps(cfg, seed, d.global_batch, lr, **kw)
        readings = {
            "control_int8": dict(precision="int8"),
            "fault_half_batch": dict(rows=(0, d.global_batch // 2)),
            "fault_state_unchanged": dict(frozen=True),
        }
        if d.chips > 1:
            readings["fault_no_exchange"] = dict(rows=(0, d.per_chip))
        for name, how in readings.items():
            other = ref_mod.first_steps(cfg, seed, d.global_batch, lr,
                                        **kw, **how)
            numbers = compare.training_numbers(other, ref)
            emit(kind=name, seed=seed, **strip(numbers),
                 leaf_grad_diffs=numbers["_where"]["leaf_grad_diffs"])
