"""The check of outputs has to fail when it should.

- The control: the plain reference computed one precision below the one
  the configuration states, put in the program's place, comes out not
  correct (here at a tiny size; on the chip at the cell's own size the
  readings are in ``PERF.md``).
- The faults: a whole rehearsal run with the timed path broken underneath
  — a step that returns its state unchanged, half of the batch left out
  with the mean taken over the rest, the exchange between chips left out
  — ends with ``correct`` false.
"""

from __future__ import annotations

import json

import pytest

import compare
import run
from test_harness import CELLS, drive, rehearsal_for

TRAIN_CELLS = sorted(
    c for c in CELLS if run.load_cell(c).traffic["driver"] == "train_bsp")


def _tiny_cell(cell):
    loaded = run.load_cell(cell)
    run.apply_rehearsal(loaded, rehearsal_for(cell))
    return loaded


@pytest.mark.parametrize("cell", TRAIN_CELLS[:1])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_one_precision_below_is_not_correct(cell, seed):
    loaded = _tiny_cell(cell)
    ref_mod = run.load_module("references", loaded.config_name)
    cfg = loaded.config
    batch = int(cfg["batch_size_per_chip"]) * loaded.chips
    lr = run.load_module("drivers", "train_bsp").stated_lr(cfg, loaded.chips)
    kw = dict(steps=3, block=int(loaded.traffic["reference_block"]))
    ref = ref_mod.first_steps(cfg, seed, batch, lr, **kw)
    control = ref_mod.first_steps(cfg, seed, batch, lr, precision="int8", **kw)
    rows = compare.judge(compare.training_numbers(control, ref), loaded.limits)
    assert not all(ok for *_, ok in rows), rows
    same = ref_mod.first_steps(cfg, seed, batch, lr, **kw)
    rows = compare.judge(compare.training_numbers(same, ref), loaded.limits)
    assert all(ok for *_, ok in rows), rows


def _break_train_fn(monkeypatch, wrap):
    """Replace the compiled step every model builds by ``wrap(step)``."""
    from theanompi_tpu.models import base

    orig = base.TpuModel.compile_train

    def compile_train(self, *a, **kw):
        fn = orig(self, *a, **kw)
        self.train_fn = wrap(fn, self)
        return self.train_fn

    monkeypatch.setattr(base.TpuModel, "compile_train", compile_train)


def state_unchanged(fn, model):
    import jax
    import jax.numpy as jnp

    def step(params, net_state, opt_state, x, y, key):
        copies = jax.tree.map(jnp.copy, (params, net_state, opt_state))
        out = fn(*copies, x, y, key)  # donates the copies
        return params, net_state, opt_state, out[3], out[4]

    return step


def half_batch_left_out(fn, model):
    import jax.numpy as jnp

    def step(params, net_state, opt_state, x, y, key):
        h = x.shape[0] // 2
        # the mean over the first half alone, at the step's own shape
        return fn(params, net_state, opt_state,
                  jnp.concatenate([x[:h], x[:h]]),
                  jnp.concatenate([y[:h], y[:h]]), key)

    return step


@pytest.mark.parametrize("cell", TRAIN_CELLS[:1])
@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out],
                         ids=lambda f: f.__name__)
def test_a_broken_step_is_not_correct(cell, fault, monkeypatch):
    _break_train_fn(monkeypatch, fault)
    rc, lines, err = drive(cell, 4242, 0)
    assert rc == 0, err
    last = json.loads(lines[-1])
    assert last["correct"] is False, last["compared"]
    assert "NOT CORRECT" in err


@pytest.mark.parametrize(
    "cell", [c for c in TRAIN_CELLS if CELLS[c]["chips"] > 1][:1])
def test_exchange_left_out_is_not_correct(cell, monkeypatch):
    """Every chip keeps its own gradient: the step's replicated outputs
    are then the first chip's, a quarter of the batch."""
    import jax

    if len(jax.devices()) < CELLS[cell]["chips"]:
        pytest.skip("needs virtual devices (see conftest.py)")
    from theanompi_tpu.parallel import exchanger

    monkeypatch.setattr(
        exchanger.BSP_Exchanger, "reduce_grads",
        lambda self, grads, *a, **kw: grads,
    )
    rc, lines, err = drive(cell, 4243, 0)
    assert rc == 0, err
    assert json.loads(lines[-1])["correct"] is False


# ---------------------------------------------------------------------------
# a served model
# ---------------------------------------------------------------------------

SERVE_CELLS = sorted(
    c for c in CELLS if run.load_cell(c).traffic["driver"] == "serve_paged")


@pytest.mark.parametrize("cell", SERVE_CELLS[:1])
def test_serving_control_one_precision_below_is_not_correct(cell):
    """At each position of the same prompts and tokens, the token the
    int8 forward puts first lies below the reference's best by more than
    the limit somewhere; the reference's own greedy tokens never do."""
    import traffic

    loaded = _tiny_cell(cell)
    ref = run.load_module("references", loaded.config_name)
    cfg = loaded.config
    worst = {"float32": [], "int8": []}
    for seed in (1, 2, 3):
        weights = ref.make_weights(cfg, seed)
        for r in traffic.generate(loaded.traffic, seed, cfg["vocab_size"])[:6]:
            prompt = r["prompt"]
            full = ref.logits(cfg, weights, prompt)
            served = [int(full[-1].argmax())]  # greedy, by the reference
            for _ in range(r["max_new_tokens"] - 1):
                nxt = ref.logits(cfg, weights, prompt + served)[-1]
                served.append(int(nxt.argmax()))
            for precision in worst:
                worst[precision] += ref.served_gaps(
                    cfg, weights, prompt, served, precision=precision)
    numbers = lambda g: {"token_gap_max": max(g), "token_gap_mean": sum(g) / len(g)}
    ok = lambda n: all(row[-1] for row in compare.judge(n, loaded.limits))
    assert ok(numbers(worst["float32"])), numbers(worst["float32"])
    assert not ok(numbers(worst["int8"])), numbers(worst["int8"])


@pytest.mark.parametrize("cell", SERVE_CELLS[:1])
def test_a_token_altered_where_it_is_produced_is_not_correct(cell, monkeypatch):
    import numpy as np
    from theanompi_tpu.serving import scheduler

    orig = scheduler.ContinuousBatchingScheduler._pick_tokens
    state = {"n": 0}

    def altered(self, picks, logits):
        toks = np.array(orig(self, picks, logits))
        state["n"] += 1
        if state["n"] % 3 == 0:  # every third pick: the runner-up's neighbour
            toks = (toks + 1) % self.engine.vocab_size
        return toks

    monkeypatch.setattr(
        scheduler.ContinuousBatchingScheduler, "_pick_tokens", altered)
    rc, lines, err = drive(cell, 4244, 0, seconds=2.0)
    assert rc == 0, err
    last = json.loads(lines[-1])
    assert last["correct"] is False, last["compared"]
