"""Stochastic sampling on the serving decode path (ROADMAP open item).

Contracts under test:

- ``temperature=0`` (the default) is EXACT greedy — bit-identical
  outputs to the pre-sampling scheduler, so the parity/bench paths are
  untouched.
- sampling is deterministic per ``(seed, token index)`` and
  independent of batch interleaving — the same determinism contract
  continuous batching gives greedy requests.
- ``top_k`` restricts the support to the k highest logits.
- sampling-config changes cause ZERO recompiles: temperature/top_k are
  traced scalars, one compiled sampler per logits shape.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.runtime.mesh import make_mesh
from theanompi_tpu.serving import (
    ContinuousBatchingScheduler,
    Request,
    PagedServingEngine,
)
from theanompi_tpu.serving.sampling import Sampler, request_key

CFG = dict(
    seq_len=64,
    vocab_size=32,
    d_model=32,
    n_heads=4,
    n_layers=2,
    batch_size=2,
    n_synth_train=2,
    n_synth_val=1,
    comm_probe=False,
    print_freq=10_000,
)


@pytest.fixture(scope="module")
def engine():
    mesh = make_mesh(devices=jax.devices()[:1])
    model = TransformerLM(config=dict(CFG), mesh=mesh)
    return PagedServingEngine(model, n_slots=2, max_len=64)


def _run(engine, requests):
    sched = ContinuousBatchingScheduler(engine)
    for r in requests:
        sched.submit(r)
    return sched.run()


# ---------------------------------------------------------------------------
# sampler unit tests (no engine needed)
# ---------------------------------------------------------------------------

def test_no_recompile_across_configs():
    """The zero-recompile discipline: any mix of temperature/top_k
    values runs ONE compiled program per logits shape."""
    s = Sampler()
    logits = jnp.asarray(np.random.RandomState(0).randn(32), jnp.float32)
    for temp, k in [(0.7, 0), (1.3, 5), (0.1, 1), (2.0, 31), (5.0, 0),
                    (0.0, 0), (0.0, 3)]:
        tok = s.sample(logits, jax.random.PRNGKey(1), temp, k)
        assert 0 <= tok < 32
    assert s._n_traces == 1, (
        f"sampler retraced {s._n_traces}x across sampling configs"
    )


def test_temperature_zero_is_exact_argmax():
    s = Sampler()
    rng = np.random.RandomState(1)
    for _ in range(5):
        logits = jnp.asarray(rng.randn(32), jnp.float32)
        tok = s.sample(logits, jax.random.PRNGKey(0), 0.0, 0)
        assert tok == int(jnp.argmax(logits))


def test_top_k_one_is_greedy_even_at_high_temperature():
    s = Sampler()
    logits = jnp.asarray(np.random.RandomState(2).randn(32), jnp.float32)
    best = int(jnp.argmax(logits))
    for i in range(20):
        assert s.sample(logits, jax.random.PRNGKey(i), 10.0, 1) == best


def test_top_k_restricts_support():
    s = Sampler()
    logits = jnp.asarray(np.random.RandomState(3).randn(32), jnp.float32)
    top4 = set(np.argsort(np.asarray(logits))[-4:].tolist())
    drawn = {
        s.sample(logits, jax.random.PRNGKey(i), 3.0, 4) for i in range(64)
    }
    assert drawn <= top4
    assert len(drawn) > 1, "high temperature should spread over the top-k"


def test_sampling_is_key_deterministic():
    s = Sampler()
    logits = jnp.asarray(np.random.RandomState(4).randn(32), jnp.float32)
    a = s.sample(logits, jax.random.PRNGKey(7), 1.0, 0)
    b = s.sample(logits, jax.random.PRNGKey(7), 1.0, 0)
    assert a == b
    draws = {
        s.sample(logits, jax.random.PRNGKey(i), 1.5, 0) for i in range(32)
    }
    assert len(draws) > 1, "different keys never vary the draw?"


def test_request_key_depends_on_seed_and_index_only():
    k1 = request_key(11, "reqA", 3)
    k2 = request_key(11, "reqB", 3)  # same seed wins over id
    k3 = request_key(11, "reqA", 4)
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    assert not np.array_equal(np.asarray(k1), np.asarray(k3))
    # unseeded: stable hash of the id (process-independent)
    u1 = request_key(None, "reqA", 0)
    u2 = request_key(None, "reqA", 0)
    u3 = request_key(None, "reqB", 0)
    assert np.array_equal(np.asarray(u1), np.asarray(u2))
    assert not np.array_equal(np.asarray(u1), np.asarray(u3))


def test_request_validation():
    with pytest.raises(ValueError, match="temperature"):
        Request(id="r", prompt=[1], temperature=-0.1)
    with pytest.raises(ValueError, match="top_k"):
        Request(id="r", prompt=[1], top_k=-1)


# ---------------------------------------------------------------------------
# batched device-side pick (ISSUE 8 satellite: no host round trip per
# emitted token — one fused argmax/sample dispatch per tick)
# ---------------------------------------------------------------------------

def test_pick_batch_matches_single_row_sampler():
    """Row i of a batched pick is bit-identical to a single-row sample
    with row i's key/temperature/top_k — batching can never perturb a
    request's stream."""
    s = Sampler()
    rng = np.random.RandomState(5)
    logits = jnp.asarray(rng.randn(6, 32), jnp.float32)
    temps = np.asarray([0.0, 0.7, 1.3, 0.1, 2.0, 0.0], np.float32)
    topks = np.asarray([0, 0, 5, 1, 31, 3], np.int32)
    keys = np.stack([
        np.asarray(jax.random.PRNGKey(100 + i)) for i in range(6)
    ]).astype(np.uint32)
    batch = s.pick_batch(logits, keys, temps, topks)
    for i in range(6):
        want = s.sample(logits[i], jnp.asarray(keys[i]),
                        float(temps[i]), int(topks[i]))
        assert int(batch[i]) == want, f"row {i} diverged"


def test_pick_batch_no_recompile_across_mixes():
    """Any mix of greedy/sampling rows runs ONE compiled batch program
    per logits shape."""
    s = Sampler()
    rng = np.random.RandomState(6)
    logits = jnp.asarray(rng.randn(4, 32), jnp.float32)
    keys = np.zeros((4, 2), np.uint32)
    for temps, ks in [
        ([0.0] * 4, [0] * 4),
        ([0.9, 0.0, 1.5, 0.0], [0, 0, 7, 2]),
        ([2.0] * 4, [1] * 4),
    ]:
        out = s.pick_batch(
            logits, keys, np.asarray(temps, np.float32),
            np.asarray(ks, np.int32),
        )
        assert out.shape == (4,)
    assert s._n_batch_traces == 1, (
        f"batched sampler retraced {s._n_batch_traces}x"
    )


def test_pick_batch_all_greedy_is_exact_argmax():
    s = Sampler()
    logits = jnp.asarray(np.random.RandomState(8).randn(3, 32), jnp.float32)
    out = s.pick_batch(
        logits, np.zeros((3, 2), np.uint32),
        np.zeros((3,), np.float32), np.zeros((3,), np.int32),
    )
    assert list(out) == list(np.argmax(np.asarray(logits), axis=-1))


# ---------------------------------------------------------------------------
# scheduler integration
# ---------------------------------------------------------------------------

def test_default_requests_unchanged_greedy(engine):
    """Requests without sampling fields go through the original
    batched-argmax path and match an explicit temperature=0 request."""
    prompt = [3, 1, 4, 1, 5]
    a = _run(engine, [Request(id="d", prompt=prompt, max_new_tokens=8)])
    b = _run(engine, [Request(id="e", prompt=prompt, max_new_tokens=8,
                              temperature=0.0)])
    assert a["d"] == b["e"]


def test_sampled_request_reproducible_and_valid(engine):
    prompt = [2, 7, 1]
    r1 = _run(engine, [Request(id="s", prompt=prompt, max_new_tokens=8,
                               temperature=0.9, top_k=8, seed=42)])
    r2 = _run(engine, [Request(id="s", prompt=prompt, max_new_tokens=8,
                               temperature=0.9, top_k=8, seed=42)])
    assert r1["s"] == r2["s"]
    assert all(0 <= t < CFG["vocab_size"] for t in r1["s"])


def test_sampling_independent_of_interleaving(engine):
    """The continuous-batching determinism contract extends to
    sampling: a request's tokens don't depend on who shares the batch."""
    target = Request(id="t", prompt=[5, 6, 7], max_new_tokens=6,
                     temperature=0.8, top_k=0, seed=123)
    solo = _run(engine, [target])["t"]
    crowd = _run(engine, [
        Request(id="a", prompt=[9, 9], max_new_tokens=10),
        Request(id="t", prompt=[5, 6, 7], max_new_tokens=6,
                temperature=0.8, top_k=0, seed=123),
        Request(id="b", prompt=[1], max_new_tokens=4,
                temperature=1.2, seed=7),
    ])["t"]
    assert solo == crowd


def test_mixed_greedy_and_sampling_greedy_unperturbed(engine):
    """Greedy requests sharing ticks with sampling requests keep their
    bit-exact outputs (the batched argmax path still serves them)."""
    g_solo = _run(engine, [
        Request(id="g", prompt=[8, 2, 3], max_new_tokens=8),
    ])["g"]
    mixed = _run(engine, [
        Request(id="g", prompt=[8, 2, 3], max_new_tokens=8),
        Request(id="s", prompt=[4, 4], max_new_tokens=8,
                temperature=1.0, seed=1),
    ])
    assert mixed["g"] == g_solo
