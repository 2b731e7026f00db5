"""Stochastic sampling — temperature and top-k on the decode path.

The scheduler's default stays greedy argmax (bit-reproducible parity
with the no-cache forward, the contract tests/test_serving.py pins).
This module adds the standard serving knobs on top of the SAME logits:

- **temperature** — logits scaled by ``1/T`` before sampling; ``T=0``
  is EXACT greedy (the argmax path, not a small-temperature limit — a
  request with ``temperature=0`` is bitwise-identical to today).
- **top-k** — all but the k highest logits masked to -inf before
  sampling; ``top_k=0`` disables the filter.

Recompile contract (the serving engine's zero-recompile discipline):
``temperature`` and ``top_k`` enter the jitted sampler as TRACED
scalars, never Python constants — any mix of sampling configs across
requests runs ONE compiled program per logits shape
(tests/test_serving_sampling.py::test_no_recompile_across_configs).
The top-k threshold is therefore computed with a dynamic gather into
the sorted logits (shape-static) rather than ``lax.top_k`` (whose
output shape would bake ``k`` into the program).

Determinism: sampling draws from ``jax.random`` keyed by the request's
``seed`` folded with the token index, so a request replayed with the
same seed produces the same tokens regardless of batch interleaving —
the same interleaving-independence the greedy scheduler guarantees.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from theanompi_tpu.serving.engine import host_input

_NEG_INF = -1e30  # the attention masks' finite value (ops.pallas_flash)


class Sampler:
    """One jit-compiled sampling program shared by every request.

    ``sample`` takes host scalars and returns a Python int token;
    the compiled program is cached per logits shape only.
    ``pick_batch`` is the same draw vmapped over slot rows: one fused
    argmax/sample dispatch per scheduler tick, one host transfer —
    never a per-slot round trip.  Row i draws with row i's key, so a
    batched pick is bit-identical to len(batch) single picks (tested).
    """

    def __init__(self):
        self._n_traces = 0  # observability: tests pin the no-recompile
        # contract by counting trace-time executions
        self._n_batch_traces = 0
        self._fn = jax.jit(self._sample)
        self._batch_fn = jax.jit(self._sample_batch)

    def _sample(self, logits, key, temperature, top_k):
        self._n_traces += 1  # runs at trace time only
        return self._sample_core(logits, key, temperature, top_k)

    def _sample_batch(self, logits, keys, temperatures, top_ks):
        self._n_batch_traces += 1  # runs at trace time only
        return jax.vmap(self._sample_core)(
            logits, keys, temperatures, top_ks
        )

    def _sample_core(self, logits, key, temperature, top_k):
        v = logits.shape[-1]
        lg = logits.astype(jnp.float32)
        greedy = jnp.argmax(lg, axis=-1)
        # top-k mask with k as a TRACED scalar: threshold = k-th largest
        # via a dynamic gather into the descending sort — shape-static,
        # so distinct k values share one executable (lax.top_k would
        # bake k into the output shape = a compile per distinct k)
        desc = jnp.sort(lg, axis=-1)[..., ::-1]
        k = jnp.clip(top_k, 1, v)
        thresh = jnp.take_along_axis(
            desc, (k - 1).reshape((1,) * desc.ndim), axis=-1
        ).squeeze(-1)
        masked = jnp.where(
            (top_k > 0) & (lg < thresh[..., None]), _NEG_INF, lg
        )
        # categorical is gumbel-argmax on the scaled logits — no
        # exp/normalize, so tiny temperatures can't overflow
        scaled = masked / jnp.maximum(temperature, jnp.float32(1e-6))
        drawn = jax.random.categorical(key, scaled, axis=-1)
        return jnp.where(temperature > 0.0, drawn, greedy)

    def sample(
        self,
        logits,
        key,
        temperature: float,
        top_k: int = 0,
    ) -> int:
        """Sample one token id from ``logits`` (V,)."""
        out = self._fn(
            logits,
            key,
            jnp.float32(temperature),
            jnp.int32(top_k),
        )
        return int(out)

    def draw_batch(self, logits, keys, temperatures, top_ks):
        """One token id per row of ``logits`` (N, V) in a single
        dispatch.  ``keys`` (N, 2) uint32 raw PRNG keys (row ignored
        where temperature is 0), ``temperatures`` (N,) float,
        ``top_ks`` (N,) int.  Rows with temperature 0 are exact argmax
        — the greedy hot path rides along for free.  Returns the
        device int array (N,), not waited for."""
        return self._batch_fn(
            jnp.asarray(logits),
            host_input(keys, jnp.uint32),
            host_input(temperatures, jnp.float32),
            host_input(top_ks, jnp.int32),
        )

    def pick_batch(self, logits, keys, temperatures, top_ks):
        """``draw_batch`` fetched: a host int array (N,)."""
        import numpy as np

        return np.asarray(
            self.draw_batch(logits, keys, temperatures, top_ks)
        )


def request_key(seed: Optional[int], rid: str, token_index: int):
    """Per-draw PRNG key: request seed (or a stable hash of the id when
    unseeded) folded with the token index — decode order across slots
    never changes a request's stream."""
    if seed is None:
        # stable across processes (Python's str hash is salted):
        # zlib.crc32 of the id, cheap and deterministic
        import zlib

        seed = zlib.crc32(rid.encode("utf-8"))
    return jax.random.fold_in(jax.random.PRNGKey(int(seed)), token_index)
