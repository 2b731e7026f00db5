"""The one generator of serving traffic.  A traffic mix is a data file of
parameters (``benchmarks/traffic/<name>.json``); this turns it and a seed
into the requests a driver sends.

The sizes of a mix are no random draw: a length distribution is laid out
as its own ``n_requests`` quantiles, so the list *is* the distribution the
file states (its mean is the file's mean to a hundredth of a token) and
no lucky or unlucky sample decides a metric.  The quantiles are shuffled
once, by the mix's own ``order_seed`` (prompts and outputs apart, so the
two do not correlate), and every run sends them in that one order; the
run's seed draws the token ids (and, in the driver, the weights).  So two
seeds offer the same work in the same order, and what differs between
them is the system's doing.

Parameters of a mix (all lengths in tokens):

- ``n_requests`` — how many requests the list holds; a driver that runs
  out starts the list again.
- ``prompt_len`` / ``output_len`` — ``{"dist": "lognormal", "mean",
  "sigma", "min", "max"}``: log-normal quantiles, rounded and clipped to
  ``min``..``max``, with the median solved so that the list's mean is
  ``mean`` (the number a source publishes); or ``{"dist": "fixed",
  "value"}``.
- ``max_total`` — prompt + output never exceeds it (the output is cut).
- ``shared_prefix`` — tokens that every prompt starts with (0: none).
- ``order_seed`` — the seed of the one order of the sizes.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lo, hi = int(spec["min"]), int(spec["max"])
    spread = np.exp(float(spec["sigma"]) * z)

    def at(median: float) -> np.ndarray:
        return np.clip(np.rint(median * spread), lo, hi).astype(np.int64)

    want = float(spec["mean"])
    if not lo < want < hi:
        raise ValueError(f"mean {want} outside {lo}..{hi}")
    a, b = float(lo), float(hi)  # the list's mean rises with the median
    for _ in range(60):
        mid = 0.5 * (a + b)
        if at(mid).mean() < want:
            a = mid
        else:
            b = mid
    return at(b)


def _lengths(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        return _lognormal_quantiles(spec, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def sizes(mix: dict):
    """[(prompt length, output length)] of the mix in sending order, the
    same for every seed of a run."""
    n = int(mix["n_requests"])
    rng = np.random.default_rng(int(mix["order_seed"]))
    prompts = rng.permutation(_lengths(mix["prompt_len"], n))
    outputs = rng.permutation(_lengths(mix["output_len"], n))
    cap = int(mix["max_total"])
    prompts = np.minimum(prompts, cap - 1)
    outputs = np.maximum(1, np.minimum(outputs, cap - prompts))
    return list(zip(prompts.tolist(), outputs.tolist()))


def generate(mix: dict, seed: int, vocab_size: int):
    """The requests of one run, in sending order:
    ``[{"id", "prompt", "max_new_tokens"}]``."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    shared = int(mix.get("shared_prefix", 0))
    prefix = rng.integers(0, vocab_size, shared).tolist()
    out = []
    for i, (p, m) in enumerate(sizes(mix)):
        body = rng.integers(0, vocab_size, max(p - shared, 1)).tolist()
        out.append({"id": f"r{i}", "prompt": (prefix + body)[:max(p, 1)],
                    "max_new_tokens": int(m)})
    return out
