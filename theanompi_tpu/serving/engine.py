"""What every serving program shares, whatever its block family:
``host_input`` (how scheduler state reaches a jitted program), the
pool's reserved ``TRASH_BLOCK`` and the prefill bucket ladder
(``default_buckets``, ``_validate_buckets``).  The engine itself is
``paging.PagedServingEngine``; the families' programs are
``serving/dense.py`` and ``serving/latent.py``.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu import observability as obs

# the tracer imports no jax: the program's jax-importing modules hand it
# the profiler's annotation, so boundary spans show in any profile
obs.install_annotation_hook(jax.profiler.TraceAnnotation)

TRASH_BLOCK = 0  # reserved physical block: masked/inactive writes land here


def default_buckets(max_len: int, lo: int = 16) -> Tuple[int, ...]:
    """Power-of-two prefill buckets ``lo, 2·lo, … , max_len`` (max_len
    always included so every admissible prompt has a bucket)."""
    out: List[int] = []
    b = lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def _validate_buckets(buckets, max_len: int) -> Tuple[int, ...]:
    """Normalize prefill bucket lengths to a sorted tuple of distinct
    positive ints — the compile-time contract of the prefill path.

    Each bucket is a padded prompt SHAPE: the engine compiles exactly
    ``len(buckets)`` prefill programs, and ``pick_chunk_bucket`` keys on exact
    integer lengths.  Anything looser recompiles per request instead of
    erroring here: a float bucket (16.5) silently truncates to a shape
    no prompt maps back to, a bool coerces to 0/1, a duplicate is a
    wasted compile, and an unhashable container would defeat the jit
    cache outright.  Validate once at construction, with the offending
    value in the message.
    """
    try:
        items = list(buckets)
    except TypeError:
        raise TypeError(
            f"buckets must be an iterable of ints, got "
            f"{type(buckets).__name__}"
        )
    if not items:
        raise ValueError("buckets must contain at least one length")
    out = []
    for b in items:
        # bool is an int subclass — reject it explicitly, True/False
        # are config mistakes, not prompt lengths
        if isinstance(b, bool) or not isinstance(b, (int, np.integer)):
            raise TypeError(
                f"bucket lengths must be ints (prefill shapes are "
                f"compile-time constants), got {b!r} of type "
                f"{type(b).__name__} — a non-int bucket means a "
                "recompile per request instead of a cache hit"
            )
        b = int(b)
        if b < 1:
            raise ValueError(f"bucket lengths must be >= 1, got {b}")
        out.append(b)
    if len(set(out)) != len(out):
        dupes = sorted({b for b in out if out.count(b) > 1})
        raise ValueError(
            f"duplicate bucket length(s) {dupes}: each bucket compiles "
            "one prefill program — duplicates waste compiles"
        )
    out = tuple(sorted(out))
    if out[-1] > max_len:
        raise ValueError(f"bucket {out[-1]} exceeds max_len={max_len}")
    return out


def host_input(x, dtype=None):
    """A device array from a PRIVATE host copy of ``x`` — for handing
    scheduler state to a jitted program.

    Dispatch returns before the program has read its inputs, and on the
    CPU client a NumPy buffer given to jax is shared, not copied.  A
    scheduler that mutates ``_lengths``/``_tables``/``_tokens`` in place
    on the next line therefore raced the program it had just launched:
    tokens that differed run to run (PR 21).  ``jnp.asarray`` shares the
    caller's buffer outright; ``jnp.array`` shares it too and then
    copies ON the device, asynchronously — a narrower window, still a
    race (a request's third token alternated 30/28 between identical
    runs until the copy moved here).  So the copy is taken by NumPy,
    synchronously, before jax sees anything; what jax may then alias is
    a buffer nobody else holds.  The arrays are a few hundred bytes."""
    return jnp.asarray(np.array(x, dtype=dtype, copy=True))
