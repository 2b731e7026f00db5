"""All-to-all (Ulysses-style) sequence parallelism.

The second sequence-parallel strategy next to ``parallel.ring_attention``
(the reference framework has neither — SURVEY.md §3.4/§6 long-context
"ABSENT" — but long-context is first-class here, so both canonical
layouts are provided and selectable per model config):

- **ring**: every device keeps its query shard; K/V blocks rotate around
  the ``sp`` ring via ``ppermute``. Communication is 2·(T/n)·D per hop ×
  n hops, overlapped with blockwise compute. Scales to sequence lengths
  where even one head's full-sequence scores would not fit.
- **all-to-all (this module)**: two ``lax.all_to_all`` reshuffles flip
  the sharding from sequence-split to *head*-split and back. Between
  them every device holds the FULL sequence for ``H/n`` heads, so plain
  dense attention (fused by XLA, no per-hop latency chain) runs locally.
  After the DeepSpeed-Ulysses layout; on TPU the all-to-all rides ICI
  as one fused collective instead of n ppermute hops, which wins when
  ``n_heads % n == 0`` and the full (T × T) score tile per head fits.

Both are numerically exact. Trade-off summary: ring has O(n) latency
depth but constant memory per device; all-to-all has O(1) collective
depth but needs the dense T×T attention per local head.

Everything runs *inside* ``shard_map`` on local shards (B, T/n, H, D).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from theanompi_tpu.parallel.ring_attention import SEQ_AXIS, full_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = SEQ_AXIS,
    axis_size: Optional[int] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    attn_impl: str = "xla",
) -> jax.Array:
    """Exact attention over sequence shards via head⇄sequence all-to-all.

    Call inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``. Local shapes: q/k/v (B, T_local, H, D); returns the
    local output shard (B, T_local, H, D) in q's dtype. Requires
    ``H % axis_size == 0`` (each device owns H/n whole heads in the
    middle phase). ``axis_size=1`` degrades to dense attention with no
    collectives traced. ``attn_impl='flash'`` runs the local dense
    attention (full sequence × local heads) through the fused Pallas
    kernel — the combination that makes the memory story work at long T.
    """

    def dense(qq, kk, vv):
        from theanompi_tpu.parallel.ring_attention import local_attention

        return local_attention(qq, kk, vv, causal, scale, attn_impl)

    if axis_size is None:
        raise ValueError("ulysses_attention needs static axis_size (mesh.shape[axis])")
    if axis_size == 1:
        return dense(q, k, v)
    h = q.shape[2]
    if h % axis_size:
        raise ValueError(
            f"all-to-all sequence parallelism needs n_heads % sp == 0, "
            f"got n_heads={h}, sp={axis_size} (use sp_mode='ring' instead)"
        )

    def seq_to_heads(x):
        # (B, T/n, H, D) → (B, T, H/n, D): scatter heads, gather sequence
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        # (B, T, H/n, D) → (B, T/n, H, D): the inverse reshuffle
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full sequence resident: plain causal masking is exact; the local
    # dense attention is XLA-fused or the Pallas flash kernel
    out = dense(qg, kg, vg)
    return heads_to_seq(out).astype(q.dtype)


def ulysses_self_attention(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = SEQ_AXIS,
    causal: bool = False,
):
    """Standalone sharded entry point (tests / direct use).

    Takes *global* (B, T, H, D) arrays, shard_maps the all-to-all
    attention over ``mesh`` axis ``axis`` (T and H must divide by its
    size), returns the global result.
    """
    n = int(mesh.shape[axis])
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        partial(ulysses_attention, axis_name=axis, axis_size=n, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)(q, k, v)
