"""KV-cache inference engine for ``TransformerLM``.

The training model (``models/transformer.py``) has no autoregressive
path: its ``net.apply`` recomputes attention over the whole sequence.
This engine re-expresses the SAME forward math (identical projection /
LayerNorm / softmax numerics — fp32 statistics, fp32 MXU accumulation)
as two jit-compiled programs:

- **prefill**: one whole-prompt pass that fills a slot's K/V cache and
  returns the logits at the last real token.  Prompts are padded to a
  small set of *length buckets* so serving arbitrary prompt lengths
  compiles ``len(buckets)`` programs total, not one per length.
- **decode_step**: one token for EVERY slot at once — q/k/v for the new
  token only, attention against the cached K/V, cache written in place
  (buffers donated, so the cache never copies).

The cache is preallocated at ``(n_layers, n_slots, max_len, heads,
head_dim)`` and laid out on the model's own mesh: the slot axis shards
over ``dp`` when it divides, the head axis over ``tp`` when the model
is tensor-parallel (matching the column-parallel wq/wk/wv shards that
produce it), so serving reuses the training sharding machinery instead
of gathering params to one device.

Decode correctness contract (tested in tests/test_serving.py): greedy
decode through the cache is argmax-identical, step for step, to the
no-cache full-recompute forward — causal attention at position ``t``
sees exactly tokens ``[0, t]`` either way.

Scope: the dense non-MoE, non-pipelined stack (``moe_experts=0``,
``pp=1``).  ``sp`` is a long-context *training* axis (ring attention
over sequence shards); single-token decode has no sequence dim to
shard, so the engine requires ``sp=1`` and serves tensor parallelism
through GSPMD instead (params stay in their Megatron layout under
``jit``; XLA partitions the dense ops).
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu import observability as obs
from theanompi_tpu.runtime.mesh import DATA_AXIS, TP_AXIS

# the tracer imports no jax: the program's jax-importing modules hand it
# the profiler's annotation, so boundary spans show in any profile
obs.install_annotation_hook(jax.profiler.TraceAnnotation)

_NEG_INF = -1e30  # same finite mask value as parallel.ring_attention

_PREFILLS = obs.get_registry().counter(
    "serve_prefills_total",
    "prefill dispatches by padded bucket length (compile-cache "
    "visibility: one distinct bucket label per compiled program)",
)


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
    ``len(buckets)`` prefill programs, and ``pick_bucket`` keys on exact
    integer lengths.  Anything looser recompiles per request instead of
    erroring here: a float bucket (16.5) silently truncates to a shape
    no prompt maps back to, a bool coerces to 0/1, a duplicate is a
    wasted compile, and an unhashable container would defeat the jit
    cache outright.  Validate once at construction, with the offending
    value in the message.
    """
    import numpy as np

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


class ServingEngine:
    """Prefill + continuous-decode executor over a ``TransformerLM``.

    ``model`` supplies the config, mesh, params and (for tp) the
    ``param_specs`` produced by ``_build_param_specs`` — the same specs
    training shards by.  The engine never mutates the model.
    """

    def __init__(
        self,
        model,
        n_slots: int = 4,
        max_len: Optional[int] = None,
        buckets: Optional[Sequence[int]] = None,
    ):
        cfg = model.config
        # a 'latent_moe' model (ops.latent_block) brings its own forward
        # pass, experts included; it is served paged (serving/latent.py)
        self.latent = str(cfg.get("block", "dense")) == "latent_moe"
        if self.latent and not getattr(self, "is_paged", False):
            raise ValueError("a block='latent_moe' model is served by "
                             "PagedServingEngine")
        if int(cfg.get("moe_experts", 0) or 0) and not self.latent:
            raise ValueError("serving supports the dense FFN stack only "
                             "(moe_experts=0)")
        if getattr(model, "pp_size", 1) > 1:
            raise ValueError("serving requires pp=1 (the GPipe scan has no "
                             "single-token decode form)")
        if getattr(model, "sp_size", 1) > 1:
            raise ValueError(
                "serving requires sp=1: sequence parallelism shards the "
                "sequence dim, which a single-token decode step does not "
                "have — rebuild the model with sp=1 (tp is supported)"
            )
        self.model = model
        self.mesh = model.mesh
        self.d_model = int(cfg.d_model)
        self.n_heads = int(cfg.n_heads)
        self.n_layers = int(cfg.n_layers)
        self.vocab_size = int(cfg.vocab_size)
        self.head_dim = self.d_model // self.n_heads
        self.scale = self.head_dim ** -0.5
        self.compute_dtype = (
            jnp.dtype(cfg.compute_dtype) if cfg.compute_dtype else None
        )
        self.n_slots = int(n_slots)
        train_len = int(cfg.seq_len)
        self.max_len = int(max_len) if max_len is not None else train_len
        if self.max_len > train_len and not self.latent:  # rotary: no table
            raise ValueError(
                f"max_len={self.max_len} exceeds the learned positional "
                f"table ({train_len} rows, config seq_len)"
            )
        self.buckets = _validate_buckets(
            buckets if buckets is not None else default_buckets(self.max_len),
            self.max_len,
        )
        # cache layout on the model's mesh: slots over dp when it
        # divides, heads over the Megatron tp shards that produce them
        slot_ax = (
            DATA_AXIS
            if DATA_AXIS in self.mesh.shape
            and int(self.mesh.shape[DATA_AXIS]) > 1
            and self.n_slots % int(self.mesh.shape[DATA_AXIS]) == 0
            else None
        )
        head_ax = (
            TP_AXIS
            if TP_AXIS in self.mesh.shape and int(self.mesh.shape[TP_AXIS]) > 1
            else None
        )
        self.kv_spec = P(None, slot_ax, None, head_ax, None)
        # trace-time counters: tests pin the zero-recompile discipline
        # (one decode program ever; one prefill program per bucket) by
        # counting how often these functions actually retrace
        self._n_prefill_traces = 0
        self._n_decode_traces = 0
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=(1,))
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=(1,))

    # ------------------------------------------------------------------
    # cache
    # ------------------------------------------------------------------
    def init_cache(self):
        """Preallocated K/V cache pytree: ``k``/``v`` of shape
        (layers, slots, max_len, heads, head_dim) plus per-slot
        ``length`` (tokens resident).  Allocated ALREADY sharded —
        a big cache must never materialize on one device first."""
        dt = self.compute_dtype or jnp.float32
        sh = NamedSharding(self.mesh, self.kv_spec)
        shape = (
            self.n_layers, self.n_slots, self.max_len,
            self.n_heads, self.head_dim,
        )
        rep = NamedSharding(self.mesh, P())
        return {
            "k": jnp.zeros(shape, dt, device=sh),
            "v": jnp.zeros(shape, dt, device=sh),
            "length": jnp.zeros((self.n_slots,), jnp.int32, device=rep),
        }

    def pick_bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt of {n} tokens exceeds the largest bucket "
            f"{self.buckets[-1]} (max_len={self.max_len})"
        )

    # ------------------------------------------------------------------
    # shared forward pieces (numerics mirror ops.attention exactly)
    # ------------------------------------------------------------------
    def _weights(self, params):
        """Split the Sequential params list: embedding, positions, the
        block dicts, final LN, logits head."""
        n = self.n_layers
        emb, pos = params[0], params[1]
        blocks = params[2:2 + n]
        lnf, head = params[2 + n], params[3 + n]
        return emb, pos, blocks, lnf, head

    def _ln(self, p, x):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + 1e-5)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)

    def _proj(self, x, w):
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            with jax.named_scope("cast_weights"):
                w = w.astype(self.compute_dtype)
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        return y

    def _mlp(self, bp, x):
        w1, w2 = bp["mlp_in"]["w"], bp["mlp_out"]["w"]
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            with jax.named_scope("cast_weights"):
                w1 = w1.astype(self.compute_dtype)
                w2 = w2.astype(self.compute_dtype)
        h = jnp.dot(x, w1, preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h + bp["mlp_in"]["b"])
        if self.compute_dtype is not None:
            h = h.astype(self.compute_dtype)
        y = jnp.dot(h, w2, preferred_element_type=jnp.float32)
        if self.compute_dtype is not None:
            y = y.astype(self.compute_dtype)
        return y + bp["mlp_out"]["b"].astype(y.dtype)

    def _embed(self, emb, pos, tokens, positions):
        x = jnp.take(emb["table"], tokens, axis=0)
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
        return x + jnp.take(pos["pos"], positions, axis=0).astype(x.dtype)

    def _head(self, lnf, head, x):
        x = self._ln(lnf, x)
        w = head["w"]
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
            with jax.named_scope("cast_weights"):
                w = w.astype(self.compute_dtype)
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return y.astype(jnp.float32) + head["b"]

    # ------------------------------------------------------------------
    # prefill: whole padded prompt, one slot
    # ------------------------------------------------------------------
    def _prefill_fn(self, params, cache, tokens, slot, true_len):
        """tokens (B,) int32 padded to a bucket; writes slot's K/V rows
        [0, B) (rows past ``true_len`` are pad garbage the decode mask
        never reads and the next decode write overwrites) and returns
        logits at the last real token."""
        self._n_prefill_traces += 1  # runs at trace time only
        emb, pos, blocks, lnf, head = self._weights(params)
        (b,) = tokens.shape
        x = self._embed(emb, pos, tokens, jnp.arange(b))  # (B, D)
        h = self.n_heads
        hd = self.head_dim
        causal = jnp.arange(b)[:, None] >= jnp.arange(b)[None, :]
        ks, vs = [], []
        for bp in blocks:
            y = self._ln(bp["ln1"], x)
            q = self._proj(y, bp["attn"]["wq"]).reshape(b, h, hd)
            k = self._proj(y, bp["attn"]["wk"]).reshape(b, h, hd)
            v = self._proj(y, bp["attn"]["wv"]).reshape(b, h, hd)
            s = jnp.einsum(
                "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
            ) * self.scale
            s = jnp.where(causal[None], s, _NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(
                "hqk,khd->qhd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            ).astype(y.dtype)
            x = x + self._proj(o.reshape(b, h * hd), bp["attn"]["wo"])
            x = x + self._mlp(bp, self._ln(bp["ln2"], x))
            ks.append(k)
            vs.append(v)
        dt = cache["k"].dtype
        k_new = jnp.stack(ks).astype(dt)[:, None]  # (L, 1, B, H, hd)
        v_new = jnp.stack(vs).astype(dt)[:, None]
        cache = dict(
            cache,
            k=lax.dynamic_update_slice(
                cache["k"], k_new, (0, slot, 0, 0, 0)
            ),
            v=lax.dynamic_update_slice(
                cache["v"], v_new, (0, slot, 0, 0, 0)
            ),
            length=cache["length"].at[slot].set(true_len),
        )
        logits = self._head(lnf, head, x[true_len - 1])
        return cache, logits

    def prefill(self, params, cache, slot: int, tokens, rid=None):
        """Host entry: pad ``tokens`` (list/array of ints) to its bucket
        and run the compiled prefill.  Returns (cache, logits (V,)).
        ``rid`` (request id) rides the span args only — request-trace
        routing, zero effect on the compiled dispatch."""
        toks = np.asarray(tokens, dtype=np.int32).reshape(-1)
        n = int(toks.size)
        if n < 1:
            raise ValueError("cannot prefill an empty prompt")
        b = self.pick_bucket(n)
        padded = np.zeros((b,), np.int32)
        padded[:n] = toks
        _PREFILLS.inc(bucket=str(b))
        extra = {"rid": rid} if rid is not None else {}
        with obs.span("prefill_dispatch", bucket=b, true_len=n, **extra):
            return self._prefill_jit(
                params, cache, host_input(padded),
                jnp.int32(slot), jnp.int32(n),
            )

    # ------------------------------------------------------------------
    # decode: one token for every slot
    # ------------------------------------------------------------------
    def _decode_fn(self, params, cache, tokens, active):
        """tokens (S,) int32 — the token ENTERING each slot; active (S,)
        bool.  Writes each slot's K/V at its current ``length`` row,
        advances active slots' lengths, and returns logits (S, V) for
        the written tokens.  Inactive slots compute garbage that is
        never read (their length does not advance, so the row is
        overwritten by the slot's next real token)."""
        self._n_decode_traces += 1  # runs at trace time only
        emb, pos, blocks, lnf, head = self._weights(params)
        s_ = self.n_slots
        h = self.n_heads
        hd = self.head_dim
        pos_idx = cache["length"]  # (S,) position of the incoming token
        x = self._embed(emb, pos, tokens, pos_idx)  # (S, D)
        t = self.max_len
        # row t is valid iff row <= pos (the new token attends to itself)
        att_mask = jnp.arange(t)[None, :] <= pos_idx[:, None]  # (S, T)

        def write(cache_l, new):  # (S,T,H,hd), (S,H,hd) at per-slot pos
            return jax.vmap(
                lambda c, u, p: lax.dynamic_update_slice_in_dim(
                    c, u[None], p, axis=0
                )
            )(cache_l, new, pos_idx)

        k_cache, v_cache = cache["k"], cache["v"]
        dt = k_cache.dtype
        new_k, new_v = [], []
        for i, bp in enumerate(blocks):
            y = self._ln(bp["ln1"], x)
            q = self._proj(y, bp["attn"]["wq"]).reshape(s_, h, hd)
            k = self._proj(y, bp["attn"]["wk"]).reshape(s_, h, hd)
            v = self._proj(y, bp["attn"]["wv"]).reshape(s_, h, hd)
            kc = write(k_cache[i], k.astype(dt))  # (S, T, H, hd)
            vc = write(v_cache[i], v.astype(dt))
            new_k.append(kc)
            new_v.append(vc)
            s = jnp.einsum(
                "shd,sthd->sht", q, kc, preferred_element_type=jnp.float32
            ) * self.scale
            s = jnp.where(att_mask[:, None, :], s, _NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(
                "sht,sthd->shd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            ).astype(y.dtype)
            x = x + self._proj(o.reshape(s_, h * hd), bp["attn"]["wo"])
            x = x + self._mlp(bp, self._ln(bp["ln2"], x))
        cache = dict(
            cache,
            k=jnp.stack(new_k),
            v=jnp.stack(new_v),
            length=pos_idx + active.astype(jnp.int32),
        )
        return cache, self._head(lnf, head, x)

    def decode_step(self, params, cache, tokens, active):
        """One decode tick for all slots. ``tokens``/``active`` are
        host arrays (S,) — see ``_decode_fn``."""
        return self._decode_jit(
            params, cache,
            host_input(tokens, jnp.int32),
            host_input(active, bool),
        )

    # ------------------------------------------------------------------
    # convenience: single-sequence greedy decode (tests / smoke)
    # ------------------------------------------------------------------
    def greedy(self, prompt, n_new: int, params=None) -> List[int]:
        """Greedy-decode ``n_new`` tokens after ``prompt`` on slot 0.
        The scheduler is the real serving path; this is the minimal
        parity/smoke surface."""
        params = params if params is not None else self.model.params
        cache = self.init_cache()
        cache, logits = self.prefill(params, cache, 0, prompt)
        out = [int(jnp.argmax(logits))]
        tokens = np.zeros((self.n_slots,), np.int32)
        active = np.zeros((self.n_slots,), bool)
        active[0] = True
        for _ in range(n_new - 1):
            tokens[0] = out[-1]
            cache, logits = self.decode_step(params, cache, tokens, active)
            out.append(int(jnp.argmax(logits[0])))
        return out
