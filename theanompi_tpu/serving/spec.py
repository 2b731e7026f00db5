"""Speculative decoding: a draft model proposes, the target verifies.

Plain continuous-batching decode pays ONE full-target-model dispatch
per generated token — the hot-path cost the ISSUE-11 tentpole attacks.
Speculative decoding restructures it: a small **draft** model (same
``TransformerLM`` family, typically ``models.transformer.make_draft``'s
truncated self-draft) greedily proposes up to ``k`` tokens per round,
and the target scores ALL of them — plus the bonus token that follows a
fully-accepted run — in ONE batched multi-token dispatch
(``PagedServingEngine.verify_chunks``, the chunked-prefill machinery
with logits at every chunk position).  A round emits between 1 and
``k + 1`` tokens for one target dispatch; the speedup is the acceptance
rate times the draft/target cost ratio.

**Token identity** (the correctness contract, pinned in
tests/test_serving_spec.py): the verify logits at chunk position ``j``
condition on exactly the tokens a non-speculative decode would have
emitted — the acceptance loop only *uses* position ``j`` when every
earlier proposal matched the target's own pick.  Greedy requests
therefore produce bit-identical streams with speculation on or off, and
sampling requests do too, because every pick draws with the request's
own ``(seed, token_index)`` key (``Sampler.pick_batch`` semantics) —
speculation changes how many picks happen per dispatch, never what any
pick sees.

**Rollback is host-side data.**  The verify dispatch writes K/V for all
``k`` proposals; when the target rejects a tail, the garbage rows stay
in the pool and the per-slot *length* simply does not advance past the
accepted prefix — masked out of every later attention, overwritten when
the real tokens arrive.  Lengths and tables are data to the jitted
programs, so acceptance-length churn (0 … k per lane per round)
recompiles NOTHING: one verify program, one draft decode program, ever.

**Budget clamp.**  A lane about to finish proposes fewer tokens
(``k_eff = min(k, remaining - 1)``): rows past the request's block
allocation must never be written as real (they would alias the trash
block into attended positions).  ``k_eff`` varies per lane per round —
it enters the dispatch as the ``true_len`` DATA vector, never as a
shape (the recompile discipline graftlint's GL-J005 rule now enforces
on decode paths).

The draft runs its own paged world (pool, tables, lengths) mirrored by
this module: admission prefills the prompt into the draft cache once,
rejection rolls the draft length back beside the target's, and an
all-accepted round leaves ONE catch-up token (the last proposal, whose
K/V the draft never computed) to force-feed next round.
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from theanompi_tpu import observability as obs
from theanompi_tpu.serving import metrics as smetrics


class SpecDecoder:
    """Draft-side state and the propose/commit halves of a spec round.

    One per scheduler (like ``BlockPool``): owns the draft engine's
    allocator, block tables, lengths and catch-up queues for every
    target slot.  The scheduler drives ``ensure_slot`` on admission,
    ``propose`` + ``commit`` per round, ``release_slot`` on finish.
    """

    def __init__(self, engine, draft_engine, k: int, draft_params=None):
        if int(k) < 1:
            raise ValueError(
                f"spec k must be >= 1 (got {k}); spec_k=0 on the "
                "scheduler disables speculation instead"
            )
        if draft_engine.vocab_size != engine.vocab_size:
            raise ValueError(
                f"draft vocab {draft_engine.vocab_size} != target vocab "
                f"{engine.vocab_size} — proposals would be meaningless"
            )
        if draft_engine.n_slots != engine.n_slots:
            raise ValueError(
                f"draft n_slots {draft_engine.n_slots} != target "
                f"{engine.n_slots} — the draft mirrors every target lane"
            )
        if draft_engine.max_len < engine.max_len:
            raise ValueError(
                f"draft max_len {draft_engine.max_len} < target "
                f"{engine.max_len} — the draft must hold every sequence "
                "the target can"
            )
        self.engine = engine
        self.draft = draft_engine
        self.k = int(k)
        # the draft's serving tree (paging.serving_params), this
        # decoder's own
        self.draft_params = draft_engine.serving_params(
            draft_params if draft_params is not None
            else draft_engine.model.params
        )
        self.pool = draft_engine.make_pool()
        self.state = draft_engine.init_state()
        n = engine.n_slots
        self._tables = np.zeros((n, draft_engine.blocks_per_seq), np.int32)
        self._lengths = np.zeros((n,), np.int32)
        self._blocks: List[List[int]] = [[] for _ in range(n)]
        # tokens resident on the target but not yet in the draft cache
        # (the all-accepted case leaves exactly one per round)
        self._pending: List[List[int]] = [[] for _ in range(n)]
        self.stats = {
            "rounds": 0,
            "draft_prefill_chunks": 0,
            "draft_dispatches": 0,
            "verify_dispatches": 0,
            "proposed": 0,
            "accepted": 0,
            "emitted": 0,
        }

    # ------------------------------------------------------------------
    # slot lifecycle (mirrors the target scheduler's)
    # ------------------------------------------------------------------
    def ensure_slot(self, i: int, prompt, max_new: int, rid=None) -> None:
        """Mirror-admit target slot ``i``: allocate draft blocks and
        prefill the whole prompt into the draft cache (chunked through
        the draft's own bucket ladder).  Idempotent.  ``rid`` labels the
        draft-prefill span with the owning stream (trace-only)."""
        if self._blocks[i]:
            return
        need = self.draft.max_seq_blocks(len(prompt) + max_new)
        blocks = self.pool.alloc(need)
        if blocks is None:
            # the default draft pool is sized for n_slots worst-case
            # sequences, so this is a geometry bug, not a load condition
            raise RuntimeError(
                "draft block pool exhausted — build the draft engine "
                "with n_blocks >= n_slots * blocks_per_seq + 1"
            )
        self._blocks[i] = blocks
        self._tables[i, :] = 0
        self._tables[i, :len(blocks)] = blocks
        cap = self.draft.chunk_buckets[-1]
        p0 = 0
        extra = {"rid": rid} if rid is not None else {}
        with obs.span(
            "spec_draft_prefill", slot=i, n_prompt=len(prompt), **extra
        ):
            while p0 < len(prompt):
                chunk = list(prompt[p0:p0 + cap])
                self.state, _ = self.draft.prefill_chunks(
                    self.draft_params, self.state,
                    [{"tokens": chunk, "p0": p0, "table": blocks}],
                )
                self.stats["draft_prefill_chunks"] += 1
                p0 += len(chunk)
        self._lengths[i] = len(prompt)
        self._pending[i] = []

    def release_slot(self, i: int) -> None:
        if self._blocks[i]:
            self.pool.release_all(self._blocks[i])
        self._blocks[i] = []
        self._tables[i, :] = 0
        self._lengths[i] = 0
        self._pending[i] = []

    # ------------------------------------------------------------------
    # one round: propose, then (after the target verifies) commit
    # ------------------------------------------------------------------
    def propose(self, lanes, last_tokens, k_eff) -> np.ndarray:
        """Greedy draft proposals for every lane where ``lanes`` is
        True: up to ``k_eff[i]`` tokens continuing lane i after
        ``last_tokens[i]``.  Catch-up tokens (``_pending``) are
        force-fed first, so the draft cache is position-exact before
        the first proposal.  All lanes advance together — one batched
        draft dispatch per tick, ``max(pending + k_eff)`` ticks per
        round.  Returns ``props`` (n, k) int32 (rows valid to
        ``k_eff[i]``)."""
        n = self.engine.n_slots
        props = np.zeros((n, self.k), np.int32)
        feeds: List[List[int]] = []
        for i in range(n):
            if lanes[i]:
                f = list(self._pending[i])
                if k_eff[i] > 0:
                    f.append(int(last_tokens[i]))
                feeds.append(f)
            else:
                feeds.append([])
        n_pend = [len(self._pending[i]) if lanes[i] else 0
                  for i in range(n)]
        ticks = [n_pend[i] + int(k_eff[i]) if lanes[i] else 0
                 for i in range(n)]
        total = max(ticks) if ticks else 0
        cur = np.zeros((n,), np.int32)
        tok = np.zeros((n,), np.int32)
        for t in range(total):
            act = np.array([t < ticks[i] for i in range(n)], bool)
            for i in range(n):
                if act[i]:
                    tok[i] = feeds[i][t] if t < len(feeds[i]) else cur[i]
            with obs.span("spec_draft_step", active=int(act.sum())):
                self.state, logits = self.draft.decode_step_paged(
                    self.draft_params, self.state, tok,
                    self._tables, self._lengths, act,
                )
            self._lengths[act] += 1
            self.stats["draft_dispatches"] += 1
            smetrics.SPEC_DRAFT_DISPATCHES.inc()
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            for i in range(n):
                if act[i] and t >= n_pend[i]:
                    props[i, t - n_pend[i]] = int(nxt[i])
                    cur[i] = int(nxt[i])
        for i in range(n):
            if lanes[i]:
                self._pending[i] = []
        return props

    def commit(self, i: int, a: int, k_eff_i: int, props_row, t0: int,
               p0_i: int) -> None:
        """Reconcile the draft cache with the target's verdict for lane
        ``i``: ``a`` proposals accepted out of ``k_eff_i``.

        Rejection (``a < k_eff_i``) rolls the draft length back to the
        accepted prefix — pure host-side data, the rejected rows are
        masked garbage until overwritten.  Full acceptance leaves the
        final proposal's K/V missing from the draft (it was never fed),
        so it queues as next round's catch-up feed."""
        if a < k_eff_i:
            self._lengths[i] = p0_i + a + 1
            self._pending[i] = []
        else:
            self._lengths[i] = p0_i + a
            self._pending[i] = [int(props_row[a - 1]) if a > 0 else int(t0)]

    def note_lane(self, proposed: int, accepted: int, emitted: int) -> None:
        """Per-lane accounting within one round (``rounds`` itself is
        counted once per verify tick by the scheduler)."""
        self.stats["proposed"] += proposed
        self.stats["accepted"] += accepted
        self.stats["emitted"] += emitted
        smetrics.SPEC_PROPOSED.inc(proposed)
        smetrics.SPEC_ACCEPTED.inc(accepted)

    def summary(self) -> dict:
        s = dict(self.stats)
        s["accept_rate"] = (
            round(s["accepted"] / s["proposed"], 4) if s["proposed"] else 0.0
        )
        s["tokens_per_round"] = (
            round(s["emitted"] / s["rounds"], 4) if s["rounds"] else 0.0
        )
        s["k"] = self.k
        return s
