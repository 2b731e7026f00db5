"""The program calls of the traced stretch, for the readers that set a
kernel's device time against the work its calls had to do.

A latent model's decode and prefill programs return their expert
layers' counters, and the scheduler completes the call's boundary span
with them (``decode_step`` and ``prefill_chunk_dispatch``: ``experts_hit``,
``expert_load_max``, ``tokens_routed``).  The driver starts and stops the
profiler between ticks, with the device drained by the tick's last pick,
so the calls whose spans start inside ``facts["traced"]`` are the calls
whose kernels the device trace holds, whole.

A program without such counters (a parent commit, a model without
experts) has no such span: ``calls`` is then empty and the readers
return ``None``.
"""

from __future__ import annotations

import span_read

SPANS = ("decode_step", "prefill_chunk_dispatch")


def calls(facts: dict, spans=None) -> list:
    """``[args]`` of the traced calls that carry counters, oldest first."""
    traced = facts.get("traced")
    if not traced or traced[0] is None or traced[1] is None:
        return []
    on, off = traced
    return [s["args"] for s in span_read.window(facts, spans)
            if s["name"] in SPANS and on <= s["start"] < off
            and "tokens_routed" in s["args"]]


def kernel_seconds(trace, *prefixes: str) -> float:
    """Summed device time of the operations whose short name starts with
    one of ``prefixes`` (a kernel's ``name=``)."""
    return sum(v for k, v in trace.op_totals().items() if k.startswith(prefixes))
