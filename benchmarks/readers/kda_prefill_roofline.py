"""The chunked recurrence's share of its roofline over the traced
prefill calls, in percent.

Work of one traced ``prefill_chunk_dispatch`` call, from its span: the
larger of its operations over ``bf16_flops_per_s`` (``tokens_routed``
useful tokens x ``flops.state_flops_per_token``: decay, delta and read
of the state, the recurrence's own work whatever the chunked form adds
inside a chunk) and its bytes over ``hbm_bytes_per_s`` (``tokens_routed``
x ``flops.kda_token_bytes``: a token's ``q, k, v, g`` read and ``o``
written a layer; plus, for each of the call's ``rows``, one read and one
write of a lane's state, ``flops.kda_state_bytes``).  Padding counts
nothing: the share cannot pass 100.

Time: the summed device time of the operations named
``args["kernel"]*`` (the scan over chunks; the triangular inverses before
it are XLA's and outside it).  No such operation, or no such span:
``None``."""

import span_read
import traced_calls


def read(ctx):
    traced = ctx.facts.get("traced")
    if ctx.trace is None or not traced or None in traced:
        return None
    seconds = traced_calls.kernel_seconds(ctx.trace, ctx.args["kernel"])
    on, off = traced
    calls = [s["args"] for s in span_read.window(ctx.facts)
             if s["name"] == "prefill_chunk_dispatch"
             and on <= s["start"] < off and "tokens_routed" in s["args"]]
    if not seconds or not calls:
        return None
    flops = ctx.flops.state_flops_per_token(ctx.config)
    token = ctx.flops.kda_token_bytes(ctx.config)
    state = ctx.flops.kda_state_bytes(ctx.config)
    least = sum(
        max(int(c["tokens_routed"]) * flops / ctx.peaks["bf16_flops_per_s"],
            (int(c["tokens_routed"]) * token + 2 * int(c["rows"]) * state)
            / ctx.peaks["hbm_bytes_per_s"])
        for c in calls)
    return 100.0 * least / seconds
