"""The grouped expert products' share of their roofline over the traced
stretch, in percent.

Work of one traced program call, from the counters the program returned
with it (``traced_calls``): the larger of its operations over the chip's
``bf16_flops_per_s`` (``tokens_routed`` useful tokens x the routed
experts' operations a token through every expert layer) and its bytes
over ``hbm_bytes_per_s`` (``experts_hit``, the distinct experts that
received a token, summed over the expert layers, x one expert's three
matrices).  A decode call is bound by the bytes, a prefill call by the
operations.  Routed experts only (the shared one is a dense product
outside the kernel); padding rows count nothing; an expert's matrices
count once a call however many tiles re-read them: the share cannot pass
100.

Time: the summed device time of the operations named
``args["kernel"]*``.  No such operation, or no call with counters:
``None``."""

import traced_calls


def read(ctx):
    if ctx.trace is None:
        return None
    calls = traced_calls.calls(ctx.facts)
    seconds = traced_calls.kernel_seconds(ctx.trace, ctx.args["kernel"])
    if not calls or not seconds:
        return None
    flops = ctx.flops.expert_flops_per_token(ctx.config)
    nbytes = ctx.flops.expert_bytes(ctx.config)
    least = sum(
        max(int(c["tokens_routed"]) * flops / ctx.peaks["bf16_flops_per_s"],
            int(c["experts_hit"]) * nbytes / ctx.peaks["hbm_bytes_per_s"])
        for c in calls)
    return 100.0 * least / seconds
