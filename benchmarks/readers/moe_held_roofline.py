"""The grouped expert products' share of their roofline where the chip
holds a share of the experts, in percent.

As ``moe_expert_roofline``, but the operations are counted from
``pairs_routed``, the token-expert pairs the call computed (the picks of
useful tokens that fell on a held expert, summed over the expert
layers), and not from ``num_experts_per_tok`` picks a token, which a
share does not compute.  Per traced program call the larger of
``pairs_routed`` x ``flops.expert_pair_flops`` over ``bf16_flops_per_s``
and ``experts_hit`` x ``flops.expert_bytes`` over ``hbm_bytes_per_s``;
summed, over the summed device time of the operations named
``args["kernel"]*``.  Padding rows count nothing and an expert's
matrices count once a call: the share cannot pass 100.

No such operation, or no call with ``pairs_routed`` (a parent commit):
``None``."""

import traced_calls


def read(ctx):
    if ctx.trace is None:
        return None
    calls = [c for c in traced_calls.calls(ctx.facts) if "pairs_routed" in c]
    seconds = traced_calls.kernel_seconds(ctx.trace, ctx.args["kernel"])
    if not calls or not seconds:
        return None
    flops = ctx.flops.expert_pair_flops(ctx.config)
    nbytes = ctx.flops.expert_bytes(ctx.config)
    least = sum(
        max(int(c["pairs_routed"]) * flops / ctx.peaks["bf16_flops_per_s"],
            int(c["experts_hit"]) * nbytes / ctx.peaks["hbm_bytes_per_s"])
        for c in calls)
    return 100.0 * least / seconds
