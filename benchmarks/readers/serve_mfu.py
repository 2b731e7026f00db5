"""The served model's whole-step share of the chip's peak over the
window: useful tokens only (prompt tokens fed and tokens decoded; padding
rows and padded positions count nothing) x the operations each needs,
from the configuration's ``flops`` file, over window x chips x peak."""


def read(ctx):
    f, window = ctx.facts, ctx.facts.get("window_s")
    if not window or "decode_tokens" not in f:
        return None
    flops = ctx.flops.forward_flops(
        ctx.config,
        tokens=f["prefill_tokens"] + f["decode_tokens"],
        attended=f["prefill_attended"] + f["decode_attended"],
    )
    return 100.0 * flops / (window * ctx.chips * ctx.peaks["bf16_flops_per_s"])
