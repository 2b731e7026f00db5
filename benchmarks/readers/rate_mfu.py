"""A whole step's share of the chip's peak: a rate the driver measured
on the chip (samples or tokens per second) x the operations one of them
needs, from the configuration's ``flops`` file, over chips x peak."""


def read(ctx):
    rate = ctx.facts.get(ctx.args["rate_fact"])
    if not rate:
        return None
    per_item = getattr(ctx.flops, ctx.args["flops_fn"])(ctx.config)
    return 100.0 * rate * per_item / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
