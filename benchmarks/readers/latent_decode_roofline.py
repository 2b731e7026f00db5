"""The latent decode kernel's share of its memory roofline over the
traced stretch, in percent.

Work, counted from the tokens and not from the kernel: the latent rows
resident in the lanes that decode, which the kernel has to read once a
layer each tick, shared by all the heads.  Over the ticks that start
inside ``facts["traced"]`` (the driver's ticks are (start, seconds,
tokens, was a prefill tick, resident tokens)): resident x
(``kv_lora_rank`` + ``qk_rope_head_dim``) x bytes x layers, from the
configuration through its ``flops`` file.  Useful bytes only: the lanes
of a stored row past those numbers, blocks fetched past a lane's length,
the queries and the outputs count nothing, so the share cannot pass 100.
Bound by memory: absorbed, a row's 1,152 bytes meet 2 x 32 x (576 + 512)
operations, 60 a byte against the chip's ridge of 240.

Least time: those bytes over the chip's ``hbm_bytes_per_s``.  Time: the
summed device time of the operations named ``args["kernel"]*``.  No such
operation: ``None``."""

import traced_calls


def read(ctx):
    ticks, traced = ctx.facts.get("ticks"), ctx.facts.get("traced")
    if ctx.trace is None or not ticks or not traced or traced[0] is None:
        return None
    seconds = traced_calls.kernel_seconds(ctx.trace, ctx.args["kernel"])
    if not seconds:
        return None
    on, off = traced
    resident = sum(t[4] for t in ticks if on <= t[0] < off)
    least = (resident * ctx.flops.latent_row_bytes(ctx.config)
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
