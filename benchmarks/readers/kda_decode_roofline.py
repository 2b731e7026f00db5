"""The recurrent decode kernel's share of its memory roofline over the
traced stretch, in percent.

Work, counted from the tokens and not from the kernel: every token a
decoding lane produces reads and writes that lane's recurrent matrices
once a linear layer.  Over the ``decode_step`` boundary spans that start
inside ``facts["traced"]``: ``tokens_routed`` (the lanes that decoded) x
``flops.kda_state_bytes`` x 2.  The step's queries, keys, values and
decays, the outputs and an idle lane's state (which the kernel moves
too) count nothing, so the share cannot pass 100.  Bound by memory: six
operations an element of four bytes read and four written.

Least time: those bytes over ``hbm_bytes_per_s``.  Time: the summed
device time of the operations named ``args["kernel"]*``.  No such
operation, no such span (a parent commit, a model without recurrent
layers): ``None``."""

import span_read
import traced_calls


def read(ctx):
    traced = ctx.facts.get("traced")
    if ctx.trace is None or not traced or None in traced:
        return None
    seconds = traced_calls.kernel_seconds(ctx.trace, ctx.args["kernel"])
    on, off = traced
    tokens = sum(int(s["args"]["tokens_routed"])
                 for s in span_read.window(ctx.facts)
                 if s["name"] == "decode_step" and on <= s["start"] < off
                 and "tokens_routed" in s["args"])
    if not seconds or not tokens:
        return None
    least = (2 * tokens * ctx.flops.kda_state_bytes(ctx.config)
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
