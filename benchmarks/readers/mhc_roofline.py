"""The hyper-connection kernels' share of their memory roofline over the
traced stretch, in percent.

Work: the useful tokens of the traced program calls (``tokens_routed``
of ``traced_calls``: a decode call's active lanes, a prefill call's real
tokens) x what the two kernels have to move for a token through every
sublayer (the state of ``hc_mult`` streams read twice and written once,
the sublayer's input written and its output read:
``flops.hyper_bytes_per_token``).  Padding rows, the coefficients and
``Phi`` count nothing.  Bound by memory: 24 multiply-adds an element of
the state.

Least time: those bytes over ``hbm_bytes_per_s``.  Time: the summed
device time of the operations named ``args["kernels"]*``.  No such
operation, or no call with counters: ``None``."""

import traced_calls


def read(ctx):
    if ctx.trace is None:
        return None
    calls = traced_calls.calls(ctx.facts)
    seconds = traced_calls.kernel_seconds(ctx.trace, *ctx.args["kernels"])
    if not calls or not seconds:
        return None
    tokens = sum(int(c["tokens_routed"]) for c in calls)
    least = (tokens * ctx.flops.hyper_bytes_per_token(ctx.config)
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
