"""Device time of the operations whose short name starts with one of
``args["prefixes"]`` over the device's busy time in the traced stretch,
in percent.  No such operation: ``None``."""

import traced_calls


def read(ctx):
    if ctx.trace is None or not ctx.trace.busy_s:
        return None
    seconds = traced_calls.kernel_seconds(ctx.trace, *ctx.args["prefixes"])
    if not seconds:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
