"""Idle share of the traced window on the worst device."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.idle_pct_worst()
