"""Median wall time, in milliseconds, of the ticks of one class (see
``tick_share``).  A median is right here: a per-layer statistic, not an
end-to-end one."""

import statistics


def read(ctx):
    ticks = ctx.facts.get("ticks")
    if not ticks:
        return None
    want = bool(ctx.args["prefill"])
    mine = [t[1] for t in ticks if bool(t[3]) == want and t[2] > 0]
    if not mine:
        return None
    return 1e3 * statistics.median(mine)
