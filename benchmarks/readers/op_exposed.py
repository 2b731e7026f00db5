"""Share of the traced window in which an operation matching ``pattern``
(against its short name, ``all-reduce.3`` and not the whole HLO line, so
that an operation which only consumes a match does not count) runs on a
device and no other operation of ``XLA Ops`` does, worst device.  Matching operations are looked for on ``XLA Ops`` and on
``Async XLA Ops`` (a collective between its start and its done).
Finds nothing, and returns nothing, where no operation matches."""

import re


def read(ctx):
    if ctx.trace is None:
        return None
    from trace_reduce import short_name, subtract

    rx = re.compile(ctx.args["pattern"])
    worst = None
    for dev in ctx.trace.devices:
        mine = [(s, e) for n, s, e in dev.ops + dev.async_ops if rx.search(short_name(n))]
        if not mine:
            continue
        others = [(s, e) for n, s, e in dev.ops if not rx.search(short_name(n))]
        share = 100.0 * subtract(mine, others) / 1e9 / dev.window_s
        worst = share if worst is None else max(worst, share)
    return worst
