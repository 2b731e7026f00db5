"""A percentile (``args["percentile"]``, 50 the median) of a list the
driver's own clock filled over the window."""

import statistics


def read(ctx):
    values = ctx.facts.get(ctx.args["fact"])
    if not values or len(values) < 2:
        return None
    q = float(ctx.args["percentile"])
    if q == 50:
        at = statistics.median(values)
    else:
        # numpy's default (linear) percentile, as the drivers use
        v, pos = sorted(values), (len(values) - 1) * q / 100.0
        lo = int(pos)
        at = v[lo] + (v[min(lo + 1, len(v) - 1)] - v[lo]) * (pos - lo)
    return at * float(ctx.args.get("scale", 1.0))
