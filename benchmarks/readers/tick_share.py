"""Share of the window's wall time spent in ticks of one class.  The
driver's ``ticks`` are (start, seconds, tokens, was a prefill tick,
resident tokens); ``args["prefill"]`` picks the class."""


def read(ctx):
    ticks, window = ctx.facts.get("ticks"), ctx.facts.get("window_s")
    if not ticks or not window:
        return None
    want = bool(ctx.args["prefill"])
    return 100.0 * sum(t[1] for t in ticks if bool(t[3]) == want) / window
