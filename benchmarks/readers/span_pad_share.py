"""Share of the tokens a span's calls computed that were padding, in
percent: 100 x (1 - sum of ``args["useful"]`` over sum of
``args["computed"]``) over the window's spans named ``args["span"]``;
both are counts the program attached to the span at the boundary."""

import span_read


def share(spans, name, useful, computed):
    mine = span_read.named(spans, name)
    total = sum(int(s["args"].get(computed, 0)) for s in mine)
    if not total:
        return None
    return 100.0 * (1.0 - sum(int(s["args"].get(useful, 0)) for s in mine) / total)


def read(ctx):
    a = ctx.args
    return share(span_read.window(ctx.facts), a["span"], a["useful"], a["computed"])
