"""Time in the window's spans named ``args["part"]`` as a share, in
percent, of the time in those named ``args["whole"]``."""

import span_read


def share(spans, part, whole):
    total = sum(map(span_read.duration, span_read.named(spans, whole)))
    if not total:
        return None
    return 100.0 * sum(map(span_read.duration, span_read.named(spans, part))) / total


def read(ctx):
    return share(span_read.window(ctx.facts), ctx.args["part"], ctx.args["whole"])
