"""Median self time, in milliseconds, of the window's spans named
``args["span"]``: a span's duration less its children's, the time the
layer spent in its own code between the calls it timed."""

import span_read


def self_median_ms(spans, name):
    own = span_read.self_times(spans)
    return span_read.median_ms(own[s["id"]] for s in span_read.named(spans, name))


def read(ctx):
    return self_median_ms(span_read.window(ctx.facts), ctx.args["span"])
