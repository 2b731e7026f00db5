"""Median self time, in milliseconds, of the window's spans named
``args["span"]`` that have a child named ``args["child"]``: the layer's
own time where the program times that part of it apart.  A program that
does not (a parent commit) reads nothing, not the whole span."""

import span_read


def self_median_ms(spans, name, child):
    own = span_read.self_times(spans)
    kids = span_read.child_names(spans)
    return span_read.median_ms(own[s["id"]] for s in span_read.named(spans, name)
                               if child in kids.get(s["id"], ()))


def read(ctx):
    return self_median_ms(span_read.window(ctx.facts), ctx.args["span"],
                          ctx.args["child"])
