"""Median duration, in milliseconds, of the window's spans named
``args["span"]``.  With ``args["parent"]`` only those whose parent has
that name, and with ``args["parent_without"]`` only where that parent
has no child of that name (a ``pick`` directly under a ``tick`` that
ran no ``prefill``: the decode-only ticks)."""

import span_read


def median_ms(spans, name, parent=None, parent_without=None):
    by_id = {s["id"]: s for s in spans}
    kids = span_read.child_names(spans)
    mine = []
    for s in span_read.named(spans, name):
        if parent is not None:
            p = by_id.get(s["parent"])
            if p is None or p["name"] != parent:
                continue
            if parent_without is not None and parent_without in kids[p["id"]]:
                continue
        mine.append(span_read.duration(s))
    return span_read.median_ms(mine)


def read(ctx):
    a = ctx.args
    return median_ms(span_read.window(ctx.facts), a["span"],
                     a.get("parent"), a.get("parent_without"))
