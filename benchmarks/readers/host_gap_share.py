"""Share, in percent, of the traced stretch in which the device had
nothing queued and the host had not yet enqueued the next program.

The serving loop is synchronous: once a ``device_wait`` span with
``drains`` true ends, no program is left on the device until the host's
next dispatch span (``decode_step``, ``prefill_chunk_dispatch``,
``spec_verify``) ends, having enqueued one.  Over the ``tick`` spans
that start inside ``facts["traced"]``: the sum of those intervals over
the first such tick's start to the last one's end.  It closes against
``serve_device_idle_pct`` of the same stretch; what it leaves out is
the device's side of a hand-over (the launch's latency, a transfer in
flight).  A program without ``device_wait`` spans (a parent commit)
reads nothing."""

import span_read

DISPATCHES = ("decode_step", "prefill_chunk_dispatch", "spec_verify")


def share(spans, on, off):
    ticks = [s for s in span_read.named(spans, "tick") if on <= s["start"] < off]
    if not ticks:
        return None
    lo, hi = min(t["start"] for t in ticks), max(t["end"] for t in ticks)
    inside = sorted((s for s in spans if lo <= s["start"] < hi),
                    key=lambda s: s["start"])
    if not span_read.named(inside, "device_wait"):
        return None
    gap, drained = 0.0, None
    for s in inside:
        if s["name"] == "device_wait" and s["args"].get("drains"):
            drained = s["end"] if drained is None else drained
        elif s["name"] in DISPATCHES and drained is not None:
            gap += s["end"] - drained
            drained = None
    return 100.0 * gap / (hi - lo)


def read(ctx):
    traced = ctx.facts.get("traced")
    if not traced or traced[0] is None or traced[1] is None:
        return None
    return share(span_read.window(ctx.facts), *traced)
