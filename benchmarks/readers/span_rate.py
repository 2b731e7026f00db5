"""Time in the window's spans named ``args["span"]``, in milliseconds a
second of the window (``facts["window_s"]``: the profiler's own pauses
apart).  None where the program records no boundary span of that name
(a parent commit): a window without one there is not a window at 0."""

import span_read


def recorded(name) -> bool:
    try:
        from theanompi_tpu import observability as obs

        return name in obs.BOUNDARY_SPANS
    except (ImportError, AttributeError):
        return False


def ms_per_s(spans, name, window_s):
    if not window_s:
        return None
    return 1e3 * sum(map(span_read.duration, span_read.named(spans, name))) / window_s


def read(ctx):
    name = ctx.args["span"]
    if not recorded(name):
        return None
    return ms_per_s(span_read.window(ctx.facts), name, ctx.facts.get("window_s"))
