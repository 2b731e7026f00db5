"""The grouped-query decode kernel's share of its memory roofline over
the traced stretch, in percent.

Work, counted from the scheduler's host lengths and not from the
kernel: the key and value rows each decode call has to read, which the
scheduler puts on the call's ``decode_step`` boundary span as
``kv_rows_full`` (the layers that attend to the whole context: ``length
+ 1`` rows a decoding lane) and ``kv_rows_window`` (the window layers:
``min(length + 1, W)``).  Over the spans that start inside
``facts["traced"]``: (``kv_rows_full`` + ``kv_rows_window``) x
``flops.kv_row_bytes`` (a row's keys and values, key/value heads x head
size x 2 sides x 2 B).  Blocks fetched past a lane's length or before
its window, the queries, the outputs and an idle lane's step count
nothing, so the share cannot pass 100.  Bound by memory: a row's 2,048
bytes meet 2 x 2 x 28 x 128 operations, 7 a byte against the chip's
ridge of 240.

Least time: those bytes over ``hbm_bytes_per_s``.  Time: the summed
device time of the operations named ``args["kernel"]*``.  No such
operation, no span with the counts (a parent commit): ``None``."""

import span_read
import traced_calls


def read(ctx):
    traced = ctx.facts.get("traced")
    if ctx.trace is None or not traced or None in traced:
        return None
    seconds = traced_calls.kernel_seconds(ctx.trace, ctx.args["kernel"])
    on, off = traced
    rows = sum(int(s["args"]["kv_rows_full"]) + int(s["args"]["kv_rows_window"])
               for s in span_read.window(ctx.facts)
               if s["name"] == "decode_step" and on <= s["start"] < off
               and "kv_rows_window" in s["args"])
    if not seconds or not rows:
        return None
    least = (rows * ctx.flops.kv_row_bytes(ctx.config)
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
