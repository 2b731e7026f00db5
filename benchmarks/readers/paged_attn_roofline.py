"""The paged decode kernel's share of its memory roofline over the
traced stretch, in percent.

Work, counted from the tokens and not from the kernel: the keys and
values resident in the lanes that decode, which the kernel has to read
once per layer each tick.  Over the ticks that start inside
``facts["traced"]`` (the driver's ticks are (start, seconds, tokens, was
a prefill tick, resident tokens)): resident x 2 (keys and values) x
layers x heads x head size x the pool's bytes per element, from the
configuration.  Useful bytes only: blocks fetched past a lane's length,
the queries and the outputs count nothing, so the share cannot pass 100.

Least time: those bytes over the chip's ``hbm_bytes_per_s``.  Time: the
summed device time of the operations whose short name starts with
``args["kernel"]``, the ``name=`` of the kernel's ``pallas_call``.  No
such operation (the XLA gather serves ``paged_attn``, or a program
whose kernel has no name): ``None``."""

import trace_reduce

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def kv_bytes_per_token(config: dict) -> int:
    """Bytes of keys and values one resident token holds over all the
    layers, in the pool's own element type."""
    if config.get("engine", {}).get("kv_dtype", "fp32") == "int8":
        # one byte a value and a float32 scale a (row, head)
        per_layer = int(config["n_embd"]) + 4 * int(config["n_head"])
    else:
        per_layer = int(config["n_embd"]) * ITEMSIZE[config["compute_dtype"]]
    return 2 * int(config["n_layer"]) * per_layer


def kernel_seconds(trace, kernel: str) -> float:
    return sum(v for k, v in trace.op_totals().items() if k.startswith(kernel))


def read(ctx):
    ticks, traced = ctx.facts.get("ticks"), ctx.facts.get("traced")
    if ctx.trace is None or not ticks or not traced or traced[0] is None:
        return None
    seconds = kernel_seconds(ctx.trace, ctx.args["kernel"])
    if not seconds:
        return None
    on, off = traced
    resident = sum(t[4] for t in ticks if on <= t[0] < off)
    least = resident * kv_bytes_per_token(ctx.config) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
