"""Bytes the decode steps of the traced span must read (weights once a step,
the live context's K and V) over device time, over the HBM peak.  With
``part="attention"``: the K/V bytes alone over the attention call's time."""

from benchmarks import shapes
from benchmarks.trace import module_seconds, op_seconds


def read(ctx, part="all", module="decode_burst", op=None):
    if ctx.trace is None or ctx.peaks is None:
        return None
    wbytes = {"int8": 1.0, "bfloat16": 2.0}[ctx.config["weights"]["dtype"]]
    total = attn = 0.0
    for t, rows, kv in ctx.probe.bursts:
        if ctx.in_trace(t):
            a, b = shapes.burst_bytes(ctx.model, wbytes, rows, kv, ctx.decode_burst)
            total, attn = total + a, attn + b
    if part == "attention":
        seconds, nbytes = (op_seconds(ctx.trace, op) if op else 0.0), attn
    else:
        seconds, nbytes = module_seconds(ctx.trace, module), total
    if not nbytes or not seconds:
        return None
    return 100.0 * nbytes / (seconds * ctx.peaks["hbm_bytes_per_s"] * ctx.chips)
