"""FLOPs of the real prompt tokens prefilled in the traced span, over the
device time of the prefill programs, over the chip's bf16 peak (int8 weights
are dequantised into bf16 products, so bf16 is the peak that binds)."""

from benchmarks import shapes
from benchmarks.trace import module_seconds


def read(ctx, module="forward_paged", peak="bf16_flops"):
    if ctx.trace is None or ctx.peaks is None:
        return None
    flops = 0.0
    for t, rows in ctx.probe.prefills:
        if ctx.in_trace(t):
            new = sum(n for _, n, _ in rows)
            pairs = sum(shapes.causal_pairs(c, n) for c, n, _ in rows)
            flops += shapes.prefill_flops(ctx.model, new, pairs, sum(1 for *_, d in rows if d))
    seconds = module_seconds(ctx.trace, module)
    if not flops or not seconds:
        return None
    return 100.0 * flops / (seconds * ctx.peaks[peak] * ctx.chips)
