"""A percentile of time to first token over the window, client side."""

from benchmarks.estimators import percentile, ttft_ms


def read(ctx, q=90):
    vals = [v for v in (ttft_ms(r) for r in ctx.window_records()) if v is not None]
    return percentile(vals, q) if vals else None
