"""How late the load generator ran: sent minus due, over the window."""

from benchmarks.estimators import percentile


def read(ctx, q=95):
    lags = [(r["sent_t"] - r["due_t"]) * 1e3 for r in ctx.window_records()]
    return percentile(lags, q) if lags else None
