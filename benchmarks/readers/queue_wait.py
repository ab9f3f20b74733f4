"""Engine admit time minus enqueue time, for requests enqueued in the window."""

from benchmarks.estimators import percentile


def read(ctx, q=50):
    waits = [(r["prefill_start_t"] - r["submit_t"]) * 1e3 for r in ctx.probe.results
             if ctx.in_window(r["submit_t"]) and r["prefill_start_t"] is not None]
    return percentile(waits, q) if waits else None
