"""Live rows per decode-burst dispatch, over the window."""


def read(ctx):
    rows = [n for t, n, _ in ctx.probe.bursts if ctx.in_window(t)]
    return sum(rows) / len(rows) if rows else None
