"""1 - union of device-op intervals over the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
