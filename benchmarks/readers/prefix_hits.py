"""Prompt tokens served from the prefix cache over prompt tokens, for the
requests admitted in the window."""


def read(ctx):
    rs = [r for r in ctx.probe.results if ctx.in_window(r["prefill_start_t"])]
    total = sum(r["prompt_tokens"] for r in rs)
    return 100.0 * sum(r["cached_tokens"] for r in rs) / total if total else None
