"""A percentile of the time between two stamps of the request record, over
the requests received in the window.  The record is written inside the
serving path (``GenerationResult.timings``: recv_t, enqueue_t, submit_t,
prefill_start_t, prefill_end_t, first_token_t, first_emit_t) and kept, for
every finished request, in ``AsyncEngine.request_ring``, which a reader
reaches through the program's per-replica profiler registry.  A program
without the ring (any commit before PR 24) reads as None."""

from benchmarks.estimators import percentile


def records(ctx) -> list:
    """The window's finished requests, or [] where the program keeps none."""
    from githubrepostorag_tpu.obs.continuous import profilers

    out = []
    for prof in profilers().values():
        for rec in list(getattr(prof, "request_ring", None) or ()):
            t = rec.get("timings") or {}
            if ctx.in_window(t.get("recv_t")):
                out.append(rec)
    return out


def read(ctx, a, b, q=50):
    spans = [(r["timings"].get(a), r["timings"].get(b)) for r in records(ctx)]
    vals = [(t1 - t0) * 1e3 for t0, t1 in spans if t0 is not None and t1 is not None]
    return percentile(vals, q) if vals else None
