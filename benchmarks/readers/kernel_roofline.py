"""A kernel's share of its roofline: the seconds the roofline allows (the
larger of its bytes over the HBM peak and its operations over the bf16 peak)
over the seconds the kernel's ops took in the trace.  Bytes and operations
are the family's count (``ctx.family.work.<work>``) of each dispatch's own
stats, as the program wrote them on its annotation:

* ``dispatch="burst"``: ``engine.decode_burst``'s ``rows`` / ``kv_tokens`` /
  ``steps``, for the dispatches the trace matched to a module event;
* ``dispatch="prefill"``: ``engine.prefill_batch``'s ``pairs`` (query, key)
  and ``cached_tokens + new_tokens`` (the rows of cache the wave's rows walk).

The kernel's seconds are every op whose name matches ``op``.  None where the
program names no such op or the family has no count."""

from benchmarks.readers import host_phases
from benchmarks.trace import op_seconds


def dispatches(plain: dict, dispatch: str) -> list:
    if dispatch == "burst":
        return [(rows, kv, steps) for (_, rows, kv, steps), _ in host_phases.matched_bursts(plain)]
    return [(h[3]["pairs"], h[3]["cached_tokens"] + h[3]["new_tokens"])
            for h in plain["host"] if h[0] == "engine.prefill_batch" and "pairs" in h[3]]


def read(ctx, op, work, dispatch="burst"):
    count = getattr(ctx.family.work, work, None)
    plain = host_phases.phases_of(ctx)
    if count is None or plain is None or ctx.trace is None or ctx.peaks is None:
        return None
    seconds = op_seconds(ctx.trace, op)
    stats = dispatches(plain, dispatch)
    if not seconds or not stats:
        return None
    allowed = 0.0
    for args in stats:
        nbytes, flops = count(ctx.model, *args)
        allowed += max(nbytes / ctx.peaks["hbm_bytes_per_s"], flops / ctx.peaks["bf16_flops"])
    return 100.0 * allowed / (seconds * ctx.chips)
