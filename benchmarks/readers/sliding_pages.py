"""Readings of a model's SLIDING kind of page (serving/kv_cache.SlidingPages,
PR 50): the pool of the layers that attend a window.  The engine writes, on
every ``engine.decode_burst`` annotation, ``sliding_tokens`` (over the live
rows, the keys of a row inside a sliding layer's window) beside ``rows`` and
``steps``; on every ``engine.prefill_batch`` annotation ``sliding_pairs`` (the
wave's (query, key) pairs inside the window), ``sliding_keys`` (the keys its
rows walk there) and the cumulative ``page_hit_tokens`` (prompt tokens whose
pages the global kind held at admission), ``sliding_hit_tokens`` (of those,
the tokens served: as deep as the sliding kind held the window's pages too)
and ``sliding_pages_freed``.

* ``burst_roofline`` / ``wave_roofline``: the seconds the roofline allows the
  sliding layers' kernel (the family's ``work.<work>`` of each dispatch's own
  stats: the larger of its bytes over the HBM peak and its operations over the
  bf16 peak) over the seconds of the ops matching ``op``, percent;
* ``hit_share``: ``sliding_hit_tokens`` over ``page_hit_tokens`` between the
  trace's first and last wave, percent: what the sliding pool keeps of what
  the global pages offer;
* ``burst_hbm``: the bytes of the matched bursts (the family's
  ``work.burst_counted_bytes`` of each dispatch's ``rows``, ``kv_tokens``,
  ``sliding_tokens`` and ``steps``, the routed experts at the share of the
  offered slots that the engine counted hit between the trace's first and
  last burst: ``readers/moe_counters.deltas``) over the matched module
  events' seconds, over the HBM peak, percent.  No model of the router enters
  it, where the accepted ``burst_hbm_frac`` hands the family ``rows`` alone.

None where the program writes no such stats (any other model, any commit
before PR 50) or the family has no such count."""

from benchmarks.readers import host_phases, moe_counters
from benchmarks.trace import op_seconds


def bursts(plain: dict) -> list:
    """(rows, sliding_tokens, steps) of the bursts the trace matched to a
    module event."""
    stats = {h[1]: h[3] for h in plain["host"]
             if h[0] == "engine.decode_burst" and "sliding_tokens" in h[3]}
    return [(rows, stats[start]["sliding_tokens"], steps)
            for (start, rows, _, steps), _ in host_phases.matched_bursts(plain) if start in stats]


def burst_hbm(ctx, plain: dict):
    count = getattr(ctx.family.work, "burst_counted_bytes", None)
    hit = moe_counters.deltas(plain)
    stats = {h[1]: h[3] for h in plain["host"]
             if h[0] == "engine.decode_burst" and "sliding_tokens" in h[3]}
    pairs = [(d, m) for d, m in host_phases.matched_bursts(plain) if d[0] in stats]
    if count is None or ctx.peaks is None or not pairs or not hit or not hit["expert_slots"]:
        return None
    share = hit["experts_hit"] / hit["expert_slots"]
    wbytes = ctx.family.work.bytes_per_weight(ctx.config)
    nbytes = sum(count(ctx.model, wbytes, rows, kv, stats[start]["sliding_tokens"], steps, share)
                 for (start, rows, kv, steps), _ in pairs)
    seconds = sum(m[2] for _, m in pairs)
    return 100.0 * nbytes / (seconds * ctx.peaks["hbm_bytes_per_s"] * ctx.chips) if seconds else None


def waves(plain: dict) -> list:
    return [h[3] for h in plain["host"]
            if h[0] == "engine.prefill_batch" and "sliding_pairs" in h[3]]


def read(ctx, what, op=None, work=None):
    plain = host_phases.phases_of(ctx)
    if plain is None:
        return None
    if what == "hit_share":
        events = waves(plain)
        if len(events) < 2:
            return None
        pages, served = (events[-1][k] - events[0][k]
                         for k in ("page_hit_tokens", "sliding_hit_tokens"))
        return 100.0 * served / pages if pages else None
    if what == "burst_hbm":
        return burst_hbm(ctx, plain)
    count = getattr(ctx.family.work, work or "", None)
    if count is None or ctx.trace is None or ctx.peaks is None or not op:
        return None
    if what == "burst_roofline":
        stats = bursts(plain)
    elif what == "wave_roofline":
        stats = [(e["sliding_pairs"], e["sliding_keys"]) for e in waves(plain)]
    else:
        raise ValueError(f"unknown reading {what!r}")
    seconds = op_seconds(ctx.trace, op)
    if not stats or not seconds:
        return None
    allowed = 0.0
    for args in stats:
        nbytes, flops = count(ctx.model, *args)
        allowed += max(nbytes / ctx.peaks["hbm_bytes_per_s"], flops / ctx.peaks["bf16_flops"])
    return 100.0 * allowed / (seconds * ctx.chips)
