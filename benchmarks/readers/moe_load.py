"""How unevenly the router loads the held experts (PR 54).  A step program
that counts it (models/mellum.py) returns, beside [experts hit, pairs routed
to held experts], the fullest held expert's pairs summed over expert layers
and steps; the engine adds it up as it does the other two and writes
``experts_max_pairs`` beside ``expert_tokens`` on every ``engine.decode_burst``
annotation.  A reading is the difference between the trace's last and first
annotation.

* ``max_over_mean``: the fullest expert's pairs over the mean expert's, a
  layer and step: ``experts_max_pairs * held experts / expert_tokens`` (the
  mean is the pairs over the held experts; layers and steps cancel).  1 is an
  even load.  Where every expert is held it is the stragglers, not the
  misses, that set a wave's tile count and a burst's longest expert.

None where the program writes no such count (any other model, any commit
before PR 54)."""

from benchmarks.readers import host_phases


def read(ctx, what="max_over_mean"):
    if what != "max_over_mean":
        raise ValueError(f"unknown reading {what!r}")
    plain = host_phases.phases_of(ctx)
    if plain is None:
        return None
    events = [h[3] for h in plain["host"]
              if h[0] == "engine.decode_burst" and "experts_max_pairs" in h[3]]
    if len(events) < 2:
        return None
    fullest, pairs = (events[-1][k] - events[0][k] for k in ("experts_max_pairs", "expert_tokens"))
    lo, hi = ctx.model["experts_held"]
    return fullest * (hi - lo) / pairs if pairs else None
