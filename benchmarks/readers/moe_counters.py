"""Readings from the expert layers' counters.  The engine adds each decode
burst's [experts hit, pairs routed to held experts] to cumulative counts when
it reads the burst's tokens back, and writes the counts (``experts_hit``,
``expert_tokens``, ``expert_slots``: held experts x expert layers x steps
offered) on every ``engine.decode_burst`` annotation; a reading is the
difference between the trace's last and first annotation.

* ``hit_share``: experts hit over expert slots, percent (an expert that
  received no token streams no weights);
* ``tokens_per_expert``: pairs over experts hit;
* ``experts_hbm_frac``: bytes of the experts hit (the family's
  ``work.expert_bytes``) over the seconds of the ops matching ``op`` (the
  decode burst's expert products), over the HBM peak, percent.  ``op`` is a
  template that the family's ``work.expert_op_sizes`` fills (the rows of a
  dispatch tile, the gate|up width, the hidden width), so it follows the
  engine's sizes.  The device plane of a trace carries no scope path, only
  instruction names, so the products under the ``moe_experts`` scope are
  found by their output shapes; ``tests/test_deepseek_v3_compile.py`` holds
  the filled pattern to that scope in the compiled program.

None where the program writes no such counts (any other model, any commit
before PR 27)."""

from benchmarks.readers import host_phases
from benchmarks.trace import op_seconds


def deltas(plain: dict):
    events = [h[3] for h in plain["host"]
              if h[0] == "engine.decode_burst" and "experts_hit" in h[3]]
    if len(events) < 2:
        return None
    return {k: events[-1][k] - events[0][k] for k in ("experts_hit", "expert_tokens",
                                                      "expert_slots")}


def read(ctx, what, op=None):
    plain = host_phases.phases_of(ctx)
    d = deltas(plain) if plain is not None else None
    if not d or not d["expert_slots"]:
        return None
    if what == "hit_share":
        return 100.0 * d["experts_hit"] / d["expert_slots"]
    if what == "tokens_per_expert":
        return d["expert_tokens"] / d["experts_hit"] if d["experts_hit"] else None
    if what == "experts_hbm_frac":
        size = getattr(ctx.family.work, "expert_bytes", None)
        sizes = getattr(ctx.family.work, "expert_op_sizes", None)
        if size is None or sizes is None or not ctx.trace or not op or ctx.peaks is None:
            return None
        seconds = op_seconds(ctx.trace, op.format(**sizes(ctx.model, ctx.config)))
        if not seconds:
            return None
        nbytes = d["experts_hit"] * size(ctx.model, ctx.family.work.bytes_per_weight(ctx.config))
        return 100.0 * nbytes / (seconds * ctx.peaks["hbm_bytes_per_s"] * ctx.chips)
    raise ValueError(f"unknown reading {what!r}")
