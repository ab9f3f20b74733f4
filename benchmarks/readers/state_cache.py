"""Readings from a recurrent model's state cache (serving/kv_cache.StateSlots,
PR 34).  The engine writes its cumulative counts on every
``engine.prefill_batch`` annotation: ``page_hit_tokens`` (prompt tokens whose
pages the prefix cache held at admission), ``state_hit_tokens`` (of those, the
tokens a prefill could skip because a state snapshot lay at that depth),
``state_restored``, ``state_snapshots``, ``state_evicted``.  A reading is the
difference between the trace's last and first annotation.

* ``resume_share``: ``state_hit_tokens`` over ``page_hit_tokens``, percent:
  what the snapshot policy keeps of what the pages offer (100 where every
  page hit found a snapshot as deep);
* ``wave_roofline``: the seconds the roofline allows the chunked Gated
  DeltaNet rule for the real new tokens of the trace's waves (the family's
  ``work.<work>`` of each wave's ``new_tokens`` and ``rows``: the larger of
  its bytes over the HBM peak and its operations over the bf16 peak) over the
  seconds of the ops matching ``op``, percent.  ``op`` is a template the
  family's ``work.state_op_sizes`` fills; a trace's device plane names
  instructions and not scopes, so the rule's ops are found by their shapes,
  and ``tests/test_qwen3_next_compile.py`` holds the filled pattern to the
  ``gdn_chunked`` scope in the compiled wave.

None where the program writes no such counts (any other model, any commit
before PR 34)."""

from benchmarks.readers import host_phases
from benchmarks.trace import op_seconds

KEYS = ("page_hit_tokens", "state_hit_tokens")


def waves(plain: dict) -> list:
    return [h[3] for h in plain["host"]
            if h[0] == "engine.prefill_batch" and "state_hit_tokens" in h[3]]


def read(ctx, what, op=None, work=None):
    plain = host_phases.phases_of(ctx)
    events = waves(plain) if plain is not None else []
    if len(events) < 2:
        return None
    if what == "resume_share":
        pages, state = (events[-1][k] - events[0][k] for k in KEYS)
        return 100.0 * state / pages if pages else None
    if what == "wave_roofline":
        count = getattr(ctx.family.work, work or "", None)
        sizes = getattr(ctx.family.work, "state_op_sizes", None)
        if count is None or sizes is None or ctx.trace is None or ctx.peaks is None or not op:
            return None
        seconds = op_seconds(ctx.trace, op.format(**sizes(ctx.model, ctx.config)))
        if not seconds:
            return None
        allowed = 0.0
        for e in events:
            nbytes, flops = count(ctx.model, e["new_tokens"], e["rows"])
            allowed += max(nbytes / ctx.peaks["hbm_bytes_per_s"], flops / ctx.peaks["bf16_flops"])
        return 100.0 * allowed / (seconds * ctx.chips)
    raise ValueError(f"unknown reading {what!r}")
