"""Where the seconds to ready went: readings from the program's compile
ledger (``obs/engine_profile.compile_ledger``: every trace, lowering and
back-end compile JAX announced, with its seconds and function) and its
start-up record (``obs/startup.startup_record``: named phases written by the
functions that do the work).  Both are process-wide, so the reader needs no
handle from the harness.  A program without them (any commit before PR 36)
reads as None.

``readings`` works on plain data alone (the two ``snapshot()`` dicts), so
the arithmetic is tested on a recorded set-up without a chip.  The parts
overlap by design (a weights phase holds its own compiles; the encoder's
programs are in ``setup_trace_lower_s`` and in ``setup_retrieval_s``) and
are not asked to sum; ``setup_other_s`` is taken over their union."""

from __future__ import annotations

import json
import os

from benchmarks import manifest

WEIGHTS = ("startup.weights", "startup.engine_init")
RETRIEVAL = ("startup.encoder", "startup.ingest.", "startup.index_build")
TRACE, LOWER, COMPILE = "trace", "lower", "compile"


def snapshot():
    """{"ledger": ..., "record": ...} of this process, or None where the
    program keeps neither."""
    try:
        from githubrepostorag_tpu.obs.engine_profile import compile_ledger
        from githubrepostorag_tpu.obs.startup import startup_record
    except ImportError:
        return None
    return {"ledger": compile_ledger().snapshot(), "record": startup_record().snapshot()}


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] covered by at least one of ``intervals``."""
    total, end = 0.0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b > max(a, end):
            total += b - max(a, end)
            end = b
    return total


def _phase_seconds(phases: list, names: tuple):
    """Seconds covered by the phases whose name is, or starts with, one of
    ``names`` (one phase may lie inside another: the encoder is loaded inside
    ingest's ``vector_write``); None where there is none, or one of them never
    closed."""
    mine = [p for p in phases if any(p["name"] == n or (n.endswith(".") and
                                                       p["name"].startswith(n)) for n in names)]
    if not mine or any(p["end"] is None for p in mine):
        return None
    spans = [(p["start"], p["end"]) for p in mine]
    return union_seconds(spans, min(a for a, _ in spans), max(b for _, b in spans))


def readings(snap: dict, t_open: float, lead_in_s: float) -> dict:
    """The six readings; a reading that cannot be taken is None."""
    ledger, record = snap["ledger"], snap["record"]
    warm_t = ledger["warm_t"]
    if warm_t is None:  # the program never said it was ready
        return {}
    # an event: [t, own seconds, kind, function, cache hit, step program, wall seconds]
    before = [e for e in ledger["events"] if e[0] < warm_t]
    phases = record["phases"]
    t_ready = t_open - lead_in_s  # the load generator starts its lead-in here
    t0 = record["process_start"]
    named = [(p["start"], p["end"]) for p in phases if p["end"] is not None]
    named += [(e[0] - e[6], e[0]) for e in ledger["events"]]
    return {
        "setup_trace_lower_s": sum(e[1] for e in before if e[2] in (TRACE, LOWER)),
        "setup_compile_s": sum(e[1] for e in before if e[2] == COMPILE),
        "setup_programs": float(sum(1 for e in before if e[2] == COMPILE)),
        "setup_cache_hits": float(sum(1 for e in before if e[2] == COMPILE and e[4])),
        "setup_weights_s": _phase_seconds(phases, WEIGHTS),
        "setup_retrieval_s": _phase_seconds(phases, RETRIEVAL),
        "setup_other_s": max(0.0, (t_ready - t0) - union_seconds(named, t0, t_ready)),
        "to_ready_s": t_ready - t0, "to_warm_s": warm_t - t0,
    }


def read(ctx, what):
    if not hasattr(ctx, "_startup"):
        snap = snapshot()
        ctx._startup = {} if snap is None else readings(
            snap, ctx.t_open, float(ctx.traffic.get("lead_in_s", 0.0)))
        if snap is not None and os.environ.get("BENCH_KEEP_TRACE"):  # for the tests and PERF.md
            out = manifest.ROOT / ".bench_work" / ctx.cell.name / "startup.json"
            out.write_text(json.dumps({"t_open": ctx.t_open, "readings": ctx._startup, **snap}))
    return ctx._startup.get(what)
