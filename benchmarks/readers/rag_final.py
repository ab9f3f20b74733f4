"""Readings from each job's final SSE event (its ``phases`` and call count)."""

from benchmarks.estimators import percentile


def read(ctx, what):
    finals = [(r, r["final"]) for r in ctx.window_records() if r.get("final")]
    if not finals:
        return None
    if what == "llm_calls_per_answer":
        calls = [f["llm_calls"] for _, f in finals if f.get("llm_calls") is not None]
        return sum(calls) / len(calls) if calls else None
    if what == "retrieve_ms_p50":
        vals = [f["phases"]["retrieve"] * 1e3 for _, f in finals
                if (f.get("phases") or {}).get("retrieve") is not None]
        return percentile(vals, 50) if vals else None
    if what == "agent_llm_share":
        llm = sum(f["llm_seconds"] for _, f in finals if f.get("llm_seconds") is not None)
        wall = sum(r["done_t"] - r["sent_t"] for r, f in finals
                   if f.get("llm_seconds") is not None)
        return 100.0 * llm / wall if wall else None
    raise ValueError(f"unknown reading {what!r}")
