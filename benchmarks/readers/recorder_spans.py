"""Readings per answered job from the flight recorder's spans (the API pod's
``/debug/traces`` plane; the benchmark serves ``/rag/jobs`` in this process,
so the recorder is at hand).  Where the program records no such span (the
request-record spans are PR 24's) the reading is None."""

from benchmarks.estimators import percentile

LLM_CALLS = ("llm.complete", "llm.stream", "llm.complete_batch")
WAIT = ("server.submit_wait", "engine.queue_wait", "engine.first_token_lag")


def jobs(ctx) -> list:
    """(answer seconds, spans) of each job the window answered."""
    from githubrepostorag_tpu.obs.recorder import get_recorder

    by_trace = {tid: spans for tid, spans, _ in get_recorder().export_spans()}
    out = []
    for r in ctx.window_records():
        tid = (r.get("final") or {}).get("trace_id") or r.get("trace_id")
        if r.get("done_t") and tid in by_trace:
            out.append((r["done_t"] - r["sent_t"], by_trace[tid]))
    return out


def seconds(spans, names) -> float:
    return sum(s.end - s.start for s in spans if s.name in names and s.end is not None)


def retrieve_self_seconds(spans) -> float | None:
    """``agent.retrieve`` without the LLM calls beneath it: its own time plus
    the embedding of the query and the index search."""
    parent = {s.span_id: s.parent_id for s in spans}
    retrieves = {s.span_id for s in spans if s.name == "agent.retrieve"}
    if not retrieves:
        return None

    def under_retrieve(span) -> bool:
        pid = span.parent_id
        while pid is not None:
            if pid in retrieves:
                return True
            pid = parent.get(pid)
        return False

    llm = sum(s.end - s.start for s in spans
              if s.name in LLM_CALLS and s.end is not None and under_retrieve(s))
    return seconds(spans, ("agent.retrieve",)) - llm


def read(ctx, what):
    found = jobs(ctx)
    if not found:
        return None
    if what == "retrieve_search_ms_p50":
        vals = [v * 1e3 for v in (retrieve_self_seconds(sp) for _, sp in found) if v is not None]
        return percentile(vals, 50) if vals else None
    names = {"answer_wait_share": WAIT, "answer_decode_share": ("engine.decode",)}[what]
    if not any(s.name == names[-1] for _, sp in found for s in sp):
        return None  # the program records no such span
    wall = sum(w for w, _ in found)
    return 100.0 * sum(seconds(sp, names) for _, sp in found) / wall if wall else None
