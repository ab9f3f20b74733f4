"""Observability stack: trace context propagation (wire + contextvar),
the flight recorder's bounds and payloads, MeteredLLM span/status/token
accounting, /metrics label cardinality, the XLA compile watchdog, and the
full-stack connected-trace path API -> worker -> agent -> engine."""

import asyncio
import json
import subprocess
import sys
from pathlib import Path

import pytest

from githubrepostorag_tpu.metrics import (
    DECODE_TOKENS,
    FAULTS_INJECTED,
    HTTP_REQUESTS,
    LLM_CALLS,
    XLA_COMPILES,
    MeteredLLM,
    counter_value,
)
from githubrepostorag_tpu.obs import (
    NOOP_SPAN,
    FlightRecorder,
    get_recorder,
    reset_recorder,
    root_span,
    span,
)
from githubrepostorag_tpu.obs.trace import Span, TraceContext, current_context, trace_scope
from githubrepostorag_tpu.resilience.policy import Deadline

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def sampled(monkeypatch):
    """Force-sample every new root and start from an empty recorder."""
    monkeypatch.setenv("TRACE_SAMPLE", "1")
    yield reset_recorder()
    reset_recorder()


# ------------------------------------------------------------------- wire --


def test_traceparent_header_round_trip():
    ctx = TraceContext("ab" * 16, "cd" * 8, flags=1)
    back = TraceContext.from_header(ctx.to_header())
    assert back is not None
    assert (back.trace_id, back.span_id, back.flags) == (ctx.trace_id, ctx.span_id, 1)

    unsampled = TraceContext("ef" * 16, "01" * 8, flags=0)
    back = TraceContext.from_header(unsampled.to_header())
    assert back is not None and not back.sampled

    for junk in (None, "", "garbage", "00-zz-xx-01", "01-" + "a" * 32 + "-" + "b" * 16 + "-01"):
        assert TraceContext.from_header(junk) is None


def test_trace_rides_the_envelope_next_to_deadline():
    """The queue hop carries kwargs["trace"] beside kwargs["deadline"];
    both survive a JSON round trip (the Redis envelope is JSON)."""
    ctx = TraceContext("12" * 16, "34" * 8, flags=1)
    kwargs = {"deadline": Deadline(5.0).to_wire(), "trace": ctx.to_wire()}
    kwargs = json.loads(json.dumps(kwargs))  # the actual wire transform

    back = TraceContext.from_wire(kwargs.get("trace"))
    assert back is not None and back.trace_id == ctx.trace_id and back.sampled
    deadline = Deadline.from_wire(kwargs["deadline"])
    assert 3.0 < deadline.remaining() <= 5.0


def test_old_envelope_without_trace_key_still_parses():
    """Envelopes enqueued by a pre-tracing deployment have no trace field;
    from_wire must answer None for every malformed shape, never raise."""
    old = json.loads(json.dumps({"deadline": Deadline(2.0).to_wire()}))
    assert TraceContext.from_wire(old.get("trace")) is None
    assert Deadline.from_wire(old["deadline"]).remaining() > 0
    for junk in (None, 42, [], {"traceparent": 7}, {"other": "x"}):
        assert TraceContext.from_wire(junk) is None


# ------------------------------------------------------------ span scopes --


def test_span_without_scope_is_the_shared_noop(monkeypatch):
    monkeypatch.delenv("TRACE_SAMPLE", raising=False)
    with span("anything") as sp:
        assert sp is NOOP_SPAN
    with span("nested") as outer:
        with span("inner") as inner:
            assert outer is inner is NOOP_SPAN


def test_trace_sample_zero_records_nothing(monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE", "0")
    rec = reset_recorder()
    try:
        with root_span("http POST /rag/jobs") as sp:
            assert sp is NOOP_SPAN
            assert sp.context is None  # -> create_job sends trace=None
            with span("agent.run") as child:
                assert child is NOOP_SPAN
        assert rec.trace_ids() == []
    finally:
        reset_recorder()


def test_root_span_continues_wire_context_and_children_nest(sampled):
    wire = TraceContext("fe" * 16, "dc" * 8, flags=1).to_wire()
    with root_span("worker.job", wire=wire) as sp:
        assert sp.trace_id == "fe" * 16
        assert sp.parent_id == "dc" * 8
        with span("agent.run") as child:
            assert child.parent_id == sp.span_id
            assert current_context().span_id == child.span_id
    payload = sampled.trace_payload("fe" * 16)
    assert {s["name"] for s in payload["spans"]} == {"worker.job", "agent.run"}


def test_span_error_status_on_exception(sampled):
    with pytest.raises(ValueError):
        with root_span("worker.job"):
            with span("agent.plan"):
                raise ValueError("nope")
    tid = sampled.trace_ids()[0]
    by_name = {s["name"]: s for s in sampled.trace_payload(tid)["spans"]}
    assert by_name["agent.plan"]["status"] == "error: ValueError"
    assert by_name["worker.job"]["status"] == "error: ValueError"


# --------------------------------------------------------------- recorder --


def _finished_span(name, trace_id, dur=0.01):
    sp = Span(name, TraceContext(trace_id, "", 1))
    sp.end = sp.start + dur
    return sp


def test_recorder_evicts_oldest_trace_and_counts_drops():
    rec = FlightRecorder(max_traces=2, max_spans_per_trace=8)
    for i in range(4):
        rec.record(_finished_span("s", f"{i:032x}"))
    assert rec.trace_ids() == [f"{2:032x}", f"{3:032x}"]
    payload = rec.summaries_payload()
    assert payload["dropped_traces"] == 2
    assert payload["trace_count"] == 2
    assert rec.trace_payload(f"{0:032x}") is None  # evicted


def test_recorder_caps_spans_per_trace():
    rec = FlightRecorder(max_traces=4, max_spans_per_trace=3)
    tid = "aa" * 16
    for _ in range(5):
        rec.record(_finished_span("s", tid))
    payload = rec.trace_payload(tid)
    assert payload["span_count"] == 3
    assert payload["dropped_spans"] == 2


def test_recorder_bounded_memory_under_concurrent_writers():
    # the bounded-memory contract must hold while threaded producers race
    # the ring: trace count never exceeds max_traces, per-trace spans never
    # exceed max_spans_per_trace, and every record() is accounted for as
    # either a stored span, a dropped span, or part of an evicted trace
    import threading

    max_traces, max_spans = 8, 4
    writers, spans_each = 6, 200
    rec = FlightRecorder(max_traces=max_traces, max_spans_per_trace=max_spans)
    start = threading.Barrier(writers)

    def produce(widx):
        start.wait()
        for i in range(spans_each):
            # writers collide on shared trace ids (cap path) and mint
            # fresh ones (eviction path) in the same interleaving
            tid = f"{(widx * spans_each + i) % (max_traces * 3):032x}"
            rec.record(_finished_span("s", tid))

    threads = [threading.Thread(target=produce, args=(w,)) for w in range(writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    payload = rec.summaries_payload()
    assert payload["trace_count"] <= max_traces
    assert len(rec.trace_ids()) == payload["trace_count"]
    stored = dropped_spans = 0
    for tid in rec.trace_ids():
        tp = rec.trace_payload(tid)
        if tp is None:
            continue  # evicted between the two reads
        assert tp["span_count"] <= max_spans
        stored += tp["span_count"]
        dropped_spans += tp["dropped_spans"]
    assert stored <= max_traces * max_spans
    # no record() vanished silently: with 3*max_traces trace ids cycling,
    # evictions and span drops must both have fired under the race
    assert payload["dropped_traces"] > 0
    assert dropped_spans + stored > 0


def test_recorder_drop_counters_are_exact_single_trace_race():
    # all writers hammer ONE trace id: no evictions possible, so stored +
    # dropped must equal exactly the number of record() calls
    import threading

    max_spans = 16
    writers, spans_each = 8, 100
    rec = FlightRecorder(max_traces=2, max_spans_per_trace=max_spans)
    tid = "cc" * 16
    start = threading.Barrier(writers)

    def produce():
        start.wait()
        for _ in range(spans_each):
            rec.record(_finished_span("s", tid))

    threads = [threading.Thread(target=produce) for _ in range(writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    tp = rec.trace_payload(tid)
    assert tp["span_count"] == max_spans
    assert tp["dropped_spans"] == writers * spans_each - max_spans
    assert rec.summaries_payload()["dropped_traces"] == 0


def test_phase_summary_maps_and_sums_span_names():
    rec = FlightRecorder(max_traces=4, max_spans_per_trace=16)
    tid = "bb" * 16
    rec.record(_finished_span("engine.queue_wait", tid, dur=0.5))
    rec.record(_finished_span("engine.prefill", tid, dur=1.0))
    rec.record(_finished_span("engine.decode", tid, dur=2.0))
    rec.record(_finished_span("agent.retrieve", tid, dur=0.25))
    rec.record(_finished_span("agent.retrieve", tid, dur=0.25))  # second wave sums
    rec.record(_finished_span("worker.job", tid, dur=9.0))  # not a phase
    phases = rec.phase_summary(tid)
    assert phases == {"queue": 0.5, "prefill": 1.0, "decode": 2.0, "retrieve": 0.5}


def test_debug_traces_schema_matches_committed_golden():
    import os

    proc = subprocess.run(
        [sys.executable, "scripts/check_traces_schema.py"],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------- counter_value --


def test_counter_value_reads_each_multi_label_series():
    base_drop = counter_value(FAULTS_INJECTED, site="obs.test", action="drop")
    base_err = counter_value(FAULTS_INJECTED, site="obs.test", action="error")
    FAULTS_INJECTED.labels(site="obs.test", action="drop").inc()
    FAULTS_INJECTED.labels(site="obs.test", action="drop").inc()
    FAULTS_INJECTED.labels(site="obs.test", action="error").inc()
    assert counter_value(FAULTS_INJECTED, site="obs.test", action="drop") == base_drop + 2
    assert counter_value(FAULTS_INJECTED, site="obs.test", action="error") == base_err + 1
    assert counter_value(FAULTS_INJECTED, site="obs.test", action="never") == 0.0


# -------------------------------------------------------------- MeteredLLM --


class _ScriptedStream:
    """Inner LLM whose stream behavior is programmable per test."""

    def __init__(self, deltas=(), raises=None):
        self.deltas = list(deltas)
        self.raises = raises

    def stream_complete(self, prompt, **kw):
        for d in self.deltas:
            yield d
        if self.raises is not None:
            raise self.raises


def _llm_counts():
    return {s: counter_value(LLM_CALLS, status=s)
            for s in ("ok", "error", "cancelled")}


def test_metered_stream_counts_tokens_and_ok(sampled):
    before, tok_before = _llm_counts(), counter_value(DECODE_TOKENS)
    llm = MeteredLLM(_ScriptedStream(deltas=["a", "b", "c"]))
    with root_span("worker.job"):
        assert list(llm.stream_complete("q")) == ["a", "b", "c"]
    after = _llm_counts()
    assert after["ok"] == before["ok"] + 1
    assert after["error"] == before["error"]
    assert counter_value(DECODE_TOKENS) == tok_before + 3
    tid = sampled.trace_ids()[0]
    stream = next(s for s in sampled.trace_payload(tid)["spans"]
                  if s["name"] == "llm.stream")
    assert stream["status"] == "ok" and stream["attrs"]["deltas"] == 3


def test_metered_stream_error_delta_is_not_ok(sampled):
    """Regression: stream_complete used to label every call status="ok"
    even when the backend yielded its errors-as-text sentinel."""
    before = _llm_counts()
    llm = MeteredLLM(_ScriptedStream(deltas=["Error: backend down"]))
    with root_span("worker.job"):
        list(llm.stream_complete("q"))
    after = _llm_counts()
    assert after["error"] == before["error"] + 1
    assert after["ok"] == before["ok"]
    tid = sampled.trace_ids()[0]
    stream = next(s for s in sampled.trace_payload(tid)["spans"]
                  if s["name"] == "llm.stream")
    assert stream["status"].startswith("error")


def test_metered_stream_raise_is_not_ok():
    before = _llm_counts()
    llm = MeteredLLM(_ScriptedStream(deltas=["a"], raises=RuntimeError("boom")))
    with pytest.raises(RuntimeError):
        list(llm.stream_complete("q"))
    after = _llm_counts()
    assert after["error"] == before["error"] + 1
    assert after["ok"] == before["ok"]


def test_metered_stream_early_close_counts_cancelled():
    before = _llm_counts()
    llm = MeteredLLM(_ScriptedStream(deltas=["a", "b", "c"]))
    gen = llm.stream_complete("q")
    assert next(gen) == "a"
    gen.close()
    after = _llm_counts()
    assert after["cancelled"] == before["cancelled"] + 1
    assert after["ok"] == before["ok"]


# ----------------------------------------------------- compile watchdog ---


def test_compile_watchdog_detects_a_genuine_recompile():
    import jax
    import jax.numpy as jnp

    from githubrepostorag_tpu.obs.engine_profile import CompileWatchdog

    def watchdog_probe(x):
        return x + 1

    f = jax.jit(watchdog_probe)
    f(jnp.zeros((2,), jnp.float32))
    dog = CompileWatchdog(programs=[f])
    assert dog.sample() == 0  # warm shape, no new programs
    f(jnp.zeros((2,), jnp.float32))
    assert dog.sample() == 0  # cache hit is not a compile
    f(jnp.zeros((3,), jnp.float32))  # fresh shape -> real XLA compile
    assert dog.sample() == 1 and dog.grown[0].startswith("watchdog_probe ")
    assert dog.sample() == 0 and dog.grown == []  # delta, not level


@pytest.mark.parametrize("family", ["qwen2", "deepseek_v3", "qwen3_next"])
def test_an_engine_names_its_step_programs_to_the_ledger(family):
    """No list of modules: what the watchdog watches is what the engine of
    each family says it dispatches."""
    import importlib

    import jax
    import jax.numpy as jnp

    from githubrepostorag_tpu.obs.engine_profile import compile_ledger, program_name
    from githubrepostorag_tpu.serving import Engine

    model = importlib.import_module(f"githubrepostorag_tpu.models.{family}")
    kw = dict(max_num_seqs=2, num_pages=32, page_size=16, max_seq_len=128, prefill_chunk=64,
              kv_dtype=jnp.float32)
    if family == "qwen2":
        cfg = model.Qwen2Config.tiny()
        params = model.init_params(cfg, jax.random.PRNGKey(0))
    else:
        cfg = {"deepseek_v3": "DeepseekV3Config", "qwen3_next": "Qwen3NextConfig"}[family]
        cfg = getattr(model, cfg).tiny()
        params = model.init_params(cfg, seed=0)
    eng = Engine(params, cfg, **kw)
    programs = eng.step_programs()
    assert all(callable(p._cache_size) for p in programs)
    # the two programs a step dispatches are the family's own objects
    assert model.forward_paged_wave in programs
    assert (model.decode_burst if family != "qwen2" else eng._decode_burst_fn) in programs
    names = {program_name(p) for p in programs}
    assert {"forward_paged_wave", "decode_burst", "_mark_presence_chunks"} <= names
    assert names <= compile_ledger().step_programs


# ------------------------------------------------- full stack over a bus ---

AGENT_SCRIPT = {
    r"Pick the retrieval scope": '{"scope": "chunk", "filters": {}}',
    r"Assess whether the retrieved": '{"coverage": 0.9, "needs_more": false}',
    r"senior engineer": "Jobs are created via POST /rag/jobs [1].",
}


def _tiny_llm(max_num_seqs=2, num_pages=128):
    import jax
    import jax.numpy as jnp

    from githubrepostorag_tpu.llm import InProcessLLM
    from githubrepostorag_tpu.models import Qwen2Config, init_params
    from githubrepostorag_tpu.serving import Engine
    from githubrepostorag_tpu.serving.async_engine import AsyncEngine
    from githubrepostorag_tpu.serving.tokenizer import ByteTokenizer

    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, max_num_seqs=max_num_seqs, num_pages=num_pages,
                 page_size=8, max_seq_len=256, prefill_chunk=64,
                 kv_dtype=jnp.float32)
    return InProcessLLM(AsyncEngine(eng), ByteTokenizer(),
                        default_max_tokens=8, default_temperature=0.0,
                        context_window=128)


class _HybridLLM:
    """Scripted plan/judge via FakeLLM; the synthesis prompt (the only one
    matching "senior engineer") runs through the real in-process engine so
    the trace reaches genuine prefill/decode spans."""

    def __init__(self, fake, real):
        self.fake, self.real = fake, real

    def _pick(self, prompt):
        return self.real if "senior engineer" in prompt else self.fake

    def complete(self, prompt, **kw):
        return self._pick(prompt).complete(prompt, **kw)

    def stream_complete(self, prompt, **kw):
        return self._pick(prompt).stream_complete(prompt, **kw)


def _stack(llm):
    from githubrepostorag_tpu.agent import GraphAgent
    from githubrepostorag_tpu.api.app import RagApi
    from githubrepostorag_tpu.embedding import HashingTextEncoder
    from githubrepostorag_tpu.events import MemoryBus, MemoryCancelFlags, MemoryJobQueue
    from githubrepostorag_tpu.retrieval import RetrieverFactory
    from githubrepostorag_tpu.store import Doc, MemoryVectorStore
    from githubrepostorag_tpu.worker import RagWorker

    store, enc = MemoryVectorStore(), HashingTextEncoder()
    texts = [
        ("c1", "async def create_job(request): enqueue and return job id",
         {"repo": "api", "module": "app", "file_path": "app/jobs.py"}),
        ("c2", "class RagWorker: consumes jobs and emits progress events",
         {"repo": "api", "module": "worker", "file_path": "worker/worker.py"}),
    ]
    store.upsert("embeddings", [
        Doc(d, t, {"namespace": "default", "scope": "chunk", **m}, enc.encode([t])[0])
        for d, t, m in texts
    ])
    agent = GraphAgent(llm, RetrieverFactory(store, enc), namespace="default")
    bus = MemoryBus(ping_interval=0.05)
    flags, queue = MemoryCancelFlags(), MemoryJobQueue()
    worker = RagWorker(agent, bus, flags, queue, max_jobs=2, job_timeout=120)
    return RagApi(bus, flags, queue), worker


async def _collect_events(session, base, job_id, timeout=120):
    import aiohttp

    events = []
    async with session.get(f"{base}/rag/jobs/{job_id}/events",
                           timeout=aiohttp.ClientTimeout(total=timeout)) as resp:
        async for raw in resp.content:
            line = raw.decode().strip()
            if line.startswith("data: "):
                events.append(json.loads(line[6:]))
                if events[-1]["event"] == "final":
                    break
    return events


async def test_one_connected_trace_api_to_engine_decode(sampled, monkeypatch):
    """The acceptance trace: root API span -> worker continuation -> agent
    phase spans -> engine prefill/decode spans, all one trace_id, the full
    tree retrievable from /debug/traces/{trace_id}, and the compact phase
    summary on the terminal SSE event."""
    import aiohttp

    from githubrepostorag_tpu.config import reload_settings
    from githubrepostorag_tpu.llm import FakeLLM

    # the warm call below compiles on the CPU: where nothing in the process
    # compiled such programs before, or the host is loaded, its first token
    # or its token gap misses the objectives, one bad request of one burns
    # critically, and the API sheds the job this test posts
    monkeypatch.setenv("SLO_TTFT_P99_MS", "600000")
    monkeypatch.setenv("SLO_TPOT_MS", "600000")
    reload_settings()
    real = _tiny_llm()
    real.complete("warm the engine compile cache")  # compiles outside the job
    api, worker = _stack(_HybridLLM(FakeLLM(script=AGENT_SCRIPT), real))
    reset_recorder()  # drop the warmup call's trace noise
    port = await api.start(host="127.0.0.1", port=0)
    worker_task = asyncio.create_task(worker.run_forever())
    try:
        async with aiohttp.ClientSession() as session:
            base = f"http://127.0.0.1:{port}"
            resp = await session.post(f"{base}/rag/jobs",
                                      json={"query": "how are jobs created?"})
            body = await resp.json()
            trace_id = body["trace_id"]
            assert len(trace_id) == 32

            events = await _collect_events(session, base, body["job_id"])
            final = events[-1]["data"]
            assert final["trace_id"] == trace_id
            for phase in ("plan", "retrieve", "judge", "synthesize",
                          "prefill", "decode"):
                assert phase in final["phases"], (phase, final["phases"])
                assert final["phases"][phase] >= 0.0

            # worker.job finishes just after the final event; poll briefly
            payload, by_name = {}, {}
            for _ in range(50):
                detail = await session.get(f"{base}/debug/traces/{trace_id}")
                assert detail.status == 200
                payload = await detail.json()
                by_name = {s["name"]: s for s in payload["spans"]}
                if "worker.job" in by_name:
                    break
                await asyncio.sleep(0.05)
            for name in ("http POST /rag/jobs", "worker.job", "agent.run",
                         "agent.plan", "agent.retrieve", "agent.judge",
                         "agent.synthesize", "llm.generate",
                         "engine.queue_wait", "engine.prefill", "engine.decode"):
                assert name in by_name, f"missing span {name}: {sorted(by_name)}"

            # parent links form ONE connected tree rooted at the API span
            root = by_name["http POST /rag/jobs"]
            assert root["parent_id"] is None
            assert by_name["worker.job"]["parent_id"] == root["span_id"]
            assert by_name["agent.run"]["parent_id"] == by_name["worker.job"]["span_id"]
            assert (by_name["agent.synthesize"]["parent_id"]
                    == by_name["agent.run"]["span_id"])
            assert (by_name["llm.generate"]["parent_id"]
                    == by_name["agent.synthesize"]["span_id"])
            for eng_span in ("engine.queue_wait", "engine.prefill", "engine.decode"):
                assert (by_name[eng_span]["parent_id"]
                        == by_name["llm.generate"]["span_id"])

            # the index lists the trace under its API root
            summary = await (await session.get(f"{base}/debug/traces")).json()
            row = next(t for t in summary["traces"] if t["trace_id"] == trace_id)
            assert row["root"] == "http POST /rag/jobs"
            assert row["span_count"] == len(payload["spans"])

            missing = await session.get(f"{base}/debug/traces/{'0' * 32}")
            assert missing.status == 404
    finally:
        worker.stop()
        worker_task.cancel()
        await api.stop()
        real.close()
        monkeypatch.undo()
        reload_settings()


async def test_post_warmup_recompile_fires_watchdog(sampled):
    """A fresh XLA compile observed during live stepping must increment
    rag_xla_compiles_total and stamp an xla_compile event on the in-flight
    request's span."""
    import jax
    import jax.numpy as jnp

    from githubrepostorag_tpu.obs.engine_profile import CompileWatchdog

    def watchdog_sentinel(x):
        return x * 2

    f = jax.jit(watchdog_sentinel)
    f(jnp.zeros((2,), jnp.float32))  # pre-warm shape A
    llm = _tiny_llm()
    # watch our sentinel jit: its recompile below is a genuine XLA compile,
    # found by the real per-step sampling on the engine driver thread
    llm.engine.profiler.watchdog = CompileWatchdog(programs=[f])
    try:
        llm.complete("warm")  # AsyncEngine.start() -> profiler.mark_warm()
        before = counter_value(XLA_COMPILES)

        f(jnp.zeros((5,), jnp.float32))  # the post-warmup recompile
        with trace_scope(TraceContext(f"{7:032x}", "", 1)):
            out = llm.complete("probe request")
        assert isinstance(out, str)

        assert counter_value(XLA_COMPILES) == before + 1
        payload = get_recorder().trace_payload(f"{7:032x}")
        assert payload is not None
        gen = next(s for s in payload["spans"] if s["name"] == "llm.generate")
        compile_events = [e for e in gen["events"] if e["name"] == "xla_compile"]
        assert compile_events and compile_events[0]["new_programs"] == 1
    finally:
        llm.close()


# --------------------------------------------------- /metrics cardinality --


async def test_metrics_path_labels_use_route_templates():
    """A scrape must see ONE path label per route regardless of how many
    job ids traffic minted — raw ids in labels are a cardinality leak."""
    import aiohttp

    from githubrepostorag_tpu.api.app import RagApi
    from githubrepostorag_tpu.events import MemoryBus, MemoryCancelFlags, MemoryJobQueue

    api = RagApi(MemoryBus(ping_interval=0.05), MemoryCancelFlags(), MemoryJobQueue())
    port = await api.start(host="127.0.0.1", port=0)
    try:
        async with aiohttp.ClientSession() as session:
            base = f"http://127.0.0.1:{port}"
            for i in range(12):
                r = await session.get(f"{base}/rag/jobs/{i:032x}/result")
                assert r.status == 404  # unknown job; the route still matched
                c = await session.post(f"{base}/rag/jobs/{i:032x}/cancel")
                assert c.status == 200
        result_paths = {
            s.labels["path"]
            for s in HTTP_REQUESTS.collect()[0].samples
            if not s.name.endswith("_created") and "result" in s.labels.get("path", "")
        }
        assert result_paths == {"/rag/jobs/{job_id}/result"}
        cancel_paths = {
            s.labels["path"]
            for s in HTTP_REQUESTS.collect()[0].samples
            if not s.name.endswith("_created") and "cancel" in s.labels.get("path", "")
        }
        assert cancel_paths == {"/rag/jobs/{job_id}/cancel"}
    finally:
        await api.stop()
