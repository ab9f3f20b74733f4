"""The request record and the step record (PR 24): seven stamps per request
written inside the serving path, and the driver's host phases as profiler
annotations that carry each dispatch's counts."""

import asyncio
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models import Qwen2Config, init_params
from githubrepostorag_tpu.obs import reset_recorder
from githubrepostorag_tpu.obs.continuous import profilers
from githubrepostorag_tpu.obs.engine_profile import TTFT_PARTS
from githubrepostorag_tpu.obs.recorder import get_recorder
from githubrepostorag_tpu.serving import Engine
from githubrepostorag_tpu.serving.async_engine import AsyncEngine
from githubrepostorag_tpu.serving.openai_api import OpenAIServer
from githubrepostorag_tpu.serving.tokenizer import ByteTokenizer
from githubrepostorag_tpu.utils.profiling import annotate

STAMPS = ("recv_t", "enqueue_t", "submit_t", "prefill_start_t", "prefill_end_t",
          "first_token_t", "first_emit_t")
DRIVER_PHASES = ("engine.admit", "engine.prefill_batch", "engine.burst_prepare",
                 "engine.decode_burst", "engine.commit_fetch", "engine.commit_host")
DRIVER_NAMES = DRIVER_PHASES + ("driver.step", "driver.export", "driver.emit", "driver.wait",
                                "server.submit_wait")


def _engine(max_num_seqs=4):
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return Engine(params, cfg, max_num_seqs=max_num_seqs, num_pages=256, page_size=8,
                  max_seq_len=256, prefill_chunk=64, kv_dtype=jnp.float32)


def _host_events(trace_dir, names):
    """(name, start_ns, end_ns, stats, thread) of the named annotations in a
    trace; ``thread`` numbers the host lines."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for thread, line in enumerate(plane.lines):
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats), thread)
                        for e in line.events if e.name in names]
    return sorted(out, key=lambda ev: ev[1])


# ------------------------------------------------------ the request record --


async def test_every_finished_request_has_seven_ordered_stamps(monkeypatch):
    """Through OpenAIServer + AsyncEngine + Engine: streamed and not, chat and
    plain, several at once.  The six parts of time to first token are
    consecutive, so they sum to first_emit_t - recv_t exactly."""
    import aiohttp

    monkeypatch.setenv("TRACE_SAMPLE", "1")
    reset_recorder()
    aeng = AsyncEngine(_engine())
    server = OpenAIServer(aeng, ByteTokenizer(), model_name="tiny-test")
    port = await server.start(host="127.0.0.1", port=0)
    base = f"http://127.0.0.1:{port}"

    async def post(session, i):
        stream = i % 2 == 0
        if i % 3 == 0:
            url, body = "/v1/completions", {"prompt": f"request number {i} " * (1 + i)}
        else:
            url, body = "/v1/chat/completions", {
                "messages": [{"role": "user", "content": f"hello {i} " * (1 + i)}]}
        resp = await session.post(base + url, json={**body, "max_tokens": 1 + i % 5,
                                                    "temperature": 0, "stream": stream})
        assert resp.status == 200
        await resp.read()

    try:
        async with aiohttp.ClientSession() as session:
            await asyncio.gather(*(post(session, i) for i in range(9)))
    finally:
        await server.stop()

    ring = list(profilers()[aeng.replica].request_ring)
    assert ring is not None and len(ring) == 9 and ring == list(aeng.request_ring)
    for rec in ring:
        assert rec["request_id"] and rec["prompt_tokens"] > 0 and rec["output_tokens"] > 0
        assert rec["reason"] in ("stop", "length") and rec["cached_tokens"] >= 0
        t = rec["timings"]
        stamps = [t[k] for k in STAMPS]
        assert all(isinstance(s, float) for s in stamps), t
        assert stamps == sorted(stamps) and t["done_t"] >= t["first_token_t"], t
        parts = [t[b] - t[a] for _, a, b in TTFT_PARTS]
        assert all(p >= 0 for p in parts)
        assert sum(parts) == pytest.approx(t["first_emit_t"] - t["recv_t"], abs=1e-12)

    # the model pod's requests reach the flight recorder like the API pod's:
    # one root span per request with the six parts and prefill/decode under it
    rec = get_recorder()
    assert len(rec.trace_ids()) == 9
    for tid in rec.trace_ids():
        payload = rec.trace_payload(tid)
        names = [s["name"] for s in payload["spans"]]
        root = [s for s in payload["spans"] if s["parent_id"] is None]
        assert len(root) == 1 and root[0]["name"].startswith("http POST /v1/")
        assert {n for n, _, _ in TTFT_PARTS} <= set(names) and "engine.prefill" in names
        assert set(payload["phases"]) <= {"queue", "prefill", "decode"}  # schema unchanged


async def test_in_process_stream_stamps_its_own_receipt():
    """Without an HTTP handler the receipt is the entry of stream()."""
    aeng = AsyncEngine(_engine())
    try:
        result = await aeng.generate(list(range(3, 40)))
    finally:
        await aeng.stop()
    t = result.timings
    assert [t[k] for k in STAMPS] == sorted(t[k] for k in STAMPS)
    assert aeng.request_ring[-1]["timings"] is t


async def test_jobs_that_hold_every_pool_thread_can_still_submit():
    """Agent jobs run in the loop's default executor and call the engine from
    their threads (``worker.py``, ``llm/inprocess.py``): a submission that
    itself needed a thread of that pool would wait for ever once as many jobs
    run as the pool has threads."""
    from concurrent.futures import ThreadPoolExecutor

    loop = asyncio.get_running_loop()
    pool = ThreadPoolExecutor(max_workers=2)
    loop.set_default_executor(pool)
    aeng = AsyncEngine(_engine())
    await aeng.start()

    def job(i):
        call = asyncio.run_coroutine_threadsafe(aeng.generate(list(range(3 + i, 24 + i))), loop)
        return call.result(60)

    try:
        results = await asyncio.wait_for(
            asyncio.gather(*(loop.run_in_executor(None, job, i) for i in range(2))), 90)
    finally:
        await aeng.stop()
        pool.shutdown(wait=False)
    assert all(r.output_tokens and r.finish_reason in ("stop", "length") for r in results)


async def test_a_slow_step_names_the_phase_that_held_it(monkeypatch, caplog):
    """Above ``SLOW_STEP_S`` the driver warns with the step's ledger record and
    the engine's host seconds by phase (a stall names itself in any run)."""
    from githubrepostorag_tpu.serving import async_engine

    monkeypatch.setattr(async_engine, "SLOW_STEP_S", 0.0)
    aeng = AsyncEngine(_engine())
    try:
        with caplog.at_level("WARNING", logger=async_engine.logger.name):
            await aeng.generate(list(range(3, 40)))
    finally:
        await aeng.stop()
    slow = [r.getMessage() for r in caplog.records if "slow engine step" in r.getMessage()]
    assert slow and "host phases {'engine.admit'" in slow[0] and "free pages" in slow[0]
    assert any("'engine.decode_burst'" in m for m in slow)
    assert set(aeng.engine.step_phase_s) <= set(DRIVER_PHASES)


def test_add_request_keeps_its_call_shape_for_wrappers():
    """benchmarks/system.Probe wraps add_request(prompt_ids, sampling=None, *a,
    **kw), _decode_step(finished), _prefill_batch(reqs, finished) and
    _result(req, reason) by name; recv_t/enqueue_t ride as keywords."""
    from benchmarks.system import Probe

    eng = _engine()
    probe = Probe()
    probe.attach(eng)
    rid = eng.add_request([5, 6, 7, 8], recv_t=1.0, enqueue_t=2.0)
    while eng.has_work():
        done = eng.step()
    assert done[0].request_id == rid
    assert done[0].timings["recv_t"] == 1.0 and done[0].timings["enqueue_t"] == 2.0
    assert len(probe.results) == 1 and probe.prefills and probe.bursts


# --------------------------------------------------------- the step record --


@pytest.fixture(scope="module")
def traced_steps(tmp_path_factory):
    """A profiler trace of a few driver cycles on the CPU, with a Probe on
    the same engine."""
    from benchmarks.system import Probe
    from githubrepostorag_tpu.serving.sampling_params import SamplingParams

    eng = _engine()
    sp = SamplingParams(max_tokens=20, temperature=0.0, stop_token_ids=())
    eng.generate([[7] * 70, [9] * 5], sp)  # compile outside the trace
    probe = Probe()
    probe.attach(eng)
    trace_dir = str(tmp_path_factory.mktemp("trace"))

    async def drive():
        aeng = AsyncEngine(eng)
        await aeng.start()
        await asyncio.sleep(0.1)  # the driver asleep with no work, before the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            await asyncio.sleep(0.05)
            first = [aeng.generate([3 + i] * (20 + 50 * i), sp) for i in range(3)]
            tasks = [asyncio.ensure_future(c) for c in first]
            await asyncio.sleep(0.02)  # a second wave joins running rows
            tasks.append(asyncio.ensure_future(aeng.generate([11] * 90, sp)))
            await asyncio.gather(*tasks)
            await asyncio.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
            await aeng.stop()

    asyncio.run(drive())
    events = _host_events(trace_dir, DRIVER_NAMES)
    # a driver that an earlier test of this process left running idles through
    # the trace too (driver.export, driver.wait): keep the thread that ran bursts
    mine = {ev[4] for ev in events if ev[0] == "engine.decode_burst"}
    return [ev for ev in events if ev[4] in mine or ev[0] == "server.submit_wait"], probe


def test_every_annotation_of_the_driver_cycle_is_in_the_trace(traced_steps):
    events, _ = traced_steps
    assert {ev[0] for ev in events} == set(DRIVER_NAMES)
    # metadata moved into the event's stats: the names stay bare
    step = next(ev for ev in events if ev[0] == "driver.step")
    assert step[3]["mono_ns"] > 0
    steps = [ev for ev in events if ev[0] == "driver.step"]
    # the anchor maps time.monotonic() onto the trace's clock: both advance alike
    d_trace = steps[-1][1] - steps[0][1]
    d_mono = steps[-1][3]["mono_ns"] - steps[0][3]["mono_ns"]
    assert d_mono > 0 and abs(d_trace - d_mono) < 20e6  # 20 ms of some hundred


def test_phases_of_a_step_do_not_overlap(traced_steps):
    events, _ = traced_steps
    phases = [ev for ev in events if ev[0] in DRIVER_PHASES]
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a, b)
    # and each lies inside one driver.step; export/emit/wait follow it
    steps = [ev for ev in events if ev[0] == "driver.step"]
    for ph in phases:
        assert any(s[1] <= ph[1] and ph[2] <= s[2] for s in steps), ph
    flat = [ev for ev in events if ev[0].startswith("driver.")]
    for a, b in zip(flat, flat[1:]):
        assert a[2] <= b[1], (a, b)


def test_dispatch_metadata_equals_what_a_probe_counted(traced_steps):
    events, probe = traced_steps
    bursts = [(ev[3]["rows"], ev[3]["kv_tokens"]) for ev in events
              if ev[0] == "engine.decode_burst"]
    assert bursts and all(ev[3]["steps"] == 8 for ev in events if ev[0] == "engine.decode_burst")
    # and whether the device still had work queued when the step's programs went out
    assert all(ev[3]["ahead"] in (0, 1) for ev in events if ev[0] == "engine.decode_burst")
    # the Probe also counts the calls that land the in-flight burst without a
    # dispatch; the annotation is written by dispatches alone
    counted = [(rows, kv) for _, rows, kv in probe.bursts]
    it = iter(counted)
    assert all(b in it for b in bursts), (bursts, counted)
    assert len(bursts) >= len(counted) // 2
    prefills = [ev[3] for ev in events if ev[0] == "engine.prefill_batch"]
    assert len(prefills) == len(probe.prefills)
    for meta, (_, rows) in zip(prefills, probe.prefills):
        assert meta["rows"] == len(rows)
        assert meta["new_tokens"] == sum(n for _, n, _ in rows)
        assert meta["cached_tokens"] == sum(c for c, _, _ in rows)
        assert meta["completes"] == sum(1 for *_, done in rows if done)
        assert meta["pairs"] == sum(n * c + n * (n + 1) // 2 for c, n, _ in rows)
    admitted = sum(ev[3].get("admitted", 0) for ev in events if ev[0] == "engine.admit")
    assert admitted == 4
    tokens = sum(ev[3].get("tokens", 0) for ev in events if ev[0] == "engine.commit_host")
    assert tokens >= 4 * 20


def test_encoder_and_index_write_the_names_the_reducer_reads(tmp_path):
    from benchmarks.trace import HOST_SPANS
    from githubrepostorag_tpu.embedding import JaxBertTextEncoder
    from githubrepostorag_tpu.models.encoder import BertConfig, init_params as bert_params
    from githubrepostorag_tpu.retrieval import DeviceIndexedStore
    from githubrepostorag_tpu.store.base import Doc
    from githubrepostorag_tpu.store.memory import MemoryVectorStore

    class Tok:
        def __call__(self, texts, **kw):
            return {"input_ids": [[(ord(c) % 250) + 1 for c in t[:20]] for t in texts]}

    cfg = BertConfig.tiny()
    enc = JaxBertTextEncoder(bert_params(cfg, jax.random.PRNGKey(1)), cfg, Tok(),
                             max_length=64, batch_size=8, e5_prefixes=False)
    rng = np.random.default_rng(0)
    inner = MemoryVectorStore()
    inner.upsert("t", [Doc(doc_id=f"d{i}", text="x", metadata={},
                           vector=rng.normal(size=24).astype(np.float32)) for i in range(12)])
    dev = DeviceIndexedStore(inner, k_bucket=8)
    q = rng.normal(size=24).astype(np.float32)
    enc.encode(["warm"]), dev.search("t", q, 3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        enc.encode(["alpha", "beta", "gamma"])
        dev.search("t", q, 3)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path), ("embed.batch", "index.search", "encoder.embed_batch"))
    assert [(ev[0], ev[3]) for ev in events] == [
        ("embed.batch", {"texts": 3}), ("index.search", {"queries": 1, "k": 8})]
    assert {"embed.batch", "index.search"} <= set(HOST_SPANS)


# ----------------------------------------------------------------- annotate --


def test_annotate_formats_nothing_while_no_trace_is_taken():
    class Loud:
        seen = 0

        def __str__(self):
            Loud.seen += 1
            raise RuntimeError("formatted with tracing off")

        __repr__ = __format__ = lambda self, *a: self.__str__()

    with annotate("engine.decode_burst", rows=Loud(), kv_tokens=Loud()) as ann:
        ann.set_metadata(tokens=Loud())
    with annotate("bare"):
        pass
    assert Loud.seen == 0


def test_annotate_without_a_profiler_is_a_no_op(monkeypatch):
    from githubrepostorag_tpu.utils import profiling

    monkeypatch.setattr(profiling, "_Annotation", None)
    with profiling.annotate("x", rows=1) as ann:
        ann.set_metadata(more=2)


def test_named_scopes_reach_the_step_programs():
    """The same five names in the paged prefill and in the decode burst."""
    from githubrepostorag_tpu.models.qwen2 import forward_paged
    from githubrepostorag_tpu.serving.decode_burst import decode_burst

    eng = _engine(max_num_seqs=2)
    b, w = 2, 64
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    prefill = forward_paged.lower(
        eng.params, eng.cfg, z(b, w), z(b, w), eng._k_pages, eng._v_pages, z(b, w),
        z(b, eng.max_pages_per_seq), z(b), z(b), use_pallas=False, logits_at=z(b)).as_text(
            debug_info=True)
    burst = decode_burst.lower(
        eng.params, eng.cfg, z(b), z(b), eng._k_pages, eng._v_pages, eng._presence,
        jnp.ones((b,), bool), z(b), z(b, eng.max_pages_per_seq), jax.random.PRNGKey(0),
        jnp.ones((b,)), jnp.ones((b,)), z(b), jnp.ones((b,)), n_steps=8,
        use_pallas=False, first_tokens=z(b), fresh=jnp.zeros((b,), bool), fresh_lens=z(b),
        key_step=jnp.uint32(0)).as_text(debug_info=True)
    for scope in ("paged_attention", "mlp", "attn_proj", "sample", "kv_write"):
        assert f"/{scope}/" in prefill or f"{scope}/" in prefill, scope
        assert f"/{scope}/" in burst or f"{scope}/" in burst, scope
