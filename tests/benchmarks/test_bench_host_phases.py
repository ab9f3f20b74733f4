"""The readers of PR 24's request record and step record: on hand-made
events, on a recorded trace of one v5e chip (0.3 s of chat-steady), and in
the rehearsal of both cells."""

import json
import os
import subprocess
import sys
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import manifest, trace
from benchmarks.readers import host_phases as hp, recorder_spans, request_ring

SAMPLE = json.loads((Path(__file__).parent / "data" / "trace_v5e_chat_phases_300ms.json")
                    .read_text())


def _as_reducer_sees_it(plain: dict) -> dict:
    """The plain form ``benchmarks.trace`` keeps: four host names, no extras."""
    return {"devices": plain["devices"],
            "host": [h[:3] for h in plain["host"] if h[0] in trace.HOST_SPANS]}


# ------------------------------------------------------------- by hand --

HAND = {
    "devices": {"0": {
        "ops": [["a", 0.0, 1.0, "fusion"], ["b", 1.5, 0.5, "fusion"], ["c", 2.25, 0.25, "copy"],
                ["d", 4.0, 1.0, "fusion"], ["e", 7.0, 1.0, "fusion"]],
        "modules": [["jit_decode_burst(1)", 0.0, 1.0], ["jit_forward_paged(2)", 1.5, 0.5],
                    ["jit_decode_burst(1)", 2.25, 0.25], ["jit_decode_burst(1)", 4.0, 1.0]]}},
    "host": [
        ["driver.step", 0.9, 1.7, {"mono_ns": 5}],
        ["engine.admit", 0.95, 0.04, {"admitted": 1, "waiting": 0}],
        ["engine.decode_burst", 1.1, 0.2, {"rows": 3, "kv_tokens": 30, "steps": 8}],
        ["engine.commit_fetch", 1.9, 0.2, {}],
        ["engine.commit_host", 2.1, 0.45, {"tokens": 24}],
        ["driver.export", 2.55, 0.05, {"work": 1}],
        ["driver.export", 2.6, 0.3, {"work": 1}],
        ["embed.batch", 2.4, 0.4, {"texts": 2}],
        ["driver.emit", 2.9, 0.1, {"finished": 0}],
        ["driver.wait", 3.0, 0.9, {}],
        ["driver.step", 3.9, 1.3, {"mono_ns": 9}],
        ["engine.decode_burst", 3.95, 0.1, {"rows": 5, "kv_tokens": 60, "steps": 8}],
        ["engine.decode_burst", 5.1, 0.1, {"rows": 7, "kv_tokens": 90, "steps": 8}],
        ["driver.export", 5.3, 0.1, {"work": 0}],
    ]}


def test_idle_gaps_take_the_name_of_the_phase_they_began_in():
    idle = hp.idle_by_phase(HAND)
    assert idle.pop("window_s") == pytest.approx(8.0)
    # 1.0-1.5 began in a step between two phases; 2.0-2.25 in the fetch; 2.5-4.0
    # in commit_host, although an encoder call and later phases overlap it;
    # 5.0-7.0 in a step after its last phase closed
    assert idle == {"driver.step": pytest.approx(2.5), "engine.commit_fetch": pytest.approx(0.25),
                    "engine.commit_host": pytest.approx(1.5)}
    shares = [hp.idle_share({**idle, "window_s": 8.0}, names, unnamed) for names, unnamed in (
        (hp.COMMIT, False), (hp.SCHED + (hp.STEP,), False), (hp.OBS, False), (hp.OTHER, True))]
    assert shares == [pytest.approx(21.875), pytest.approx(31.25), 0.0, 0.0]
    r = trace.reduce(_as_reducer_sees_it(HAND))
    assert sum(shares) == pytest.approx(100.0 * (1 - r["busy_s"] / r["window_s"]))


@pytest.mark.parametrize("active,want", [
    ([["driver.step", 0, 9, {}], ["engine.admit", 1, 1, {}], ["embed.batch", 2, 5, {}]],
     "engine.admit"),
    ([["driver.step", 0, 9, {}], ["index.search", 2, 5, {}]], "driver.step"),
    ([["driver.wait", 0, 9, {}], ["index.search", 2, 5, {}]], "index.search"),
    ([["driver.wait", 0, 9, {}]], "driver.wait"),
    ([], "none"),
])
def test_which_open_event_explains_an_idle_device(active, want):
    assert hp._pick(active) == want


def test_dispatches_pair_with_the_module_events_that_follow_them():
    pairs = hp.matched_bursts(HAND)
    # the module event at 0.0 began before the first dispatch in the trace (it
    # belongs to a dispatch from before it); the dispatch at 5.1 runs after it
    assert [(d[1], m[1]) for d, m in pairs] == [(3, 2.25), (5, 4.0)]
    assert [d[1:] for d in hp.burst_dispatches(HAND)] == [(3, 30, 8), (5, 60, 8), (7, 90, 8)]


def _ctx(plain, **kw):
    ctx = SimpleNamespace(trace_span=(0.0, 1.0), trace=None, peaks=None, chips=1, **kw)
    ctx._host_phases = plain
    return ctx


def test_readings_by_hand():
    model = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
             "intermediate_size": 128, "num_hidden_layers": 2, "vocab_size": 1000}
    ctx = _ctx(HAND, model=model, config={"weights": {"dtype": "int8"}})
    assert hp.read(ctx, "burst_rows_mean") == pytest.approx(5.0)
    assert hp.read(ctx, "obs_ms_per_step") == pytest.approx(1e3 * 0.35 / 2)  # work=0 left out
    assert hp.read(ctx, "burst_hbm_frac") is None  # no peaks: not a chip
    ctx.peaks = {"hbm_bytes_per_s": 1e6}
    from benchmarks import shapes

    want = sum(shapes.burst_bytes(model, 1.0, r, kv, 8)[0] for r, kv in ((3, 30), (5, 60)))
    assert hp.read(ctx, "burst_hbm_frac") == pytest.approx(100.0 * want / (1.25 * 1e6))
    assert hp.read(ctx, "idle_share", names=hp.COMMIT) == pytest.approx(21.875)
    with pytest.raises(ValueError):
        hp.read(ctx, "nope")


def test_a_program_without_the_annotations_reads_as_nothing():
    ctx = SimpleNamespace(trace_span=None, cell=SimpleNamespace(name="no-such-cell"))
    assert hp.read(ctx, "burst_rows_mean") is None  # no trace taken
    ctx = SimpleNamespace(trace_span=(0, 1), cell=SimpleNamespace(name="no-such-cell"))
    assert hp.read(ctx, "obs_ms_per_step") is None  # trace gone from the disk


# -------------------------------------------------------- recorded trace --

def test_recorded_trace_idle_shares_sum_to_the_device_idle_share():
    # (no prefill wave falls into these 0.3 s)
    assert {h[0] for h in SAMPLE["host"]} >= (set(hp.COMMIT + hp.SCHED + hp.OBS) | {hp.STEP}) \
        - {"engine.prefill_batch"}
    idle = hp.idle_by_phase(SAMPLE)
    r = trace.reduce(_as_reducer_sees_it(SAMPLE))
    assert idle["window_s"] == pytest.approx(r["window_s"], rel=1e-9)
    groups = ((hp.COMMIT, False), (hp.SCHED + (hp.STEP,), False), (hp.OBS, False),
              (hp.OTHER, True))
    shares = [hp.idle_share(idle, names, unnamed) for names, unnamed in groups]
    assert all(s >= 0 for s in shares)
    assert sum(shares) == pytest.approx(100.0 * (1 - r["busy_first_s"] / r["window_s"]), abs=1e-6)
    # what the old reducer called "before:engine.decode_burst" has names now; no
    # name only before the first recorded step (the step in progress when the
    # profiler starts is not in the trace)
    first_step = min(h[1] for h in SAMPLE["host"] if h[0] == hp.STEP)
    unnamed = [(s, e) for s, e, name in hp.labelled_gaps(SAMPLE) if name == "none"]
    later = sum(e - s for s, e in unnamed if s >= first_step)  # between two steps: the loop's top
    assert 0 < later < 1e-4 * idle["window_s"] < sum(e - s for s, e in unnamed)
    assert idle["engine.burst_prepare"] > 10 * idle.get("engine.commit_fetch", 0.0)


def test_recorded_trace_drops_the_dispatches_that_run_outside_it():
    dispatches, pairs = hp.burst_dispatches(SAMPLE), hp.matched_bursts(SAMPLE)
    mods = [m for m in SAMPLE["devices"]["0"]["modules"] if "decode_burst" in m[0]]
    # three dispatches and two module events in these 0.3 s: the first module
    # event began half a millisecond before the first dispatch (it runs a burst
    # dispatched before the trace), the second runs the first dispatch, and the
    # other two dispatches run after the sample's end
    assert (len(dispatches), len(mods), len(pairs)) == (3, 2, 1)
    (start, rows, kv, steps), m = pairs[0]
    assert m is mods[1] and mods[0][1] < start < m[1]
    assert (rows, steps) == (dispatches[0][1], 8) and 0 < rows <= 32 and kv > rows
    assert mods[0][2] == pytest.approx(8 * 0.0152, rel=0.05)  # a whole burst: 8 steps
    # the attention kernel keeps the name the accepted reducer finds it by
    assert any(o[0].startswith("closed_call.") and o[3] == "custom-call"
               for o in SAMPLE["devices"]["0"]["ops"])


# ------------------------------------------------- the request record --

def test_request_ring_reader_takes_the_windows_requests(monkeypatch):
    from githubrepostorag_tpu.obs import continuous

    def rec(recv, **stamps):
        return {"request_id": "r", "timings": {"recv_t": recv, **stamps}}

    ring = deque([rec(9.0, first_emit_t=9.5), rec(10.0, first_emit_t=10.25),
                  rec(11.0, first_emit_t=11.75), rec(12.0, first_emit_t=None),
                  rec(21.0, first_emit_t=30.0), {"request_id": "e", "timings": None}])
    monkeypatch.setattr(continuous, "_profilers", {
        "r0": SimpleNamespace(request_ring=ring), "old": SimpleNamespace()})
    ctx = SimpleNamespace(in_window=lambda t: t is not None and 10.0 <= t < 20.0)
    assert request_ring.read(ctx, "recv_t", "first_emit_t", q=50) == pytest.approx(500.0)
    assert len(request_ring.records(ctx)) == 3
    monkeypatch.setattr(continuous, "_profilers", {"old": SimpleNamespace()})
    assert request_ring.read(ctx, "recv_t", "first_emit_t") is None  # a program without a ring


def test_recorder_span_readers(monkeypatch):
    from githubrepostorag_tpu.obs import recorder as recorder_mod

    def span(name, sid, parent, start, end):
        return SimpleNamespace(name=name, span_id=sid, parent_id=parent, start=start, end=end)

    spans = [span("agent.retrieve", "r", "root", 0.0, 5.0), span("llm.complete", "l", "r", 1.0, 4.0),
             span("llm.generate", "g", "l", 1.1, 3.9), span("engine.decode", "d", "g", 2.0, 3.5),
             span("engine.first_token_lag", "f", "g", 1.5, 2.0),
             span("engine.queue_wait", "q", "g", 1.2, 1.3),
             span("llm.complete", "l2", "root", 6.0, 8.0)]
    monkeypatch.setattr(recorder_mod, "_recorder",
                        SimpleNamespace(export_spans=lambda: [("t1", spans, 0.0)]))
    records = [{"done_t": 20.0, "sent_t": 10.0, "final": {"trace_id": "t1"}},
               {"done_t": 20.0, "sent_t": 10.0, "final": {"trace_id": "gone"}}]
    ctx = SimpleNamespace(window_records=lambda: records)
    assert recorder_spans.read(ctx, "retrieve_search_ms_p50") == pytest.approx(2000.0)
    assert recorder_spans.read(ctx, "answer_decode_share") == pytest.approx(15.0)
    assert recorder_spans.read(ctx, "answer_wait_share") == pytest.approx(6.0)
    spans[:] = [s for s in spans if s.name != "engine.first_token_lag"]
    assert recorder_spans.read(ctx, "answer_wait_share") is None  # the parent's spans


# ------------------------------------------------------------ rehearsal --

@pytest.mark.parametrize("cell,seconds,want", [
    ("qwen2-7b-int8.chat-steady", "5", {
        "ttft_server_p50_ms", "srv_tokenize_p50_ms", "srv_submit_wait_p50_ms",
        "srv_emit_lag_p50_ms", "prefill_ms_p50", "first_token_lag_p50_ms", "burst_rows_mean",
        "obs_ms_per_step"}),
    ("qwen2-7b-int8.rag-answer", "12", {
        "answer_wait_share", "answer_decode_share", "retrieve_search_ms_p50"}),
])
def test_rehearsal_prints_the_request_path_metrics(cell, seconds, want):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-m", "benchmarks.run", "--workload", cell, "--seed",
                          str(2**31 + 24), "--seconds", seconds, "--trace", "1", "--rehearse"],
                         cwd=manifest.ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    assert last["correct"] is True and want <= set(last["metrics"]), sorted(last["metrics"])
    m = {k: v["value"] for k, v in last["metrics"].items()}
    if "ttft_server_p50_ms" in want:
        parts = ("srv_tokenize_p50_ms", "srv_submit_wait_p50_ms", "prefill_ms_p50",
                 "first_token_lag_p50_ms", "srv_emit_lag_p50_ms")
        assert all(m[k] >= 0 for k in parts) and m["ttft_server_p50_ms"] <= m["ttft_p50_ms"]
        assert m["burst_rows_mean"] >= 1 and 0 < m["obs_ms_per_step"] < 50
    else:
        assert 0 < m["answer_wait_share"] < 100 and 0 < m["answer_decode_share"] < 100
        assert 0 < m["retrieve_search_ms_p50"] <= m["retrieve_ms_p50"]
