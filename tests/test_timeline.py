"""Pod timeline & HBM observatory (obs/timeline.py, obs/hbm.py,
obs/continuous.py).

Pins the PR's acceptance bar at obs granularity: the merged Perfetto
export stays inside its time window with properly nested span slices and
adds zero live XLA compiles to the traffic it observes; the page
observatory's per-request page-second attribution agrees with the
allocator-side occupancy integral to within 1%; the continuous profiler
samples every Nth step into a bounded ring; fleet fences and FAULTS
injections land on the victim replica's track; and the flight recorder's
new meta block (eviction/drop counters + high-water marks) stays exact.
"""

from __future__ import annotations

import asyncio
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.obs.continuous import (ContinuousProfiler,
                                                 register_profiler)
from githubrepostorag_tpu.obs.hbm import PageObservatory, get_hbm_plane
from githubrepostorag_tpu.obs.ledger import SNAPSHOT_FIELDS, TokenLedger
from githubrepostorag_tpu.obs.recorder import FlightRecorder
from githubrepostorag_tpu.obs.slo import SLOMonitor, get_slo_plane
from githubrepostorag_tpu.obs.timeline import (build_timeline, dump_timeline,
                                               set_fleet_events_provider)
from githubrepostorag_tpu.obs.trace import Span, TraceContext
from githubrepostorag_tpu.serving import Engine, SamplingParams

REPO = Path(__file__).resolve().parents[1]

GREEDY = dict(temperature=0.0, stop_token_ids=())


@pytest.fixture(scope="module")
def tiny():
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
    return cfg, params


def _span(name, trace_id, start, end=None, parent=None):
    sp = Span(name, TraceContext(trace_id, parent, 1), start=start)
    sp.end = end
    return sp


def _prompts(n, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, 6 + i).tolist() for i in range(n)]


def _register_ledger(replica, now, steps=1):
    """One replica with `steps` classified ledger steps ending near now."""
    ledger = TokenLedger(replica, flops_per_tok=1e9, peak_flops=1e12,
                         window_s=600.0)
    snap = {f: 0.0 for f in SNAPSHOT_FIELDS}
    for i in range(steps):
        snap["committed_tokens"] += 4.0
        snap["decode_seconds_total"] += 1e-3
        t0 = now - 0.1 * (steps - i)
        ledger.on_step(dict(snap), t0, t0 + 0.05)
    get_slo_plane().register(replica, ledger=ledger,
                             monitor=SLOMonitor(replica))
    return ledger


# ------------------------------------------------------- recorder meta --


def test_recorder_meta_block_counts_evictions_and_watermarks():
    rec = FlightRecorder(max_traces=2, max_spans_per_trace=3)
    t = time.monotonic()
    for i in range(4):
        for _ in range(5):  # 5 records into a 3-span cap
            rec.record(_span("s", f"{i:032x}", t, t + 0.01))
    meta = rec.summaries_payload()["meta"]
    assert meta["evicted_traces"] == 2
    assert meta["dropped_spans_total"] == 2 * 4
    assert meta["trace_watermark"] == 2
    assert meta["span_watermark"] == 3
    assert meta["trace_ring_utilization"] == 1.0
    assert meta["span_watermark_utilization"] == 1.0
    # clear() resets the marks with the counters — no stale peaks
    rec.clear()
    meta = rec.summaries_payload()["meta"]
    assert meta == {"evicted_traces": 0, "dropped_spans_total": 0,
                    "trace_watermark": 0, "span_watermark": 0,
                    "trace_ring_utilization": 0.0,
                    "span_watermark_utilization": 0.0}


# -------------------------------------------------- continuous profiler --


def test_profiler_samples_every_nth_step_into_a_bounded_ring():
    prof = ContinuousProfiler("rp", sample_every=4, ring=8)
    base = time.monotonic()
    rec = {"decode": 1e-3, "wall": 2e-3, "committed": 4.0}
    for i in range(64):
        prof.on_step(base + i * 0.01, rec, queue=(2, 1, 0), pool=(10, 3))
    samples = prof.samples()
    assert len(samples) == 8  # ring bound, not 64/4
    seqs = [s["seq"] for s in samples]
    assert seqs == list(range(seqs[0], seqs[0] + 32, 4))  # every 4th step
    assert samples[-1]["seq"] == 64
    assert samples[0] == {"t": samples[0]["t"], "seq": seqs[0],
                          "running": 2, "waiting": 1, "parked": 0,
                          "free_pages": 10, "host_pages": 3,
                          "prefill": 0.0, "decode": 1e-3,
                          "kv_migration": 0.0, "kv_transfer": 0.0,
                          "sched_stall": 0.0, "compile": 0.0,
                          "committed": 4.0, "wall": 2e-3, "compiles": 0.0}
    cut = samples[4]["t"]
    assert [s["t"] for s in prof.samples(cut)] == [s["t"] for s in samples[4:]]
    payload = prof.payload()
    assert payload["steps_seen"] == 64
    assert payload["captured"] == 16
    assert payload["retained"] == 8
    assert payload["evicted"] == 8


def test_profiler_sample_every_zero_disables_capture():
    prof = ContinuousProfiler("rz", sample_every=0, ring=8)
    for i in range(16):
        prof.on_step(time.monotonic(), {"wall": 1e-3})
    assert prof.samples() == []
    assert prof.payload()["steps_seen"] == 16


# --------------------------------------------------- hbm observatory ----


def test_hbm_attribution_agrees_with_occupancy_integral(tiny):
    """The acceptance bar: per-request page-second attribution (engine
    hold/release seams) must sum to the allocator-side occupancy integral
    (claims seams) within 1% — same pages, two independent accountings."""
    cfg, params = tiny
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=4,
                 max_seq_len=64, kv_dtype=jnp.float32, decode_burst=4)
    obs = PageObservatory("ra")
    eng.attach_page_observer(obs)
    sp = SamplingParams(max_tokens=8, **GREEDY)
    for wave in range(3):
        eng.generate(_prompts(4, seed=20 + wave), sp)
    now = time.monotonic()
    occ = obs.occupancy_integral(now)
    attr = obs.attributed_page_seconds(now)
    assert occ > 0.0
    assert abs(occ - attr) <= 0.01 * occ, \
        f"attribution {attr} vs occupancy integral {occ} off by >1%"
    a = obs.payload(now)["attribution"]
    assert a["finished_requests"] == 12
    assert a["live_requests"] == 0
    assert a["by_priority"]  # every request charged to a priority class
    assert sum(p["requests"] for p in a["by_priority"].values()) == 12


def test_hbm_plane_pod_payload_and_justification(tiny):
    cfg, params = tiny
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=4,
                 max_seq_len=64, kv_dtype=jnp.float32, decode_burst=4)
    obs = PageObservatory("rb")
    eng.attach_page_observer(obs)
    obs.attach_pool_view(lambda: {"num_pages": 32,
                                  "free": eng._allocator.free_count})
    get_hbm_plane().register("rb", obs)
    eng.generate(_prompts(3, seed=30), SamplingParams(max_tokens=4, **GREEDY))
    now = time.monotonic()
    pod = get_hbm_plane().payload(now)
    assert pod["replica_count"] == 1
    rep = pod["replicas"]["rb"]
    assert rep["pool"]["held_claims"] == 0  # everything recycled
    assert rep["pool"]["held_peak"] > 0
    assert pod["totals"]["occupancy_integral_page_s"] > 0
    just = get_hbm_plane().justification("rb", now)
    assert just is not None and just["held_peak"] == rep["pool"]["held_peak"]
    assert get_hbm_plane().justification("missing", now) is None


# ------------------------------------------------------ timeline export --


async def test_timeline_under_live_traffic_window_nesting_zero_compiles(
        tiny, monkeypatch):
    from tests.helpers.compile_guard import compile_guard, watchdog_counter

    from githubrepostorag_tpu.config import reload_settings
    from githubrepostorag_tpu.serving.async_engine import AsyncEngine

    monkeypatch.setenv("PROFILE_SAMPLE_EVERY", "1")  # sample every step
    reload_settings()
    cfg, params = tiny
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=4,
                 max_seq_len=64, kv_dtype=jnp.float32, decode_burst=8)
    eng.warmup()
    ae = AsyncEngine(eng, replica="rt")
    sp = SamplingParams(max_tokens=8, **GREEDY)
    t_start = time.monotonic()
    try:
        await asyncio.gather(*(ae.generate(p, sp)
                               for p in _prompts(3, seed=40)))
        with compile_guard(watchdog_counter(), label="live traffic"):
            await asyncio.gather(*(ae.generate(p, sp)
                                   for p in _prompts(3, seed=41)))
            # a sampled request-span tree riding the same window
            root = _span("api.request", "ef" * 16, time.monotonic())
            child = Span("engine.decode",
                         TraceContext("ef" * 16, root.span_id, 1),
                         start=time.monotonic())
            child.finish()
            root.finish()
            now = time.monotonic()
            tl = build_timeline(window_s=now - t_start + 1.0, now=now)
    finally:
        await ae.stop()

    md = tl["metadata"]
    assert md["replicas"] == ["rt"]
    src = md["sources"]
    assert src["spans"] >= 2 and src["steps"] > 0 and src["samples"] > 0
    now_us = int(round(now * 1e6))
    t_min_us = int(round((now - md["window_s"]) * 1e6))
    events = [e for e in tl["traceEvents"] if e["ph"] != "M"]
    assert events, "no events from live traffic"
    for e in events:
        assert e["ts"] <= now_us + 1
        # slices may START before the window as long as they reach into it;
        # instants and counters must sit inside it
        end = e["ts"] + e.get("dur", 0)
        assert end >= t_min_us - 1, f"event fully outside window: {e}"
        if e["ph"] in ("i", "C"):
            assert e["ts"] >= t_min_us - 1

    # span slices nest: every child lies within its parent's extent
    spans = {e["args"]["span_id"]: e for e in events
             if e.get("cat") == "span"}
    nested = 0
    for e in spans.values():
        parent = spans.get(e["args"]["parent_id"] or "")
        if parent is None:
            continue
        nested += 1
        assert parent["ts"] <= e["ts"] + 1
        assert (e["ts"] + e["dur"]) <= (parent["ts"] + parent["dur"]) + 2
    assert nested >= 1, "no nested span pair exported"


def test_timeline_fence_and_controller_land_on_their_tracks():
    now = time.monotonic()
    _register_ledger("r0", now)
    _register_ledger("r1", now)
    set_fleet_events_provider(lambda: [
        {"t": now - 0.2, "kind": "router.pick", "replica": "r1",
         "decision": "least_loaded"},
        {"t": now - 0.1, "kind": "fleet.fence", "replica": "r0",
         "failed": 2, "failed_requests": ["req-1", "req-2"]},
    ])
    get_slo_plane().set_controller_info(lambda: {"log": [{
        "t": now - 0.05, "replica": "r0", "action": "failover",
        "reason": "dead", "status": "dispatched",
        "justification": {"liveness": {"thread_alive": False}},
    }]})
    tl = build_timeline(window_s=60.0, now=now)
    events = [e for e in tl["traceEvents"] if e["ph"] != "M"]
    # sorted replicas: r0 -> pid 10, r1 -> pid 11
    fenced = [e for e in events if e.get("cat") == "fence"]
    assert sorted(e["args"]["request_id"] for e in fenced) == ["req-1", "req-2"]
    assert all(e["pid"] == 10 and e["tid"] == 3 for e in fenced), \
        "fenced-request instants must land on the VICTIM replica's track"
    ctrl = [e for e in events if e.get("cat") == "controller"]
    assert len(ctrl) == 1 and ctrl[0]["name"] == "ctrl.failover"
    assert ctrl[0]["pid"] == 3
    assert ctrl[0]["args"]["justification"]["liveness"]["thread_alive"] is False
    picks = [e for e in events if e.get("cat") == "fleet"
             and e["name"] == "router.pick"]
    assert picks and picks[0]["pid"] == 2
    assert tl["metadata"]["sources"]["fenced_requests"] == 2


def test_timeline_fault_instant_attributed_to_victim_replica(monkeypatch):
    from githubrepostorag_tpu.config import reload_settings
    from githubrepostorag_tpu.resilience.faults import get_registry, reset_faults

    _register_ledger("r0", time.monotonic())
    monkeypatch.setenv("FAULTS", "fleet.step.r0:error")
    reload_settings()
    reset_faults()
    action, _ = get_registry().decide("fleet.step.r0")
    assert action == "error"
    tl = build_timeline(window_s=60.0)
    faults = [e for e in tl["traceEvents"] if e.get("cat") == "fault"]
    assert len(faults) == 1
    assert faults[0]["name"] == "fault.error"
    assert faults[0]["args"]["site"] == "fleet.step.r0"
    assert faults[0]["pid"] == 10, \
        "a fault whose site names a replica belongs on that replica's track"


def test_timeline_window_bounds_and_max_events_drop_oldest():
    now = time.monotonic()
    ledger = TokenLedger("r0", flops_per_tok=1e9, peak_flops=1e12,
                         window_s=600.0)
    snap = {f: 0.0 for f in SNAPSHOT_FIELDS}
    for i in range(8):
        snap["committed_tokens"] += 4.0
        t0 = now - 100.0 + i * 10.0  # steps at -100s .. -30s
        ledger.on_step(dict(snap), t0, t0 + 0.05)
    get_slo_plane().register("r0", ledger=ledger, monitor=SLOMonitor("r0"))

    # a 35s window keeps only the newest step (t_end ~ now-30)
    tl = build_timeline(window_s=35.0, now=now)
    assert tl["metadata"]["sources"]["steps"] == 1
    full = build_timeline(window_s=120.0, now=now)
    assert full["metadata"]["sources"]["steps"] == 8
    assert full["metadata"]["dropped_events"] == 0

    total = len([e for e in full["traceEvents"] if e["ph"] != "M"])
    assert total >= 16  # X slice + C counter per step, plus ambient sources
    capped = build_timeline(window_s=120.0, now=now, max_events=3)
    non_meta = [e for e in capped["traceEvents"] if e["ph"] != "M"]
    assert len(non_meta) == 3
    assert capped["metadata"]["dropped_events"] == total - 3
    # oldest dropped, newest kept
    assert min(e["ts"] for e in non_meta) > int((now - 60.0) * 1e6)


def test_dump_timeline_writes_a_perfetto_loadable_file(tmp_path):
    import json as _json

    _register_ledger("r0", time.monotonic())
    path = tmp_path / "timeline.json"
    trace = dump_timeline(str(path), window_s=60.0)
    on_disk = _json.loads(path.read_text())
    assert on_disk["displayTimeUnit"] == "ms"
    assert on_disk["traceEvents"] == trace["traceEvents"]
    phs = {e["ph"] for e in on_disk["traceEvents"]}
    assert "M" in phs and {"X", "C"} & phs


def test_debug_timeline_schema_matches_committed_golden():
    import os

    proc = subprocess.run(
        [sys.executable, "scripts/check_timeline_schema.py"],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
