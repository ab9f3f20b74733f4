"""Fleet SLO plane (obs/ledger.py + obs/slo.py): token-ledger bucket
classification and limiter attribution, SRE multi-window burn-rate state
machine, per-replica metric federation under dp=2, the FAULTS-driven chaos
path (deadline storm -> ok -> critical -> ok with counted transitions),
and the API observing the admission hint on its shedding path."""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.config import reload_settings
from githubrepostorag_tpu.metrics import DECODE_TOKENS, JOBS_SHED, counter_value
from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.obs.ledger import (
    BUCKETS,
    SNAPSHOT_FIELDS,
    TokenLedger,
    flops_per_token,
)
from githubrepostorag_tpu.obs.slo import (
    CRITICAL,
    OK,
    WARN,
    SLOMonitor,
    get_slo_plane,
)
from githubrepostorag_tpu.parallel import MeshPlan
from githubrepostorag_tpu.resilience import admission_hint, should_shed
from githubrepostorag_tpu.resilience.admission import (
    clear_hint_provider,
    set_hint_provider,
)
from githubrepostorag_tpu.resilience.faults import reset_faults
from githubrepostorag_tpu.resilience.policy import Deadline, deadline_scope
from githubrepostorag_tpu.serving import Engine, SamplingParams
from githubrepostorag_tpu.serving.multi_engine import MultiAsyncEngine, dp_submeshes


def _snap(**kw) -> dict[str, float]:
    """A cumulative engine snapshot with every field defaulted to zero."""
    base = {f: 0.0 for f in SNAPSHOT_FIELDS}
    base.update(kw)
    return base


# ------------------------------------------------------------ token ledger


def test_ledger_bucket_classification_and_goodput():
    led = TokenLedger("t0", window_s=60.0)
    led.on_step(_snap(prefill_tokens=20, prefill_seconds_total=0.3),
                100.0, 100.4)
    led.on_step(_snap(prefill_tokens=20, prefill_seconds_total=0.3,
                      committed_tokens=8, decode_seconds_total=0.55),
                100.5, 100.8)  # 0.1s gap after the previous step_end
    snap = led.snapshot(now=100.8)
    assert snap["steps"] == 2
    assert snap["bucket_seconds"]["prefill"] == pytest.approx(0.3)
    assert snap["bucket_seconds"]["decode"] == pytest.approx(0.55)
    assert snap["bucket_seconds"]["sched_stall"] == pytest.approx(0.1)
    assert snap["bucket_seconds"]["compile"] == 0.0
    assert snap["tokens"]["committed"] == 8
    assert snap["tokens"]["prefill"] == 20
    # elapsed = now - first step_end = 0.4s -> 8 committed / 0.4
    assert snap["goodput_tok_s"] == pytest.approx(20.0)
    assert set(snap["bucket_seconds"]) == set(BUCKETS)


def test_ledger_compile_bucket_is_unaccounted_step_time():
    led = TokenLedger("t1", window_s=60.0)
    # a fresh XLA compile: 2.0s wall but only 0.2s of measured phase time
    led.on_step(_snap(prefill_seconds_total=0.2), 10.0, 12.0, compiles=1)
    snap = led.snapshot(now=12.0)
    assert snap["bucket_seconds"]["compile"] == pytest.approx(1.8)
    assert snap["limiter"] == "compile"


def test_ledger_limiter_hbm_pages_when_admission_blocked():
    led = TokenLedger("t2", window_s=60.0)
    led.on_step(_snap(decode_seconds_total=0.1, admission_blocked_steps=1),
                10.0, 10.1)
    led.on_step(_snap(decode_seconds_total=0.2, admission_blocked_steps=2),
                10.1, 10.2)
    assert led.snapshot(now=10.2)["limiter"] == "hbm_pages"


def test_ledger_limiter_swap_wait_when_migration_dominates():
    led = TokenLedger("t3", window_s=60.0)
    led.on_step(_snap(decode_seconds_total=0.4, migration_seconds_total=0.6),
                10.0, 11.0)
    assert led.snapshot(now=11.0)["limiter"] == "swap_wait"


def test_ledger_limiter_stall_when_gaps_dominate():
    led = TokenLedger("t4", window_s=60.0)
    led.on_step(_snap(decode_seconds_total=0.1), 100.0, 100.1)
    led.on_step(_snap(decode_seconds_total=0.2), 101.0, 101.1)  # 0.9s gap
    assert led.snapshot(now=101.1)["limiter"] == "stall"


def test_ledger_idle_gap_is_not_a_scheduler_stall():
    led = TokenLedger("t5", window_s=60.0)
    led.on_step(_snap(decode_seconds_total=0.1), 20.0, 20.1)
    led.idle(now=20.5)  # driver went idle between requests
    led.on_step(_snap(decode_seconds_total=0.2), 21.0, 21.1)
    assert led.snapshot(now=21.1)["bucket_seconds"]["sched_stall"] == 0.0


def test_ledger_window_prunes_and_goodput_decays_to_zero():
    led = TokenLedger("t6", window_s=1.0)
    led.on_step(_snap(committed_tokens=8, decode_seconds_total=0.2),
                10.0, 10.2)
    assert led.snapshot(now=10.4)["goodput_tok_s"] > 0
    stale = led.snapshot(now=12.0)  # the only step fell out of the window
    assert stale["steps"] == 0
    assert stale["goodput_tok_s"] == 0.0
    assert stale["limiter"] == "none"


def test_ledger_wasted_token_accounting():
    led = TokenLedger("t7", window_s=60.0)
    led.on_step(_snap(committed_tokens=6, reaped_tokens=2), 10.0, 10.3)
    tokens = led.snapshot(now=10.3)["tokens"]
    assert tokens["deadline_reaped"] == 2
    # wasted = 2 reaped / (6 committed + 2 wasted)
    assert tokens["wasted_fraction"] == pytest.approx(0.25)


def test_ledger_mfu_from_flops_per_token():
    led = TokenLedger("t8", flops_per_tok=1e9, peak_flops=1e12, window_s=60.0)
    led.on_step(_snap(prefill_tokens=10, prefill_seconds_total=0.4), 0.0, 0.5)
    led.on_step(_snap(prefill_tokens=10, prefill_seconds_total=0.4,
                      committed_tokens=10, decode_seconds_total=0.4),
                0.5, 1.0)
    snap = led.snapshot(now=1.0)
    # 20 tokens x 1e9 flops over 0.5s x 1e12 peak = 4% MFU
    assert snap["mfu"] == pytest.approx(0.04)
    assert snap["goodput_tok_s"] == pytest.approx(20.0)


def test_flops_per_token_estimate_is_parameter_scaled():
    cfg = Qwen2Config.tiny()
    fpt = flops_per_token(cfg)
    assert fpt > 2.0 * cfg.vocab_size * cfg.hidden_size  # at least the lm head
    assert fpt < 1e12  # sane for a tiny config


# ----------------------------------------------------- burn-rate monitor


def test_monitor_trips_critical_then_recovers(monkeypatch):
    monkeypatch.setenv("SLO_WINDOWS", "1,5")
    reload_settings()
    mon = SLOMonitor("m0")
    t0 = 1000.0
    for i in range(5):
        mon.observe(deadline_missed=True, now=t0 + 0.1 * i)
    # burn = (5/5 miss) / 0.05 budget = 20 >= 14.4 on BOTH windows
    assert mon.worst_state() == CRITICAL
    counts = mon.transition_counts()
    assert counts[("deadline_miss", "interactive", "critical")] == 1

    # the bad burst ages out of the long window; good traffic replaces it
    for i in range(3):
        mon.observe(deadline_missed=False, now=t0 + 10.0 + 0.1 * i)
    assert mon.worst_state() == OK
    counts = mon.transition_counts()
    assert counts[("deadline_miss", "interactive", "ok")] == 1

    payload = mon.payload(now=t0 + 10.5)
    assert payload["replica"] == "m0"
    assert payload["state"] == "ok"
    assert payload["transitions"] == 2
    row = next(r for r in payload["objectives"]
               if r["objective"] == "deadline_miss")
    assert [b["window_s"] for b in row["burn"]] == [1.0, 5.0]
    assert all(b["rate"] == 0.0 for b in row["burn"])


def test_monitor_warn_between_thresholds(monkeypatch):
    monkeypatch.setenv("SLO_WINDOWS", "1,5")
    reload_settings()
    mon = SLOMonitor("m1")
    t0 = 2000.0
    # 5/10 missed -> burn = 0.5 / 0.05 = 10: past warn (6), short of 14.4
    for i in range(10):
        mon.observe(deadline_missed=(i % 2 == 0), now=t0 + 0.05 * i)
    assert mon.worst_state() == WARN
    plane = get_slo_plane()
    plane.register("m1", monitor=mon)
    assert plane.admission_hint() == "throttle"
    assert admission_hint() == "throttle"
    assert not should_shed()


def test_monitor_requires_both_windows_to_alert(monkeypatch):
    """The long window filters blips: a short bad burst trips the 1s window
    but not the 5s one, so the state machine must stay ok."""
    monkeypatch.setenv("SLO_WINDOWS", "1,5")
    reload_settings()
    mon = SLOMonitor("m2")
    t0 = 3000.0
    for i in range(6):
        mon.observe(deadline_missed=False, now=t0 + 0.05 * i)
    for i in range(2):  # blip: short window is 100% bad, long is 2/8
        mon.observe(deadline_missed=True, now=t0 + 2.0 + 0.05 * i)
    assert mon.worst_state() == OK
    assert mon.transition_counts() == {}


def test_monitor_ttft_and_tpot_objectives(monkeypatch):
    monkeypatch.setenv("SLO_WINDOWS", "1,5")
    monkeypatch.setenv("SLO_TPOT_MS", "100")
    reload_settings()
    mon = SLOMonitor("m3")
    t0 = 4000.0
    for i in range(5):
        mon.observe("batch", ttft_s=0.01, tpot_s=0.5, now=t0 + 0.05 * i)
    payload = mon.payload(now=t0 + 0.3)
    by_name = {r["objective"]: r for r in payload["objectives"]
               if r["klass"] == "batch"}
    assert by_name["tpot"]["state"] == "critical"  # 100% over 100ms budget 5%
    assert by_name["ttft_p99"]["state"] == "ok"
    assert by_name["tpot"]["events"] == 5 and by_name["tpot"]["bad"] == 5


def test_monitor_longctx_class_has_relaxed_thresholds(monkeypatch):
    """A 5s TTFT is a hard interactive miss but comfortably inside the
    longctx objectives — same monitor, per-class threshold override."""
    monkeypatch.setenv("SLO_WINDOWS", "1,5")
    monkeypatch.setenv("SLO_TTFT_P99_MS", "1000")
    monkeypatch.setenv("SLO_LONGCTX_TTFT_P99_MS", "45000")
    reload_settings()
    mon = SLOMonitor("m4")
    t0 = 5000.0
    for i in range(5):
        mon.observe("interactive", ttft_s=5.0, now=t0 + 0.05 * i)
        mon.observe("longctx", ttft_s=5.0, now=t0 + 0.05 * i)
    payload = mon.payload(now=t0 + 0.3)
    rows = {(r["objective"], r["klass"]): r for r in payload["objectives"]}
    assert rows[("ttft_p99", "interactive")]["state"] == "critical"
    assert rows[("ttft_p99", "longctx")]["state"] == "ok"
    assert rows[("ttft_p99", "longctx")]["bad"] == 0


def test_slo_payload_config_includes_longctx_thresholds():
    plane = get_slo_plane()
    cfg = plane.slo_payload()["config"]
    assert cfg["longctx_ttft_p50_ms"] > cfg["ttft_p50_ms"]
    assert cfg["longctx_ttft_p99_ms"] > cfg["ttft_p99_ms"]
    assert cfg["longctx_tpot_ms"] >= cfg["tpot_ms"]


# ------------------------------------------------------------ SLO plane


def test_plane_fleet_payload_federates_ledger_and_monitor():
    plane = get_slo_plane()
    led = TokenLedger("p0", window_s=60.0)
    now = time.monotonic()  # fleet_payload snapshots at real monotonic time
    led.on_step(_snap(committed_tokens=10, decode_seconds_total=0.2,
                      reaped_tokens=1), now - 0.5, now)
    mon = SLOMonitor("p0")
    mon.observe(deadline_missed=False)
    plane.register("p0", ledger=led, monitor=mon,
                   stats=lambda: {"num_running": 0})

    slo = plane.slo_payload()
    assert slo["admission_hint"] == "accept"
    assert set(slo["config"]) >= {"windows_s", "burn_warn", "burn_critical",
                                  "ttft_p99_ms", "deadline_miss_budget"}
    assert [r["replica"] for r in slo["replicas"]] == ["p0"]

    fleet = plane.fleet_payload()
    assert fleet["fleet"]["replicas"] == 1
    assert fleet["fleet"]["committed_tokens"] == 10
    assert fleet["fleet"]["wasted_tokens"] == 1
    rep = fleet["replicas"][0]
    assert rep["ledger"]["tokens"]["committed"] == 10
    assert rep["slo"]["state"] == "ok"
    assert rep["stats"] == {"num_running": 0}

    plane.unregister("p0")
    assert plane.fleet_payload()["fleet"]["replicas"] == 0


def test_admission_hint_is_failure_open():
    assert admission_hint() == "accept"  # no provider registered
    set_hint_provider(lambda: 1 / 0)
    try:
        assert admission_hint() == "accept"  # broken plane never blocks
    finally:
        clear_hint_provider()
    set_hint_provider(lambda: "bogus")
    try:
        assert admission_hint() == "accept"  # unknown hints are ignored
    finally:
        clear_hint_provider()


# ------------------------------------------- dp=2 metrics federation


@pytest.fixture(scope="module")
def tiny():
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    return cfg, params


def _engine(params, cfg, mesh=None):
    return Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=4,
                  max_seq_len=64, kv_dtype=jnp.float32, decode_burst=8,
                  mesh=mesh)


def _prompts(n):
    rng = np.random.default_rng(11)
    return [rng.integers(0, 512, 6 + i).tolist() for i in range(n)]


async def test_dp2_replica_series_distinct_and_summed(tiny):
    """Regression for the replica-aliasing bug: with dp=2 every engine
    driver used to write the same unlabeled series; now r0/r1 must be
    distinct AND sum to the true total."""
    cfg, params = tiny
    meshes, _ = dp_submeshes(MeshPlan(tp=2, dp=2))
    multi = MultiAsyncEngine([_engine(params, cfg, mesh=m) for m in meshes])
    base = {r: counter_value(DECODE_TOKENS, replica=r) for r in ("r0", "r1")}
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
    try:
        results = await asyncio.gather(
            *(multi.generate(p, sp) for p in _prompts(4)))
    finally:
        await multi.stop()
    total = sum(len(r.output_tokens) for r in results)
    assert total == 32
    delta = {r: counter_value(DECODE_TOKENS, replica=r) - base[r]
             for r in ("r0", "r1")}
    assert delta["r0"] > 0 and delta["r1"] > 0  # distinct per-replica series
    assert delta["r0"] + delta["r1"] == total  # no double count, no aliasing

    fleet = multi.fleet()
    assert fleet["fleet"]["replicas"] == 2
    assert [r["replica"] for r in fleet["replicas"]] == ["r0", "r1"]
    committed = sum(r["ledger"]["tokens"]["committed"]
                    for r in fleet["replicas"])
    assert committed == total
    for rep in fleet["replicas"]:
        assert rep["slo"]["replica"] == rep["replica"]
        assert "free_pages" in rep["stats"]


# ------------------------------------------------------------ chaos path


def _build_llm(replica: str):
    from githubrepostorag_tpu.llm import InProcessLLM
    from githubrepostorag_tpu.serving.async_engine import AsyncEngine
    from githubrepostorag_tpu.serving.tokenizer import ByteTokenizer

    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=128, page_size=8,
                 max_seq_len=256, prefill_chunk=64, kv_dtype=jnp.float32)
    ae = AsyncEngine(eng, replica=replica)
    return InProcessLLM(ae, ByteTokenizer(), default_max_tokens=8,
                        default_temperature=0.0, context_window=128), ae


def test_chaos_deadline_storm_trips_critical_then_recovers(monkeypatch):
    """End-to-end chaos drill: a FAULTS-injected llm.complete delay burns
    most of each request's deadline budget, the engine reaps the rows, the
    deadline-miss burn rate trips ok->critical, the admission hint flips to
    shed, and clearing the fault recovers critical->ok — with every
    transition counted."""
    # tight windows so the drill runs in seconds; park the latency
    # objectives so only the (deterministic) deadline-miss one can trip
    monkeypatch.setenv("SLO_WINDOWS", "0.5,2")
    monkeypatch.setenv("SLO_TTFT_P50_MS", "60000")
    monkeypatch.setenv("SLO_TTFT_P99_MS", "60000")
    monkeypatch.setenv("SLO_TPOT_MS", "60000")
    reload_settings()
    llm, ae = _build_llm("chaos0")
    try:
        llm.complete("warm the engine compile cache")  # no faults yet
        assert ae.slo.worst_state() == OK

        monkeypatch.setenv("FAULTS", "llm.complete:delay=0.45")
        reload_settings()
        reset_faults()
        for _ in range(4):
            with deadline_scope(Deadline(0.51)):
                # the fault eats 0.45s of the 0.51s budget before submission;
                # 200 tokens cannot decode in ~60ms -> the engine reaps the
                # row at a step boundary (finish_reason="deadline")
                out = llm.complete("deadline storm request", max_tokens=200)
            assert "reaped" in out
        ae.slo.maybe_refresh(force=True)  # don't race the 0.25s rate limit
        assert ae.slo.worst_state() == CRITICAL
        counts = ae.slo.transition_counts()
        assert counts.get(("deadline_miss", "interactive", "critical"), 0) >= 1
        # the hint the API's shedding path consults
        assert admission_hint() == "shed"
        assert should_shed()

        monkeypatch.setenv("FAULTS", "")
        reload_settings()
        reset_faults()
        deadline = time.monotonic() + 20.0
        while ae.slo.worst_state() != OK and time.monotonic() < deadline:
            assert "Error" not in llm.complete("healthy traffic", max_tokens=4)
            time.sleep(0.05)
        assert ae.slo.worst_state() == OK
        counts = ae.slo.transition_counts()
        assert counts.get(("deadline_miss", "interactive", "ok"), 0) >= 1
        assert admission_hint() == "accept"
        assert not should_shed()
    finally:
        llm.close()


# ------------------------------------------------- API shedding path


async def test_api_sheds_jobs_while_hint_is_shed():
    from tests.test_api_worker import _with_service

    class _CriticalMonitor:
        def worst_state(self):
            return CRITICAL

    plane = get_slo_plane()
    plane.register("storm", monitor=_CriticalMonitor())
    shed_before = counter_value(JOBS_SHED)

    async def body(session, base, api, worker):
        resp = await session.post(f"{base}/rag/jobs", json={"query": "q"})
        assert resp.status == 429
        payload = await resp.json()
        assert "SLO" in payload["error"]
        assert resp.headers.get("Retry-After") == "1"
        # burn recovers -> hint back to accept -> admission resumes
        plane.unregister("storm")
        resp2 = await session.post(
            f"{base}/rag/jobs", json={"query": "how are jobs created?"})
        assert resp2.status != 429
        assert "job_id" in await resp2.json()

    await _with_service(body)
    assert counter_value(JOBS_SHED) == shed_before + 1
