"""Preempt-to-host SLO scheduling (serving/engine.py + serving/kv_cache.py).

Pins the PR's acceptance bar at engine granularity: a saturated tiered
engine parks a batch-class victim's KV pages to the host tier so a
protected (interactive) arrival admits immediately, then resumes the
victim through the claim/fault-in machinery — decode continues
token-identically with ZERO recomputed prompt tokens.  Also covers the
nasty lifecycle corner (deadline reap while parked), the per-class headroom /
critical-pause admission ladder, and the per-class decision table's
counted fail-open.
"""

from __future__ import annotations

import time

import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.metrics import ADMISSION_FAILOPEN, counter_value
from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.resilience import admission
from githubrepostorag_tpu.serving import Engine, SamplingParams


@pytest.fixture(scope="module")
def tiny():
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    return cfg, params


def _engine(params, cfg, **kw):
    # tiny tiered pool: two batch rows oversubscribe it, so a protected
    # arrival has no admission path except preemption
    defaults = dict(
        max_num_seqs=2, num_pages=16, page_size=4, max_seq_len=64,
        prefill_chunk=16, kv_dtype=jnp.float32, decode_burst=4,
        kv_tier="on", kv_host_pool_pages=64, preempt="on",
        default_priority="interactive", protected_priority="interactive",
    )
    defaults.update(kw)
    return Engine(params, cfg, **defaults)


def _drain(eng, results, max_steps=400):
    steps = 0
    while eng.has_work():
        results.extend(eng.step())
        steps += 1
        assert steps < max_steps, "engine wedged"
    eng.flush_kv_migrations()
    return results


GREEDY = dict(temperature=0.0, stop_token_ids=())


# --------------------------------------------------- preempt round trip --


def test_preempt_resumes_token_identical_with_zero_recomputed_prefill(tiny):
    """The tentpole bar: victim parks to host, protected admits, victim
    resumes via prefix claim + fault-in and finishes byte-identical to an
    unloaded reference — no recomputed prompt tokens, all pages recycled."""
    cfg, params = tiny
    prompts = {
        "b0": list(range(1, 9)),
        "b1": list(range(21, 29)),
        "hot": list(range(41, 49)),
    }
    sp_batch = SamplingParams(max_tokens=16, **GREEDY)
    sp_hot = SamplingParams(max_tokens=8, **GREEDY)

    # unloaded reference: every request alone on a plain engine
    ref_eng = _engine(params, cfg, kv_tier="off", preempt="off")
    ref = {
        name: ref_eng.generate([p], sp_batch if name != "hot" else sp_hot)[0]
        .output_tokens
        for name, p in prompts.items()
    }

    eng = _engine(params, cfg)
    results = []
    rids = {
        name: eng.add_request(prompts[name], sp_batch, priority="batch")
        for name in ("b0", "b1")
    }
    # run the batch pair past their prompts so both are eligible victims
    for _ in range(3):
        results.extend(eng.step())
    assert eng.num_running == 2 and not eng._free_rows

    rids["hot"] = eng.add_request(prompts["hot"], sp_hot)  # default class
    _drain(eng, results)

    assert eng.preemptions == 1
    assert eng.preempted_pages > 0
    assert eng.preempt_resumes == 1
    # resume recomputes at most the partial tail page — and the victim was
    # parked at a step boundary past its prompt, so NO prompt recompute
    assert eng.resume_recomputed_prompt_tokens == 0
    parked_events = eng.drain_park_events()
    assert len(parked_events) == 1 and parked_events[0] in (rids["b0"], rids["b1"])
    assert eng.drain_park_events() == []  # drain is consume-once

    by_id = {r.request_id: r for r in results}
    assert set(by_id) == set(rids.values())
    for name, rid in rids.items():
        res = by_id[rid]
        assert res.finish_reason == "length"
        assert res.output_tokens == ref[name], name
        # the parked victim reports it; the others do not
    assert sum(by_id[r].preempted for r in rids.values()) == 1
    assert by_id[rids["hot"]].preempted == 0
    # prompts survive the park/fold round trip un-mutated in the result
    for name, rid in rids.items():
        assert by_id[rid].prompt_tokens == prompts[name]

    assert eng._allocator.free_count == eng._allocator.num_pages


def test_parked_victim_deadline_reaped_frees_both_tiers_once(tiny):
    """A victim whose deadline lapses while parked is reaped at the next
    step boundary with finish_reason 'deadline'.  Its device pages were
    already returned at park time — the reap must NOT free them again —
    and the pool ends whole."""
    cfg, params = tiny
    eng = _engine(params, cfg)
    results = []
    # the co-resident row is protected (never a victim), so the preempt
    # pass must pick the deadline-bearing batch request; 8+24 tokens each
    # = 8 pages each — together they hold the entire 16-page pool
    prot0 = eng.add_request(list(range(1, 9)),
                            SamplingParams(max_tokens=24, **GREEDY))
    victim = eng.add_request(list(range(21, 29)),
                             SamplingParams(max_tokens=24, **GREEDY),
                             priority="batch",
                             deadline_s=time.monotonic() + 0.5)
    for _ in range(3):
        results.extend(eng.step())

    # critical pressure blocks un-park (anti-thrash), holding the victim
    # in the parked state until its deadline lapses
    eng.set_class_pressure({"interactive": 2})
    hot = eng.add_request(list(range(41, 49)),
                          SamplingParams(max_tokens=8, **GREEDY))
    steps = 0
    while eng.preemptions == 0:
        results.extend(eng.step())
        steps += 1
        assert steps < 50, "saturated protected arrival never preempted"
    assert eng.drain_park_events() == [victim]
    assert eng.num_parked == 1

    time.sleep(0.6)  # let the parked victim's deadline lapse
    results.extend(eng.step())
    assert eng.num_parked == 0 and eng.deadline_reaps == 1
    eng.set_class_pressure({})
    _drain(eng, results)

    by_id = {r.request_id: r for r in results}
    res = by_id[victim]
    assert res.finish_reason == "deadline"
    assert res.preempted == 1
    assert len(res.output_tokens) < 24
    assert by_id[prot0].finish_reason == "length"
    assert by_id[hot].finish_reason == "length"
    assert eng.preempt_resumes == 0  # reaped, never resumed
    # both tiers freed exactly once: pool whole, and the pool still serves
    assert eng._allocator.free_count == eng._allocator.num_pages
    out = eng.generate([list(range(61, 69))],
                       SamplingParams(max_tokens=4, **GREEDY))[0]
    assert len(out.output_tokens) == 4
    assert eng._allocator.free_count == eng._allocator.num_pages


# ------------------------------------------------- admission ladder -----


def test_protected_arrival_jumps_batch_waiters(tiny):
    cfg, params = tiny
    eng = _engine(params, cfg, max_num_seqs=1)
    sp = SamplingParams(max_tokens=4, **GREEDY)
    eng.add_request(list(range(1, 5)), sp, priority="batch")
    b = eng.add_request(list(range(11, 15)), sp, priority="batch")
    hot = eng.add_request(list(range(21, 25)), sp)
    # protected arrival inserted ahead of the queued batch waiter
    order = [r.request_id for r in eng._waiting]
    assert order.index(hot) < order.index(b)
    results = _drain(eng, [])
    assert {r.request_id for r in results} >= {b, hot}


def test_warn_pressure_doubles_batch_headroom(tiny):
    """warn on the protected class tightens batch admission (headroom
    doubles); clearing the pressure re-opens the gate."""
    cfg, params = tiny
    eng = _engine(params, cfg, num_pages=8, preempt="off",
                  preempt_headroom_pages=3)
    sp = SamplingParams(max_tokens=4, **GREEDY)
    # base headroom: need 2 + headroom 3 <= 8 free -> admits
    # warn headroom: need 2 + headroom 6 > 8 free -> parks at the gate
    eng.set_class_pressure({"interactive": 1})
    rid = eng.add_request(list(range(1, 9)), sp, priority="batch")
    results = eng.step()
    assert results == [] and eng.num_waiting == 1 and eng.num_running == 0
    eng.set_class_pressure({})
    results = _drain(eng, list(results))
    assert [r.request_id for r in results] == [rid]
    assert len(results[0].output_tokens) == 4


def test_critical_pressure_pauses_batch_admission_entirely(tiny):
    """critical on the protected class stops batch intake even with a
    near-empty pool; protected traffic still admits."""
    cfg, params = tiny
    eng = _engine(params, cfg)
    sp = SamplingParams(max_tokens=4, **GREEDY)
    eng.set_class_pressure({"interactive": 2})
    b = eng.add_request(list(range(1, 9)), sp, priority="batch")
    hot = eng.add_request(list(range(21, 29)), sp)
    results = eng.step()
    assert eng.num_running >= 1 or any(r.request_id == hot for r in results)
    assert all(r.request_id != b for r in results)
    # the batch request is still parked at the gate, not shed
    assert any(r.request_id == b for r in eng._waiting)
    eng.set_class_pressure({"interactive": 0})
    results = _drain(eng, list(results))
    got = {r.request_id for r in results}
    assert {b, hot} <= got  # batch finished, not died


# ------------------------------------------- per-class decision table ---


@pytest.fixture()
def _clean_admission():
    yield
    admission.clear_table_provider()
    admission.clear_hint_provider()


def test_admission_table_per_class_decisions(_clean_admission):
    admission.set_table_provider(
        lambda: {"interactive": admission.ACCEPT, "batch": admission.SHED})
    assert admission.admission_decision("batch") == admission.SHED
    assert admission.should_shed("batch")
    assert admission.admission_decision("interactive") == admission.ACCEPT
    assert not admission.should_shed("interactive")


def test_admission_unknown_class_inherits_fleet_hint(_clean_admission):
    admission.set_table_provider(lambda: {"batch": admission.THROTTLE})
    admission.set_hint_provider(lambda: admission.SHED)
    # a brand-new label falls back to the legacy worst-state hint rather
    # than being silently accepted
    assert admission.admission_decision("research") == admission.SHED
    assert admission.should_shed(None)


def test_admission_table_fails_open_logged_and_counted(_clean_admission):
    def boom():
        raise RuntimeError("slo plane fell over")

    before = counter_value(ADMISSION_FAILOPEN)
    admission.set_table_provider(boom)
    assert admission.admission_table() == {}
    assert admission.admission_decision("batch") == admission.ACCEPT
    assert not admission.should_shed("batch")
    assert counter_value(ADMISSION_FAILOPEN) > before

    # garbage shapes fail open too: non-dict, and unknown decision strings
    admission.set_table_provider(lambda: ["shed"])
    assert admission.admission_table() == {}
    admission.set_table_provider(lambda: {"batch": "explode"})
    assert admission.admission_table() == {}  # bad decision dropped
    assert counter_value(ADMISSION_FAILOPEN) >= before + 3
