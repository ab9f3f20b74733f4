"""Disaggregated prefill/decode serving (serving/disagg.py + the
MultiAsyncEngine handoff): role-assignment viability, fused-vs-disagg
token identity (including prefix-dedup repeat traffic and int8 KV), the
fused fallback when the transfer dies,
role-aware fleet stats merging, and the zero-live-recompile contract
across mixed handoff / dedup / short-prompt traffic.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.config import reload_settings
from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.serving import Engine, SamplingParams
from githubrepostorag_tpu.serving.disagg import InProcessTransport, assign_roles
from githubrepostorag_tpu.serving.multi_engine import MultiAsyncEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    return cfg, params


def _engine(params, cfg, **kw):
    defaults = dict(max_num_seqs=2, num_pages=32, page_size=4, max_seq_len=64,
                    kv_dtype=jnp.float32, decode_burst=8,
                    kv_tier="on", kv_host_pool_pages=64)
    defaults.update(kw)
    return Engine(params, cfg, **defaults)


def _fleet(monkeypatch, params, cfg, n=3, prefill=1, **kw):
    """A DISAGG=on fleet: env is set + settings reloaded BEFORE construction
    because assign_roles reads get_settings() at fleet-build time."""
    monkeypatch.setenv("DISAGG", "on")
    monkeypatch.setenv("DISAGG_PREFILL_REPLICAS", str(prefill))
    reload_settings()
    return MultiAsyncEngine([_engine(params, cfg, **kw) for _ in range(n)])


def _prompts(n, seed=11):
    rng = np.random.default_rng(seed)
    # 12+ tokens at page_size=4: every prompt has >=2 full shippable pages
    return [rng.integers(0, 512, 12 + i).tolist() for i in range(n)]


def _sp(max_tokens=8):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0,
                          stop_token_ids=())


# -------------------------------------------------------- role assignment --


def test_assign_roles_off_or_unviable_stays_fused(tiny, monkeypatch):
    cfg, params = tiny
    # DISAGG=off (the default): everything fused, disagg plane dark
    multi = MultiAsyncEngine([_engine(params, cfg) for _ in range(3)])
    assert multi.disagg_stats() == {
        "enabled": False, "prefill_replicas": [], "decode_replicas": [],
        "handoffs": 0, "pages_shipped": 0, "pages_deduped": 0,
        "fallbacks": {}, "transport": None,
    }
    assert all(ae.role == "fused" for ae in multi._engines)

    # DISAGG=on but only one replica: nothing to split
    solo = _fleet(monkeypatch, params, cfg, n=1)
    assert not solo.disagg_stats()["enabled"]
    assert solo._engines[0].role == "fused"

    # DISAGG=on but an untiered replica: the handoff has no host tier to
    # move pages through, so the whole fleet stays fused
    monkeypatch.setenv("DISAGG", "on")
    reload_settings()
    mixed = MultiAsyncEngine([_engine(params, cfg),
                              _engine(params, cfg, kv_tier="off")])
    assert not mixed.disagg_stats()["enabled"]
    assert all(ae.role == "fused" for ae in mixed._engines)


def test_assign_roles_splits_and_clamps(tiny, monkeypatch):
    cfg, params = tiny
    multi = _fleet(monkeypatch, params, cfg, n=3, prefill=1)
    ds = multi.disagg_stats()
    assert ds["enabled"]
    assert ds["prefill_replicas"] == ["r0"]
    assert ds["decode_replicas"] == ["r1", "r2"]
    assert ds["transport"]["kind"] == "in_process"

    # DISAGG_PREFILL_REPLICAS is clamped so >=1 decode replica remains
    greedy = _fleet(monkeypatch, params, cfg, n=3, prefill=5)
    ds = greedy.disagg_stats()
    assert ds["prefill_replicas"] == ["r0", "r1"]
    assert ds["decode_replicas"] == ["r2"]


def test_assign_roles_keeps_spares_fused(tiny, monkeypatch):
    cfg, params = tiny
    monkeypatch.setenv("DISAGG", "on")
    monkeypatch.setenv("DISAGG_PREFILL_REPLICAS", "1")
    reload_settings()
    engines = [_engine(params, cfg) for _ in range(3)]
    multi = MultiAsyncEngine(engines, spares=1)
    roles = {ae.replica: ae.role for ae in multi._engines}
    assert list(roles.values()).count("prefill") == 1
    assert list(roles.values()).count("decode") == 1
    # the warm spare is neither: it joins as a decoder only when activated
    spare = [ae for ae in multi._engines if ae.lifecycle != "active"]
    assert len(spare) == 1 and spare[0].role == "fused"


# -------------------------------------------------------------- parity -----


async def test_disagg_token_identical_to_fused(tiny, monkeypatch):
    """The acceptance bar: the same prompts through a disaggregated fleet
    produce exactly the tokens a fused engine produces, with real handoffs
    (pages shipped, decode replicas importing) behind them."""
    cfg, params = tiny
    prompts = _prompts(4)
    sp = _sp()
    expected = [r.output_tokens
                for r in _engine(params, cfg).generate(prompts, sp)]

    multi = _fleet(monkeypatch, params, cfg, n=3, prefill=1)
    try:
        results = await asyncio.gather(
            *[multi.generate(p, sp) for p in prompts])
        assert [r.output_tokens for r in results] == expected
        ds = multi.disagg_stats()
        assert ds["handoffs"] == len(prompts)
        assert ds["pages_shipped"] > 0
        assert ds["fallbacks"] == {}
        imported = sum(ae.engine.kv_pages_imported
                       for ae in multi._engines if ae.role == "decode")
        assert imported > 0
        exported = sum(ae.engine.kv_pages_exported
                       for ae in multi._engines if ae.role == "prefill")
        assert exported >= imported
    finally:
        await multi.stop()


async def test_disagg_repeat_traffic_dedups_the_wire(tiny, monkeypatch):
    """A decode replica already holding the prefix content-hash-deduped
    pays nothing: replaying the same prompts through a 1-prefill/1-decode
    fleet must dedup on the second pass instead of re-storing pages."""
    cfg, params = tiny
    prompts = _prompts(2, seed=5)
    sp = _sp()
    expected = [r.output_tokens
                for r in _engine(params, cfg).generate(prompts, sp)]

    multi = _fleet(monkeypatch, params, cfg, n=2, prefill=1)
    try:
        first = [await multi.generate(p, sp) for p in prompts]
        assert [r.output_tokens for r in first] == expected
        ds = multi.disagg_stats()
        shipped_1, deduped_1 = ds["pages_shipped"], ds["pages_deduped"]
        assert shipped_1 > 0

        second = [await multi.generate(p, sp) for p in prompts]
        assert [r.output_tokens for r in second] == expected
        ds = multi.disagg_stats()
        # with a single decode replica the replay lands where the pages
        # already live: the wire dedups instead of shipping again
        assert ds["pages_deduped"] > deduped_1
        assert ds["pages_shipped"] - shipped_1 < shipped_1
    finally:
        await multi.stop()


@pytest.mark.parametrize("extra", [
    pytest.param(dict(kv_quant=True), id="int8_kv"),
])
async def test_disagg_parity_composes_with_quant(tiny, monkeypatch, extra):
    """The handoff must compose with the KV features riding the same
    pools: int8 KV pages ship with their scales, token-identically."""
    cfg, params = tiny
    prompts = _prompts(3, seed=7)
    sp = _sp()
    expected = [r.output_tokens
                for r in _engine(params, cfg, **extra).generate(prompts, sp)]

    multi = _fleet(monkeypatch, params, cfg, n=3, prefill=1, **extra)
    try:
        results = await asyncio.gather(
            *[multi.generate(p, sp) for p in prompts])
        assert [r.output_tokens for r in results] == expected
        assert multi.disagg_stats()["handoffs"] == len(prompts)
    finally:
        await multi.stop()


async def test_short_prompt_skips_the_handoff(tiny, monkeypatch):
    """A prompt without a single full shippable page has nothing a peer
    could reuse: it goes straight to a decode replica, no handoff."""
    cfg, params = tiny
    sp = _sp(max_tokens=4)
    expected = _engine(params, cfg).generate([[1, 2, 3, 4]], sp)[0]

    multi = _fleet(monkeypatch, params, cfg, n=2, prefill=1)
    try:
        res = await multi.generate([1, 2, 3, 4], sp)
        assert res.output_tokens == expected.output_tokens
        ds = multi.disagg_stats()
        assert ds["handoffs"] == 0 and ds["fallbacks"] == {}
        # it decoded where the decoders live
        assert multi.router_stats()["per_replica"]["r1"]["routed"] == 1
    finally:
        await multi.stop()


# ------------------------------------------------------------- fallback ----


async def test_transfer_failure_finishes_fused(tiny, monkeypatch):
    """A dead wire mid-handoff must not surface to the caller: the request
    finishes fused on the prefill replica — token-identically — and the
    fallback is accounted."""
    cfg, params = tiny
    prompts = _prompts(2, seed=9)
    sp = _sp()
    expected = [r.output_tokens
                for r in _engine(params, cfg).generate(prompts, sp)]

    multi = _fleet(monkeypatch, params, cfg, n=2, prefill=1)

    async def dead_wire(src, dst, hashes):
        raise ConnectionError("wire down")

    monkeypatch.setattr(multi._transport, "transfer", dead_wire)
    try:
        results = [await multi.generate(p, sp) for p in prompts]
        assert [r.output_tokens for r in results] == expected
        ds = multi.disagg_stats()
        assert ds["handoffs"] == 0
        assert ds["fallbacks"]["transfer_error"] == len(prompts)
        # fused fallback ran on the prefill replica that holds the prefix
        assert multi.router_stats()["per_replica"]["r0"]["routed"] > 0
    finally:
        await multi.stop()


async def test_no_decode_replica_finishes_fused(tiny, monkeypatch):
    """Draining the only decode replica mid-flight leaves nowhere to ship
    to: requests finish fused on the prefill side instead of erroring."""
    cfg, params = tiny
    sp = _sp(max_tokens=4)
    prompt = _prompts(1, seed=13)[0]
    expected = _engine(params, cfg).generate([prompt], sp)[0]

    multi = _fleet(monkeypatch, params, cfg, n=2, prefill=1)
    try:
        await multi.drain("r1")
        res = await multi.generate(prompt, sp)
        assert res.output_tokens == expected.output_tokens
        assert multi.disagg_stats()["fallbacks"]["no_decode_replica"] == 1
    finally:
        await multi.stop()


# ------------------------------------------------------- role-aware stats --


def test_merge_rows_excludes_prefill_from_rate_means():
    """The fleet merge's mean_rows seam: a prefill-specialized replica's
    idle decode-side rates must not drag the fleet means, while counters
    still sum across every replica."""
    prefill_row = {"requests": 10, "acceptance_rate": 0.0, "role": "prefill"}
    decode_row = {"requests": 30, "acceptance_rate": 0.8, "role": "decode"}
    merged = MultiAsyncEngine._merge_rows([prefill_row, decode_row],
                                          mean_rows=[decode_row])
    assert merged["requests"] == 40  # counters: SUM over everyone
    assert merged["acceptance_rate"] == pytest.approx(0.8)  # mean: decode only
    # without the seam the prefill zero would halve the fleet rate
    naive = MultiAsyncEngine._merge_rows([prefill_row, decode_row])
    assert naive["acceptance_rate"] == pytest.approx(0.4)


async def test_fleet_stats_expose_roles_and_per_role(tiny, monkeypatch):
    cfg, params = tiny
    multi = _fleet(monkeypatch, params, cfg, n=3, prefill=1)
    try:
        await multi.generate(_prompts(1)[0], _sp(max_tokens=4))
        stats = multi.stats()
        by_replica = {ae.replica: s["role"] for ae, s in
                      zip(multi._engines, stats["per_replica"])}
        assert by_replica == {"r0": "prefill", "r1": "decode", "r2": "decode"}
        assert set(stats["per_role"]) == {"prefill", "decode"}
        # the per-role sub-aggregates split the fleet's admission counter
        assert (stats["per_role"]["prefill"]["requests_admitted"]
                + stats["per_role"]["decode"]["requests_admitted"]
                == stats["requests_admitted"])
        assert stats["router"]["disagg"]["enabled"]
    finally:
        await multi.stop()


# ------------------------------------------------------ compile discipline --


async def test_disagg_zero_live_compiles(tiny, monkeypatch):
    """Mixed handoff / dedup-replay / short-prompt traffic after warmup
    compiles ZERO new XLA programs: export gathers and import-side
    fault-in scatters ride the warmup-precompiled migrate buckets on both
    roles, and import itself touches only host dicts."""
    from tests.helpers.compile_guard import compile_guard, watchdog_counter

    cfg, params = tiny
    prompts = _prompts(2, seed=17)
    sp = _sp(max_tokens=4)

    multi = _fleet(monkeypatch, params, cfg, n=3, prefill=1)
    try:
        for ae in multi._engines:
            ae.engine.warmup()
        # prime outside the guard: first traffic starts the driver threads
        await multi.generate(prompts[0], sp)
        with compile_guard(watchdog_counter(), label="mixed disagg traffic"):
            await asyncio.gather(
                multi.generate(prompts[1], sp),   # fresh handoff
                multi.generate(prompts[0], sp),   # dedup replay
                multi.generate([1, 2, 3], sp),    # shippable=0: no handoff
            )
        assert multi.disagg_stats()["handoffs"] >= 2
    finally:
        await multi.stop()
