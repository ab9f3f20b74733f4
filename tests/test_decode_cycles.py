"""The decode cycle as the engine writes it (burst landing to burst landing):
the cycle ring, the request records and the ``engine.decode`` span's
attributes agree with each other and with the order the step programs were
dispatched in.  On the CPU, at a tiny model."""

import math

import jax
import jax.numpy as jnp
import pytest

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.serving import Engine, SamplingParams

CHUNK, STEPS = 32, 8


def _sp(n):
    return SamplingParams(max_tokens=n, temperature=0.0, stop_token_ids=())


@pytest.fixture(scope="module")
def mixed():
    """Two rows decode; a prompt longer than two chunks arrives beside them;
    one of the two is cancelled; the engine empties; one more request runs.
    Kept: every dispatch (kind, number, a wave's new tokens) and every landing
    (what it says of its cycle, the requests it brought a token, whether
    another burst was in flight behind it) as they happened, and the results."""
    cfg = Qwen2Config.tiny()
    eng = Engine(init_params(cfg, jax.random.PRNGKey(0)), cfg, max_num_seqs=4, num_pages=256,
                 page_size=8, max_seq_len=256, prefill_chunk=CHUNK, kv_dtype=jnp.float32)
    assert eng.decode_burst == STEPS
    dispatched, landed = [], []
    wave, burst, land = eng._wave_fn, eng._decode_burst_fn, eng._cycle_landed

    def _wave(*a, **kw):
        dispatched.append(("wave", eng.step_dispatches_total, int(a[11].sum())))  # new_lens
        return wave(*a, **kw)

    def _burst(*a, **kw):
        dispatched.append(("burst", eng.step_dispatches_total, 0))
        return burst(*a, **kw)

    def _land(facts, got):
        landed.append((dict(facts),
                       [r.request_id for row, r in eng._row_req.items()
                        if r.state == "running" and got[row]], eng._chain is not None))
        return land(facts, got)

    eng._wave_fn, eng._decode_burst_fn, eng._cycle_landed = _wave, _burst, _land
    done = []
    eng.add_request([5] * 10, _sp(60), request_id="a")
    eng.add_request([6] * 12, _sp(60), request_id="b")
    for _ in range(3):
        done += eng.step()
    eng.add_request([7] * (2 * CHUNK + 6), _sp(12), request_id="long")
    for _ in range(3):
        done += eng.step()
    eng.cancel("b")
    while eng.has_work():
        done += eng.step()
    drained_at = eng.step_dispatches_total
    eng.add_request([8] * 9, _sp(20), request_id="late")
    while eng.has_work():
        done += eng.step()
    return eng, dispatched, landed, {r.request_id: r for r in done}, drained_at


def test_one_counter_numbers_every_dispatch(mixed):
    eng, dispatched, _, _, _ = mixed
    assert [seq for _, seq, _ in dispatched] == list(range(1, len(dispatched) + 1))
    assert eng.step_dispatches_total == len(dispatched) and not hasattr(eng, "_dispatch_seq")
    assert eng.cycle_programs == {"burst": "jit_decode_burst", "wave": "jit_forward_paged_wave"}


def test_a_cycles_waves_are_the_waves_numbered_between_two_bursts(mixed):
    eng, dispatched, landed, _, _ = mixed
    bursts = [seq for kind, seq, _ in dispatched if kind == "burst"]
    assert [facts["seq"] for facts, _, _ in landed] == bursts  # every burst lands, in order
    for facts, _, _ in landed:
        before = max((s for s in bursts if s < facts["seq"]), default=0)
        between = [(seq, tokens) for kind, seq, tokens in dispatched
                   if kind == "wave" and before < seq < facts["seq"]]
        assert facts["waves"] == len(between) == facts["seq"] - before - 1
        assert facts["wave_tokens"] == sum(t for _, t in between)
        assert set(facts) == {"seq", "waves", "wave_tokens"}
    # the long prompt's three chunks rode in cycles of the two decoding rows
    with_wave = [facts for facts, _, _ in landed if facts["waves"]]
    assert sum(f["wave_tokens"] for f in with_wave) >= 2 * CHUNK + 6


def test_the_ring_holds_the_chained_landings_and_no_other(mixed):
    eng, dispatched, landed, _, drained_at = mixed
    ring = list(eng.cycle_ring)
    by_seq = {facts["seq"]: facts for facts, _, _ in landed}
    assert ring and all({k: c[k] for k in by_seq[c["seq"]]} == by_seq[c["seq"]] for c in ring)
    assert set(ring[0]) == {"seq", "waves", "wave_tokens", "landed_t", "cycle_s"}
    assert all(c["cycle_s"] > 0 for c in ring)
    stamps = [c["landed_t"] for c in ring]
    assert stamps == sorted(stamps)
    # A landing with no burst before it in flight starts the clock and records
    # none: the engine's first burst, and the first after a chain was drained
    # (a landing that leaves nothing in flight behind it is a drain's)
    bursts = [seq for kind, seq, _ in dispatched if kind == "burst"]
    after_a_drain = {bursts[0]} | {nxt["seq"] for (_, _, behind), (nxt, _, _)
                                   in zip(landed, landed[1:]) if not behind}
    assert min(s for s in bursts if s > drained_at) in after_a_drain
    assert {c["seq"] for c in ring} == set(bursts) - after_a_drain
    # two records next to each other in the ring, with no drain between them,
    # are one cycle apart
    for a, b in zip(ring, ring[1:]):
        if bursts.index(b["seq"]) == bursts.index(a["seq"]) + 1:
            assert b["cycle_s"] == pytest.approx(b["landed_t"] - a["landed_t"])
    assert eng._landed_t is None  # the engine is empty: the next burst starts the clock


def test_a_requests_record_counts_its_landings(mixed):
    _, _, landed, results, _ = mixed
    assert set(results) == {"a", "b", "long", "late"}
    assert results["b"].finish_reason == "cancelled"
    for rid, res in results.items():
        t = res.timings
        mine = [facts for facts, rows, _ in landed if rid in rows]
        assert t["decode_cycles"] == len(mine)
        # the first landing follows the request's own last wave: not counted
        assert t["decode_wave_cycles"] == sum(1 for f in mine[1:] if f["waves"])
        assert t["decode_wave_tokens"] == sum(f["wave_tokens"] for f in mine[1:])
        assert t["first_token_t"] <= t["last_token_t"] <= t["done_t"]
        if res.finish_reason == "length":
            # a burst in flight past a row's last token brings it nothing
            assert t["decode_cycles"] == math.ceil((len(res.output_tokens) - 1) / STEPS)
    a, b, late = results["a"].timings, results["b"].timings, results["late"].timings
    assert a["decode_wave_cycles"] >= 1 and a["decode_wave_tokens"] >= CHUNK
    # the cancelled row stops at its last token; its neighbour goes on
    assert b["decode_cycles"] < a["decode_cycles"]
    assert b["decode_cycles"] == math.ceil((len(results["b"].output_tokens) - 1) / STEPS)
    assert late["decode_wave_cycles"] == 0 and late["decode_cycles"] == 3


def test_the_decode_span_says_what_the_record_says(mixed):
    from githubrepostorag_tpu.obs.engine_profile import DECODE_COUNTS, record_engine_spans
    from githubrepostorag_tpu.obs.recorder import get_recorder
    from githubrepostorag_tpu.obs.trace import TraceContext

    _, _, _, results, _ = mixed
    tid = f"{52:032x}"
    record_engine_spans(results["a"], TraceContext(tid, "", 1))
    spans = get_recorder().trace_payload(tid)["spans"]
    decode = next(s for s in spans if s["name"] == "engine.decode")
    t = results["a"].timings
    assert {k: decode["attrs"][k] for k in DECODE_COUNTS} == {k: t[k] for k in DECODE_COUNTS}
    assert decode["attrs"]["output_tokens"] == 60
    # a result from an engine that counts none (a fake, a commit before) still spans
    old = {k: v for k, v in t.items() if k not in DECODE_COUNTS}
    results["late"].timings, kept = old, results["late"].timings
    try:
        record_engine_spans(results["late"], TraceContext(f"{53:032x}", "", 1))
    finally:
        results["late"].timings = kept
    spans = get_recorder().trace_payload(f"{53:032x}")["spans"]
    assert not set(DECODE_COUNTS) & set(next(
        s for s in spans if s["name"] == "engine.decode")["attrs"])


def test_the_histogram_counts_cycles_by_the_waves_in_them(mixed):
    from githubrepostorag_tpu.metrics import ENGINE_CYCLE, REGISTRY

    eng, _, _, _, _ = mixed
    ring = list(eng.cycle_ring)
    for label, want in (("0", lambda w: w == 0), ("1", lambda w: w == 1),
                        ("2+", lambda w: w >= 2)):
        count = REGISTRY.get_sample_value("rag_engine_cycle_seconds_count", {"waves": label})
        # other engines of this process observe too: at least this engine's
        assert (count or 0) >= sum(1 for c in ring if want(c["waves"]))
    assert ENGINE_CYCLE.labels(waves="1")._sum.get() > 0


def test_a_packed_wave_is_a_wave_of_its_cycle_and_no_probe_of_the_device():
    """The token-budget prefill path dispatches under the same counter: its
    programs are the numbers between two bursts and their tokens the cycle's;
    whether the host kept ahead is asked before a padded wave and a burst, as
    it was, and nowhere else."""
    cfg = Qwen2Config.tiny()
    eng = Engine(init_params(cfg, jax.random.PRNGKey(0)), cfg, max_num_seqs=4, num_pages=256,
                 page_size=8, max_seq_len=256, prefill_chunk=CHUNK, prefill_token_budget=CHUNK,
                 kv_dtype=jnp.float32)
    probes, note = [], eng._note_dispatch
    eng._note_dispatch = lambda: (probes.append(eng.step_dispatches_total), note())[1]
    landed, land = [], eng._cycle_landed
    eng._cycle_landed = lambda facts, got: (landed.append(dict(facts)), land(facts, got))[1]
    eng.add_request([5] * 10, _sp(30), request_id="a")
    for _ in range(2):
        eng.step()
    eng.add_request([7] * (2 * CHUNK + 6), _sp(10), request_id="long")
    while eng.has_work():
        eng.step()
    bursts = [f["seq"] for f in landed]
    assert probes == bursts  # a burst's number, before it goes out: no packed dispatch asks
    assert eng.bursts_ahead + eng.bursts_starved == len(bursts)
    assert sum(f["waves"] for f in landed) == eng.step_dispatches_total - len(bursts) >= 4
    assert sum(f["wave_tokens"] for f in landed) == eng.packed_prefill_tokens == 10 + 2 * CHUNK + 6
    assert [f["waves"] for f in landed] == [b - a - 1 for a, b in zip([0] + bursts, bursts)]
