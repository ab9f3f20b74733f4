"""``benchmarks/readers/decode_cycles.py``: the split of a trace by decode
cycle on a small plain trace written by hand, the rings' readings on records
written by hand, the entries PR 52 appended to ``BENCHMARK.json``, and one
rehearsal that prints them."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.readers import decode_cycles

ROOT = manifest.ROOT
DATA = Path(__file__).parent / "data"
PROGRAMS = {"burst": "jit_decode_burst", "wave": "jit_forward_paged_wave"}
SEVEN = {"tpot_server_p50_ms", "tpot_wave_cycle_share", "cycle_ms_p50", "cycle_wave_extra_ms",
         "cycle_burst_share", "cycle_wave_share", "cycle_other_share", "cycle_gap_share"}
RAG = {f"cycle_{k}_share.answer_s_mean" for k in ("burst", "wave", "other", "gap")}
MONO = 5000.0  # time.monotonic() at the trace's zero


def _landing(at, seq, waves, chained=1):
    return ["engine.commit_host", at, 0.002, {"tokens": 64, "seq": seq, "waves": waves,
                                              "wave_tokens": 300 * waves, "chained": chained}]


def _trace(planted: bool) -> dict:
    """Seven bursts of 100 ms on one device, in ``host_phases``' plain form,
    each dispatched before the burst before it landed.  The host has a burst's
    tokens 0.3-0.4 ms after the burst ended, by when the device has begun the
    next program.  A's dispatch (10) lies before the trace; cycle B (wave 11,
    burst 12) holds a helper, a wave and a gap; C (13) a burst alone; D (14)
    and E (wave 15, burst 16) as announced, or ``planted``: D runs a wave its
    landing does not announce and E's wave did not run; then the engine
    empties (F, wave 17 and burst 18, starts the clock); G (19)."""
    burst = lambda s: ["jit_decode_burst(12987647341442333126)", s, 0.1]  # noqa: E731
    wave = lambda s, d: ["jit_forward_paged_wave(10446460126967057382)", s, d]  # noqa: E731
    modules = [
        burst(0.0),                                                  # A
        ["jit__mark_presence_chunks(14417140945391936975)", 0.1001, 0.001],
        wave(0.102, 0.030),
        burst(0.135),                                                # B, after 3 ms idle
        burst(0.2351),                                               # C
        *([wave(0.3352, 0.010)] if planted else []),
        burst(0.3452),                                               # D
        *([] if planted else [wave(0.4453, 0.010)]),
        burst(0.4554),                                               # E
        ["jit_forward_paged(999)", 0.60, 0.020],                     # another program's: other
        wave(0.67, 0.020),
        burst(0.70),                                                 # F
        burst(0.8001),                                               # G
    ]
    step = lambda at, dur: ["driver.step", at, dur, {"mono_ns": int((MONO + at) * 1e9)}]  # noqa: E731
    sent = lambda name, at, seq: [name, at, 0.0005, {"seq": seq, "rows": 8, "kv_tokens": 4096,  # noqa: E731
                                                     "steps": 8, "new_tokens": 300}]
    host = [
        sent("engine.prefill_batch", 0.040, 11), sent("engine.decode_burst", 0.045, 12),
        step(0.05, 0.0505),
        _landing(0.1004, 10, 1, chained=0),                          # A: nothing before it
        ["engine.commit_host", 0.1003, 0.0001, {"tokens": 2}],       # a first-token wave's
        sent("engine.decode_burst", 0.105, 13), step(0.11, 0.13),
        _landing(0.2353, 12, 1),                                     # B
        sent("engine.decode_burst", 0.238, 14), step(0.24, 0.1),
        _landing(0.3355, 13, 0),                                     # C
        sent("engine.prefill_batch", 0.338, 15), sent("engine.decode_burst", 0.339, 16),
        _landing(0.4456, 14, 0),                                     # D
        _landing(0.5558, 16, 1),                                     # E
        sent("engine.prefill_batch", 0.66, 17), sent("engine.decode_burst", 0.665, 18),
        step(0.69, 0.3), sent("engine.decode_burst", 0.695, 19),
        _landing(0.8003, 18, 1, chained=0),                          # F
        _landing(0.9004, 19, 0),                                     # G
    ]
    return {"devices": {"0": {"ops": [], "modules": modules},
                        "1": {"ops": [], "modules": [burst(0.05)]}},
            "host": sorted(host, key=lambda h: h[1])}


@pytest.fixture(scope="module")
def plain():
    return _trace(planted=True)


def test_a_cycle_runs_from_one_bursts_end_on_the_device_to_the_next(plain):
    found = decode_cycles.split(plain, PROGRAMS)
    cycles = {c["seq"]: c for c in found["cycles"]}
    assert sorted(cycles) == [12, 13, 14, 16, 19]  # F (18) and A (10) start a clock
    b = cycles[12]
    assert (b["t0"], b["t1"]) == (pytest.approx(0.1), pytest.approx(0.235))
    # the helper began before the host had burst A's tokens (0.1004) and after
    # the burst had ended: booked by its start, it is cycle B's
    assert b["seconds"] == {"burst": pytest.approx(0.1), "wave": pytest.approx(0.030),
                            "other": pytest.approx(0.001), "gap": pytest.approx(0.004)}
    assert cycles[13]["seconds"]["gap"] == pytest.approx(0.0001)
    assert cycles[19]["t0"] == pytest.approx(0.8)  # F's burst's end, not F's landing
    # what ran while the engine was empty, F's own wave too, lies in no cycle
    assert sum(c["seconds"]["other"] for c in found["cycles"]) == pytest.approx(0.001)
    assert sum(c["seconds"]["wave"] for c in found["cycles"]) == pytest.approx(0.040)
    lag = found["fetch_lag_s"]
    assert len(lag) == 7 and min(lag) == pytest.approx(0.0003) and max(lag) < 0.00041


def test_the_four_shares_sum_to_100(plain):
    found = decode_cycles.split(plain, PROGRAMS)
    shares = decode_cycles.shares(found)
    total = 0.135 + 0.1001 + 0.1101 + 0.1102 + 0.1001
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-9)
    assert shares["burst"] == pytest.approx(100 * 0.5 / total)
    assert shares["wave"] == pytest.approx(100 * 0.040 / total)
    assert shares["other"] == pytest.approx(100 * 0.001 / total)
    assert shares["gap"] == pytest.approx(100 * 0.0145 / total)


def test_the_join_counts_what_it_missed(plain):
    found = decode_cycles.split(plain, PROGRAMS)
    # D ran a wave its landing did not announce; E announced one that is not there
    assert found["missed"] == 2 and found["waves_not_found"] == 1
    assert found["wave_s_unannounced"] == pytest.approx(0.010)
    assert found["unjoined"] == 0 and found["resynced"] == 0 and found["misnumbered"] == 0
    assert [c["wave_modules"] for c in found["cycles"]] == [1, 0, 1, 0, 0]
    assert not decode_cycles.sound(found)  # 2 of 5: no reading
    clean = decode_cycles.split(_trace(planted=False), PROGRAMS)
    assert clean["missed"] == 0 and decode_cycles.sound(clean)
    assert [c["wave_modules"] for c in clean["cycles"]] == [1, 0, 0, 1, 0]
    # a landing whose burst is not in the trace has no edge, and the cycles on
    # both sides of it are not whole; the rest stand
    cut = {"devices": {"0": {"ops": [], "modules": [
        m for m in plain["devices"]["0"]["modules"] if m[1] != 0.2351]}}, "host": plain["host"]}
    found = decode_cycles.split(cut, PROGRAMS)
    assert found["unjoined"] == 2 and [c["seq"] for c in found["cycles"]] == [12, 16, 19]
    # ... unless it is the trace's head: the burst had ended when the trace began
    headless = {"devices": {"0": {"ops": [], "modules": plain["devices"]["0"]["modules"][1:]}},
                "host": plain["host"]}
    found = decode_cycles.split(headless, PROGRAMS)
    assert found["unjoined"] == 0 and [c["seq"] for c in found["cycles"]] == [13, 14, 16, 19]
    # a trace with none of the engine's bursts among its modules joins nothing
    assert decode_cycles.split(plain, {**PROGRAMS, "burst": "jit_burst"}) is None


def test_the_dispatch_numbers_check_the_join_from_the_other_side():
    clean = _trace(planted=False)
    found = decode_cycles.split(clean, PROGRAMS)
    # bursts 12 to 19 were dispatched in the trace and pair by order with the
    # module their landing found; A's dispatch lies before the trace
    assert (found["order_pairs"], found["order_off"], found["misnumbered"]) == (6, 0, 0)
    # a wave dispatch the trace numbers between D and E, and E's landing does not count
    host = [h if not (h[0] == "engine.commit_host" and h[3].get("seq") == 16)
            else _landing(h[1], 16, 0) for h in clean["host"]]
    found = decode_cycles.split({**clean, "host": host}, PROGRAMS)
    assert found["misnumbered"] == 1 and found["missed"] == 1 and found["waves_not_found"] == 0
    # B's cycle is not checked so: the waves after A's dispatch may lie before the trace
    host = [h for h in clean["host"] if h[3].get("seq") != 11]
    assert decode_cycles.split({**clean, "host": host}, PROGRAMS)["misnumbered"] == 0
    # the first burst module to START after the trace's first dispatch is the
    # burst before that dispatch's, when a wave held the device meanwhile: by
    # order every dispatch then pairs one module early (R7g-4), and the
    # landings say so
    modules = [m for m in clean["devices"]["0"]["modules"] if m[1] >= 0.1]
    host = [h for h in clean["host"] if h[1] >= 0.1]  # begins inside cycle B, past 12's dispatch
    found = decode_cycles.split({"devices": {"0": {"ops": [], "modules": modules}},
                                 "host": host}, PROGRAMS)
    assert (found["order_pairs"], found["order_off"]) == (5, 5)
    assert found["missed"] == 0 and [c["seq"] for c in found["cycles"]] == [13, 14, 16, 19]


def test_ring_stamps_land_on_the_annotations_through_the_anchor(plain):
    ring = [{"seq": seq, "waves": 0, "landed_t": MONO + at + 0.0002, "cycle_s": 0.1}
            for at, seq in ((0.2353, 12), (0.3355, 13), (0.4456, 14), (0.9004, 19))]
    off = decode_cycles.ring_against_trace(plain, ring)
    assert len(off) == 4 and all(abs(x) < 1e-3 for x in off)
    assert off == pytest.approx([0.0002] * 4, abs=1e-6)
    late = decode_cycles.ring_against_trace(plain, [{**ring[1], "landed_t": MONO + 0.3405}])
    assert late == pytest.approx([0.005], abs=1e-6)
    # a record whose landing the trace does not hold is not compared
    assert decode_cycles.ring_against_trace(plain, [{**ring[0], "seq": 99}]) == []


def test_the_rings_readings():
    rec = lambda n, first, last, cycles, wave: {  # noqa: E731
        "output_tokens": n, "timings": {
            "recv_t": 1.0, "first_token_t": first, "last_token_t": last, "decode_cycles": cycles,
            "decode_wave_cycles": wave, "decode_wave_tokens": 100 * wave}}
    records = [rec(41, 10.0, 10.4, 5, 1), rec(81, 10.0, 11.6, 10, 5), rec(1, 10.0, None, 0, 0),
               {"output_tokens": 9, "timings": {"recv_t": 1.0, "first_token_t": 3.0}}]  # PR 51's
    assert decode_cycles.tpot_server_ms(records) == pytest.approx(15.0)  # 10 and 20 ms
    assert decode_cycles.wave_cycle_share(records) == pytest.approx(100 * 6 / 15)
    assert decode_cycles.tpot_server_ms(records[3:]) is None
    assert decode_cycles.wave_cycle_share(records[3:]) is None
    cycles = [{"cycle_s": 0.080, "waves": 0}] * 30 + [{"cycle_s": 0.120, "waves": 1}] * 19
    assert decode_cycles.cycle_ms(cycles) == pytest.approx(80.0)
    assert decode_cycles.wave_extra_ms(cycles) is None  # 19 cycles with a wave: too few
    cycles += [{"cycle_s": 0.150, "waves": 2}]
    assert decode_cycles.wave_extra_ms(cycles) == pytest.approx(40.0)
    assert decode_cycles.cycle_ms([]) is None and decode_cycles.wave_extra_ms([]) is None


def test_a_program_without_the_rings_or_the_stats_reads_none(monkeypatch, capsys):
    """The parent commit: no cycle ring, no ``seq`` on a landing."""
    from githubrepostorag_tpu.obs import continuous

    old = json.loads((DATA / "trace_v5e_chat_phases_300ms.json").read_text())  # PR 24's program
    assert decode_cycles.split(old, PROGRAMS) is None
    monkeypatch.setattr(continuous, "profilers", lambda: {"r0": SimpleNamespace(
        request_ring=[{"output_tokens": 9, "timings": {"recv_t": 1.0, "first_token_t": 1.5}}])})
    ctx = SimpleNamespace(in_window=lambda t: t is not None, trace_span=(0.0, 1.0),
                          _host_phases=old)
    for name in sorted(SEVEN | RAG):
        spec = manifest.metric_spec(name)
        assert spec["reader"] == "decode_cycles"
        assert decode_cycles.read(ctx, **spec["args"]) is None, name
    assert capsys.readouterr().err == ""


def test_the_reader_says_what_its_join_found(plain, monkeypatch, capsys):
    from githubrepostorag_tpu.obs import continuous

    ring = [{"seq": 12, "waves": 1, "landed_t": MONO + 0.2354, "cycle_s": 0.135}]
    monkeypatch.setattr(continuous, "profilers", lambda: {"r0": SimpleNamespace(
        request_ring=[], cycle_ring=ring, cycle_programs=PROGRAMS)})
    window = {"in_window": lambda t: t is not None, "trace_span": (0.0, 1.0)}
    ctx = SimpleNamespace(**window, _host_phases=_trace(planted=False))
    got = {k: decode_cycles.read(ctx, what="share", kind=k) for k in decode_cycles.KINDS}
    assert sum(got.values()) == pytest.approx(100.0)
    assert decode_cycles.read(ctx, what="cycle_ms") == pytest.approx(135.0)
    err = capsys.readouterr().err
    assert err.count("[decode_cycles]") == 1  # once a run, whatever the metrics read
    assert "5 whole cycles" in err and "0 of 5 cycles missed" in err and "1 compared" in err
    assert "0 of 6 burst dispatches" in err and "not reported" not in err
    # where the join did not hold there is no reading, and the line says why
    ctx = SimpleNamespace(**window, _host_phases=plain)
    assert all(decode_cycles.read(ctx, what="share", kind=k) is None
               for k in decode_cycles.KINDS)
    err = capsys.readouterr().err
    assert "2 of 5 cycles missed" in err and "the shares are not reported" in err
    with pytest.raises(ValueError):
        decode_cycles.read(ctx, what="nope")


def test_the_manifest_holds_the_new_metrics_where_the_issue_put_them():
    man = manifest.load_manifest()
    manifest.validate(man)
    by_name = {m["name"]: m for m in man["per_layer"]}
    judged = [m for m in man["end_to_end"] if m["name"] == "tpot_p50_ms"][0]["workloads"]
    for name in SEVEN:
        assert by_name[name]["workloads"] == judged and by_name[name]["moves"] == "tpot_p50_ms"
    for name in RAG:
        assert by_name[name]["workloads"] == ["qwen2-7b-int8.rag-answer"]
        assert by_name[name]["moves"] == "answer_s_mean" and by_name[name]["layer"] == "device"
    layers = {m["layer"] for m in man["per_layer"] if m["name"] not in SEVEN | RAG}
    assert {by_name[n]["layer"] for n in SEVEN | RAG} <= layers  # no layer is new
    for cell in judged:
        assert SEVEN <= set(manifest.load_cell(cell, man).per_layer)
    assert RAG <= set(manifest.load_cell("qwen2-7b-int8.rag-answer", man).per_layer)


def test_a_rehearsal_prints_the_rings_metrics(tmp_path):
    """One traced run of the chat cell at test widths: what the rings give is
    in the line as a number; what needs a device's module events is left out
    (a CPU's trace has none), and nothing raises."""
    top = tmp_path / "top"
    shutil.copytree(ROOT / "benchmarks", top / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copyfile(ROOT / "BENCHMARK.json", top / "BENCHMARK.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", "qwen2-7b-int8.chat-steady",
         "--seed", str(2**31 + 52), "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=top, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert last["correct"] is True
    assert {"tpot_server_p50_ms", "tpot_wave_cycle_share", "cycle_ms_p50"} <= set(m), sorted(m)
    assert all(isinstance(m[k], float) for k in SEVEN & set(m))
    assert not {k for k in SEVEN if k.endswith("_share") and k.startswith("cycle_")} & set(m)
    # a row receives a burst's steps of tokens a cycle: its gap is a part of the cycle
    assert 0 < m["tpot_server_p50_ms"] < m["cycle_ms_p50"]
    assert 0 <= m["tpot_wave_cycle_share"] <= 100
    assert last["metrics"]["cycle_ms_p50"]["unit"] == "ms"
