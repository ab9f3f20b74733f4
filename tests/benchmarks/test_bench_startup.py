"""The start-up readings (benchmarks/readers/startup.py) on a recorded ledger
and record, the two dispatch shares (benchmarks/readers/dispatch_shares.py)
on a plain trace, and one CPU rehearsal of each cell that must print them.
The rehearsals run in a copy of the benchmark, so their work directory is not
the one ``test_bench_run.py``'s rehearsals of the same cells write to."""

import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.readers import dispatch_shares, startup

ROOT = manifest.ROOT
DATA = Path(__file__).parent / "data"
SETUP = ("setup_trace_lower_s", "setup_compile_s", "setup_programs", "setup_weights_s",
         "setup_other_s")


@pytest.fixture(scope="module")
def recorded():
    """``chat-steady``'s set-up on a v5e with a warm cache (PR 36): the
    ledger's events (traces under half a millisecond dropped) and the record."""
    return json.loads((DATA / "startup_v5e_chat.json").read_text())


def test_the_six_readings_of_a_recorded_setup(recorded):
    lead_in = 8.0
    got = startup.readings(recorded, recorded["t_open"], lead_in)
    events, phases = recorded["ledger"]["events"], recorded["record"]["phases"]
    warm = recorded["ledger"]["warm_t"]
    assert all(e[0] < warm for e in events)  # this run compiled nothing after ready
    by_kind = {k: [e for e in events if e[2] == k] for k in ("trace", "lower", "compile")}
    assert got["setup_programs"] == len(by_kind["compile"]) == len(by_kind["lower"])
    assert got["setup_cache_hits"] == got["setup_programs"]  # warm: every program was read
    assert got["setup_compile_s"] == pytest.approx(sum(e[1] for e in by_kind["compile"]))
    assert got["setup_trace_lower_s"] == pytest.approx(
        sum(e[1] for e in by_kind["trace"]) + sum(e[1] for e in by_kind["lower"]))
    named = {p["name"]: p["end"] - p["start"] for p in phases}
    assert got["setup_weights_s"] == pytest.approx(
        named["startup.weights"] + named["startup.engine_init"])
    assert got["setup_retrieval_s"] is None  # no encoder, ingest or index in this cell
    # the unexplained rest, by a second route: a grid of milliseconds
    t0, t_ready = recorded["record"]["process_start"], recorded["t_open"] - lead_in
    spans = [(p["start"], p["end"]) for p in phases] + [(e[0] - e[6], e[0]) for e in events]
    n = int((t_ready - t0) * 1000)
    covered = bytearray(n)
    for a, b in spans:
        lo, hi = max(0, int((a - t0) * 1000)), min(n, int((b - t0) * 1000) + 1)
        covered[lo:hi] = b"\x01" * max(0, hi - lo)
    assert got["setup_other_s"] == pytest.approx((n - sum(covered)) / 1000, abs=0.35)
    assert 0 < got["setup_other_s"] < got["to_ready_s"] and got["to_warm_s"] < got["to_ready_s"]
    # a nested trace's seconds are its own: the sum stays under the wall clock it covers
    assert sum(e[1] for e in by_kind["trace"]) <= startup.union_seconds(
        [(e[0] - e[6], e[0]) for e in by_kind["trace"]], t0, t_ready) + 1e-6


def test_other_is_never_negative_and_an_open_phase_reads_none(recorded):
    snap = copy.deepcopy(recorded)
    t0 = snap["record"]["process_start"]
    # phases that cover more than the whole set-up, twice over
    snap["record"]["phases"] += [
        {"name": "startup.encoder", "start": t0 - 5.0, "dispatched": None,
         "end": recorded["t_open"] + 5.0},
        {"name": "startup.ingest.embed", "start": t0, "dispatched": None, "end": t0 + 30.0}]
    got = startup.readings(snap, recorded["t_open"], 8.0)
    assert got["setup_other_s"] == 0.0
    # the ingest stage lies inside the encoder's phase here: covered seconds, not a sum
    assert got["setup_retrieval_s"] == pytest.approx(recorded["t_open"] - t0 + 10.0)
    snap["record"]["phases"].append(
        {"name": "startup.index_build", "start": t0 + 1.0, "dispatched": t0 + 2.0, "end": None})
    for p in snap["record"]["phases"]:
        if p["name"] == "startup.engine_init":
            p["end"] = None
    got = startup.readings(snap, recorded["t_open"], 8.0)
    assert got["setup_retrieval_s"] is None and got["setup_weights_s"] is None  # not 0
    assert got["setup_trace_lower_s"] > 0  # the ledger's readings do not depend on the record
    snap["ledger"]["warm_t"] = None  # a program that never said it was ready
    assert startup.readings(snap, recorded["t_open"], 8.0) == {}


def test_a_program_without_ledger_and_record_reads_as_none(monkeypatch):
    monkeypatch.setattr(startup, "snapshot", lambda: None)  # any commit before PR 36
    ctx = SimpleNamespace(t_open=100.0, traffic={"lead_in_s": 8.0},
                          cell=SimpleNamespace(name="x"))
    assert all(startup.read(ctx, what) is None for what in SETUP)


def test_union_seconds():
    assert startup.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 9), (-4, -1)], 0, 8) == 6.0
    assert startup.union_seconds([], 0, 8) == 0.0


def test_the_dispatch_shares_on_a_plain_trace():
    plain = {"devices": {}, "host": [
        ["engine.decode_burst", 0.0, 0.001, {"rows": 4, "ahead": 1}],
        ["engine.decode_burst", 0.1, 0.001, {"rows": 4, "ahead": 0}],
        ["engine.decode_burst", 0.2, 0.001, {"rows": 4, "ahead": 1}],
        ["engine.decode_burst", 0.3, 0.001, {"rows": 4, "ahead": 1}],
        ["engine.prefill_batch", 0.05, 0.002, {"rows": 2, "new_tokens": 300, "padded_tokens": 512}],
        ["engine.prefill_batch", 0.15, 0.002, {"rows": 1, "new_tokens": 84, "padded_tokens": 128}],
        ["driver.step", 0.0, 0.4, {}]]}
    assert dispatch_shares.burst_ahead_share(plain) == 75.0
    assert dispatch_shares.prefill_pad_share(plain) == pytest.approx(100 * (1 - 384 / 640))
    old = json.loads((DATA / "trace_v5e_chat_phases_300ms.json").read_text())  # PR 24's program
    assert dispatch_shares.burst_ahead_share(old) is None  # carried neither stat
    assert dispatch_shares.prefill_pad_share(old) is None


@pytest.fixture(scope="module")
def top(tmp_path_factory):
    top = tmp_path_factory.mktemp("startup_rehearsals")
    shutil.copytree(ROOT / "benchmarks", top / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copyfile(ROOT / "BENCHMARK.json", top / "BENCHMARK.json")
    return top


@pytest.mark.parametrize("cell", [w["name"] for w in manifest.load_manifest()["workloads"]])
def test_a_rehearsal_of_each_cell_prints_the_setup_metrics(cell, top):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    seconds = "12" if cell.endswith("rag-answer") else "6"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", cell, "--seed", str(2**31 + 36),
         "--seconds", seconds, "--trace", "1", "--rehearse"], cwd=top, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    m = {k: v["value"] for k, v in last["metrics"].items()}
    want = set(SETUP) | ({"setup_retrieval_s"} if cell.endswith("rag-answer") else set())
    assert want <= set(m), sorted(m)
    assert ("setup_retrieval_s" in m) == cell.endswith("rag-answer")
    assert all(m[k] >= 0 for k in want) and m["setup_programs"] >= 3
    assert m["setup_trace_lower_s"] > 0 and m["setup_compile_s"] > 0 and m["setup_weights_s"] > 0
    assert last["metrics"]["setup_programs"]["unit"] == "programs"
    twin = ".answer_s_mean" if cell.endswith("rag-answer") else ""
    assert 0 <= m["burst_ahead_share" + twin] <= 100 and 0 <= m["prefill_pad_share" + twin] < 100
    # the operator's line, once, from the program itself
    ready = [ln for ln in out.stderr.splitlines() if '"msg": "ready ' in ln]
    assert len(ready) == 1 and "startup.weights" in ready[0] and "startup.serve" in ready[0]
