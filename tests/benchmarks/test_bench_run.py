"""One run end to end at test widths on a CPU the test pins, and the refusal
to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import manifest

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(args, timeout=420):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m", "benchmarks.run", *args], cwd=manifest.ROOT,
                          env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell,trace", [("qwen2-7b-int8.chat-steady", 0),
                                        ("qwen2-7b-int8.chat-steady", 1),
                                        ("qwen2-7b-int8.rag-answer", 1),
                                        ("qwen2-7b-int8.rag-answer", 0)])
def test_rehearsal_prints_the_contracts_line(cell, trace):
    seconds = "12" if cell.endswith("rag-answer") else "5"  # both kinds of answer must finish
    out = _run(["--workload", cell, "--seed", str(2**31 + 19), "--seconds", seconds,
                "--trace", str(trace), "--rehearse"])
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    last = json.loads(lines[-1])
    assert KEYS <= set(last) and set(last) - KEYS <= {"breakdown", "checks"}
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    assert set(last["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    c = manifest.load_cell(cell)
    names = set(last["metrics"])
    if trace:
        assert names and names <= set(c.per_layer)  # device-trace readers find nothing on a CPU
    else:
        assert names == set(c.end_to_end)
    for m in last["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    assert any(ln.startswith("correct: ") and " limit " in ln for ln in lines)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    out = _run(["--workload", "qwen2-7b-int8.chat-steady", "--seed", "1", "--seconds", "5",
                "--trace", "0"], timeout=120)
    assert out.returncode != 0
    assert not any(ln.lstrip().startswith('{"correct"') for ln in out.stdout.splitlines())
    assert "no TPU" in out.stderr


def test_unknown_workload_fails():
    out = _run(["--workload", "nope", "--seed", "1", "--seconds", "5", "--trace", "0"], timeout=60)
    assert out.returncode != 0
