"""chip_smoke.py's contract, as far as a machine without a chip can show it:
the rehearsal drives every phase through the real entry points on the CPU,
and without an accelerator (or outside a checkout) the script fails and
prints no result."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _run(args, cwd=ROOT, script=SCRIPT, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}  # conftest's 8 devices
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("args, phases, count", [
    # five server start-ups take a little over a minute on the CPU: too
    # long for tier-1's time limit, so this case runs with the slow tests
    pytest.param([], ["serve", "rag", "flagship"], 1, marks=pytest.mark.slow, id="one-chip"),
    pytest.param(["--chips", "4"], ["tp4"], 4, id="four-chips"),
])
def test_rehearse_runs_every_phase_on_the_cpu(args, phases, count):
    out = _run(["--rehearse", *args])
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    assert lines[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": count}}
    ran = [ln["phase"] for ln in lines[:-1] if ln["phase"] in ("serve", "rag", "flagship", "tp4")]
    assert ran == phases  # --chips 4 runs no one-chip phase, and the reverse
    assert all(ln["platform"] == "cpu" for ln in lines[:-1] if ln["phase"] in phases)


def test_without_an_accelerator_it_fails_and_prints_no_result():
    out = _run([])  # the suite is pinned to the CPU; the script must not follow it there
    assert out.returncode != 0
    assert out.stdout == ""
    assert '"ok": false' in out.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(["--rehearse"], cwd=tmp_path, script=lone)
    assert out.returncode != 0
    assert out.stdout == ""
