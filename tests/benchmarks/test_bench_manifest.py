"""BENCHMARK.json keeps to its contract, and the harness finds a cell's
files by name alone."""

import json
import shutil
from pathlib import Path

import pytest

from benchmarks import manifest

ROOT = manifest.ROOT
MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]


def test_manifest_validates():
    manifest.validate(MAN)
    assert MAN["command"][:3] == ["python3", "-m", "benchmarks.run"]
    assert len(json.dumps(MAN)) < 64 * 1024


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_and_units_keep_to_the_allowed_characters(group):
    for item in MAN[group]:
        assert manifest.NAME_RE.match(item["name"]), item["name"]
        if "unit" in item:
            assert manifest.UNIT_RE.match(item["unit"]), item["unit"]
        for key in ("config", "traffic"):
            if key in item:
                assert manifest.NAME_RE.match(item[key])
        extra = set(item) - {"name", "source", "file", "reduced", "why", "config", "traffic",
                             "chips", "unit", "better", "bound", "layer", "moves", "workloads"}
        assert not extra, f"{item['name']}: keys the contract does not know: {extra}"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = manifest.load_cell(cell, MAN)
    assert c.config["source"].startswith("https://")
    assert c.traffic["loop"] in ("open", "closed")
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2 and c.per_layer
    for name in c.per_layer:
        spec = manifest.metric_spec(name)
        assert (manifest.HERE / "readers" / f"{spec['reader']}.py").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_why_names_the_users_the_layer_and_the_counterpart(cell):
    why = next(w["why"] for w in MAN["workloads"] if w["name"] == cell)
    assert "counterpart" in why and len(why) <= 200


def test_a_cell_added_as_files_alone_is_picked_up(tmp_path):
    """A later PR adds an entry and data files and edits no file that is
    there: copy the data directories, add one traffic file and one entry."""
    here = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "metrics", "readers"):
        shutil.copytree(manifest.HERE / d, here / d)
    traffic = json.loads((here / "traffic" / "chat-steady.json").read_text())
    traffic["rate_rps"] = 1.0
    (here / "traffic" / "chat-light.json").write_text(json.dumps(traffic))
    man = json.loads(json.dumps(MAN))
    man["workloads"].append({"name": "qwen2-7b-int8.chat-light", "config": "qwen2-7b-int8",
                             "traffic": "chat-light", "chips": 1,
                             "why": "a later PR's cell; counterpart chat-steady"})
    for m in man["end_to_end"]:
        if m["name"] in ("ttft_p50_ms", "tpot_p50_ms"):
            m["workloads"].append("qwen2-7b-int8.chat-light")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    shutil.copytree(ROOT / "benchmarks" / "configs", tmp_path / "benchmarks" / "configs",
                    dirs_exist_ok=True)
    manifest.validate(man, root=tmp_path, here=here)
    cell = manifest.load_cell("qwen2-7b-int8.chat-light", man, root=tmp_path, here=here)
    assert cell.traffic["rate_rps"] == 1.0
    assert "decode_rows_mean" in cell.per_layer  # no ``workloads`` key: every chat cell reports it


def test_unknown_names_are_refused():
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no-such-cell", MAN)
    bad = json.loads(json.dumps(MAN))
    bad["end_to_end"][0]["bound"] = 0.5
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)


def test_paths_hold_only_the_benchmark():
    assert MAN["paths"] == ["benchmarks", "tests/benchmarks"]
    for c in MAN["configs"]:
        assert Path(c["file"]).parts[0] == "benchmarks"
