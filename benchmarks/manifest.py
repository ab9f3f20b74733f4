"""BENCHMARK.json and the data files its names point to."""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """BENCHMARK.json or one of the files it names breaks the contract."""


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict      # configs/<config>.json
    traffic: dict     # traffic/<traffic>.json
    end_to_end: list  # names of the end-to-end metrics this cell reports
    per_layer: list   # names of the per-layer metrics this cell reports


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _data_file(kind: str, name: str, here: Path) -> dict:
    path = here / kind / f"{name}.json"
    if not path.is_file():
        raise ManifestError(f"{kind}/{name}.json not found (named in BENCHMARK.json)")
    return json.loads(path.read_text())


def metric_spec(name: str, here: Path = HERE) -> dict:
    """metrics/<name>.json: layer, unit, better, source, moves, reader."""
    return _data_file("metrics", name, here)


def cells_of(metric: dict, manifest: dict, all_metrics: list) -> list:
    """The cells a metric is reported in: its ``workloads`` key, else every
    cell that reports the end-to-end metric it moves (an end-to-end metric
    without the key is reported everywhere)."""
    if "workloads" in metric:
        return list(metric["workloads"])
    names = [w["name"] for w in manifest["workloads"]]
    moves = metric.get("moves")
    if moves is None:
        return names
    target = next(m for m in all_metrics if m["name"] == moves)
    return cells_of(target, manifest, all_metrics)


def load_cell(workload: str, manifest: dict | None = None, root: Path = ROOT,
              here: Path = HERE) -> Cell:
    manifest = manifest or load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise ManifestError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next((c for c in manifest["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise ManifestError(f"workload {workload!r} names unknown config {entry['config']!r}")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = _data_file("traffic", entry["traffic"], here)
    e2e = manifest["end_to_end"]
    e2e_here = [m["name"] for m in e2e if workload in cells_of(m, manifest, e2e)]
    pl_here = [m["name"] for m in manifest["per_layer"]
               if workload in cells_of(m, manifest, e2e)]
    return Cell(workload, entry["config"], entry["traffic"], int(entry["chips"]),
                config, traffic, e2e_here, pl_here)


def validate(manifest: dict, root: Path = ROOT, here: Path = HERE) -> None:
    """The parts of the contract a test can hold the file to."""
    keys = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(manifest) != keys:
        raise ManifestError(f"BENCHMARK.json keys {sorted(manifest)} != {sorted(keys)}")
    if not 1 <= int(manifest["run_seconds"]) <= 51:
        raise ManifestError("run_seconds outside 1..51")
    seen: set = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for item in manifest[group]:
            name = item["name"]
            if not NAME_RE.match(name):
                raise ManifestError(f"{group}: bad name {name!r}")
            if (group in ("end_to_end", "per_layer") and ("metric", name) in seen) or \
                    (group, name) in seen:
                raise ManifestError(f"duplicate name {name!r}")
            seen.add((group, name))
            if group in ("end_to_end", "per_layer"):
                seen.add(("metric", name))
                if not UNIT_RE.match(item["unit"]):
                    raise ManifestError(f"{name}: bad unit {item['unit']!r}")
                if item["better"] not in ("lower", "higher"):
                    raise ManifestError(f"{name}: better must be lower|higher")
                if item["source"] not in SOURCES:
                    raise ManifestError(f"{name}: unknown source {item['source']!r}")
    e2e_names = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e_names:
        raise ManifestError("no setup_s among the end-to-end metrics")
    for m in manifest["end_to_end"]:
        if not 0 < float(m["bound"]) <= 0.1:
            raise ManifestError(f"{m['name']}: bound outside (0, 0.1]")
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{m['name']}: end-to-end source must be host_clock|device_trace")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e_names:
            raise ManifestError(f"{m['name']}: moves unknown metric {m['moves']!r}")
        spec = metric_spec(m["name"], here)
        for k in ("layer", "unit", "better", "source", "moves"):
            if spec.get(k) != m[k]:
                raise ManifestError(f"metrics/{m['name']}.json disagrees with BENCHMARK.json on {k}")
        if not (here / "readers" / f"{spec['reader']}.py").is_file():
            raise ManifestError(f"{m['name']}: reader {spec['reader']!r} not found")
    cfg_names = {c["name"] for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        if w["config"] not in cfg_names:
            raise ManifestError(f"{w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{w['name']}: chips must be 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"{w['name']}: config/traffic pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        if not (len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]):
            raise ManifestError(f"{w['name']}: why too long or multi-line")
        cell = load_cell(w["name"], manifest, root, here)
        if "setup_s" not in cell.end_to_end or len(cell.end_to_end) < 2 or not cell.per_layer:
            raise ManifestError(f"{w['name']}: needs setup_s, another end-to-end metric "
                                "and a per-layer metric")
        for name in cell.per_layer:
            moves = next(m["moves"] for m in manifest["per_layer"] if m["name"] == name)
            if moves not in cell.end_to_end:
                raise ManifestError(f"{w['name']}: {name} moves {moves}, which the cell does not report")
    if used != cfg_names:
        raise ManifestError(f"configs used by no cell: {sorted(cfg_names - used)}")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        raise ManifestError("too many four-chip cells")
    paths = manifest["paths"]
    for c in manifest["configs"]:
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            raise ManifestError(f"{c['name']}: file outside paths")
        if not (root / c["file"]).is_file():
            raise ManifestError(f"{c['name']}: {c['file']} missing")
