"""From a profiler trace to numbers: device busy/idle as the union of op
intervals, time per op, time per program, and each idle gap named by what
the host was doing in it.

Two steps, so that the arithmetic can be tested without a chip: ``load``
turns the profiler's ``.xplane.pb`` into a plain dict (seconds, floats),
``reduce`` works on that dict alone.  The plain form:

    {"devices": {"0": {"ops": [[name, start, dur, opcode], ...],
                       "modules": [[name, start, dur], ...]}, ...},
     "host": [[name, start, dur], ...]}
"""

from __future__ import annotations

import glob
import os
import re

# host annotations the program writes (utils/profiling.annotate) and that
# gaps are attributed to
HOST_SPANS = ("engine.decode_burst", "engine.prefill_batch", "embed.batch", "index.search")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_INSTR_RE = re.compile(r"^%?([^\s=]+) = \(?([a-z]+[0-9]*)\[([0-9,]*)\]")
_OPCODE_RE = re.compile(r"[})] ([a-z][a-z0-9\-]*)\(")
CONTAINERS = ("while", "conditional", "call")  # their time is their children's


def short_name(instr: str) -> tuple:
    """An HLO instruction as the trace names it (``%fusion.198 = bf16[32,1,
    37888]{...} fusion(...)``) -> (``fusion.198_bf16_32_1_37888_``, opcode).
    The shape stays in the name so that an op can be recognised after a
    rebuild renumbers it."""
    m = _INSTR_RE.match(instr)
    op = _OPCODE_RE.search(instr)
    opcode = op.group(1) if op else ""
    if not m:
        return instr[:64], opcode
    return f"{m.group(1)}_{m.group(2)}_{m.group(3).replace(',', '_')}_", opcode


def load(xplane_path: str, host_names=HOST_SPANS) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        name, opcode = short_name(e.name)
                        if opcode not in CONTAINERS:
                            dev["ops"].append([name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                               opcode])
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                                      for e in line.events]
            out["devices"][m.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        out["host"].append([e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9])
    out["host"].sort(key=lambda x: x[1])
    return out


def describe(xplane_path: str, limit: int = 6) -> dict:
    """Plane and line names with a few events each: what a builder looks at
    before trusting ``load`` on a new kind of trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = []
            for i, e in enumerate(line.events):
                if i >= limit:
                    break
                try:
                    stats = {k: str(v)[:80] for k, v in e.stats}
                except Exception:  # noqa: BLE001
                    stats = {}
                evs.append({"name": e.name[:80], "start_ns": e.start_ns,
                            "dur_ns": e.duration_ns, "stats": stats})
            lines[line.name] = evs
        out[plane.name] = lines
    return out


def head(plain: dict, seconds: float) -> dict:
    """The first ``seconds`` of a plain trace, times rebased to its start:
    small enough to keep with the tests."""
    starts = [o[1] for d in plain["devices"].values() for o in d["ops"]]
    if not starts:
        return plain
    t0 = min(starts)
    keep = lambda ev: t0 <= ev[1] < t0 + seconds  # noqa: E731
    shift = lambda ev: [ev[0], round(ev[1] - t0, 9), round(ev[2], 9), *ev[3:]]  # noqa: E731
    return {"devices": {k: {"ops": [shift(o) for o in d["ops"] if keep(o)],
                            "modules": [shift(m) for m in d["modules"] if keep(m)]}
                        for k, d in plain["devices"].items()},
            "host": [shift(h) for h in plain["host"] if keep(h)]}


def union_length(intervals) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """The parts of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def attribute_gap(gap, host: list, lookahead_s: float = 0.02) -> str:
    """Name an idle gap by the host annotation that covers its start, else
    by the annotation that begins next (within ``lookahead_s`` of its end),
    else ``unattributed``."""
    s, e = gap
    for name, hs, hd in host:
        if hs <= s < hs + hd:
            return name
    nxt = [(hs, name) for name, hs, hd in host if s <= hs <= e + lookahead_s]
    if nxt:
        return "before:" + min(nxt)[1]
    return "unattributed"


def reduce(plain: dict, top: int = 10) -> dict:
    devices = plain["devices"]
    if not devices:
        return {}
    spans = [(o[1], o[1] + o[2]) for dev in devices.values() for o in dev["ops"]]
    spans += [(s, s + d) for _, s, d in plain["host"]]
    if not spans:
        return {}
    w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    busy = [union_length([(max(o[1], w0), min(o[1] + o[2], w1)) for o in dev["ops"]])
            for dev in devices.values()]
    first = devices[sorted(devices)[0]]
    per_op: dict = {}
    per_opcode: dict = {}
    for name, _, d, opcode in first["ops"]:
        per_op[name] = per_op.get(name, 0.0) + d
        per_opcode[opcode] = per_opcode.get(opcode, 0.0) + d
    per_module: dict = {}
    for name, _, d in first["modules"]:
        key = re.sub(r"\(.*$", "", name)
        per_module[key] = per_module.get(key, 0.0) + d
    idle: dict = {}
    for g in gaps([(o[1], o[1] + o[2]) for o in first["ops"]], w0, w1):
        label = attribute_gap(g, plain["host"])
        idle[label] = idle.get(label, 0.0) + (g[1] - g[0])
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "window_s": w1 - w0,
        "busy_s": sum(busy) / len(busy),
        "busy_first_s": busy[0],
        "per_op": per_op,
        "per_opcode": per_opcode,
        "per_module": per_module,
        "device_ops": [[k, v] for k, v in rank(per_op)],
        "idle_gaps": [[k, v] for k, v in rank(idle)],
    }


def op_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds (first device) of the ops whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("per_op", {}).items() if rx.search(k))


def module_seconds(reduced: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("per_module", {}).items() if rx.search(k))


def opcode_seconds(reduced: dict, opcodes) -> float:
    return sum(v for k, v in reduced.get("per_opcode", {}).items() if k in opcodes)
