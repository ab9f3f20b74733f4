"""Readings from the program's host-phase annotations in the profiler trace
(``utils/profiling.annotate``): the counts each dispatch carries, what the
always-on planes cost a step, and every idle gap of the device named by the
phase the driver was in when it began.

``benchmarks.trace.load`` keeps four host names and no metadata, so this
reader loads the xplane again (it is still on disk when readers run) into
the same plain form with one addition: a host event is ``[name, start, dur,
stats]``.  Every function below ``load`` works on the plain form alone, so
the arithmetic is tested on a recorded sample without a chip.  A trace of a
program that writes none of these annotations (any commit before PR 24)
reads as None.  (The device side has nothing to add: on this libtpu an op
event carries its instruction and three timing stats, and no scope path.)
"""

from __future__ import annotations

import re

from benchmarks import manifest, shapes, trace as trace_mod

# the driver's cycle, leaf phases first: at most one of them is open at a time
COMMIT = ("engine.commit_fetch", "engine.commit_host")
SCHED = ("engine.admit", "engine.burst_prepare", "engine.prefill_batch", "engine.decode_burst")
OBS = ("driver.export", "driver.emit")
OTHER = ("driver.wait", "embed.batch", "index.search")
STEP = "driver.step"
NAMES = COMMIT + SCHED + OBS + OTHER + (STEP, "server.submit_wait")


def load(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == trace_mod.OPS_LINE:
                    for e in line.events:
                        name, opcode = trace_mod.short_name(e.name)
                        if opcode not in trace_mod.CONTAINERS:
                            dev["ops"].append([name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                               opcode])
                elif line.name == trace_mod.MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                                      for e in line.events]
            out["devices"][m.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in NAMES:
                        stats = {k: v for k, v in e.stats if isinstance(v, (int, float))}
                        out["host"].append([e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                            stats])
    out["host"].sort(key=lambda x: x[1])
    return out


def head(plain: dict, seconds: float) -> dict:
    """The first ``seconds`` of a plain trace from its first device op, times
    rebased to it and events cut at its end: the sample kept with the tests."""
    starts = [o[1] for d in plain["devices"].values() for o in d["ops"]]
    t0 = min(starts) if starts else min((h[1] for h in plain["host"]), default=0.0)
    keep = lambda ev: t0 <= ev[1] < t0 + seconds  # noqa: E731
    shift = lambda ev: [ev[0], round(ev[1] - t0, 9),  # noqa: E731
                        round(min(ev[2], t0 + seconds - ev[1]), 9), *ev[3:]]
    return {"devices": {k: {"ops": [shift(o) for o in d["ops"] if keep(o)],
                            "modules": [shift(m) for m in d["modules"] if keep(m)]}
                        for k, d in plain["devices"].items()},
            "host": [shift(h) for h in plain["host"] if keep(h)]}


def phases_of(ctx):
    """The plain trace of this run, loaded once; None when there is no trace
    or the program wrote no ``driver.step`` into it."""
    if not hasattr(ctx, "_host_phases"):
        plain = None
        if ctx.trace_span is not None:
            try:
                path = trace_mod.find_xplane(
                    str(manifest.ROOT / ".bench_work" / ctx.cell.name / "trace"))
                plain = load(path)
            except FileNotFoundError:
                plain = None
        if plain is not None and not any(h[0] == STEP for h in plain["host"]):
            plain = None
        ctx._host_phases = plain
    return ctx._host_phases


# ------------------------------------------------------------------ idle --

def window_of(plain: dict) -> tuple:
    """The window ``benchmarks.trace.reduce`` measures idle over: first start
    to last end over the device ops and the host spans it keeps."""
    spans = [(o[1], o[1] + o[2]) for d in plain["devices"].values() for o in d["ops"]]
    spans += [(h[1], h[1] + h[2]) for h in plain["host"] if h[0] in trace_mod.HOST_SPANS]
    return min(s for s, _ in spans), max(e for _, e in spans)


def _pick(active: list) -> str:
    """Of the host events open at an instant, the one that explains an idle
    device: the driver's leaf phase, else the step it is in, else an encoder
    or index call in flight on another thread, else the driver's sleep."""
    for group in (COMMIT + SCHED + OBS, (STEP,), ("embed.batch", "index.search"),
                  ("driver.wait",)):
        hits = [ev for ev in active if ev[0] in group]
        if hits:
            return max(hits, key=lambda ev: ev[1])[0]
    return "none"


def labelled_gaps(plain: dict) -> list:
    """(start, end, name) of every idle gap of the first device: ``name`` is
    the host phase open when the gap began (``none``: no annotation at all)."""
    w0, w1 = window_of(plain)
    first = plain["devices"][sorted(plain["devices"])[0]]
    events = [h for h in plain["host"] if h[0] != "server.submit_wait"]
    out, active, i = [], [], 0
    for s, e in trace_mod.gaps([(o[1], o[1] + o[2]) for o in first["ops"]], w0, w1):
        while i < len(events) and events[i][1] <= s:  # both sorted by start: one sweep
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] + ev[2] > s]
        out.append((s, e, _pick(active)))
    return out


def idle_by_phase(plain: dict) -> dict:
    """Idle seconds of the first device by phase name, and ``window_s``."""
    if not plain["devices"]:
        return {}
    out: dict = {}
    for s, e, name in labelled_gaps(plain):
        out[name] = out.get(name, 0.0) + (e - s)
    w0, w1 = window_of(plain)
    out["window_s"] = w1 - w0
    return out


def idle_share(idle: dict, names, unnamed: bool = False) -> float | None:
    """Percent of the window idle under ``names`` (``idle``: idle_by_phase's)."""
    if not idle.get("window_s"):
        return None
    seconds = sum(idle.get(n, 0.0) for n in names)
    if unnamed:  # gaps that began under no annotation at all
        seconds += idle.get("none", 0.0)
    return 100.0 * seconds / idle["window_s"]


# ------------------------------------------------------------- dispatches --

def burst_dispatches(plain: dict) -> list:
    """``engine.decode_burst`` annotations in order: (start, rows, kv_tokens, steps)."""
    return [(h[1], h[3]["rows"], h[3]["kv_tokens"], h[3]["steps"])
            for h in plain["host"] if h[0] == "engine.decode_burst" and "rows" in h[3]]


def matched_bursts(plain: dict, module: str = "decode_burst") -> list:
    """(dispatch, module event) pairs: the k-th dispatch in the trace runs as
    the k-th module event that starts after the first dispatch (bursts are
    pipelined one deep and the device runs them in order, so a module event
    that began earlier belongs to a dispatch from before the trace).
    Dispatches whose execution lies past the trace's end find no partner."""
    devices = plain["devices"]
    dispatches = burst_dispatches(plain)
    if not devices or not dispatches:
        return []
    mods = sorted((m for m in devices[sorted(devices)[0]]["modules"] if module in m[0]),
                  key=lambda m: m[1])
    mods = [m for m in mods if m[1] >= dispatches[0][0]]
    return list(zip(dispatches, mods))


# ----------------------------------------------------------------- reader --

def read(ctx, what, names=(), unnamed=False):
    plain = phases_of(ctx)
    if plain is None:
        return None
    if what == "burst_rows_mean":
        rows = [d[1] for d in burst_dispatches(plain)]
        return sum(rows) / len(rows) if rows else None
    if what == "obs_ms_per_step":
        steps = sum(1 for h in plain["host"] if h[0] == STEP)
        export = sum(h[2] for h in plain["host"]
                     if h[0] == "driver.export" and h[3].get("work", 1))
        return 1e3 * export / steps if steps else None
    if what == "idle_share":
        if not hasattr(ctx, "_idle_by_phase"):  # four metrics read one sweep
            ctx._idle_by_phase = idle_by_phase(plain)
        return idle_share(ctx._idle_by_phase, names, unnamed)
    if what == "burst_hbm_frac":
        pairs = matched_bursts(plain)
        if not pairs or ctx.peaks is None:
            return None
        wbytes = {"int8": 1.0, "bfloat16": 2.0}[ctx.config["weights"]["dtype"]]
        nbytes = sum(shapes.burst_bytes(ctx.model, wbytes, rows, kv, steps)[0]
                     for (_, rows, kv, steps), _ in pairs)
        seconds = sum(m[2] for _, m in pairs)
        return 100.0 * nbytes / (seconds * ctx.peaks["hbm_bytes_per_s"] * ctx.chips) \
            if seconds else None
    raise ValueError(f"unknown reading {what!r}")


def main(argv=None) -> int:
    """What a builder looks at after a ``BENCH_KEEP_TRACE=1 ... --trace 1`` run:
    every annotation's count and seconds, idle by phase with the longest gaps
    that began under no annotation, the dispatches matched to module events;
    with ``--inside NAME`` what the runtime's own threads did during the longest
    NAME event; with ``--sample``, the head of the plain trace for the tests."""
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("cell")
    ap.add_argument("--inside", default=None, help="an annotation to look inside")
    ap.add_argument("--sample", type=float, default=0.0, help="seconds of plain trace to keep")
    ap.add_argument("--out", default=None, help="where the sample goes")
    args = ap.parse_args(argv)
    path = trace_mod.find_xplane(str(manifest.ROOT / ".bench_work" / args.cell / "trace"))
    plain = load(path)
    by_name: dict = {}
    for h in plain["host"]:
        n, total = by_name.get(h[0], (0, 0.0))
        by_name[h[0]] = (n + 1, total + h[2])
    report = {"xplane_bytes": os.path.getsize(path),
              "host_events": {k: {"count": n, "seconds": s} for k, (n, s) in sorted(by_name.items())},
              "dispatches": len(burst_dispatches(plain)), "matched": len(matched_bursts(plain))}
    if plain["devices"]:
        w0, _ = window_of(plain)
        report["idle_by_phase_s"] = idle_by_phase(plain)
        steps = [h for h in plain["host"] if h[0] == STEP]
        report["first_step_at_s"] = steps[0][1] - w0 if steps else None
        unnamed = [[round(s - w0, 6), round(e - s, 6)] for s, e, name in labelled_gaps(plain)
                   if name == "none"]
        report["unnamed_gaps_at_s"] = sorted(unnamed, key=lambda g: -g[1])[:12]
    if args.inside:
        from jax.profiler import ProfileData

        target = max((h for h in plain["host"] if h[0] == args.inside), key=lambda h: h[2],
                     default=None)
        inside = []
        if target is not None:
            for plane in ProfileData.from_file(path).planes:
                if plane.name.startswith("/host:"):
                    for line in plane.lines:
                        for e in line.events:
                            t = e.start_ns * 1e-9
                            if target[1] <= t < target[1] + target[2] and e.duration_ns > 1e6:
                                inside.append([line.name, e.name[:60], round(t - target[1], 6),
                                               round(e.duration_ns * 1e-9, 6)])
        report["inside"] = {"event": target, "longest": sorted(inside, key=lambda x: -x[3])[:40]}
    print(json.dumps(report, indent=1))
    if args.sample and args.out:
        with open(args.out, "w") as f:
            json.dump(head(plain, args.sample), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
