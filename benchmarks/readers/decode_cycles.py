"""What a decoding row waits for between two bursts of tokens.

The unit is the decode CYCLE: from one decode burst's tokens landing on the
host to the next burst's.  With the host a burst ahead the landings are paced
by the device, so a cycle is the device's time for everything dispatched
between two bursts (the later burst included) plus what it sat idle, and every
live row's gap between tokens is the cycle over the burst's steps.  The engine
writes it in three places (``serving/engine.py:Engine._cycle_landed``):

* each finished request's record (``AsyncEngine.request_ring``) gains
  ``last_token_t``, ``decode_cycles``, ``decode_wave_cycles``,
  ``decode_wave_tokens``;
* ``Engine.cycle_ring``, one record a landing that closed a cycle (``seq``,
  ``waves``, ``wave_tokens``, ``landed_t``, ``cycle_s``), hung beside the
  request ring with ``cycle_programs``, the module names of the engine's
  burst and wave;
* the burst's ``engine.commit_host`` annotation (``seq``, ``waves``,
  ``wave_tokens``, ``chained``) in a trace: its start is the landing on the
  trace's own clock.  ``seq`` is the burst's dispatch number, which its
  ``engine.decode_burst`` annotation carries too, and every wave's
  ``engine.prefill_batch``.

The rings are read over the whole window, trace or no trace.  The trace is
split by cycle on the DEVICE's side of the landing: the host sees a burst's
tokens some hundred microseconds after the burst ended, by when the device
has already begun the next program, so a cycle's edge is the end of the burst
module event that the landing carried (the last to end before the
annotation's start).  Between two such edges every module event of the first
device is the engine's burst, its wave, or something else, and the rest is
idle between programs.  Which events are the burst's and the wave's is the
engine's to say (``cycle_programs``), not a list kept here.  The dispatch
numbers check the join from the other side: the wave annotations numbered
between two bursts are a cycle's ``waves``, and the burst module a landing
found by time is set against the one its dispatch pairs with by order
(``host_phases.matched_bursts``, what ``burst_hbm_frac`` rests on).  The shares
are reported only where the join held (``sound``).

Everything below ``window_cycles`` works on ``host_phases``' plain form alone.
A program without the rings or the stats (any commit before PR 52) reads as
None everywhere.
"""

from __future__ import annotations

import sys
from bisect import bisect_left, bisect_right

from benchmarks.estimators import percentile
from benchmarks.readers import host_phases, request_ring

LANDING = "engine.commit_host"  # with ``seq``: a burst's; without: a first-token wave's
BURST, WAVE = "engine.decode_burst", "engine.prefill_batch"  # the dispatches, numbered
KINDS = ("burst", "wave", "other", "gap")
MIN_CYCLES = 20  # of either sort, before the two medians are compared
MISSED_MOST = 0.02  # of the trace's chained cycles, for the shares to be reported


# ------------------------------------------------------------- the rings --

def window_cycles(ctx) -> tuple:
    """(the cycle ring's records that landed in the window, the engine's
    ``cycle_programs``); ([], None) where the program keeps none."""
    from githubrepostorag_tpu.obs.continuous import profilers

    cycles, programs = [], None
    for prof in profilers().values():
        cycles += [c for c in list(getattr(prof, "cycle_ring", None) or ())
                   if ctx.in_window(c.get("landed_t"))]
        programs = programs or getattr(prof, "cycle_programs", None)
    return cycles, programs


def tpot_server_ms(records: list, q: float = 50):
    """Percentile of (last landing - first token) / (output tokens - 1) over
    finished requests' records: the client's ``tpot`` as the engine saw it."""
    vals = []
    for rec in records:
        t = rec.get("timings") or {}
        first, last, n = t.get("first_token_t"), t.get("last_token_t"), rec.get("output_tokens", 0)
        if first is not None and last is not None and n > 1:
            vals.append((last - first) / (n - 1) * 1e3)
    return percentile(vals, q) if vals else None


def wave_cycle_share(records: list):
    """Of the landings that brought the requests tokens, the percent whose
    cycle carried a prefill wave."""
    counted = [r["timings"] for r in records if "decode_cycles" in (r.get("timings") or {})]
    total = sum(t["decode_cycles"] for t in counted)
    return 100.0 * sum(t["decode_wave_cycles"] for t in counted) / total if total else None


def cycle_ms(cycles: list, q: float = 50):
    return percentile([c["cycle_s"] * 1e3 for c in cycles], q) if cycles else None


def wave_extra_ms(cycles: list):
    """Median cycle with a wave in it less the median cycle without: what a
    wave costs every decoding row."""
    with_wave = [c["cycle_s"] * 1e3 for c in cycles if c["waves"] > 0]
    alone = [c["cycle_s"] * 1e3 for c in cycles if c["waves"] == 0]
    if min(len(with_wave), len(alone)) < MIN_CYCLES:
        return None
    return percentile(with_wave, 50) - percentile(alone, 50)


# ------------------------------------------------------------- the trace --

def landings(plain: dict) -> list:
    """Burst landings in the trace, in order: (annotation start, stats)."""
    return [(h[1], h[3]) for h in plain["host"] if h[0] == LANDING and "seq" in h[3]]


def numbered(plain: dict, name: str) -> list:
    """The dispatch numbers on the annotations called ``name``, in order."""
    return [h[3]["seq"] for h in plain["host"] if h[0] == name and "seq" in h[3]]


def split(plain: dict, programs: dict) -> dict | None:
    """The trace's whole cycles, each with its seconds by kind, and the join's
    own check.  A cycle runs from the end of the burst module that one landing
    carried to the end of the next landing's; it is whole when the later
    landing says a burst was in flight before it (``chained``) and both ends
    were found.  A module event is booked to the cycle its START lies in.
    ``missed`` counts cycles whose annotations and module events disagree:
    wave seconds under ``waves`` = 0, no wave module under ``waves`` > 0, not
    exactly one burst module, or another count of wave dispatches numbered
    between the two bursts than ``waves`` (``misnumbered``); ``unjoined``
    counts chained landings an end was not found for, past the trace's head.
    ``order_off`` of ``order_pairs`` burst dispatches pair by order with
    another module than their landing found: not this join's miss, the
    accepted ``burst_hbm_frac``'s."""
    lands = landings(plain)
    if not lands or not plain["devices"]:
        return None
    first = plain["devices"][sorted(plain["devices"])[0]]
    kinds = {programs["burst"]: "burst", programs["wave"]: "wave"}  # jit_<name>(<hash>)
    mods = sorted(([kinds.get(m[0].split("(")[0], "other"), m[1], m[1] + m[2]]
                   for m in first["modules"]), key=lambda m: m[1])
    burst_ends = [m[2] for m in mods if m[0] == "burst"]
    if not burst_ends:  # the engine's burst is no module of this trace: nothing to join
        return None
    # Each landing's edge on the device: the last burst to have ended before the
    # host had its tokens.  Bursts run and land in one order, so that is the
    # burst after the last edge; a landing that finds two ended since then has
    # lost its place (``resynced``), one that finds none has no edge
    edges, j, resynced = [], -1, 0
    for at, _ in lands:
        last = bisect_right(burst_ends, at) - 1
        edges.append(burst_ends[last] if last > j else None)
        resynced += j >= 0 and last > j + 1
        j = max(j, last)
    # The same join from the dispatches' side.  Every dispatch annotation
    # carries its number: the waves numbered between two bursts are in the
    # trace once the earlier burst's dispatch is; and the k-th burst dispatch
    # pairs by order with a module that its number's landing also found
    wave_seqs, burst_seqs = (numbered(plain, name) for name in (WAVE, BURST))
    dispatched = set(burst_seqs)
    by_order = {seq: m[1] + m[2] for seq, (_, m)
                in zip(burst_seqs, host_phases.matched_bursts(plain))}
    paired = [(by_order[st["seq"]], e) for (_, st), e in zip(lands, edges)
              if e is not None and st["seq"] in by_order]
    out = {"cycles": [], "unjoined": 0, "missed": 0, "resynced": resynced,
           "wave_s_unannounced": 0.0, "waves_not_found": 0, "misnumbered": 0,
           "order_pairs": len(paired), "order_off": sum(abs(a - b) > 1e-9 for a, b in paired),
           "fetch_lag_s": [at - e for (at, _), e in zip(lands, edges) if e is not None]}
    i = 0  # modules and cycles both run forward in time: one sweep
    # a landing before the first edge is at the trace's head: its burst had
    # ended when the trace began
    head = next((k for k, e in enumerate(edges) if e is not None), len(edges))
    for k in range(head + 1, len(lands)):
        stats = lands[k][1]
        if not stats["chained"]:
            continue
        t0, t1 = edges[k - 1], edges[k]
        if t0 is None or t1 is None:
            out["unjoined"] += 1
            continue
        seconds = dict.fromkeys(KINDS, 0.0)
        counts = {"burst": 0, "wave": 0}
        while i < len(mods) and mods[i][1] < t0:
            i += 1
        while i < len(mods) and mods[i][1] < t1:
            kind, s, e = mods[i]
            seconds[kind] += e - s
            if kind in counts:
                counts[kind] += 1
            i += 1
        seconds["gap"] = (t1 - t0) - sum(seconds.values())
        cycle = {"seq": stats["seq"], "waves": stats["waves"], "t0": t0, "t1": t1,
                 "seconds": seconds, "wave_modules": counts["wave"]}
        unannounced = cycle["waves"] == 0 and counts["wave"] > 0
        not_found = cycle["waves"] > 0 and counts["wave"] == 0
        s0 = lands[k - 1][1]["seq"]
        misnumbered = s0 in dispatched and cycle["waves"] != (
            bisect_left(wave_seqs, stats["seq"]) - bisect_right(wave_seqs, s0))
        out["wave_s_unannounced"] += seconds["wave"] if unannounced else 0.0
        out["waves_not_found"] += not_found
        out["misnumbered"] += misnumbered
        out["missed"] += unannounced or not_found or misnumbered or counts["burst"] != 1
        out["cycles"].append(cycle)
    return out


def sound(found: dict) -> bool:
    """Whether the join held well enough for the split to be a reading."""
    chained = len(found["cycles"]) + found["unjoined"]
    return 0 < chained and found["missed"] + found["unjoined"] <= MISSED_MOST * chained


def shares(found: dict) -> dict | None:
    """Percent of the whole cycles' seconds by kind; the four sum to 100."""
    total = sum(c["t1"] - c["t0"] for c in found["cycles"])
    if total <= 0:
        return None
    return {kind: 100.0 * sum(c["seconds"][kind] for c in found["cycles"]) / total
            for kind in KINDS}


def ring_against_trace(plain: dict, cycles: list) -> list:
    """Seconds between each ring record's ``landed_t``, mapped onto the trace's
    clock through the nearest ``driver.step`` before it (trace time = the
    event's start + (stamp - ``mono_ns``)), and the start of the annotation
    with the same ``seq``."""
    anchors = sorted((h[3]["mono_ns"] * 1e-9, h[1]) for h in plain["host"]
                     if h[0] == host_phases.STEP and "mono_ns" in h[3])
    at = {stats["seq"]: start for start, stats in landings(plain)}
    out = []
    for c in cycles:
        if c["seq"] in at and anchors:
            mono, start = anchors[max(0, bisect_right(anchors, (c["landed_t"], 0.0)) - 1)]
            out.append(start + (c["landed_t"] - mono) - at[c["seq"]])
    return out


def _report(found: dict, off: list) -> None:
    n = len(found["cycles"])
    lag = found["fetch_lag_s"]
    print(f"[decode_cycles] {n} whole cycles in the trace, {found['unjoined']} chained "
          f"landings without a burst module's end, {found['resynced']} that found two; join: "
          f"{found['missed']} of {n} cycles "
          f"missed ({found['wave_s_unannounced']:.4f} s of wave modules in cycles announced "
          f"with none, {found['waves_not_found']} cycles announced with waves and none found, "
          f"{found['misnumbered']} with another count of waves numbered between their bursts)"
          f"{'' if sound(found) else ': the shares are not reported'}; by order "
          f"{found['order_off']} of {found['order_pairs']} burst dispatches pair with another "
          f"module than their landing found; landing after its burst's end p50 "
          f"{percentile(lag, 50) * 1e3 if lag else float('nan'):.3f} ms; ring stamps against "
          f"annotations: {len(off)} compared, largest "
          f"{max((abs(x) for x in off), default=float('nan')) * 1e3:.3f} ms",
          file=sys.stderr, flush=True)


def traced(ctx) -> dict | None:
    """This run's trace split by cycle, once; None without a trace, the
    annotations' stats or the engine's program names."""
    if not hasattr(ctx, "_decode_cycles"):
        plain = host_phases.phases_of(ctx)
        cycles, programs = window_cycles(ctx)
        found = split(plain, programs) if plain is not None and programs else None
        if found is not None and found["cycles"]:
            _report(found, ring_against_trace(plain, cycles))
        ctx._decode_cycles = found
    return ctx._decode_cycles


# ----------------------------------------------------------------- reader --

def read(ctx, what, kind=None):
    if what == "tpot_server_ms":
        return tpot_server_ms(request_ring.records(ctx))
    if what == "wave_cycle_share":
        return wave_cycle_share(request_ring.records(ctx))
    if what == "cycle_ms":
        return cycle_ms(window_cycles(ctx)[0])
    if what == "wave_extra_ms":
        return wave_extra_ms(window_cycles(ctx)[0])
    if what == "share":
        found = traced(ctx)
        by_kind = shares(found) if found is not None and sound(found) else None
        return by_kind[kind] if by_kind else None
    raise ValueError(f"unknown reading {what!r}")
