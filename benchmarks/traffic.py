"""The one general traffic generator: a traffic file of parameters and a
seed give a plan of requests.

Every run of a cell offers the same work.  Lengths are a fixed multiset per
cell (quantiles of the file's distribution, so no draw decides them); the
seed decides only the order, the content and the phase of the arrivals.
Arrival gaps of an open loop are exponential from the seed and rescaled so
that the same number of requests falls in the window (which makes them the
order statistics of uniform draws over it).  The load starts ``lead_in_s``
before the window opens and goes on ``tail_s`` past its close, so the batch
is at its steady occupancy for every request that counts; requests due in
the window are counted whenever they finish.

A closed loop's client may pause between an answer and its next request
(``think_s``): the pauses are a fixed multiset too.  Without them, clients
whose calls all decode the same number of tokens fall into lockstep or out
of it from run to run, and the batch they share is faster in step than out.

A plan is plain JSON: the client process reads it and needs no generator.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles of the distribution: the same
    multiset in every run."""
    if n <= 0:
        return []
    kind = spec.get("dist", "fixed")
    if kind == "fixed":
        return [int(spec["value"])] * n
    if kind == "cycle":
        vals = list(spec["values"])
        return [int(vals[i % len(vals)]) for i in range(n)]
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    out = []
    for i in range(n):
        v = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def _paired(traffic: dict, n: int) -> list[tuple[int, int]]:
    """(prompt, output) pairs: both multisets fixed, and the pairing fixed by
    the file's own ``pairing_seed`` so that no run seed changes the work."""
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    outputs = quantile_lengths(traffic["output_tokens"], n)
    random.Random(int(traffic.get("pairing_seed", 0)) * 7919 + n).shuffle(outputs)
    return list(zip(prompts, outputs))


def _arrivals(rng: random.Random, n: int, start: float, length: float) -> list[float]:
    """n + 1 exponential gaps rescaled so that exactly n arrivals fall in
    [start, start + length)."""
    gaps = [rng.expovariate(1.0) for _ in range(n + 1)]
    total = sum(gaps)
    out, acc = [], 0.0
    for g in gaps[:n]:
        acc += g
        out.append(start + length * acc / total)
    return out


def _deal(pairs: list, k: int, rng: random.Random) -> list:
    """The multiset dealt into ``k`` slices of the phase so that every slice
    carries the same number of requests (to within one) and the same work:
    pairs sorted by their token count go round the slices like cards, then
    each slice is shuffled by the seed.  Poisson arrivals keep their
    short-range bursts inside a slice; what no longer varies from seed to
    seed is how much load each few seconds of the window carry."""
    order = sorted(pairs, key=lambda po: po[0] + 4 * po[1])
    starts = list(range(k))
    rng.shuffle(starts)
    slices: list = [[] for _ in range(k)]
    for i, pair in enumerate(order):
        rnd, pos = divmod(i, k)
        slot = starts[pos] if rnd % 2 == 0 else starts[k - 1 - pos]  # boustrophedon
        slices[slot].append(pair)
    for part in slices:
        rng.shuffle(part)
    return slices


def _blocks_for(traffic: dict, j: int, rng: random.Random) -> list[int]:
    """Context blocks of request ``j`` of the fixed multiset: its topic's
    ordered run (shared with every request of that topic, so neighbours
    share pages) and then ``extra`` blocks of the pool in seeded order."""
    b = traffic["blocks"]
    topics, run = int(b["topics"]), int(b["topic_blocks"])
    lo, hi = int(b["extra_blocks"]["min"]), int(b["extra_blocks"]["max"])
    topic = j % topics
    extra = lo + (j // topics) % (hi - lo + 1)
    shared = [(topic * run + k) % b["pool"] for k in range(run)]
    rest = [x for x in range(b["pool"]) if x not in shared]
    return shared + rng.sample(rest, extra)


def make_plan(traffic: dict, seed: int, seconds: float) -> dict:
    rng = random.Random((int(seed) * 1_000_003 + 17) & 0xFFFFFFFFFFFF)
    lead, tail = float(traffic["lead_in_s"]), float(traffic["tail_s"])
    plan = {"entry": traffic["entry"], "loop": traffic["loop"], "window_s": float(seconds),
            "lead_in_s": lead, "tail_s": tail,
            "system_prefix_tokens": int(traffic.get("system_prefix_tokens", 0)),
            "request_timeout_s": float(traffic.get("request_timeout_s", 120))}
    if traffic["loop"] == "open":
        rate = float(traffic["rate_rps"])
        slice_s = float(traffic.get("arrival_slice_s", 0) or 0)
        reqs = []
        for phase, start, length in (("lead", -lead, lead), ("window", 0.0, seconds),
                                     ("tail", seconds, tail)):
            n = int(round(rate * length))
            pairs = _paired(traffic, n)
            k = max(1, int(round(length / slice_s))) if slice_s else 1
            for j, part in enumerate(_deal(pairs, k, rng)):
                dues = _arrivals(rng, len(part), start + length * j / k, length / k)
                for due, (p, o) in zip(dues, part):
                    reqs.append({"phase": phase, "due": due, "prompt_tokens": p,
                                 "output_tokens": o, "words_seed": rng.getrandbits(31)})
        for i, r in enumerate(reqs):
            r["i"] = i
        plan["requests"] = reqs
        return plan
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    clients, per = int(traffic["clients"]), int(traffic["requests_per_client"])
    n = clients * per
    if traffic["entry"] == "rag_jobs":
        kinds = list(traffic["kinds"])
        lanes = []
        for c in range(clients):
            k0 = rng.randrange(len(kinds))
            q0 = {k: rng.randrange(len(traffic["kinds"][k])) for k in kinds}
            lane = []
            for j in range(per):
                kind = kinds[(k0 + j) % len(kinds)]  # exact alternation
                qs = traffic["kinds"][kind]
                lane.append({"kind": kind, "query": qs[(q0[kind] + j // len(kinds)) % len(qs)]})
            lanes.append(lane)
    else:
        outputs = quantile_lengths(traffic["output_tokens"], n)
        prompts = None if "blocks" in traffic else quantile_lengths(traffic["prompt_tokens"], n)
        pool = []
        for j in range(n):
            r = {"output_tokens": outputs[j], "words_seed": rng.getrandbits(31)}
            if prompts is None:
                r["blocks"] = _blocks_for(traffic, j, rng)
            else:
                r["prompt_tokens"] = prompts[j]
            pool.append(r)
        rng.shuffle(pool)
        lanes = [pool[c::clients] for c in range(clients)]
    order = list(range(clients))
    rng.shuffle(order)
    # clients start spread over the lead-in, so no two begin in one step
    starts = {c: -lead + lead * 0.8 * k / max(1, clients) for k, c in enumerate(order)}
    think = float((traffic.get("think_s") or {}).get("max", 0))
    pauses = [think * (j + 0.5) / n for j in range(n)]  # a fixed multiset, uniform on [0, max]
    rng.shuffle(pauses)
    i = 0
    for c, lane in enumerate(lanes):
        for r in lane:
            r["i"], r["client"] = i, c
            if think:  # seconds a client waits after an answer before it asks again
                r["think"] = pauses[i % n]
            i += 1
    plan["clients"] = [{"start": starts[c], "requests": lanes[c]} for c in range(clients)]
    if "ratio" in traffic:
        plan["ratio"] = traffic["ratio"]
    return plan


def window_multiset(plan: dict) -> list[tuple]:
    """What the window offers, as a sorted multiset (tests compare it across
    seeds)."""
    if plan["loop"] == "open":
        return sorted((r["prompt_tokens"], r["output_tokens"])
                      for r in plan["requests"] if r["phase"] == "window")
    out = []
    for c in plan["clients"]:
        for r in c["requests"]:
            out.append((r.get("kind", ""), len(r.get("blocks", ())), r.get("prompt_tokens", 0),
                        r.get("output_tokens", 0)))
    return sorted(out)
