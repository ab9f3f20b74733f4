"""Find a cell's knee, once, on the chip: the same set-up as a run, then one
window per offered load, in one process.

    python -m benchmarks.sweep --workload <name> --seed <n> --seconds <s> --loads 2,3,4,5

``--loads`` are requests per second for an open loop and client counts for a
closed one.  Prints one JSON line per load: the cell's end-to-end metrics,
live rows, the queue's length at the window's close, and the share of the
window's requests that ended.  The rate chosen is then written into the
traffic file as a number; the benchmark itself never searches.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

from benchmarks import estimators
from benchmarks.run import Session, count_failures


async def main_async(args) -> int:
    ses = Session(args.workload, args.seed, False)
    await ses.setup()
    key = "rate_rps" if ses.traffic["loop"] == "open" else "clients"
    for load in args.loads.split(","):
        traffic = {**ses.traffic, key: float(load) if key == "rate_rps" else int(load)}
        plan = ses.plan(args.seconds, traffic)
        data = await ses.window(plan, False)
        e2e = {k: v for k, (v, _) in ses.e2e(data, None, traffic).items() if k != "setup_s"}
        attempted, failed, _ = count_failures(data)
        ok = [r for r in estimators.in_window(data["records"]) if not r.get("error")]
        ttft = [v for v in (estimators.ttft_ms(r) for r in ok) if v is not None]
        rows = [n for t, n, _ in ses.probe.bursts if data["t_open"] <= t < data["t_close"]]
        half = data["t_open"] + args.seconds / 2
        late = [estimators.ttft_ms(r) for r in ok if r["due_t"] >= half and r.get("first_t")]
        early = [estimators.ttft_ms(r) for r in ok if r["due_t"] < half and r.get("first_t")]
        print(json.dumps({
            key: load, **e2e, "attempted": attempted, "failed": failed,
            "ttft_p90_ms": estimators.percentile(ttft, 90) if ttft else None,
            "ttft_p50_first_half": estimators.percentile(early, 50) if early else None,
            "ttft_p50_second_half": estimators.percentile(late, 50) if late else None,
            "rows_mean": sum(rows) / len(rows) if rows else None,
            "rows_max": max(rows) if rows else None,
            "waiting_at_close": ses.engine.num_waiting,
            "compiles_in_window": data["compiles_in_window"]}), flush=True)
        await asyncio.sleep(2.0)
    await ses.entry.stop()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--loads", required=True)
    return asyncio.run(main_async(ap.parse_args()))


if __name__ == "__main__":
    import os

    rc = main()
    sys.stdout.flush()
    os._exit(rc)
