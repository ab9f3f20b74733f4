"""Read the two numbers a correctness limit is set from, in one process:
the comparison's numbers for the program over many seeds, and for the
control (the reference at the next precision below) over a few.

    python -m benchmarks.seeds --workload <name> --seeds 1,2,...,12 --control 3

Weights, prompts and the sample all come from each seed, as in a run; no
server and no timed window are needed.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmarks import textgen, traffic as traffic_mod
from benchmarks.run import Session


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3, help="seeds that also run the control")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks import correctness, system
    from githubrepostorag_tpu.runtime import enable_compile_cache

    ses = Session(args.workload, 0, args.rehearse)  # the cell's files; never set up
    config, traffic, model, needs = ses.config, ses.traffic, ses.model, ses.needs
    if traffic["entry"] != "openai_chat":
        raise SystemExit("seeds: prompts come from chat traffic; a RAG cell shares its "
                         "configuration's limits with the chat cells")
    enable_compile_cache()
    system.require_devices(ses.cell.chips, args.rehearse)
    tokenizer = system.load_tokenizer(ses.build_tokenizer(), True)
    prompts = textgen.Prompts(tokenizer)
    spec, fuse = ses.correctness_spec(), ses.fused
    k = int(spec["sequences"])
    engine = None
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        engine = None
        gc.collect()
        engine, _ = system.build_engine(config, model, needs, seed)
        plan = traffic_mod.make_plan(traffic, seed, 30.0)
        textgen.render_plan(plan, traffic, prompts)
        reqs = plan["requests"] if plan["loop"] == "open" else \
            [r for c in plan["clients"] for r in c["requests"]]
        ids = [tokenizer.encode_chat(r["messages"]) for r in reqs[:8 * k]]
        wseed = system.weight_seed(seed)
        out = {"seed": seed, "program": correctness.check(
            engine, model, wseed, fuse, ids, seed, spec)["numbers"]}
        if n < args.control:
            out["control"] = correctness.check(
                engine, model, wseed, fuse, ids, seed, spec,
                control=config["correctness"]["precision_control"])["numbers"]
        out["seconds"] = round(time.monotonic() - t0, 1)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import os

    rc = main()
    sys.stdout.flush()
    os._exit(rc)
