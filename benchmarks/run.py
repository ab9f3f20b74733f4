"""One cell, one run, one JSON line.

    python -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (tokenizer, weights from the seed on the device, warm-up of the
cell's own programs, the lead-in that brings the batch to its steady
occupancy) is everything before the window opens and is reported as
``setup_s``.  The window lasts ``--seconds``; the load goes on past it
until the window's requests have ended, and nothing is cut at an edge.
Then, outside the window: the correctness comparison, the reduction of
the records (and, with ``--trace 1``, of the device trace), and the line.

Without a TPU (or with fewer chips than the cell asks for) the run ends
with a non-zero code and prints no result.  ``--rehearse`` is for tests:
tiny widths on a CPU the caller pinned, platform named in the line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

from benchmarks import estimators, manifest, textgen, traffic as traffic_mod  # noqa: E402

ROOT = manifest.ROOT
WORK = ROOT / ".bench_work"  # in .gitignore: everything made at run time lives here
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "rope_theta", "rms_norm_eps",
              "tie_word_embeddings", "max_position_embeddings")


class Context:
    """What a per-layer reader may read."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)

    def window_records(self) -> list:
        return [r for r in estimators.in_window(self.records) if not r.get("error")]

    def in_window(self, t) -> bool:
        return t is not None and self.t_open <= t < self.t_close

    def in_trace(self, t) -> bool:
        return self.trace_span is not None and t is not None \
            and self.trace_span[0] <= t < self.trace_span[1]


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def model_of(config: dict, rehearse: bool) -> dict:
    model = {k: config[k] for k in MODEL_KEYS}
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


async def run_client(plan: dict, base: str, work: Path, on_open) -> dict:
    """Start the load generator (a child that never touches JAX), tell it
    when the window opens, wait for it to end.  The child is always reaped."""
    plan_path, out_path = work / "plan.json", work / "records.json"
    plan_path.write_text(json.dumps(plan))
    out_path.unlink(missing_ok=True)
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "benchmarks.client", str(plan_path), str(out_path), base,
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE, cwd=str(ROOT))
    try:
        line = await asyncio.wait_for(proc.stdout.readline(), timeout=60)
        if line.strip() != b"READY":
            raise RuntimeError(f"load generator said {line!r}")
        t_open = time.monotonic() + plan["lead_in_s"] + 0.25
        proc.stdin.write(f"{t_open!r}\n".encode())
        await proc.stdin.drain()
        on_open(t_open)
        limit = plan["lead_in_s"] + plan["window_s"] + plan["tail_s"] + 60
        await asyncio.wait_for(proc.wait(), timeout=limit)
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    return json.loads(out_path.read_text())


async def traced(t_open: float, start: float, length: float, trace_dir: Path) -> tuple:
    """Trace ``length`` seconds of the window, off the event loop's thread."""
    import jax

    loop = asyncio.get_running_loop()
    await asyncio.sleep(max(0.0, t_open + start - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.monotonic()
    await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
        str(trace_dir), profiler_options=opts))
    await asyncio.sleep(max(0.0, t0 + length - time.monotonic()))
    t1 = time.monotonic()
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    return t0, t1


class Session:
    """The system under test, set up once; ``window`` drives one plan
    through it.  ``run.py`` opens one window, ``sweep.py`` several."""

    def __init__(self, workload: str, seed: int, rehearse: bool) -> None:
        self.man = manifest.load_manifest()
        self.cell = manifest.load_cell(workload, self.man)
        self.config, self.traffic = self.cell.config, self.cell.traffic
        if rehearse:  # same generator, same entry point, sizes a CPU can hold
            self.traffic = {**self.traffic, **self.traffic.get("rehearse", {})}
        self.seed, self.rehearse = seed, rehearse
        self.work = WORK / self.cell.name
        self.model = model_of(self.config, rehearse)
        self.needs = self.traffic.get("rehearse_needs" if rehearse else "needs", {})

    def build_tokenizer(self) -> Path:
        return textgen.build_tokenizer(
            WORK / ("tokenizer-rehearse" if self.rehearse else "tokenizer"),
            self.model["vocab_size"], bpe_vocab=1800 if self.rehearse else 32000)

    async def setup(self) -> None:
        from benchmarks import system  # imports the program: fails in a bare directory

        self.work.mkdir(parents=True, exist_ok=True)
        self.rag = importlib.import_module("benchmarks.rag") \
            if self.traffic["entry"] == "rag_jobs" else None
        tok_dir = self.build_tokenizer()
        from githubrepostorag_tpu.runtime import enable_compile_cache

        cache_dir = enable_compile_cache()
        import jax

        # every program goes to the persistent cache, the small ones too
        # (the program keeps only those that took a second to compile): a
        # run after the first then compiles nothing, in set-up or window
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.compiles: list = []  # (t, seconds) of every backend compile
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: self.compiles.append((time.monotonic(), secs))
            if event.endswith("backend_compile_duration") else None)
        self.device = system.require_devices(self.cell.chips, self.rehearse)
        log(f"device {self.device}, compile cache {cache_dir}")
        if self.rag is not None:  # ingest through the seeded encoder, index filled to size
            self.rag.prepare(self, log)
        self.engine, _ = system.build_engine(self.config, self.model, self.needs, self.seed)
        rows = self.needs.get("warm_prefill_rows", [1, 2])
        system.warm(self.engine, rows, sampled=bool(self.needs.get("warm_sampled_burst")))
        self.probe = system.Probe()
        self.probe.attach(self.engine)
        log(f"engine warm (prefill rows {rows})")
        self.tokenizer = system.load_tokenizer(tok_dir, self.traffic.get("ignore_eos", True))
        if self.rag is not None:
            self.entry = self.rag.RagEntry(self)
        else:
            self.entry = system.OpenAIEntry(self.engine, self.tokenizer, self.cell.config_name)
            self.prompts = textgen.Prompts(self.tokenizer)
        import logging

        logging.getLogger("aiohttp.access").setLevel(logging.WARNING)  # one line a request
        self.base = await self.entry.start()
        log(f"serving on {self.base}")

    def plan(self, seconds: float, traffic: dict | None = None) -> dict:
        traffic = traffic or self.traffic
        plan = traffic_mod.make_plan(traffic, self.seed, seconds)
        if self.rag is not None:
            self.rag.render_plan(plan, traffic, self)
        else:
            textgen.render_plan(plan, traffic, self.prompts)
        return plan

    async def window(self, plan: dict, trace: bool) -> dict:
        """Drive one plan.  Returns the client's records with the window's
        edges, the trace span and whether a step program compiled inside."""
        state: dict = {}
        watch = self.entry.async_engine.profiler
        client = asyncio.ensure_future(
            run_client(plan, self.base, self.work, lambda t: state.setdefault("t_open", t)))
        while "t_open" not in state and not client.done():
            await asyncio.sleep(0.01)
        trace_task, compiles = None, 0
        self.trace_dir = self.work / "trace"
        if "t_open" in state:
            t_open, seconds = state["t_open"], plan["window_s"]
            if trace:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                spec = self.traffic.get("trace", {})
                length = min(float(spec.get("seconds", 8.0)), seconds)
                start = min(float(spec.get("start_s", seconds / 3)), seconds - length)
                trace_task = asyncio.ensure_future(traced(t_open, start, length, self.trace_dir))
            await asyncio.sleep(max(0.0, t_open - time.monotonic()))
            before = watch.live_compiles
            await asyncio.sleep(max(0.0, t_open + seconds - time.monotonic()))
            compiles = watch.live_compiles - before
        data = await client
        data["trace_span"] = await trace_task if trace_task is not None else None
        data["compiles_in_window"] = compiles
        small = [d for t, d in self.compiles if data["t_open"] <= t < data["t_close"]]
        data["backend_compiles_in_window"] = (len(small), sum(small))
        return data

    def e2e(self, data: dict, setup_s: float | None, traffic: dict | None = None) -> dict:
        cell, traffic = self.cell, traffic or self.traffic
        ok = [r for r in estimators.in_window(data["records"]) if not r.get("error")]
        out = {}
        window = data["t_close"] - data["t_open"]
        for name in cell.end_to_end:
            if name == "setup_s":
                out[name] = (setup_s, "s")
            elif name == "ttft_p50_ms":
                vals = [v for v in (estimators.ttft_ms(r) for r in ok) if v is not None]
                out[name] = (estimators.percentile(vals, 50) if vals else None, "ms")
            elif name == "tpot_p50_ms":
                vals = [v for v in (estimators.tpot_ms(r) for r in ok) if v is not None]
                out[name] = (estimators.percentile(vals, 50) if vals else None, "ms")
            elif name == "out_tok_s":
                n = estimators.tokens_by_arrival(data["records"], data["t_open"],
                                                 data["t_close"])
                out[name] = (n / window, "tokens/s")
            elif name == "answer_s_mean":
                rows = [{"kind": r["kind"], "seconds": r["done_t"] - r["sent_t"]}
                        for r in ok if r.get("done_t")]
                out[name] = (estimators.stratified_mean(rows, traffic["ratio"]), "s")
            else:
                raise SystemExit(f"no estimator for end-to-end metric {name!r}")
        return out

    def per_layer(self, data: dict, reduced, peak_bytes: int, extra: dict) -> dict:
        from benchmarks.peaks import peaks_for

        peaks = peaks_for(self.device["kind"]) if self.device["platform"] == "tpu" else None
        ctx = Context(records=data["records"], t_open=data["t_open"], t_close=data["t_close"],
                      probe=self.probe, trace=reduced, trace_span=data["trace_span"],
                      cell=self.cell, model=self.model, config=self.config,
                      traffic=self.traffic, peaks=peaks, peak_bytes=peak_bytes, extra=extra,
                      chips=self.cell.chips, decode_burst=self.engine.decode_burst)
        out = {}
        for name in self.cell.per_layer:
            spec = manifest.metric_spec(name)
            reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:
                out[name] = {"value": float(value), "unit": spec["unit"]}
        return out

    def correctness_spec(self) -> dict:
        """The traffic's sample sizes with the configuration's limits."""
        spec = {**self.traffic["correctness"], "limits": self.config["correctness"]["limits"]}
        if self.rehearse:
            spec.update(self.traffic.get("rehearse_correctness", {}))
            spec["limits"] = self.config["rehearse"]["limits"]
        return spec

    @property
    def fused(self) -> bool:
        return (self.config.get("mesh") or {}).get("tp", 1) == 1

    def correctness(self, data: dict, control: str | None = None) -> dict:
        from benchmarks import correctness, system

        prompts = [ids for t, ids, _ in self.probe.prompts if t >= data["t_open"] - 1.0]
        return correctness.check(self.engine, self.model, system.weight_seed(self.seed),
                                 self.fused, prompts, self.seed, self.correctness_spec(),
                                 control=control)


def count_failures(data: dict) -> tuple:
    recs = estimators.in_window(data["records"])
    failed = [r for r in recs if r.get("error") or not r.get("done_t")]
    return len(recs), len(failed), [r.get("error") for r in failed[:3]]


async def main_async(args) -> int:
    ses = Session(args.workload, args.seed, args.rehearse)
    await ses.setup()
    plan = ses.plan(float(args.seconds))
    data = await ses.window(plan, bool(args.trace))
    setup_s = data["t_open"] - T_START
    log(f"window closed and drained; backend compiles inside it (count, seconds): "
        f"{data['backend_compiles_in_window']}; in the whole run: {len(ses.compiles)}")
    import jax

    mem = [d.memory_stats() or {} for d in jax.devices()[:ses.cell.chips]]
    peak_bytes = max((m.get("peak_bytes_in_use") or 0) for m in mem)
    extra = await ses.entry.after_window(data) if hasattr(ses.entry, "after_window") else {}
    await ses.entry.stop()
    if data["compiles_in_window"]:
        log(f"{data['compiles_in_window']} step program(s) compiled inside the window: the "
            "cell's warm-up does not cover its traffic; no result")
        return 4

    verdict = ses.correctness(data)
    for ln in verdict.get("lines", [f"correct: {verdict.get('why', '')}"]):
        print(ln, flush=True)
    correct = verdict["correct"]
    for ok, line in (extra.get("checks") or {}).values():
        print(f"correct: {line}", flush=True)
        correct = correct and ok
    attempted, failed, errors = count_failures(data)
    by_kind: dict = {}
    for r in estimators.in_window(data["records"]):
        if r.get("done_t"):
            by_kind.setdefault(r.get("kind"), []).append(r["done_t"] - r["sent_t"])
    log("window's requests by kind (count, mean s, max s): " + str(
        {k: (len(v), round(sum(v) / len(v), 2), round(max(v), 2)) for k, v in by_kind.items()}))
    if errors:
        log(f"failed requests: {errors}")

    device_out = {"platform": ses.device["platform"], "kind": ses.device["kind"],
                  "count": ses.device["count"], "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct and failed == 0), "attempted": attempted, "failed": failed}
    if not args.trace:
        metrics = {}
        for name, (value, unit) in ses.e2e(data, setup_s).items():
            if value is None:
                log(f"end-to-end metric {name} has no value")
                return 5
            metrics[name] = {"value": value, "unit": unit}
    else:
        reduced = None
        if data["trace_span"] is not None and ses.device["platform"] == "tpu":
            from benchmarks import trace as trace_mod

            xplane = trace_mod.find_xplane(str(ses.trace_dir))
            if os.environ.get("BENCH_KEEP_TRACE"):
                (ses.work / "trace_describe.json").write_text(
                    json.dumps(trace_mod.describe(xplane), indent=1))
            plain = trace_mod.load(xplane)
            if os.environ.get("BENCH_KEEP_TRACE"):  # a small recorded trace for the tests
                (ses.work / "trace_sample.json").write_text(
                    json.dumps(trace_mod.head(plain, 0.45)))
            reduced = trace_mod.reduce(plain)
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        metrics = ses.per_layer(data, reduced, peak_bytes, extra)
    if not os.environ.get("BENCH_KEEP_TRACE"):
        shutil.rmtree(ses.work / "trace", ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = device_out
    result["checks"] = verdict.get("numbers", {})
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on a CPU pinned by the caller (tests); not chip evidence")
    args = ap.parse_args(argv)
    return asyncio.run(main_async(args))


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # daemon threads of the program (engine driver, loops) must not hold the exit
