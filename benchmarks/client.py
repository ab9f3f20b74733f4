"""The load generator: a child process that never imports JAX.

``python -m benchmarks.client <plan.json> <records.json> <base-url>`` prints
``READY`` once it has loaded the plan, reads one line from its standard
input (the ``time.monotonic()`` reading at which the window opens; the
clock is the machine's, shared with the parent), drives the plan over HTTP
from one thread, and writes one record per request.  All times in the
records are ``time.monotonic()`` readings taken when the bytes reached this
process.

An open loop sends each request when it is due whether or not earlier ones
have finished, and times it from when it was due.  A closed loop's client
sends its next request when the previous one has ended.  Requests of the
lead-in and of the tail keep the load up and are recorded with their phase;
the estimators count the window's.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import aiohttp


async def _chat(session, base: str, req: dict, rec: dict, timeout_s: float) -> None:
    body = {"messages": req["messages"], "max_tokens": req["output_tokens"],
            "temperature": 0, "stream": True}
    token_ts = rec["token_ts"]
    async with session.post(f"{base}/v1/chat/completions", json=body,
                            timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = (await resp.text())[:200]
            return
        async for raw in resp.content:
            if not raw.startswith(b"data: "):
                continue
            now = time.monotonic()
            payload = raw[6:].strip()
            if payload == b"[DONE]":
                rec["done_t"] = now
                break
            choice = json.loads(payload)["choices"][0]
            if choice["delta"].get("content"):
                token_ts.append(now)
            if choice.get("finish_reason"):
                rec["finish_reason"] = choice["finish_reason"]


async def _rag_job(session, base: str, req: dict, rec: dict, timeout_s: float) -> None:
    body = {"query": req["query"], "namespace": req["namespace"], "top_k": req.get("top_k", 5)}
    tmo = aiohttp.ClientTimeout(total=timeout_s)
    async with session.post(f"{base}/rag/jobs", json=body, timeout=tmo) as resp:
        rec["status"] = resp.status
        if resp.status not in (200, 201, 202):
            rec["error"] = (await resp.text())[:200]
            return
        job = await resp.json()
    rec["job_id"], rec["trace_id"] = job.get("job_id"), job.get("trace_id")
    token_ts = rec["token_ts"]
    async with session.get(f"{base}/rag/jobs/{job['job_id']}/events", timeout=tmo) as resp:
        async for raw in resp.content:
            if not raw.startswith(b"data: "):
                continue
            now = time.monotonic()
            event = json.loads(raw[6:])
            name = event.get("event")
            if name == "token":
                token_ts.append(now)
            elif name == "error":
                rec["error"] = json.dumps(event.get("data"))[:200]
            elif name == "final":
                rec["done_t"] = now
                rec["finish_reason"] = "final"
                data = event.get("data") or {}
                rec["final"] = {k: data.get(k) for k in ("phases", "llm_calls", "trace_id")}
                rec["final"]["sources"] = len(data.get("sources") or ())
                break


ENTRIES = {"openai_chat": _chat, "rag_jobs": _rag_job}


async def _one(session, base, entry, req, due_t, phase, records, timeout_s) -> dict:
    rec = {"i": req["i"], "phase": phase, "due_t": due_t, "sent_t": time.monotonic(),
           "token_ts": [], "status": None, "kind": req.get("kind"),
           "prompt_tokens": req.get("prompt_tokens"), "output_tokens": req.get("output_tokens")}
    records.append(rec)
    try:
        await ENTRIES[entry](session, base, req, rec, timeout_s)
    except asyncio.CancelledError:
        rec["error"] = "cancelled at the end of the run"
        raise
    except Exception as exc:  # noqa: BLE001 - a failed request is a record, not a crash
        rec["error"] = f"{type(exc).__name__}: {exc}"[:200]
    finally:
        ts = rec["token_ts"]
        rec["n_tokens"] = len(ts)
        rec["first_t"] = ts[0] if ts else None
        rec["last_t"] = ts[-1] if ts else None
    return rec


async def drive(plan: dict, base: str, t_open: float) -> list:
    entry, window = plan["entry"], plan["window_s"]
    t_close = t_open + window
    t_end = t_close + plan["tail_s"]
    timeout_s = plan["request_timeout_s"]
    records: list = []
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        tasks: list = []

        def phase_of(t: float) -> str:
            return "lead" if t < t_open else ("window" if t < t_close else "tail")

        async def sleep_until(t: float) -> None:
            d = t - time.monotonic()
            if d > 0:
                await asyncio.sleep(d)

        if plan["loop"] == "open":
            async def open_loop() -> None:
                for req in sorted(plan["requests"], key=lambda r: r["due"]):
                    due_t = t_open + req["due"]
                    await sleep_until(due_t)
                    tasks.append(asyncio.ensure_future(_one(
                        session, base, entry, req, due_t, req["phase"], records, timeout_s)))
            feeders = [asyncio.ensure_future(open_loop())]
        else:
            async def lane(client: dict) -> None:
                await sleep_until(t_open + client["start"])
                for k, req in enumerate(client["requests"]):
                    if k and req.get("think"):
                        await asyncio.sleep(req["think"])
                    now = time.monotonic()
                    if now >= t_end:
                        return
                    await _one(session, base, entry, req, now, phase_of(now), records, timeout_s)
            feeders = [asyncio.ensure_future(lane(c)) for c in plan["clients"]]

        def window_pending() -> bool:
            return any(r["phase"] == "window" and "done_t" not in r and "error" not in r
                       for r in records)

        await sleep_until(t_close)
        while time.monotonic() < t_end and window_pending():
            await asyncio.sleep(0.05)
        for f in feeders + tasks:  # what is left is lead-in or tail: not counted
            f.cancel()
        await asyncio.gather(*feeders, *tasks, return_exceptions=True)
    return records


def main(argv: list[str]) -> int:
    plan_path, out_path, base = argv
    with open(plan_path) as fh:
        plan = json.load(fh)
    print("READY", flush=True)
    t_open = float(sys.stdin.readline())
    records = asyncio.run(drive(plan, base, t_open))
    with open(out_path, "w") as fh:
        json.dump({"t_open": t_open, "t_close": t_open + plan["window_s"], "records": records}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
