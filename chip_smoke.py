#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

Drives the main path once through the entry points a user calls, at the
published widths of the models the repo serves, with weights made from
``--seed``.  Run it from the root of a checkout:

    python chip_smoke.py              one TPU chip: phases serve, rag, flagship
    python chip_smoke.py --chips 4    four chips: tp:4 against one chip, only
    python chip_smoke.py --rehearse   the same phases on the CPU at tiny widths

This parent never imports JAX: a chip belongs to one process at a time, so
each phase runs in a child that holds the chip alone and has exited before
the next one starts.  Children run with ``JAX_PLATFORMS=tpu`` (a missing chip
is a hard error there, never a CPU run); ``--rehearse`` pins them to the CPU
and says so in every line it prints.

Earlier stdout lines are one JSON object each (per phase: seconds to ready,
requests and tokens served, compile-cache state, peak HBM).  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase ends the run with a non-zero exit code and ``"ok": false``.
Without an accelerator the run fails before any phase and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PKG = "githubrepostorag_tpu"

# Server geometry per mode.  "real" is config.py's defaults (KV_PAGE_SIZE,
# KV_NUM_PAGES, MAX_NUM_SEQS, PREFILL_CHUNK are left unset) plus the
# context windows the issue fixes; "rehearse" shrinks everything so the CPU
# backend and interpret-mode kernels finish in about a minute.
MODES = {
    "real": {
        "llm": "qwen2-1.5b",
        "serve_env": {"CONTEXT_WINDOW": "4096"},
        "flagship_env": {"CONTEXT_WINDOW": "2048", "MAX_NUM_SEQS": "32"},
        "bpe_vocab": 32000,
        "bert": dict(hidden_size=384, num_hidden_layers=12, num_attention_heads=12,
                     intermediate_size=1536, vocab_size=30522),
        "ingest_dir": PKG,
        "gen_tokens": 64, "long_prompt_tokens": 1200, "concurrent": 16,
        "concurrent_tokens": 128, "rag_max_output": 96,
        "flagship_requests": 8, "flagship_tokens": 128,
        "tp_tokens": 32, "ready_timeout_s": 900,
    },
    "rehearse": {
        "llm": "qwen2-tiny",
        "serve_env": {"CONTEXT_WINDOW": "512", "MAX_NUM_SEQS": "8", "KV_PAGE_SIZE": "16",
                      "KV_NUM_PAGES": "128", "PREFILL_CHUNK": "64"},
        "flagship_env": {"CONTEXT_WINDOW": "256", "MAX_NUM_SEQS": "4", "KV_PAGE_SIZE": "16",
                         "KV_NUM_PAGES": "64", "PREFILL_CHUNK": "64"},
        "bpe_vocab": 500,
        "bert": dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                     intermediate_size=128, vocab_size=2000),
        "ingest_dir": f"{PKG}/agent",
        "gen_tokens": 16, "long_prompt_tokens": 150, "concurrent": 8,
        "concurrent_tokens": 8, "rag_max_output": 24,
        "flagship_requests": 4, "flagship_tokens": 16,
        "tp_tokens": 8, "ready_timeout_s": 600,
    },
}

# published config.json values (Qwen/Qwen2-1.5B) and the repo's test config
LLM_CONFIGS = {
    "qwen2-1.5b": dict(vocab_size=151936, hidden_size=1536, intermediate_size=8960,
                       num_hidden_layers=28, num_attention_heads=12, num_key_value_heads=2,
                       rope_theta=1000000.0, rms_norm_eps=1e-6, tie_word_embeddings=True,
                       max_position_embeddings=32768),
    "qwen2-tiny": dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                       rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=True,
                       max_position_embeddings=512),
}
CHATML = ["<|endoftext|>", "<|im_start|>", "<|im_end|>"]


class SmokeFailure(Exception):
    """A phase did not do what it must; the run ends non-zero."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------ artifacts ----


def _corpus() -> list[str]:
    """Text the tokenizers train on and the prompts are cut from: the
    repo's own sources, in a fixed order."""
    files = sorted((ROOT / PKG).rglob("*.py")) + [ROOT / "README.md"]
    return [p.read_text(errors="replace") for p in files if p.is_file()]


def _rand_bf16(rng, shape, scale: float):
    """Uniform(-a, a) with std ``scale``, as bfloat16.  Bulk generation:
    one float32 draw, in-place affine, then the top 16 bits of each word
    (truncation; the values are random anyway)."""
    import ml_dtypes
    import numpy as np

    x = rng.random(shape, dtype=np.float32)
    x -= 0.5
    x *= 2.0 * scale * 3 ** 0.5
    return (x.view(np.uint32) >> 16).astype(np.uint16).view(ml_dtypes.bfloat16)


def _write_llm_checkpoint(out: Path, hf: dict, seed: int, bpe_vocab: int, texts: list[str]) -> None:
    """A seeded HF-layout Qwen2 checkpoint: config.json, a byte-level BPE
    tokenizer.json with the ChatML specials, sharded bf16 safetensors."""
    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file
    from tokenizers.implementations import ByteLevelBPETokenizer

    out.mkdir(parents=True, exist_ok=True)
    tok = ByteLevelBPETokenizer()
    tok.train_from_iterator((ln for t in texts for ln in t.splitlines()),
                            vocab_size=bpe_vocab, show_progress=False)
    tok.add_special_tokens(CHATML)  # appended after the BPE ids
    n_tok = tok.get_vocab_size()
    check(n_tok <= hf["vocab_size"], f"tokenizer has {n_tok} ids > model vocab {hf['vocab_size']}")
    tok.save(str(out / "tokenizer.json"))
    (out / "tokenizer_config.json").write_text(json.dumps({"eos_token": "<|im_end|>"}))
    (out / "config.json").write_text(json.dumps(
        {"model_type": "qwen2", "architectures": ["Qwen2ForCausalLM"],
         "torch_dtype": "bfloat16", **hf}, indent=1))

    d, inter = hf["hidden_size"], hf["intermediate_size"]
    nq, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = d // nq
    L = hf["num_hidden_layers"]
    ones = lambda n: np.ones((n,), dtype=ml_dtypes.bfloat16)
    zeros = lambda n: np.zeros((n,), dtype=ml_dtypes.bfloat16)
    seeds = np.random.SeedSequence(seed).spawn(L + 1)

    def layer(i: int) -> dict:
        rng = np.random.default_rng(seeds[i])
        p = f"model.layers.{i}."
        w = lambda o, n: _rand_bf16(rng, (o, n), 0.02)  # HF layout [out, in]
        return {
            p + "input_layernorm.weight": ones(d),
            p + "post_attention_layernorm.weight": ones(d),
            p + "self_attn.q_proj.weight": w(nq * hd, d), p + "self_attn.q_proj.bias": zeros(nq * hd),
            p + "self_attn.k_proj.weight": w(nkv * hd, d), p + "self_attn.k_proj.bias": zeros(nkv * hd),
            p + "self_attn.v_proj.weight": w(nkv * hd, d), p + "self_attn.v_proj.bias": zeros(nkv * hd),
            p + "self_attn.o_proj.weight": w(d, nq * hd),
            p + "mlp.gate_proj.weight": w(inter, d), p + "mlp.up_proj.weight": w(inter, d),
            p + "mlp.down_proj.weight": w(d, inter),
        }

    def head() -> dict:
        embed = _rand_bf16(np.random.default_rng(seeds[L]), (hf["vocab_size"], d), 0.02)
        # Rows the tokenizer cannot decode (its specials and the padding up
        # to the published vocab) are zero, like never-trained padding
        # rows: with tied embeddings their logit is exactly 0, so a greedy
        # model never stops early and every token it emits decodes to text.
        embed[n_tok - len(CHATML):] = 0
        return {"model.embed_tokens.weight": embed, "model.norm.weight": ones(d)}

    per_shard = 7
    groups = [list(range(s, min(s + per_shard, L))) for s in range(0, L, per_shard)]
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        head_f = pool.submit(head)
        for n, group in enumerate(groups, start=1):
            tensors: dict = {}
            for part in pool.map(layer, group):
                tensors.update(part)
            if n == len(groups):
                tensors.update(head_f.result())
            save_file(tensors, str(out / f"model-{n:05d}-of-{len(groups):05d}.safetensors"))


def _write_bert_checkpoint(out: Path, bert: dict, seed: int, texts: list[str]) -> None:
    """A seeded HF-layout BERT checkpoint at e5-small-v2 widths with the
    tokenizer files ``JaxBertTextEncoder.from_pretrained`` reads."""
    import numpy as np
    from safetensors.numpy import save_file
    from tokenizers.implementations import BertWordPieceTokenizer

    out.mkdir(parents=True, exist_ok=True)
    tok = BertWordPieceTokenizer(lowercase=True)
    tok.train_from_iterator((ln for t in texts for ln in t.splitlines()),
                            vocab_size=bert["vocab_size"], show_progress=False)
    check(tok.get_vocab_size() <= bert["vocab_size"], "wordpiece vocab exceeds the model's")
    tok.save_model(str(out))  # vocab.txt
    (out / "tokenizer_config.json").write_text(json.dumps(
        {"tokenizer_class": "BertTokenizer", "do_lower_case": True, "model_max_length": 512}))
    cfg = {"model_type": "bert", "architectures": ["BertModel"], "max_position_embeddings": 512,
           "type_vocab_size": 2, "layer_norm_eps": 1e-12, **bert}
    (out / "config.json").write_text(json.dumps(cfg, indent=1))

    rng = np.random.default_rng(seed + 1)
    d, inter = bert["hidden_size"], bert["intermediate_size"]
    w = lambda *shape: (rng.standard_normal(shape, dtype=np.float32) * 0.02)
    one, zero = (lambda n: np.ones((n,), np.float32)), (lambda n: np.zeros((n,), np.float32))
    t = {
        "embeddings.word_embeddings.weight": w(bert["vocab_size"], d),
        "embeddings.position_embeddings.weight": w(512, d),
        "embeddings.token_type_embeddings.weight": w(2, d),
        "embeddings.LayerNorm.weight": one(d), "embeddings.LayerNorm.bias": zero(d),
    }
    for i in range(bert["num_hidden_layers"]):
        p = f"encoder.layer.{i}."
        for name in ("attention.self.query", "attention.self.key", "attention.self.value",
                     "attention.output.dense"):
            t[p + name + ".weight"], t[p + name + ".bias"] = w(d, d), zero(d)
        t[p + "intermediate.dense.weight"], t[p + "intermediate.dense.bias"] = w(inter, d), zero(inter)
        t[p + "output.dense.weight"], t[p + "output.dense.bias"] = w(d, inter), zero(d)
        for ln in ("attention.output.LayerNorm", "output.LayerNorm"):
            t[p + ln + ".weight"], t[p + ln + ".bias"] = one(d), zero(d)
    save_file(t, str(out / "model.safetensors"))


def prepare(art: Path, mode: dict, seed: int) -> dict:
    """Make everything the phases read, from the seed.  Checkpoints are
    kept between runs (a stamp names seed and widths), so a second run in
    the same tool command shows warm start-up, not regeneration."""
    t0 = time.monotonic()
    texts = _corpus()
    llm_dir, bert_dir = art / mode["llm"], art / "e5-small-v2-seeded"
    built = []
    for path, want, write in (
        (llm_dir, {"seed": seed, "bpe": mode["bpe_vocab"], **LLM_CONFIGS[mode["llm"]]},
         lambda: _write_llm_checkpoint(llm_dir, LLM_CONFIGS[mode["llm"]], seed,
                                       mode["bpe_vocab"], texts)),
        (bert_dir, {"seed": seed, **mode["bert"]},
         lambda: _write_bert_checkpoint(bert_dir, mode["bert"], seed, texts)),
    ):
        stamp = path / "SEEDED.json"
        if not (stamp.is_file() and json.loads(stamp.read_text()) == want):
            write()
            stamp.write_text(json.dumps(want))
            built.append(path.name)
    nbytes = sum(f.stat().st_size for f in llm_dir.glob("*.safetensors"))
    emit({"phase": "prepare", "built": built, "llm_checkpoint_bytes": nbytes,
          "seconds": round(time.monotonic() - t0, 1)})
    return {"llm": llm_dir, "bert": bert_dir, "texts": texts}


# -------------------------------------------------------------- children ----


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One process that may hold the chip.  Output goes to a log file; the
    process group is killed on exit from the ``with`` block whatever happened."""

    def __init__(self, name: str, argv: list[str], env: dict, log: Path) -> None:
        self.name, self.log = name, log
        log.parent.mkdir(parents=True, exist_ok=True)
        self.t0 = time.monotonic()
        with open(log, "wb") as fh:  # the child keeps its own descriptor
            self.proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=fh,
                                         stderr=subprocess.STDOUT, start_new_session=True)

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self, grace_s: float = 30.0) -> int:
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait(timeout=30)
        return self.proc.returncode

    def wait(self, timeout_s: float) -> int:
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name}: still running after {timeout_s:.0f}s\n{self.tail()}")

    def text(self) -> str:
        return self.log.read_text(errors="replace")

    def tail(self, n: int = 40) -> str:
        return "\n".join(f"  [{self.name}] {ln}" for ln in self.text().splitlines()[-n:])


def child_env(platform: str, extra: dict) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


def _open(method: str, url: str, body: dict | None, timeout: float):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def http(method: str, url: str, body: dict | None = None, timeout: float = 300.0):
    with _open(method, url, body, timeout) as resp:
        raw = resp.read()
    return json.loads(raw) if raw[:1] in (b"{", b"[") else raw.decode()


def sse(method: str, url: str, body: dict | None = None, timeout: float = 300.0):
    """Read a server-sent-event stream to its end -> [(arrival_s, payload)]."""
    out = []
    t0 = time.monotonic()
    with _open(method, url, body, timeout) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data: "):
                out.append((time.monotonic() - t0, line[6:]))
    return out


def wait_until(child: Child, probe, timeout_s: float, what: str):
    """Poll ``probe()`` (None = not yet) while the child lives."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise SmokeFailure(f"{child.name} exited rc={child.proc.returncode} before {what}\n"
                               f"{child.tail()}")
        try:
            got = probe()
        except (urllib.error.URLError, ConnectionError, TimeoutError, OSError):
            got = None
        if got is not None:
            return got
        time.sleep(1.0)
    raise SmokeFailure(f"{child.name}: no {what} within {timeout_s:.0f}s\n{child.tail()}")


def cache_state(rehearse: bool) -> dict:
    """Where children keep compiled programs and whether anything was there
    before this run."""
    if rehearse:
        return {"dir": None, "entries_at_start": 0}  # off on the CPU backend
    from githubrepostorag_tpu.runtime import compile_cache_dir  # imports no JAX

    d = Path(compile_cache_dir())
    return {"dir": str(d), "entries_at_start": sum(1 for _ in d.glob("*")) if d.is_dir() else 0}


def check_device_facts(stats: dict, device: dict, rehearse: bool, who: str,
                       warmed: bool = True) -> None:
    check(stats.get("backend") == device["platform"],
          f"{who} reports backend {stats.get('backend')!r}, not {device['platform']!r}")
    check(stats.get("device_kind") == device["kind"], f"{who} device_kind {stats.get('device_kind')!r}")
    if not rehearse:
        check(stats.get("pallas") is True, f"{who} runs without the Pallas path")
    if warmed:  # an engine that ran warmup() compiles nothing under traffic
        check(stats.get("live_compiles") == 0,
              f"{who} compiled {stats.get('live_compiles')} program(s) under live traffic")


# ----------------------------------------------------------- phase serve ----


def phase_serve(art: Path, mode: dict, files: dict, device: dict, rehearse: bool) -> None:
    """The model-server pod as users start it."""
    t_phase = time.monotonic()
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    env = child_env(device["platform"], {"MODEL_WEIGHTS_PATH": str(files["llm"]), **mode["serve_env"]})
    argv = [sys.executable, "-m", f"{PKG}.serving", "--host", "127.0.0.1", "--port", str(port)]
    text = files["texts"][-1]  # README.md
    with Child("serving", argv, env, art / "logs" / "serving.log") as srv:
        wait_until(srv, lambda: http("GET", f"{base}/health", timeout=5),
                   mode["ready_timeout_s"], "/health")
        ready_s = time.monotonic() - srv.t0
        n = mode["gen_tokens"]
        tokens = 0

        def complete(prompt: str, max_tokens: int) -> dict:
            r = http("POST", f"{base}/v1/completions",
                     {"prompt": prompt, "max_tokens": max_tokens, "temperature": 0})
            check(r["choices"][0]["finish_reason"] == "length",
                  f"finish_reason {r['choices'][0]['finish_reason']!r}, expected 'length'")
            check(r["usage"]["completion_tokens"] == max_tokens,
                  f"{r['usage']['completion_tokens']} completion tokens, expected {max_tokens}")
            return r

        # 1. one non-streamed completion; the same temperature-0 prompt
        #    posted again returns the same text
        first = complete(text[:400], n)
        again = complete(text[:400], n)
        check(first["choices"][0]["text"] == again["choices"][0]["text"],
              "two temperature-0 posts of one prompt returned different text")
        check(first["choices"][0]["text"].strip() != "", "completion text is empty")
        tokens += 2 * n

        # 2. one streamed chat completion: content arrives before the end
        events = sse("POST", f"{base}/v1/chat/completions", {
            "messages": [{"role": "user", "content": text[400:700]}],
            "max_tokens": n, "temperature": 0, "stream": True})
        check(events and events[-1][1] == "[DONE]", "stream did not end with [DONE]")
        chunks = [(t, json.loads(p)) for t, p in events[:-1]]
        content = [t for t, c in chunks if c["choices"][0]["delta"].get("content")]
        done = [t for t, c in chunks if c["choices"][0]["finish_reason"]]
        check(len(done) == 1 and chunks[-1][1]["choices"][0]["finish_reason"] == "length",
              "stream has no single final 'length' chunk")
        check(len(content) >= 2 and content[0] < done[0],
              f"streamed content did not arrive before the end ({len(content)} chunks)")
        tokens += n

        # 3. a prompt that crosses PREFILL_CHUNK
        long_text = " ".join(files["texts"][:40])[: mode["long_prompt_tokens"] * 6]
        want = mode["long_prompt_tokens"]
        from tokenizers import Tokenizer  # no JAX: cut the prompt to its token count

        tk = Tokenizer.from_file(str(files["llm"] / "tokenizer.json"))
        long_prompt = tk.decode(tk.encode(long_text).ids[:want])
        r = complete(long_prompt, 16 if not rehearse else 4)
        check(abs(r["usage"]["prompt_tokens"] - want) <= want // 10,
              f"long prompt is {r['usage']['prompt_tokens']} tokens, wanted about {want}")
        tokens += r["usage"]["completion_tokens"]

        # 4. concurrent temperature-0 completions: continuous batching
        k, m = mode["concurrent"], mode["concurrent_tokens"]
        peak_running = [0]
        stop = threading.Event()

        def watch() -> None:
            while not stop.is_set():
                try:
                    peak_running[0] = max(peak_running[0],
                                          http("GET", f"{base}/health", timeout=5)["running"])
                except (urllib.error.URLError, OSError):
                    pass
                time.sleep(0.02)

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        try:
            with ThreadPoolExecutor(max_workers=k) as pool:
                futs = [pool.submit(complete, text[700 + 150 * i: 1000 + 150 * i], m)
                        for i in range(k)]
                results = [f.result() for f in futs]
        finally:
            stop.set()
            watcher.join(timeout=10)
        check(len({r["choices"][0]["text"] for r in results}) > 1,
              "distinct concurrent prompts all returned one text")
        check(peak_running[0] >= 2, f"never saw two requests running at once ({peak_running[0]})")
        tokens += k * m

        stats = http("GET", f"{base}/health", timeout=10)
        check_device_facts(stats, device, rehearse, "model server")
        check(stats["requests_admitted"] >= 4 + k, f"only {stats['requests_admitted']} requests admitted")
        check(f"backend={device['platform']}" in srv.text(), "start-up line does not name the backend")
        rc = srv.stop()
    check(srv.proc.poll() is not None, "model server did not exit")
    emit({"phase": "serve", "model": mode["llm"], "platform": device["platform"],
          "seconds_to_ready": round(ready_s, 1), "wall_seconds": round(time.monotonic() - t_phase, 1),
          "requests": 4 + k, "tokens": tokens, "peak_running": peak_running[0],
          "prefix_cache_hit_tokens": stats["prefix_cache_hit_tokens"],
          "hbm_peak_bytes": stats["hbm_peak_bytes"], "server_exit_code": rc})


# ------------------------------------------------------------- phase rag ----


def phase_rag(art: Path, mode: dict, files: dict, device: dict, rehearse: bool) -> None:
    """The product loop in single-pod mode: ingest CLI, then the API with the
    in-process engine, the JAX BERT encoder and the device index."""
    t_phase = time.monotonic()
    store = art / "store"
    if store.exists():
        import shutil

        shutil.rmtree(store)
    common = {
        "STORE_BACKEND": "native", "STORE_PATH": str(store), "EMBED_MODEL": str(files["bert"]),
        "EMBED_DIM": str(mode["bert"]["hidden_size"]),
        # auto = on exactly when the process is on the chip; the CPU
        # rehearsal forces the device path so it is rehearsed at all
        "DEVICE_INDEX": "on" if rehearse else "auto",
    }
    argv = [sys.executable, "-m", f"{PKG}.ingest", "--local", mode["ingest_dir"],
            "--namespace", "smoke", "--repo", "self"]
    env = child_env(device["platform"], {**common, "LLM_BACKEND": "fake"})
    with Child("ingest", argv, env, art / "logs" / "ingest.log") as ing:
        rc = ing.wait(mode["ready_timeout_s"])
        ingest_s = time.monotonic() - ing.t0
        check(rc == 0, f"ingest exited rc={rc}\n{ing.tail()}")
        log = ing.text()
    check("embedding: JAX BERT encoder" in log, "ingest did not use the JAX BERT encoder")
    check("hashing encoder" not in log, "ingest fell back to the hashing encoder")
    record = json.loads(log[log.index("\n{\n") + 1: log.index("\n}\n") + 2])  # the CLI's own report
    check(record["written"]["chunk"] > 0, f"ingest wrote no chunks: {record}")

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    env = child_env(device["platform"], {
        **common, **mode["serve_env"], "LLM_BACKEND": "inprocess",
        "MODEL_WEIGHTS_PATH": str(files["llm"]), "QWEN_MAX_OUTPUT": str(mode["rag_max_output"]),
    })
    argv = [sys.executable, "-m", f"{PKG}.api", "--host", "127.0.0.1", "--port", str(port)]
    with Child("api", argv, env, art / "logs" / "api.log") as api:
        def engine_wired():
            llm = http("GET", f"{base}/health", timeout=5)["components"]["llm"]["details"]
            return llm if "pallas" in llm else None

        wait_until(api, engine_wired, mode["ready_timeout_s"], "in-process engine on /health")
        ready_s = time.monotonic() - api.t0
        job = http("POST", f"{base}/rag/jobs", {
            "query": "what does the function plan_scope() return in graph.py",
            "namespace": "smoke", "top_k": 5})
        check("job_id" in job and "trace_id" in job, f"job not accepted: {job}")
        events = [json.loads(p) for _, p in sse("GET", f"{base}/rag/jobs/{job['job_id']}/events")]
        names = [e["event"] for e in events]
        check(names and names[-1] == "final", f"event stream ended with {names[-1:]}")
        check("error" not in names, f"job reported an error: {[e for e in events if e['event'] == 'error']}")
        n_tok = names.count("token")
        check(n_tok > 0 and names.index("token") < len(names) - 1, "no token events before the final one")
        final = events[-1]["data"]
        check(final.get("sources"), "final event carries no sources")

        trace = http("GET", f"{base}/debug/traces/{job['trace_id']}")
        spans = [s["name"] for s in trace["spans"]]
        order = [spans.index(nm) for nm in ("agent.plan", "agent.retrieve", "agent.judge",
                                            "agent.synthesize") if nm in spans]
        check(len(order) == 4 and order == sorted(order),
              f"trace does not show plan -> retrieve -> judge -> synthesize: {spans}")
        calls = [s for s in trace["spans"] if s["name"] in ("llm.complete", "llm.stream")]
        llm_calls = len(calls)
        check(llm_calls >= 3 and all(s["status"] == "ok" for s in calls),
              f"expected >= 3 ok LLM calls (plan, judge, synthesize): "
              f"{[(s['name'], s['status']) for s in calls]}")

        metrics = http("GET", f"{base}/metrics")
        searches = {ln.split("{", 1)[1].split("}")[0]: float(ln.rsplit(" ", 1)[1])
                    for ln in metrics.splitlines()
                    if ln.startswith("rag_device_index_searches_total{")}
        check(searches.get('path="device"', 0) > 0, f"no device-index searches: {searches}")
        check(searches.get('path="fallback"', 0) == 0, f"device index fell back: {searches}")

        llm = engine_wired()
        # api/__main__.py builds its engine without warmup(): its first job
        # compiles (or reads the cache the serve phase filled) — reported below
        check_device_facts(llm, device, rehearse, "in-process engine", warmed=False)
        check(llm["requests_admitted"] >= llm_calls, "LLM calls were not answered by the in-process engine")
        log = api.text()
        check("embedding: JAX BERT encoder" in log, "api did not use the JAX BERT encoder")
        check("hashing encoder" not in log, "api fell back to the hashing encoder")
        rc = api.stop()
    emit({"phase": "rag", "model": mode["llm"], "platform": device["platform"],
          "ingest_seconds": round(ingest_s, 1), "ingest_written": record["written"],
          "seconds_to_ready": round(ready_s, 1), "wall_seconds": round(time.monotonic() - t_phase, 1),
          "requests": 1, "llm_calls": llm_calls, "token_events": n_tok,
          "sources": len(final["sources"]), "device_index_searches": searches['path="device"'],
          "job_phases": final.get("phases"), "live_compiles": llm["live_compiles"],
          "hbm_peak_bytes": llm["hbm_peak_bytes"],
          "server_exit_code": rc})


# ------------------------------------------- phases that run in one child ----


def run_own_child(name: str, art: Path, device: dict, args, extra_env: dict, timeout_s: float) -> None:
    """flagship / tp4 run this file again as ``--child``: the child builds
    the engine in-process and prints its own JSON lines, which pass through."""
    argv = [sys.executable, str(Path(__file__).resolve()), "--child", name, "--seed", str(args.seed)]
    if args.rehearse:
        argv.append("--rehearse")
    env = child_env(device["platform"], extra_env)
    with Child(name, argv, env, art / "logs" / f"{name}.log") as ch:
        rc = ch.wait(timeout_s)
        lines = [ln for ln in ch.text().splitlines() if ln.startswith('{"phase"')]
        for ln in lines:
            print(ln, flush=True)
        check(rc == 0 and lines, f"{name} exited rc={rc}\n{ch.tail()}")


def _child_device(rehearse: bool) -> dict:
    import jax

    d = jax.devices()
    dev = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    if not rehearse and dev["platform"] != "tpu":
        raise SmokeFailure(f"child is on {dev['platform']!r}, not the TPU")
    return dev


def child_flagship(seed: int, rehearse: bool) -> None:
    """Qwen2-7B widths, 28 layers, int8 weights, through Engine -> warmup ->
    AsyncEngine -> OpenAIServer, with the settings the model pod reads."""
    import asyncio

    import aiohttp
    import jax

    from githubrepostorag_tpu.config import get_settings
    from githubrepostorag_tpu.models.quant import init_params_quantized, params_nbytes
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config
    from githubrepostorag_tpu.runtime import enable_compile_cache, on_tpu
    from githubrepostorag_tpu.serving.async_engine import AsyncEngine
    from githubrepostorag_tpu.serving.engine import Engine
    from githubrepostorag_tpu.serving.openai_api import OpenAIServer
    from githubrepostorag_tpu.serving.tokenizer import make_tokenizer

    t0 = time.monotonic()
    enable_compile_cache()
    device = _child_device(rehearse)
    mode = MODES["rehearse" if rehearse else "real"]
    cfg = Qwen2Config.tiny() if rehearse else Qwen2Config.qwen2_7b()
    params = init_params_quantized(cfg, seed=seed, bits=8, fuse=True)
    jax.block_until_ready(params)
    s = get_settings()
    engine = Engine(params, cfg, max_num_seqs=s.max_num_seqs, num_pages=s.kv_num_pages,
                    page_size=s.kv_page_size, max_seq_len=s.context_window,
                    prefill_chunk=s.prefill_chunk, use_pallas=on_tpu())
    engine.warmup()
    tokenizer = make_tokenizer(os.environ["SMOKE_TOKENIZER_DIR"])
    n_req, n_tok = mode["flagship_requests"], mode["flagship_tokens"]
    prompts = [ln for ln in (ROOT / "README.md").read_text().splitlines() if len(ln) > 80][:n_req]

    async def drive() -> tuple[float, list[dict], dict]:
        server = OpenAIServer(AsyncEngine(engine), tokenizer, model_name="qwen2-7b-int8")
        port = await server.start(host="127.0.0.1", port=0)
        ready = time.monotonic() - t0
        try:
            async with aiohttp.ClientSession() as http_:
                async def post(prompt: str) -> dict:
                    async with http_.post(f"http://127.0.0.1:{port}/v1/completions", json={
                            "prompt": prompt, "max_tokens": n_tok, "temperature": 0}) as r:
                        return await r.json()

                results = await asyncio.gather(*(post(p) for p in prompts))
                async with http_.get(f"http://127.0.0.1:{port}/health") as r:
                    stats = await r.json()
        finally:
            await server.stop()
        return ready, results, stats

    ready_s, results, stats = asyncio.run(drive())
    for r in results:
        check(r["choices"][0]["finish_reason"] == "length"
              and r["usage"]["completion_tokens"] == n_tok, f"bad flagship completion: {r}")
    check(len(results) == n_req, "missing flagship responses")
    check_device_facts(stats, device, rehearse, "flagship engine")
    emit({"phase": "flagship", "model": "qwen2-tiny-int8" if rehearse else "qwen2-7b-int8",
          "platform": device["platform"], "weight_bytes": params_nbytes(params),
          "seconds_to_ready": round(ready_s, 1), "wall_seconds": round(time.monotonic() - t0, 1),
          "requests": n_req, "tokens": n_req * n_tok, "hbm_peak_bytes": stats["hbm_peak_bytes"]})


def child_tp4(seed: int, rehearse: bool) -> None:
    """One process, four chips: the 7B int8 tree on an Engine over tp:4,
    and the same tree on a one-chip Engine on device 0 to compare with."""
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from githubrepostorag_tpu.models.quant import fuse_projections, init_params_quantized
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config, forward_paged
    from githubrepostorag_tpu.parallel import MeshPlan, make_mesh
    from githubrepostorag_tpu.runtime import enable_compile_cache, on_tpu
    from githubrepostorag_tpu.serving import Engine, SamplingParams
    from githubrepostorag_tpu.serving.decode_burst import decode_burst

    t0 = time.monotonic()
    enable_compile_cache()
    device = _child_device(rehearse)
    check(device["count"] >= 4, f"--chips 4 needs four devices, found {device['count']}")
    devs = jax.devices()[:4]
    mode = MODES["rehearse" if rehearse else "real"]
    # the only supported model whose heads (28/4) divide by four; the CPU
    # rehearsal widens the test config's heads so tp:4 divides them too
    cfg = (dataclasses.replace(Qwen2Config.tiny(), num_heads=8, num_kv_heads=4)
           if rehearse else Qwen2Config.qwen2_7b())
    geo = (dict(max_num_seqs=8, num_pages=64, page_size=16, max_seq_len=256, prefill_chunk=64)
           if rehearse else
           dict(max_num_seqs=8, num_pages=256, page_size=128, max_seq_len=2048, prefill_chunk=512))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in rng.integers(24, geo["prefill_chunk"] // 2, size=8)]
    n_tok = mode["tp_tokens"]
    sampling = SamplingParams(max_tokens=n_tok, temperature=0.0, stop_token_ids=())

    def in_use() -> list[int | None]:
        return [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]

    def next_logits(eng: Engine, seqs: list[list[int]]) -> np.ndarray:
        """The engine's own prefill program on 8 sequences, one page run per
        row, returning the logits the token after each is sampled from."""
        rb, w, ps = len(seqs), geo["prefill_chunk"], geo["page_size"]
        per = -(-w // ps)
        ids = np.zeros((rb, w), np.int32)
        slots = np.full((rb, w), -1, np.int32)
        bt = np.zeros((rb, eng.max_pages_per_seq), np.int32)
        lens = np.asarray([len(p) for p in seqs], np.int32)
        for i, p in enumerate(seqs):
            ids[i, : len(p)] = p
            bt[i, :per] = np.arange(i * per, (i + 1) * per)
            slots[i, : len(p)] = i * per * ps + np.arange(len(p))
        pos = np.broadcast_to(np.arange(w, dtype=np.int32), (rb, w))
        logits, eng._k_pages, eng._v_pages = forward_paged(
            eng.params, cfg, jnp.asarray(ids), jnp.asarray(pos), eng._k_pages, eng._v_pages,
            jnp.asarray(slots), jnp.asarray(bt), jnp.zeros((rb,), jnp.int32), jnp.asarray(lens),
            use_pallas=eng.use_pallas, logits_at=jnp.asarray(lens - 1),
            int4_kernel=eng._int4_kernel, mesh=eng.mesh)
        return np.asarray(logits[:, 0], np.float32)

    def decode_hlo(eng: Engine) -> str:
        """Compiled text of the decode burst at the shapes the engine runs
        (compiled past the persistent cache: the text is read from a fresh
        compile, not from a deserialized executable)."""
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return _decode_hlo(eng)
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()

    def _decode_hlo(eng: Engine) -> str:
        b = eng.max_num_seqs
        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        rep = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=eng._replicated)
        return decode_burst.lower(
            jax.tree_util.tree_map(sds, eng.params), cfg, rep((b,), jnp.int32), rep((b,), jnp.int32),
            sds(eng._k_pages), sds(eng._v_pages), sds(eng._presence), rep((b,), jnp.bool_),
            rep((b,), jnp.int32), rep((b, eng.max_pages_per_seq), jnp.int32), rep((2,), jnp.uint32),
            rep((b,), jnp.float32), rep((b,), jnp.float32), rep((b,), jnp.int32), rep((b,), jnp.float32),
            n_steps=eng.decode_burst, use_pallas=eng.use_pallas, mesh=eng.mesh,
            filter_sampling=False, first_tokens=rep((b,), jnp.int32), fresh=rep((b,), jnp.bool_),
            fresh_lens=rep((b,), jnp.int32), key_step=rep((), jnp.uint32),
        ).compile().as_text()

    # ---- tp:4 ----
    base_use = in_use()
    tree = init_params_quantized(cfg, seed=seed, bits=8, fuse=False)  # unfused for the mesh
    mesh = make_mesh(MeshPlan(tp=4), devices=devs)
    eng = Engine(tree, cfg, use_pallas=on_tpu(), mesh=mesh, **geo)
    del tree
    gc.collect()
    jax.block_until_ready(eng.params)
    held = [None if u is None else u - b0 for u, b0 in zip(in_use(), base_use)]
    tp_logits = next_logits(eng, prompts)
    tp_tokens = [r.output_tokens for r in eng.generate(prompts, sampling)]
    tp_last = next_logits(eng, [p + t[:-1] for p, t in zip(prompts, tp_tokens)])
    hlo = decode_hlo(eng)
    check("all-reduce" in hlo, "tp:4 decode program has no all-reduce")
    if not rehearse:
        check("tpu_custom_call" in hlo, "tp:4 decode program has no Pallas kernel")
        total = sum(held)
        check(max(held) <= 1.25 * min(held) and held[0] <= 0.4 * total,
              f"weights and KV pools are not spread over the chips: {held}")
    tp_seconds = time.monotonic() - t0
    del eng
    gc.collect()

    # ---- one chip, device 0: the same tree, fused in place ----
    t1 = time.monotonic()
    tree = fuse_projections(init_params_quantized(cfg, seed=seed, bits=8, fuse=False), in_place=True)
    eng = Engine(tree, cfg, use_pallas=on_tpu(), mesh=None, **geo)
    one_logits = next_logits(eng, prompts)
    one_tokens = [r.output_tokens for r in eng.generate(prompts, sampling)]
    one_last = next_logits(eng, [p + t[:-1] for p, t in zip(prompts, one_tokens)])

    # bf16 tolerance: both engines round every projection output to bf16,
    # and tp:4 also rounds each shard's partial product before the two
    # all-reduces of a layer, so after 28 layers the logits differ by a few
    # percent RMS (2**-8 per rounding, ~sqrt(2L) of them) and the largest
    # of 1.2M differences sits some five sigma out.  A sharding fault moves
    # whole rows by the logits' own scale.
    diff = tp_logits - one_logits
    rms = float(np.sqrt(np.mean(one_logits ** 2)))
    rel_rms = float(np.sqrt(np.mean(diff ** 2))) / rms
    scale = float(np.abs(one_logits).max())
    err = float(np.abs(diff).max())
    # Greedy tokens match up to the first near-tie: with random weights
    # that rounding flips an argmax between near-equal logits, after which
    # two sequences legitimately differ.  So a token is held to the logits,
    # not to the other engine's token: it must lie within ``tie`` of the
    # row's best logit (a handful of the 152k candidates do).
    tie = 0.1 * scale

    def below_best(logits: np.ndarray, toks: list[int]) -> float:
        return float(max(row.max() - row[t] for row, t in zip(logits, toks)))

    gaps = {
        # each engine's first token, against the OTHER engine's prefill
        "tp4_first_vs_one_chip": below_best(one_logits, [t[0] for t in tp_tokens]),
        "one_chip_first_vs_tp4": below_best(tp_logits, [t[0] for t in one_tokens]),
        # each engine's LAST token (after n-1 decode-burst steps), against
        # its own prefill program fed the same history: decode == prefill
        "tp4_last_vs_own_prefill": below_best(tp_last, [t[-1] for t in tp_tokens]),
        "one_chip_last_vs_own_prefill": below_best(one_last, [t[-1] for t in one_tokens]),
    }
    agree = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b), len(x))
             for x, y in zip(tp_tokens, one_tokens)]
    emit({"phase": "tp4", "model": "qwen2-tiny-int8" if rehearse else "qwen2-7b-int8",
          "platform": device["platform"], "devices": device["count"],
          "bytes_held_per_device": held, "logits_rel_rms_diff": round(rel_rms, 5),
          "logits_max_abs_diff": round(err, 5), "logits_max_abs": round(scale, 4),
          "near_tie_tolerance": round(tie, 4),
          "token_logit_below_best": {k: round(v, 4) for k, v in gaps.items()},
          "greedy_agreement": agree, "tokens_per_prompt": n_tok,
          "tp4_seconds": round(tp_seconds, 1), "one_chip_seconds": round(time.monotonic() - t1, 1),
          "decode_hlo": {"all_reduce": hlo.count("all-reduce"),
                         "tpu_custom_call": hlo.count("tpu_custom_call")}})
    check(np.isfinite(tp_logits).all() and np.isfinite(one_logits).all(), "non-finite logits")
    check(rel_rms <= 0.1 and err <= tie,
          f"first-step logits differ: rms {rel_rms:.4f} of the logits' rms, max |d|={err:.4f} "
          f"at max |logit| {scale:.4f}")
    check(all(len(t) == n_tok for t in tp_tokens + one_tokens), "short generation")
    check(max(gaps.values()) <= tie, f"a greedy token is no near-tie of its row's best logit: {gaps}")


# ------------------------------------------------------------------ main ----


def probe_device(platform: str, n_devices: int) -> dict | None:
    """Ask a child what JAX finds.  None when there is no such backend."""
    env = child_env(platform, {})
    if platform == "cpu":
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n_devices}").strip()
    code = ("import json, jax; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))")
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT, capture_output=True,
                             text=True, timeout=300)
    except subprocess.TimeoutExpired:
        return None
    if out.returncode != 0:
        sys.stderr.write(out.stderr[-2000:])
        return None
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0, help="seed of every generated weight and prompt")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tp:4 path and the one-chip engine it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="same phases at tiny widths on the CPU backend (not chip evidence)")
    ap.add_argument("--child", choices=("flagship", "tp4"), help=argparse.SUPPRESS)
    args = ap.parse_args()

    if not (ROOT / PKG / "runtime.py").is_file():
        sys.stderr.write(f"chip_smoke.py: no {PKG}/ beside this script — run it from a checkout\n")
        return 2
    sys.path.insert(0, str(ROOT))
    if args.child:
        {"flagship": child_flagship, "tp4": child_tp4}[args.child](args.seed, args.rehearse)
        return 0

    # a terminated parent must still stop the child that holds the chip:
    # turn SIGTERM into SystemExit so every ``with Child(...)`` unwinds
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.monotonic()
    platform = "cpu" if args.rehearse else "tpu"
    device = probe_device(platform, args.chips)
    if device is None or device["platform"] != platform or device["count"] < args.chips:
        # no result line on stdout: nothing ran
        sys.stderr.write(json.dumps({"ok": False, "error": f"no {platform} backend with "
                                     f"{args.chips} device(s)", "found": device}) + "\n")
        return 3
    mode = MODES["rehearse" if args.rehearse else "real"]
    art = ROOT / "artifacts" / "chip_smoke" / ("rehearse" if args.rehearse else "real")
    ok = False
    try:
        make = subprocess.run(["make", "-C", str(ROOT / "native"), "clean", "all"],
                              capture_output=True, text=True)
        emit({"phase": "native", "built": make.returncode == 0,
              **({} if make.returncode == 0 else {"note": "running on the Python fallbacks",
                                                  "make_stderr": make.stderr[-400:]})})
        emit({"phase": "start", "device": device, "rehearse": args.rehearse,
              "seed": args.seed, "compile_cache": cache_state(args.rehearse)})
        if args.chips == 4:
            xla = {"XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                                 + " --xla_force_host_platform_device_count=4").strip()} if args.rehearse else {}
            run_own_child("tp4", art, device, args, xla, 3000)
        else:
            files = prepare(art, mode, args.seed)
            phase_serve(art, mode, files, device, args.rehearse)
            phase_rag(art, mode, files, device, args.rehearse)
            run_own_child("flagship", art, device, args,
                          {**mode["flagship_env"], "SMOKE_TOKENIZER_DIR": str(files["llm"])}, 1500)
        ok = True
    except SmokeFailure as exc:
        sys.stderr.write(f"chip_smoke.py: FAILED: {exc}\n")
    except Exception:  # noqa: BLE001 - any other error in a phase fails the run too
        import traceback

        sys.stderr.write("chip_smoke.py: FAILED:\n" + traceback.format_exc())
    emit({"phase": "end", "wall_seconds": round(time.monotonic() - t0, 1)})
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
