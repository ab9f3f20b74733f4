"""The system under test, built from a configuration file and a seed.

What is taken from the program: ``Qwen2Config``, the on-device weight
initialisers, ``Engine``, ``AsyncEngine``, ``OpenAIServer``, the tokenizer
loader and, for the RAG entry point, the pieces ``api/__main__.serve``
wires together.  What this file adds is outside them: counters read by
wrapping four engine methods (the program exports none of them), and a
warm-up of exactly the row buckets the cell's traffic can reach.
"""

from __future__ import annotations

import time
from pathlib import Path


def model_config(model: dict):
    """configs/<name>.json ``model`` (HF config.json keys) -> Qwen2Config."""
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config

    return Qwen2Config(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        rope_theta=float(model["rope_theta"]), rms_norm_eps=float(model["rms_norm_eps"]),
        tie_word_embeddings=bool(model["tie_word_embeddings"]),
        max_position_embeddings=model["max_position_embeddings"])


def weight_seed(seed: int) -> int:
    """``--seed`` can exceed 32 bits' worth of the initialiser's salt
    arithmetic (seed * 40503 + 12345 as uint32): fold it first."""
    return int(seed) % 65521


def require_devices(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it.  A missing accelerator, or fewer chips
    than the cell asks for, ends the run: no fallback."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if rehearse:
        if dev["platform"] != "cpu":
            raise SystemExit("--rehearse is the CPU rehearsal: set JAX_PLATFORMS=cpu")
    elif dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX is on {dev['platform']!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX finds {len(devs)}")
    dev["count"] = chips
    return dev


class Probe:
    """Counters the program does not export, read by wrapping the engine's
    own methods from here: live rows and cached tokens at each decode-burst
    dispatch, each finished request's enqueue/admit/first-token stamps, and
    the prompts of the window (the correctness sample is drawn from them)."""

    def __init__(self) -> None:
        self.bursts: list = []    # (t, live rows, kv tokens over the live rows)
        self.prefills: list = []  # (t, [(cached, new tokens, completes)] per row)
        self.results: list = []   # per finished request
        self.prompts: list = []   # (t, prompt ids, max_tokens)

    def attach(self, engine) -> None:
        probe = self
        decode_step, result, add_request = engine._decode_step, engine._result, engine.add_request
        prefill_batch = engine._prefill_batch

        def _prefill_batch(reqs, finished):
            chunk = engine.prefill_chunk
            rows = []
            for r in reqs:
                new = min(len(r.prompt) - r.prefill_pos, chunk)
                rows.append((r.prefill_pos, new, r.prefill_pos + new >= len(r.prompt)))
            probe.prefills.append((time.monotonic(), rows))
            return prefill_batch(reqs, finished)

        def _decode_step(finished):
            rows = [r for r in engine._row_req.values() if r.state == "running"]
            probe.bursts.append((time.monotonic(), len(rows), sum(r.seq_len for r in rows)))
            return decode_step(finished)

        def _result(req, reason):
            res = result(req, reason)
            probe.results.append({
                "submit_t": req.submit_t, "prefill_start_t": req.prefill_start_t,
                "first_token_t": req.first_token_t, "done_t": res.timings["done_t"],
                "prompt_tokens": len(res.prompt_tokens), "cached_tokens": req.cached_tokens,
                "output_tokens": len(res.output_tokens), "reason": reason})
            return res

        def _add_request(prompt_ids, sampling=None, *a, **kw):
            if len(probe.prompts) < 4096:
                probe.prompts.append((time.monotonic(), list(prompt_ids),
                                      getattr(sampling, "max_tokens", 0)))
            return add_request(prompt_ids, sampling, *a, **kw)

        engine._decode_step, engine._result, engine.add_request = \
            _decode_step, _result, _add_request
        engine._prefill_batch = _prefill_batch


def build_engine(config: dict, model: dict, needs: dict, seed: int):
    """(engine, cfg).  Weights are made on the device from the seed by the
    program's own initialiser, in the type they are served in.  ``needs`` is
    what the cell's traffic asks of the deployment (context window, pool)."""
    import jax

    from githubrepostorag_tpu.runtime import on_tpu
    from githubrepostorag_tpu.serving.engine import Engine

    geo = {**config["engine"], **{k: v for k, v in needs.items()
                                  if k in ("max_seq_len", "num_pages", "page_size",
                                           "prefill_chunk", "max_num_seqs")}}
    cfg = model_config(model)
    wseed = weight_seed(seed)
    tp = (config.get("mesh") or {}).get("tp", 1)
    mesh = None
    if tp > 1:
        from githubrepostorag_tpu.parallel import MeshPlan, make_mesh

        mesh = make_mesh(MeshPlan(tp=tp), devices=jax.devices()[:tp])
    weights = config["weights"]
    if weights["dtype"] == "int8":
        from githubrepostorag_tpu.models.quant import init_params_quantized

        params = init_params_quantized(cfg, seed=wseed, bits=8, fuse=tp == 1)
    else:
        raise SystemExit(f"weights.dtype {weights['dtype']!r}: no initialiser wired")
    jax.block_until_ready(params)
    engine = Engine(params, cfg, max_num_seqs=geo["max_num_seqs"], num_pages=geo["num_pages"],
                    page_size=geo["page_size"], max_seq_len=geo["max_seq_len"],
                    prefill_chunk=geo["prefill_chunk"], decode_burst=geo.get("decode_burst", 8),
                    use_pallas=on_tpu(), mesh=mesh, rng_seed=wseed)
    return engine, cfg


def warm(engine, rows: list[int], sampled: bool = False) -> None:
    """Compile (or load from the persistent cache) the programs this cell's
    traffic runs and no others: the prefill program at each row bucket the
    traffic can reach, the greedy decode burst, first-token sampling and the
    cached-prefix presence marking at the same buckets."""
    import jax.numpy as jnp

    from githubrepostorag_tpu.serving.engine import _mark_presence_chunks
    from githubrepostorag_tpu.serving.sampling_params import SamplingParams

    sp = SamplingParams(max_tokens=2, temperature=0.0, stop_token_ids=())
    plen = min(engine.prefill_chunk, engine.max_seq_len - 3)
    for wave, nb in enumerate(rows):
        if nb > engine.max_num_seqs:
            continue
        tok = 2 + wave % max(2, engine.cfg.vocab_size - 2)
        engine.generate([[tok] * plen] + [[tok] * 3] * (nb - 1), sp)
        if engine.prefix_caching:
            engine._presence = _mark_presence_chunks(
                engine._presence, jnp.zeros((nb,), jnp.int32),
                jnp.zeros((nb, engine.max_seq_len), jnp.int32), jnp.zeros((nb,), jnp.int32),
                engine.cfg.vocab_size)
    # The engine overlays freshly prefilled rows on a burst's inputs with eager
    # gathers and scatters whose shapes depend on how many rows finished
    # prefill together (``last_d.at[rows].set(tokens_d[idxs])`` in
    # ``Engine._decode_step``).  Each count is a small program of its own;
    # unwarmed, it compiles (or is read from the cache) under traffic.
    import numpy as np

    last = jnp.zeros((engine.max_num_seqs,), jnp.int32)
    for nb in rows:
        tokens = jnp.zeros((nb,), jnp.int32)
        for k in range(1, nb + 1):
            idx = jnp.asarray(np.zeros((k,), np.int32))
            last = last.at[idx].set(tokens[idx])
    last.block_until_ready()
    if sampled:  # the burst variant that filters (top-p): the agent's calls sample
        engine.generate([[5, 6, 7]], SamplingParams(max_tokens=2, temperature=0.7, top_p=0.9,
                                                    stop_token_ids=()))


def load_tokenizer(tok_dir: Path, ignore_eos: bool):
    from githubrepostorag_tpu.serving.tokenizer import make_tokenizer

    tok = make_tokenizer(str(tok_dir), backend="native")
    if ignore_eos:
        # the server has no ignore_eos switch: it stops on the tokenizer's
        # EOS id.  A model with random weights emits that id by chance
        # (1 token in 152,064), which would cut a request short and change
        # the run's work; handing the server a tokenizer without one is
        # the benchmark's "EOS ignored".
        tok.eos_token_id = None
    return tok


class OpenAIEntry:
    """``/v1/chat/completions`` served as ``serving/__main__`` serves it:
    Engine -> AsyncEngine -> OpenAIServer."""

    def __init__(self, engine, tokenizer, name: str) -> None:
        from githubrepostorag_tpu.serving.async_engine import AsyncEngine
        from githubrepostorag_tpu.serving.openai_api import OpenAIServer

        self.async_engine = AsyncEngine(engine)
        self.server = OpenAIServer(self.async_engine, tokenizer, model_name=name)

    async def start(self) -> str:
        port = await self.server.start(host="127.0.0.1", port=0)
        return f"http://127.0.0.1:{port}"

    async def stop(self) -> None:
        await self.server.stop()
