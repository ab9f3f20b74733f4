"""DeepSeek-V3 (latent attention, a dense lead-in, expert layers with a
shared expert) as one chip's share of a deployment: what the harness takes
from the program to run it, the reference it is held to, and its counts.

From the program: ``DeepseekV3Config``, ``init_params``, ``forward_paged``
(models/deepseek_v3.py) and ``Engine``.  The reference is
``benchmarks/reference_deepseek_v3.py``.  The counts (``work``) are below:
the weights a decode step streams with only the experts hit, the latent
cache's bytes, prefill FLOPs with the materialised up-projection, and the
decode kernel's own operations and bytes.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from benchmarks.system import weight_seed

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_experts_per_tok", "n_group", "topk_group", "n_shared_experts",
              "routed_scaling_factor", "norm_topk_prob", "rope_theta", "rope_scaling",
              "rms_norm_eps", "max_position_embeddings", "experts_held")


def model_of(config: dict, rehearse: bool) -> dict:
    """The share as the program and the reference are given it.  In the file
    ``n_routed_experts`` counts the experts held here; the model's own key is
    the router's width (all the experts it scores)."""
    model = {k: config[k] for k in MODEL_KEYS}
    model["n_routed_experts"] = config["router_width"]
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


def model_config(model: dict):
    from githubrepostorag_tpu.models.deepseek_v3 import DeepseekV3Config

    sc = model["rope_scaling"]
    return DeepseekV3Config(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_layers=model["num_hidden_layers"], first_k_dense=model["first_k_dense_replace"],
        num_heads=model["num_attention_heads"], q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"], qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        n_routed_experts=model["n_routed_experts"],
        num_experts_per_tok=model["num_experts_per_tok"], n_group=model["n_group"],
        topk_group=model["topk_group"], n_shared_experts=model["n_shared_experts"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        norm_topk_prob=bool(model["norm_topk_prob"]), rope_theta=float(model["rope_theta"]),
        rope_factor=float(sc["factor"]),
        rope_original_max=int(sc["original_max_position_embeddings"]),
        rope_beta_fast=float(sc["beta_fast"]), rope_beta_slow=float(sc["beta_slow"]),
        rope_mscale=float(sc["mscale"]), rope_mscale_all_dim=float(sc["mscale_all_dim"]),
        rms_norm_eps=float(model["rms_norm_eps"]),
        max_position_embeddings=model["max_position_embeddings"],
        experts_held=tuple(model["experts_held"]))


def checkpoint_seed(config: dict) -> int:
    """The seed of the weights: the configuration's own, the same in every
    run.  A deployment serves one checkpoint, and with a router the weights
    decide the work: which held experts a topic's rows wake, so how many
    expert stacks a decode step streams.  Drawn anew from ``--seed`` that
    share moved a run's ``tpot_p50_ms`` by more than the traffic's order did
    (PERF.md, Findings, PR 27); ``--seed`` draws the traffic, the sampler's
    key and the correctness sample."""
    return weight_seed(config["weights"]["seed"])


def build_engine(config: dict, model: dict, needs: dict, seed: int):
    import jax

    from githubrepostorag_tpu.models.deepseek_v3 import init_params
    from githubrepostorag_tpu.runtime import on_tpu
    from githubrepostorag_tpu.serving.engine import Engine

    if config["weights"]["dtype"] != "bfloat16":
        raise SystemExit(f"weights.dtype {config['weights']['dtype']!r}: no initialiser wired")
    geo = {**config["engine"], **{k: v for k, v in needs.items()
                                  if k in ("max_seq_len", "num_pages", "page_size",
                                           "prefill_chunk", "max_num_seqs")}}
    cfg = model_config(model)
    params = init_params(cfg, seed=checkpoint_seed(config))
    jax.block_until_ready(params)
    return Engine(params, cfg, max_num_seqs=geo["max_num_seqs"], num_pages=geo["num_pages"],
                  page_size=geo["page_size"], max_seq_len=geo["max_seq_len"],
                  prefill_chunk=geo["prefill_chunk"], decode_burst=geo.get("decode_burst", 8),
                  use_pallas=on_tpu(), rng_seed=weight_seed(seed))


def prefill_logits(engine, seqs: list) -> np.ndarray:
    """Next-token logits [K, V] from the engine's prefill program on the
    engine's weights and latent pool, chunk by chunk as the engine dispatches
    it (so every chunk after the first attends a cached prefix).  Pages are
    taken from the top of the pool without asking the allocator, so this runs
    last: the prefix cache is no longer valid afterwards."""
    import jax.numpy as jnp

    from githubrepostorag_tpu.models.deepseek_v3 import forward_paged
    from githubrepostorag_tpu.serving.engine import _bucket

    rb = _bucket(len(seqs), engine.max_num_seqs, minimum=1)
    w, ps = engine.prefill_chunk, engine.page_size
    per = -(-max(len(s) for s in seqs) // ps)
    if per > engine.max_pages_per_seq or rb * per > engine._allocator.num_pages:
        raise RuntimeError("correctness sample does not fit the page pool")
    bt = np.zeros((rb, engine.max_pages_per_seq), np.int32)
    for i in range(len(seqs)):
        bt[i, :per] = np.arange(i * per, (i + 1) * per)
    out = np.zeros((len(seqs), engine.cfg.vocab_size), np.float32)
    for c in range(-(-max(len(s) for s in seqs) // w)):
        start = c * w
        ids = np.zeros((rb, w), np.int32)
        slots = np.full((rb, w), -1, np.int32)
        cached = np.zeros((rb,), np.int32)
        lens = np.zeros((rb,), np.int32)
        for i, s in enumerate(seqs):
            valid = max(0, min(len(s) - start, w))
            if not valid:
                continue
            ids[i, :valid] = s[start:start + valid]
            pos = start + np.arange(valid)
            slots[i, :valid] = bt[i, pos // ps] * ps + pos % ps
            cached[i], lens[i] = start, valid
        pos2 = np.broadcast_to(start + np.arange(w, dtype=np.int32), (rb, w))
        logits, engine.page_pool, _, _ = forward_paged(
            engine.params, engine.cfg, jnp.asarray(ids), jnp.asarray(pos2), engine.page_pool,
            None, jnp.asarray(slots), jnp.asarray(bt), jnp.asarray(cached), jnp.asarray(lens),
            use_pallas=engine.use_pallas, logits_at=jnp.asarray(np.maximum(lens - 1, 0)))
        got = np.asarray(logits[:, 0], np.float32)
        for i, s in enumerate(seqs):
            if start < len(s) <= start + w:
                out[i] = got[i]
    return out


def reference_logits_at(config: dict, model: dict, wseed: int, full: list, positions: list,
                        control: str | None = None) -> list:
    from benchmarks import reference_deepseek_v3  # imports jax: not before a run needs it

    # the harness hands over the seed it folds from ``--seed``; the weights are the checkpoint's
    return reference_deepseek_v3.logits_at(model, checkpoint_seed(config), full, positions,
                                           control=control)


# ------------------------------------------------------------------ counts --

def _dims(model: dict) -> SimpleNamespace:
    lo, hi = model["experts_held"]
    return SimpleNamespace(
        d=model["hidden_size"], h=model["num_attention_heads"], q_rank=model["q_lora_rank"],
        rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], vd=model["v_head_dim"], ff=model["intermediate_size"],
        ffe=model["moe_intermediate_size"],
        ffs=model["moe_intermediate_size"] * model["n_shared_experts"],
        e=model["n_routed_experts"], k=model["num_experts_per_tok"], n=hi - lo,
        ld=model["first_k_dense_replace"],
        lm=model["num_hidden_layers"] - model["first_k_dense_replace"], v=model["vocab_size"])


def attention_params(model: dict) -> int:
    s = _dims(model)
    return s.d * s.q_rank + s.q_rank * s.h * (s.nope + s.rope) + s.d * (s.rank + s.rope) \
        + s.h * s.nope * s.rank + s.h * s.rank * s.vd + s.h * s.vd * s.d


def expert_params(model: dict) -> int:
    s = _dims(model)
    return 3 * s.d * s.ffe


def experts_hit(model: dict, tokens: float) -> float:
    """Held experts that receive at least one of ``tokens`` tokens, expected
    under a router that spreads its k choices evenly over all the experts."""
    s = _dims(model)
    return s.n * (1.0 - (1.0 - s.k / s.e) ** tokens)


def latent_row_bytes(model: dict, kv_bytes: float = 2.0) -> float:
    """One token's latent row in one layer as the algorithm needs it: the
    ``rank + rope`` columns of ``[c_kv | k_rope]`` (1,152 B).  The pool pads
    the row to whole 128-lane tiles (640 columns); the 64 columns of padding
    are the path's cost, not bytes needed, and count against its share."""
    s = _dims(model)
    return (s.rank + s.rope) * kv_bytes


def weight_bytes(model: dict, bytes_per_weight: float, rows: float = 1.0) -> float:
    """Bytes of the weights one decode step over ``rows`` live rows streams:
    attention, router and shared expert of every layer, the dense layers'
    MLP, only the routed experts that a row hit, and the output head.  The
    embedding table is not streamed (one row a live sequence)."""
    s = _dims(model)
    attn = attention_params(model)
    dense = s.ld * (attn + 3 * s.d * s.ff)
    moe = s.lm * (attn + s.d * s.e + 3 * s.d * s.ffs + experts_hit(model, rows)
                  * expert_params(model))
    return (dense + moe + s.d * s.v) * bytes_per_weight


def burst_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int, steps: int,
                kv_bytes: float = 2.0) -> tuple:
    """(all bytes, attention bytes) of a burst of ``steps`` decode steps that
    starts with ``kv_tokens`` cached over ``rows`` live rows."""
    s = _dims(model)
    per_tok = (s.ld + s.lm) * latent_row_bytes(model, kv_bytes)
    attn = sum((kv_tokens + rows * i) * per_tok for i in range(steps))
    return steps * weight_bytes(model, bytes_per_weight, rows) + attn, attn


def latent_attention_work(model: dict, rows: int, kv_tokens: int, steps: int,
                          kv_bytes: float = 2.0) -> tuple:
    """(bytes, FLOPs) the decode kernel needs over a burst, all layers: every
    cached row read once a step, and for each of them 2 * H * ((rank + rope)
    + rank) operations (the score against the latent row, the weighted sum of
    its first ``rank`` columns)."""
    s = _dims(model)
    layers = s.ld + s.lm
    read = sum(kv_tokens + rows * i for i in range(steps)) * layers
    return read * latent_row_bytes(model, kv_bytes), \
        read * 2.0 * s.h * (2 * s.rank + s.rope)


def latent_prefill_work(model: dict, pairs: int, kv_tokens: int, kv_bytes: float = 2.0) -> tuple:
    """(bytes, FLOPs) the prefill kernel's path needs for one wave, all
    layers: every cached row the wave's rows walk is read once and turned into
    K and V for every head (2 * rank * (nope + v) a head), and every (query,
    key) pair costs 2 * (nope + rope + v) a head."""
    s = _dims(model)
    layers = s.ld + s.lm
    flops = layers * s.h * (2.0 * (s.nope + s.rope + s.vd) * pairs
                            + 2.0 * s.rank * (s.nope + s.vd) * kv_tokens)
    return layers * kv_tokens * latent_row_bytes(model, kv_bytes), flops


def prefill_flops(model: dict, new_tokens: int, context_pairs: int, sequences: int) -> float:
    """FLOPs to prefill ``new_tokens`` real prompt tokens: 2 per weight per
    token in the projections, the dense MLPs, router and shared expert, and
    in the routed experts held here for the share of pairs a uniform router
    sends them (k * n / E a token); 4 * 192-or-128 per (query, key) pair and
    head for scores and values; the vocabulary projection once a sequence.
    The materialised up-projection of the cached latents is NOT counted: it
    is the price of the path, not work the algorithm needs (the absorbed form
    does without it)."""
    s = _dims(model)
    attn = attention_params(model)
    per_token = s.ld * (attn + 3 * s.d * s.ff) + s.lm * (
        attn + s.d * s.e + 3 * s.d * s.ffs + s.k * s.n / s.e * expert_params(model))
    pairs = 2.0 * s.h * (s.nope + s.rope + s.vd) * (s.ld + s.lm) * context_pairs
    return 2.0 * per_token * new_tokens + pairs + 2.0 * s.d * s.v * sequences


def causal_pairs(cached: int, new: int) -> int:
    return new * cached + new * (new + 1) // 2


def expert_op_sizes(model: dict, config: dict) -> dict:
    """What names the decode burst's expert products in a trace: a dispatch
    tile holds the burst's rows (``max_num_seqs``, to a multiple of 8, at most
    models/moe.dropless_experts' 128), gate|up is ``2 * moe_intermediate``
    wide in bfloat16, down and the combine's scatter-add ``hidden`` wide in
    float32."""
    s = _dims(model)
    rows = min(128, -(-config["engine"]["max_num_seqs"] // 8) * 8)
    return {"tile_rows": rows, "gate_up": 2 * s.ffe, "hidden": s.d}


def _bytes_per_weight(config: dict) -> float:
    return {"bfloat16": 2.0}[config["weights"]["dtype"]]


work = SimpleNamespace(
    bytes_per_weight=_bytes_per_weight, weight_bytes=weight_bytes, burst_bytes=burst_bytes,
    prefill_flops=prefill_flops, causal_pairs=causal_pairs,
    latent_attention_work=latent_attention_work, latent_prefill_work=latent_prefill_work,
    expert_bytes=lambda model, bpw: expert_params(model) * bpw, expert_op_sizes=expert_op_sizes)
