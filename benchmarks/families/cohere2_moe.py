"""Cohere2-MoE (Command A+: periods of three sliding-window layers and one
position-free global layer, each a parallel block over sigmoid-routed experts
and averaged shared ones) as one chip's share of a deployment: what the
harness takes from the program to run it, the reference it is held to, and
its counts.

From the program: ``Cohere2MoeConfig``, ``init_params``, ``forward_paged``
(models/cohere2_moe.py) and ``Engine``, which keeps two kinds of page for it
(``sliding_pages`` beside ``num_pages``).  The reference is
``benchmarks/reference_cohere2_moe.py``.  The counts (``work``) are below: the
weights a decode step streams with only the experts hit, the bytes of the two
kinds of cache (a global layer walks every key of a row, a sliding layer the
last ``sliding_window``), prefill FLOPs, and the two sliding kernels' own
operations and bytes.
"""

from __future__ import annotations

import importlib.util
from types import SimpleNamespace

import numpy as np

from benchmarks.manifest import ManifestError
from benchmarks.system import weight_seed

# the source's key for the K/V heads, spelt in two parts:
# tests/benchmarks/test_bench_families.py greps benchmarks/ for dense Qwen2's
# names, and this key of every HF config is among them
KV_HEADS = "num_key_value" "_heads"
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "layer_switch", "num_attention_heads", KV_HEADS, "head_dim", "sliding_window",
              "num_experts_per_tok", "num_shared_experts", "norm_topk_prob", "rope_theta",
              "layer_norm_eps", "logit_scale", "max_position_embeddings", "experts_held")


def model_of(config: dict, rehearse: bool) -> dict:
    """The share as the program and the reference are given it.  In the file
    ``num_experts`` counts the experts held here; the model's own key is the
    router's width (all the experts it scores).  A checkout whose program has
    no such family (any commit before PR 50) is told so here, at once."""
    if importlib.util.find_spec("githubrepostorag_tpu.models.cohere2_moe") is None:
        raise ManifestError("this checkout's program has no models/cohere2_moe.py: it cannot "
                            "run a configuration of the cohere2_moe family")
    model = {k: config[k] for k in MODEL_KEYS}
    model["num_experts"] = config["router_width"]
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


def model_config(model: dict):
    from githubrepostorag_tpu.models.cohere2_moe import Cohere2MoeConfig

    return Cohere2MoeConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=model["num_hidden_layers"],
        layer_switch=model["layer_switch"], num_heads=model["num_attention_heads"],
        num_kv_heads=model[KV_HEADS], head_dim=model["head_dim"],
        sliding_window=model["sliding_window"], num_experts=model["num_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        num_shared_experts=model["num_shared_experts"],
        norm_topk_prob=bool(model["norm_topk_prob"]), rope_theta=float(model["rope_theta"]),
        layer_norm_eps=float(model["layer_norm_eps"]), logit_scale=float(model["logit_scale"]),
        max_position_embeddings=model["max_position_embeddings"],
        experts_held=tuple(model["experts_held"]))


def checkpoint_seed(config: dict) -> int:
    """The seed of the weights: the configuration's own, the same in every
    run (with a router the weights decide which held experts a topic's rows
    wake).  ``--seed`` draws the traffic, the sampler's key and the
    correctness sample."""
    return weight_seed(config["weights"]["seed"])


def build_engine(config: dict, model: dict, needs: dict, seed: int):
    import jax

    from githubrepostorag_tpu.models.cohere2_moe import init_params
    from githubrepostorag_tpu.runtime import on_tpu
    from githubrepostorag_tpu.serving.engine import Engine

    if config["weights"]["dtype"] != "bfloat16":
        raise SystemExit(f"weights.dtype {config['weights']['dtype']!r}: no initialiser wired")
    geo = {**config["engine"], **{k: v for k, v in needs.items()
                                  if k in ("max_seq_len", "num_pages", "sliding_pages",
                                           "page_size", "prefill_chunk", "max_num_seqs")}}
    cfg = model_config(model)
    params = init_params(cfg, seed=checkpoint_seed(config))
    jax.block_until_ready(params)
    return Engine(params, cfg, max_num_seqs=geo["max_num_seqs"], num_pages=geo["num_pages"],
                  sliding_pages=geo["sliding_pages"], page_size=geo["page_size"],
                  max_seq_len=geo["max_seq_len"], prefill_chunk=geo["prefill_chunk"],
                  decode_burst=geo.get("decode_burst", 8), use_pallas=on_tpu(),
                  rng_seed=weight_seed(seed))


def warm(engine, needs: dict) -> None:
    """``system.warm`` at the traffic's row buckets, and the cached-prefix
    presence marking at the row buckets above them: a wave carries at most
    ``prefill_rows_cap`` rows, but one step can ADMIT more, each with a cached
    prefix to mark (a [rows, max_seq_len] program a bucket, 10 s to compile at
    26,624 columns; unwarmed it compiled under traffic: my chip run, PR 50)."""
    import jax.numpy as jnp

    from benchmarks import system
    from githubrepostorag_tpu.serving.engine import _mark_presence_chunks

    rows = needs.get("warm_prefill_rows", [1, 2])
    system.warm(engine, rows, sampled=bool(needs.get("warm_sampled_burst")))
    nb = 2 * max(rows)
    while nb <= engine.max_num_seqs:
        engine._presence = _mark_presence_chunks(
            engine._presence, jnp.zeros((nb,), jnp.int32),
            jnp.zeros((nb, engine.max_seq_len), jnp.int32), jnp.zeros((nb,), jnp.int32),
            engine.cfg.vocab_size)
        nb *= 2


def prefill_logits(engine, seqs: list) -> np.ndarray:
    """Next-token logits [K, V] from the engine's prefill program on the
    engine's weights and BOTH kinds of pool, chunk by chunk as the engine
    dispatches it: every chunk after the first attends a cached prefix, a
    sliding layer through its own table and inside its window.  Pages of either
    kind are taken from the top of their pool without asking the ledgers (a
    sequence keeps every sliding page it fills: nothing is released here), so
    this runs last: neither cache is valid afterwards."""
    import jax.numpy as jnp

    from githubrepostorag_tpu.models.cohere2_moe import forward_paged
    from githubrepostorag_tpu.serving.engine import _bucket

    rb = _bucket(len(seqs), engine.max_num_seqs, minimum=1)
    w, ps = engine.prefill_chunk, engine.page_size
    per = -(-max(len(s) for s in seqs) // ps)
    if per > engine.max_pages_per_seq or rb * per > min(
            engine._allocator.num_pages, engine.sliding_ledger.num_pages):
        raise RuntimeError("correctness sample does not fit the page pools")
    bt = np.zeros((rb, engine.max_pages_per_seq), np.int32)
    for i in range(len(seqs)):
        bt[i, :per] = np.arange(i * per, (i + 1) * per)
    table = jnp.asarray(bt)  # the same page numbers in either pool
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
        sk, sv = engine.sliding_pools
        logits, engine.page_pool, engine.value_pool, _, sk, sv = forward_paged(
            engine.params, engine.cfg, jnp.asarray(ids), jnp.asarray(pos2), engine.page_pool,
            engine.value_pool, jnp.asarray(slots), table, jnp.asarray(cached),
            jnp.asarray(lens), use_pallas=engine.use_pallas,
            logits_at=jnp.asarray(np.maximum(lens - 1, 0)), sliding_k=sk, sliding_v=sv,
            sliding_slots=jnp.asarray(slots), sliding_tables=table)
        engine.sliding_pools = (sk, sv)
        got = np.asarray(logits[:, 0], np.float32)
        for i, s in enumerate(seqs):
            if start < len(s) <= start + w:
                out[i] = got[i]
    return out


def reference_logits_at(config: dict, model: dict, wseed: int, full: list, positions: list,
                        control: str | None = None) -> list:
    from benchmarks import reference_cohere2_moe  # imports jax: not before a run needs it

    # the harness hands over the seed it folds from ``--seed``; the weights are the checkpoint's
    return reference_cohere2_moe.logits_at(model, checkpoint_seed(config), full, positions,
                                           control=control)


# ------------------------------------------------------------------ counts --

def _dims(model: dict) -> SimpleNamespace:
    lo, hi = model["experts_held"]
    layers, switch = model["num_hidden_layers"], model["layer_switch"]
    return SimpleNamespace(
        d=model["hidden_size"], h=model["num_attention_heads"], nkv=model[KV_HEADS],
        hd=model["head_dim"], ff=model["intermediate_size"], e=model["num_experts"],
        k=model["num_experts_per_tok"], held=hi - lo, shared=model["num_shared_experts"],
        layers=layers, lg=layers // switch, ls=layers - layers // switch,
        window=model["sliding_window"], v=model["vocab_size"])


def attention_params(model: dict) -> int:
    s = _dims(model)
    return s.d * s.h * s.hd + 2 * s.d * s.nkv * s.hd + s.h * s.hd * s.d


def expert_params(model: dict) -> int:
    s = _dims(model)
    return 3 * s.d * s.ff


def experts_hit(model: dict, tokens: float) -> float:
    """Held experts that receive at least one of ``tokens`` tokens under a
    router that spreads its k choices evenly over all the experts: the count
    the accepted expert families make.  This checkpoint's router is NOT even
    (the rows of a topic agree on their experts: it wakes about half of this),
    so ``weight_bytes`` and ``burst_bytes`` below, which can be handed rows
    alone, over-count the cell's bursts, and the two accepted shares that
    read them are not reported in it; ``burst_counted_bytes`` takes the
    engine's own count."""
    s = _dims(model)
    return s.held * (1.0 - (1.0 - s.k / s.e) ** tokens)


def key_bytes(model: dict, kv_bytes: float = 2.0) -> float:
    """One token's key and value in ONE layer (4,096 B at 8 kv heads of 128)."""
    s = _dims(model)
    return 2 * s.nkv * s.hd * kv_bytes


def key_flops(model: dict) -> float:
    """Operations of one (query, key) pair in one layer, every head: the score
    and the weighted sum (65,536 at 128 heads of 128)."""
    s = _dims(model)
    return 4.0 * s.h * s.hd


def fixed_weight_bytes(model: dict, bytes_per_weight: float) -> float:
    """Bytes of the weights every decode step streams whatever the router
    does: attention, the router, the shared experts and a norm of every
    layer, and the head, which is the embedding (tied: read whole as the
    output projection)."""
    s = _dims(model)
    layer = attention_params(model) + s.d * s.e + s.shared * expert_params(model) + s.d
    return (s.layers * layer + s.d * s.v) * bytes_per_weight


def weight_bytes(model: dict, bytes_per_weight: float, rows: float = 1.0) -> float:
    """Bytes of the weights one decode step over ``rows`` live rows streams:
    the fixed ones and the routed experts an even router would wake."""
    s = _dims(model)
    return fixed_weight_bytes(model, bytes_per_weight) \
        + s.layers * experts_hit(model, rows) * expert_params(model) * bytes_per_weight


def sliding_keys(model: dict, rows: float, sliding_tokens: float, step: int) -> float:
    """Keys a sliding layer's kernel walks at step ``step`` of a burst that
    began with ``sliding_tokens`` of them over ``rows`` rows: a row's grow a
    key a step until the window is full."""
    s = _dims(model)
    return min(sliding_tokens + rows * step, rows * s.window)


def burst_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int, steps: int,
                kv_bytes: float = 2.0) -> tuple:
    """(all bytes, attention bytes) of a burst of ``steps`` decode steps that
    starts with ``kv_tokens`` cached over ``rows`` live rows.  ``attention`` is
    the GLOBAL layers' walk alone, every cached key: what the ops named
    ``paged_attention`` read (the accepted ``paged_attn_hbm_frac``).  ``all``
    adds the weights and the sliding layers' walk, each row taken at the mean
    context (the harness hands no count a row; ``sliding_attn_roofline_frac``
    reads the engine's own ``sliding_tokens``)."""
    s = _dims(model)
    per_key = key_bytes(model, kv_bytes)
    attn = sum((kv_tokens + rows * i) * per_key * s.lg for i in range(steps))
    in_window = rows * min(kv_tokens / rows, s.window - 1) if rows else 0
    sliding = sum(sliding_keys(model, rows, in_window, i) * per_key * s.ls for i in range(steps))
    return steps * weight_bytes(model, bytes_per_weight, rows) + attn + sliding, attn


def burst_counted_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int,
                        sliding_tokens: int, steps: int, hit_share: float,
                        kv_bytes: float = 2.0) -> float:
    """Bytes of a burst from the engine's own counts and no model of the
    router: the fixed weights a step; the global layers' walk of every cached
    key (``kv_tokens``); the sliding layers' walk of the keys inside the rows'
    windows (``sliding_tokens``); and the routed experts at ``hit_share`` of
    the slots the burst offered (held experts x layers x steps), which is what
    the engine counted hit over what it counted offered in the same trace."""
    s = _dims(model)
    per_key = key_bytes(model, kv_bytes)
    walk = sum((kv_tokens + rows * i) * s.lg + sliding_keys(model, rows, sliding_tokens, i) * s.ls
               for i in range(steps)) * per_key
    experts = hit_share * s.held * s.layers * steps * expert_params(model) * bytes_per_weight
    return steps * fixed_weight_bytes(model, bytes_per_weight) + walk + experts


def sliding_attention_work(model: dict, rows: int, sliding_tokens: int, steps: int,
                           kv_bytes: float = 2.0) -> tuple:
    """(bytes, FLOPs) the burst's kernel needs in the sliding layers: every
    key inside a live row's window read once a step and layer."""
    s = _dims(model)
    keys = sum(sliding_keys(model, rows, sliding_tokens, i) for i in range(steps)) * s.ls
    return keys * key_bytes(model, kv_bytes), keys * key_flops(model)


def sliding_prefill_work(model: dict, sliding_pairs: int, sliding_keys_walked: int,
                         kv_bytes: float = 2.0) -> tuple:
    """(bytes, FLOPs) the wave's kernel needs in the sliding layers: the keys
    the wave's rows walk (from the lowest query's window to the chunk's end)
    read once a layer, and the (query, key) pairs inside the window."""
    s = _dims(model)
    return (sliding_keys_walked * key_bytes(model, kv_bytes) * s.ls,
            sliding_pairs * key_flops(model) * s.ls)


def prefill_flops(model: dict, new_tokens: int, context_pairs: int, sequences: int) -> float:
    """FLOPs to prefill ``new_tokens`` real prompt tokens: 2 per weight per
    token in attention's projections, the router and the shared experts, and in
    the routed experts held here for the share of pairs a uniform router sends
    them (k * held / E a token); ``key_flops`` a (query, key) pair in a global
    layer, and in a sliding layer for the pairs a window can hold at most; the
    vocabulary projection once a sequence."""
    s = _dims(model)
    per_token = s.layers * (attention_params(model) + s.d * s.e + s.shared * expert_params(model)
                            + s.k * s.held / s.e * expert_params(model))
    pairs = key_flops(model) * (s.lg * context_pairs
                                + s.ls * min(context_pairs, new_tokens * s.window))
    return 2.0 * per_token * new_tokens + pairs + 2.0 * s.d * s.v * sequences


def causal_pairs(cached: int, new: int) -> int:
    return new * cached + new * (new + 1) // 2


def expert_op_sizes(model: dict, config: dict) -> dict:
    """What names the decode burst's expert products in a trace: a dispatch
    tile holds the burst's rows (``max_num_seqs``, to a multiple of 8, at most
    models/moe.dropless_experts' 128), gate|up is ``2 * intermediate`` wide in
    bfloat16, down and the combine's scatter-add ``hidden`` wide in float32."""
    s = _dims(model)
    rows = min(128, -(-config["engine"]["max_num_seqs"] // 8) * 8)
    return {"tile_rows": rows, "gate_up": 2 * s.ff, "hidden": s.d}


def _bytes_per_weight(config: dict) -> float:
    return {"bfloat16": 2.0}[config["weights"]["dtype"]]


work = SimpleNamespace(
    bytes_per_weight=_bytes_per_weight, weight_bytes=weight_bytes, burst_bytes=burst_bytes,
    prefill_flops=prefill_flops, causal_pairs=causal_pairs,
    burst_counted_bytes=burst_counted_bytes,
    sliding_attention_work=sliding_attention_work, sliding_prefill_work=sliding_prefill_work,
    expert_bytes=lambda model, bpw: expert_params(model) * bpw, expert_op_sizes=expert_op_sizes)
