"""Mellum (Mellum2-12B-A2.5B: periods of three sliding-window layers and one
YaRN-scaled global layer, both rotary, each layer a sequential block that ends
in 64 softmax-routed experts, ALL of them on this chip) as the first stage of a
pipeline: what the harness takes from the program to run it, the reference it
is held to, and its counts.

From the program: ``MellumConfig``, ``init_params``, ``forward_paged``
(models/mellum.py) and ``Engine``, which keeps two kinds of page for it
(``sliding_pages`` beside ``num_pages``).  The reference is
``benchmarks/reference_mellum.py``.  The counts (``work``) are below: the
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
MODEL_KEYS = ("vocab_size", "hidden_size", "moe_intermediate_size", "num_hidden_layers",
              "layer_types", "num_attention_heads", KV_HEADS, "head_dim", "sliding_window",
              "num_experts", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
              "rope_parameters", "max_position_embeddings", "experts_held")
KINDS = {"sliding_attention": "sliding", "full_attention": "global"}


def model_of(config: dict, rehearse: bool) -> dict:
    """The stage as the program and the reference are given it: the source's
    keys, ``num_experts`` the router's width (every expert is held here, so the
    file's count is the source's).  A checkout whose program has no such family
    (any commit before PR 54) is told so here, at once."""
    if importlib.util.find_spec("githubrepostorag_tpu.models.mellum") is None:
        raise ManifestError("this checkout's program has no models/mellum.py: it cannot "
                            "run a configuration of the mellum family")
    model = {k: config[k] for k in MODEL_KEYS}
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


def period_of(model: dict) -> tuple:
    """One period of ``layer_types`` in the program's names: up to and with the
    first global layer.  The layers kept must be whole periods of it."""
    kept = [KINDS[t] for t in model["layer_types"][:model["num_hidden_layers"]]]
    period = tuple(kept[:kept.index("global") + 1])
    if list(period) * (len(kept) // len(period)) != kept:
        raise ManifestError(f"layer_types' first {len(kept)} entries are not whole periods of "
                            f"{period}")
    return period


def model_config(model: dict):
    from githubrepostorag_tpu.models.mellum import MellumConfig

    local, full = (model["rope_parameters"][k] for k in ("sliding_attention", "full_attention"))
    if local["rope_type"] != "default" or full["rope_type"] != "yarn" \
            or local["rope_theta"] != full["rope_theta"]:
        raise ManifestError("rope_parameters: a plain table for the sliding layers and a YaRN "
                            "one of the same theta for the global ones is what is built")
    return MellumConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_layers=model["num_hidden_layers"], period=period_of(model),
        num_heads=model["num_attention_heads"], num_kv_heads=model[KV_HEADS],
        head_dim=model["head_dim"], sliding_window=model["sliding_window"],
        num_experts=model["num_experts"], num_experts_per_tok=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), rms_norm_eps=float(model["rms_norm_eps"]),
        rope_theta=float(full["rope_theta"]), yarn_factor=float(full["factor"]),
        yarn_original_max=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]), yarn_beta_slow=float(full["beta_slow"]),
        attention_factor=float(full["attention_factor"]),
        max_position_embeddings=model["max_position_embeddings"],
        experts_held=tuple(model["experts_held"]))


def checkpoint_seed(config: dict) -> int:
    """The seed of the weights: the configuration's own, the same in every
    run (with a router the weights decide which experts a topic's rows wake).
    ``--seed`` draws the traffic, the sampler's key and the correctness
    sample."""
    return weight_seed(config["weights"]["seed"])


def build_engine(config: dict, model: dict, needs: dict, seed: int):
    import jax

    from githubrepostorag_tpu.models.mellum import init_params
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
    prefix to mark (a [rows, max_seq_len] program a bucket; unwarmed it
    compiled under traffic in Command A+'s cell: PERF.md, Findings, PR 50)."""
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
    kind are taken from the top of their pool without asking the ledgers, so
    this runs last: neither cache is valid afterwards.  A sequence keeps every
    global page it fills; of the sliding kind it is given a RING of the
    ledger's ``cap`` pages (a window, a chunk and one more), absolute page j at
    ``j % cap``: four 25k-token prompts are 788 pages of a pool that holds 512,
    and a page is written over only when every key in it lies behind the
    window of the chunk being written (the tables are indexed by absolute
    page, and what they name behind the window is never read)."""
    import jax.numpy as jnp

    from githubrepostorag_tpu.models.mellum import forward_paged
    from githubrepostorag_tpu.serving.engine import _bucket

    rb = _bucket(len(seqs), engine.max_num_seqs, minimum=1)
    w, ps = engine.prefill_chunk, engine.page_size
    per = -(-max(len(s) for s in seqs) // ps)
    ring = engine.sliding_ledger.cap
    if per > engine.max_pages_per_seq or rb * per > engine._allocator.num_pages \
            or rb * ring > engine.sliding_ledger.num_pages:
        raise RuntimeError("correctness sample does not fit the page pools")
    bt = np.zeros((rb, engine.max_pages_per_seq), np.int32)
    st = np.zeros((rb, engine.max_pages_per_seq), np.int32)
    for i in range(len(seqs)):
        bt[i, :per] = np.arange(i * per, (i + 1) * per)
        st[i, :per] = i * ring + np.arange(per) % ring
    table, sliding_table = jnp.asarray(bt), jnp.asarray(st)
    out = np.zeros((len(seqs), engine.cfg.vocab_size), np.float32)
    for c in range(-(-max(len(s) for s in seqs) // w)):
        start = c * w
        ids = np.zeros((rb, w), np.int32)
        slots = np.full((rb, w), -1, np.int32)
        sslots = np.full((rb, w), -1, np.int32)
        cached = np.zeros((rb,), np.int32)
        lens = np.zeros((rb,), np.int32)
        for i, s in enumerate(seqs):
            valid = max(0, min(len(s) - start, w))
            if not valid:
                continue
            ids[i, :valid] = s[start:start + valid]
            pos = start + np.arange(valid)
            slots[i, :valid] = bt[i, pos // ps] * ps + pos % ps
            sslots[i, :valid] = st[i, pos // ps] * ps + pos % ps
            cached[i], lens[i] = start, valid
        pos2 = np.broadcast_to(start + np.arange(w, dtype=np.int32), (rb, w))
        sk, sv = engine.sliding_pools
        logits, engine.page_pool, engine.value_pool, _, sk, sv = forward_paged(
            engine.params, engine.cfg, jnp.asarray(ids), jnp.asarray(pos2), engine.page_pool,
            engine.value_pool, jnp.asarray(slots), table, jnp.asarray(cached),
            jnp.asarray(lens), use_pallas=engine.use_pallas,
            logits_at=jnp.asarray(np.maximum(lens - 1, 0)), sliding_k=sk, sliding_v=sv,
            sliding_slots=jnp.asarray(sslots), sliding_tables=sliding_table)
        engine.sliding_pools = (sk, sv)
        got = np.asarray(logits[:, 0], np.float32)
        for i, s in enumerate(seqs):
            if start < len(s) <= start + w:
                out[i] = got[i]
    return out


def reference_logits_at(config: dict, model: dict, wseed: int, full: list, positions: list,
                        control: str | None = None) -> list:
    from benchmarks import reference_mellum  # imports jax: not before a run needs it

    # the harness hands over the seed it folds from ``--seed``; the weights are the checkpoint's
    return reference_mellum.logits_at(model, checkpoint_seed(config), full, positions,
                                      control=control)


# ------------------------------------------------------------------ counts --

def _dims(model: dict) -> SimpleNamespace:
    lo, hi = model["experts_held"]
    layers = model["num_hidden_layers"]
    lg = [KINDS[t] for t in model["layer_types"][:layers]].count("global")
    return SimpleNamespace(
        d=model["hidden_size"], h=model["num_attention_heads"], nkv=model[KV_HEADS],
        hd=model["head_dim"], ff=model["moe_intermediate_size"], e=model["num_experts"],
        k=model["num_experts_per_tok"], held=hi - lo, layers=layers, lg=lg, ls=layers - lg,
        window=model["sliding_window"], v=model["vocab_size"])


def attention_params(model: dict) -> int:
    s = _dims(model)
    return s.d * s.h * s.hd + 2 * s.d * s.nkv * s.hd + s.h * s.hd * s.d


def expert_params(model: dict) -> int:
    s = _dims(model)
    return 3 * s.d * s.ff


def beside_params(model: dict) -> int:
    """A layer's parameters beside its experts: attention, the router, two
    block norms and the two per-head norms' gains."""
    s = _dims(model)
    return attention_params(model) + s.d * s.e + 2 * s.d + 2 * s.hd


def experts_hit(model: dict, tokens: float) -> float:
    """Held experts that receive at least one of ``tokens`` tokens under a
    router that spreads its k choices evenly over all the experts.  This
    checkpoint's router need not be even; ``burst_counted_bytes`` takes the
    engine's own count."""
    s = _dims(model)
    return s.held * (1.0 - (1.0 - s.k / s.e) ** tokens)


def key_bytes(model: dict, kv_bytes: float = 2.0) -> float:
    """One token's key and value in ONE layer (2,048 B at 4 kv heads of 128)."""
    s = _dims(model)
    return 2 * s.nkv * s.hd * kv_bytes


def key_flops(model: dict) -> float:
    """Operations of one (query, key) pair in one layer, every head: the score
    and the weighted sum (16,384 at 32 heads of 128)."""
    s = _dims(model)
    return 4.0 * s.h * s.hd


def fixed_weight_bytes(model: dict, bytes_per_weight: float) -> float:
    """Bytes of the weights every decode step streams whatever the router
    does: attention, the router and the norms of every layer, the last norm
    and the head (a matrix of its own, read whole; of the embedding a step
    reads a row a sequence, which is not counted)."""
    s = _dims(model)
    return (s.layers * beside_params(model) + s.d + s.d * s.v) * bytes_per_weight


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
    token in attention's projections and the router, and in the routed experts
    held here for the share of pairs a uniform router sends them (k * held / E
    a token: all 8 where every expert is held); ``key_flops`` a (query, key)
    pair in a global layer, and in a sliding layer for the pairs a window can
    hold at most; the head once a sequence."""
    s = _dims(model)
    per_token = s.layers * (attention_params(model) + s.d * s.e
                            + s.k * s.held / s.e * expert_params(model))
    pairs = key_flops(model) * (s.lg * context_pairs
                                + s.ls * min(context_pairs, new_tokens * s.window))
    return 2.0 * per_token * new_tokens + pairs + 2.0 * s.d * s.v * sequences


def causal_pairs(cached: int, new: int) -> int:
    return new * cached + new * (new + 1) // 2


def expert_op_sizes(model: dict, config: dict) -> dict:
    """What names the decode burst's expert products in a trace: a dispatch
    tile holds the burst's rows (``max_num_seqs``, to a multiple of 8, at most
    models/moe.dropless_experts' 128), gate|up is ``2 * moe_intermediate`` wide
    in bfloat16, down and the combine ``hidden`` wide in float32."""
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
