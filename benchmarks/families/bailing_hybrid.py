"""Ling-3.0-flash (five Kimi Delta Attention layers then one latent-attention
layer a period, a dense layer then group-routed sigmoid experts with a shared
expert) as one chip's share of a deployment: what the harness takes from the
program to run it, the reference it is held to, and its counts.

From the program: ``BailingHybridConfig``, ``init_params``, ``forward_paged``
(models/bailing_hybrid.py) and ``Engine``.  The reference is
``benchmarks/reference_bailing_hybrid.py``.  The counts (``work``) are below:
the weights a decode step streams, the bytes of the two kinds of cache (the
latent rows of the latent layers, the state of the KDA layers read and
written once a step), prefill FLOPs with the chunked rule's products, and
the KDA rule's and the latent kernels' own operations and bytes, each counted
from the WORK (rows, tokens, heads, dk, dv) and not from what implements it.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from benchmarks.system import weight_seed

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "num_hidden_layers", "layer_group_size",
              "layer_kinds", "first_k_dense_replace", "num_attention_heads", "head_dim",
              "short_conv_kernel_size", "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "rope_theta", "num_experts_per_tok", "n_group",
              "topk_group", "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
              "max_position_embeddings", "experts_held")
KDA_BLOCK = 64  # tokens of a block of the chunked rule (ops/gated_delta.BLOCK)


def model_of(config: dict, rehearse: bool) -> dict:
    """The share as the program and the reference are given it.  In the file
    ``num_experts`` counts the experts held here; the model's own key is the
    router's width (all the experts it scores)."""
    model = {k: config[k] for k in MODEL_KEYS}
    model["num_experts"] = config["router_width"]
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


def model_config(model: dict):
    from githubrepostorag_tpu.models.bailing_hybrid import BailingHybridConfig

    return BailingHybridConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        shared_expert_intermediate_size=model["moe_shared_expert_intermediate_size"],
        num_layers=model["num_hidden_layers"], layer_group_size=model["layer_group_size"],
        layer_kinds=model["layer_kinds"], first_k_dense=model["first_k_dense_replace"],
        num_heads=model["num_attention_heads"], kda_head_dim=model["head_dim"],
        short_conv_kernel_size=model["short_conv_kernel_size"],
        kda_lower_bound=float(model["kda_lower_bound"]), kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], rope_theta=float(model["rope_theta"]),
        num_experts=model["num_experts"], num_experts_per_tok=model["num_experts_per_tok"],
        n_group=model["n_group"], topk_group=model["topk_group"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        norm_topk_prob=bool(model["norm_topk_prob"]), rms_norm_eps=float(model["rms_norm_eps"]),
        max_position_embeddings=model["max_position_embeddings"],
        experts_held=tuple(model["experts_held"]))


def checkpoint_seed(config: dict) -> int:
    """The seed of the weights: the configuration's own, the same in every run
    (with a router the weights decide which held experts a topic's rows wake).
    ``--seed`` draws the traffic, the sampler's key and the correctness sample."""
    return weight_seed(config["weights"]["seed"])


def build_engine(config: dict, model: dict, needs: dict, seed: int):
    import jax

    from githubrepostorag_tpu.models.bailing_hybrid import init_params
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
                  state_snapshots=geo.get("state_snapshots"), use_pallas=on_tpu(),
                  rng_seed=weight_seed(seed))


# ``system.warm`` at the traffic's row buckets and the cached-prefix presence marking above them:
# the same requests as Mellum2's cell admit the same rows a step, and its family says why
from benchmarks.families.mellum import warm  # noqa: E402, F401


def prefill_logits(engine, seqs: list) -> np.ndarray:
    """Next-token logits [K, V] from the engine's prefill program on the
    engine's weights, latent pool and state pool, chunk by chunk as the engine
    dispatches it: every chunk after the first attends a cached prefix of
    latent rows and resumes the state the chunk before left in its row's slot.
    Pages are taken from the top of the pool and the rows' slots without asking
    their ledgers, so this runs last: neither cache is valid afterwards."""
    import jax.numpy as jnp

    from githubrepostorag_tpu.models.bailing_hybrid import forward_paged
    from githubrepostorag_tpu.serving.engine import _bucket

    rb = _bucket(len(seqs), engine.max_num_seqs, minimum=1)
    w, ps = engine.prefill_chunk, engine.page_size
    per = -(-max(len(s) for s in seqs) // ps)
    if per > engine.max_pages_per_seq or rb * per > engine._allocator.num_pages:
        raise RuntimeError("correctness sample does not fit the page pool")
    trash = engine.state_slots.trash
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
        src = np.full((rb,), -1, np.int32)
        dst = np.full((rb,), trash, np.int32)
        for i, s in enumerate(seqs):
            valid = max(0, min(len(s) - start, w))
            if not valid:
                continue
            ids[i, :valid] = s[start:start + valid]
            pos = start + np.arange(valid)
            slots[i, :valid] = bt[i, pos // ps] * ps + pos % ps
            cached[i], lens[i] = start, valid
            src[i], dst[i] = (i if start else -1), i
        pos2 = np.broadcast_to(start + np.arange(w, dtype=np.int32), (rb, w))
        logits, engine.page_pool, _, _, engine.state_pools = forward_paged(
            engine.params, engine.cfg, jnp.asarray(ids), jnp.asarray(pos2), engine.page_pool,
            None, jnp.asarray(slots), jnp.asarray(bt), jnp.asarray(cached), jnp.asarray(lens),
            use_pallas=engine.use_pallas, logits_at=jnp.asarray(np.maximum(lens - 1, 0)),
            state=engine.state_pools, state_src=jnp.asarray(src), state_dst=jnp.asarray(dst),
            state_snap=jnp.full((rb,), trash, jnp.int32), snap_col=jnp.zeros((rb,), jnp.int32))
        got = np.asarray(logits[:, 0], np.float32)
        for i, s in enumerate(seqs):
            if start < len(s) <= start + w:
                out[i] = got[i]
    return out


def reference_logits_at(config: dict, model: dict, wseed: int, full: list, positions: list,
                        control: str | None = None) -> list:
    from benchmarks import reference_bailing_hybrid  # imports jax: not before a run needs it

    # the harness hands over the seed it folds from ``--seed``; the weights are the checkpoint's
    return reference_bailing_hybrid.logits_at(model, checkpoint_seed(config), full, positions,
                                              control=control)


# ------------------------------------------------------------------ counts --

def kinds_of(model: dict) -> str:
    stated = model.get("layer_kinds")
    if stated:
        return stated
    return "".join("A" if (i + 1) % model["layer_group_size"] == 0 else "R"
                   for i in range(model["num_hidden_layers"]))


def _dims(model: dict) -> SimpleNamespace:
    lo, hi = model["experts_held"]
    kinds, layers = kinds_of(model), model["num_hidden_layers"]
    h, dk = model["num_attention_heads"], model["head_dim"]
    return SimpleNamespace(
        d=model["hidden_size"], h=h, dk=dk, taps=model["short_conv_kernel_size"],
        channels=3 * h * dk, rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], vd=model["v_head_dim"], ff=model["intermediate_size"],
        ffe=model["moe_intermediate_size"], ffs=model["moe_shared_expert_intermediate_size"],
        e=model["num_experts"], k=model["num_experts_per_tok"], n=hi - lo, layers=layers,
        ld=model["first_k_dense_replace"], lm=layers - model["first_k_dense_replace"],
        lg=kinds.count("R"), la=kinds.count("A"), v=model["vocab_size"])


def kda_params(model: dict) -> int:
    """W_q, W_k, W_v, the decay projection, the output gate, W_beta, the
    convolution's taps and W_o of one KDA mixer (63.0 M at the published widths)."""
    s = _dims(model)
    return s.d * s.channels + 2 * s.d * s.h * s.dk + s.d * s.h + s.channels * s.taps \
        + s.h * s.dk * s.d


def latent_params(model: dict) -> int:
    """W_q, W_kva, W_kvb (as W_uk and W_uv), the head gate and W_o of one latent
    mixer (32.0 M)."""
    s = _dims(model)
    return s.d * s.h * (s.nope + s.rope) + s.d * (s.rank + s.rope) + s.h * s.nope * s.rank \
        + s.h * s.rank * s.vd + s.d * s.h + s.h * s.vd * s.d


def expert_params(model: dict) -> int:
    s = _dims(model)
    return 3 * s.d * s.ffe


def state_bytes(model: dict) -> int:
    """One sequence's state in one KDA layer: the float32 matrix a head and the
    bfloat16 history of the convolution (2,170,880 B at the published widths)."""
    s = _dims(model)
    return s.h * s.dk * s.dk * 4 + (s.taps - 1) * s.channels * 2


def latent_row_bytes(model: dict, kv_bytes: float = 2.0) -> float:
    """One token's latent row in one layer as the algorithm needs it: the ``rank
    + rope`` columns (1,152 B); the pool's padding to 640 columns is the path's
    cost and counts against its share."""
    s = _dims(model)
    return (s.rank + s.rope) * kv_bytes


def weight_bytes(model: dict, bytes_per_weight: float) -> float:
    """Bytes of the weights every decode step streams whatever the router
    does: every mixer, the dense layers' MLP, every router and shared expert,
    and the output head (the embedding is one row a live sequence)."""
    s = _dims(model)
    mixers = s.lg * kda_params(model) + s.la * latent_params(model)
    ffn = s.ld * 3 * s.d * s.ff + s.lm * (s.d * s.e + 3 * s.d * s.ffs)
    return (mixers + ffn + s.d * s.v) * bytes_per_weight


def kda_decode_work(model: dict, rows: int, kv_tokens: int = 0, steps: int = 1) -> tuple:
    """(bytes, FLOPs) the one-token rule needs over a burst, all KDA layers:
    every live row's state and history read once and written once a step; 7
    operations an element of the matrix (the row's decay, S^T k, the rank-one
    update, S^T q)."""
    s = _dims(model)
    cells = rows * s.lg * steps
    return 2.0 * cells * state_bytes(model), 7.0 * cells * s.h * s.dk * s.dk


def kda_prefill_work(model: dict, new_tokens: int, rows: int = 1) -> tuple:
    """(bytes, FLOPs) the chunked rule needs for ``new_tokens`` real tokens of
    ``rows`` rows, all KDA layers.  A head and token, blocks of C = 64: K K^T,
    Q K^T and the two products with the block's inverse (2 C (3 dk + 2 dv)), the
    three products with the carried state and K^T V_new (8 dk dv); the
    triangular inverse and the decay factors a channel are left out, as is the
    padding of a rung.  Bytes: q, k, v, g in and o out in float32, the state
    read and written once a block, and once more a row for the slots."""
    s = _dims(model)
    c, dk = KDA_BLOCK, s.dk
    flops = new_tokens * s.h * (2.0 * c * 5 * dk + 8.0 * dk * dk)
    nbytes = new_tokens * s.h * 5 * dk * 4.0 + (new_tokens / c + rows) * 2.0 * s.h * dk * dk * 4.0
    return s.lg * nbytes, s.lg * flops


def attention_bytes(model: dict, rows: int, kv_tokens: int, steps: int,
                    kv_bytes: float = 2.0) -> float:
    """The latent rows a burst of ``steps`` decode steps reads that starts with
    ``kv_tokens`` cached over ``rows`` live rows, every latent layer."""
    per_tok = _dims(model).la * latent_row_bytes(model, kv_bytes)
    return sum((kv_tokens + rows * i) * per_tok for i in range(steps))


def latent_attention_work(model: dict, rows: int, kv_tokens: int, steps: int,
                          kv_bytes: float = 2.0) -> tuple:
    """(bytes, FLOPs) the decode kernel needs over a burst, the latent layers:
    every cached row read once a step, and for each 2 * H * ((rank + rope) +
    rank) operations (benchmarks/families/deepseek_v3.py's count at 32 heads)."""
    s = _dims(model)
    read = sum(kv_tokens + rows * i for i in range(steps)) * s.la
    return read * latent_row_bytes(model, kv_bytes), read * 2.0 * s.h * (2 * s.rank + s.rope)


def latent_prefill_work(model: dict, pairs: int, kv_tokens: int, kv_bytes: float = 2.0) -> tuple:
    """(bytes, FLOPs) the prefill kernel's path needs for one wave, the latent
    layers: every cached row the wave's rows walk read once and turned into K
    and V for every head (2 * rank * (nope + v)), every (query, key) pair 2 *
    (nope + rope + v) a head."""
    s = _dims(model)
    flops = s.la * s.h * (2.0 * (s.nope + s.rope + s.vd) * pairs
                          + 2.0 * s.rank * (s.nope + s.vd) * kv_tokens)
    return s.la * kv_tokens * latent_row_bytes(model, kv_bytes), flops


def burst_counted_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int,
                        sliding_tokens, steps: int, hit_share: float,
                        kv_bytes: float = 2.0) -> float:
    """Bytes of a burst from the engine's own counts and no model of the router
    (``decode_hbm_mfu_frac``): the fixed weights a step; the latent rows of every
    cached token (``kv_tokens``; no layer attends a window); the KDA layers' state
    read and written once a live row and step; and the routed experts at
    ``hit_share`` of the slots the burst offered (held experts x expert layers x
    steps): what the engine counted hit over what it counted offered."""
    s = _dims(model)
    state, _ = kda_decode_work(model, rows, kv_tokens, steps)
    experts = hit_share * s.n * s.lm * steps * expert_params(model) * bytes_per_weight
    return steps * weight_bytes(model, bytes_per_weight) \
        + attention_bytes(model, rows, kv_tokens, steps, kv_bytes) + state + experts


def prefill_flops(model: dict, new_tokens: int, context_pairs: int, sequences: int) -> float:
    """FLOPs to prefill ``new_tokens`` real prompt tokens: 2 per weight per token
    in the mixers' projections, the dense MLP, router and shared expert, and in
    the routed experts held here for the share of pairs a uniform router sends
    them (k * n / E a token); the chunked rule's products; 2 * (nope + rope + v)
    per (query, key) pair and head in the latent layers (the materialised
    up-projection of cached latents is the path's price, not counted); the
    vocabulary projection once a sequence."""
    s = _dims(model)
    per_token = s.lg * kda_params(model) + s.la * latent_params(model) + s.ld * 3 * s.d * s.ff \
        + s.lm * (s.d * s.e + 3 * s.d * s.ffs + s.k * s.n / s.e * expert_params(model))
    pairs = 2.0 * s.h * (s.nope + s.rope + s.vd) * s.la * context_pairs
    return 2.0 * per_token * new_tokens + kda_prefill_work(model, new_tokens)[1] + pairs \
        + 2.0 * s.d * s.v * sequences


def causal_pairs(cached: int, new: int) -> int:
    return new * cached + new * (new + 1) // 2


def expert_op_sizes(model: dict, config: dict) -> dict:
    """What names the decode burst's expert products in a trace
    (benchmarks/families/qwen3_next.expert_op_sizes)."""
    s = _dims(model)
    rows = min(128, -(-config["engine"]["max_num_seqs"] // 8) * 8)
    return {"tile_rows": rows, "gate_up": 2 * s.ffe, "hidden": s.d}


def state_op_sizes(model: dict, config: dict) -> dict:
    """What names an op on the state pool in a trace: the pool's shapes (KDA
    layers x slots x one slot) and the burst's view of its rows."""
    s = _dims(model)
    eng = config["engine"]
    return {"layers": s.lg, "slots": eng["max_num_seqs"] + eng["state_snapshots"] + 1,
            "rows": eng["max_num_seqs"], "hv": s.h, "dk": s.dk, "dv": s.dk,
            "taps": s.taps - 1, "channels": s.channels, "block": KDA_BLOCK}


def _bytes_per_weight(config: dict) -> float:
    return {"bfloat16": 2.0}[config["weights"]["dtype"]]


work = SimpleNamespace(
    bytes_per_weight=_bytes_per_weight, weight_bytes=weight_bytes,
    attention_bytes=attention_bytes, burst_counted_bytes=burst_counted_bytes,
    prefill_flops=prefill_flops, causal_pairs=causal_pairs,
    kda_decode_work=kda_decode_work, kda_prefill_work=kda_prefill_work,
    latent_attention_work=latent_attention_work, latent_prefill_work=latent_prefill_work,
    expert_bytes=lambda model, bpw: expert_params(model) * bpw, expert_op_sizes=expert_op_sizes,
    state_op_sizes=state_op_sizes, state_bytes=state_bytes)
