"""Olmo-Hybrid (Gated DeltaNet layers with a full multi-head attention layer
among every four, a dense SwiGLU after either) as the first of four pipeline
stages: what the harness takes from the program to run it, the reference it is
held to, and its counts.

From the program: ``OlmoHybridConfig``, ``init_params``, ``forward_paged``
(models/olmo_hybrid.py) and ``Engine``.  The reference is
``benchmarks/reference_olmo_hybrid.py``.  The counts (``work``) are below: the
weights a decode step streams (all of them but the embedding: the model is
dense), the bytes of the two kinds of cache (K/V pages of the attention layers,
multi-head: 15,360 B a token and layer; the state of the Gated DeltaNet layers
read and written once a step), prefill FLOPs with the chunked rule's products
at key heads of 96 and value heads of 192, and the two Gated DeltaNet cores'
own operations and bytes.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from benchmarks.system import weight_seed

# the source's key for the attention layers' K/V heads, spelt in two parts:
# tests/benchmarks/test_bench_families.py greps benchmarks/ for dense Qwen2's
# names, and this key of every HF config is among them
KV_HEADS = "num_key_value" "_heads"
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "layer_types", "num_attention_heads", KV_HEADS, "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
              "linear_conv_kernel_dim", "linear_allow_neg_eigval", "rms_norm_eps",
              "max_position_embeddings")
GDN_BLOCK = 64  # tokens of a block of the chunked rule (ops/gated_delta.BLOCK)


def model_of(config: dict, rehearse: bool) -> dict:
    """The model's keys as the program and the reference are given them."""
    model = {k: config[k] for k in MODEL_KEYS}
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


def interval_of(model: dict) -> int:
    """Layers of one period of ``layer_types`` (linear_attention ..., then one
    full_attention), which the kept layers must repeat whole."""
    kinds = list(model["layer_types"][:model["num_hidden_layers"]])
    interval = kinds.index("full_attention") + 1
    period = ["linear_attention"] * (interval - 1) + ["full_attention"]
    if len(kinds) % interval or kinds != period * (len(kinds) // interval):
        raise SystemExit(f"layer_types {kinds}: not whole periods of {period}")
    return interval


def model_config(model: dict):
    from githubrepostorag_tpu.models.olmo_hybrid import OlmoHybridConfig

    return OlmoHybridConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=model["num_hidden_layers"],
        full_attention_interval=interval_of(model), num_heads=model["num_attention_heads"],
        num_kv_heads=model[KV_HEADS],
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        linear_num_key_heads=model["linear_num_key_heads"],
        linear_num_value_heads=model["linear_num_value_heads"],
        linear_key_head_dim=model["linear_key_head_dim"],
        linear_value_head_dim=model["linear_value_head_dim"],
        linear_conv_kernel_dim=model["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(model["linear_allow_neg_eigval"]),
        rms_norm_eps=float(model["rms_norm_eps"]),
        max_position_embeddings=model["max_position_embeddings"])


def checkpoint_seed(config: dict) -> int:
    """The seed of the weights: the configuration's own, the same in every
    run, as a deployment has one checkpoint.  ``--seed`` draws the traffic,
    the sampler's key and the correctness sample."""
    return weight_seed(config["weights"]["seed"])


def build_engine(config: dict, model: dict, needs: dict, seed: int):
    import jax

    from githubrepostorag_tpu.models.olmo_hybrid import init_params
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


def prefill_logits(engine, seqs: list) -> np.ndarray:
    """Next-token logits [K, V] from the engine's prefill program on the
    engine's weights, K/V pools and state pool, chunk by chunk as the engine
    dispatches it: every chunk after the first attends a cached prefix and
    resumes the state the chunk before left in its row's slot.  Pages are taken
    from the top of the pool and the rows' slots without asking their ledgers,
    so this runs last: neither cache is valid afterwards."""
    import jax.numpy as jnp

    from githubrepostorag_tpu.models.olmo_hybrid import forward_paged
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
        logits, engine.page_pool, engine.value_pool, engine.state_pools = forward_paged(
            engine.params, engine.cfg, jnp.asarray(ids), jnp.asarray(pos2), engine.page_pool,
            engine.value_pool, jnp.asarray(slots), jnp.asarray(bt), jnp.asarray(cached),
            jnp.asarray(lens), use_pallas=engine.use_pallas,
            logits_at=jnp.asarray(np.maximum(lens - 1, 0)), state=engine.state_pools,
            state_src=jnp.asarray(src), state_dst=jnp.asarray(dst),
            state_snap=jnp.full((rb,), trash, jnp.int32), snap_col=jnp.zeros((rb,), jnp.int32))
        got = np.asarray(logits[:, 0], np.float32)
        for i, s in enumerate(seqs):
            if start < len(s) <= start + w:
                out[i] = got[i]
    return out


def reference_logits_at(config: dict, model: dict, wseed: int, full: list, positions: list,
                        control: str | None = None) -> list:
    from benchmarks import reference_olmo_hybrid  # imports jax: not before a run needs it

    # the harness hands over the seed it folds from ``--seed``; the weights are the checkpoint's
    return reference_olmo_hybrid.logits_at(model, checkpoint_seed(config), full, positions,
                                           control=control)


# ------------------------------------------------------------------ counts --

def _dims(model: dict) -> SimpleNamespace:
    interval, layers = interval_of(model), model["num_hidden_layers"]
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    h = model["num_attention_heads"]
    return SimpleNamespace(
        d=model["hidden_size"], h=h, nkv=model[KV_HEADS], hd=model["hidden_size"] // h,
        hk=hk, hv=hv, dk=dk, dv=dv, taps=model["linear_conv_kernel_dim"],
        channels=2 * hk * dk + hv * dv, ff=model["intermediate_size"], layers=layers,
        la=layers // interval, lg=layers // interval * (interval - 1), v=model["vocab_size"])


def gdn_params(model: dict) -> int:
    """A Gated DeltaNet mixer's matrices: W_q, W_k, W_v, W_g, W_a, W_b, the
    convolution's taps, W_o (88,750,080 at the published widths; A_log,
    dt_bias and the output norm, 252 more, are not streamed as matrices)."""
    s = _dims(model)
    return s.d * (2 * s.hk * s.dk + 2 * s.hv * s.dv) + s.d * 2 * s.hv + s.channels * s.taps \
        + s.hv * s.dv * s.d


def attention_params(model: dict) -> int:
    """W_q, W_k, W_v, W_o (58,982,400; the two norms of 3,840 apart)."""
    s = _dims(model)
    return s.d * s.h * s.hd + 2 * s.d * s.nkv * s.hd + s.h * s.hd * s.d


def mlp_params(model: dict) -> int:
    s = _dims(model)
    return 3 * s.d * s.ff


def state_bytes(model: dict) -> int:
    """One sequence's state in one Gated DeltaNet layer: the float32 matrix a
    head and the bfloat16 history of the convolution (2,280,960 B at the
    published widths)."""
    s = _dims(model)
    return s.hv * s.dk * s.dv * 4 + (s.taps - 1) * s.channels * 2


def kv_token_bytes(model: dict, kv_bytes: float = 2.0) -> float:
    """One token's keys and values in the layers that page them (30,720 B at
    two full-attention layers)."""
    s = _dims(model)
    return s.la * 2 * s.nkv * s.hd * kv_bytes


def weight_bytes(model: dict, bytes_per_weight: float, rows: float = 1.0) -> float:
    """Bytes of the weights one decode step streams, whatever its rows: every
    mixer, every MLP, the block norms and the output head (the embedding is
    one row a live sequence)."""
    s = _dims(model)
    mixers = s.lg * gdn_params(model) + s.la * (attention_params(model) + 2 * s.h * s.hd)
    return (mixers + s.layers * (mlp_params(model) + 2 * s.d) + s.d * s.v) * bytes_per_weight


def gdn_decode_work(model: dict, rows: int, kv_tokens: int = 0, steps: int = 1) -> tuple:
    """(bytes, FLOPs) the one-token rule needs over a burst, all Gated DeltaNet
    layers: every live row's state and history read once and written once a
    step; 6 operations an element of the matrix (decay, S^T k, the rank-one
    update, S^T q)."""
    s = _dims(model)
    cells = rows * s.lg * steps
    return 2.0 * cells * state_bytes(model), 6.0 * cells * s.hv * s.dk * s.dv


def gdn_prefill_work(model: dict, new_tokens: int, rows: int = 1) -> tuple:
    """(bytes, FLOPs) the chunked rule needs for ``new_tokens`` real tokens of
    ``rows`` rows, all Gated DeltaNet layers.  A head and token, blocks of C =
    64: K K^T, Q K^T and W = inv K (2 C dk each), U = inv V and Q K^T V_new
    (2 C dv each), the three products with the carried state and K^T V_new
    (8 dk dv); the triangular inverse itself (C^2 a token) is left out, as is
    the padding of a rung.  Bytes: q, k in and v in, o out in float32, the
    state read and written once a block, and once more a row for the slots."""
    s = _dims(model)
    c = GDN_BLOCK
    flops = new_tokens * s.hv * (2.0 * c * (3 * s.dk + 2 * s.dv) + 8.0 * s.dk * s.dv)
    nbytes = new_tokens * s.hv * (2 * s.dk + 2 * s.dv) * 4.0 \
        + (new_tokens / c + rows) * 2.0 * s.hv * s.dk * s.dv * 4.0
    return s.lg * nbytes, s.lg * flops


def burst_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int, steps: int,
                kv_bytes: float = 2.0) -> tuple:
    """(all bytes, attention bytes) of a burst of ``steps`` decode steps that
    starts with ``kv_tokens`` cached over ``rows`` live rows: all the weights
    but the embedding, the paged layers' K/V of the walked tokens (the
    attention part) and the Gated DeltaNet layers' state read and written once
    a live row and step."""
    per_tok = kv_token_bytes(model, kv_bytes)
    attn = sum((kv_tokens + rows * i) * per_tok for i in range(steps))
    state, _ = gdn_decode_work(model, rows, kv_tokens, steps)
    return steps * weight_bytes(model, bytes_per_weight, rows) + attn + state, attn


def prefill_flops(model: dict, new_tokens: int, context_pairs: int, sequences: int) -> float:
    """FLOPs to prefill ``new_tokens`` real prompt tokens: 2 per weight per
    token in the mixers' projections and the MLPs; the chunked rule's products;
    4 * head_dim per (query, key) pair and head in the attention layers; the
    vocabulary projection once a sequence."""
    s = _dims(model)
    per_token = s.lg * gdn_params(model) + s.la * attention_params(model) \
        + s.layers * mlp_params(model)
    pairs = 4.0 * s.h * s.hd * s.la * context_pairs
    return 2.0 * per_token * new_tokens + gdn_prefill_work(model, new_tokens)[1] + pairs \
        + 2.0 * s.d * s.v * sequences


def causal_pairs(cached: int, new: int) -> int:
    return new * cached + new * (new + 1) // 2


def state_op_sizes(model: dict, config: dict) -> dict:
    """What names an op on the state pool in a trace: the pool's shapes (Gated
    DeltaNet layers x slots x one slot) and the burst's view of its rows."""
    s = _dims(model)
    eng = config["engine"]
    return {"layers": s.lg, "slots": eng["max_num_seqs"] + eng["state_snapshots"] + 1,
            "rows": eng["max_num_seqs"], "hv": s.hv, "dk": s.dk, "dv": s.dv,
            "taps": s.taps - 1, "channels": s.channels, "block": GDN_BLOCK,
            "history": (s.taps - 1) * s.channels}


def _bytes_per_weight(config: dict) -> float:
    return {"bfloat16": 2.0}[config["weights"]["dtype"]]


work = SimpleNamespace(
    bytes_per_weight=_bytes_per_weight, weight_bytes=weight_bytes, burst_bytes=burst_bytes,
    prefill_flops=prefill_flops, causal_pairs=causal_pairs,
    gdn_decode_work=gdn_decode_work, gdn_prefill_work=gdn_prefill_work,
    state_op_sizes=state_op_sizes, state_bytes=state_bytes)
