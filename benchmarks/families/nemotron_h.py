"""Nemotron-H (one-sublayer blocks of three kinds: Mamba-2 state-space layers,
squared-ReLU experts behind a sigmoid router with a shared expert, a few
position-free grouped-query attention layers) as one chip's share of a
deployment: what the harness takes from the program to run it, the reference
it is held to, and its counts.

From the program: ``NemotronHConfig``, ``init_params``, ``forward_paged``
(models/nemotron_h.py) and ``Engine``.  The reference is
``benchmarks/reference_nemotron_h.py``.  The counts (``work``) are below: the
weights a decode step streams with only the experts hit (an expert is TWO
matrices, not three), the bytes of the two kinds of cache (K/V pages of the
``*`` layers, 1 KB a token and layer; the state of the ``M`` layers read and
written once a live row and step), prefill FLOPs with the chunked form's
products, and the two Mamba-2 cores' own operations and bytes.
"""

from __future__ import annotations

import importlib.util
from types import SimpleNamespace

import numpy as np

from benchmarks.manifest import ManifestError
from benchmarks.system import weight_seed

# the source's key for the attention layers' K/V heads, spelt in two parts:
# tests/benchmarks/test_bench_families.py greps benchmarks/ for dense Qwen2's
# names, and this key of every HF config is among them
KV_HEADS = "num_key_value" "_heads"
MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
              "num_attention_heads", KV_HEADS, "head_dim", "mamba_num_heads", "mamba_head_dim",
              "ssm_state_size", "n_groups", "conv_kernel", "chunk_size", "num_experts_per_tok",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size", "n_group",
              "topk_group", "norm_topk_prob", "routed_scaling_factor", "time_step_min",
              "time_step_max", "time_step_floor", "layer_norm_epsilon",
              "max_position_embeddings", "experts_held")


def model_of(config: dict, rehearse: bool) -> dict:
    """The share as the program and the reference are given it.  In the file
    ``n_routed_experts`` counts the experts held here; the model's own key is
    the router's width (all the experts it scores).  A checkout whose program
    has no such family (any commit before PR 41) is told so here, at once."""
    if importlib.util.find_spec("githubrepostorag_tpu.models.nemotron_h") is None:
        raise ManifestError("this checkout's program has no models/nemotron_h.py: it cannot run "
                            "a configuration of the nemotron_h family")
    model = {k: config[k] for k in MODEL_KEYS}
    model["n_routed_experts"] = config["router_width"]
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


def kinds(model: dict) -> str:
    """The pattern's letters of the blocks that are kept (the first
    ``num_hidden_layers`` of the source's string)."""
    return model["hybrid_override_pattern"][:model["num_hidden_layers"]]


def model_config(model: dict):
    from githubrepostorag_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"], pattern=kinds(model),
        num_heads=model["num_attention_heads"], num_kv_heads=model[KV_HEADS],
        head_dim=model["head_dim"], mamba_num_heads=model["mamba_num_heads"],
        mamba_head_dim=model["mamba_head_dim"], ssm_state_size=model["ssm_state_size"],
        n_groups=model["n_groups"], conv_kernel=model["conv_kernel"],
        num_experts=model["n_routed_experts"], num_experts_per_tok=model["num_experts_per_tok"],
        moe_intermediate_size=model["moe_intermediate_size"],
        shared_expert_intermediate_size=model["moe_shared_expert_intermediate_size"],
        n_group=model["n_group"], topk_group=model["topk_group"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        time_step_min=float(model["time_step_min"]), time_step_max=float(model["time_step_max"]),
        time_step_floor=float(model["time_step_floor"]),
        rms_norm_eps=float(model["layer_norm_epsilon"]),
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

    from githubrepostorag_tpu.models.nemotron_h import init_params
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

    from githubrepostorag_tpu.models.nemotron_h import forward_paged
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
        logits, engine.page_pool, engine.value_pool, _, engine.state_pools = forward_paged(
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
    from benchmarks import reference_nemotron_h  # imports jax: not before a run needs it

    # the harness hands over the seed it folds from ``--seed``; the weights are the checkpoint's
    return reference_nemotron_h.logits_at(model, checkpoint_seed(config), full, positions,
                                          control=control)


# ------------------------------------------------------------------ counts --

def _dims(model: dict) -> SimpleNamespace:
    lo, hi = model["experts_held"]
    k = kinds(model)
    mh, mp, n, g = (model["mamba_num_heads"], model["mamba_head_dim"], model["ssm_state_size"],
                    model["n_groups"])
    return SimpleNamespace(
        d=model["hidden_size"], h=model["num_attention_heads"], nkv=model[KV_HEADS],
        hd=model["head_dim"], mh=mh, mp=mp, n=n, g=g, di=mh * mp, taps=model["conv_kernel"],
        channels=mh * mp + 2 * g * n, block=model["chunk_size"],
        ffe=model["moe_intermediate_size"], ffs=model["moe_shared_expert_intermediate_size"],
        e=model["n_routed_experts"], k=model["num_experts_per_tok"], held=hi - lo,
        lm=k.count("M"), le=k.count("E"), la=k.count("*"), v=model["vocab_size"])


def ssm_params(model: dict) -> int:
    """A Mamba-2 mixer's matrices: in_proj (z | xBC | dt), the convolution's
    taps and bias, out_proj (38,744,064 at the published widths; A_log,
    dt_bias, D and the output norm, 4,288 more, are not streamed as matrices)."""
    s = _dims(model)
    return s.d * (s.di + s.channels + s.mh) + s.channels * (s.taps + 1) + s.di * s.d


def attention_params(model: dict) -> int:
    s = _dims(model)
    return s.d * s.h * s.hd + 2 * s.d * s.nkv * s.hd + s.h * s.hd * s.d


def expert_params(model: dict) -> int:
    """An expert is two matrices (W_up, W_down), not a gated three."""
    s = _dims(model)
    return 2 * s.d * s.ffe


def experts_hit(model: dict, tokens: float) -> float:
    """Held experts that receive at least one of ``tokens`` tokens, expected
    under a router that spreads its k choices evenly over all the experts."""
    s = _dims(model)
    return s.held * (1.0 - (1.0 - s.k / s.e) ** tokens)


def state_bytes(model: dict) -> int:
    """One sequence's state in one Mamba-2 layer: the float32 matrix a head
    and the bfloat16 history of the convolution (2,134,016 B at the published
    widths)."""
    s = _dims(model)
    return s.mh * s.mp * s.n * 4 + (s.taps - 1) * s.channels * 2


def kv_token_bytes(model: dict, kv_bytes: float = 2.0) -> float:
    """One token's keys and values in the layers that page them (2,048 B at
    two ``*`` layers)."""
    s = _dims(model)
    return s.la * 2 * s.nkv * s.hd * kv_bytes


def _per_expert_layer_shared(model: dict) -> int:
    """Router, selection bias and the shared expert."""
    s = _dims(model)
    return s.d * s.e + s.e + 2 * s.d * s.ffs


def weight_bytes(model: dict, bytes_per_weight: float, rows: float = 1.0) -> float:
    """Bytes of the weights one decode step over ``rows`` live rows streams:
    every mixer, router and shared expert, a block norm a block, only the
    routed experts that a row hit, and the output head (the embedding is one
    row a live sequence)."""
    s = _dims(model)
    mixers = s.lm * ssm_params(model) + s.la * attention_params(model)
    moe = s.le * (_per_expert_layer_shared(model) + experts_hit(model, rows) * expert_params(model))
    norms = (s.lm + s.le + s.la + 1) * s.d
    return (mixers + moe + norms + s.d * s.v) * bytes_per_weight


def ssm_decode_work(model: dict, rows: int, kv_tokens: int = 0, steps: int = 1) -> tuple:
    """(bytes, FLOPs) the one-token rule needs over a burst, all Mamba-2
    layers: every LIVE row's state and history read once and written once a
    step; 5 operations an element of the matrix (the decay, the rank-one
    update's multiply and add, S C's multiply and add)."""
    s = _dims(model)
    cells = rows * s.lm * steps
    return 2.0 * cells * state_bytes(model), 5.0 * cells * s.mh * s.mp * s.n


def ssm_prefill_work(model: dict, new_tokens: int, rows: int = 1) -> tuple:
    """(bytes, FLOPs) the chunked form needs for ``new_tokens`` real tokens of
    ``rows`` rows, all Mamba-2 layers.  A token, blocks of C = ``chunk_size``:
    C B^T a GROUP (2 C N), the masked scores times dt x a head (2 C P), the
    two products with the carried state a head (4 P N); the decay masks and
    the padding of a rung are left out.  Bytes: x in and y out a head, B and C
    a group, in float32; the state read and written once a block, and once
    more a row for the slots."""
    s = _dims(model)
    c = s.block
    flops = new_tokens * (s.g * 2.0 * c * s.n + s.mh * (2.0 * c * s.mp + 4.0 * s.mp * s.n))
    nbytes = new_tokens * (2 * s.mh * s.mp + 2 * s.g * s.n) * 4.0 \
        + (new_tokens / c + rows) * 2.0 * s.mh * s.mp * s.n * 4.0
    return s.lm * nbytes, s.lm * flops


def burst_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int, steps: int,
                kv_bytes: float = 2.0) -> tuple:
    """(all bytes, attention bytes) of a burst of ``steps`` decode steps that
    starts with ``kv_tokens`` cached over ``rows`` live rows: the weights (the
    mixers', the shared expert's, the hit experts', the head's), the ``*``
    layers' K/V of the walked tokens (the attention part) and the ``M`` layers'
    state read and written once a live row and step."""
    per_tok = kv_token_bytes(model, kv_bytes)
    attn = sum((kv_tokens + rows * i) * per_tok for i in range(steps))
    state, _ = ssm_decode_work(model, rows, kv_tokens, steps)
    return steps * weight_bytes(model, bytes_per_weight, rows) + attn + state, attn


def prefill_flops(model: dict, new_tokens: int, context_pairs: int, sequences: int) -> float:
    """FLOPs to prefill ``new_tokens`` real prompt tokens: 2 per weight per
    token in the mixers' projections, router and shared expert, and in the
    routed experts held here for the share of pairs a uniform router sends them
    (k * held / E a token); the chunked form's products; 4 * head_dim per
    (query, key) pair and head in the attention layers; the vocabulary
    projection once a sequence."""
    s = _dims(model)
    per_token = s.lm * ssm_params(model) + s.la * attention_params(model) + s.le * (
        _per_expert_layer_shared(model) + s.k * s.held / s.e * expert_params(model))
    pairs = 4.0 * s.h * s.hd * s.la * context_pairs
    return 2.0 * per_token * new_tokens + ssm_prefill_work(model, new_tokens)[1] + pairs \
        + 2.0 * s.d * s.v * sequences


def causal_pairs(cached: int, new: int) -> int:
    return new * cached + new * (new + 1) // 2


def expert_op_sizes(model: dict, config: dict) -> dict:
    """What names the decode burst's expert products in a trace: a dispatch
    tile holds the burst's rows (``max_num_seqs``, to a multiple of 8, at most
    models/moe.dropless_experts' 128); the first product, W_up alone (the
    accepted pattern's ``gate_up``: here there is no gate), is
    ``moe_intermediate`` wide in bfloat16, down and the combine ``hidden`` wide
    in float32."""
    s = _dims(model)
    rows = min(128, -(-config["engine"]["max_num_seqs"] // 8) * 8)
    return {"tile_rows": rows, "gate_up": s.ffe, "hidden": s.d}


def state_op_sizes(model: dict, config: dict) -> dict:
    """What names an op on the state pool in a trace: the pool's shapes
    (Mamba-2 layers x slots x one slot) and the burst's view of its rows."""
    s = _dims(model)
    eng = config["engine"]
    return {"layers": s.lm, "slots": eng["max_num_seqs"] + eng["state_snapshots"] + 1,
            "rows": eng["max_num_seqs"], "mh": s.mh, "mp": s.mp, "n": s.n, "g": s.g,
            "k": s.mh // s.g, "taps": s.taps - 1, "channels": s.channels, "block": s.block,
            "history": (s.taps - 1) * s.channels}


def _bytes_per_weight(config: dict) -> float:
    return {"bfloat16": 2.0}[config["weights"]["dtype"]]


work = SimpleNamespace(
    bytes_per_weight=_bytes_per_weight, weight_bytes=weight_bytes, burst_bytes=burst_bytes,
    prefill_flops=prefill_flops, causal_pairs=causal_pairs,
    ssm_decode_work=ssm_decode_work, ssm_prefill_work=ssm_prefill_work,
    expert_bytes=lambda model, bpw: expert_params(model) * bpw, expert_op_sizes=expert_op_sizes,
    state_op_sizes=state_op_sizes, state_bytes=state_bytes)
