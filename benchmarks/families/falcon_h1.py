"""Falcon-H1 (every layer a Mamba-2 mixer AND rotary grouped-query attention
on one normed input, summed, then a dense SwiGLU; twelve muP multipliers) as
the first pipeline stage of a deployment: what the harness takes from the
program to run it, the reference it is held to, and its counts.

From the program: ``FalconH1Config``, ``init_params``, ``forward_paged``
(models/falcon_h1.py) and ``Engine``.  The reference is
``benchmarks/reference_falcon_h1.py``.  The counts (``work``) are below: the
weights a decode step streams (every layer whole: no experts), the bytes of the
TWO caches every layer keeps (K/V pages, 2 KB a token and layer; the state
read and written once a live row and step, 4.2 MB a layer), prefill FLOPs with
the chunked form's products, and the two Mamba-2 cores' own operations and
bytes at heads of 128 x 256.
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
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
               "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", KV_HEADS, "head_dim", "rope_theta", "mamba_d_ssm",
              "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
              "mamba_chunk_size", "rms_norm_eps", "max_position_embeddings", *MULTIPLIERS)


def model_of(config: dict, rehearse: bool) -> dict:
    """The stage as the program and the reference are given it.  A checkout
    whose program has no such family (any commit before PR 47) is told so
    here, at once."""
    if importlib.util.find_spec("githubrepostorag_tpu.models.falcon_h1") is None:
        raise ManifestError("this checkout's program has no models/falcon_h1.py: it cannot run "
                            "a configuration of the falcon_h1 family")
    model = {k: config[k] for k in MODEL_KEYS}
    if rehearse:
        model.update(config["rehearse"]["model"])
    return model


def model_config(model: dict):
    from githubrepostorag_tpu.models.falcon_h1 import FalconH1Config

    if model["mamba_d_ssm"] != model["mamba_n_heads"] * model["mamba_d_head"]:
        raise ManifestError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    return FalconH1Config(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"], num_kv_heads=model[KV_HEADS],
        head_dim=model["head_dim"], rope_theta=float(model["rope_theta"]),
        mamba_num_heads=model["mamba_n_heads"], mamba_head_dim=model["mamba_d_head"],
        ssm_state_size=model["mamba_d_state"], n_groups=model["mamba_n_groups"],
        conv_kernel=model["mamba_d_conv"], rms_norm_eps=float(model["rms_norm_eps"]),
        max_position_embeddings=model["max_position_embeddings"],
        **{k: (tuple(model[k]) if isinstance(model[k], list) else float(model[k]))
           for k in MULTIPLIERS})


def checkpoint_seed(config: dict) -> int:
    """The seed of the weights: the configuration's own, the same in every
    run (a deployment has one checkpoint).  ``--seed`` draws the traffic, the sampler's key and the
    correctness sample."""
    return weight_seed(config["weights"]["seed"])


def build_engine(config: dict, model: dict, needs: dict, seed: int):
    import jax

    from githubrepostorag_tpu.models.falcon_h1 import init_params
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

    from githubrepostorag_tpu.models.falcon_h1 import forward_paged
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
    from benchmarks import reference_falcon_h1  # imports jax: not before a run needs it

    # the harness hands over the seed it folds from ``--seed``; the weights are the checkpoint's
    return reference_falcon_h1.logits_at(model, checkpoint_seed(config), full, positions,
                                         control=control)


# ------------------------------------------------------------------ counts --

def _dims(model: dict) -> SimpleNamespace:
    mh, mp, n, g = (model["mamba_n_heads"], model["mamba_d_head"], model["mamba_d_state"],
                    model["mamba_n_groups"])
    return SimpleNamespace(
        d=model["hidden_size"], ff=model["intermediate_size"], layers=model["num_hidden_layers"],
        h=model["num_attention_heads"], nkv=model[KV_HEADS], hd=model["head_dim"], mh=mh, mp=mp,
        n=n, g=g, di=mh * mp, taps=model["mamba_d_conv"], channels=mh * mp + 2 * g * n,
        block=model["mamba_chunk_size"], v=model["vocab_size"])


def ssm_params(model: dict) -> int:
    """A Mamba-2 mixer: in_proj (z | xBC | dt), the convolution's taps and
    bias, out_proj, ``A_log``, ``dt_bias``, ``D`` and the gated norm
    (68,351,072 at the published widths)."""
    s = _dims(model)
    return (s.d * (s.di + s.channels + s.mh) + s.channels * (s.taps + 1) + s.di * s.d
            + 3 * s.mh + s.di)


def attention_params(model: dict) -> int:
    s = _dims(model)
    return s.d * s.h * s.hd + 2 * s.d * s.nkv * s.hd + s.h * s.hd * s.d


def mlp_params(model: dict) -> int:
    s = _dims(model)
    return 3 * s.d * s.ff


def layer_params(model: dict) -> int:
    """Both mixers, the SwiGLU and the two block norms (430,120,032)."""
    return (ssm_params(model) + attention_params(model) + mlp_params(model)
            + 2 * model["hidden_size"])


def total_params(model: dict) -> int:
    """The stage: its layers, the embedding, the head and the final norm."""
    s = _dims(model)
    return s.layers * layer_params(model) + 2 * s.v * s.d + s.d


def state_bytes(model: dict) -> int:
    """One sequence's state in one layer: the float32 matrix a head and the
    bfloat16 history of the convolution (4,225,024 B at the published widths)."""
    s = _dims(model)
    return s.mh * s.mp * s.n * 4 + (s.taps - 1) * s.channels * 2


def kv_token_bytes(model: dict, kv_bytes: float = 2.0) -> float:
    """One token's keys and values in every layer (2,048 B a layer)."""
    s = _dims(model)
    return s.layers * 2 * s.nkv * s.hd * kv_bytes


def weight_bytes(model: dict, bytes_per_weight: float, rows: float = 1.0) -> float:
    """Bytes of the weights one decode step streams: every layer whole, the
    final norm and the output head (the embedding is one row a live sequence)."""
    s = _dims(model)
    return (s.layers * layer_params(model) + s.d + s.d * s.v) * bytes_per_weight


def ssm_decode_work(model: dict, rows: int, kv_tokens: int = 0, steps: int = 1) -> tuple:
    """(bytes, FLOPs) the one-token rule needs over a burst, all layers: every
    LIVE row's matrix read once and written once a step (4,194,304 B each way
    a row and layer: the kernel moves nothing else of that size; the history
    is the convolution's, outside the rule); 5 operations an element of the
    matrix (the decay, the rank-one update's multiply and add, S C's multiply
    and add)."""
    s = _dims(model)
    cells = rows * s.layers * steps
    matrix = s.mh * s.mp * s.n
    return 2.0 * cells * matrix * 4, 5.0 * cells * matrix


def ssm_prefill_work(model: dict, new_tokens: int, rows: int = 1) -> tuple:
    """(bytes, FLOPs) the chunked form needs for ``new_tokens`` real tokens of
    ``rows`` rows, all layers.  A token, blocks of C = ``mamba_chunk_size``:
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
    return s.layers * nbytes, s.layers * flops


def burst_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int, steps: int,
                kv_bytes: float = 2.0) -> tuple:
    """(all bytes, attention bytes) of a burst of ``steps`` decode steps that
    starts with ``kv_tokens`` cached over ``rows`` live rows: the weights, the
    K/V of the walked tokens in every layer (the attention part) and every
    layer's state and history read and written once a live row and step."""
    s = _dims(model)
    per_tok = kv_token_bytes(model, kv_bytes)
    attn = sum((kv_tokens + rows * i) * per_tok for i in range(steps))
    state = 2.0 * rows * s.layers * steps * state_bytes(model)
    return steps * weight_bytes(model, bytes_per_weight, rows) + attn + state, attn


def prefill_flops(model: dict, new_tokens: int, context_pairs: int, sequences: int) -> float:
    """FLOPs to prefill ``new_tokens`` real prompt tokens: 2 per weight per
    token in both mixers' projections and the SwiGLU; the chunked form's
    products; 4 * head_dim per (query, key) pair and head in every layer; the
    vocabulary projection once a sequence."""
    s = _dims(model)
    matrices = s.layers * (ssm_params(model) - 3 * s.mh - s.di + attention_params(model)
                           + mlp_params(model))
    pairs = 4.0 * s.h * s.hd * s.layers * context_pairs
    return 2.0 * matrices * new_tokens + ssm_prefill_work(model, new_tokens)[1] + pairs \
        + 2.0 * s.d * s.v * sequences


def causal_pairs(cached: int, new: int) -> int:
    return new * cached + new * (new + 1) // 2


def state_op_sizes(model: dict, config: dict) -> dict:
    """What names an op on the state pool or of the chunked form in a trace:
    the pool's shapes (layers x slots x one slot) and the burst's view of its
    rows."""
    s = _dims(model)
    eng = config["engine"]
    return {"layers": s.layers, "slots": eng["max_num_seqs"] + eng["state_snapshots"] + 1,
            "rows": eng["max_num_seqs"], "mh": s.mh, "mp": s.mp, "n": s.n, "g": s.g,
            "k": s.mh // s.g, "taps": s.taps - 1, "channels": s.channels, "block": s.block,
            "history": (s.taps - 1) * s.channels}


def _bytes_per_weight(config: dict) -> float:
    return {"bfloat16": 2.0}[config["weights"]["dtype"]]


work = SimpleNamespace(
    bytes_per_weight=_bytes_per_weight, weight_bytes=weight_bytes, burst_bytes=burst_bytes,
    prefill_flops=prefill_flops, causal_pairs=causal_pairs,
    ssm_decode_work=ssm_decode_work, ssm_prefill_work=ssm_prefill_work,
    state_op_sizes=state_op_sizes, state_bytes=state_bytes)
