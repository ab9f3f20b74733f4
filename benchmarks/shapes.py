"""Operations and bytes the algorithm needs, from shapes alone.

Only work counts: the tokens really prefilled (no padding to the 512-wide
chunk, no row-bucket padding) and the bytes a step must read once (weights
once per step, the live context's K and V once per step).  ``model`` is the
configuration file's ``model`` dict (HF config.json keys).
"""

from __future__ import annotations


def _dims(model: dict) -> tuple:
    d = model["hidden_size"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // nq
    return d, nq, nkv, hd, model["intermediate_size"], model["num_hidden_layers"], \
        model["vocab_size"]


def layer_matmul_params(model: dict) -> int:
    """Weights of one layer's projections (q, k, v, o, gate, up, down)."""
    d, nq, nkv, hd, inter, _, _ = _dims(model)
    return d * (nq + 2 * nkv) * hd + nq * hd * d + 3 * d * inter


def prefill_flops(model: dict, new_tokens: int, context_pairs: int, sequences: int) -> float:
    """FLOPs to prefill ``new_tokens`` real prompt tokens: 2 per weight per
    token in every projection, 4 * head_dim per (query, key) pair per query
    head for scores and values, and the vocabulary projection once per
    sequence (the engine projects only each prompt's last position).
    ``context_pairs`` is the sum over new tokens of the keys each attends to
    (its own position included)."""
    d, nq, _, hd, _, L, v = _dims(model)
    dense = 2.0 * layer_matmul_params(model) * L * new_tokens
    attn = 4.0 * hd * nq * L * context_pairs
    head = 2.0 * d * v * sequences
    return dense + attn + head


def causal_pairs(cached: int, new: int) -> int:
    """Keys attended by ``new`` tokens appended after ``cached`` ones."""
    return new * cached + new * (new + 1) // 2


def weight_bytes(model: dict, bytes_per_weight: float, scale_bytes: int = 2) -> float:
    """Bytes of the weights one decode step streams: every layer's
    projections, norms and per-channel scales, and the output head.  The
    embedding table is not streamed (one row per live sequence)."""
    d, nq, nkv, hd, inter, L, v = _dims(model)
    per_layer = layer_matmul_params(model) * bytes_per_weight + 2 * d * 2
    if bytes_per_weight < 2:  # quantised: one scale per output channel
        per_layer += ((nq + 2 * nkv) * hd + d + 2 * inter + d) * scale_bytes
    head = d * v * bytes_per_weight + (v * scale_bytes if bytes_per_weight < 2 else 0)
    return L * per_layer + head + d * 2


def kv_bytes_per_token(model: dict, kv_bytes: float = 2.0) -> float:
    """K and V of one token over all layers."""
    _, _, nkv, hd, _, L, _ = _dims(model)
    return 2.0 * L * nkv * hd * kv_bytes


def decode_step_bytes(model: dict, bytes_per_weight: float, kv_tokens: int,
                      kv_bytes: float = 2.0) -> float:
    """Bytes one decode step must read: the weights once and the K/V of the
    live context (``kv_tokens`` summed over the live rows)."""
    return weight_bytes(model, bytes_per_weight) + kv_tokens * kv_bytes_per_token(model, kv_bytes)


def burst_bytes(model: dict, bytes_per_weight: float, rows: int, kv_tokens: int, steps: int,
                kv_bytes: float = 2.0) -> tuple:
    """(all bytes, attention bytes) of a burst of ``steps`` decode steps that
    starts with ``kv_tokens`` cached over ``rows`` live rows: each step reads
    the weights once and a context one token longer per row."""
    per_tok = kv_bytes_per_token(model, kv_bytes)
    attn = sum((kv_tokens + rows * s) * per_tok for s in range(steps))
    return steps * weight_bytes(model, bytes_per_weight) + attn, attn
