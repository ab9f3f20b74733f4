"""Dense grouped-query attention (the reference path the Pallas paged kernel
is validated against, and the prefill path of the serving engine).

GQA is computed with a grouped einsum — Q heads are reshaped to
[n_kv, group] so K/V are never materialized repeated across the group, which
matters on TPU where HBM bandwidth is the bottleneck.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def dense_attention(
    q: jnp.ndarray,  # [B, Sq, n_q, hd]
    k: jnp.ndarray,  # [B, Sk, n_kv, hd]
    v: jnp.ndarray,  # [B, Sk, n_kv, hd]
    *,
    causal: bool = True,
    q_offset: jnp.ndarray | int = 0,
    kv_lengths: jnp.ndarray | None = None,  # [B] valid kv length per seq
    kv_valid: jnp.ndarray | None = None,  # [B, Sk] bool — arbitrary validity
    sliding: int | None = None,  # a query at position p sees keys p - sliding < j <= p
) -> jnp.ndarray:
    """Scaled-dot-product attention with causal masking and GQA.

    ``q_offset`` is the absolute position of q's first token within the kv
    sequence (decode: Sk-1 for a single new token; chunked prefill: the chunk
    start).  ``kv_lengths`` masks right-padded kv entries per batch row;
    ``kv_valid`` masks arbitrary kv entries (the decode burst's
    pool-prefix + staged-tail layout, where validity isn't a prefix).
    Returns [B, Sq, n_q, hd] in q.dtype; softmax in float32.
    """
    b, sq, n_q, hd = q.shape
    _, sk, n_kv, _ = k.shape
    group = n_q // n_kv
    scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(b, sq, n_kv, group, hd)
    # [B, n_kv, g, Sq, Sk]
    scores = jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale

    kv_pos = jnp.arange(sk)
    mask = jnp.zeros((b, 1, 1, sq, sk), dtype=bool)
    if causal:
        q_pos = jnp.arange(sq) + jnp.asarray(q_offset).reshape(-1, 1)  # [B or 1, Sq]
        causal_mask = kv_pos[None, None, :] > q_pos[:, :, None]  # [B or 1, Sq, Sk]
        mask = mask | causal_mask[:, None, None, :, :]
        if sliding is not None:
            behind = kv_pos[None, None, :] <= q_pos[:, :, None] - sliding
            mask = mask | behind[:, None, None, :, :]
    if kv_lengths is not None:
        pad_mask = kv_pos[None, :] >= kv_lengths[:, None]  # [B, Sk]
        mask = mask | pad_mask[:, None, None, None, :]
    if kv_valid is not None:
        mask = mask | (~kv_valid)[:, None, None, None, :]
    scores = jnp.where(mask, NEG_INF, scores)

    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, n_q, hd).astype(q.dtype)
