"""Sequence-parallel long-context prefill: the whole prompt in ONE device
program with ring attention over the ``sp`` mesh axis, K/V committed to the
paged pools.

The reference *avoids* long context (vLLM ``--max-model-len 11712`` plus a
truncation cascade — SURVEY.md §5.7); this path is what makes long prompts a
scaling axis instead of a cap.  Chunked prefill already bounds single-chip
memory, but its attention work is serial in the chunk count; here the
sequence axis is sharded over ``sp``: each device keeps its contiguous query
shard resident, K/V shards rotate around the ring over ICI
(parallel/ring_attention.py — ppermute + online softmax, exact causal), and
every layer's K/V shards are scattered into the page pools once at the end.
Decode then proceeds on the standard paged path, so a long-context request
is only special for its first step.

Logits are projected at the prompt's last token only: a full [1, S, V]
projection at S=32k is gigabytes of HBM for one row.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import (
    Qwen2Config,
    _block,
    _embed_dtype,
    _logits,
)
from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.rope import rope_cos_sin
from githubrepostorag_tpu.parallel.ring_attention import make_ring_attend


@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(4, 5))
def ring_prefill_packed(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,  # [1, Sp] int32, many prompts back to back
    positions: jnp.ndarray,  # [1, Sp] int32, restarting at 0 per segment
    k_pages: jnp.ndarray,  # [L, n_kv, P, page_size, hd] (donated)
    v_pages: jnp.ndarray,  # (donated)
    slot_mapping: jnp.ndarray,  # [1, Sp] int32 flat pool slots, -1 padding
    seg_ids: jnp.ndarray,  # [1, Sp] int32 segment ids; >= R marks padding
    logits_at: jnp.ndarray,  # [R] int32 — each segment's last-token index
    mesh,  # jax.sharding.Mesh with sp >= 1
    k_scales: jnp.ndarray | None = None,  # [L, n_kv, P] f32 — int8 pools'
    v_scales: jnp.ndarray | None = None,  # per-page scales (kv_quant)
):
    """Segment-packed ring prefill: one prompt or MANY, flattened back to
    back into one fixed-budget ring pass.  ``seg_ids`` confines attention to each
    prompt's own tokens (parallel/ring_attention.py rotates the kv-side ids
    with the K/V blocks), ``positions`` restart per segment so RoPE sees each
    prompt from 0, and every segment's K/V lands in its own pages through the
    shared flat-slot scatter.  ``logits_at`` picks each segment's last real
    token; rows past the live segment count point at index 0 and the caller
    ignores them.  Returns (logits [R, 1, V] float32, k_pages, v_pages,
    k_scales, v_scales); the scales are None unless the pools are int8
    (kv_quant), in which case the commit quantizes each page with the same
    first-write-fixes-the-scale rule as the chunked/burst paths
    (serving/kv_cache.commit_paged).  Padding tokens carry a segment id no
    real token shares, so they stay out of every real position's attention,
    and their K/V carry slot -1 (dropped by the scatter).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    hd = cfg.head_dim
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    total_slots = num_pages * page_size

    attend = make_ring_attend(
        mesh, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        segmented=True,
    )
    # pin the sequence axis onto sp so the dense program around the ring
    # (embeddings, QKV/MLP matmuls) shards the same way shard_map expects
    input_ids = jax.lax.with_sharding_constraint(
        input_ids, NamedSharding(mesh, P(None, "sp"))
    )
    seg_ids = jax.lax.with_sharding_constraint(
        seg_ids, NamedSharding(mesh, P(None, "sp"))
    )

    h = embedding_lookup(params["embed"], input_ids, dtype=_embed_dtype(params))
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)

    def body(h, layer_xs):
        (p,) = layer_xs
        # capture each layer's post-RoPE K/V as scan outputs — exactly what
        # the paged cache stores (models/qwen2.py forward_paged writes the
        # same tensors chunk by chunk)
        h, kv = _block(
            cfg, h, p, cos, sin,
            lambda q, k, v: (attend(q, k, v, seg_ids), (k, v)),
        )
        return h, kv

    h, (ks, vs) = jax.lax.scan(body, h, (params["layers"],))
    h = rms_norm(h, params["norm"], cfg.rms_norm_eps)
    # per-segment last-token hidden states, same gather as the packed chunked
    # path (models/qwen2.py forward_paged_packed)
    h_last = h[0, logits_at][:, None, :]  # [R, 1, d]
    logits = _logits(params, h_last)

    flat_slots = slot_mapping.reshape(-1)  # [Sp]
    # negative (padding) slots would WRAP in a JAX scatter; send them out of
    # range so mode="drop" discards them
    flat_slots = jnp.where(flat_slots < 0, total_slots, flat_slots)

    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    def commit(pools, stacked, scales):
        # stacked [L, 1, Sp, n_kv, hd] -> [L, n_kv, Sp, hd] matching the
        # flat [L, n_kv, P*ps, hd] pool view
        vals = stacked[:, 0].transpose(0, 2, 1, 3)
        return commit_paged(pools, vals, flat_slots, scales, page_size)

    k_pages, k_scales = commit(k_pages, ks, k_scales)
    v_pages, v_scales = commit(v_pages, vs, v_scales)
    # fixed arity: scales are None for full-precision pools — callers
    # unpack five values unconditionally
    return logits, k_pages, v_pages, k_scales, v_scales
