"""Paged attention over the page-pool KV cache.

Two implementations with one contract:
  - ``paged_attention_ref`` — gather the sequence's pages into a contiguous
    [B, S_max] view and run dense attention.  Always correct, materializes
    the gathered KV in HBM; used on CPU tests and as the Pallas kernel's
    oracle.
  - ``paged_attention`` (ops/pallas_paged.py) — the TPU kernel: walks the
    block table page by page in VMEM with an online-softmax accumulator, so
    nothing is materialized.  Falls back to the reference path off-TPU.

Contract (both): q for ONE new-token step per row plus optional chunk width:
  q            [B, S, n_q, hd]  — new queries (right-padded per row)
  k_pages      [n_kv, P, page_size, hd] — this layer's pool
  v_pages      [n_kv, P, page_size, hd]
  block_tables [B, max_pages]   int32 — page ids per row
  cached_lens  [B] int32        — tokens already in cache BEFORE this step
  new_lens     [B] int32        — valid new tokens this step (<= S)
Returns [B, S, n_q, hd].  Rows attend to their cache prefix plus the causal
part of the new chunk; padded queries/kv are masked.
"""

from __future__ import annotations

import jax.numpy as jnp

from githubrepostorag_tpu.ops.attention import dense_attention


def gather_kv(k_pages, v_pages, block_tables, k_scales=None, v_scales=None,
              dtype=None):
    """[n_kv, P, ps, hd] + [B, max_pages] -> [B, max_pages*ps, n_kv, hd].

    With ``k_scales``/``v_scales`` ([n_kv, P] per-PAGE dequant scales,
    kv_quant pools — kv_cache.quantize_kv_paged) the gathered quantized
    pages dequantize to ``dtype`` (default bf16) on the way out.  uint8
    pools are nibble-packed int4 (kv_cache.pack_int4): the gathered bytes
    unpack to the full head width before the scale multiply, so this stays
    the bit-exact oracle for the fused kernel's in-register dequant."""
    b, max_pages = block_tables.shape
    n_kv, _, ps, hd_store = k_pages.shape

    def gather(pages, scales):
        g = pages[:, block_tables]  # [n_kv, B, max_pages, ps, hd_store]
        g = jnp.moveaxis(g, 0, 3)  # [B, max_pages, ps, n_kv, hd_store]
        if pages.dtype == jnp.uint8:
            from githubrepostorag_tpu.serving.kv_cache import unpack_int4

            g = unpack_int4(g)  # [..., hd_store] uint8 -> [..., hd] int8
        g = g.reshape(b, max_pages * ps, n_kv, g.shape[-1])
        if scales is None:
            return g
        s = jnp.moveaxis(scales[:, block_tables], 0, 2)  # [B, mp, n_kv]
        s = jnp.repeat(s, ps, axis=1)  # page scale -> its ps token rows
        return (g.astype(jnp.float32) * s[..., None]).astype(dtype or jnp.bfloat16)

    return gather(k_pages, k_scales), gather(v_pages, v_scales)


def paged_attention_ref(q, k_pages, v_pages, block_tables, cached_lens, new_lens,
                        k_scales=None, v_scales=None, sliding=None):
    """``sliding``: the window of a sliding layer, in keys: the query at
    position p sees the keys ``p - sliding < j <= p`` and no older one (its
    table's entries for pages wholly behind every window are never looked at:
    whatever page they name is masked)."""
    k, v = gather_kv(k_pages, v_pages, block_tables, k_scales, v_scales,
                     dtype=q.dtype)
    # The new tokens are already scattered into the pages before attention,
    # so the valid kv length is cached + new.
    return dense_attention(
        q,
        k,
        v,
        causal=True,
        q_offset=cached_lens,
        kv_lengths=cached_lens + new_lens,
        sliding=sliding,
    )
