"""Segment-ID packed prefill attention over the page-pool KV cache.

The padded prefill dispatch ([row_bucket, width] with every row padded to
the widest pending chunk) burns compute on padding whenever a wave is
heterogeneous — short uncached suffixes after prefix-cache hits, tail
chunks, mixed prompt lengths.  The packed path flattens every prefilling
row's next chunk into ONE fixed-size [budget] token buffer with per-token
segment IDs, so dense-layer FLOPs (projections/MLP — the bulk of prefill
compute) scale with real tokens instead of rows x max-chunk.

Attention itself still needs per-segment causal structure, so the op
internally re-pads the packed queries to a segment-major [R, tq] view
(scatter by ``seg_ids * tq + in_chunk_index``; tq = the static per-segment
chunk cap) and masks with each segment's cached/new lengths:

  - XLA reference path: gather the block-table pages to a contiguous view
    and run ``dense_attention`` — exactly the padded path's oracle, so
    parity with ``paged_attention_ref`` is structural.
  - Pallas path: a flash-prefill kernel that walks the block table page by
    page in VMEM with an online-softmax accumulator, computing the causal
    mask from the scalar-prefetched cached/total lengths.  Nothing is
    materialized in HBM — at 1k-2k-token prompts the per-layer
    [R, max_pages*ps, n_kv, hd] gather is the dominant HBM cost of the
    reference path.

Contract:
  q            [T, n_q, hd]    — packed new-token queries (T = token budget)
  k_pages      [n_kv, P, page_size, hd] — this layer's pool (post-commit:
               the packed chunk's K/V are already scattered in)
  v_pages      [n_kv, P, page_size, hd]
  block_tables [R, max_pages] int32 — page ids per segment
  cached_lens  [R] int32 — tokens in cache BEFORE this chunk, per segment
  new_lens     [R] int32 — valid new tokens this chunk, per segment
  seg_ids      [T] int32 — owning segment per packed token; >= R marks
               padding tokens (they drop out of the segment view)
  positions    [T] int32 — absolute sequence position per packed token
               (token t sits at in-chunk index positions[t] -
               cached_lens[seg_ids[t]], always < tq)
Returns [T, n_q, hd] in q.dtype.  Padding tokens get finite garbage —
their K/V never committed (slot -1) and their logits are never read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from githubrepostorag_tpu.ops.attention import dense_attention
from githubrepostorag_tpu.ops.paged_attention import gather_kv
from githubrepostorag_tpu.runtime import on_tpu

NEG_INF = -1e30


def _segment_scatter_indices(seg_ids, positions, cached_lens, tq):
    """Destination row in the segment-major [R*tq] view for every packed
    token.  Padding tokens (seg >= R) map to the out-of-range sentinel
    R*tq, which a mode="drop" scatter discards (JAX scatter *wraps*
    negative indices, so the sentinel must be explicit and positive)."""
    r = cached_lens.shape[0]
    cached_ext = jnp.concatenate(
        [cached_lens.astype(jnp.int32), jnp.zeros((1,), jnp.int32)]
    )
    seg_c = jnp.minimum(seg_ids, r)
    in_chunk = positions - cached_ext[seg_c]
    return jnp.where(seg_ids >= r, r * tq, seg_c * tq + in_chunk)


def _packed_prefill_kernel(
    # scalar prefetch
    block_tables_ref,  # [R, max_pages] SMEM
    cached_lens_ref,  # [R] SMEM
    total_lens_ref,  # [R] SMEM
    # blocks
    q_ref,  # [1, 1, group, tq, hd] VMEM (one segment, one kv head)
    k_ref,  # [1, 1, page_size, hd] VMEM (one page, one kv head)
    v_ref,  # [1, 1, page_size, hd] VMEM
    out_ref,  # [1, 1, group, tq, hd] VMEM
    # scratch
    m_ref,  # [group, tq, 128] f32
    l_ref,  # [group, tq, 128] f32
    acc_ref,  # [group, tq, hd] f32
    *,
    page_size: int,
    scale: float,
):
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    num_pi = pl.num_programs(2)

    @pl.when(pi == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cached = cached_lens_ref[bi]  # chunk start == each q row's base position
    total = total_lens_ref[bi]  # valid kv length for this segment
    page_start = pi * page_size

    @pl.when(page_start < total)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)  # [group, tq, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [page_size, hd]
        v = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [group, tq, page_size]
        # causal within the segment: q row ti sits at absolute position
        # cached + ti; kv beyond the segment's valid length is padding
        kv_pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        q_pos = cached + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kv_pos <= q_pos) & (kv_pos < total), s, NEG_INF)

        m_prev = m_ref[:, :, :1]  # [group, tq, 1]
        l_prev = l_ref[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [group, tq, page_size]
        l_ref[:, :, :1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, :, :1] = m_new

    @pl.when(pi == num_pi - 1)
    def _():
        # bucket-padding segments (total == 0) never hit the accumulate
        # branch; guard the 0/0
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_ref[...] / safe_l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def packed_prefill_attention_seg(
    q_seg: jnp.ndarray,  # [R, tq, n_q, hd] segment-major queries
    k_pages: jnp.ndarray,  # [n_kv, P, page_size, hd]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [R, max_pages]
    cached_lens: jnp.ndarray,  # [R]
    new_lens: jnp.ndarray,  # [R]
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas flash-prefill over the segment-major view: grid
    (R, n_kv, max_pages), one page's K/V slab in VMEM per step, online
    softmax across the page walk.  Matches ``dense_attention`` over the
    gathered pages (the reference path below) bit-for-bit in structure."""
    r, tq, n_q, hd = q_seg.shape
    n_kv, num_pages, page_size, _ = k_pages.shape
    group = n_q // n_kv
    max_pages = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)

    total_lens = (cached_lens + new_lens).astype(jnp.int32)
    # [R, tq, n_kv, group, hd] -> [R, n_kv, group, tq, hd]: one kv head's
    # whole query group rides each grid step's MXU dots
    q_r = q_seg.reshape(r, tq, n_kv, group, hd).transpose(0, 2, 3, 1, 4)

    grid = (r, n_kv, max_pages)

    def q_map(bi, hi, pi, bt, cl, tl):
        return (bi, hi, 0, 0, 0)

    def kv_map(bi, hi, pi, bt, cl, tl):
        # Clamp the walk to allocated pages: beyond the segment's length
        # the kernel skips compute, so any valid page id works — page 0.
        page = jax.lax.select(pi * page_size < tl[bi], bt[bi, pi], 0)
        return (hi, page, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, tq, hd), q_map),
            pl.BlockSpec((1, 1, page_size, hd), kv_map),
            pl.BlockSpec((1, 1, page_size, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, tq, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, tq, 128), jnp.float32),
            pltpu.VMEM((group, tq, 128), jnp.float32),
            pltpu.VMEM((group, tq, hd), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _packed_prefill_kernel, page_size=page_size, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n_kv, group, tq, hd), q_seg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), cached_lens.astype(jnp.int32),
      total_lens, q_r, k_pages, v_pages)

    # [R, n_kv, group, tq, hd] -> [R, tq, n_q, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(r, tq, n_q, hd)


def packed_prefill_attention(
    q: jnp.ndarray,  # [T, n_q, hd] packed queries
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [R, max_pages]
    cached_lens: jnp.ndarray,  # [R]
    new_lens: jnp.ndarray,  # [R]
    seg_ids: jnp.ndarray,  # [T]
    positions: jnp.ndarray,  # [T]
    *,
    tq: int,  # static per-segment chunk cap (min(prefill_chunk, budget))
    use_pallas: bool = False,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Packed-buffer entry point (see module docstring for the contract).

    Scatters the packed queries into the segment-major [R, tq] view, runs
    segment-masked attention there (Pallas when ``use_pallas``: the seg
    kernel for full-precision pools, the fused window kernel
    (ops/fused_decode.py) for quantized pools — int8/int4 pages
    dequantize in-register instead of taking the materialized gather
    path), and gathers the outputs back to packed order."""
    t, n_q, hd = q.shape
    r = block_tables.shape[0]
    quant = k_scales is not None
    if use_pallas and quant:
        from githubrepostorag_tpu.ops.fused_decode import fused_packed_attention

        return fused_packed_attention(
            q, k_pages, v_pages, block_tables, cached_lens, new_lens,
            seg_ids, positions, tq=tq, k_scales=k_scales, v_scales=v_scales,
        )
    dest = _segment_scatter_indices(seg_ids, positions, cached_lens, tq)
    q_seg = (
        jnp.zeros((r * tq, n_q, hd), q.dtype)
        .at[dest].set(q, mode="drop")
        .reshape(r, tq, n_q, hd)
    )
    if use_pallas and not quant:
        interpret = not on_tpu()
        out_seg = packed_prefill_attention_seg(
            q_seg, k_pages, v_pages, block_tables, cached_lens, new_lens,
            interpret=interpret,
        )
    else:
        k, v = gather_kv(k_pages, v_pages, block_tables, k_scales, v_scales,
                         dtype=q.dtype)
        out_seg = dense_attention(
            q_seg, k, v,
            causal=True,
            q_offset=cached_lens,
            kv_lengths=cached_lens + new_lens,
        )
    # gather back to packed order; padding tokens read a clamped garbage
    # row (finite — never committed to KV, never projected to logits)
    flat = out_seg.reshape(r * tq, n_q, hd)
    return flat[jnp.clip(dest, 0, r * tq - 1)]


def ring_segment_layout(lens: list[int], width: int, rb: int):
    """Host-side layout of a segment-packed RING buffer: whole prompts back
    to back (the ring path always runs from position 0, so unlike the
    chunked contract above there are no cached prefixes — in-segment index
    IS the RoPE position).  Returns numpy arrays sized for the compiled
    ring program:

      seg       [width] int32 — owning segment per token; rb (the fixed
                segment-row bucket) marks padding
      positions [width] int32 — restarting at 0 per segment
      logits_at [rb]    int32 — each segment's last-token index into the
                flat buffer; rows past len(lens) point at 0 (ignored)
      starts    [len(lens)] int32 — each segment's first-token offset

    Shared by the engine's packed dispatch and its tests/bench so the
    buffer layout can never fork between them."""
    import numpy as np

    assert sum(lens) <= width and len(lens) <= rb
    seg = np.full((width,), rb, dtype=np.int32)
    positions = np.zeros((width,), dtype=np.int32)
    logits_at = np.zeros((rb,), dtype=np.int32)
    starts = np.zeros((len(lens),), dtype=np.int32)
    off = 0
    for i, n in enumerate(lens):
        seg[off : off + n] = i
        positions[off : off + n] = np.arange(n)
        logits_at[i] = off + n - 1
        starts[i] = off
        off += n
    return seg, positions, logits_at, starts
