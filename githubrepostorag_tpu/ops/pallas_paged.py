"""Pallas TPU paged-attention decode kernel (flash-decoding over the block
table).

In-tree replacement for the PagedAttention CUDA kernel vLLM brings to the
reference deployment (helm/templates/qwen-deployment.yaml).  One grid step
processes one (sequence, kv-head, page) triple: the page's K/V slab is
DMA'd into VMEM by the Pallas pipeline (double-buffered automatically via
the BlockSpec index map, which reads the *scalar-prefetched* block table),
scores for the kv-head's query group hit the MXU, and an online-softmax
accumulator in VMEM scratch carries (m, l, acc) across the page walk.
Nothing is ever materialized in HBM — the gather-based reference path
(ops/paged_attention.py) exists only as the correctness oracle.

Contract matches paged_attention_ref for the decode shape S == 1:
  q            [B, 1, n_q, hd]
  k_pages      [n_kv, P, page_size, hd]   (one layer's pool)
  v_pages      [n_kv, P, page_size, hd]
  block_tables [B, max_pages] int32
  cached_lens  [B] int32  (tokens in cache BEFORE this step)
  new_lens     [B] int32  (1 for active rows, 0 for padding rows)
Returns [B, 1, n_q, hd] in q.dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from githubrepostorag_tpu.runtime import on_tpu

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, max_pages] SMEM
    total_lens_ref,  # [B] SMEM
    # blocks
    q_ref,  # [1, 1, group, hd] VMEM
    k_ref,  # [1, 1, page_size, hd] VMEM (one page, one kv head)
    v_ref,  # [1, 1, page_size, hd] VMEM
    out_ref,  # [1, 1, group, hd] VMEM
    # scratch
    m_ref,  # [group, 128] f32
    l_ref,  # [group, 128] f32
    acc_ref,  # [group, hd] f32
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

    total = total_lens_ref[bi]  # valid kv length for this row
    page_start = pi * page_size

    @pl.when(page_start < total)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)  # [group, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [page_size, hd]
        v = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [group, page_size]
        kv_pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kv_pos < total, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [group, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [group, page_size]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_new

    @pl.when(pi == num_pi - 1)
    def _():
        # padding rows never hit the accumulate branch; guard the 0/0
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_ref[...] / safe_l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_decode(
    q: jnp.ndarray,  # [B, 1, n_q, hd]
    k_pages: jnp.ndarray,  # [n_kv, P, page_size, hd]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_pages]
    cached_lens: jnp.ndarray,  # [B]
    new_lens: jnp.ndarray,  # [B]
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, n_q, hd = q.shape
    assert s == 1, "pallas kernel is the decode path (S == 1)"
    n_kv, num_pages, page_size, _ = k_pages.shape
    group = n_q // n_kv
    max_pages = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)

    total_lens = (cached_lens + new_lens).astype(jnp.int32)
    q_r = q.reshape(b, n_kv, group, hd)

    grid = (b, n_kv, max_pages)

    def q_map(bi, hi, pi, bt, tl):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, pi, bt, tl):
        # Clamp the walk to allocated pages: beyond the row's length the
        # kernel skips compute, so any valid page id works — reuse page 0.
        page = jax.lax.select(pi * page_size < tl[bi], bt[bi, pi], 0)
        return (hi, page, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), q_map),
            pl.BlockSpec((1, 1, page_size, hd), kv_map),
            pl.BlockSpec((1, 1, page_size, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )

    kernel = functools.partial(_decode_kernel, page_size=page_size, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), total_lens, q_r, k_pages, v_pages)

    return out.reshape(b, 1, n_q, hd)


def _decode_staged_kernel(
    *refs,
    page_size: int,
    scale: float,
    layered: bool = False,
    kv_quant: bool = False,
):
    """Decode-burst attention: online softmax over [pool-prefix pages |
    staged tail].  Grid (B, max_pages + 1): the first max_pages steps walk
    the row's block table for ALL kv heads at once (skipping pages past
    ``pool_lens``); the final step folds in the burst's staged K/V
    (positions < ``staged_len``) and writes the normalized output.  One
    grid step per (row, page) — not per (row, head, page) — keeps the
    kernel's fixed per-step cost off the decode critical path.

    Refs, in order: scalar prefetch [block_tables (B, max_pages) SMEM,
    pool_lens (B), staged_len (1), + layer (1) when ``layered``, + k/v
    per-PAGE scales (n_kv, P) f32 when ``kv_quant``], blocks
    [q (1, n_kv, group, hd) VMEM, k/v (one pool page, every kv head —
    leading extra 1 for the layer axis when ``layered``), staged k/v
    (1, n_kv, n_steps, hd)], out (1, n_kv, group, hd), scratch [m, l
    (n_kv, group, 128) f32, acc (n_kv, group, hd) f32].  ``kv_quant``:
    pool tiles are int8; each page's scale is read per kv head from the
    SMEM scalar channel (zero extra operand DMAs — per-token scale tiles
    measured 5-18x slower, r04) and dequant happens here in VMEM, right
    before the dots."""
    n_scalars = (4 if layered else 3) + (2 if kv_quant else 0)
    scalar_refs = refs[:n_scalars]
    block_tables_ref, pool_lens_ref, staged_len_ref = scalar_refs[:3]
    blocks = refs[n_scalars : n_scalars + 5]
    q_ref, k_ref, v_ref, sk_ref, sv_ref = blocks
    out_ref, m_ref, l_ref, acc_ref = refs[n_scalars + 5 :]
    if layered:
        raw_k = lambda: k_ref[0, :, 0]  # [n_kv, page_size, hd]
        raw_v = lambda: v_ref[0, :, 0]
    else:
        raw_k = lambda: k_ref[:, 0]
        raw_v = lambda: v_ref[:, 0]
    bi = pl.program_id(0)
    pi = pl.program_id(1)
    num_pi = pl.num_programs(1)
    if kv_quant:
        # per-PAGE scales ride the SCALAR-PREFETCH channel ([n_kv, P] f32
        # in SMEM, already layer-sliced by the wrapper) and are read as
        # per-head scalars — the r03 per-token scale TILES added two tiny
        # operand DMAs to every (row, page) grid step and measured 5-18x
        # slower than bf16 pools; int8 pages with SMEM scales run at bf16
        # speed + halved KV HBM (r04 isolation)
        ks_ref, vs_ref = scalar_refs[-2:]
        n_kv_heads = k_ref.shape[1] if layered else k_ref.shape[0]
        page = block_tables_ref[bi, jnp.minimum(pi, num_pi - 2)]

        def dequant(raw, ref):
            # per-head scalar-from-SMEM x [ps, hd] plane, restacked on the
            # leading axis (a [n_kv] vector reshaped to [n_kv,1,1] is an
            # unsupported Mosaic shape cast; scalar broadcasts are free)
            x = raw().astype(jnp.float32)
            return jnp.stack([x[h] * ref[h, page] for h in range(n_kv_heads)])

        k_page = lambda: dequant(raw_k, ks_ref)
        v_page = lambda: dequant(raw_v, vs_ref)
    else:
        k_page, v_page = raw_k, raw_v

    @pl.when(pi == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    total = pool_lens_ref[bi]
    page_start = pi * page_size

    # batched-over-heads dot: [n_kv, g, hd] x [n_kv, T, hd] -> [n_kv, g, T]
    bdot = lambda a, b: jax.lax.dot_general(
        a, b, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    # [n_kv, g, T] x [n_kv, T, hd] -> [n_kv, g, hd]
    pdot = lambda p, v: jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )

    def accumulate(s, vals):
        """Online-softmax update: s [n_kv, g, T] over vals [n_kv, T, hd]."""
        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, :, :1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + pdot(p, vals)
        m_ref[:, :, :1] = m_new

    @pl.when((pi < num_pi - 1) & (page_start < total))
    def _():
        q = q_ref[0].astype(jnp.float32)  # [n_kv, group, hd]
        k = k_page().astype(jnp.float32)  # [n_kv, page_size, hd]
        s = bdot(q, k) * scale  # [n_kv, group, page_size]
        kv_pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kv_pos < total, s, NEG_INF)
        accumulate(s, v_page().astype(jnp.float32))

    @pl.when(pi == num_pi - 1)
    def _():
        q = q_ref[0].astype(jnp.float32)
        sk = sk_ref[0].astype(jnp.float32)  # [n_kv, n_steps, hd]
        s = bdot(q, sk) * scale  # [n_kv, group, n_steps]
        idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(idx < staged_len_ref[0], s, NEG_INF)
        accumulate(s, sv_ref[0].astype(jnp.float32))

        # staged_len >= 1 always, so l > 0 for every row incl. padding rows
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_ref[...] / safe_l).astype(out_ref.dtype)


def paged_attention_decode_staged(
    q: jnp.ndarray,  # [B, 1, n_q, hd]
    k_pages: jnp.ndarray,  # [n_kv, P, ps, hd] or [L, n_kv, P, ps, hd] pool
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_pages]
    pool_lens: jnp.ndarray,  # [B] — valid pool-prefix tokens per row
    staged_k: jnp.ndarray,  # [B, n_kv, n_steps, hd] — burst staging buffer
    staged_v: jnp.ndarray,
    staged_len: jnp.ndarray,  # [1] int32 — staged entries valid this step
    layer: jnp.ndarray | None = None,  # [] / [1] int32, REQUIRED for rank-5
    k_scales: jnp.ndarray | None = None,  # per-PAGE dequant scales (int8
    v_scales: jnp.ndarray | None = None,  # pools): [(L,) n_kv, P] f32
    interpret: bool = False,
) -> jnp.ndarray:
    """Burst-decode attention over [pool prefix | staged tail] without ever
    materializing the gathered KV in HBM (replaces gather_kv+dense in
    serving/decode_burst.py).  Not jitted — always called inside the burst's
    compiled program.

    Rank-5 pools + ``layer``: the burst's layer loop passes the WHOLE
    [L, n_kv, P, ps, hd] pool and the current layer index as a prefetched
    scalar — the BlockSpec index map addresses (layer, head, page)
    directly, so no per-layer pool slice is ever materialized.  Device
    profiling showed the sliced form costing ~0.5 ms/step at 0.5B/bs8
    (2 x 4 MB x 24 layers of dynamic-slice copy traffic per decode step).

    ``k_scales``/``v_scales`` mark int8 (kv_quant) pools: page tiles
    arrive int8 and dequantize in VMEM right before the dots with their
    per-PAGE scale read from the scalar-prefetch SMEM channel — KV HBM
    reads halve at zero extra operand DMAs (per-token scale tiles
    measured 5-18x slower, r04); the staged tail stays full precision."""
    b, s, n_q, hd = q.shape
    assert s == 1, "staged kernel is the decode path (S == 1)"
    layered = k_pages.ndim == 5
    kv_quant = k_scales is not None
    if layered:
        assert layer is not None, "rank-5 pools need the layer index"
        n_kv, num_pages, page_size, _ = k_pages.shape[1:]
    else:
        n_kv, num_pages, page_size, _ = k_pages.shape
    group = n_q // n_kv
    max_pages = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)
    q_r = q.reshape(b, n_kv, group, hd)

    grid = (b, max_pages + 1)

    def q_map(bi, pi, *refs):
        return (bi, 0, 0, 0)

    def clamp_page(bi, pi, bt, pool):
        # Clamp the walk to allocated pages; the staged grid step and pages
        # past the row's prefix skip compute, so any valid page id works.
        pp = jnp.minimum(pi, max_pages - 1)
        return jax.lax.select(
            (pi < max_pages) & (pi * page_size < pool[bi]), bt[bi, pp], 0
        )

    if layered:
        def kv_map(bi, pi, bt, pool, sl, *rest):
            return (rest[0][0], 0, clamp_page(bi, pi, bt, pool), 0, 0)

        kv_block = (1, n_kv, 1, page_size, hd)
    else:
        def kv_map(bi, pi, bt, pool, sl, *rest):
            return (0, clamp_page(bi, pi, bt, pool), 0, 0)

        kv_block = (n_kv, 1, page_size, hd)

    def staged_map(bi, pi, *refs):
        return (bi, 0, 0, 0)

    n_steps = staged_k.shape[2]
    scalars = [
        block_tables.astype(jnp.int32),
        pool_lens.astype(jnp.int32),
        staged_len.astype(jnp.int32),
    ]
    if layered:
        scalars.append(jnp.reshape(layer, (1,)).astype(jnp.int32))
    if kv_quant:
        # per-page scales [n_kv, P] join the SCALAR-PREFETCH channel (SMEM,
        # like the block tables): zero extra per-grid-step operand DMAs.
        # Layer-sliced here — a [n_kv, P] f32 slice is ~KBs, not a pool copy
        ks, vs = k_scales, v_scales
        if layered:
            li = jnp.reshape(layer, ()).astype(jnp.int32)
            ks = jax.lax.dynamic_index_in_dim(ks, li, 0, keepdims=False)
            vs = jax.lax.dynamic_index_in_dim(vs, li, 0, keepdims=False)
        scalars += [ks.astype(jnp.float32), vs.astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((1, n_kv, group, hd), q_map),
        pl.BlockSpec(kv_block, kv_map),
        pl.BlockSpec(kv_block, kv_map),
        pl.BlockSpec((1, n_kv, n_steps, hd), staged_map),
        pl.BlockSpec((1, n_kv, n_steps, hd), staged_map),
    ]
    operands = [q_r, k_pages, v_pages, staged_k, staged_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv, group, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((n_kv, group, 128), jnp.float32),
            pltpu.VMEM((n_kv, group, 128), jnp.float32),
            pltpu.VMEM((n_kv, group, hd), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _decode_staged_kernel, page_size=page_size, scale=scale,
        layered=layered, kv_quant=kv_quant,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, *operands)

    return out.reshape(b, 1, n_q, hd)


def paged_attention(q, k_pages, v_pages, block_tables, cached_lens, new_lens):
    """Dispatcher with the paged_attention_ref contract: Pallas for decode
    steps, gather+dense for prefill chunks (S > 1)."""
    from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref

    if q.shape[1] == 1:
        interpret = not on_tpu()
        return paged_attention_decode(
            q, k_pages, v_pages, block_tables, cached_lens, new_lens, interpret=interpret
        )
    return paged_attention_ref(q, k_pages, v_pages, block_tables, cached_lens, new_lens)
