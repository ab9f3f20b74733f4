"""Fused decode-step attention: spec-verify windows + paged attention +
mixed prefill/decode segments in ONE Pallas launch, over fp/int8/int4 pages.

Before this op the decode hot path was a chain of separately-shaped
dispatches: the spec-verify forward scored its k+1 candidate positions
through the PALLAS DECODE kernel's gather fallback (ops/pallas_paged.py
routes any S > 1 window to gather_kv + dense — a full [B, mp*ps, n_kv, hd]
HBM materialization per layer), quantized pools forced the same fallback
even at S == 1, and packed prefill rows needed their own program.  This
module is one generalized flash kernel that covers all of it:

  - WINDOW attention: every row scores an S-token window (S = k+1 for
    spec verify, S = 1 for plain decode) starting at its ``cached_lens``
    base against its block-table pages — online softmax across the page
    walk, nothing materialized in HBM.  The verify dispatch and the
    decode dispatch become the same program shape.
  - SEGMENT-packed grids: the ops/packed_prefill.py scatter idiom re-pads
    a [T]-packed mixed wave into the segment-major [R, tq] view — which
    IS the window layout — so chunked-prefill rows and decode rows ride
    one grid (`fused_packed_attention`), one compiled program per
    (row-bucket, tq).
  - Quantized pages IN-KERNEL: int8 pages dequantize by the per-page
    scalar-prefetched scale at the dot; int4 pages (kv_cache.pack_int4's
    nibble planes, uint8 [ps, hd//2]) widen through int32 (Mosaic
    legalizes neither uint8 shifts nor uint8->bf16 casts — the
    ops/pallas_int4.py rule) and score as TWO plane dots against the
    matching halves of q, never materializing the unpacked page.

Oracle: ``paged_attention_ref`` (gather_kv unpacks/dequantizes the same
bit pattern), which tests/test_fused_decode.py holds this kernel to across
row buckets, k widths, quant modes, and block-table holes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from githubrepostorag_tpu.ops.packed_prefill import _segment_scatter_indices
from githubrepostorag_tpu.runtime import on_tpu

NEG_INF = -1e30


def _fused_window_kernel(
    # scalar prefetch: block tables, cached and total lens, then the two
    # scale refs (quant != 0), then the layer index (rank-5 pools; only the
    # index maps read it), then blocks and scratch
    *refs,
    page_size: int,
    scale: float,
    quant: int,  # 0 = full precision, 8 = int8 pages, 4 = int4 nibble pages
    sliding: int | None = None,  # a sliding layer's window, in keys
    bf16_products: bool = False,  # the two products on the pool's own bfloat16 (float32 sums)
):
    block_tables_ref, cached_lens_ref, total_lens_ref = refs[:3]
    if quant:
        ks_ref, vs_ref = refs[3:5]
    q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref = refs[-7:]

    bi = pl.program_id(0)
    hi = pl.program_id(1)
    pi = pl.program_id(2)
    num_pi = pl.num_programs(2)

    @pl.when(pi == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cached = cached_lens_ref[bi]  # each q row's base position in the window
    total = total_lens_ref[bi]  # valid kv length for this row
    page_index = pi
    if sliding is not None:
        # the lowest query (position ``cached``) sees no key at or before
        # cached - sliding: the walk begins at the page of its first key (the
        # grid's page axis is as long as a window and a chunk, not as the table)
        page_index = pi + jnp.maximum(cached - sliding + 1, 0) // page_size
    page_start = page_index * page_size
    wanted = page_start < total

    @pl.when(wanted)
    def _():
        # ``bf16_products``: q, k, v and the softmax weights enter the products as
        # bfloat16, accumulated in float32 (one pass of the MXU where a float32
        # product takes several; a 25k-token context is 196 of these a head)
        wide = (lambda x: x) if bf16_products else (lambda x: x.astype(jnp.float32))
        q = wide(q_ref[0, 0])  # [group, W, hd]
        half = q.shape[-1] // 2

        if quant == 4:
            # nibble planes: byte c = component c | component c+half << 4
            # of the SAME token (kv_cache.pack_int4).  Widen through int32
            # — Mosaic has no uint8 shift/compare lowering — and
            # sign-extend two's-complement nibbles in-register.
            ki = k_ref[0, 0].astype(jnp.int32)  # [page_size, hd//2]
            k_lo = (((ki & 0xF) ^ 8) - 8).astype(jnp.float32)
            k_hi = (((ki >> 4) ^ 8) - 8).astype(jnp.float32)
            # two plane dots against the matching q halves — equivalent to
            # one dot against the unpacked [page_size, hd] page, which
            # never materializes
            s = jax.lax.dot_general(
                q[..., :half], k_lo, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + jax.lax.dot_general(
                q[..., half:], k_hi, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            k = wide(k_ref[0, 0])  # [page_size, hd]
            s = jax.lax.dot_general(
                q, k, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group, W, page_size]

        if quant:
            # per-page scalar dequant rides the softmax scale: this grid
            # step covers exactly one (kv head, page) pair
            page = block_tables_ref[bi, page_index]
            s = s * (scale * ks_ref[hi, page])
        else:
            s = s * scale

        # causal within the window: q row ti sits at absolute position
        # cached + ti; kv beyond the row's valid length is padding
        kv_pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        q_pos = cached + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = (kv_pos <= q_pos) & (kv_pos < total)
        if sliding is not None:
            seen = seen & (kv_pos > q_pos - sliding)
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_ref[:, :, :1]  # [group, W, 1]
        l_prev = l_ref[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [group, W, page_size]
        l_ref[:, :, :1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :, :1] = m_new

        if quant == 4:
            vi = v_ref[0, 0].astype(jnp.int32)  # [page_size, hd//2]
            v_lo = (((vi & 0xF) ^ 8) - 8).astype(jnp.float32)
            v_hi = (((vi >> 4) ^ 8) - 8).astype(jnp.float32)
            vs = vs_ref[hi, block_tables_ref[bi, page_index]]
            o_lo = jax.lax.dot_general(
                p, v_lo, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * vs
            o_hi = jax.lax.dot_general(
                p, v_hi, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * vs
            # plane outputs land in their own halves of the accumulator —
            # static ref slices, no in-kernel concat
            acc = acc_ref[...]
            acc_ref[:, :, :half] = acc[:, :, :half] * alpha + o_lo
            acc_ref[:, :, half:] = acc[:, :, half:] * alpha + o_hi
        else:
            v = wide(v_ref[0, 0])
            o = jax.lax.dot_general(
                p if p.dtype == v.dtype else p.astype(v.dtype), v, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if quant:
                o = o * vs_ref[hi, block_tables_ref[bi, page_index]]
            acc_ref[...] = acc_ref[...] * alpha + o

    @pl.when(pi == num_pi - 1)
    def _():
        # inactive / bucket-padding rows (total == 0) never hit the
        # accumulate branch; guard the 0/0
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_ref[...] / safe_l).astype(out_ref.dtype)


def fused_window_attention(
    q_win: jnp.ndarray,  # [B, S, n_q, hd] — per-row windows based at cached_lens
    k_pages: jnp.ndarray,  # [(L,) n_kv, P, page_size, hd] (or [.., hd//2] uint8 int4)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_pages]
    cached_lens: jnp.ndarray,  # [B]
    new_lens: jnp.ndarray,  # [B] valid new tokens (<= S) — already committed
    k_scales: jnp.ndarray | None = None,  # [(L,) n_kv, P] f32 per-page (quant pools)
    v_scales: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,  # [] / [1] int32, REQUIRED for rank-5
    interpret: bool = False,
    sliding: int | None = None,  # a sliding layer's window, in keys
    bf16_products: bool = False,
) -> jnp.ndarray:
    """ONE Pallas launch for every row's S-token window: grid
    (B, n_kv, max_pages), one page slab in VMEM per step.  Same contract
    as ``paged_attention_ref`` (its oracle).

    Rank-5 pools + ``layer``: the WHOLE [L, n_kv, P, ps, hd] pool and the
    layer index as one more prefetched scalar, so the index map addresses
    (layer, head, page) and no layer of the pool is sliced out — the same
    form as pallas_paged.paged_attention_decode_staged.

    ``sliding``: the query at position p sees the keys ``p - sliding < j <= p``;
    a row's walk begins at the page of its lowest query's first key and is as
    long as a window and the S columns can span, whatever the table's length
    (the table is indexed by absolute page; what it names before that page is
    never read).  None: the program it was.  ``bf16_products`` (full-precision pools only):
    the kernel's two products take bfloat16 operands."""
    b, s_w, n_q, hd = q_win.shape
    layered = k_pages.ndim == 5
    if layered:
        assert layer is not None, "rank-5 pools need the layer index"
    n_kv, _, page_size, hd_store = k_pages.shape[-4:]
    group = n_q // n_kv
    max_pages = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)
    if k_scales is None:
        quant = 0
    else:
        quant = 4 if k_pages.dtype == jnp.uint8 else 8

    total_lens = (cached_lens + new_lens).astype(jnp.int32)
    # [B, S, n_kv, group, hd] -> [B, n_kv, group, S, hd]: one kv head's
    # whole query group rides each grid step's MXU dots
    q_r = q_win.reshape(b, s_w, n_kv, group, hd).transpose(0, 2, 3, 1, 4)

    def q_map(bi, hi, pi, *scalars):
        return (bi, hi, 0, 0, 0)

    def kv_map(bi, hi, pi, bt, cl, tl, *scalars):
        # Clamp the walk to allocated pages: beyond the row's length the
        # kernel skips compute, so any valid page id works — page 0.
        at = pi
        if sliding is not None:
            at = jnp.minimum(pi + jnp.maximum(cl[bi] - sliding + 1, 0) // page_size,
                             max_pages - 1)
        page = jax.lax.select(at * page_size < tl[bi], bt[bi, at], 0)
        if layered:  # the layer index is the LAST prefetched scalar
            return (scalars[-1][0], hi, page, 0, 0)
        return (hi, page, 0, 0)

    # the kernel body reads its page as k_ref[0, 0]: squeeze the layer axis
    kv_block = (None,) * layered + (1, 1, page_size, hd_store)
    prefetch = [block_tables.astype(jnp.int32), cached_lens.astype(jnp.int32),
                total_lens]
    if quant:
        # per-page scales ride the scalar-prefetch channel as one layer's
        # [n_kv, P]: a slice of KBs, not of a pool
        for sc in (k_scales, v_scales):
            if layered:
                sc = jax.lax.dynamic_index_in_dim(
                    sc, jnp.reshape(layer, ()), 0, keepdims=False)
            prefetch.append(sc.astype(jnp.float32))
    if layered:
        prefetch.append(jnp.reshape(layer, (1,)).astype(jnp.int32))

    walk = max_pages
    if sliding is not None:  # pages from the lowest query's first key to the highest query
        walk = min(max_pages, (sliding + s_w - 2) // page_size + 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_kv, walk),
        in_specs=[
            pl.BlockSpec((1, 1, group, s_w, hd), q_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, s_w, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, s_w, 128), jnp.float32),
            pltpu.VMEM((group, s_w, 128), jnp.float32),
            pltpu.VMEM((group, s_w, hd), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _fused_window_kernel, page_size=page_size, scale=scale, quant=quant, sliding=sliding,
        bf16_products=bf16_products and not quant,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, group, s_w, hd), q_win.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*prefetch, q_r, k_pages, v_pages)

    # [B, n_kv, group, S, hd] -> [B, S, n_q, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s_w, n_q, hd)


_window_attention = fused_window_attention
fused_window_attention = functools.partial(
    jax.jit, static_argnames=("interpret", "sliding", "bf16_products"))(_window_attention)


@functools.partial(jax.jit, static_argnames=("interpret", "sliding", "bf16_products"))
def sliding_prefill_attention(*args, **kw):
    """``fused_window_attention`` under the name a SLIDING layer's calls carry
    in a device trace: an instruction is named for the innermost jit around it
    (inside a ``lax.cond`` branch a bare scope's name is lost to the
    branch's), and a trace has to tell these calls from a global layer's."""
    return _window_attention(*args, **kw)


def fused_paged_attention(q, k_pages, v_pages, block_tables, cached_lens,
                          new_lens, k_scales=None, v_scales=None, layer=None, sliding=None,
                          bf16_products=False):
    """Drop-in for ``paged_attention_ref``/``pallas_paged.paged_attention``
    at the forward_paged seam: spec-verify windows (S = k+1), plain decode
    (S = 1), and quantized pools all hit the SAME kernel instead of the
    dispatcher's gather fallback.  Interpret mode off-TPU keeps CPU tests
    on the kernel's exact compute graph."""
    call = fused_window_attention if sliding is None else sliding_prefill_attention
    return call(
        q, k_pages, v_pages, block_tables, cached_lens, new_lens,
        k_scales, v_scales, layer, interpret=not on_tpu(), sliding=sliding,
        bf16_products=bf16_products,
    )


def fused_packed_attention(
    q: jnp.ndarray,  # [T, n_q, hd] packed mixed-phase queries
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [R, max_pages]
    cached_lens: jnp.ndarray,  # [R]
    new_lens: jnp.ndarray,  # [R]
    seg_ids: jnp.ndarray,  # [T]; >= R marks padding tokens
    positions: jnp.ndarray,  # [T] absolute positions
    *,
    tq: int,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Mixed-phase launch: the packed_prefill scatter re-pads the [T]
    buffer to the segment-major [R, tq] view — a prefill CHUNK and a
    decode/verify WINDOW are the same shape there (cached_lens base,
    new_lens valid tokens) — and one fused-kernel grid covers every
    segment regardless of phase or pool quantization."""
    t, n_q, hd = q.shape
    r = block_tables.shape[0]
    dest = _segment_scatter_indices(seg_ids, positions, cached_lens, tq)
    q_seg = (
        jnp.zeros((r * tq, n_q, hd), q.dtype)
        .at[dest].set(q, mode="drop")
        .reshape(r, tq, n_q, hd)
    )
    out_seg = fused_window_attention(
        q_seg, k_pages, v_pages, block_tables, cached_lens, new_lens,
        k_scales, v_scales, interpret=not on_tpu(),
    )
    # gather back to packed order; padding tokens read a clamped garbage
    # row (finite — never committed to KV, never projected to logits)
    flat = out_seg.reshape(r * tq, n_q, hd)
    return flat[jnp.clip(dest, 0, r * tq - 1)]
