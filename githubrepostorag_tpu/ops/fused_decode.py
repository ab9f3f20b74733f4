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

  - SEVERAL PAGES a grid step: a full-precision pool's walk folds up to
    eight pages of a kv head with one online-softmax update, in passes of
    query heads (``_fold_pages`` / ``_query_tile``: both from the call's
    shapes); a quantised pool's folds one, whose scale rides the softmax's.

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
from githubrepostorag_tpu.ops.pallas_paged import _head_page_bytes
from githubrepostorag_tpu.runtime import on_tpu

NEG_INF = -1e30


VMEM_BYTES = 16 * 1024 * 1024  # what one kernel may hold on a v5e
FOLD_PAGES = 8  # pages a grid step folds at the most
TILE_BYTES = 2 * 1024 * 1024  # the float32 scores one pass of a step's body makes


def _fold_pages(walk: int, group: int, s_w: int, hd: int, page_size: int, itemsize: int,
                quant: int) -> int:
    """Pages of one kv head a grid step brings in and folds with ONE softmax
    update.  At the most ``FOLD_PAGES``, and as many as fit beside the call's
    resident blocks (q and out in two buffers, the softmax state, the
    accumulator) and a pass's scores and weights; then spread evenly over the
    steps the walk takes (a sliding walk of 34 pages is 5 steps of 7, not of 8; a
    walk shorter than a step is one step, its missing pages masked).  Quantised
    pools fold ONE page: a page's scale rides the softmax scale of the step's
    only page."""
    if quant:
        return 1
    resident = group * s_w * (2 * 2 * hd * itemsize + 2 * 128 * 4 + hd * 4)
    fit = (VMEM_BYTES - resident - 2 * TILE_BYTES) // _head_page_bytes(page_size, hd, itemsize)
    most = max(1, min(FOLD_PAGES, fit, walk))
    return pl.cdiv(walk, pl.cdiv(walk, most))


def _query_tile(group: int, s_w: int, span: int) -> tuple[int, int]:
    """(query heads, columns) one pass of a step's body takes against the step's
    ``span`` keys: as many rows as keep the pass's float32 scores within
    ``TILE_BYTES`` (the whole group's, 8 MB at 16 heads of 128 columns over 8
    pages, do not fit beside the call's blocks everywhere; the chip reads 2 MB
    as fast as all of it, and 0.5 MB 60% slower).  Whole heads where a head's
    columns fit, else a divisor of the columns; a window whose columns do not
    fill sublanes (a decode's one, a verify's k + 1) goes whole."""
    rows = max(8, TILE_BYTES // (4 * span))
    if s_w % 8 or group * s_w <= rows:
        return group, s_w
    if s_w <= rows:
        return max(d for d in range(1, group + 1) if group % d == 0 and d * s_w <= rows), s_w
    return 1, max(d for d in range(8, rows + 1, 8) if s_w % d == 0)


def _fused_window_kernel(
    # scalar prefetch: the pages each row walks, cached and total lens, then the two
    # scale refs (quant != 0), then the layer index (rank-5 pools; only the
    # index maps read it), then blocks and scratch
    *refs,
    page_size: int,
    scale: float,
    quant: int,  # 0 = full precision, 8 = int8 pages, 4 = int4 nibble pages
    fold: int,  # pages a grid step folds: as many K blocks, then as many V blocks
    tile: tuple[int, int],  # (query heads, columns) a pass of the body takes
    sliding: int | None = None,  # a sliding layer's window, in keys
    bf16_products: bool = False,  # the two products on the pool's own bfloat16 (float32 sums)
):
    walked_ref, cached_lens_ref, total_lens_ref = refs[:3]
    if quant:
        ks_ref, vs_ref = refs[3:5]
    out_ref, m_ref, l_ref, acc_ref = refs[-4:]
    q_ref = refs[-5 - 2 * fold]
    k_refs, v_refs = refs[-4 - 2 * fold:-4 - fold], refs[-4 - fold:-4]
    group, s_w = q_ref.shape[2:4]
    heads, cols = tile
    span = fold * page_size  # keys a step folds

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
    page_index = pi * fold
    if sliding is not None:
        # the lowest query (position ``cached``) sees no key at or before
        # cached - sliding: the walk begins at the page of its first key (the
        # grid's page axis is as long as a window and a chunk, not as the table)
        page_index = page_index + jnp.maximum(cached - sliding + 1, 0) // page_size
    step_start = page_index * page_size
    wanted = step_start < total

    @pl.when(wanted)
    def _():
        # ``bf16_products``: q, k, v and the softmax weights enter the products as
        # bfloat16, accumulated in float32 (one pass of the MXU where a float32
        # product takes several; a 25k-token context is 196 pages a head)
        wide = (lambda x: x) if bf16_products else (lambda x: x.astype(jnp.float32))
        nt = (((2,), (1,)), ((), ()))  # [heads, cols, hd] x [keys, hd] -> [heads, cols, keys]
        nn = (((2,), (0,)), ((), ()))  # [heads, cols, keys] x [keys, hd] -> [heads, cols, hd]
        dot = functools.partial(jax.lax.dot_general, preferred_element_type=jnp.float32)
        half = q_ref.shape[-1] // 2
        step_scale = scale

        def pages(page_refs):
            """The step's pages of one side, one under the other: [span, hd]."""
            if fold == 1:
                return page_refs[0][0, 0]
            return jnp.concatenate([r[0, 0] for r in page_refs], axis=0)

        if quant == 4:
            # nibble planes: byte c = component c | component c+half << 4
            # of the SAME token (kv_cache.pack_int4).  Widen through int32
            # — Mosaic has no uint8 shift/compare lowering — and
            # sign-extend two's-complement nibbles in-register.
            planes = lambda x: ((((x & 0xF) ^ 8) - 8).astype(jnp.float32),  # noqa: E731
                                (((x >> 4) ^ 8) - 8).astype(jnp.float32))
            k_lo, k_hi = planes(pages(k_refs).astype(jnp.int32))  # [page_size, hd//2]
            v_lo, v_hi = planes(pages(v_refs).astype(jnp.int32))
        else:
            k, v = wide(pages(k_refs)), wide(pages(v_refs))  # [span, hd]
        if quant:
            # per-page scalar dequant rides the softmax scale: a quantised
            # pool's step covers exactly one (kv head, page) pair
            page = walked_ref[bi, pi]
            step_scale, v_scale = scale * ks_ref[hi, page], vs_ref[hi, page]

        def one_tile(gi, ci):
            at = (pl.ds(gi, heads), pl.ds(ci, cols))
            q = wide(q_ref[(0, 0, *at)])  # [heads, cols, hd]
            if quant == 4:
                # two plane dots against the matching q halves — equivalent to
                # one dot against the unpacked [page_size, hd] page, which
                # never materializes
                s = dot(q[..., :half], k_lo, nt) + dot(q[..., half:], k_hi, nt)
            else:
                s = dot(q, k, nt)
            s = s * step_scale  # [heads, cols, span]

            # causal within the window: q row ti sits at absolute position
            # cached + ti; kv beyond the row's length is padding (a page of the
            # step past the row's last among it).  In EVERY step: the mask costs
            # the chip nothing beside the products (PERF.md section 5, PR 51)
            kv_pos = step_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            q_pos = cached + ci + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = (kv_pos <= q_pos) & (kv_pos < total)
            if sliding is not None:
                seen = seen & (kv_pos > q_pos - sliding)
            s = jnp.where(seen, s, NEG_INF)

            m_prev = m_ref[(*at, slice(0, 1))]  # [heads, cols, 1]
            l_prev = l_ref[(*at, slice(0, 1))]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # [heads, cols, span]
            l_ref[(*at, slice(0, 1))] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[(*at, slice(0, 1))] = m_new

            if quant == 4:
                # plane outputs land in their own halves of the accumulator —
                # static ref slices, no in-kernel concat
                acc = acc_ref[at]
                acc_ref[(*at, slice(0, half))] = acc[..., :half] * alpha + dot(p, v_lo, nn) * v_scale
                acc_ref[(*at, slice(half, None))] = acc[..., half:] * alpha + dot(p, v_hi, nn) * v_scale
            else:
                o = dot(p if p.dtype == v.dtype else p.astype(v.dtype), v, nn)
                if quant:
                    o = o * v_scale
                acc_ref[at] = acc_ref[at] * alpha + o

        across = s_w // cols
        if group // heads * across == 1:
            one_tile(0, 0)
        else:
            def body(i, carry):
                one_tile(i // across * heads, pl.multiple_of(i % across * cols, cols))
                return carry

            jax.lax.fori_loop(0, group // heads * across, body, 0)

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
    (B, n_kv, ceil(walk / N)), N pages of K and of V in VMEM a step (N block
    specs a side, so the next step's 2 N pages are in flight while one is
    folded), one online-softmax update a step.  N is ``_fold_pages``' of the
    call's shapes.  Same contract as ``paged_attention_ref`` (its oracle).

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

    cached_lens = cached_lens.astype(jnp.int32)
    total_lens = (cached_lens + new_lens).astype(jnp.int32)
    # [B, S, n_kv, group, hd] -> [B, n_kv, group, S, hd]: one kv head's
    # whole query group rides each grid step's MXU dots
    q_r = q_win.reshape(b, s_w, n_kv, group, hd).transpose(0, 2, 3, 1, 4)

    walk = max_pages
    if sliding is not None:  # pages from the lowest query's first key to the highest query
        walk = min(max_pages, (sliding + s_w - 2) // page_size + 2)
    fold = _fold_pages(walk, group, s_w, hd, page_size, k_pages.dtype.itemsize, quant)

    # the pages a row walks, step by step, worked out HERE and handed to the index
    # maps as the prefetched table: a call has 2 N of them, each traced and lowered
    # at every call site, and a look-up is all that is left to them.  Past the
    # row's length the kernel skips compute, so any valid page id works: page 0
    first = 0
    if sliding is not None:
        first = (jnp.maximum(cached_lens - sliding + 1, 0) // page_size)[:, None]
    at = first + jnp.arange(pl.cdiv(walk, fold) * fold, dtype=jnp.int32)[None, :]
    walked = jnp.where(
        at * page_size < total_lens[:, None],
        jnp.take_along_axis(block_tables.astype(jnp.int32), jnp.minimum(at, max_pages - 1), axis=1),
        0)

    def q_map(bi, hi, pi, *scalars):
        return (bi, hi, 0, 0, 0)

    def kv_map(j, bi, hi, pi, walked, *scalars):
        page = walked[bi, pi * fold + j]
        if layered:  # the layer index is the LAST prefetched scalar
            return (scalars[-1][0], hi, page, 0, 0)
        return (hi, page, 0, 0)

    # the kernel body reads a page as k_ref[0, 0]: squeeze the layer axis
    kv_block = (None,) * layered + (1, 1, page_size, hd_store)
    pages = [pl.BlockSpec(kv_block, functools.partial(kv_map, j)) for j in range(fold)]
    prefetch = [walked, cached_lens, total_lens]
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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_kv, pl.cdiv(walk, fold)),
        in_specs=[pl.BlockSpec((1, 1, group, s_w, hd), q_map), *pages, *pages],
        out_specs=pl.BlockSpec((1, 1, group, s_w, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, s_w, 128), jnp.float32),
            pltpu.VMEM((group, s_w, 128), jnp.float32),
            pltpu.VMEM((group, s_w, hd), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _fused_window_kernel, page_size=page_size, scale=scale, quant=quant, fold=fold,
        tile=_query_tile(group, s_w, fold * page_size), sliding=sliding,
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
    )(*prefetch, q_r, *[k_pages] * fold, *[v_pages] * fold)

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
