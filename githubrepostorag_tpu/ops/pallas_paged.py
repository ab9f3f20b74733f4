"""Pallas TPU paged-attention decode kernels (flash-decoding over the block
table).

In-tree replacement for the PagedAttention CUDA kernel vLLM brings to the
reference deployment (helm/templates/qwen-deployment.yaml).  Nothing is ever
materialized in HBM — the gather-based reference path
(ops/paged_attention.py) exists only as the correctness oracle.

Two kernels:

* ``paged_attention_decode`` — one decode step outside a burst (no cell
  runs it).  A dense grid (sequence, kv-head, page): the page's K/V slab is
  DMA'd into VMEM by the Pallas pipeline through a BlockSpec index map that
  reads the *scalar-prefetched* block table, and an online-softmax
  accumulator in VMEM scratch carries (m, l, acc) across the page walk.

* ``paged_attention_decode_staged`` — the decode burst's kernel
  (serving/decode_burst.py), over [pool prefix | the burst's staged tail].
  Its work follows the pages that live rows hold, not the table: the pools
  stay in HBM (``memory_space=pl.ANY``) and the kernel copies a row's own
  ``ceil(pool_len / page_size)`` pages itself, in waves of N pages with the
  next wave's DMAs in flight (the row's own, or the next live row's first)
  while the current one is folded into the softmax as one product N pages
  wide.  A dead row (pool length 0) is one small product over the staged
  tail: no page, no DMA, no grid step of its own (a grid step takes
  ``ROWS_PER_STEP`` row slots).  N is ``_wave_pages``: what fits
  ``WAVE_VMEM_BYTES`` given the kv heads, the page, the head and the pool's
  dtype — 4 pages for Qwen2-7B's bfloat16 pools, 8 for 1.5B's, 16 for one
  kv head of a tp shard.  Where not even ``WAVE_MIN_PAGES`` pages of every kv
  head fit, a wave takes a SLICE of the kv heads (``_wave_heads``: the most
  heads, a divisor of their number, of which that many pages fit) and the
  rows are walked once a slice: 30 kv heads of 128 (multi-head attention, a
  page of every head is 7.9 MB against the 4 MB budget) go through as 5
  slices of 6 heads, 2 pages a wave.  One layer's call at Qwen2-7B widths, 32 row slots
  and tables of 16 pages, on a v5e (PERF.md, Findings, PR 28): 24 us at 7
  live rows of ~350 tokens, 77 us at 13 of ~1.5k, 188 us with every row at
  2,048 (87% of the HBM peak); the dense (row, page) grid this replaced took
  102 / 176 / 338 us.

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


WAVE_VMEM_BYTES = 4 * 1024 * 1024  # what one wave of the burst kernel may hold in VMEM
ROWS_PER_STEP = 8  # row slots one grid step of the burst kernel takes


WAVE_MIN_PAGES = 2  # pages a wave holds at the least: one folded, its successor in flight


def _head_page_bytes(page_size: int, hd: int, itemsize: int) -> int:
    """One page of one kv head in a wave: K and V, two DMA slots in the pool's
    dtype plus the float32 copies the products run on."""
    return page_size * hd * (2 * 2 * itemsize + 2 * 4)


def _wave_heads(n_kv: int, page_size: int, hd: int, itemsize: int) -> int:
    """kv heads one wave of the burst kernel holds: all of them where a page
    of every head fits ``WAVE_VMEM_BYTES``; else the largest divisor of
    ``n_kv`` of which ``WAVE_MIN_PAGES`` pages fit (one head at the least)."""
    fit = max(1, WAVE_VMEM_BYTES // _head_page_bytes(page_size, hd, itemsize))
    if n_kv <= fit:
        return n_kv
    return max(d for d in range(1, n_kv + 1)
               if n_kv % d == 0 and (d * WAVE_MIN_PAGES <= fit or d == 1))


def _wave_pages(n_kv: int, page_size: int, hd: int, itemsize: int, max_pages: int) -> int:
    """Pages the burst kernel reads and folds at a time: the largest power
    of two whose K and V tiles (the wave's kv heads; two DMA slots in the
    pool's dtype plus the float32 copies the products run on) fit
    ``WAVE_VMEM_BYTES``, and no more than a row's table holds."""
    heads = _wave_heads(n_kv, page_size, hd, itemsize)
    fit = max(1, WAVE_VMEM_BYTES // (heads * _head_page_bytes(page_size, hd, itemsize)))
    return min(1 << (fit.bit_length() - 1), max_pages)


def _burst_kernel(
    *refs,
    page_size: int,
    scale: float,
    wave: int,
    layered: bool = False,
    kv_quant: bool = False,
    head_slices: int = 1,
    sliding: bool = False,
    bf16_products: bool = False,
):
    """Decode-burst attention: online softmax over [the pages the row holds
    | staged tail].  Grid (B / R,): a step takes R row slots, one after the
    other (``head_slices`` > 1: the kv heads go through a slice at a time,
    grid (slices * B / R,), and a "row" below is a row of one slice: row r of
    slice s is walked as virtual row s * B + r, so the first wave of a
    slice's first live row is in flight while the slice before ends).  A row
    walks its own ``ceil(pool_len / page_size)`` pages in waves
    of ``wave`` pages, ALL kv heads at once: each page is one DMA from the
    pool in HBM into a VMEM slot, a wave is one [n_kv, group, wave *
    page_size] product, and while a wave is folded in the next one's DMAs are
    in flight: the row's own next wave, or after its last the first wave of
    the next live row.  The row then folds in the burst's staged K/V
    (positions < ``staged_len``) and writes its normalized output, so a dead
    row (pool length 0) costs that one small product: no page, no DMA, no
    grid step.

    Refs, in order: scalar prefetch [block_tables (B, max_pages) SMEM,
    pool_lens (B), staged_len (1), + layer (1) when ``layered``, + k/v
    per-PAGE scales (n_kv, P) f32 when ``kv_quant``], q (R, n_kv, group,
    hd) VMEM, the k and v pools WHOLE in HBM ([L,] n_kv, P, page_size, hd),
    staged k/v (R, n_kv, n_steps, hd), out (R, n_kv, group, hd), scratch
    [k and v slots (2, n_kv, wave * page_size, hd) in the pool's dtype, DMA
    semaphores (2, 2), the slot the next first wave was sent to (1,) SMEM,
    m, l (n_kv, group, 128) f32, acc (n_kv, group, hd) f32].  ``kv_quant``:
    pool pages are int8; each page's scale is read per kv head from the SMEM
    scalar channel (zero extra DMAs: per-token scale tiles measured 5-18x
    slower, r04) and dequant happens here in VMEM, right before the dots.

    ``sliding``: one more prefetched scalar after the layer, ``pool_starts``
    (B): the FIRST KEY a row may see (a sliding layer: the row's position less
    the window).  The row's walk begins at that key's page (``first_page``: the
    table is indexed by absolute page, the pages before it are never named),
    the keys of that page that lie before the start are masked in the first
    wave, and a row whose start lies past what it holds walks nothing, as a
    dead row does.  Without it the program is the one it was.

    ``bf16_products`` (full-precision pools): the pages enter the two products
    as the bfloat16 they are stored in, with q and the softmax weights rounded
    to it, and float32 sums: no float32 copy of a wave is made (at 8 kv heads
    of 128 a wave's copies are 2 MB a page pair, and the conversions cost more
    than the pages' DMAs), and the MXU takes one pass where float32 takes several."""
    n_scalars = (4 if layered else 3) + (2 if kv_quant else 0) + (1 if sliding else 0)
    scalar_refs = refs[:n_scalars]
    block_tables_ref, pool_lens_ref, staged_len_ref = scalar_refs[:3]
    q_ref, k_hbm, v_hbm, sk_ref, sv_ref, out_ref = refs[n_scalars : n_scalars + 6]
    k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref = refs[n_scalars + 6 :]
    if layered:
        k_hbm, v_hbm = k_hbm.at[scalar_refs[3][0]], v_hbm.at[scalar_refs[3][0]]
    ks_ref, vs_ref = scalar_refs[-2:] if kv_quant else (None, None)
    starts_ref = scalar_refs[4 if layered else 3] if sliding else None
    n_kv_heads = k_buf.shape[1]  # of one wave: all of them, or a slice
    rows, max_pages = block_tables_ref.shape
    block_rows = q_ref.shape[0]
    walked = rows * head_slices  # virtual rows: every row once a slice of heads

    def row_of(vr):
        return vr if head_slices == 1 else vr % rows

    def first_page(vr):
        """The page of the first key virtual row ``vr`` may see."""
        return starts_ref[row_of(vr)] // page_size

    def pages_of(vr):
        held = (pool_lens_ref[row_of(vr)] + page_size - 1) // page_size
        return jnp.maximum(held - first_page(vr), 0) if sliding else held

    def page_at(vr, w, j):
        at = w * wave + j
        if sliding:
            at = at + first_page(vr)
        return block_tables_ref[row_of(vr), jnp.minimum(at, max_pages - 1)]

    def dead(vr):
        if sliding:
            return pages_of(vr) == 0
        return pool_lens_ref[row_of(vr)] == 0

    def heads_of(hbm, vr):
        """The pool's kv heads that virtual row ``vr``'s slice takes."""
        if head_slices == 1:
            return hbm
        return hbm.at[pl.ds((vr // rows) * n_kv_heads, n_kv_heads)]

    def page_dmas(row, w, slot, go):
        """``go`` (start or wait) on the DMA of every page ``row`` holds in
        its wave ``w``: page -> rows [j * page_size, (j + 1) * page_size) of
        slot ``slot``, every kv head in one strided copy."""
        held = pages_of(row)
        for j in range(wave):
            @pl.when(w * wave + j < held)
            def _():
                page, at = page_at(row, w, j), pl.ds(j * page_size, page_size)
                for which, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                    go(pltpu.make_async_copy(
                        heads_of(hbm, row).at[:, page], buf.at[slot, :, at],
                        sems.at[which, slot]))

    def start_first_wave(after, slot):
        """Start the first wave of the next live row at or after ``after``,
        if there is one, so that it lands while the rows before it work."""
        row = jax.lax.while_loop(
            lambda r: (r < walked) & dead(jnp.minimum(r, walked - 1)),
            lambda r: r + 1, after)

        @pl.when(row < walked)
        def _():
            page_dmas(row, 0, slot, lambda dma: dma.start())

    def tile(buf, scales_ref, row, w, slot):
        """Wave ``w`` of ``row`` as float32 [n_kv, wave * page_size, hd]."""
        if bf16_products and not kv_quant:
            return buf[slot]
        x = buf[slot].astype(jnp.float32)
        if not kv_quant:
            return x
        # per-PAGE scales ride the SCALAR-PREFETCH channel ([n_kv, P] f32 in
        # SMEM, already layer-sliced by the wrapper) and are read as per-head
        # scalars x [ps, hd] planes, restacked (a [n_kv] vector reshaped to
        # [n_kv,1,1] is an unsupported Mosaic shape cast; scalar broadcasts
        # are free): int8 pages with SMEM scales run at bf16 speed + halved
        # KV HBM (r04 isolation)
        h0 = 0 if head_slices == 1 else (row // rows) * n_kv_heads
        return jnp.stack([
            jnp.concatenate([
                x[h, j * page_size : (j + 1) * page_size] * scales_ref[h0 + h, page_at(row, w, j)]
                for j in range(wave)
            ], axis=0)
            for h in range(n_kv_heads)
        ])

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
        if p.dtype != vals.dtype:  # ``bf16_products``: the weights meet the pages in their type
            p = p.astype(vals.dtype)
        acc_ref[...] = acc_ref[...] * alpha + pdot(p, vals)
        m_ref[:, :, :1] = m_new

    # read out here: the interpreter has no program_id inside a loop's body
    first_row = pl.program_id(0) * block_rows

    @pl.when(first_row == 0)
    def _():
        # a row's last wave fills only the pages the row holds; what the
        # rest of the V slot holds meets a weight of exactly 0 and must be
        # finite for that (K's leftovers are masked after the product)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start_first_wave(0, 0)

    def one_row(r, carry):
        bi = first_row + r
        total = pool_lens_ref[row_of(bi)]
        n_waves = (pages_of(bi) + wave - 1) // wave
        first_slot = slot_ref[0]  # where this row's first wave was sent
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        narrow = bf16_products and not kv_quant
        q = q_ref[r] if narrow else q_ref[r].astype(jnp.float32)  # [n_kv, group, hd]

        def fold_wave(w, carry):
            slot = (first_slot + w) % 2

            @pl.when(w + 1 < n_waves)
            def _():
                page_dmas(bi, w + 1, 1 - slot, lambda dma: dma.start())

            @pl.when(w + 1 == n_waves)
            def _():
                start_first_wave(bi + 1, 1 - slot)

            page_dmas(bi, w, slot, lambda dma: dma.wait())
            s = bdot(q, tile(k_buf, ks_ref, bi, w, slot)) * scale  # [n_kv, group, wave * ps]
            kv_pos = w * wave * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            seen = kv_pos < total
            if sliding:
                kv_pos = kv_pos + first_page(bi) * page_size
                seen = (kv_pos < total) & (kv_pos >= starts_ref[row_of(bi)])
            accumulate(jnp.where(seen, s, NEG_INF), tile(v_buf, vs_ref, bi, w, slot))
            return carry

        jax.lax.fori_loop(0, n_waves, fold_wave, 0)
        slot_ref[0] = (first_slot + n_waves) % 2

        staged = (lambda ref: ref[r]) if narrow else (lambda ref: ref[r].astype(jnp.float32))
        s = bdot(q, staged(sk_ref)) * scale  # [n_kv, group, n_steps]
        idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        accumulate(jnp.where(idx < staged_len_ref[0], s, NEG_INF), staged(sv_ref))
        # staged_len >= 1 always, so l > 0 for every row incl. dead ones
        l = l_ref[:, :, :1]
        out_ref[r] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_rows, one_row, 0)


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
    pool_starts: jnp.ndarray | None = None,  # [B] the first key each row may see
    bf16_products: bool = False,  # see ``_burst_kernel``
) -> jnp.ndarray:
    """Burst-decode attention over [pool prefix | staged tail] without ever
    materializing the gathered KV in HBM (replaces gather_kv+dense in
    serving/decode_burst.py).  Not jitted — always called inside the burst's
    compiled program.  Its cost follows the pages that live rows hold
    (``_burst_kernel``), not the block table's size.

    Rank-5 pools + ``layer``: the burst's layer loop passes the WHOLE
    [L, n_kv, P, ps, hd] pool and the current layer index as a prefetched
    scalar; the pools stay in HBM and the kernel's DMAs address (layer,
    page) directly, so no per-layer pool slice is ever materialized.  Device
    profiling showed the sliced form costing ~0.5 ms/step at 0.5B/bs8
    (2 x 4 MB x 24 layers of dynamic-slice copy traffic per decode step).

    ``k_scales``/``v_scales`` mark int8 (kv_quant) pools: pages arrive int8
    and dequantize in VMEM right before the dots with their per-PAGE scale
    read from the scalar-prefetch SMEM channel — KV HBM reads halve at zero
    extra DMAs (per-token scale tiles measured 5-18x slower, r04); the
    staged tail stays full precision.

    ``pool_starts`` (a sliding layer): keys of the pool before a row's start
    are neither read (whole pages) nor seen (the start's own page);
    ``block_tables`` is indexed by absolute page all the same.  The staged tail
    is seen whole: a burst is shorter than any window."""
    b, s, n_q, hd = q.shape
    assert s == 1, "staged kernel is the decode path (S == 1)"
    layered = k_pages.ndim == 5
    kv_quant = k_scales is not None
    sliding = pool_starts is not None
    assert not (sliding and kv_quant), "no quantized pool has a first key"
    if layered:
        assert layer is not None, "rank-5 pools need the layer index"
    n_kv, _, page_size, _ = k_pages.shape[-4:]
    group = n_q // n_kv
    n_steps = staged_k.shape[2]
    itemsize = k_pages.dtype.itemsize
    wave = _wave_pages(n_kv, page_size, hd, itemsize, block_tables.shape[1])
    heads = _wave_heads(n_kv, page_size, hd, itemsize)
    head_slices = n_kv // heads
    q_r = q.reshape(b, n_kv, group, hd)

    scalars = [
        block_tables.astype(jnp.int32),
        pool_lens.astype(jnp.int32),
        staged_len.astype(jnp.int32),
    ]
    if layered:
        scalars.append(jnp.reshape(layer, (1,)).astype(jnp.int32))
    if sliding:
        scalars.append(pool_starts.astype(jnp.int32))
    if kv_quant:
        # per-page scales [n_kv, P] join the SCALAR-PREFETCH channel (SMEM,
        # like the block tables): zero extra DMAs.  Layer-sliced here — a
        # [n_kv, P] f32 slice is ~KBs, not a pool copy
        ks, vs = k_scales, v_scales
        if layered:
            li = jnp.reshape(layer, ()).astype(jnp.int32)
            ks = jax.lax.dynamic_index_in_dim(ks, li, 0, keepdims=False)
            vs = jax.lax.dynamic_index_in_dim(vs, li, 0, keepdims=False)
        scalars += [ks.astype(jnp.float32), vs.astype(jnp.float32)]

    # rows a grid step takes, the largest divisor of B up to ROWS_PER_STEP: a
    # step's own cost and its three small operand DMAs are paid once for them,
    # dead rows included (26.1 -> 24.2 us a call at 7 live rows of 32; PR 28)
    block_rows = next(r for r in range(min(b, ROWS_PER_STEP), 0, -1) if b % r == 0)

    steps = b // block_rows  # grid steps of one slice of the kv heads

    def row_map(gi, *refs):
        if head_slices == 1:
            return (gi, 0, 0, 0)
        return (gi % steps, gi // steps, 0, 0)  # slice after slice, each over every row

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    slot = pltpu.VMEM((2, heads, wave * page_size, hd), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(head_slices * steps,),
        in_specs=[
            pl.BlockSpec((block_rows, heads, group, hd), row_map),
            in_hbm,
            in_hbm,
            pl.BlockSpec((block_rows, heads, n_steps, hd), row_map),
            pl.BlockSpec((block_rows, heads, n_steps, hd), row_map),
        ],
        out_specs=pl.BlockSpec((block_rows, heads, group, hd), row_map),
        scratch_shapes=[
            slot,
            slot,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, group, 128), jnp.float32),
            pltpu.VMEM((heads, group, 128), jnp.float32),
            pltpu.VMEM((heads, group, hd), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _burst_kernel, page_size=page_size, scale=1.0 / (hd ** 0.5), wave=wave,
        layered=layered, kv_quant=kv_quant, head_slices=head_slices, sliding=sliding,
        bf16_products=bf16_products,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, group, hd), q.dtype),
        # rows in order on one core: the first step zeroes the V slots, and
        # each live row starts the first DMAs of the next
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, q_r, k_pages, v_pages, staged_k, staged_v)

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
