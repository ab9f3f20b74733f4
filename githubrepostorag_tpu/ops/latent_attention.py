"""Attention over a latent (MLA) page pool: one row ``[c_kv | k_rope | pad]``
a token and layer (``rank + rope`` columns used, zeros up to whole lane
tiles), no V pool.

Two paths over the same pool, because the two shapes of work want opposite
things (DeepSeek-V2, arXiv:2405.04434, section 2.1):

* **decode** (``latent_decode_attention``): the absorbed form.  The
  up-projection of the keys is folded into the query (``q' = q_nope W_uk^T``)
  and that of the values is applied after the weighted sum, so every head
  attends the 576-wide latent row directly: multi-query attention with 128
  query heads over one shared key of width 576 whose first 512 columns are
  also the value.  A token of context costs one row read (1,152 B in
  bfloat16) and 2 * H * (576 + 512) FLOPs.  On the chip a Pallas kernel walks
  the pages that live rows hold with its own DMAs (ops/pallas_paged.py's
  burst kernel over one pool) and folds in the burst's staged tail; elsewhere
  a gather and dense products stand in for it and are its oracle.

* **prefill of a chunk** (``latent_prefill_attention``): the materialised
  form.  For S new tokens against a long cached prefix the absorbed form
  costs about twice the FLOPs, so ``k_nope`` and ``v`` are rebuilt from the
  cached latents a page at a time (a whole 8k prefix would be 537 MB a row
  and layer) under an online softmax.  On the chip a Pallas kernel does it
  per (row, a few heads), K, V and the scores living in VMEM only, and does
  only what the wave's (query, key) pairs need: the query tiles of a row
  that hold a real token (the wave's padding is not computed) and the heads
  of a step in one basic block, so that one's products hide another's
  softmax (PERF.md, Findings, PR 53; ``prefill_tile_counts`` is the same
  rules on the host).  Elsewhere
  an XLA loop over tiles of pages is its oracle (on the chip its [H, S, tile]
  float32 scores crossed HBM several times a tile: 5% of the FLOP peak,
  PERF.md, Findings, PR 27).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from githubrepostorag_tpu.runtime import on_tpu

NEG_INF = -1e30


def einsum_f32(eq: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """einsum accumulated and returned in float32.  On the chip the operands
    stay as stored (bfloat16 on the MXU); XLA's CPU runtime has no
    bf16 x bf16 -> f32 product inside a loop body, so off the chip (tests,
    the rehearsal) they are widened first."""
    if on_tpu():
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))


def _softmax_step(s, values, m_ref, l_ref, acc_ref):
    """One online-softmax update in VMEM: scores ``s`` [M, T] float32 over
    ``values`` [T, v]; (m, l) live in column 0 of their [M, 128] scratch."""
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[:, :1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(values.dtype), values, preferred_element_type=jnp.float32)
    m_ref[:, :1] = m_new


PAGES_PER_STEP = 8  # my chip runs, PR 27, one layer at the cell's shapes: the prefill kernel
# (1 x 512 queries over 8,704 rows) 20.6 / 9.7 / 6.6 / 5.6 ms at 1 / 2 / 4 / 8 pages a step, a head
# a step and every column computed; 4.3 ms at 8 since PR 53 (358 of the columns real: PERF.md
# section 5).  The decode kernel walks in waves of its own since PR 48: DECODE_WAVE_PAGES, below


TILE_PAGES = 4  # pages of K and V the XLA oracle of the prefill path rebuilds at a time


QUERY_TILE = 128  # columns of a chunk the prefill kernel runs or leaves out together


HEAD_COLUMNS = 1024  # columns over all the heads a grid step of the prefill kernel takes.  My chip
# runs, PR 53: the body is unrolled over heads and live tiles, and a kernel of more than ~64k VLIW
# bundles runs 2-4 times slower (61.6k: 8.5 ms a two-row call at 512 columns; 69k: 21.6 ms); at
# 1,024 the three rungs are 30k / 24k / 21k bundles; four heads at 512 columns want 40 MB of VMEM


def _query_tile(s: int) -> int:
    """Columns of one query tile: ``QUERY_TILE`` where it divides the chunk,
    else the chunk whole."""
    return QUERY_TILE if s % QUERY_TILE == 0 else s


def _heads_per_step(h: int, s: int) -> int:
    """Heads one grid step of the prefill kernel takes: the step's pages are
    brought in and laid under one another once for all of them, and one head's
    products run under another's softmax.  As many as keep heads x columns
    within ``HEAD_COLUMNS`` (2 / 4 / 8 at 512 / 256 / 128 columns); must
    divide the head count."""
    return next(n for n in range(min(h, max(1, HEAD_COLUMNS // s)), 0, -1) if h % n == 0)


def _pages_per_step(max_pages: int) -> int:
    """Pages one grid step of the prefill kernel reads: the pool is handed to
    it that many times, each operand's index map picking its own page of the
    block table, so a step's products are that many pages wide and its fixed
    cost (~0.35 us, and small products that leave the MXU idle) is paid that
    much less often.  Must divide the table's width."""
    return next(n for n in (PAGES_PER_STEP, 4, 2, 1) if max_pages % n == 0)


# ------------------------------------------------------------------ decode --

DECODE_WAVE_PAGES = 8  # pages a wave of the decode kernel holds.  My chip runs, PR 48, one layer
# at the cell's shapes (18 live rows of 8.3-9.4k of 32): 0.370 ms a call; 4 pages 0.376, 16 pages
# 0.389; with the arithmetic taken out 0.286 (the DMAs: 713 GB/s), with the DMAs taken out 0.331


WAVE_SLOTS = 5  # the wave folded, the two whose scores are taken beside it, two in flight (7 or 9
# slots: the same 0.370 ms)


def _decode_kernel(bt_ref, lens_ref, slen_ref, layer_ref, q_ref, pool_hbm, st_ref, out_ref,
                   buf, sems, state, s_ref, m_ref, l_ref, acc_ref, *, page_size: int, rank: int,
                   wave: int):
    """Absorbed decode attention: online softmax over [the pages a row holds
    | the burst's staged tail], ops/pallas_paged.py:_burst_kernel's walk over
    one latent pool.  Grid (B / R,): a step takes R row slots one after the
    other.  The pages of the LIVE rows are one stream of waves of ``wave``
    pages (a row's ``ceil(len / page_size)`` pages, row after row): each page
    is one DMA from the pool in HBM straight into rows [j * page_size, (j + 1)
    * page_size) of a VMEM slot, so a wave is one contiguous [wave *
    page_size, width] tile, and the stream is started three to five waves
    ahead of the wave being folded, across rows and grid steps.  A dead row
    (length 0) is no part of it: no page, no DMA, no grid step; it costs the
    one small product over its staged rows.

    A row's waves are folded two at a time, and a wave's scores are taken in
    the basic block that folds the wave before it (the even waves' through
    ``s_ref``, the odd ones' as a value), so its products run on the MXU while
    the other's softmax runs on the VPU: one wave at a time, product -> max ->
    exp -> weighted sum is a chain, and the MXU waits through the softmax
    (0.411 ms a call against 0.370, PR 48).  Only a row's last wave can hold a
    key past its length, so only it is masked.  The row then folds in its
    staged rows (positions < ``staged_len``) and writes its normalised output.

    One score product over the STORED width: q = [q_lat | q_rope | 0] against
    rows [c_kv | k_rope | pad]; the values are a row's first ``rank`` columns,
    a lane-aligned view of the same tile.  Products take the pool's dtype
    (bfloat16) and accumulate in float32: at 242 FLOP per byte the kernel sits
    on the v5e's ridge, and a float32 product would put it far on the wrong
    side.

    Refs: scalar prefetch [block tables (B, max_pages), pool lens (B), staged
    len (1), layer (1)], q (R, H, width), the pool WHOLE in HBM, staged (R,
    n_steps, width), out (R, H, rank), scratch [wave slots (WAVE_SLOTS, wave *
    page_size, width), their DMA semaphores, the stream's state (4,) SMEM, the
    even waves' scores (H, wave * page_size), m, l (H, 128), acc (H, rank)
    float32]."""
    rows, max_pages = bt_ref.shape
    block_rows, slots = q_ref.shape[0], buf.shape[0]
    pool = pool_hbm.at[layer_ref[0], 0]  # [P, page_size, width]
    nt = (((1,), (1,)), ((), ()))  # a [m, k] . b [n, k] -> [m, n]
    # the stream's state: the (row, wave) to start next and its slot; the next wave folded's slot
    next_row, next_wave, next_slot, fold_slot = range(4)

    def waves_of(row):
        return (lens_ref[row] + wave * page_size - 1) // (wave * page_size)

    def page_dmas(row, w, slot, go):
        """``go`` (start or wait) on the DMA of every page ``row`` holds in
        its wave ``w``, each into its own rows of slot ``slot``."""
        held = (lens_ref[row] + page_size - 1) // page_size
        for j in range(wave):
            @pl.when(w * wave + j < held)
            def _():
                page = bt_ref[row, jnp.minimum(w * wave + j, max_pages - 1)]
                go(pltpu.make_async_copy(
                    pool.at[page], buf.at[slot, pl.ds(j * page_size, page_size)], sems.at[slot]))

    def live_row(after):
        """The first live row at or after ``after`` (``rows``: none)."""
        return jax.lax.while_loop(
            lambda r: (r < rows) & (lens_ref[jnp.minimum(r, rows - 1)] == 0),
            lambda r: r + 1, after)

    def start_next():
        """Start the stream's next wave, if it has one, and step its state."""
        row, w, slot = state[next_row], state[next_wave], state[next_slot]

        @pl.when(row < rows)
        def _():
            page_dmas(row, w, slot, lambda dma: dma.start())
            last = w + 1 >= waves_of(row)
            state[next_row] = jnp.where(last, live_row(row + 1), row)
            state[next_wave] = jnp.where(last, 0, w + 1)
            state[next_slot] = (slot + 1) % slots

    # read out here: the interpreter has no program_id inside a loop's body
    first_row = pl.program_id(0) * block_rows

    @pl.when(first_row == 0)
    def _():
        # a row's last wave fills only the pages the row holds; what the rest
        # of the slot holds meets a weight of exactly 0 and must be finite
        buf[...] = jnp.zeros_like(buf)
        state[next_row] = live_row(0)
        state[next_wave] = 0
        state[next_slot] = 0
        state[fold_slot] = 0
        for _ in range(slots - 2):
            start_next()

    def one_row(r, carry):
        bi = first_row + r
        total = lens_ref[bi]
        n_waves = waves_of(bi)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[r]  # [H, width]
        slot0 = state[fold_slot]  # where this row's first wave was sent

        def slot_of(w):
            return (slot0 + w) % slots

        def landed(w):
            page_dmas(bi, w, slot_of(w), lambda dma: dma.wait())

        def scores(w):
            return jax.lax.dot_general(q, buf[slot_of(w)], nt, preferred_element_type=jnp.float32)

        def fold(s, w, last: bool):
            if last:
                kv_pos = w * wave * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(kv_pos < total, s, NEG_INF)
            _softmax_step(s, buf[slot_of(w), :, :rank], m_ref, l_ref, acc_ref)

        @pl.when(n_waves > 0)
        def _():
            landed(0)
            s_ref[...] = scores(0)

        def two_waves(k, carry):
            """Waves 2k and 2k + 1, where 2k + 2 is there too: neither is the last."""
            w = 2 * k
            start_next()
            start_next()
            landed(w + 1)
            landed(w + 2)
            s_odd = scores(w + 1)
            fold(s_ref[...], w, last=False)
            s_ref[...] = scores(w + 2)
            fold(s_odd, w + 1, last=False)
            return carry

        pairs = jnp.maximum(n_waves - 1, 0) // 2
        jax.lax.fori_loop(0, pairs, two_waves, 0)
        w = 2 * pairs  # what is left: waves w and w + 1, wave w, or nothing (a dead row)

        @pl.when(n_waves - w == 2)
        def _():
            start_next()
            start_next()
            landed(w + 1)
            s_odd = scores(w + 1)
            fold(s_ref[...], w, last=False)
            fold(s_odd, w + 1, last=True)

        @pl.when(n_waves - w == 1)
        def _():
            start_next()
            fold(s_ref[...], w, last=True)

        state[fold_slot] = slot_of(n_waves)

        staged = st_ref[r]  # [n_steps, width]
        s = jax.lax.dot_general(q, staged, nt, preferred_element_type=jnp.float32)
        idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _softmax_step(jnp.where(idx < slen_ref[0], s, NEG_INF), staged[:, :rank],
                      m_ref, l_ref, acc_ref)
        l = l_ref[:, :1]  # staged_len >= 1, so l > 0 for dead rows too
        out_ref[r] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_rows, one_row, 0)


DECODE_ROWS_PER_STEP = 8  # row slots one grid step of the decode kernel takes (4: the same time)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(q_lat, q_rope, pool, layer, block_tables, pool_lens, staged, staged_len,
                   interpret: bool):
    """Jitted for its trace cache alone, as ``_prefill_pallas`` is: the dense
    and the expert stack of every burst program call it with the same shapes,
    and the kernel's unrolled DMA descriptors make it the dearest thing in a
    burst to trace and to lower."""
    b, h, rank = q_lat.shape
    page_size, width = pool.shape[3], pool.shape[4]
    max_pages, n_steps = block_tables.shape[1], staged.shape[1]
    wave = min(DECODE_WAVE_PAGES, max_pages)
    block_rows = next(r for r in range(min(b, DECODE_ROWS_PER_STEP), 0, -1) if b % r == 0)
    # the pad columns of the pool are zeros by construction; the query's are made so here
    pad = jnp.zeros((b, h, width - rank - q_rope.shape[-1]), q_lat.dtype)
    q = jnp.concatenate([q_lat, q_rope, pad], axis=-1)

    def row_map(gi, *refs):
        return (gi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, h, width), row_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((block_rows, n_steps, width), row_map)],
        out_specs=pl.BlockSpec((block_rows, h, rank), row_map),
        scratch_shapes=[pltpu.VMEM((WAVE_SLOTS, wave * page_size, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((WAVE_SLOTS,)), pltpu.SMEM((4,), jnp.int32),
                        pltpu.VMEM((h, wave * page_size), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32), pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, rank), jnp.float32)],
    )
    call = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, rank=rank, wave=wave),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q_lat.dtype),
        # rows in order on one core: the first step zeroes the slots and starts
        # the stream, which runs ahead of the rows across steps
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
    # XLA names the custom call after the innermost scope: the name a trace's
    # reader finds it by, kept under this function's own jit
    with jax.named_scope("latent_attention"):
        return call(block_tables.astype(jnp.int32), pool_lens.astype(jnp.int32),
                    jnp.reshape(staged_len, (1,)).astype(jnp.int32),
                    jnp.reshape(layer, (1,)).astype(jnp.int32), q, pool, staged)


def _decode_gather(q_lat, q_rope, pool, layer, block_tables, pool_lens, staged, staged_len):
    """The oracle: every page of every row gathered, dense products."""
    b, h, rank = q_lat.shape
    ps, used = pool.shape[3], rank + q_rope.shape[-1]
    rows = pool[layer, 0, block_tables].reshape(b, -1, pool.shape[-1])  # [B, mp*ps, W]
    kv = jnp.concatenate([rows, staged], axis=1)[..., :used].astype(jnp.float32)
    valid = jnp.concatenate([
        jnp.arange(block_tables.shape[1] * ps)[None, :] < pool_lens[:, None],
        jnp.broadcast_to(jnp.arange(staged.shape[1])[None, :] < staged_len, (b, staged.shape[1])),
    ], axis=1)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q, kv)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btc->bhc", p, kv[..., :rank]).astype(q_lat.dtype)


def latent_decode_attention(
    q_lat: jnp.ndarray,  # [B, H, rank] absorbed queries, softmax scale applied
    q_rope: jnp.ndarray,  # [B, H, rope] rotated, scale applied
    pool: jnp.ndarray,  # [L, 1, P, page_size, >= rank + rope], whole
    layer: jnp.ndarray,  # [] int32
    block_tables: jnp.ndarray,  # [B, max_pages]
    pool_lens: jnp.ndarray,  # [B] rows of the pool that are valid for each row
    staged: jnp.ndarray,  # [B, n_steps, pool width] this burst's rows so far
    staged_len: jnp.ndarray,  # [] int32, how many of them are valid (>= 1)
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """softmax(q . [c_kv | k_rope]) c_kv over [pool prefix | staged tail]
    -> [B, H, rank]; the caller applies W_uv and W_o."""
    if use_pallas:
        return _decode_pallas(q_lat, q_rope, pool, layer, block_tables, pool_lens, staged,
                              staged_len, interpret)
    return _decode_gather(q_lat, q_rope, pool, layer, block_tables, pool_lens, staged,
                          staged_len)


# ----------------------------------------------------------------- prefill --

def _step_runs(start, kv_len):
    """A key step of the prefill kernel's walk holds a key of the row."""
    return start < kv_len


def _live_tiles(new, tile: int):
    """Query tiles of a row that hold a real token: the others are padding up
    to the wave's width and are not computed."""
    return (new + tile - 1) // tile


def _causal_mask(sc, start, cached, kv_len):
    """Scores [columns, keys] of a step from key ``start`` with every key a
    query may not see at ``NEG_INF``: the keys after its own position
    (``cached`` + its column) and those past the row's last."""
    kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    q_pos = cached + jax.lax.broadcasted_iota(jnp.int32, (sc.shape[0], 1), 0)
    return jnp.where(kv_pos <= jnp.minimum(q_pos, kv_len - 1), sc, NEG_INF)


@jax.jit
def _head_scores(c_kv, k_rope, w_uk, w_uv, q, start, cached, kv_len):
    """One head's K and V of a step rebuilt from its latent rows (c_kv
    W_uk,h^T and c_kv W_uv,h, bfloat16 out of float32 sums) and the masked
    scores of its queries against them: w_uk [1, rank, nope], w_uv [1, rank,
    v], q [1, 1, columns, nope + rope] (the kernel's blocks of one head, as
    sliced off their refs) -> ([columns, keys] float32, V [keys, v]).
    Jitted for its trace cache alone, as ``_head_fold`` is: the kernel's
    bodies are unrolled over heads and live columns and every rung and row
    bucket has a kernel of its own, so traced inline they cost a server 7 s of
    its start (PERF.md, Findings, PR 53); so a shape is traced once a process."""
    nope = w_uk.shape[-1]
    q = q[0, 0]
    nt = (((1,), (1,)), ((), ()))  # a [m, k] . b [n, k] -> [m, n]
    k_nope = jnp.dot(c_kv, w_uk[0], preferred_element_type=jnp.float32).astype(c_kv.dtype)
    v = jnp.dot(c_kv, w_uv[0], preferred_element_type=jnp.float32).astype(c_kv.dtype)
    sc = jax.lax.dot_general(q[:, :nope], k_nope, nt, preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(q[:, nope:], k_rope, nt, preferred_element_type=jnp.float32)
    return _causal_mask(sc, start, cached, kv_len), v


@jax.jit
def _head_fold(sc, v, m_prev, l_prev, acc):
    """``_softmax_step``'s update as values: scores [columns, keys] over ``v``
    into a head's (m, l [1, columns, 1], acc [1, columns, v])."""
    m_new = jnp.maximum(m_prev[0], jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev[0] - m_new)
    p = jnp.exp(sc - m_new)
    l_new = l_prev[0] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc[0] * alpha + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return m_new[None], l_new[None], acc_new[None]


def prefill_tile_counts(cached_lens, new_lens, columns: int, max_pages: int,
                        page_size: int) -> dict[str, int]:
    """On the host, what ``_prefill_kernel`` does with a wave by the rules it
    and its wrapper go by: the (query tile, key step) pairs of the rows' grid
    it ``run``s and the ones it ``skipped`` (no real query in the tile, or no
    key of the row in the step).  The two add up to rows x tiles a row x
    steps of the table."""
    span = _pages_per_step(max_pages) * page_size
    tile = _query_tile(columns)
    counts = {"run": 0, "skipped": 0}
    for cached, new in zip(map(int, cached_lens), map(int, new_lens)):
        for start in range(0, max_pages * page_size, span):
            live = _live_tiles(new, tile) if _step_runs(start, cached + new) else 0
            counts["run"] += live
            counts["skipped"] += columns // tile - live
    return counts


def _prefill_kernel(*refs, page_size: int, rank: int, pps: int, tile: int):
    """Grid (B, H / hb, max_pages / pps): ``hb`` heads of one row walk the
    row's block table ``pps`` pages at a time.  A step lays its pages under
    one another once, rebuilds K and V of each head from the latent rows in
    VMEM (``_head_scores``), takes the scores of the row's LIVE columns (whole
    query tiles up to its last real token: the rest are the wave's padding,
    stay zero and cost nothing) and folds them into that head's online softmax
    (m, l, acc: ``_head_fold``); scores never leave VMEM.  The heads of a step
    are unrolled in ONE basic block a case of live columns, so that one head's
    products run on the MXU under another's softmax on the VPU.  Steps past
    the row's last key skip compute and re-use page 0's block.

    Refs: scalar prefetch [block tables, cached lens, kv lens, layer], blocks
    [q = [q_nope | q_rope] (1, hb, S, nope + rope), ``pps`` pages of the pool,
    W_uk^T (hb, rank, nope), W_uv (hb, rank, v)], out (1, hb, S, v), scratch
    [m, l (hb, S, 128), acc (hb, S, v)]."""
    bt_ref, cached_ref, lens_ref, layer_ref, q_ref = refs[:5]
    k_refs = refs[5:5 + pps]
    wuk_ref, wuv_ref, out_ref, m_ref, l_ref, acc_ref = refs[5 + pps:]
    bi, pi = pl.program_id(0), pl.program_id(2)
    num_pi = pl.num_programs(2)
    hb, s, _ = q_ref.shape[1:]
    rope = q_ref.shape[-1] - wuk_ref.shape[-1]

    @pl.when(pi == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len, cached = lens_ref[bi], cached_ref[bi]
    start = pi * pps * page_size

    def fold(live: int):
        """The step for the first ``live`` columns of every head of the block.
        Refs are cut with slices only, a head's h:h + 1 too: an integer index
        is traced four times as slowly, and this is traced and lowered for
        every case of every rung's and row bucket's kernel."""
        rows = [k[...].reshape(page_size, -1) for k in k_refs]  # pps x [page_size, width]
        page_rows = rows[0] if pps == 1 else jnp.concatenate(rows, axis=0)
        c_kv, k_rope = page_rows[:, :rank], page_rows[:, rank:rank + rope]

        def scores(h):
            return _head_scores(c_kv, k_rope, wuk_ref[h:h + 1], wuv_ref[h:h + 1],
                                q_ref[:, h:h + 1, :live, :], start, cached, kv_len)

        ahead = scores(0)
        for h in range(hb):  # a head's products are asked for before the softmax of the one before
            sc, v = ahead
            if h + 1 < hb:
                ahead = scores(h + 1)
            at = (slice(h, h + 1), slice(0, live))
            m_ref[(*at, slice(0, 1))], l_ref[(*at, slice(0, 1))], acc_ref[at] = _head_fold(
                sc, v, m_ref[(*at, slice(0, 1))], l_ref[(*at, slice(0, 1))], acc_ref[at])

    runs, live_tiles = _step_runs(start, kv_len), _live_tiles(kv_len - cached, tile)
    for n in range(1, s // tile + 1):
        pl.when(runs & (live_tiles == n))(functools.partial(fold, n * tile))

    @pl.when(pi == num_pi - 1)
    def _():
        l = l_ref[:, :, :1]  # a padding row walks no page, a padding tile folds nothing: l == 0
        out_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _prefill_pallas(q, pool, layer, block_tables, cached_lens, kv_lens, w_uk, w_uv,
                    interpret: bool):
    """q [B, H, S, nope + rope] (scaled) -> [B, H, S, v].
    Jitted for its trace cache alone (it is only ever called inside a step
    program): the dense and the expert stack call it with the same shapes,
    and tracing the call is most of what tracing a layer costs."""
    b, h, s, _ = q.shape
    nope, rank, vd = w_uk.shape[1], w_uk.shape[2], w_uv.shape[-1]
    page_size, width = pool.shape[3], pool.shape[4]
    max_pages = block_tables.shape[1]
    pps = _pages_per_step(max_pages)
    hb = _heads_per_step(h, s)

    def q_map(bi, hi, pi, *refs):
        return (bi, hi, 0, 0)

    def w_map(bi, hi, pi, *refs):
        return (hi, 0, 0)

    def page_map(j):
        def index(bi, hi, pi, bt, cached, lens, li):
            at = pi * pps + j
            return (li[0], 0, jax.lax.select(at * page_size < lens[bi], bt[bi, at], 0), 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, h // hb, max_pages // pps),
        in_specs=[pl.BlockSpec((1, hb, s, q.shape[-1]), q_map)]
        + [pl.BlockSpec((1, 1, 1, page_size, width), page_map(j)) for j in range(pps)]
        + [pl.BlockSpec((hb, rank, nope), w_map), pl.BlockSpec((hb, rank, vd), w_map)],
        out_specs=pl.BlockSpec((1, hb, s, vd), q_map),
        scratch_shapes=[pltpu.VMEM((hb, s, 128), jnp.float32),
                        pltpu.VMEM((hb, s, 128), jnp.float32),
                        pltpu.VMEM((hb, s, vd), jnp.float32)],
    )
    call = pl.pallas_call(
        functools.partial(_prefill_kernel, page_size=page_size, rank=rank, pps=pps,
                          tile=_query_tile(s)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    # XLA names the custom call after the innermost scope: the name a trace's
    # reader finds it by, kept under this function's own jit
    with jax.named_scope("latent_prefill_attention"):
        return call(block_tables.astype(jnp.int32), cached_lens.astype(jnp.int32),
                    kv_lens.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
                    q, *([pool] * pps), w_uk.swapaxes(1, 2), w_uv)


def latent_prefill_attention(
    q_nope: jnp.ndarray,  # [B, S, H, nope]
    q_rope: jnp.ndarray,  # [B, S, H, rope] rotated
    pool: jnp.ndarray,  # [L, 1, P, page_size, >= rank + rope], the chunk's rows already written
    layer: jnp.ndarray,  # [] int32
    block_tables: jnp.ndarray,  # [B, max_pages]
    cached_lens: jnp.ndarray,  # [B] rows cached before this chunk
    new_lens: jnp.ndarray,  # [B] valid new tokens
    w_uk: jnp.ndarray,  # [H, nope, rank]
    w_uv: jnp.ndarray,  # [H, rank, v]
    scale: float,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal attention of a chunk over its row's cached prefix and itself,
    K and V rebuilt from the latent rows a page (kernel) or ``TILE_PAGES``
    pages (XLA, the oracle) at a time; pages past the longest row are not
    visited.  -> [B, S, H, v]."""
    b, s, h, _ = q_nope.shape
    kv_lens = cached_lens + new_lens
    qn = (q_nope.astype(jnp.float32) * scale).astype(q_nope.dtype)
    qr = (q_rope.astype(jnp.float32) * scale).astype(q_rope.dtype)
    if use_pallas:
        q = jnp.concatenate([qn, qr], axis=-1).swapaxes(1, 2)  # one block a head for the kernel
        return _prefill_pallas(q, pool, layer, block_tables, cached_lens, kv_lens, w_uk, w_uv,
                               interpret).swapaxes(1, 2)
    ps, width = pool.shape[3], pool.shape[4]
    rank, vd, rope = w_uk.shape[-1], w_uv.shape[-1], q_rope.shape[-1]
    max_pages = block_tables.shape[1]
    tp = min(TILE_PAGES, max_pages)
    tile = tp * ps
    n_tiles = (jnp.max(kv_lens) + tile - 1) // tile
    q_pos = cached_lens[:, None] + jnp.arange(s)[None, :]  # [B, S]
    # the table padded so that the last tile's slice never runs off its end
    pad = (-max_pages) % tp
    table = jnp.pad(block_tables, ((0, 0), (0, pad)))

    def body(t, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice(table, (0, t * tp), (b, tp))
        rows = pool[layer, 0, pages].reshape(b, tile, width)  # one gather from the pool
        c_kv, k_rope = rows[..., :rank], rows[..., rank:rank + rope]
        k_nope = jnp.einsum("btc,hnc->bthn", c_kv, w_uk)
        v = jnp.einsum("btc,hcv->bthv", c_kv, w_uv)
        sc = einsum_f32("bshn,bthn->bhst", qn, k_nope) + einsum_f32("bshr,btr->bhst", qr, k_rope)
        kv_pos = t * tile + jnp.arange(tile)
        ok = (kv_pos[None, None, :] <= q_pos[:, :, None]) \
            & (kv_pos[None, None, :] < kv_lens[:, None, None])  # [B, S, T]
        sc = jnp.where(ok[:, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + einsum_f32("bhst,bthv->bhsv", p.astype(v.dtype), v)
        return m_new, l, acc

    m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
    carry = (m0, jnp.zeros((b, h, s), jnp.float32), jnp.zeros((b, h, s, vd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_tiles, body, carry)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]  # padding rows: l == 0
    return out.swapaxes(1, 2).astype(q_nope.dtype)  # [B, S, H, v]
