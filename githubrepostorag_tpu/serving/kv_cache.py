"""Paged KV cache: device-side page pools + host-side page allocator.

The vLLM idea (PagedAttention) rebuilt for TPU/XLA: K/V live in fixed page
pools ``[L, num_pages, page_size, n_kv, hd]`` so sequences grow without
reallocation or copy; a sequence's pages are an indirection table
(``block_table``).  Writes name flat slots with out-of-bounds drop
semantics (padding tokens get slot -1) and go through ``commit_paged``,
which updates the donated pools in place: runs of consecutive slots as
windows of slots, anything else a row per slot.

Host side, the ``PageAllocator`` is plain Python — allocation decisions are
control flow, not compute, and belong off-device (SURVEY.md §7 stage 2).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from githubrepostorag_tpu.models.qwen2 import Qwen2Config
from githubrepostorag_tpu.ops.prefill_width import MAX_RUNG_ROWS
from githubrepostorag_tpu.serving.chain_hash import chain_hashes


@dataclass
class PagePools:
    """Device arrays holding every sequence's K/V pages for all layers.

    Layout [L, n_kv, P, page_size, hd] keeps each page's (page_size, hd)
    slab contiguous in the trailing two axes — the natural (sublane, lane)
    tile for the Pallas kernel's page DMAs — and lets the KV commit index a
    flat [n_kv, P*page_size, hd] view (or its windows of whole tiles) with
    one slot vector shared by all heads.

    ``ks``/``vs``: per-PAGE dequant scales [L, n_kv, P] f32
    when the pools are int8 (``kv_quant`` engines — each cached token
    vector is symmetric int8 with its own scale: no calibration, and the
    scale read is 1/hd of the payload); None for full-precision pools.

    A latent (MLA) model has ONE pool: ``k`` holds a row ``[c_kv | k_rope]``
    a token and layer ([L, 1, P, page_size, kv_lora_rank + rope padded to
    whole lane tiles]: ``cfg.head_dim``) and ``v`` is None.  Allocator, prefix cache and block tables are per token
    position and do not change."""

    k: jnp.ndarray  # [L, n_kv, P, page_size, hd]
    v: jnp.ndarray | None
    ks: jnp.ndarray | None = None  # [L, n_kv, P] f32 (per-page)
    vs: jnp.ndarray | None = None

    @property
    def num_pages(self) -> int:
        return self.k.shape[2]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]


def quant_bits(quant) -> int:
    """Normalize the ``kv_quant`` knob to a bit width: 0 (off), 8 (int8
    pages), or 4 (nibble-packed int4 pages).  Accepts the historical bool,
    the Settings int, or the env-style string."""
    if quant is None or quant is False:
        return 0
    if quant is True:
        return 8
    if isinstance(quant, int) and quant in (0, 4, 8):
        return quant
    val = str(quant).strip().lower()
    if val in {"", "0", "false", "off"}:
        return 0
    if val in {"1", "true", "on", "int8", "8"}:
        return 8
    if val in {"int4", "4"}:
        return 4
    raise ValueError(f"kv_quant={quant!r} not understood; use int4, int8, or a bool")


def page_kinds(cfg) -> tuple:
    """The kinds of page a configuration object states: ``(name, layers, window
    or None)`` each, the first the GLOBAL kind (pages that keep every key of a
    sequence for its life: ``num_pages``, the allocator and the block tables
    every model has), any other a SLIDING kind (pages of layers that attend the
    last ``window`` keys and nothing older: a pool, a table and a ledger of
    their own, ``SlidingPages``).  A model that states none is one global kind
    of ``kv_layers`` (or all its) layers."""
    stated = getattr(cfg, "page_kinds", None)
    if stated:
        return tuple(stated)
    return (("global", getattr(cfg, "kv_layers", cfg.num_layers), None),)


def make_page_pools(
    cfg: Qwen2Config, num_pages: int, page_size: int, dtype=jnp.bfloat16,
    quant=False, layers: int | None = None,
) -> PagePools:
    # a hybrid model pages keys and values in some of its layers only; a kind
    # of page other than the global one says how many layers it serves
    if layers is None:
        layers = getattr(cfg, "kv_layers", cfg.num_layers)
    shape = (layers, cfg.num_kv_heads, num_pages, page_size, cfg.head_dim)
    bits = quant_bits(quant)
    if getattr(cfg, "latent_kv", False):
        if bits:
            raise ValueError("a latent page pool has no quantized form")
        return PagePools(k=jnp.zeros(shape, dtype=dtype), v=None)
    if bits == 4:
        # int4: two head components share a byte (pack_int4's nibble
        # planes), so the payload axis is hd//2 uint8 — the dtype is the
        # discriminator every consumer keys on (uint8 pools = int4).
        # Scales stay per-page f32 exactly like int8.
        if cfg.head_dim % 2:
            raise ValueError("int4 KV pages need an even head_dim")
        packed = (*shape[:-1], cfg.head_dim // 2)
        return PagePools(
            k=jnp.zeros(packed, dtype=jnp.uint8),
            v=jnp.zeros(packed, dtype=jnp.uint8),
            ks=jnp.zeros(shape[:-2], dtype=jnp.float32),
            vs=jnp.zeros(shape[:-2], dtype=jnp.float32),
        )
    if bits == 8:
        # per-PAGE scales [L, n_kv, P] (quantize_kv_paged): small enough
        # for the decode kernel's scalar-prefetch channel — per-token
        # scale tiles cost 5-18x in per-grid-step DMAs (r04)
        return PagePools(
            k=jnp.zeros(shape, dtype=jnp.int8),
            v=jnp.zeros(shape, dtype=jnp.int8),
            ks=jnp.zeros(shape[:-2], dtype=jnp.float32),
            vs=jnp.zeros(shape[:-2], dtype=jnp.float32),
        )
    return PagePools(k=jnp.zeros(shape, dtype=dtype), v=jnp.zeros(shape, dtype=dtype))


def make_state_pools(cfg, slots: int) -> dict:
    """Zeroed state pools of ``slots`` slots for a model with recurrent state,
    from what its configuration object states: ``state_layers`` layers, each
    slot the arrays of ``state_shapes()`` ((shape, dtype) by name).  The
    engine adds the slot that takes dropped writes itself (``StateSlots``)."""
    return {name: jnp.zeros((cfg.state_layers, slots, *shape), dtype)
            for name, (shape, dtype) in cfg.state_shapes().items()}


def quantize_kv(x: jnp.ndarray):
    """Per-token-vector symmetric int8: ``x`` [..., hd] ->
    (q int8 [..., hd], scale f32 [...]).  Kept as the reference recipe for
    tests; the POOLS use per-page scales (quantize_kv_paged) — device
    profiling showed the per-token scale tiles' tiny per-grid-step DMAs
    costing the staged kernel 5-18x, while int8 pages with no scale
    operands ran at bf16 speed (r04)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


# headroom on a page's first-write scale: later tokens appended to the same
# page reuse it, so the first chunk's amax gets margin before clipping
KV_SCALE_HEADROOM = 1.25


def quantize_kv_paged(
    vals: jnp.ndarray,  # [..., N, hd] new K or V vectors (any leading dims)
    flat_slots: jnp.ndarray,  # [N] int32 pool slots; >= P*ps means dropped
    scales: jnp.ndarray,  # [..., P] f32 per-page scales (0 = never written)
    page_size: int,
    qmax: int = 127,  # 127 for int8 pages, 7 for int4 nibbles
):
    """Per-PAGE symmetric int8 quantization for pool writes.

    A page's scale is fixed by the FIRST write that touches it (detected
    as this batch containing the page's slot 0 — sequential fills always
    open a page at its first slot) from that write's amax with
    KV_SCALE_HEADROOM margin; later appends to a partially-filled page
    reuse the stored scale and clip at +-127.  Per-page (not per-token)
    because scales must reach the decode kernel WITHOUT per-grid-step
    operand tiles: [n_kv, P] rides the scalar-prefetch SMEM channel like
    the block tables, costing zero extra DMAs (VERDICT r03 #4b).

    Returns (q int8 [..., N, hd], new_scales [..., P])."""
    p = scales.shape[-1]
    lead = scales.shape[:-1]
    total = p * page_size
    page_of = jnp.where(
        (flat_slots >= 0) & (flat_slots < total), flat_slots // page_size, p
    )  # sentinel page p -> dropped by the scatters below
    amax = jnp.max(jnp.abs(vals.astype(jnp.float32)), axis=-1)  # [..., N]
    zeros_ext = jnp.zeros((*lead, p + 1), jnp.float32)
    page_amax = zeros_ext.at[..., page_of].max(amax, mode="drop")
    fresh = jnp.zeros((p + 1,), bool).at[
        jnp.where(flat_slots % page_size == 0, page_of, p)
    ].set(True, mode="drop")
    scale_new = jnp.maximum(page_amax * (KV_SCALE_HEADROOM / qmax), 1e-8)
    scales_ext = jnp.concatenate(
        [scales, jnp.ones((*lead, 1), jnp.float32)], axis=-1
    )
    upd = jnp.where(fresh, scale_new, scales_ext)
    tok_scale = jnp.take_along_axis(
        upd, jnp.broadcast_to(page_of, (*lead, page_of.shape[0])), axis=-1
    )  # [..., N]
    q = jnp.clip(
        jnp.round(vals.astype(jnp.float32) / tok_scale[..., None]), -qmax, qmax
    ).astype(jnp.int8)
    return q, upd[..., :p]


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Nibble-pack int4 values [..., hd] -> uint8 bytes [..., hd//2].

    PLANE packing: byte c of a token holds component c (low nibble) and
    component c + hd//2 (high nibble) of the SAME token, two's-complement
    nibbles.  The split-by-half layout lets the fused kernel score each
    plane with its own dot against the matching half of q instead of
    interleaving lanes (ops/pallas_int4.py's idiom)."""
    half = q.shape[-1] // 2
    qi = q.astype(jnp.int32)
    lo = qi[..., :half] & 0xF
    hi = (qi[..., half:] & 0xF) << 4
    return (lo | hi).astype(jnp.uint8)


def unpack_int4(b: jnp.ndarray) -> jnp.ndarray:
    """Inverse of pack_int4: uint8 [..., hd//2] -> int8 values [..., hd].
    Sign extension is ``((x & 0xF) ^ 8) - 8`` per nibble (two's
    complement), the exact formula the fused kernel applies in-register."""
    bi = b.astype(jnp.int32)
    lo = ((bi & 0xF) ^ 8) - 8
    hi = ((bi >> 4) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


def _run_window(pools: jnp.ndarray, run: int | None) -> int | None:
    """Slots in one window of the run form for runs of ``run`` slots into
    ``pools``: whole (8, 128) tiles of 32-bit words (16 rows of bfloat16),
    doubled until one holds a run or is a page; None where the pool keeps
    the row form (no run length given, a row that is not whole lanes, a
    page that does not hold whole windows)."""
    if run is None:
        return None
    ps, hd = pools.shape[-2:]
    win = 32 // pools.dtype.itemsize
    while win < min(run, ps):
        win *= 2
    return win if hd % 128 == 0 and ps % win == 0 else None


def _commit_runs(pools, vals, flat_slots, layer, run: int, win: int):
    """``commit_paged``'s run form.  The pool is read as windows of ``win``
    slots ([..., P * ps / win, win, hd]: a window never leaves its page); a
    run of ``run`` consecutive positions falls in at most ``n_win`` of them,
    wherever it starts and whatever page boundaries it crosses.  Each is
    read over all leading axes at once, the run's kept rows are laid over
    it and it is written back where it was: an update-slice of the donated
    pool, in place (5 windows of 128 KB a layer where a 512-column chunk was
    2,048 rows; 64 windows of 448 KB where a burst was 28,672 rows).  A slot
    is written only by the row that names it, so every other slot keeps its
    bits: dropped rows (padding, steps past a row's limit, dead row slots,
    pages the row does not hold) write nothing, and a window no kept row
    falls in is window 0 written back as it was read.

    Many runs (a burst's row slots, a wave of many rows) are a loop over
    the runs with the pool as its carry; the one or two runs of a wave that
    has rungs (``MAX_RUNG_ROWS``) are written inline, because with the pool
    as the carry of a loop INSIDE a branch of the wave's ``lax.switch`` the
    v5e compiler copies it into and out of every iteration (2 of the 3 rungs
    of a one-row wave, PR 38), and inline everywhere a start-up traces,
    lowers and loads 320 windows a layer for the 32-row bucket (+1.5 s to
    ready).  What keeps the compiler from re-laying the pool out so that a
    window over the leading axes is contiguous (PR 25) is the attention
    kernel, which reads the same buffer in the layout it arrives in:
    tests/test_tpu_compile.py holds the step programs to all three."""
    p, ps, hd = pools.shape[-3:]
    total, lead = p * ps, vals.shape[:-2]
    n_runs = flat_slots.shape[0] // run
    first, widx, write = _plan_windows(flat_slots, total, run, win)
    vals = vals.astype(pools.dtype).reshape(*lead, n_runs, run, hd)
    view = pools.reshape(*pools.shape[:-3], total // win, win, hd)
    lay = lambda r, view: _lay_run(view, vals, layer, r, first, widx, write)  # noqa: E731
    if n_runs <= MAX_RUNG_ROWS:
        for r in range(n_runs):
            view = lay(r, view)
    else:
        view = jax.lax.fori_loop(0, n_runs, lay, view)
    return view.reshape(pools.shape)


@partial(jax.jit, static_argnames=("total", "run", "win"))
def _plan_windows(flat_slots, total: int, run: int, win: int):
    """For each run r of ``flat_slots`` and each of the K windows it can fall
    in: ``first`` [R, K] the run's column that lies on the window's row 0
    (negative: the run starts inside the window), ``widx`` [R, K] the
    window's index in the pool's [P * ps / win] windows (0 where no kept slot
    falls in it) and ``write`` [R, K, win] the window's rows that a kept slot
    of the run names.  Jitted so that the K and the V commit of a layer, which
    hand in the same slots, trace it once."""
    n_win = (run + win - 2) // win + 1
    slots = flat_slots.reshape(-1, run)
    kept = (slots >= 0) & (slots < total)
    # where in its window a run's column 0 lies, from the first slot it keeps
    j0 = jnp.argmax(kept, axis=1)
    off = (jnp.take_along_axis(slots, j0[:, None], axis=1)[:, 0] - j0) % win  # [R]
    first = jnp.arange(n_win)[None, :] * win - off[:, None]
    cols = first[..., None] + jnp.arange(win)  # [R, K, win]
    at_col = jnp.take_along_axis(
        jnp.where(kept, slots, -1)[:, None, :], jnp.clip(cols, 0, run - 1), axis=2)
    at_col = jnp.where((cols >= 0) & (cols < run), at_col, -1)  # slot of each window row, -1: none
    widx = jnp.max(at_col, axis=2, initial=0) // win
    return first, widx, at_col == widx[..., None] * win + jnp.arange(win)


@jax.jit
def _lay_run(view, vals, layer, r, first, widx, write):
    """Run ``r`` of ``_commit_runs``: for each of its K windows, rows
    ``first[r, k] .. + win - 1`` of ``vals[..., r, :, :]`` laid over window
    ``widx[r, k]`` of ``view`` [(L,) ..., W, win, hd] where ``write[r, k]``
    says so.  A function of its own so that a commit of many runs is traced
    once and called, not traced window by window (seconds to ready: the
    wave of a 32-row bucket has 320 windows a layer)."""
    win, lead = view.shape[-2], vals.shape[:-3]
    rows = jax.lax.dynamic_index_in_dim(vals, r, len(lead), keepdims=False)
    rows = jnp.pad(rows, [(0, 0)] * len(lead) + [(win, win), (0, 0)])
    first, widx, write = (jax.lax.dynamic_index_in_dim(x, r, keepdims=False)
                          for x in (first, widx, write))
    origin, shape = (0,) * len(lead), (*lead, 1, *view.shape[-2:])  # every leading index
    if layer is not None:
        origin, shape = (layer, *origin), (1, *shape)
    for k in range(first.shape[0]):
        at = (*origin, widx[k], 0, 0)
        new = jax.lax.dynamic_slice_in_dim(rows, first[k] + win, win, axis=-2).reshape(shape)
        new = jnp.where(write[k][:, None], new, jax.lax.dynamic_slice(view, at, shape))
        view = jax.lax.dynamic_update_slice(view, new, at)
    return view


def commit_paged(
    pools: jnp.ndarray,  # [..., P, page_size, hd]
    vals: jnp.ndarray,  # [..., N, hd] new K or V vectors, leading dims match
    flat_slots: jnp.ndarray,  # [N] int32 flat slots; out-of-range = dropped
    scales: jnp.ndarray | None,  # [..., P] f32 per-page (int8 pools) or None
    page_size: int,
    layer: jnp.ndarray | None = None,  # [] int32: pools/scales keep their
    # leading [L] axis, vals (and the write) are that one layer's
    run: int | None = None,  # static: the slots are N // run runs, see below
):
    """Write new K or V vectors into flat pool slots — THE pool-commit
    rule, shared by the chunked-prefill (models/qwen2.forward_paged),
    decode-burst (serving/decode_burst), and ring-prefill
    (serving/long_prefill) paths so the quantization/write semantics can
    never drift apart.  ``scales is None`` = full-precision pools (vals
    cast to the pool dtype); else quantized pools with each page's scale
    fixed by its first write (quantize_kv_paged) — int8 when the pool
    dtype is int8, nibble-packed int4 (pack_int4) when it is uint8.

    Two forms, one result to the bit, chosen from the static shapes and
    dtype handed in:

    * the RUN form, where the caller says its slots come as runs
      (``run``: every ``run`` consecutive entries of ``flat_slots`` are
      consecutive positions of one sequence, any of them dropped — a burst
      row's ``n_steps`` tokens, a wave row's chunk) — ``_commit_runs``
      moves each run as the few aligned windows of slots it falls in;
    * the ROW form, the general case: a scatter that writes ONE [hd] row
      per (leading index..., slot), 68-74 ns a row on a v5e whatever it
      holds (PR 25).  Every leading axis is indexed, none is a window: a
      scatter window over the leading axes (``flat.at[:, slots]``: all
      layers and heads of a slot) makes the TPU compiler re-lay the whole
      pool out so that the window is contiguous, and back again after: two
      transposes of the pool around a scatter of a few MB (PERF.md,
      Findings, PR 25).  Quantized pools keep it (a page's scale is found
      from all rows of the commit, not a run at a time), and so do pools
      whose row is not whole 128-lane tiles or whose page does not hold
      whole windows (``_run_window``); no benchmark cell runs either with
      ``run`` given.  A caller that gives no ``run`` keeps it too: the
      packed and ring prefills (their slots are no runs) and DeepSeek-V3's
      latent pool.  The waves and bursts of Qwen2 (PR 38) and of the
      hybrids (models/hybrid.py, PR 43) give ``run``, and their bfloat16
      pools take the run form in every benchmark cell that runs them.

    In both the kv-head axis stays an axis of its own, so pools sharded
    over kv heads (tp) commit shard-locally.

    ``layer`` is the carried form: the caller keeps the whole
    [L, ..., P, ps, hd] pool (a scan carry, never sliced) and commits one
    layer's ``vals`` [..., N, hd] at that index.  Returns (pools, scales)."""
    p, ps, hd = pools.shape[-3:]  # hd is the STORED payload width
    win = _run_window(pools, run) if scales is None else None
    if win is not None:
        return _commit_runs(pools, vals, flat_slots, layer, run, win), None
    if scales is not None:
        qmax = 7 if pools.dtype == jnp.uint8 else 127
        page_scales = scales if layer is None else scales[layer]
        vals, page_scales = quantize_kv_paged(
            vals, flat_slots, page_scales, page_size, qmax=qmax
        )
        scales = page_scales if layer is None else scales.at[layer].set(page_scales)
        if qmax == 7:
            vals = pack_int4(vals)  # [..., N, hd] -> [..., N, hd//2] == pool hd
    # open mesh over vals' leading axes and the slots: one index per row
    index = jnp.ix_(*(jnp.arange(d) for d in vals.shape[:-2]), flat_slots)
    if layer is not None:
        index = (layer, *index)
    flat = pools.reshape(*pools.shape[:-3], p * ps, hd)
    flat = flat.at[index].set(vals.astype(pools.dtype), mode="drop")
    return flat.reshape(pools.shape), scales


class OutOfPages(RuntimeError):
    """Raised when the pool can't back a new allocation; the scheduler
    responds by queueing (or preempting) instead of corrupting the cache."""


class _ObserverSeam:
    """Advisory hooks feeding the page observatory (obs/hbm.py).

    A *claim* is one block-table listing backed by the pool: one refcount
    where refcounts exist, one allocated page where they don't.  The
    allocator reports claim deltas at the exact mutation sites, so the
    observatory's occupancy integral is maintained by construction rather
    than sampled.  Hooks are advisory — a raising observer must never
    break serving, so every call is fenced.  With no observer attached
    the cost is one falsy attribute check per allocator mutation.
    """

    _obs = None  # class default: observability off

    def attach_observer(self, obs) -> None:
        """Register an object with ``on_claims(delta)`` and
        ``on_tier_event(kind, n)`` (duck-typed: obs/hbm.PageObservatory)."""
        self._obs = obs

    def _note_claims(self, delta: int) -> None:
        if self._obs is not None and delta:
            try:
                self._obs.on_claims(delta)
            except Exception:  # noqa: BLE001 - advisory seam
                pass

    def _note_tier_event(self, kind: str, n: int = 1) -> None:
        if self._obs is not None and n:
            try:
                self._obs.on_tier_event(kind, n)
            except Exception:  # noqa: BLE001 - advisory seam
                pass


class PageAllocator(_ObserverSeam):
    """Free-list allocator over the page pool."""

    def __init__(self, num_pages: int) -> None:
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        self.num_pages = num_pages

    @property
    def free_count(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> list[int]:
        if n > len(self._free):
            raise OutOfPages(f"need {n} pages, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        self._note_claims(n)
        return out

    def release(self, pages: list[int]) -> None:
        self._free.extend(pages)
        self._note_claims(-len(pages))

    def can_admit(self, hashes: list[bytes], need: int, extra_free: int = 0,
                  headroom: int = 0) -> bool:
        """Interface parity with PrefixCachingAllocator (no cache here, so
        ``hashes`` — duplicates included — never changes the answer, and
        ``need=0`` trivially admits).  ``headroom`` pages must remain
        allocatable AFTER the admission (the per-class reservation batch
        traffic pays and protected traffic doesn't)."""
        return self.free_count + extra_free >= need + headroom

    def releasable_count(self, pages: list[int]) -> int:
        """Interface parity: without refcounts every page frees on release."""
        return len(pages)


def page_hashes(prompt: list[int], page_size: int) -> list[bytes]:
    """Chain hash per FULL page of the prompt (see serving/chain_hash.py —
    shared with the fleet router so both sides agree on page identity by
    construction)."""
    return chain_hashes(prompt, page_size)


class PrefixCachingAllocator(_ObserverSeam):
    """Refcounting page allocator with an automatic prefix cache.

    Every allocated page carries a refcount.  ``register`` associates a page
    with its prefix chain hash once its KV content is final (prefill wrote
    the whole page); ``share`` hands an admission the longest run of cached
    pages matching its prompt's chain, bumping refcounts instead of
    recomputing prefill.  Pages released to refcount 0 whose hash is
    registered park in an LRU instead of the free list — ``allocate`` evicts
    from the LRU only when the free list runs dry, so "free" HBM doubles as
    prefix cache (exactly vLLM's automatic prefix caching economics: cache
    capacity is whatever the pool isn't actively using).

    Drop-in superset of ``PageAllocator``: ``free_count`` counts evictable
    cached pages as free, so the engine's admission accounting is unchanged.
    """

    def __init__(self, num_pages: int) -> None:
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        self.num_pages = num_pages
        self._rc: dict[int, int] = {}
        self._hash_to_page: dict[bytes, int] = {}
        self._page_to_hash: dict[int, bytes] = {}
        # zero-ref cached pages, least-recently-used first (dict = ordered)
        self._lru: dict[int, None] = {}
        self.hit_tokens = 0  # stats: prompt tokens served from cache
        # called with the chain hash of a cached page as it is evicted: what
        # else is keyed by that hash (a state snapshot, StateSlots) goes too
        self.on_evict = None

    @property
    def free_count(self) -> int:
        return len(self._free) + len(self._lru)

    def allocate(self, n: int) -> list[int]:
        if n > self.free_count:
            raise OutOfPages(f"need {n} pages, {self.free_count} free")
        out: list[int] = []
        for _ in range(n):
            if self._free:
                page = self._free.pop()
            else:  # evict the coldest cached page
                page = next(iter(self._lru))
                del self._lru[page]
                h = self._page_to_hash.pop(page)
                del self._hash_to_page[h]
                if self.on_evict is not None:
                    self.on_evict(h)
            self._rc[page] = 1
            out.append(page)
        self._note_claims(n)
        return out

    def release(self, pages: list[int]) -> None:
        self._note_claims(-len(pages))
        # park TAIL-first: a chain is only matchable from its head, so the
        # head must be the last thing eviction takes (evict-leaf-first) —
        # parking in block-table order would evict h0 first and strand the
        # whole still-parked chain as unmatchable
        for page in reversed(pages):
            rc = self._rc.get(page, 0) - 1
            if rc > 0:
                self._rc[page] = rc
                continue
            self._rc.pop(page, None)
            if page in self._page_to_hash:
                self._lru[page] = None  # park: evictable but instantly reusable
            else:
                self._free.append(page)

    def releasable_count(self, pages: list[int]) -> int:
        """How many of ``pages`` would actually reach the allocatable set if
        released now (pages other requests still share won't)."""
        return sum(1 for p in pages if self._rc.get(p, 1) <= 1)

    # ---------------------------------------------------------- prefix API --

    def can_admit(self, hashes: list[bytes], need: int, extra_free: int = 0,
                  headroom: int = 0) -> bool:
        """Would ``share(hashes)`` + ``allocate(need - matched)`` succeed
        right now (plus ``extra_free`` pages the caller could recycle first)
        while leaving ``headroom`` pages allocatable?  Matched pages that
        are parked in the LRU must not double-count as allocatable free
        pages — sharing removes them from the LRU.  A page can match at
        most ONCE per admission (degenerate prompts can repeat a chain
        hash; a block table may list a page twice, but each listing is a
        separate refcount, i.e. a separate claim on capacity)."""
        matched = parked = 0
        seen: set[int] = set()
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is None or page in seen:
                break
            seen.add(page)
            matched += 1
            if page in self._lru:
                parked += 1
        avail = len(self._free) + len(self._lru) - parked + extra_free
        return avail >= need - matched + headroom

    def match_len(self, hashes: list[bytes]) -> int:
        """Pages ``share(hashes)`` would hand out, claiming nothing."""
        seen: set[int] = set()
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is None or page in seen:
                break
            seen.add(page)
        return len(seen)

    def share(self, hashes: list[bytes]) -> list[int]:
        """Claim the longest cached run matching ``hashes``: refcounts bump,
        parked pages leave the LRU.  Returns the shared pages in order.
        Mirrors ``can_admit``: the run stops at the first hash that would
        re-claim a page already shared by THIS call, so duplicate chain
        hashes never hand one physical page out twice per admission."""
        out: list[int] = []
        seen: set[int] = set()
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is None or page in seen:
                break
            seen.add(page)
            if page in self._lru:
                del self._lru[page]
            self._rc[page] = self._rc.get(page, 0) + 1
            out.append(page)
        self._note_claims(len(out))
        return out

    def register(self, h: bytes, page: int) -> None:
        """Publish a fully-written page under its chain hash.  First writer
        wins: if the hash is already served by another page (a concurrent
        twin prefilled the same prefix), this page simply stays private."""
        if h in self._hash_to_page or page in self._page_to_hash:
            return
        self._hash_to_page[h] = page
        self._page_to_hash[page] = h

    def resident_chain_hashes(self) -> frozenset[bytes]:
        """Chain hashes served from device HBM right now (router digest).
        Caller holds the driver lock (same discipline as every allocator
        method)."""
        return frozenset(self._hash_to_page)

    def host_chain_hashes(self) -> frozenset[bytes]:
        """Chain hashes recoverable by fault-in (none for the base class —
        the tiered subclass overrides)."""
        return frozenset()


class TieredPageAllocator(PrefixCachingAllocator):
    """Prefix-caching allocator with a host-RAM swap tier behind the
    indirection table.

    Residency of a registered chain hash:

    * **device** — in ``_hash_to_page`` only (the base-class maps).
    * **host** — in ``_host`` only: the page content lives in host RAM as
      an opaque payload the engine gathered off-device.  ``share`` extends
      the cached run through host hits by allocating a device page and
      staging a fault-in scatter the engine dispatches before any program
      that could read the page.
    * **saved** (both) — device copy + host copy.  ``allocate`` reclaims
      saved parked pages FIRST: dropping their device copy costs nothing
      because the hash stays servable from host RAM.
    * **in-flight** — in ``_wb_inflight``: a writeback gather is dispatched
      but its DMA hasn't landed (``complete_writeback`` pending).  Counts
      as saved for reclaim — the gather snapshot was taken at dispatch and
      registered pages are immutable, so the payload is already correct.

    Page indices the allocator hands out are plain device pages — the
    block-table/indirection machinery upstream is untouched; tiering is
    purely an allocator + step-boundary-migration concern.  Only REGISTERED
    refcount-0 pages ever move tiers: refcounted pages are pinned on device
    (they never enter the LRU), so an active row's KV can't be swapped out
    from under it.

    ``_claims`` tracks chain hashes an admitted-but-unregistered prefill is
    about to publish, letting the engine hold an identical-prefix follower
    for one registration instead of duplicating the leader's whole
    footprint (cross-user dedup under oversubscription).
    """

    def __init__(
        self, num_pages: int, host_pool_pages: int = 0, migrate_burst: int = 8
    ) -> None:
        super().__init__(num_pages)
        # <= 0 means unbounded (the engine always passes a positive cap)
        self.host_pool_pages = host_pool_pages
        self.migrate_burst = max(1, migrate_burst)
        # hash -> opaque page payload, least-recently-used first
        self._host: dict[bytes, object] = {}
        self._wb_inflight: set[bytes] = set()
        # hash -> count of admitted prefills that will register it
        self._claims: dict[bytes, int] = {}
        # (device page, payload) scatters staged by share(); the engine
        # drains via fault_in() and dispatches before dependent programs
        self._staged_faults: list[tuple[int, object]] = []
        # preempt-park priority queue: chain hashes whose device copy is a
        # parked victim's ONLY copy.  evict() serves these before the
        # cold-first scan, and until their writeback dispatches the pages
        # are pinned (excluded from free_count / _pick_eviction)
        self._park_queue: dict[bytes, None] = {}
        # cumulative stats (async engine exports deltas)
        self.fault_ins = 0  # host->device re-admissions
        self.preempt_parked_pages = 0  # pages parked by preemption
        self.writebacks = 0  # device->host saves completed
        self.dedup_hits = 0  # share() hits on pages other requests hold
        self.host_evictions = 0  # host-LRU payloads dropped at capacity
        self.tier_drops = 0  # device evictions that cost nothing (saved)
        self.page_imports = 0  # disagg handoff pages admitted (import_page)
        self.import_dedup_skips = 0  # imports skipped: hash already servable

    @property
    def host_pages(self) -> int:
        return len(self._host)

    def host_chain_hashes(self) -> frozenset[bytes]:
        """Chain hashes recoverable by fault-in from the host tier."""
        return frozenset(self._host)

    @property
    def plain_free_count(self) -> int:
        """Free pages available without evicting anything from the cache."""
        return len(self._free)

    @property
    def pending_park_writebacks(self) -> int:
        """Park-queue entries not yet drained by ``evict`` — the engine's
        preempt path loops migration until this hits zero so parked pages
        unpin within the step that parked them."""
        return len(self._park_queue)

    def _pinned_hashes(self) -> set[bytes]:
        """Park-queue hashes whose device page is still the only copy:
        LRU-resident, not yet saved or in flight.  Stale entries (re-shared
        pages, already-saved hashes) don't pin — evict() drops them."""
        out: set[bytes] = set()
        for h in self._park_queue:
            page = self._hash_to_page.get(h)
            if (page is not None and page in self._lru
                    and h not in self._host and h not in self._wb_inflight):
                out.add(h)
        return out

    @property
    def free_count(self) -> int:
        # pinned pages are NOT allocatable until their writeback dispatches
        # (one _migrate_pages step at most): reclaiming one would destroy a
        # preempted victim's only KV copy
        return len(self._free) + len(self._lru) - len(self._pinned_hashes())

    # ------------------------------------------------------------ device --

    def allocate(self, n: int) -> list[int]:
        if n > self.free_count:
            raise OutOfPages(f"need {n} pages, {self.free_count} free")
        out: list[int] = []
        for _ in range(n):
            if self._free:
                page = self._free.pop()
            else:
                page = self._pick_eviction()
                del self._lru[page]
                h = self._page_to_hash.pop(page)
                del self._hash_to_page[h]
                if h in self._host or h in self._wb_inflight:
                    self.tier_drops += 1
            self._rc[page] = 1
            out.append(page)
        self._note_claims(n)
        return out

    def _pick_eviction(self) -> int:
        # prefer the coldest SAVED parked page — its hash survives in host
        # RAM, so the device copy is free to drop; fall back to the coldest
        # overall (the hash is lost, exactly the base-class economics).
        # Preempt-pinned pages are skipped in both passes: free_count
        # excludes them, so a caller that passed the allocate() precheck is
        # guaranteed an unpinned candidate here.
        pinned = self._pinned_hashes()
        fallback = None
        for page in self._lru:
            h = self._page_to_hash[page]
            if h in pinned:
                continue
            if h in self._host or h in self._wb_inflight:
                return page
            if fallback is None:
                fallback = page
        if fallback is None:
            raise OutOfPages("every cached page is preempt-pinned")
        return fallback

    # -------------------------------------------------------- prefix API --

    def can_admit(self, hashes: list[bytes], need: int, extra_free: int = 0,
                  headroom: int = 0) -> bool:
        """Host-resident hash hits count as free-able capacity: a host hit
        still consumes a device page (the fault-in target, included in
        ``need``) but extends the shareable run instead of breaking it, and
        saved parked pages reclaim at zero cache cost.  Device-matched
        pages reduce the allocation need as in the base class (with the
        same one-match-per-page rule).  Preempt-pinned pages aren't
        allocatable — unless this admission's own run matches them, which
        is the resume fast path (sharing un-pins)."""
        pinned = self._pinned_hashes()
        matched = parked = 0
        seen: set[int] = set()
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is not None:
                if page in seen:
                    break
                seen.add(page)
                matched += 1
                if page in self._lru:
                    parked += 1
                pinned.discard(h)  # matched: counted once via ``parked``
                continue
            if h in self._host:
                continue  # fault-in target: needs a page, run continues
            break
        avail = (len(self._free) + len(self._lru) - parked - len(pinned)
                 + extra_free)
        return avail >= need - matched + headroom

    def share(self, hashes: list[bytes]) -> list[int]:
        """Claim the longest run servable from EITHER tier.  Device hits
        bump refcounts as in the base class; host hits allocate a fresh
        device page, stage its fault-in scatter, and re-register the hash
        immediately so concurrent claimants of the same prefix resolve to
        the one faulting page (paying a single migration)."""
        out: list[int] = []
        seen: set[int] = set()
        device_bumps = 0  # host hits claim via allocate(1) below — the
        # allocate seam counts those, so this seam counts ONLY direct
        # refcount bumps or the observatory would double-count claims
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is not None:
                if page in seen:
                    break
                seen.add(page)
                if self._rc.get(page, 0) > 0:
                    self.dedup_hits += 1
                if page in self._lru:
                    del self._lru[page]
                self._rc[page] = self._rc.get(page, 0) + 1
                device_bumps += 1
                out.append(page)
                continue
            payload = self._host.get(h)
            if payload is None:
                break
            try:
                [page] = self.allocate(1)
            except OutOfPages:
                break
            # refresh host-LRU recency; the payload stays (dual residency:
            # the device copy is droppable at zero cost from here on)
            del self._host[h]
            self._host[h] = payload
            self._hash_to_page[h] = page
            self._page_to_hash[page] = h
            self._staged_faults.append((page, payload))
            self.fault_ins += 1
            self._note_tier_event("fault_in")
            seen.add(page)
            out.append(page)
        self._note_claims(device_bumps)
        return out

    # --------------------------------------------------------- migration --

    def evict(self, max_n: int) -> list[tuple[int, bytes]]:
        """Plan one writeback burst: up to ``max_n`` of the coldest parked
        pages not yet saved to host (device→host is a residency transition,
        NOT a release — the pages stay device-resident and shareable until
        ``allocate`` reclaims them).  Marks each hash in-flight; the engine
        gathers the page contents and calls ``complete_writeback`` once the
        DMA lands.  Refcounted pages never appear (not in the LRU)."""
        out: list[tuple[int, bytes]] = []
        cap = self.host_pool_pages
        # preempt-parked hashes jump the queue: each is a victim's ONLY
        # copy and pins its device page until saved, so clearing them first
        # keeps the pin (which subtracts from free_count) one step long.
        # The host cap is not consulted — complete_writeback's LRU trim
        # makes room by dropping the coldest host payloads instead.
        drained: list[bytes] = []
        for h in self._park_queue:
            if len(out) >= max_n:
                break
            drained.append(h)  # served or stale either way
            page = self._hash_to_page.get(h)
            if (page is None or page not in self._lru
                    or h in self._host or h in self._wb_inflight):
                continue  # re-shared, reclaimed, or already saved
            self._wb_inflight.add(h)
            out.append((page, h))
        for h in drained:
            del self._park_queue[h]
        for page in self._lru:
            if len(out) >= max_n:
                break
            h = self._page_to_hash[page]
            if h in self._host or h in self._wb_inflight:
                continue
            if cap > 0 and len(self._host) + len(self._wb_inflight) >= cap:
                break
            self._wb_inflight.add(h)
            out.append((page, h))
        return out

    def park(self, pages: list[int]) -> int:
        """Preempt-park a victim's pages (the WPA004 ``park`` transition).

        Registered pages release into the LRU exactly like an ordinary
        ``release`` but jump the writeback queue: their hashes pin the
        device pages against reclaim until the payload is saved to host,
        so the very pool churn that triggered the preemption cannot
        destroy the victim's only KV copy before ``evict`` ships it.
        Unregistered pages (the partial tail) just free — their content
        has no chain hash to resume under and is recomputed at resume.
        Pages other requests still share stay device-resident and
        refcounted (nothing to save).  Returns how many pages remain
        resumable by ``share`` from either tier."""
        resumable = 0
        for page in pages:
            h = self._page_to_hash.get(page)
            if h is None:
                continue
            resumable += 1
            if self._rc.get(page, 0) <= 1 and not (
                    h in self._host or h in self._wb_inflight):
                self._park_queue[h] = None
        self.release(pages)  # claims seam fires inside release
        self.preempt_parked_pages += len(pages)
        self._note_tier_event("park", len(pages))
        return resumable

    def complete_writeback(self, h: bytes, payload: object) -> None:
        """Store a landed writeback payload under its chain hash.  Content
        addressing makes this unconditionally safe: even if the device page
        was reclaimed (or re-registered to a twin) meanwhile, the payload
        IS the content every holder of ``h`` expects."""
        self._wb_inflight.discard(h)
        self._host[h] = payload
        self.writebacks += 1
        self._note_tier_event("writeback")
        if self.host_pool_pages > 0:
            while len(self._host) > self.host_pool_pages:
                cold = next(iter(self._host))
                del self._host[cold]
                self.host_evictions += 1
                self._note_tier_event("host_evict")

    def fault_in(self) -> list[tuple[int, object]]:
        """Drain the staged host→device transitions for this step's scatter
        dispatch.  The caller MUST dispatch these before any program that
        could read the target pages (device program order then guarantees
        the faulted content is visible — no host sync needed)."""
        staged, self._staged_faults = self._staged_faults, []
        return staged

    # ------------------------------------------- disagg export / import --

    def host_payload(self, h: bytes) -> object | None:
        """Read a host-tier payload for the disagg export path WITHOUT
        refreshing LRU recency (an export is a read by a peer replica, not
        local reuse — it must not keep cold pages pinned here)."""
        return self._host.get(h)

    def device_page_of(self, h: bytes) -> int | None:
        """Device page currently registered under ``h``, if any (export
        falls back to a device gather when the host tier lacks the page)."""
        return self._hash_to_page.get(h)

    def import_page(self, h: bytes, payload: object) -> bool:
        """Admit a transferred page payload into the host tier (the disagg
        handoff import primitive).  Content addressing makes this
        unconditionally safe — the payload IS what every holder of ``h``
        expects — but a hash already servable from either tier is skipped
        so a redundant ship can't churn the host LRU.  Returns True when
        the payload was stored.  The imported page becomes claimable by
        the very next admission through the ordinary ``share`` fault-in
        machinery; nothing touches the device."""
        if h in self._hash_to_page or h in self._host:
            self.import_dedup_skips += 1
            return False
        self._host[h] = payload
        self.page_imports += 1
        self._note_tier_event("import")
        if self.host_pool_pages > 0:
            while len(self._host) > self.host_pool_pages:
                cold = next(iter(self._host))
                del self._host[cold]
                self.host_evictions += 1
                self._note_tier_event("host_evict")
        return True

    # ------------------------------------------------------ pending claims --

    def claim(self, hashes: list[bytes]) -> None:
        """Record that an admitted prefill will register ``hashes``."""
        for h in hashes:
            self._claims[h] = self._claims.get(h, 0) + 1

    def unclaim(self, hashes: list[bytes]) -> None:
        for h in hashes:
            n = self._claims.get(h, 0) - 1
            if n > 0:
                self._claims[h] = n
            else:
                self._claims.pop(h, None)

    def pending_claim_pages(self, hashes: list[bytes] | None = None) -> int:
        """How many pages of this prompt's shareable run are mid-prefill on
        another row right now (claimed, not yet registered).  >0 tells the
        scheduler a one-registration wait will dedup that many pages.

        With ``hashes=None``: total claimed-but-unregistered pages across
        all chains — the in-flight prefill work the fleet router folds into
        a replica's load snapshot (queue depth alone reads "idle" while a
        burst of admissions is still mid-prefill)."""
        if hashes is None:
            return sum(self._claims.values())
        n = 0
        for h in hashes:
            if self._hash_to_page.get(h) is not None or h in self._host:
                continue  # already servable — nothing to wait for
            if self._claims.get(h, 0) > 0:
                n += 1
            else:
                break
        return n


class StateSlots:
    """Host-side ledger of a recurrent model's state pool (models/
    qwen3_next.py): which slot holds what.  The pool's first ``rows`` slots
    are the engine's rows' LIVE state (row r = slot r: nothing to allocate);
    ``snapshots`` SNAPSHOT slots follow, each a copy of a sequence's state at
    a page boundary, keyed by the chain hash of the page that ends there (the
    prefix cache's own key: a prefix whose pages match and whose last page's
    hash has a snapshot can resume there); one more slot takes the writes of
    wave rows that have nothing to write.  Snapshots leave by LRU within
    their slots and with their page (``PrefixCachingAllocator.on_evict``).  A
    slot is pinned from the admission that will resume from it until the
    wave that reads it is dispatched.  The copies themselves happen on the
    device, inside the wave program; decisions here are made in dispatch
    order, which is the order the device runs them in."""

    def __init__(self, rows: int, snapshots: int) -> None:
        self.rows, self.snapshots = rows, snapshots
        self.trash = rows + snapshots
        self._free = list(range(rows + snapshots - 1, rows - 1, -1))
        self._by_hash: dict[bytes, int] = {}  # least recently used first
        self._pins: dict[int, int] = {}
        self.written = self.hits = self.evicted = 0

    @property
    def total(self) -> int:
        return self.rows + self.snapshots + 1

    @property
    def in_use(self) -> int:
        return len(self._by_hash)

    def depth(self, hashes: list[bytes]) -> int:
        """The deepest page count d <= len(hashes) with a snapshot after page
        d (keyed ``hashes[d - 1]``); 0 where there is none."""
        for d in range(len(hashes), 0, -1):
            if hashes[d - 1] in self._by_hash:
                return d
        return 0

    def take(self, h: bytes) -> int:
        """The slot of ``h``'s snapshot for an admission that resumes from it:
        most recently used, counted a hit, pinned until ``unpin``."""
        slot = self._by_hash.pop(h)
        self._by_hash[h] = slot
        self._pins[slot] = self._pins.get(slot, 0) + 1
        self.hits += 1
        return slot

    def unpin(self, slot: int) -> None:
        left = self._pins.get(slot, 0) - 1
        if left > 0:
            self._pins[slot] = left
        else:
            self._pins.pop(slot, None)

    def reserve(self, h: bytes) -> int | None:
        """A slot for a snapshot keyed ``h`` that the wave being built will
        write, evicting the least recently used unpinned one if none is
        free.  None where ``h`` already has one, or every slot is pinned."""
        if h in self._by_hash:
            return None
        if self._free:
            slot = self._free.pop()
        else:
            old = next((k for k, s in self._by_hash.items() if s not in self._pins), None)
            if old is None:
                return None
            slot = self._by_hash.pop(old)
            self.evicted += 1
        self._by_hash[h] = slot
        self.written += 1
        return slot

    def drop(self, h: bytes) -> None:
        """``h``'s page was evicted: its snapshot goes with it."""
        slot = self._by_hash.pop(h, None)
        if slot is not None:
            self._free.append(slot)
            self.evicted += 1


@dataclass
class SlidingRow:
    """One sequence's pages of a sliding kind (``SlidingPages``): ``pages`` by
    ABSOLUTE page index (page j holds positions ``[j * page_size, (j + 1) *
    page_size)``), -1 where the page was released behind the window or came
    from nobody; ``len(pages)`` is how far the row is covered, ``total`` how
    far it will ever need to be.  ``first`` is the first index still held,
    ``shared`` the index below which what it holds came from the prefix cache,
    ``private`` the pages of its own it holds, ``registered`` the index below
    which its full prompt pages have been offered to the cache."""

    total: int
    first: int = 0
    shared: int = 0
    private: int = 0
    registered: int = 0
    pages: list = None

    @property
    def covered(self) -> int:
        return len(self.pages)


class SlidingPages:
    """Host-side ledger of a SLIDING kind of page: the pool of the layers that
    attend the last ``window`` keys.  Pages are named by the prefix cache's own
    chain hashes (a page's content depends on the whole prefix in either
    kind), refcounted and kept in an LRU exactly as the global kind's are (the
    same allocator class, a second instance).  What differs is how long a
    sequence holds a page:

    * a row RELEASES a page once every key in it is older than the window of
      the row's next token (``advance``); a released page that is registered
      parks in the LRU and can serve a later prefix hit;
    * a prefix hit of ``d`` pages is usable only where this kind still holds
      the pages that cover ``[d * page_size - window + 1, d * page_size)``
      (``depth``): the rule ``StateSlots`` follows for snapshots, with pages in
      the snapshot's place;
    * a row whose pages all fit ``cap`` (the window, a prefill chunk and one
      more) gets them all at admission and never asks again.  A longer one (a
      cold long prompt) gets ``cap`` of its own and takes a page ahead for
      every page of its own it releases behind, in the same call.  So that
      taking one can never fail, such a row's own pages stay unregistered
      until it is covered to its end: nobody shares them, so releasing one
      always frees it (it is registered as it is released, and parks).  Once
      covered to its end the row registers its full prompt pages as the global
      kind does, chunk by chunk.  An admission is therefore never refused for
      what a running row may still need, and no row waits on another.
    """

    def __init__(self, num_pages: int, page_size: int, window: int, chunk: int) -> None:
        self.alloc = PrefixCachingAllocator(num_pages)
        self.num_pages, self.page_size, self.window = num_pages, page_size, window
        self.cap = pages_needed(window + chunk, page_size) + 1
        self.freed = 0  # stats: pages released behind a window
        self.in_use = 0  # claims rows hold

    def first_page(self, pos: int) -> int:
        """The first page the token at position ``pos`` sees a key of."""
        return max(0, pos - self.window + 1) // self.page_size

    def depth(self, hashes: list[bytes], match: int) -> int:
        """The deepest d <= ``match`` (pages of ``hashes`` the global kind
        holds) whose window's pages this kind holds too; 0 where none."""
        held = self.alloc._hash_to_page
        run = 0  # pages held in a row, ending at the index looked at
        runs = []
        for h in hashes[:match]:
            run = run + 1 if h in held else 0
            runs.append(run)
        for d in range(match, 0, -1):
            if runs[d - 1] >= d - self.first_page(d * self.page_size):
                return d
        return 0

    def _plan(self, d: int, total: int) -> tuple:
        """(first page held, pages of its own at admission) of a row admitted
        with ``d`` pages from the cache."""
        return self.first_page(d * self.page_size), min(total - d, self.cap)

    def can_admit(self, hashes: list[bytes], d: int, total: int, extra_free: int = 0) -> bool:
        first, fresh = self._plan(d, total)
        return self.alloc.can_admit(hashes[first:d], d - first + fresh, extra_free=extra_free)

    def admit(self, hashes: list[bytes], d: int, total: int) -> SlidingRow:
        """The pages of a row admitted ``d`` pages deep (``depth`` said so and
        ``can_admit`` agreed): the window's pages shared, its own allocated."""
        first, fresh = self._plan(d, total)
        shared = self.alloc.share(hashes[first:d])
        assert len(shared) == d - first, "depth() promised the window's pages"
        self.in_use += len(shared) + fresh
        return SlidingRow(total=total, first=first, shared=d, private=fresh, registered=d,
                          pages=[-1] * first + shared + self.alloc.allocate(fresh))

    def advance(self, row: SlidingRow, next_pos: int, hashes: list[bytes], full: int) -> None:
        """The row's next token is at ``next_pos`` and its prompt's first
        ``full`` pages are written whole: release what lies behind the window,
        take what the row is owed ahead, offer the cache what it may have."""
        alloc, partial = self.alloc, row.covered < row.total
        upto = min(self.first_page(next_pos), row.covered)
        known = min(full, len(hashes))
        for j in range(row.first, upto):
            page = row.pages[j]
            if j >= row.shared:
                row.private -= 1
                if partial and j < known:  # registered as it leaves: nobody shared it before
                    alloc.register(hashes[j], page)
            alloc.release([page])
            row.pages[j] = -1
        self.freed += max(0, upto - row.first)
        self.in_use -= max(0, upto - row.first)
        row.first = max(row.first, upto)
        if partial:
            take = min(row.total - row.covered, self.cap - row.private)
            if take > 0:
                row.pages += alloc.allocate(take)  # no more than it has just freed
                row.private += take
                self.in_use += take
        if row.covered == row.total:
            for j in range(max(row.registered, row.first), known):
                alloc.register(hashes[j], row.pages[j])
            row.registered = max(row.registered, known)

    def release(self, row: SlidingRow) -> list[int]:
        """The row ends.  Returns the pages it still holds, for the caller to
        release now or once no burst in flight reads them (``release_pages``)."""
        pages = [p for p in row.pages[row.first:] if p >= 0]
        row.pages, row.first = [], 0
        return pages

    def release_pages(self, pages: list[int]) -> None:
        self.alloc.release(pages)
        self.in_use -= len(pages)


def pages_needed(num_tokens: int, page_size: int) -> int:
    return -(-num_tokens // page_size)


def slot_mapping(
    block_table_row: np.ndarray, start_pos: int, num_tokens: int, page_size: int, pad_to: int
) -> np.ndarray:
    """Flat pool slots for tokens [start_pos, start_pos + num_tokens), padded
    with -1 (out-of-bounds -> scatter drops the write)."""
    positions = np.arange(start_pos, start_pos + num_tokens)
    slots = block_table_row[positions // page_size] * page_size + positions % page_size
    out = np.full((pad_to,), -1, dtype=np.int32)
    out[:num_tokens] = slots
    return out


def packed_slot_mapping(
    block_table_row: np.ndarray,
    start_pos: int,
    num_tokens: int,
    page_size: int,
    out: np.ndarray,
    offset: int,
) -> None:
    """Write one segment's flat pool slots for tokens
    [start_pos, start_pos + num_tokens) into ``out[offset : offset +
    num_tokens]`` — the packed-prefill variant of ``slot_mapping``, filling
    a shared [budget] buffer (pre-initialized to -1 so unfilled tail
    positions stay padding) instead of a per-row padded slice."""
    positions = np.arange(start_pos, start_pos + num_tokens)
    out[offset : offset + num_tokens] = (
        block_table_row[positions // page_size] * page_size
        + positions % page_size
    )
