"""The one pool-commit rule (serving/kv_cache.commit_paged) against a plain
NumPy loop, and the paged prefill that carries its pools through the layer
scan against the form it replaced (pools as the scan's xs/ys, a window
scatter a layer): same values in the same slots, bit for bit on the CPU.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.models.qwen2 import (
    Qwen2Config,
    _block,
    _embed_dtype,
    _logits,
    forward_paged,
    init_params,
)
from githubrepostorag_tpu.ops.fused_decode import fused_paged_attention
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref
from githubrepostorag_tpu.ops.rope import rope_cos_sin
from githubrepostorag_tpu.serving.kv_cache import (
    KV_SCALE_HEADROOM,
    commit_paged,
    pack_int4,
    quantize_kv_paged,
)

L, N_KV, P, PS, HD = 3, 2, 6, 32, 128  # a row of whole lanes, a page of whole tiles
TOTAL = P * PS
KINDS = {"bf16": (jnp.bfloat16, 0), "int8": (jnp.int8, 127), "int4": (jnp.uint8, 7)}


def _at(pages, start, n, pad_to=None):
    """Flat slots of positions start .. start + n - 1 of a sequence whose
    page i is ``pages[i]`` (P: a page the row does not hold), padded with the
    dropped sentinel to ``pad_to`` entries."""
    pos = np.arange(start, start + n)
    slots = np.asarray(pages)[pos // PS] * PS + pos % PS
    return [*np.minimum(slots, TOTAL), *[TOTAL] * ((pad_to or n) - n)]


# what a commit may be handed: ([N] flat slots, TOTAL marks a dropped token;
# the length of the runs the caller says they come in, None: says nothing)
SLOTS = {
    # a page opened at its first slot, then an append to another page
    "duplicate-free": ([32, 33, 34, 35, 36, 160, 161, 3], None),
    # padding and inactive rows arrive as the out-of-range sentinel
    "dropped-sentinels": ([64, TOTAL, 65, TOTAL, TOTAL, 66, TOTAL, 67], None),
    # one run of tokens that leaves page 2 and opens page 3
    "crosses-a-page": (_at([0, 0, 2, 3], 92, 8), None),
    "all-dropped": ([TOTAL] * 8, None),
    # ---- a burst: a row's 8 steps are 8 consecutive positions
    "burst-inside-a-page": (_at([1], 5, 8) + _at([4, 3], 40, 8), 8),
    # ... the second page anywhere in the pool, not behind the first
    "burst-across-a-page": (_at([4, 0], 28, 8) + _at([5, 2], 31, 8), 8),
    # a row at its limit after 3 steps: the other 5 are dropped
    "burst-cut-short": (_at([3], 6, 3, pad_to=8) + _at([1, 2], 30, 1, pad_to=8), 8),
    "burst-dead-row": ([TOTAL] * 8 + _at([0, 5], 27, 8) + [TOTAL] * 8, 8),
    "burst-all-dropped": ([TOTAL] * 16, 8),
    # ---- a wave: a row's chunk (64 columns, two pages) is consecutive positions
    "chunk-ends-mid-page": (_at([1, 4], 0, 40, pad_to=64), 64),
    # the table's sentinel where the second page would be
    "chunk-page-not-held": (_at([2, P], 0, 64), 64),
    "chunk-starts-mid-page": (_at([3, 5, 0], 10, 64), 64),
    "chunk-rows-of-unequal-length": (
        _at([1, 2], 0, 64) + _at([0, 5], 35, 7, pad_to=64) + [TOTAL] * 64, 64),
    # a spec-verify window of k + 1 tokens: a run shorter than a tile
    "window-of-5": (_at([2, 4], 30, 5) + _at([1], 0, 5), 5),
}


def _pack_np(q: np.ndarray) -> np.ndarray:
    half = q.shape[-1] // 2
    qi = q.astype(np.int32)
    return ((qi[..., :half] & 0xF) | ((qi[..., half:] & 0xF) << 4)).astype(np.uint8)


def _commit_loop(pools, vals, slots, scales, qmax):
    """commit_paged as a loop over one leading index, one page, one token at
    a time.  pools [*lead, P, PS, hd], vals [*lead, N, HD] float32, scales
    [*lead, P] or None."""
    pools = np.array(pools)
    scales = None if scales is None else np.array(scales)
    lead = vals.shape[:-2]
    for idx in np.ndindex(*lead):
        kept = [(n, s) for n, s in enumerate(slots) if 0 <= s < TOTAL]
        if qmax:
            for page in sorted({s // PS for _, s in kept}):
                here = [n for n, s in kept if s // PS == page]
                if any(s == page * PS for _, s in kept):  # the page's first write
                    amax = max(np.abs(vals[idx][n]).max() for n in here)
                    scales[idx][page] = max(
                        np.float32(amax) * np.float32(KV_SCALE_HEADROOM / qmax),
                        np.float32(1e-8))
        for n, s in kept:
            row = vals[idx][n]
            if qmax:
                row = np.clip(np.round(row / scales[idx][s // PS]), -qmax, qmax).astype(np.int8)
                if qmax == 7:
                    row = _pack_np(row)
            pools[idx][s // PS, s % PS] = row.astype(pools.dtype)
    return pools, scales


def _fresh(kind: str, lead: tuple, seed: int):
    """Pools already holding something (so an unwritten slot shows), scales
    of pages written before, new values."""
    dtype, qmax = KINDS[kind]
    rng = np.random.default_rng(seed)
    width = HD // 2 if kind == "int4" else HD
    if qmax:
        pools = rng.integers(0, 100, (*lead, P, PS, width)).astype(dtype)
        scales = rng.uniform(0.01, 0.05, (*lead, P)).astype(np.float32)
    else:
        pools = jnp.asarray(rng.normal(size=(*lead, P, PS, width)), dtype)
        scales = None
    return pools, scales, qmax


@pytest.mark.parametrize("slots", SLOTS, ids=list(SLOTS))
@pytest.mark.parametrize("lead", [(L, N_KV), (N_KV,)], ids=["layers-heads", "heads"])
@pytest.mark.parametrize("kind", KINDS)
def test_commit_matches_a_plain_loop(kind, lead, slots):
    """The whole pool, untouched slots included, equal to the loop's; where
    the caller names a run length, equal to what the row form gives too."""
    pools, scales, qmax = _fresh(kind, lead, seed=len(lead))
    slot_list, run = SLOTS[slots]
    vals = np.random.default_rng(7).normal(size=(*lead, len(slot_list), HD)).astype(np.float32)
    want_pools, want_scales = _commit_loop(
        np.asarray(pools, np.float32) if not qmax else pools, vals, slot_list, scales, qmax)
    args = (jnp.asarray(pools), jnp.asarray(vals), jnp.asarray(slot_list, jnp.int32),
            None if scales is None else jnp.asarray(scales), PS)
    got_pools, got_scales = commit_paged(*args, run=run)
    assert got_pools.dtype == KINDS[kind][0] and got_pools.shape == pools.shape
    if qmax:
        np.testing.assert_array_equal(np.asarray(got_pools), want_pools)
        np.testing.assert_array_equal(np.asarray(got_scales), want_scales)
    else:
        # the loop wrote float32 rows: round them as the pool does
        want = np.asarray(jnp.asarray(want_pools, jnp.bfloat16), np.float32)
        np.testing.assert_array_equal(np.asarray(got_pools, np.float32), want)
        assert got_scales is None
    if run is not None:
        row_pools, _ = commit_paged(*args)
        np.testing.assert_array_equal(np.asarray(got_pools.astype(jnp.float32)),
                                      np.asarray(row_pools.astype(jnp.float32)))


@pytest.mark.parametrize("slots", ["crosses-a-page", "burst-across-a-page",
                                   "chunk-starts-mid-page"])
@pytest.mark.parametrize("layer", [0, L - 1], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("kind", KINDS)
def test_commit_of_one_layer_into_the_carried_pool(kind, layer, slots):
    """The carried form: the whole [L, ...] pool, one layer's values, the
    layer as a traced index.  Equal to committing that layer's slab alone,
    with every other layer (and its scales) untouched."""
    pools, scales, qmax = _fresh(kind, (L, N_KV), seed=11)
    slot_list, run = SLOTS[slots]
    vals = np.random.default_rng(3).normal(size=(N_KV, len(slot_list), HD)).astype(np.float32)
    slots = jnp.asarray(slot_list, jnp.int32)
    pools_j = jnp.asarray(pools)
    scales_j = None if scales is None else jnp.asarray(scales)

    carried = jax.jit(lambda p, v, s, sc, li: commit_paged(p, v, s, sc, PS, layer=li, run=run))
    got_pools, got_scales = carried(pools_j, jnp.asarray(vals), slots, scales_j,
                                    jnp.asarray(layer, jnp.int32))
    slab, slab_scales = commit_paged(
        pools_j[layer], jnp.asarray(vals), slots,
        None if scales is None else scales_j[layer], PS)
    want = np.array(pools_j.astype(jnp.float32))
    want[layer] = np.asarray(slab.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got_pools.astype(jnp.float32)), want)
    if qmax:
        want_scales = np.array(scales)
        want_scales[layer] = np.asarray(slab_scales)
        np.testing.assert_array_equal(np.asarray(got_scales), want_scales)


# pool [1, P, page, width] of a dtype, scales or none, the run handed in -> the form
FORMS = {
    "bf16-told-runs": ((PS, HD), jnp.bfloat16, False, 8, "run"),
    "bf16-chunk-of-pages": ((PS, HD), jnp.bfloat16, False, 2 * PS, "run"),
    "float32-page-of-8": ((8, HD), jnp.float32, False, 8, "run"),
    "told-nothing": ((PS, HD), jnp.bfloat16, False, None, "row"),
    # DeepSeek-V3's latent row [c_kv | k_rope]: 4.5 lane tiles
    "latent-576-wide": ((PS, 576), jnp.bfloat16, False, 8, "row"),
    "page-of-8-bf16": ((8, HD), jnp.bfloat16, False, 8, "row"),  # half a tile of 16 rows
    "int8-scales": ((PS, HD), jnp.int8, True, 8, "row"),
    "int4-scales": ((PS, HD // 2), jnp.uint8, True, 8, "row"),
}


@pytest.mark.parametrize("case", FORMS)
def test_which_form_a_pool_takes(case):
    """Told that the slots come as runs, a full-precision pool whose row is
    whole lanes and whose page is whole tiles moves windows (update-slices,
    no scatter); every other pool keeps the row scatter."""
    (ps, width), dtype, quant, run, form = FORMS[case]
    pools = jnp.zeros((1, P, ps, width), dtype)
    n = max(16, run or 0)
    vals = jnp.ones((1, n, HD if quant else width), jnp.float32)
    scales = jnp.ones((1, P), jnp.float32) if quant else None
    slots = jnp.arange(n, dtype=jnp.int32)
    text = str(jax.make_jaxpr(lambda p, v, s, sc: commit_paged(p, v, s, sc, ps, run=run))(
        pools, vals, slots, scales))
    assert ("dynamic_update_slice" in text, "scatter" in text) == (
        (True, False) if form == "run" else (False, True))
    got, _ = commit_paged(pools, vals, slots, scales, ps, run=run)
    assert float(jnp.abs(got[0, 0, :, 0].astype(jnp.float32)).sum()) > 0  # and it wrote


# ---- forward_paged with carried pools against the form it replaced --------


def _commit_window(pools, vals, flat_slots, scales, page_size):
    """The commit as it was: one update window over every leading index."""
    p, ps, hd = pools.shape[-3:]
    if scales is None:
        vals = vals.astype(pools.dtype)
    elif pools.dtype == jnp.uint8:
        vals, scales = quantize_kv_paged(vals, flat_slots, scales, page_size, qmax=7)
        vals = pack_int4(vals)
    else:
        vals, scales = quantize_kv_paged(vals, flat_slots, scales, page_size)
    flat = pools.reshape(-1, p * ps, hd)
    flat = flat.at[:, flat_slots].set(vals.reshape(-1, vals.shape[-2], hd), mode="drop")
    return flat.reshape(pools.shape), scales


def _forward_paged_xs_ys(params, cfg, ids, pos, k_pages, v_pages, slot_mapping, bt,
                         cached, new, attn_fn, k_scales=None, v_scales=None):
    """forward_paged as it was: each layer's pool slab sliced out as the
    scan's xs, committed by window, stacked back up as its ys."""
    quant = k_scales is not None
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    page_size = k_pages.shape[3]
    h = embedding_lookup(params["embed"], ids, dtype=_embed_dtype(params))
    cos, sin = rope_cos_sin(pos, hd, cfg.rope_theta)
    flat_slots = slot_mapping.reshape(-1)
    flat_slots = jnp.where(flat_slots < 0, k_pages.shape[2] * page_size, flat_slots)

    def body(h, xs):
        p, kp, vp, ks, vs = xs

        def attend(q, k, v):
            k_t = k.reshape(-1, nkv, hd).swapaxes(0, 1)
            v_t = v.reshape(-1, nkv, hd).swapaxes(0, 1)
            new_kp, new_ks = _commit_window(kp, k_t, flat_slots, ks, page_size)
            new_vp, new_vs = _commit_window(vp, v_t, flat_slots, vs, page_size)
            scales = (new_ks, new_vs) if quant else ()
            attn = attn_fn(q, new_kp, new_vp, bt, cached, new, *scales)
            return attn, (new_kp, new_vp, new_ks, new_vs)

        return _block(cfg, h, p, cos, sin, attend)

    h, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
        body, h, (params["layers"], k_pages, v_pages, k_scales, v_scales))
    h = rms_norm(h, params["norm"], cfg.rms_norm_eps)
    return _logits(params, h), k_pages, v_pages, k_scales, v_scales


@pytest.mark.parametrize("kind,use_pallas", [
    ("bf16", False), ("bf16", True), ("int8", False), ("int8", True), ("int4", True),
])
def test_forward_paged_with_carried_pools_equals_the_sliced_form(kind, use_pallas):
    """Two chunks a row (the second reads what the first wrote), one row
    padded: logits, pools and scales equal to the replaced form's."""
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    dtype, qmax = KINDS[kind]
    pages, ps, b, s = 8, 8, 2, 12
    width = cfg.head_dim // 2 if kind == "int4" else cfg.head_dim
    shape = (cfg.num_layers, cfg.num_kv_heads, pages, ps, width)
    scale_shape = shape[:3]
    bt = jnp.asarray([[1, 4, 6], [2, 5, 7]], jnp.int32)
    attn_fn = fused_paged_attention if use_pallas else paged_attention_ref

    def fresh():
        pools = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        scales = (jnp.zeros(scale_shape, jnp.float32),) * 2 if qmax else (None, None)
        return pools, scales

    (kp, vp), (ks, vs) = fresh()
    (kp0, vp0), (ks0, vs0) = fresh()
    rng = np.random.default_rng(5)
    cached = np.zeros((b,), np.int32)
    for new in ([12, 7], [9, 12]):
        new = np.asarray(new, np.int32)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
        offs = np.arange(s)[None, :]
        pos = cached[:, None] + offs
        slot = np.asarray(bt)[np.arange(b)[:, None], pos // ps] * ps + pos % ps
        slot = np.where(offs < new[:, None], slot, -1).astype(np.int32)
        args = (ids, jnp.asarray(pos, jnp.int32))
        tail = (jnp.asarray(slot), bt, jnp.asarray(cached), jnp.asarray(new))
        out = forward_paged(params, cfg, *args, kp, vp, *tail, use_pallas=use_pallas,
                            k_scales=ks, v_scales=vs)
        logits, kp, vp = out[:3]
        if qmax:
            ks, vs = out[3:]
        want, kp0, vp0, ks0, vs0 = jax.jit(
            _forward_paged_xs_ys, static_argnames=("cfg", "attn_fn")
        )(params, cfg, *args, kp0, vp0, *tail, attn_fn=attn_fn, k_scales=ks0, v_scales=vs0)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        for got, ref in ((kp, kp0), (vp, vp0), (ks, ks0), (vs, vs0)):
            if ref is not None:
                np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                              np.asarray(ref.astype(jnp.float32)))
        cached = cached + new
    assert float(jnp.abs(kp.astype(jnp.float32)).sum()) > 0  # something was written


# ---- the two step programs with the run commit against the row commit -----


def test_wave_and_burst_commit_runs_as_they_committed_rows(monkeypatch):
    """A config whose pools take the run form (head of 128, pages of 16
    bfloat16 rows) through the prefill chunk (a row that starts on a page, one
    that starts mid-page, a padded one) and the decode burst (a row that
    crosses a page, one at its limit after 3 steps, a dead one): logits,
    tokens and both whole pools equal to the same programs traced with
    commit_paged held to its row form."""
    import githubrepostorag_tpu.serving.kv_cache as kv_cache
    from githubrepostorag_tpu.models.qwen2 import forward_paged_impl
    from githubrepostorag_tpu.serving.decode_burst import decode_burst

    cfg = Qwen2Config(vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
                      num_heads=2, num_kv_heads=2, head_dim=128, rope_theta=10_000.0,
                      tie_word_embeddings=True, max_position_embeddings=512)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    pages, ps, b, s, steps = 12, 16, 3, 24, 8
    bt = jnp.asarray([[1, 4, 6, 9], [2, 5, 7, 10], [3, 8, 11, 0]], jnp.int32)
    rng = np.random.default_rng(5)
    chunks = []
    cached = np.zeros((b,), np.int32)
    for new in ([24, 9, 0], [7, 24, 16]):
        new = np.asarray(new, np.int32)
        offs = np.arange(s)[None, :]
        pos = cached[:, None] + offs
        slot = np.asarray(bt)[np.arange(b)[:, None], pos // ps] * ps + pos % ps
        slot = np.where(offs < new[:, None], slot, -1).astype(np.int32)
        chunks.append((jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
                       jnp.asarray(pos, jnp.int32), jnp.asarray(slot), jnp.asarray(cached),
                       jnp.asarray(new)))
        cached = cached + new
    lens = jnp.asarray(cached)  # 31, 33, 16: row 0 crosses a page in the burst
    limits = jnp.asarray([64, 36, 64], jnp.int32)  # row 1 is at its limit after 3 steps
    active = jnp.asarray([True, True, False])

    def run_both():
        shape = (cfg.num_layers, cfg.num_kv_heads, pages, ps, cfg.head_dim)
        kp, vp = jnp.zeros(shape, jnp.bfloat16), jnp.ones(shape, jnp.bfloat16)
        wave = jax.jit(lambda kp, vp, *a: forward_paged_impl(params, cfg, a[0], a[1], kp, vp,
                                                             a[2], bt, a[3], a[4]))
        out = []
        for chunk in chunks:
            logits, kp, vp = wave(kp, vp, *chunk)
            out.append(logits)
        ones, zeros = jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32)
        burst = jax.jit(lambda kp, vp: decode_burst.__wrapped__(
            params, cfg, zeros + 7, lens, kp, vp, jnp.zeros((b, cfg.vocab_size), bool), active,
            limits, bt, jax.random.PRNGKey(1), 0 * ones, ones, zeros, ones, n_steps=steps,
            first_tokens=zeros, fresh=jnp.zeros((b,), bool), fresh_lens=zeros,
            key_step=jnp.uint32(0)))
        toks, valid, kp, vp, *_ = burst(kp, vp)
        return [*out, toks, valid, kp.astype(jnp.float32), vp.astype(jnp.float32)]

    assert kv_cache._run_window(jnp.zeros((1, ps, 128), jnp.bfloat16), steps) == 16
    got = run_both()
    monkeypatch.setattr(kv_cache, "_run_window", lambda pools, run: None)
    want = run_both()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    valid = np.asarray(got[-3])
    assert valid[0].all() and valid[1].sum() == 3 and not valid[2].any()
    assert not np.array_equal(got[-2], np.zeros_like(got[-2]))


@pytest.mark.parametrize("module,config", [
    pytest.param("olmo_hybrid", "OlmoHybridConfig", id="gated-deltanet"),
    pytest.param("nemotron_h", "NemotronHConfig", id="mamba-2"),
])
def test_hybrid_wave_and_burst_commit_runs_as_they_committed_rows(monkeypatch, module, config):
    """The hybrids' skeleton (models/hybrid.py) through a wave of four rows
    (more than ``MAX_RUNG_ROWS``: the runs are a loop with the pool as its
    carry; a row that starts on a page, one that starts mid-page, padded ones,
    one with nothing new) and a burst (a row that crosses a page, one at its
    limit after 3 steps, a dead one), pages of 16 bfloat16 rows of 128: logits,
    tokens, both K/V pools and both state pools equal to the same programs
    traced with commit_paged held to its row form."""
    import importlib

    import githubrepostorag_tpu.serving.kv_cache as kv_cache
    from githubrepostorag_tpu.models import hybrid

    model = importlib.import_module(f"githubrepostorag_tpu.models.{module}")
    cfg = getattr(model, config).tiny(head_dim=128)  # a head of whole lanes: the run form
    params = model.init_params(cfg, seed=3)
    pages, ps, b, s, steps = 17, 16, 4, 32, 8  # a chunk is whole blocks of the chunked rules
    bt = np.arange(1, 17, dtype=np.int32).reshape(4, 4).T  # row r holds pages r+1, r+5, r+9, r+13
    trash = b + 1  # the state slot of rows that have nothing to write
    rng = np.random.default_rng(5)
    chunks, cached = [], np.zeros((b,), np.int32)
    for new in ([32, 9, 0, 16], [13, 32, 16, 5]):
        new = np.asarray(new, np.int32)
        offs = np.arange(s)[None, :]
        pos = cached[:, None] + offs
        slot = bt[np.arange(b)[:, None], pos // ps] * ps + pos % ps
        slot = np.where(offs < new[:, None], slot, -1).astype(np.int32)
        src = np.where(cached > 0, np.arange(b), -1).astype(np.int32)  # fresh: zeros
        dst = np.where(new > 0, np.arange(b), trash).astype(np.int32)
        chunks.append(tuple(map(jnp.asarray, (
            rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32), pos.astype(np.int32), slot,
            cached, new, src, dst))))
        cached = cached + new
    lens = jnp.asarray(cached)  # 45, 41, 16, 21: row 0 crosses a page in the burst
    limits = jnp.asarray([64, 44, 64, 64], jnp.int32)  # row 1 is at its limit after 3 steps
    active = jnp.asarray([True, True, False, True])
    bt = jnp.asarray(bt)

    def run_both():
        shape = (cfg.kv_layers, cfg.num_kv_heads, pages, ps, cfg.head_dim)
        kp, vp = jnp.zeros(shape, jnp.bfloat16), jnp.ones(shape, jnp.bfloat16)
        state = kv_cache.make_state_pools(cfg, b + 2)
        full, col0 = jnp.full((b,), trash, jnp.int32), jnp.zeros((b,), jnp.int32)
        wave = jax.jit(lambda kp, vp, state, ids, pos, slot, cached, new, src, dst: hybrid.wave(
            model._Layers, params, cfg, ids, pos, kp, vp, slot, bt, cached, new, state, src, dst,
            full, col0))
        out = []
        for chunk in chunks:
            logits, kp, vp, _, state = wave(kp, vp, state, *chunk)
            out.append(logits)
        ones, zeros = jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32)
        burst = jax.jit(lambda kp, vp, state: hybrid.burst(
            model._Layers, params, cfg, zeros + 7, lens, kp, vp,
            jnp.zeros((b, cfg.vocab_size), bool), active, limits, bt, jax.random.PRNGKey(1),
            0 * ones, ones, zeros, ones, steps, False, False, zeros, jnp.zeros((b,), bool), zeros,
            jnp.uint32(0), state))
        toks, valid, kp, vp, *_, state = burst(kp, vp, state)
        return [*out, toks, valid, kp.astype(jnp.float32), vp.astype(jnp.float32),
                state["s"], state["conv"].astype(jnp.float32)]

    pool = jnp.zeros((1, ps, cfg.head_dim), jnp.bfloat16)
    assert kv_cache._run_window(pool, steps) == 16 and kv_cache._run_window(pool, s) == 16
    forms = []
    lay_run = kv_cache._lay_run
    monkeypatch.setattr(kv_cache, "_lay_run", lambda *a: forms.append("run") or lay_run(*a))
    got = run_both()
    assert forms  # the run form was traced: the callers said ``run=``
    monkeypatch.setattr(kv_cache, "_run_window", lambda pools, run: None)
    del forms[:]
    want = run_both()
    assert not forms
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    valid = np.asarray(got[3])
    assert valid[0].all() and valid[1].sum() == 3 and not valid[2].any() and valid[3].all()
    for pool in got[4:]:  # nothing compared is still what it started as
        assert np.asarray(pool).std() > 0
