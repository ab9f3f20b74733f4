"""ops/fused_decode.py's single-launch window kernel (every family's
prefill kernel) must be numerically indistinguishable from
``paged_attention_ref`` (its stated oracle) across row buckets, window
widths, quant modes, and block-table holes; int4 nibble pages must
round-trip bit-exactly through commit/gather/migration.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import Qwen2Config
from githubrepostorag_tpu.ops import fused_decode
from githubrepostorag_tpu.ops.fused_decode import (
    _fold_pages,
    _query_tile,
    fused_packed_attention,
    fused_window_attention,
)
from githubrepostorag_tpu.ops.paged_attention import gather_kv, paged_attention_ref
from githubrepostorag_tpu.ops.sampling import (
    sample_tokens_capped,
    sample_tokens_nofilter,
)
from githubrepostorag_tpu.serving.kv_cache import (
    make_page_pools,
    pack_int4,
    quant_bits,
    quantize_kv_paged,
    unpack_int4,
)

# kernel-test geometry: 2 kv heads x group 2, 8-wide heads, 4-token pages;
# each row walks MP pages (or a WALKS entry's) out of a P=64 pool through a
# shuffled block table
N_KV, GROUP, HD, PS, P, MP = 2, 2, 8, 4, 64, 4
N_Q = N_KV * GROUP
# name -> (pages a row's table holds, sliding window in keys or None).  A grid
# step folds ``_fold_pages`` pages, 8 at the most: a table of 4 is one step
# shorter than that, 11 pages are two steps of 6 (the walk no multiple of the
# fold), 19 are three of 7; under a window of 37 keys the walk is 12 or 13 pages
# that begin at the page of a row's first visible key, wherever in the table
# that falls.  Quantised pools fold one page a step whatever the table.
WALKS = {"table4": (4, None), "table11": (11, None), "table19": (19, None),
         "table19-window37": (19, 37)}
# Mellum2's proportions (PR 54): a window of 8 pages under a call of 2 pages' columns walks 11
# pages in 2 steps; under a whole chunk of 4 pages' columns, 13
WINDOW_WALKS = {"window8-call2": (8 * PS, 2 * PS, 11), "window8-chunk4": (8 * PS, 4 * PS, 13)}


def _rand_pools(key, quant):
    """Random pools in the exact storage layout of each kv_quant mode:
    f32, int8 + per-page scales, or nibble-packed uint8 + scales."""
    kf, vf, k8, v8, k4, v4, ks, vs = jax.random.split(key, 8)
    if quant == 0:
        k = jax.random.normal(kf, (N_KV, P, PS, HD), jnp.float32)
        v = jax.random.normal(vf, (N_KV, P, PS, HD), jnp.float32)
        return k, v, None, None
    if quant == 8:
        shape = (N_KV, P, PS, HD)
        k = jax.random.randint(k8, shape, -127, 128).astype(jnp.int8)
        v = jax.random.randint(v8, shape, -127, 128).astype(jnp.int8)
    else:
        shape = (N_KV, P, PS, HD // 2)  # every byte pattern is a valid nibble pair
        k = jax.random.randint(k4, shape, 0, 256).astype(jnp.uint8)
        v = jax.random.randint(v4, shape, 0, 256).astype(jnp.uint8)
    k_s = jax.random.uniform(ks, (N_KV, P), jnp.float32, 0.02, 0.2)
    v_s = jax.random.uniform(vs, (N_KV, P), jnp.float32, 0.02, 0.2)
    return k, v, k_s, v_s


def _window_case(key, b, s_w, quant, mp=MP):
    kq, kb, kp = jax.random.split(key, 3)
    k, v, ks, vs = _rand_pools(kp, quant)
    # block tables with HOLES: rows own disjoint shuffled page sets, so a
    # kernel that walked pages in pool order would read the wrong tokens
    # (the pages a grid step folds together lie anywhere in the pool)
    bt = jax.random.permutation(kb, P)[: b * mp].reshape(b, mp).astype(jnp.int32)
    q = jax.random.normal(kq, (b, s_w, N_Q, HD), jnp.float32)
    # row 0 fills its table; the others end 3, 6 tokens short of it, so no
    # ``cached_lens`` but one in four is page-aligned and a row's last step
    # holds pages past its length
    cached = jnp.asarray([mp * PS - s_w - 3 * i for i in range(b)], jnp.int32)
    new = jnp.full((b,), s_w, jnp.int32)
    return q, k, v, bt, cached, new, ks, vs


# ------------------------------------------------------- kernel vs oracle --


@pytest.mark.parametrize("walk", WALKS)
@pytest.mark.parametrize("quant", [0, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("s_w", [1, 5, 9])  # plain decode, k=4 verify, k=8
@pytest.mark.parametrize("b", [1, 3])
def test_fused_window_matches_paged_ref(quant, s_w, b, walk):
    mp, sliding = WALKS[walk]
    key = jax.random.PRNGKey(quant * 100 + s_w * 10 + b)
    q, k, v, bt, cached, new, ks, vs = _window_case(key, b, s_w, quant, mp)
    got = fused_window_attention(q, k, v, bt, cached, new, ks, vs, interpret=True,
                                 sliding=sliding)
    ref = paged_attention_ref(q, k, v, bt, cached, new, ks, vs, sliding=sliding)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", WINDOW_WALKS)
def test_fused_window_walks_a_window_of_eight_pages_at_eight_heads_a_kv_head(name):
    """A sliding layer whose window is several times a call's columns, at 8
    query heads a kv head: rows whose window starts in mid-page, at a page
    boundary and at 0, over a table whose entries behind the window name a
    page of garbage."""
    window, s_w, walk = WINDOW_WALKS[name]
    group, pages, mp = 8, 96, 24
    assert (window + s_w - 2) // PS + 2 == walk
    rng = np.random.default_rng(walk)
    k, v = (jnp.asarray(rng.standard_normal((N_KV, pages, PS, HD)), jnp.float32) for _ in range(2))
    k = k.at[:, 0].set(1e4)  # page 0 is what entries behind the window name: never read
    cached = np.array([70, 64, 3], np.int32)
    new = np.array([s_w, s_w - 3, s_w], np.int32)
    bt = np.zeros((3, mp), np.int32)
    for r in range(3):
        first, held = max(0, cached[r] - window + 1) // PS, -(-(cached[r] + new[r]) // PS)
        bt[r, first:held] = 1 + rng.permutation(pages - 1)[:held - first]
    q = jnp.asarray(rng.standard_normal((3, s_w, N_KV * group, HD)), jnp.float32)
    args = (q, k, v, jnp.asarray(bt), jnp.asarray(cached), jnp.asarray(new))
    got = fused_window_attention(*args, interpret=True, sliding=window)
    ref = paged_attention_ref(*args, sliding=window)
    live = np.arange(s_w)[None, :] < new[:, None]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live], atol=2e-4, rtol=2e-4)
    assert not np.allclose(np.asarray(paged_attention_ref(*args))[0], np.asarray(ref)[0], atol=1e-1)


# (walk, group, columns, head, page size, pool itemsize, quant) -> pages a step folds
@pytest.mark.parametrize("shape,fold", [
    ((208, 16, 128, 128, 128, 2, 0), 8),  # Command A+'s global layer: 26 steps of 8
    ((34, 16, 128, 128, 128, 2, 0), 7),  # its sliding walk: 5 steps of 7, not of 8
    ((80, 1, 512, 128, 128, 2, 0), 8),  # Olmo-Hybrid
    ((80, 5, 512, 128, 128, 2, 0), 8),  # Falcon-H1
    ((80, 8, 256, 256, 128, 2, 0), 8),  # Qwen3-Next: head 256, 8 MB resident
    ((208, 8, 256, 128, 128, 2, 0), 8),  # Mellum2's global layers: 8 heads a kv head, 256 columns
    ((11, 8, 256, 128, 128, 2, 0), 6),  # its sliding walk (a window of 8 pages): 2 steps of 6
    ((13, 8, 512, 128, 128, 2, 0), 7),  # the same window under a whole 512-column chunk: 2 of 7
    ((16, 7, 512, 128, 128, 2, 0), 8),  # Qwen2-7B's cells: a row's 2-8 pages are one step
    ((4, 2, 5, 8, 4, 4, 0), 4), ((11, 2, 5, 8, 4, 4, 0), 6), ((19, 2, 9, 8, 4, 4, 0), 7),
    ((3, 16, 128, 128, 128, 2, 0), 3),  # a walk shorter than a step is one step
    ((208, 16, 128, 128, 128, 1, 8), 1), ((208, 16, 128, 128, 128, 1, 4), 1),  # quantised: N = 1
    ((208, 16, 512, 256, 128, 2, 0), 1),  # nothing left beside the resident blocks: a page a step
])
def test_fold_pages_follows_the_calls_shapes(shape, fold):
    assert _fold_pages(*shape) == fold


@pytest.mark.parametrize("group,s_w,span,tile", [
    (16, 128, 1024, (4, 128)),  # Command A+: 2 MB of scores is four heads' columns
    (16, 128, 896, (4, 128)), (1, 512, 1024, (1, 512)), (5, 512, 1024, (1, 512)),
    (7, 512, 1024, (1, 512)), (8, 256, 1024, (2, 256)),
    (1, 2048, 1024, (1, 512)),  # a head's columns past a tile: a divisor of them
    (7, 1, 1024, (7, 1)), (2, 5, 16, (2, 5)), (4, 9, 1024, (4, 9)),  # windows go whole
])
def test_query_tile_follows_the_calls_shapes(group, s_w, span, tile):
    assert _query_tile(group, s_w, span) == tile


# the wave's call of each cell's family, one kv head, two rows: (group, columns, head,
# bfloat16 products, bytes of scores a pass may make or None for the module's)
@pytest.mark.parametrize("group,s_w,hd,narrow,tile_bytes", [
    (16, 128, 128, True, None),  # Command A+: 16 x 128 columns, passes of 4 heads
    (1, 512, 128, False, None),  # Olmo-Hybrid
    (5, 512, 128, False, None),  # Falcon-H1: passes of one head
    (7, 512, 128, False, None),  # Qwen2-7B
    (8, 256, 256, False, None),  # Qwen3-Next: head 256, passes of 2 heads
    (2, 512, 128, False, 128 * 1024),  # a head's columns in passes of 32
    (8, 256, 128, True, None),  # Mellum2: 8 x 256 columns, passes of 2 heads
], ids=["16x128", "1x512", "5x512", "7x512", "8x256-head256", "2x512-split-columns", "8x256"])
def test_fused_window_at_the_cells_wave_shapes(group, s_w, hd, narrow, tile_bytes, monkeypatch):
    """128-token pages, a table of 10: two steps of 5 pages; the first row fills
    its table, the second begins and ends in mid-page."""
    if tile_bytes:
        monkeypatch.setattr(fused_decode, "TILE_BYTES", tile_bytes)
    ps, pages, mp = 128, 24, 10
    rng = np.random.default_rng(group * s_w)
    k, v = (jnp.asarray(rng.standard_normal((1, pages, ps, hd)), jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((2, s_w, group, hd)), jnp.bfloat16)
    bt = jnp.asarray(np.stack([rng.permutation(pages)[:mp] for _ in range(2)]), jnp.int32)
    new = jnp.asarray([s_w, s_w - 37], jnp.int32)
    cached = jnp.asarray([mp * ps - s_w, 3 * ps + 11], jnp.int32)
    assert _fold_pages(mp, group, s_w, hd, ps, 2, 0) == 5
    # fresh jit: TILE_BYTES is read while the call is traced
    call = jax.jit(fused_decode._window_attention, static_argnames=("interpret", "bf16_products"))
    got = call(q, k, v, bt, cached, new, interpret=True, bf16_products=narrow)
    ref = paged_attention_ref(q, k, v, bt, cached, new)
    live = np.arange(s_w)[None, :] < np.asarray(new)[:, None]
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], np.asarray(ref, np.float32)[live],
                               atol=4e-2 if narrow else 2e-2, rtol=2e-2)


@pytest.mark.parametrize("walk", ["table4", "table11"])
def test_fused_window_inactive_rows_are_finite_zero(walk):
    """Bucket-padding rows (total length 0) must come out exactly zero —
    never NaN from an empty softmax — while live rows still match."""
    q, k, v, bt, cached, new, ks, vs = _window_case(jax.random.PRNGKey(0), 3, 5, 0, WALKS[walk][0])
    cached = cached.at[1].set(0)
    new = new.at[1].set(0)
    got = fused_window_attention(q, k, v, bt, cached, new, interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert np.array_equal(np.asarray(got[1]), np.zeros_like(got[1]))
    live = np.asarray([0, 2])
    ref = paged_attention_ref(q[live], k, v, bt[live], cached[live], new[live])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("quant", [0, 8, 4], ids=["fp", "int8", "int4"])
def test_fused_packed_mixed_phase_matches_windows(quant):
    """One segment grid over a mixed wave — a 6-token prefill chunk and a
    3-token spec-verify window — must equal the segment-major oracle at
    every packed token, with padding tokens ignored."""
    tq, r = 8, 2
    k, v, ks, vs = _rand_pools(jax.random.PRNGKey(21 + quant), quant)
    bt = jax.random.permutation(jax.random.PRNGKey(5), P)[: r * MP]
    bt = bt.reshape(r, MP).astype(jnp.int32)
    cached = jnp.asarray([0, 9], jnp.int32)
    new = jnp.asarray([6, 3], jnp.int32)
    q_pack = jax.random.normal(jax.random.PRNGKey(7), (12, N_Q, HD), jnp.float32)
    seg_ids = jnp.asarray([0] * 6 + [1] * 3 + [r] * 3, jnp.int32)  # >= r pads
    positions = jnp.asarray([0, 1, 2, 3, 4, 5, 9, 10, 11, 0, 0, 0], jnp.int32)

    got = fused_packed_attention(q_pack, k, v, bt, cached, new, seg_ids,
                                 positions, tq=tq, k_scales=ks, v_scales=vs)

    q_seg = (jnp.zeros((r, tq, N_Q, HD), jnp.float32)
             .at[0, :6].set(q_pack[:6]).at[1, :3].set(q_pack[6:9]))
    ref = paged_attention_ref(q_seg, k, v, bt, cached, new, ks, vs)
    np.testing.assert_allclose(np.asarray(got[:6]), np.asarray(ref[0, :6]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[6:9]), np.asarray(ref[1, :3]),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ int4 page layout --


def test_int4_pack_unpack_roundtrip_exact():
    vals = jax.random.randint(jax.random.PRNGKey(2), (5, 7, HD), -8, 8)
    q = vals.astype(jnp.int8)
    packed = pack_int4(q)
    assert packed.dtype == jnp.uint8 and packed.shape == (5, 7, HD // 2)
    assert np.array_equal(np.asarray(unpack_int4(packed)), np.asarray(q))


def test_int4_commit_gather_roundtrip():
    """commit_paged on a uint8 pool quantizes at qmax=7, nibble-packs, and
    gather_kv (the oracle's input path) dequantizes the exact same values
    back out — quantize -> pack -> unpack -> scale is lossless."""
    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    pools = jnp.zeros((N_KV, P, PS, HD // 2), jnp.uint8)
    scales = jnp.zeros((N_KV, P), jnp.float32)
    vals = jax.random.normal(jax.random.PRNGKey(9), (N_KV, 2 * PS, HD), jnp.float32)
    # open pages 3 and 5 at their first slots (fresh-scale detection)
    slots = jnp.concatenate([3 * PS + jnp.arange(PS), 5 * PS + jnp.arange(PS)])
    slots = slots.astype(jnp.int32)
    new_pools, new_scales = commit_paged(pools, vals, slots, scales, PS)
    assert new_pools.dtype == jnp.uint8 and new_pools.shape == pools.shape

    qv, exp_scales = quantize_kv_paged(vals, slots, scales, PS, qmax=7)
    np.testing.assert_allclose(np.asarray(new_scales), np.asarray(exp_scales))
    expected = (qv.astype(jnp.float32).reshape(N_KV, 2, PS, HD)
                * exp_scales[:, jnp.asarray([3, 5])][..., None, None])

    bt = jnp.asarray([[3, 5]], jnp.int32)
    gk, _ = gather_kv(new_pools, new_pools, bt, new_scales, new_scales,
                      dtype=jnp.float32)  # [1, 2*PS, N_KV, HD]
    got = jnp.moveaxis(gk[0].reshape(2, PS, N_KV, HD), 2, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-6, atol=1e-6)


def test_int4_migration_roundtrip_bit_exact():
    """gather_pages -> scatter_pages must reproduce the nibble-packed bytes
    and per-page scales EXACTLY (disagg and host-tier parking ship this
    layout; any re-encode would compound quantization error)."""
    from githubrepostorag_tpu.ops.page_migration import gather_pages, scatter_pages

    l = 2
    key = jax.random.PRNGKey(13)
    kk, kv, ks, vs = jax.random.split(key, 4)
    shape = (l, N_KV, P, PS, HD // 2)
    kp = jax.random.randint(kk, shape, 0, 256).astype(jnp.uint8)
    vp = jax.random.randint(kv, shape, 0, 256).astype(jnp.uint8)
    ksc = jax.random.uniform(ks, (l, N_KV, P), jnp.float32, 0.01, 0.5)
    vsc = jax.random.uniform(vs, (l, N_KV, P), jnp.float32, 0.01, 0.5)

    idx = jnp.asarray([5, 2, 9, -1], jnp.int32)  # -1 = padding, must drop
    gk, gv, gks, gvs = gather_pages(kp, vp, idx, ksc, vsc)
    dk, dv, dks, dvs = scatter_pages(
        jnp.zeros_like(kp), jnp.zeros_like(vp), idx, gk,
        jnp.zeros_like(ksc), jnp.zeros_like(vsc),
        v_vals=gv, ks_vals=gks, vs_vals=gvs,
    )
    live = np.asarray([5, 2, 9])
    assert dk.dtype == jnp.uint8
    assert np.array_equal(np.asarray(dk[:, :, live]), np.asarray(kp[:, :, live]))
    assert np.array_equal(np.asarray(dv[:, :, live]), np.asarray(vp[:, :, live]))
    np.testing.assert_array_equal(np.asarray(dks[:, :, live]),
                                  np.asarray(ksc[:, :, live]))
    np.testing.assert_array_equal(np.asarray(dvs[:, :, live]),
                                  np.asarray(vsc[:, :, live]))
    # the padding index wrote nowhere: everything outside the burst is 0
    mask = np.ones(P, bool)
    mask[live] = False
    assert not np.asarray(dk[:, :, mask]).any()
    assert not np.asarray(dks[:, :, mask]).any()


def test_int4_pages_at_equal_pool_bytes():
    """The sizing claim: at a fixed HBM byte budget, int4 pools admit
    >= 1.8x the pages of int8 pools (2x payload minus the shared per-page
    scale overhead)."""
    cfg = dataclasses.replace(Qwen2Config.tiny(), head_dim=128)
    n_pages, ps = 8, 16
    p8 = make_page_pools(cfg, n_pages, ps, quant=8)
    p4 = make_page_pools(cfg, n_pages, ps, quant=4)
    bytes8 = sum(a.nbytes for a in (p8.k, p8.v, p8.ks, p8.vs)) / n_pages
    bytes4 = sum(a.nbytes for a in (p4.k, p4.v, p4.ks, p4.vs)) / n_pages
    budget = bytes8 * 4096  # an int8 pool of 4096 pages
    assert (budget // bytes4) / 4096 >= 1.8


def test_quant_bits_knob():
    assert quant_bits(False) == 0 and quant_bits(None) == 0
    assert quant_bits(True) == 8 and quant_bits(8) == 8
    assert quant_bits(4) == 4
    assert quant_bits("int4") == 4 and quant_bits("int8") == 8
    assert quant_bits("off") == 0
    with pytest.raises(ValueError):
        quant_bits(3)


# --------------------------------------------- fused-layout sampling path --


def test_sampling_accepts_fused_segment_logits():
    """sample_tokens_capped/nofilter on the fused [B, S, V] layout with
    per-row seg_pos must equal the host-gathered [B, V] call bit-for-bit
    (same rng): the device-side take_along_axis replaces a host transpose."""
    b, s, v = 4, 3, 64
    logits3 = jax.random.normal(jax.random.PRNGKey(17), (b, s, v), jnp.float32)
    seg_pos = jnp.asarray([0, 2, 1, 0], jnp.int32)
    logits2 = jnp.take_along_axis(logits3, seg_pos[:, None, None], axis=1)[:, 0]
    temp = jnp.asarray([0.0, 0.9, 0.7, 0.0], jnp.float32)
    top_p = jnp.asarray([1.0, 0.9, 1.0, 1.0], jnp.float32)
    top_k = jnp.asarray([0, 8, 0, 0], jnp.int32)
    rep = jnp.asarray([1.0, 1.0, 1.2, 1.0], jnp.float32)
    presence = jax.random.bernoulli(jax.random.PRNGKey(18), 0.1, (b, v))
    rng = jax.random.PRNGKey(19)

    flat = sample_tokens_capped(logits2, rng, temp, top_p, top_k, rep,
                                presence, cap=32)
    fused = sample_tokens_capped(logits3, rng, temp, top_p, top_k, rep,
                                 presence, cap=32, seg_pos=seg_pos)
    assert np.asarray(flat).tolist() == np.asarray(fused).tolist()

    flat_nf = sample_tokens_nofilter(logits2, rng, temp, rep, presence)
    fused_nf = sample_tokens_nofilter(logits3, rng, temp, rep, presence,
                                      seg_pos=seg_pos)
    assert np.asarray(flat_nf).tolist() == np.asarray(fused_nf).tolist()

    # seg_pos=None means window position 0 (the committed token)
    at0 = sample_tokens_capped(logits3[:, 0], rng, temp, top_p, top_k, rep,
                               presence, cap=32)
    dflt = sample_tokens_capped(logits3, rng, temp, top_p, top_k, rep,
                                presence, cap=32)
    assert np.asarray(at0).tolist() == np.asarray(dflt).tolist()


# ------------------------------------------------- dispatch attribution --


def test_ledger_dispatch_attribution():
    """The obs ledger turns the engine's dispatch counters into the
    /debug/slo dispatch section and the dispatches-per-step gauge."""
    from githubrepostorag_tpu.obs.ledger import SNAPSHOT_FIELDS, TokenLedger

    now = time.monotonic()
    ledger = TokenLedger("r0", flops_per_tok=1e9, peak_flops=1e12)
    snap = {f: 0.0 for f in SNAPSHOT_FIELDS}
    ledger.on_step(dict(snap), now - 1.0, now - 0.8)
    snap.update(committed_tokens=5, step_dispatches_total=4)
    ledger.on_step(dict(snap), now - 0.7, now - 0.2)
    s = ledger.snapshot()
    assert s["dispatch"]["dispatches"] == 4
    assert s["dispatch"]["dispatches_per_step"] == 2.0
