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
from githubrepostorag_tpu.ops.fused_decode import (
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
# each row walks MP pages out of a P=32 pool through a shuffled block table
N_KV, GROUP, HD, PS, P, MP = 2, 2, 8, 4, 32, 4
N_Q = N_KV * GROUP


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


def _window_case(key, b, s_w, quant):
    kq, kb, kp = jax.random.split(key, 3)
    k, v, ks, vs = _rand_pools(kp, quant)
    # block tables with HOLES: rows own disjoint shuffled page sets, so a
    # kernel that walked pages in pool order would read the wrong tokens
    bt = jax.random.permutation(kb, P)[: b * MP].reshape(b, MP).astype(jnp.int32)
    q = jax.random.normal(kq, (b, s_w, N_Q, HD), jnp.float32)
    cached = jnp.asarray([(3 * i) % (MP * PS - s_w + 1) for i in range(b)], jnp.int32)
    new = jnp.full((b,), s_w, jnp.int32)
    return q, k, v, bt, cached, new, ks, vs


# ------------------------------------------------------- kernel vs oracle --


@pytest.mark.parametrize("quant", [0, 8, 4], ids=["fp", "int8", "int4"])
@pytest.mark.parametrize("s_w", [1, 5, 9])  # plain decode, k=4 verify, k=8
@pytest.mark.parametrize("b", [1, 3])
def test_fused_window_matches_paged_ref(quant, s_w, b):
    key = jax.random.PRNGKey(quant * 100 + s_w * 10 + b)
    q, k, v, bt, cached, new, ks, vs = _window_case(key, b, s_w, quant)
    got = fused_window_attention(q, k, v, bt, cached, new, ks, vs, interpret=True)
    ref = paged_attention_ref(q, k, v, bt, cached, new, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_window_inactive_rows_are_finite_zero():
    """Bucket-padding rows (total length 0) must come out exactly zero —
    never NaN from an empty softmax — while live rows still match."""
    q, k, v, bt, cached, new, ks, vs = _window_case(jax.random.PRNGKey(0), 3, 5, 0)
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
