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

L, N_KV, P, PS, HD = 3, 2, 6, 8, 16
TOTAL = P * PS
KINDS = {"bf16": (jnp.bfloat16, 0), "int8": (jnp.int8, 127), "int4": (jnp.uint8, 7)}

# what a commit may be handed: [N] flat slots, TOTAL marks a dropped token
SLOTS = {
    # a page opened at its first slot, then an append to another page
    "duplicate-free": [8, 9, 10, 11, 12, 40, 41, 3],
    # padding and inactive rows arrive as the out-of-range sentinel
    "dropped-sentinels": [16, TOTAL, 17, TOTAL, TOTAL, 18, TOTAL, 19],
    # one run of tokens that leaves page 2 and opens page 3
    "crosses-a-page": [20, 21, 22, 23, 24, 25, 26, 27],
    "all-dropped": [TOTAL] * 8,
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
    pools, scales, qmax = _fresh(kind, lead, seed=len(lead))
    slot_list = SLOTS[slots]
    vals = np.random.default_rng(7).normal(size=(*lead, len(slot_list), HD)).astype(np.float32)
    want_pools, want_scales = _commit_loop(
        np.asarray(pools, np.float32) if not qmax else pools, vals, slot_list, scales, qmax)
    got_pools, got_scales = commit_paged(
        jnp.asarray(pools), jnp.asarray(vals), jnp.asarray(slot_list, jnp.int32),
        None if scales is None else jnp.asarray(scales), PS)
    assert got_pools.dtype == KINDS[kind][0] and got_pools.shape == pools.shape
    if qmax:
        np.testing.assert_array_equal(np.asarray(got_pools), want_pools)
        np.testing.assert_array_equal(np.asarray(got_scales), want_scales)
    else:
        # the loop wrote float32 rows: round them as the pool does
        want = np.asarray(jnp.asarray(want_pools, jnp.bfloat16), np.float32)
        np.testing.assert_array_equal(np.asarray(got_pools, np.float32), want)
        assert got_scales is None


@pytest.mark.parametrize("layer", [0, L - 1], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("kind", KINDS)
def test_commit_of_one_layer_into_the_carried_pool(kind, layer):
    """The carried form: the whole [L, ...] pool, one layer's values, the
    layer as a traced index.  Equal to committing that layer's slab alone,
    with every other layer (and its scales) untouched."""
    pools, scales, qmax = _fresh(kind, (L, N_KV), seed=11)
    slot_list = SLOTS["crosses-a-page"]
    vals = np.random.default_rng(3).normal(size=(N_KV, len(slot_list), HD)).astype(np.float32)
    slots = jnp.asarray(slot_list, jnp.int32)
    pools_j = jnp.asarray(pools)
    scales_j = None if scales is None else jnp.asarray(scales)

    carried = jax.jit(lambda p, v, s, sc, li: commit_paged(p, v, s, sc, PS, layer=li))
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
