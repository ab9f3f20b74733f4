"""Int8 KV cache (kv_quant pools): per-token symmetric quantization,
engine output parity against full-precision KV, prefix-cache composition,
and the staged Pallas kernel's in-VMEM dequant (interpret mode).

VERDICT r02 #5: int8 KV halves cache reads at long context and doubles
effective page capacity under the 64-stream config (the KV-fit reasoning
behind the reference's --max-model-len 11712, values.yaml:74).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.serving import Engine, SamplingParams
from githubrepostorag_tpu.serving.kv_cache import make_page_pools, quantize_kv


@pytest.fixture(scope="module")
def tiny():
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    return cfg, params


def _engine(params, cfg, **kw):
    defaults = dict(max_num_seqs=2, num_pages=32, page_size=4, max_seq_len=64,
                    kv_dtype=jnp.float32, decode_burst=8)
    defaults.update(kw)
    return Engine(params, cfg, **defaults)


def test_quantize_kv_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 2.0, (3, 17, 64)), dtype=jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 17)
    back = q.astype(jnp.float32) * s[..., None]
    err = np.abs(np.asarray(back) - np.asarray(x))
    # per-token symmetric: error <= scale/2 = amax/254 per vector
    bound = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 254 + 1e-6
    assert (err <= bound).all()


def test_quantize_kv_zero_vector_safe():
    q, s = quantize_kv(jnp.zeros((2, 8)))
    assert np.asarray(q).max() == 0 and (np.asarray(s) > 0).all()


def test_quant_pools_shapes_and_bytes():
    cfg = Qwen2Config.tiny()
    full = make_page_pools(cfg, 16, 8)
    quant = make_page_pools(cfg, 16, 8, quant=True)
    assert quant.k.dtype == jnp.int8
    assert quant.ks.shape == quant.k.shape[:-2] and quant.ks.dtype == jnp.float32
    payload = quant.k.nbytes + quant.ks.nbytes
    assert payload < 0.55 * full.k.nbytes  # int8 + per-page scales vs bf16


def test_engine_kv_quant_tracks_full_precision(tiny):
    """Greedy decode over int8 KV must track the full-precision engine:
    same first tokens, and token-for-token equality over a short horizon
    (tiny scale, per-token scales — the quantization error is far below
    typical logit gaps)."""
    cfg, params = tiny
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_ids=())
    ref = [r.output_tokens for r in _engine(params, cfg).generate(prompts, sp)]
    got = [r.output_tokens
           for r in _engine(params, cfg, kv_quant=True).generate(prompts, sp)]
    for r, g in zip(ref, got):
        assert r[:6] == g[:6], (r, g)  # short horizon: identical
        # full horizon: allow a late near-tie flip, not divergence
        assert sum(a != b for a, b in zip(r, g)) <= 2, (r, g)


def test_engine_kv_quant_tracks_full_precision_at_page_128(tiny):
    """The production default page size (config.py KV_PAGE_SIZE=128)
    widens the first-write scale window: up to 127 decode appends into a
    page reuse the scale its OPENING write fixed (quantize_kv_paged),
    clipping any later outlier — the accuracy case the r05 throughput
    probes never measured.  Greedy decode must track the bf16 engine
    deep into a page full of first-write-scaled appends."""
    cfg, params = tiny
    geom = dict(num_pages=4, page_size=128, max_seq_len=256)
    sp = SamplingParams(max_tokens=100, temperature=0.0, stop_token_ids=())
    prompts = [[1, 2, 3, 4, 5]]
    ref = _engine(params, cfg, **geom).generate(prompts, sp)[0].output_tokens
    got = _engine(params, cfg, kv_quant=True, **geom).generate(prompts, sp)[0].output_tokens
    # first divergence (tiny random weights have near-tie logit gaps, so a
    # single late flip cascades — count faithful PREFIX length, not flips)
    first_diff = next((i for i, (a, b) in enumerate(zip(ref, got)) if a != b),
                      len(ref))
    assert first_diff >= 32, (first_diff, ref, got)


def test_kv_quant_composes_with_prefix_cache(tiny):
    """A warm request resuming from int8 cached pages must produce the
    cold request's tokens — the page content is the quantized
    representation either way."""
    cfg, params = tiny
    eng = _engine(params, cfg, kv_quant=True, prefix_caching=True)
    prefix = list(range(1, 17))  # 4 full pages
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
    cold = eng.generate([prefix + [20, 21]], sp)[0].output_tokens
    warm = eng.generate([prefix + [20, 21]], sp)[0].output_tokens
    assert eng._allocator.hit_tokens > 0
    assert warm == cold


def test_kv_quant_composes_with_sp_ring_prefill(tiny):
    """Round-4: the ring commit quantizes per page (long_prefill.py), so
    kv_quant + sp no longer rejects at construction — a long prompt rides
    the ring path onto int8 pools and decodes.  Cross-path token parity
    lives in tests/test_long_prefill.py."""
    cfg, params = tiny
    from githubrepostorag_tpu.parallel import MeshPlan, make_mesh

    eng = _engine(params, cfg, kv_quant=True, mesh=make_mesh(MeshPlan(sp=2)),
                  sp_prefill_threshold=32)
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())
    res = eng.generate([list(range(1, 41))], sp)[0]  # 40 >= threshold
    assert eng.sp_prefills == 1
    assert len(res.output_tokens) == 6


@pytest.mark.parametrize("lens,row_pages,wave", [
    pytest.param([9, 5], 3, None, id="one-wave"),
    # the page walk's own cases (tests/test_pallas_paged.py), pages of 4
    pytest.param([0, 30, 0, 0, 3, 0], 8, 2, id="dead-rows-between-live"),
    pytest.param([1, 3, 4, 5], 8, 2, id="one-token-and-around-a-page"),
    pytest.param([7, 8, 9, 0], 8, 2, id="around-a-wave"),
    pytest.param([16, 17, 31, 32], 8, 2, id="waves-plus-one-to-the-full-table"),
    pytest.param([0, 0, 0, 13, 0, 0], 8, 2, id="one-live-row-of-many"),
    pytest.param([32, 32, 32], 8, 4, id="every-row-full"),
    pytest.param([1, 15, 16, 17, 31, 32, 0], 8, 4, id="around-waves-of-4"),
])
def test_staged_kernel_int8_matches_dequant_reference(tiny, monkeypatch, lens, row_pages, wave):
    """The Pallas staged kernel's in-VMEM dequant (interpret mode) must
    match attention over the explicitly dequantized pool."""
    from githubrepostorag_tpu.ops.attention import dense_attention
    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged
    from tests.test_pallas_paged import set_wave

    rng = np.random.default_rng(1)
    L, B, n_kv, group, hd, ps, n_steps = 3, len(lens), 2, 2, 16, 4, 4
    P = B * row_pages + 2
    if wave:
        set_wave(monkeypatch, wave, n_kv, ps, hd, itemsize=1)
    q = jnp.asarray(rng.normal(size=(B, 1, n_kv * group, hd)), dtype=jnp.float32)
    kf = rng.normal(size=(L, n_kv, P, ps, hd)).astype(np.float32)
    vf = rng.normal(size=(L, n_kv, P, ps, hd)).astype(np.float32)
    def quant_per_page(x):  # [L, n_kv, P, ps, hd] -> int8 + [L, n_kv, P]
        s = np.maximum(np.abs(x).max(axis=(-2, -1)) / 127.0, 1e-8)
        q = np.clip(np.round(x / s[..., None, None]), -127, 127).astype(np.int8)
        return jnp.asarray(q), jnp.asarray(s.astype(np.float32))

    kq, ks = quant_per_page(kf)
    vq, vs = quant_per_page(vf)
    bt = jnp.asarray(rng.permutation(P)[: B * row_pages].reshape(B, row_pages), dtype=jnp.int32)
    pool_lens = jnp.asarray(lens, dtype=jnp.int32)
    sk = jnp.asarray(rng.normal(size=(B, n_kv, n_steps, hd)), dtype=jnp.float32)
    sv = jnp.asarray(rng.normal(size=(B, n_kv, n_steps, hd)), dtype=jnp.float32)
    sl = jnp.asarray([2], dtype=jnp.int32)
    li = jnp.asarray([1], dtype=jnp.int32)

    got = paged_attention_decode_staged(
        q, kq, vq, bt, pool_lens, sk, sv, sl, li, ks, vs, interpret=True
    )

    # reference: dequantize layer 1's pages, gather, dense attention
    kd = np.asarray(kq, dtype=np.float32) * np.asarray(ks)[..., None, None]
    vd = np.asarray(vq, dtype=np.float32) * np.asarray(vs)[..., None, None]
    outs = []
    for b in range(B):
        pages = np.asarray(bt)[b]
        k_seq = kd[1][:, pages].reshape(n_kv, -1, hd)  # [n_kv, 3*ps, hd]
        v_seq = vd[1][:, pages].reshape(n_kv, -1, hd)
        k_all = np.concatenate([k_seq, np.asarray(sk)[b]], axis=1)
        v_all = np.concatenate([v_seq, np.asarray(sv)[b]], axis=1)
        n_pool = int(pool_lens[b])
        valid = np.zeros((k_all.shape[1],), dtype=bool)
        valid[:n_pool] = True
        valid[row_pages * ps : row_pages * ps + int(sl[0])] = True
        out = dense_attention(
            q[b : b + 1],
            jnp.asarray(k_all.transpose(1, 0, 2))[None],
            jnp.asarray(v_all.transpose(1, 0, 2))[None],
            causal=False,
            kv_valid=jnp.asarray(valid)[None],
        )
        outs.append(np.asarray(out)[0])
    ref = np.stack(outs)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-5)


def test_quantize_kv_paged_first_write_then_append():
    """Per-page semantics: a page's scale is fixed by the write containing
    its slot 0 (with headroom); a later append to the same page reuses the
    stored scale and clips rather than rescaling; dropped slots (sentinel)
    touch nothing."""
    from githubrepostorag_tpu.serving.kv_cache import (
        KV_SCALE_HEADROOM,
        quantize_kv_paged,
    )

    ps, p, hd = 4, 8, 16
    rng = np.random.default_rng(2)
    scales = jnp.zeros((2, p), jnp.float32)  # [n_kv, P], never written

    # first write: page 3 slots 12..13 (opens at slot 0 of page 3)
    vals1 = jnp.asarray(rng.normal(0, 1.0, (2, 2, hd)), jnp.float32)
    slots1 = jnp.asarray([12, 13], jnp.int32)
    q1, scales = quantize_kv_paged(vals1, slots1, scales, ps)
    s3 = np.asarray(scales)[:, 3]
    expect = np.abs(np.asarray(vals1)).max(axis=(1, 2)) * KV_SCALE_HEADROOM / 127
    np.testing.assert_allclose(s3, expect, rtol=1e-5)
    assert (np.asarray(scales)[:, :3] == 0).all()

    # append slots 14..15: same page, larger values -> clip, scale UNCHANGED
    vals2 = jnp.asarray(rng.normal(0, 10.0, (2, 2, hd)), jnp.float32)
    slots2 = jnp.asarray([14, 15], jnp.int32)
    q2, scales2 = quantize_kv_paged(vals2, slots2, scales, ps)
    np.testing.assert_allclose(np.asarray(scales2)[:, 3], s3, rtol=0)
    assert np.abs(np.asarray(q2)).max() == 127  # clipped, not rescaled

    # dropped sentinel slots leave scales untouched
    q3, scales3 = quantize_kv_paged(vals1, jnp.asarray([-1, p * ps], jnp.int32),
                                    scales2, ps)
    np.testing.assert_array_equal(np.asarray(scales3), np.asarray(scales2))

    # roundtrip error within a freshly-scaled page is bounded by scale/2
    back = np.asarray(q1, np.float32) * s3[:, None, None]
    err = np.abs(back - np.asarray(vals1))
    assert (err <= s3[:, None, None] / 2 + 1e-6).all()


def test_kv_quant_engine_with_tp_mesh(tiny):
    """kv_quant composes with a TP mesh: rank-3 scale pools shard with
    their kv-head axis (regression: the device_put spec kept 4 axes after
    the per-page migration and crashed Engine init)."""
    from githubrepostorag_tpu.parallel import MeshPlan, make_mesh

    cfg, params = tiny
    eng = _engine(params, cfg, kv_quant=True, mesh=make_mesh(MeshPlan(tp=2)))
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())
    ref = _engine(params, cfg, kv_quant=True).generate([[1, 2, 3, 4]], sp)
    got = eng.generate([[1, 2, 3, 4]], sp)
    assert got[0].output_tokens == ref[0].output_tokens
