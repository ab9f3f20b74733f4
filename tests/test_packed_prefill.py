"""Token-budget packed prefill (PR 2 tentpole): op-level parity against a
direct per-token oracle, engine packed-vs-padded greedy parity on the
heterogeneous traffic the packed path exists for (mixed lengths, mid-chunk
splits, prefix-cache resumes), and the compiled-shape discipline (warmup
predicts the packed program count exactly; live traffic adds zero)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.serving import Engine, SamplingParams
from tests.helpers.compile_guard import compile_guard


# --------------------------------------------------------------- op level


def _ref_packed_attention(q, k_pages, v_pages, block_tables, cached_lens,
                          new_lens, seg_ids, positions):
    """Per-token oracle: packed token t of segment s attends causally over
    that segment's first positions[t]+1 cached tokens, gathered page by
    page from the pool — no segment-major scatter, no masking tricks."""
    t_, n_q, hd = q.shape
    n_kv, _, ps, _ = k_pages.shape
    group = n_q // n_kv
    qf = np.asarray(q, np.float32)
    kf = np.asarray(k_pages, np.float32)
    vf = np.asarray(v_pages, np.float32)
    bt = np.asarray(block_tables)
    out = np.zeros((t_, n_q, hd), np.float32)
    for t in range(t_):
        s = int(seg_ids[t])
        if s >= bt.shape[0]:
            continue  # padding token — op output is unspecified garbage
        kv_len = int(positions[t]) + 1
        ks = np.stack([kf[:, bt[s, p // ps], p % ps] for p in range(kv_len)])
        vs = np.stack([vf[:, bt[s, p // ps], p % ps] for p in range(kv_len)])
        for h in range(n_q):
            scores = ks[:, h // group] @ qf[t, h] / np.sqrt(hd)
            w = np.exp(scores - scores.max())
            out[t, h] = (w / w.sum()) @ vs[:, h // group]
    return out


def _packed_case(seed=0):
    """3 live segments + 1 padding token in a 16-token budget: a mid-prompt
    chunk (cached 5, new 3), a fresh full chunk (cached 0, new 8 == tq),
    and a tail chunk deep into page 2 (cached 11, new 4)."""
    rng = np.random.default_rng(seed)
    n_kv, pages, ps, hd, group = 2, 8, 8, 16, 2
    r, tq = 3, 8
    k_pages = jnp.asarray(rng.normal(0, 1, (n_kv, pages, ps, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(0, 1, (n_kv, pages, ps, hd)), jnp.float32)
    block_tables = jnp.asarray([[0, 1], [2, 3], [4, 5]], jnp.int32)
    cached = jnp.asarray([5, 0, 11], jnp.int32)
    new = jnp.asarray([3, 8, 4], jnp.int32)
    seg_ids, positions = [], []
    for s in range(r):
        for i in range(int(new[s])):
            seg_ids.append(s)
            positions.append(int(cached[s]) + i)
    seg_ids.append(r)  # padding slot
    positions.append(0)
    q = jnp.asarray(rng.normal(0, 1, (len(seg_ids), n_kv * group, hd)),
                    jnp.float32)
    return (q, k_pages, v_pages, block_tables, cached, new,
            jnp.asarray(seg_ids, jnp.int32), jnp.asarray(positions, jnp.int32),
            tq)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_packed_prefill_attention_matches_oracle(use_pallas):
    from githubrepostorag_tpu.ops.packed_prefill import packed_prefill_attention

    (q, kp, vp, bt, cached, new, seg, pos, tq) = _packed_case()
    out = packed_prefill_attention(q, kp, vp, bt, cached, new, seg, pos,
                                   tq=tq, use_pallas=use_pallas)
    ref = _ref_packed_attention(q, kp, vp, bt, cached, new, seg, pos)
    live = np.asarray(seg) < bt.shape[0]
    np.testing.assert_allclose(np.asarray(out)[live], ref[live],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()  # padding rows: finite garbage


def test_packed_prefill_attention_quant_pages_match_oracle():
    """kv_quant pools route through the gather path with per-page dequant
    (even under use_pallas) — parity is against the oracle over the
    DEQUANTIZED pages."""
    from githubrepostorag_tpu.ops.packed_prefill import packed_prefill_attention

    def quantize(pages):  # per-page symmetric int8, [n_kv, P] scales
        scales = jnp.maximum(jnp.max(jnp.abs(pages), axis=(2, 3)) / 127.0, 1e-8)
        return (jnp.round(pages / scales[:, :, None, None]).astype(jnp.int8),
                scales)

    (q, kp, vp, bt, cached, new, seg, pos, tq) = _packed_case(seed=3)
    kq, ks = quantize(kp)
    vq, vs = quantize(vp)
    out = packed_prefill_attention(q, kq, vq, bt, cached, new, seg, pos,
                                   tq=tq, use_pallas=True,  # quant forces XLA
                                   k_scales=ks, v_scales=vs)
    kdq = kq.astype(jnp.float32) * ks[:, :, None, None]
    vdq = vq.astype(jnp.float32) * vs[:, :, None, None]
    ref = _ref_packed_attention(q, kdq, vdq, bt, cached, new, seg, pos)
    live = np.asarray(seg) < bt.shape[0]
    np.testing.assert_allclose(np.asarray(out)[live], ref[live],
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- engine parity (vs HF)

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    from githubrepostorag_tpu.models.hf_loader import (
        config_from_hf,
        params_from_state_dict,
    )

    hf_cfg = transformers.Qwen2Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=True, attention_dropout=0.0,
    )
    torch.manual_seed(0)
    model = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    cfg = config_from_hf(hf_cfg.to_dict())
    params = params_from_state_dict(model.state_dict(), cfg)
    return model, params, cfg


def _make_engine(params, cfg, **kw):
    defaults = dict(
        max_num_seqs=4, num_pages=64, page_size=8, max_seq_len=128,
        prefill_chunk=32, kv_dtype=jnp.float32,
    )
    defaults.update(kw)
    return Engine(params, cfg, **defaults)


def _hf_greedy(model, prompt, n):
    ids = torch.tensor([prompt])
    with torch.no_grad():
        out = model.generate(
            ids, max_new_tokens=n, do_sample=False,
            pad_token_id=0, eos_token_id=None, use_cache=True,
        )
    return out[0, len(prompt):].tolist()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_packed_prefill_matches_padded_and_hf(tiny, use_pallas):
    """Greedy tokens must be IDENTICAL to the padded engine and to HF on a
    wave the packed path actually reshapes: mixed lengths, a budget (48)
    smaller than the pending work (splits chunks mid-way), 5 prompts
    through 4 rows (continuous-batching admission)."""
    model, params, cfg = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 16, 17, 70, 33)]
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    padded = _make_engine(params, cfg)  # its waves run at 32, 16 or 8 columns
    packed = _make_engine(params, cfg, prefill_token_budget=48,
                          use_pallas=use_pallas)
    got_padded = [r.output_tokens for r in padded.generate(prompts, sp)]
    got_packed = [r.output_tokens for r in packed.generate(prompts, sp)]
    assert got_packed == got_padded
    for prompt, toks in zip(prompts, got_packed):
        assert toks == _hf_greedy(model, prompt, 8)
    assert packed.packed_prefill_tokens == sum(len(p) for p in prompts)
    assert packed.packed_prefill_padding > 0  # heterogeneous wave padded some


def test_packed_prefill_prefix_cache_resume_matches_hf(tiny):
    """Prefix-cache hits hand the packed scheduler short uncached suffixes
    with nonzero cached_lens — the heterogeneity the budget packs around.
    A warm repeat and a shared-prefix variant must both match HF."""
    model, params, cfg = tiny
    rng = np.random.default_rng(9)
    prefix = rng.integers(0, cfg.vocab_size, size=40).tolist()  # 5 full pages
    tails = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (3, 9)]
    eng = _make_engine(params, cfg, prefill_token_budget=48)
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    cold = eng.generate([prefix + tails[0]], sp)[0]
    hits0 = eng._allocator.hit_tokens
    warm = eng.generate([prefix + t for t in tails], sp)
    assert eng._allocator.hit_tokens > hits0  # the resume path actually ran
    assert cold.output_tokens == _hf_greedy(model, prefix + tails[0], 8)
    for tail, res in zip(tails, warm):
        assert res.output_tokens == _hf_greedy(model, prefix + tail, 8)


def test_packed_kv_quant_matches_padded_kv_quant(tiny):
    """int8 KV pages quantize identically under both dispatch modes (same
    commit path), so greedy tokens stay identical packed vs padded."""
    _, params, cfg = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 17, 33)]
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    padded = _make_engine(params, cfg, kv_quant=True)
    packed = _make_engine(params, cfg, kv_quant=True, prefill_token_budget=48)
    assert ([r.output_tokens for r in packed.generate(prompts, sp)]
            == [r.output_tokens for r in padded.generate(prompts, sp)])


# ------------------------------------------------ compiled-shape discipline


def test_packed_warmup_compiles_exact_shape_set(tiny):
    """warmup() must compile exactly one forward_paged_packed program per
    packed_prefill_buckets() entry, and live traffic (mixed lengths,
    admission churn, prefix-cache resumes) must add ZERO — the packed
    path's whole point is collapsing the (row bucket x width) shape zoo."""
    from githubrepostorag_tpu.models.qwen2 import forward_paged_packed

    _, params, cfg = tiny
    # budget 40 (not the 48 other tests use): forward_paged_packed is a
    # module-global jit, so a shared buffer shape would arrive pre-compiled
    # and break the exact-count assertion below
    eng = _make_engine(params, cfg, prefill_token_budget=40)
    assert eng.packed_prefill_buckets() == [1, 2, 4]
    with compile_guard(forward_paged_packed._cache_size,
                       expect=len(eng.packed_prefill_buckets()),
                       label="packed warmup"):
        eng.warmup()
    rng = np.random.default_rng(13)
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 16, 17, 70, 33)]
    with compile_guard(forward_paged_packed._cache_size,
                       label="mixed packed traffic"):
        eng.generate(prompts, sp)
        eng.generate(prompts, sp)  # warm repeat: prefix-cache resume traffic
    # the collapse claim: packed shapes never exceed the padded engine's,
    # one program a row bucket (its width rungs are branches of it)
    padded = _make_engine(params, cfg)
    row_buckets = {min(b, padded.max_num_seqs)
                   for b in (1, 2, 4, 8) if b <= padded.max_num_seqs}
    assert len(padded.prefill_width_buckets) > 1
    assert len(eng.packed_prefill_buckets()) <= len(row_buckets)
