"""Sequence-parallel (ring attention) long-context prefill in serving:
token parity with the chunked single-device path, pool-content parity, and
mixed long+short scheduling.  Runs on the virtual 8-device CPU mesh.

Reference contrast: the reference caps context (vLLM --max-model-len 11712,
SURVEY.md §5.7) — this path *scales* it over the sp mesh axis instead.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from githubrepostorag_tpu.parallel import MeshPlan, make_mesh
from githubrepostorag_tpu.serving import Engine, SamplingParams

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    from githubrepostorag_tpu.models.hf_loader import config_from_hf, params_from_state_dict

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


def _engine(params, cfg, **kw):
    defaults = dict(
        max_num_seqs=4, num_pages=64, page_size=8, max_seq_len=256,
        prefill_chunk=32, kv_dtype=jnp.float32, decode_burst=4,
    )
    defaults.update(kw)
    return Engine(params, cfg, **defaults)


def _sp_engine(params, cfg, threshold=40, **kw):
    return _engine(
        params, cfg, mesh=make_mesh(MeshPlan(sp=2)),
        sp_prefill_threshold=threshold, **kw,
    )


def test_ring_prefill_token_parity_with_chunked(tiny):
    model, params, cfg = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=48).tolist()
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_ids=(),
                        repetition_penalty=1.2)

    expected = _engine(params, cfg).generate([prompt], sp)[0].output_tokens

    eng = _sp_engine(params, cfg)
    got = eng.generate([prompt], sp)[0].output_tokens
    assert eng.sp_prefills == 1, "prompt above threshold must ride the sp path"
    assert got == expected

    # HF ground truth too: the ring path must match the reference model
    ids = torch.tensor([prompt])
    with torch.no_grad():
        hf = model.generate(ids, max_new_tokens=12, do_sample=False,
                            pad_token_id=0, eos_token_id=None,
                            repetition_penalty=1.2, use_cache=True)
    assert got == hf[0, len(prompt):].tolist()


def test_ring_prefill_pool_contents_match_chunked(tiny):
    """The KV pages the ring path writes must equal the chunked path's —
    decode after a ring prefill reads the same cache bytes."""
    _, params, cfg = tiny
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, size=56).tolist()
    sp = SamplingParams(max_tokens=1, temperature=0.0, stop_token_ids=())

    eng_a = _engine(params, cfg)
    eng_b = _sp_engine(params, cfg)
    eng_a.generate([prompt], sp)
    eng_b.generate([prompt], sp)
    assert eng_b.sp_prefills == 1
    # same admission order -> same allocator decisions -> same block tables
    k_a, k_b = np.asarray(eng_a._k_pages), np.asarray(eng_b._k_pages)
    v_a, v_b = np.asarray(eng_a._v_pages), np.asarray(eng_b._v_pages)
    np.testing.assert_allclose(k_a, k_b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v_a, v_b, rtol=1e-5, atol=1e-5)


def test_ring_prefill_kv_quant_matches_chunked(tiny):
    """kv_quant composes with the ring path: the ring commit quantizes per
    page with the SAME first-write-fixes-the-scale rule as the chunked
    path (serving/kv_cache.quantize_kv_paged).  In this geometry every
    prefill chunk covers whole pages, so both paths fix identical scales
    — decoded tokens must match exactly, and the int8 page bytes within a
    quantization step: the paths are NOT bit-identical, because the
    chunked path's later chunks attend over already-quantized earlier
    pages (its K/V inherit that rounding) while the ring path computes
    the whole prompt full-precision before one quantized commit."""
    _, params, cfg = tiny
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=48).tolist()
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_ids=())

    eng_a = _engine(params, cfg, kv_quant=True)
    eng_b = _sp_engine(params, cfg, kv_quant=True)
    expected = eng_a.generate([prompt], sp)[0].output_tokens
    got = eng_b.generate([prompt], sp)[0].output_tokens
    assert eng_b.sp_prefills == 1, "prompt above threshold must ride the sp path"
    assert got == expected
    for a, b in ((eng_a._k_pages, eng_b._k_pages),
                 (eng_a._v_pages, eng_b._v_pages)):
        diff = np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32))
        assert diff.max() <= 2, f"pages diverged beyond rounding: {diff.max()}"
    np.testing.assert_allclose(
        np.asarray(eng_a._k_scales), np.asarray(eng_b._k_scales),
        rtol=2e-2, atol=1e-7,
    )


def test_short_prompts_stay_on_chunked_path(tiny):
    _, params, cfg = tiny
    prompt = list(range(1, 21))  # 20 tokens < threshold 40
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())
    eng = _sp_engine(params, cfg)
    expected = _engine(params, cfg).generate([prompt], sp)[0].output_tokens
    assert eng.generate([prompt], sp)[0].output_tokens == expected
    assert eng.sp_prefills == 0


def test_mixed_long_short_continuous_batching(tiny):
    """A long prompt admitted while a short stream decodes: both must match
    their solo runs and the long one must use the sp path."""
    _, params, cfg = tiny
    rng = np.random.default_rng(2)
    short = [1, 2, 3, 4]
    long_p = rng.integers(0, cfg.vocab_size, size=64).tolist()
    sp = SamplingParams(max_tokens=10, temperature=0.0, stop_token_ids=())

    solo_short = _engine(params, cfg).generate([short], sp)[0].output_tokens
    solo_long = _engine(params, cfg).generate([long_p], sp)[0].output_tokens

    eng = _sp_engine(params, cfg)
    r1 = eng.add_request(short, sp)
    for _ in range(2):
        eng.step()
    r2 = eng.add_request(long_p, sp)
    done = {}
    while eng.has_work():
        for res in eng.step():
            done[res.request_id] = res
    assert eng.sp_prefills == 1
    assert done[r1].output_tokens == solo_short
    assert done[r2].output_tokens == solo_long


def test_sp_prefill_registers_prefix_for_chunked_followers(tiny):
    """A ring-prefilled prompt publishes its pages: a later SHORT prompt
    sharing the prefix (below the sp threshold) resumes from the cache."""
    _, params, cfg = tiny
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab_size, size=24).tolist()
    long_p = prefix + rng.integers(0, cfg.vocab_size, size=24).tolist()  # 48
    short_p = prefix + [7, 8, 9]  # 27 tokens, chunked path
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())

    eng = _sp_engine(params, cfg, threshold=40)
    eng.generate([long_p], sp)
    assert eng.sp_prefills == 1
    expected = _engine(params, cfg).generate([short_p], sp)[0].output_tokens
    got = eng.generate([short_p], sp)[0].output_tokens
    assert got == expected
    assert eng._allocator.hit_tokens == 24  # 3 pages resumed from the cache


def test_warmup_precompiles_ring_prefill_buckets(tiny):
    """ADVICE r02: warmup() must run a throwaway above-threshold prompt per
    ring-prefill width bucket, so the first live long prompt never pays the
    ring program's XLA compile mid-request.  With threshold 40 and
    max_seq_len 256 the width buckets a prompt can hit are 64/128/256 ->
    three sp prefills during warmup."""
    _, params, cfg = tiny
    eng = _sp_engine(params, cfg, threshold=40)
    eng.warmup()
    assert eng.sp_prefills == 3
    # engine state is clean after warmup: a real request still works and
    # takes the sp path without growing the compile count
    sp = SamplingParams(max_tokens=4, temperature=0.0, stop_token_ids=())
    long_p = np.random.default_rng(5).integers(0, cfg.vocab_size, 64).tolist()
    expected = _engine(params, cfg).generate([long_p], sp)[0].output_tokens
    assert eng.generate([long_p], sp)[0].output_tokens == expected
    assert eng.sp_prefills == 4


def test_warmup_skips_ring_prefill_when_disabled(tiny):
    _, params, cfg = tiny
    eng = _engine(params, cfg)  # no sp axis, no threshold
    eng.warmup()
    assert eng.sp_prefills == 0


# ---- segment-packed ring passes: several long prompts in one pass ---------


def test_packed_ring_multi_segment_token_parity(tiny):
    """Three long prompts admitted together flatten into ONE segment-packed
    ring pass; every stream's tokens must match the chunked single-device
    path run solo."""
    _, params, cfg = tiny
    rng = np.random.default_rng(11)
    lens = (48, 64, 56)  # mixed lengths, all above threshold 40, sum 168
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())

    solo = [_engine(params, cfg).generate([p], sp)[0].output_tokens
            for p in prompts]

    packed = _sp_engine(params, cfg)
    got = [r.output_tokens for r in packed.generate(prompts, sp)]
    assert packed.sp_prefills == 1, "three segments must share one ring pass"
    assert packed.sp_ring_segments == 3
    assert got == solo


def test_packed_ring_pool_contents_match_chunked(tiny):
    """The packed pass commits every segment's K/V to the same pages with
    the same values as the chunked path — same admission order, same
    allocator decisions, same cache content."""
    _, params, cfg = tiny
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (56, 48)]
    sp = SamplingParams(max_tokens=1, temperature=0.0, stop_token_ids=())

    a = _sp_engine(params, cfg)
    b = _engine(params, cfg)
    a.generate(prompts, sp)
    b.generate(prompts, sp)
    assert a.sp_prefills == 1 and a.sp_ring_segments == 2
    np.testing.assert_allclose(np.asarray(a._k_pages), np.asarray(b._k_pages),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a._v_pages), np.asarray(b._v_pages),
                               rtol=1e-5, atol=1e-5)


def test_packed_ring_kv_quant_parity(tiny):
    """kv_quant composes with segment packing: the pass computes every
    prompt full-precision and quantizes once at commit with the chunked
    path's first-write-fixes-the-scale rule (whole pages a chunk here, as
    in test_ring_prefill_kv_quant_matches_chunked), so decoded tokens must
    match the chunked engine's exactly and the int8 page bytes within
    rounding."""
    _, params, cfg = tiny
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (48, 64)]
    sp = SamplingParams(max_tokens=10, temperature=0.0, stop_token_ids=())

    a = _sp_engine(params, cfg, kv_quant=True)
    b = _engine(params, cfg, kv_quant=True)
    got_a = [r.output_tokens for r in a.generate(prompts, sp)]
    got_b = [r.output_tokens for r in b.generate(prompts, sp)]
    assert a.sp_prefills == 1
    assert got_a == got_b
    for pa, pb in ((a._k_pages, b._k_pages), (a._v_pages, b._v_pages)):
        diff = np.abs(np.asarray(pa, np.int32) - np.asarray(pb, np.int32))
        assert diff.max() <= 2, f"pages diverged beyond rounding: {diff.max()}"


def test_packed_ring_token_budget_splits_passes(tiny):
    """A wave over the widest ladder width front-packs FIFO: the pass stops
    at the first prompt that doesn't fit and the leftover rides the NEXT
    step's pass — nothing starves, tokens match the solo runs."""
    _, params, cfg = tiny
    rng = np.random.default_rng(14)
    lens = (120, 120, 112)  # 240 fits the 256-wide cap, the third doesn't
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())

    solo = [_engine(params, cfg).generate([p], sp)[0].output_tokens
            for p in prompts]
    eng = _sp_engine(params, cfg)
    got = [r.output_tokens for r in eng.generate(prompts, sp)]
    assert eng.sp_prefills == 2, "240-token pass then the 112-token leftover"
    assert eng.sp_ring_segments == 3
    assert got == solo


def test_packed_ring_mixed_with_short_chunked_rows(tiny):
    """Long prompts pack into a ring pass while a short prompt in the SAME
    admission wave rides the chunked path; all match their solo runs."""
    _, params, cfg = tiny
    rng = np.random.default_rng(16)
    long_a = rng.integers(0, cfg.vocab_size, 48).tolist()
    long_b = rng.integers(0, cfg.vocab_size, 44).tolist()
    short = [3, 1, 4, 1, 5]
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())

    solo = [_engine(params, cfg).generate([p], sp)[0].output_tokens
            for p in (long_a, long_b, short)]
    eng = _sp_engine(params, cfg)
    got = [r.output_tokens for r in eng.generate([long_a, long_b, short], sp)]
    assert eng.sp_prefills == 1 and eng.sp_ring_segments == 2
    assert got == solo


def test_packed_ring_registers_prefix_for_chunked_followers(tiny):
    """Packed-ring segments publish their pages like the one-sequence path:
    a later short prompt sharing a packed segment's prefix resumes from
    the cache on the chunked path."""
    _, params, cfg = tiny
    rng = np.random.default_rng(15)
    prefix = rng.integers(0, cfg.vocab_size, 24).tolist()
    long_a = prefix + rng.integers(0, cfg.vocab_size, 24).tolist()  # 48
    long_b = rng.integers(0, cfg.vocab_size, 56).tolist()
    short = prefix + [5, 6]  # 26 tokens, chunked path
    sp = SamplingParams(max_tokens=4, temperature=0.0, stop_token_ids=())

    eng = _sp_engine(params, cfg)
    eng.generate([long_a, long_b], sp)
    assert eng.sp_prefills == 1 and eng.sp_ring_segments == 2
    expected = _engine(params, cfg).generate([short], sp)[0].output_tokens
    assert eng.generate([short], sp)[0].output_tokens == expected
    assert eng._allocator.hit_tokens == 24  # 3 pages resumed from the cache
