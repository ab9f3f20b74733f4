"""N-gram speculative decoding (serving/spec_burst.py + engine
spec_ngram_k): outputs must be token-identical to the burst path for every
sampling config — speculation is a scheduling change, not a model change —
and repetitive contexts must actually accept drafts.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from githubrepostorag_tpu.serving import Engine, SamplingParams
from githubrepostorag_tpu.serving.spec_burst import ngram_draft_device
from tests.helpers.step_paths import count_step_paths

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


# ------------------------------------------------------------- proposals --


def _drafts(rows: list[list[int]], k: int) -> list[list[int]]:
    """``ngram_draft_device`` over token lists: each row's draft, cut to the
    length the drafter reports."""
    width = max(8, max(len(r) for r in rows))
    hist = np.zeros((len(rows), width), dtype=np.int32)
    for i, r in enumerate(rows):
        hist[i, : len(r)] = r
    draft, dlen = ngram_draft_device(
        jnp.asarray(hist), jnp.asarray([len(r) for r in rows], dtype=jnp.int32), k)
    draft, dlen = np.asarray(draft), np.asarray(dlen)
    return [draft[i, : dlen[i]].tolist() for i in range(len(rows))]


def _bigram_draft_reference(tokens: list[int], k: int) -> list[int]:
    """The drafter in plain Python, the parity oracle: the EARLIEST earlier
    occurrence of the final bigram that ends before the suffix begins, and
    the (up to k) tokens that followed it.  Empty when nothing matches."""
    n = len(tokens)
    if k <= 0 or n < 4:
        return []
    suffix = tokens[-2:]
    for s in range(n - 3):
        if tokens[s : s + 2] == suffix:
            return tokens[s + 2 : s + 2 + k]
    return []


def test_ngram_draft_finds_repeats():
    toks = [1, 2, 3, 9, 9, 1, 2, 3]
    # suffix [2,3] occurred at 1; the continuation there was [9, 9, 1]
    assert _drafts([toks], 3) == [[9, 9, 1]]
    assert _drafts([toks], 1) == [[9]]
    assert _drafts([[5, 6, 7]], 4) == [[]]  # nothing repeats
    assert _drafts([[]], 4) == [[]]
    assert _drafts([[1]], 0) == [[]]


def test_ngram_draft_prefers_earliest():
    # [8,2] occurs twice earlier; the EARLIEST occurrence (index 0, vLLM
    # prompt-lookup order) wins — its continuation is [3], not the more
    # recent match's [5].  Earliest matters on repetitive text: the most
    # recent match sits just before the suffix and truncates the draft.
    toks = [8, 2, 3, 0, 8, 2, 5, 0, 8, 2]
    assert _drafts([toks], 1) == [[3]]
    # suffix [3,4] first occurred at 2 -> continuation [7], not the later
    # occurrence's [9]
    toks2 = [1, 2, 3, 4, 7, 3, 4, 9, 1, 2, 3, 4]
    assert _drafts([toks2], 1) == [[7]]


def test_ngram_draft_repeat_run_drafts_full_k():
    # a pure repeat run: earliest-match ordering drafts k tokens;
    # most-recent ordering would draft only 1
    toks = [4, 1, 7] + [9] * 12
    assert _drafts([toks], 6) == [[9] * 6]


# ----------------------------------------------------------------- engine --


def test_spec_greedy_token_identical_and_accepts(tiny):
    model, params, cfg = tiny
    # repetitive prompt: tiny random models loop quickly, and the prompt
    # itself gives the n-gram matcher material from step one
    prompt = [7, 8, 9, 10] * 8
    sp = SamplingParams(max_tokens=32, temperature=0.0, stop_token_ids=(),
                        repetition_penalty=1.0)
    plain = _engine(params, cfg).generate([prompt], sp)[0].output_tokens

    eng = _engine(params, cfg, spec_ngram_k=4)
    got = eng.generate([prompt], sp)[0].output_tokens
    assert got == plain
    assert eng.spec_proposed > 0
    assert eng.spec_accepted > 0, (
        f"no draft accepted over {eng.spec_proposed} proposed — speculation "
        "never pays off even on a looping sequence"
    )

    # HF ground truth for the same prompt
    with torch.no_grad():
        hf = model.generate(torch.tensor([prompt]), max_new_tokens=32,
                            do_sample=False, pad_token_id=0, eos_token_id=None,
                            use_cache=True)
    assert got == hf[0, len(prompt):].tolist()


def _count_decode_paths(eng):
    """The engine's calls to each step path, and under ``spec_on_mixed`` the
    speculative bursts that saw a row that is not plain greedy."""
    def before(name):
        if name == "_spec_burst_step":
            calls["spec_on_mixed"] += any(
                r.sampling.temperature > 0.0 or r.sampling.repetition_penalty != 1.0
                for r in eng._row_req.values() if r.state == "running")

    calls = count_step_paths(eng, before)
    calls["spec_on_mixed"] = 0
    return calls


def test_spec_matches_plain_on_mixed_batch(tiny):
    """Greedy, greedy+penalty, and sampled rows in one speculative batch:
    the step is demoted to plain decode, and all must match the burst
    engine run with the same seed."""
    _, params, cfg = tiny
    rng = np.random.default_rng(5)
    prompts = [
        [1, 2, 3, 4] * 6,
        rng.integers(0, cfg.vocab_size, 24).tolist(),
        rng.integers(0, cfg.vocab_size, 17).tolist(),
    ]
    sps = [
        SamplingParams(max_tokens=16, temperature=0.0, stop_token_ids=()),
        SamplingParams(max_tokens=16, temperature=0.0, stop_token_ids=(),
                       repetition_penalty=1.3),
        SamplingParams(max_tokens=16, temperature=0.8, top_p=0.9,
                       stop_token_ids=()),
    ]
    plain = _engine(params, cfg, rng_seed=3)
    spec = _engine(params, cfg, rng_seed=3, spec_ngram_k=4)
    calls = _count_decode_paths(spec)
    res_p = plain.generate(prompts, sps)
    res_s = spec.generate(prompts, sps)
    # a mixed batch decodes plainly; speculation only ever saw greedy rows
    assert calls["_decode_step"] > 0 and calls["spec_on_mixed"] == 0
    # deterministic rows must be identical across scheduling modes
    assert res_s[0].output_tokens == res_p[0].output_tokens
    assert res_s[1].output_tokens == res_p[1].output_tokens
    # the sampled row draws from a different rng call sequence; assert
    # validity, not equality
    assert len(res_s[2].output_tokens) == 16
    # penalty/sampled rows never proposed drafts
    solo = _engine(params, cfg, spec_ngram_k=4)
    calls = _count_decode_paths(solo)
    solo.generate([prompts[1]], [sps[1]])
    assert solo.spec_proposed == 0 and calls["_spec_burst_step"] == 0


def test_spec_respects_stop_and_max_tokens(tiny):
    """A stop token inside an accepted draft run must end the request at the
    stop, and page accounting must balance."""
    _, params, cfg = tiny
    prompt = [3, 4, 5] * 8
    base = _engine(params, cfg)
    sp0 = SamplingParams(max_tokens=24, temperature=0.0, stop_token_ids=())
    ref = base.generate([prompt], sp0)[0].output_tokens
    stop = ref[5]  # force a stop mid-stream
    sp = SamplingParams(max_tokens=24, temperature=0.0, stop_token_ids=(stop,))
    expect = _engine(params, cfg).generate([prompt], sp)[0]

    eng = _engine(params, cfg, spec_ngram_k=4)
    got = eng.generate([prompt], sp)[0]
    assert got.output_tokens == expect.output_tokens
    assert got.finish_reason == expect.finish_reason == "stop"
    assert eng._allocator.free_count == eng._allocator.num_pages
    assert not eng.has_work()


def test_spec_with_prefix_cache_and_continuous_batching(tiny):
    """Speculation composes with the other engine features: a second
    request admitted mid-run shares the prefix cache and both outputs
    match the plain engine."""
    _, params, cfg = tiny
    p1 = [6, 7, 8, 9] * 8
    p2 = [6, 7, 8, 9] * 8 + [1, 2, 3]
    sp = SamplingParams(max_tokens=40, temperature=0.0, stop_token_ids=())
    plain = _engine(params, cfg)
    exp1 = plain.generate([p1], sp)[0].output_tokens
    exp2 = plain.generate([p2], sp)[0].output_tokens

    eng = _engine(params, cfg, spec_ngram_k=4)
    r1 = eng.add_request(p1, sp)
    # one step commits at most 1 + spec_iters * (k + 1) = 21 tokens: r1 is
    # still running when r2 arrives
    assert eng.step() == []
    r2 = eng.add_request(p2, sp)
    done = {}
    while eng.has_work():
        for res in eng.step():
            done[res.request_id] = res
    assert done[r1].output_tokens == exp1
    assert done[r2].output_tokens == exp2
    assert eng._allocator.hit_tokens > 0  # p2 resumed from p1's pages


# ----------------------------------------------------- fused spec bursts --


def test_ngram_draft_device_matches_expectations():
    hist = np.zeros((3, 16), dtype=np.int32)
    # row 0: bigram [1,2] recurs — earliest at 0, followers [3, 9]
    hist[0, :7] = [1, 2, 3, 9, 9, 1, 2]
    # row 1: no repeat
    hist[1, :5] = [5, 6, 7, 8, 9]
    # row 2: too short for a match (needs >= 4 tokens)
    hist[2, :3] = [4, 4, 4]
    draft, dlen = ngram_draft_device(jnp.asarray(hist),
                                     jnp.asarray([7, 5, 3], dtype=jnp.int32), 4)
    draft, dlen = np.asarray(draft), np.asarray(dlen)
    assert dlen.tolist() == [4, 0, 0]
    assert draft[0, :4].tolist() == [3, 9, 9, 1]


def test_spec_burst_token_identical_and_accepts(tiny):
    """The fused on-device spec burst must produce byte-identical greedy
    output to the plain burst engine, while actually accepting drafts on a
    looping sequence."""
    model, params, cfg = tiny
    prompt = [7, 8, 9, 10] * 8
    sp = SamplingParams(max_tokens=32, temperature=0.0, stop_token_ids=(),
                        repetition_penalty=1.0)
    plain = _engine(params, cfg).generate([prompt], sp)[0].output_tokens

    eng = _engine(params, cfg, spec_ngram_k=4, spec_iters=4)
    got = eng.generate([prompt], sp)[0].output_tokens
    assert got == plain
    assert eng.spec_proposed > 0
    assert eng.spec_accepted > 0

    with torch.no_grad():
        hf = model.generate(torch.tensor([prompt]), max_new_tokens=32,
                            do_sample=False, pad_token_id=0, eos_token_id=None,
                            use_cache=True)
    assert got == hf[0, len(prompt):].tolist()


def test_spec_burst_batch_and_stop(tiny):
    """Multi-row fused spec bursts: random prompts (no drafts -> 1
    token/iteration) and looping prompts in one batch, stop tokens and
    max_tokens respected mid-burst."""
    _, params, cfg = tiny
    rng = np.random.default_rng(9)
    prompts = [
        [3, 4, 5] * 10,
        rng.integers(0, cfg.vocab_size, 21).tolist(),
    ]
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_ids=())
    plain = _engine(params, cfg)
    res_p = plain.generate(prompts, [sp, sp])
    spec = _engine(params, cfg, spec_ngram_k=4, spec_iters=3)
    res_s = spec.generate(prompts, [sp, sp])
    for a, b in zip(res_s, res_p):
        assert a.output_tokens == b.output_tokens
        assert a.finish_reason == "length"

    # stop token: generation ends exactly where the plain engine ends
    tok_stop = res_p[0].output_tokens[4]
    sp_stop = SamplingParams(max_tokens=12, temperature=0.0,
                             stop_token_ids=(tok_stop,))
    stop_p = _engine(params, cfg).generate([prompts[0]], sp_stop)[0]
    stop_s = _engine(params, cfg, spec_ngram_k=4,
                     spec_iters=3).generate([prompts[0]], sp_stop)[0]
    assert stop_s.output_tokens == stop_p.output_tokens
    assert stop_s.finish_reason == stop_p.finish_reason == "stop"


def test_spec_burst_falls_back_for_sampled_rows(tiny):
    """A sampled row in the batch drops the step to plain decode — outputs
    still match the plain engine for the deterministic row, and once the
    sampled row is gone the greedy row speculates again."""
    _, params, cfg = tiny
    prompts = [[5, 6, 7] * 8, [9, 1, 2] * 7]
    sps = [
        SamplingParams(max_tokens=16, temperature=0.0, stop_token_ids=()),
        SamplingParams(max_tokens=4, temperature=0.9, stop_token_ids=()),
    ]
    plain = _engine(params, cfg, rng_seed=11)
    spec = _engine(params, cfg, rng_seed=11, spec_ngram_k=4, spec_iters=4)
    calls = _count_decode_paths(spec)
    res_p = plain.generate(prompts, sps)
    res_s = spec.generate(prompts, sps)
    assert res_s[0].output_tokens == res_p[0].output_tokens
    assert calls["_decode_step"] > 0 and calls["spec_on_mixed"] == 0
    assert calls["_spec_burst_step"] > 0  # back on the burst, chain landed


def test_rag_quoting_construction():
    """A RAG-shaped speculation workload: zero
    layers + an untied lm_head whose column o is embed row o-1 make greedy
    argmax narrate the token cycle t -> t+1, and a prompt of SHUFFLED
    consecutive cycle segments gives the bigram prompt-lookup drafter
    partial acceptance — accepts inside each chunk's span, mispredicts at
    chunk boundaries."""
    import dataclasses

    import jax
    import numpy as np

    from githubrepostorag_tpu.models import Qwen2Config, init_params

    cfg = dataclasses.replace(Qwen2Config.tiny(), tie_word_embeddings=False)
    params = init_params(cfg, jax.random.PRNGKey(5))
    params = dict(params,
                  layers=jax.tree.map(jnp.zeros_like, params["layers"]),
                  lm_head=jnp.roll(params["embed"], 1, axis=0).T)

    span, n_chunks, s0 = 16, 4, 100
    rng = np.random.default_rng(17)
    chunk_list = [list(range(s0 + span * j, s0 + span * (j + 1)))
                  for j in range(n_chunks)]
    prompt = [t for j in rng.permutation(n_chunks) for t in chunk_list[j]] + [s0]

    sp = SamplingParams(max_tokens=40, temperature=0.0, stop_token_ids=())
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=16,
                 max_seq_len=256, prefill_chunk=32, kv_dtype=jnp.float32,
                 spec_ngram_k=8, spec_iters=8)
    out = eng.generate([prompt], sp)[0].output_tokens
    # the model narrates the cycle (the "answer quotes the chunks")
    assert out == list(range(s0 + 1, s0 + 41))
    # and the drafter's acceptance is PARTIAL: well above chance, below 1.0
    acceptance = eng.spec_accepted / max(eng.spec_proposed, 1)
    assert 0.3 < acceptance < 1.0, acceptance


# ----------------------------------------------- proposal parity + edges --


def test_ngram_draft_matches_reference_fuzz():
    """The vectorized drafter must be decision-identical to the plain-Python
    reference on thousands of random cases (small alphabets force repeats;
    empty and too-short histories included)."""
    rng = np.random.default_rng(23)
    for k in range(1, 6):
        rows = [rng.integers(0, int(rng.integers(2, 8)), int(rng.integers(0, 40))).tolist()
                for _ in range(400)]
        got = _drafts(rows, k)
        for toks, g in zip(rows, got):
            assert g == _bigram_draft_reference(toks, k), (toks, k, g)


def test_spec_burst_kv_quant_round_trip_parity(tiny):
    """Int8 KV through the fused spec burst: the scan carries the scale
    pools alongside the quantized pages, and output must be token-identical
    to the PLAIN engine on the same int8 pools — quantization error is
    shared, scheduling must not add any."""
    _, params, cfg = tiny
    prompt = [7, 8, 9, 10] * 8
    sp = SamplingParams(max_tokens=24, temperature=0.0, stop_token_ids=())
    plain = _engine(params, cfg, kv_quant=True).generate([prompt], sp)[0]
    eng = _engine(params, cfg, kv_quant=True, spec_ngram_k=4, spec_iters=3)
    got = eng.generate([prompt], sp)[0]
    assert got.output_tokens == plain.output_tokens
    assert eng.spec_proposed > 0 and eng.spec_accepted > 0
    assert eng._allocator.free_count == eng._allocator.num_pages


def test_spec_burst_draft_overflowing_row_limits(tiny):
    """A row near its KV budget: ``row_limits`` forces the draft length to
    clip mid-iteration (dlen = limit - len - 1) so the correction token
    always has a slot.  The request must end exactly where the plain
    engine ends, with pages balanced."""
    _, params, cfg = tiny
    # max_seq_len=32 -> row limit 31; the 20-token looping prompt leaves
    # 12 decode slots, so a k=4 draft must clip in the final iterations
    geom = dict(max_seq_len=32, page_size=4, num_pages=32)
    prompt = [5, 6, 7, 8] * 5
    sp = SamplingParams(max_tokens=20, temperature=0.0, stop_token_ids=())
    plain = _engine(params, cfg, **geom).generate([prompt], sp)[0]
    eng = _engine(params, cfg, spec_ngram_k=4, spec_iters=4, **geom)
    got = eng.generate([prompt], sp)[0]
    assert got.output_tokens == plain.output_tokens
    assert got.finish_reason == plain.finish_reason == "length"
    assert eng.spec_accepted > 0  # the loop really drafted near the limit
    assert eng._allocator.free_count == eng._allocator.num_pages
    assert not eng.has_work()


def test_zero_recompiles_across_mixed_ngram_plain_traffic(tiny):
    """After warmup, greedy batches at both row buckets (the burst), a
    sampled row demoting its steps to plain decode, and a penalised greedy
    row (plain decode, no sampling filter) compile ZERO new XLA programs:
    warmup covers the plain fallback of a speculating engine."""
    from tests.helpers.compile_guard import compile_guard, watchdog_counter

    _, params, cfg = tiny
    eng = _engine(params, cfg, max_num_seqs=2, spec_ngram_k=2, spec_iters=2)
    eng.warmup()
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
    sampled = SamplingParams(max_tokens=4, temperature=0.8, stop_token_ids=())
    penalised = SamplingParams(max_tokens=4, temperature=0.0, stop_token_ids=(),
                               repetition_penalty=1.3)
    with compile_guard(watchdog_counter(), label="mixed n-gram/plain traffic"):
        eng.generate([[1, 2, 3]], sp)
        eng.generate([[4, 5, 6], [7, 8, 9]], sp)
        eng.generate([[1, 2, 3], [4, 5, 6]], [sp, sampled])
        eng.generate([[1, 2, 3], [4, 5, 6]], [sp, penalised])
