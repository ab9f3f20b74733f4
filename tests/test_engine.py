"""Generation engine: paged prefill/decode vs HF generate, continuous
batching, streaming callbacks, cancellation, page accounting."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.serving import Engine, SamplingParams
from tests.helpers.step_paths import DECODE_PATHS, count_step_paths
from tests.helpers.step_programs import recorded_waves

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


def test_greedy_matches_hf(tiny):
    model, params, cfg = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=23).tolist()
    eng = _make_engine(params, cfg)
    res = eng.generate([prompt], SamplingParams(temperature=0.0, max_tokens=10))[0]
    assert res.finish_reason == "length"
    assert res.output_tokens == _hf_greedy(model, prompt, 10)


def test_concurrent_requests_match_individual(tiny):
    model, params, cfg = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (5, 17, 33)]
    eng = _make_engine(params, cfg)
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    results = eng.generate(prompts, sp)
    for prompt, res in zip(prompts, results):
        assert res.output_tokens == _hf_greedy(model, prompt, 8), "batched != individual"


def test_chunked_prefill_long_prompt(tiny):
    model, params, cfg = tiny
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, size=70).tolist()  # > prefill_chunk=32
    eng = _make_engine(params, cfg)
    res = eng.generate([prompt], SamplingParams(temperature=0.0, max_tokens=5))[0]
    assert res.output_tokens == _hf_greedy(model, prompt, 5)


def test_width_bucketed_prefill_matches_hf(tiny, monkeypatch):
    """A wave runs at the narrowest rung of the derived ladder that holds its
    longest pending chunk (the ``width`` of its annotation): a wave of short
    rows the narrow rung, a wave with one long row the full one.  Tokens are
    HF's across short, rung-boundary and multi-chunk (resume) prompts, alone
    and mixed in one batch."""
    from githubrepostorag_tpu.metrics import PREFILL_WAVE

    model, params, cfg = tiny
    rng = np.random.default_rng(7)
    # chunk=32 over pages of 8 -> rungs [32, 16, 8]: 5 -> 8, 16 -> 16,
    # 17 -> 32, 70 -> chunks 32+32+6 (the 6-token resume chunk rides an
    # 8-wide wave)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (5, 16, 17, 70)]
    eng = _make_engine(params, cfg, page_size=8)
    assert eng.prefill_width_buckets == [32, 16, 8]
    eng.warmup()
    waves = recorded_waves(monkeypatch)
    counted = lambda: {w: PREFILL_WAVE.labels(width=str(w))._value.get()  # noqa: E731
                       for w in eng.prefill_width_buckets}
    before, padded = counted(), eng.prefill_padded_tokens
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    for prompt in prompts:
        assert eng.generate([prompt], sp)[0].output_tokens == _hf_greedy(model, prompt, 8)
    assert [w["width"] for w in waves] == [8, 16, 32, 32, 32, 8]
    assert [w["padded_tokens"] for w in waves] == [8, 16, 32, 32, 32, 8]  # one row each
    assert [w["new_tokens"] for w in waves] == [5, 16, 17, 32, 32, 6]
    assert {w: n - before[w] for w, n in counted().items()} == {32: 3, 16: 1, 8: 2}
    assert eng.prefill_padded_tokens - padded == 128
    del waves[:]  # fresh prompts: the prefix cache holds the first four
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (5, 16, 17, 70)]
    for prompt, res in zip(prompts, eng.generate(prompts, sp)):
        assert res.output_tokens == _hf_greedy(model, prompt, 8)
    # four rows (and the long one among them) run whole; then its 6-token remainder
    assert [(w["rows"], w["width"], w["padded_tokens"]) for w in waves] == [
        (4, 32, 128), (1, 32, 32), (1, 8, 8)]


def test_streaming_callback_order(tiny):
    _, params, cfg = tiny
    eng = _make_engine(params, cfg)
    seen: list[tuple[str, int]] = []
    rid = eng.add_request(
        [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=6),
        on_token=lambda r, t: seen.append((r, t)),
    )
    done = []
    while eng.has_work():
        done.extend(eng.step())
    assert [t for _, t in seen] == done[0].output_tokens
    assert all(r == rid for r, _ in seen)


def test_stop_token_ends_generation(tiny):
    model, params, cfg = tiny
    prompt = [7, 8, 9, 10, 11]
    first = _hf_greedy(model, prompt, 1)[0]
    eng = _make_engine(params, cfg)
    res = eng.generate([prompt], SamplingParams(temperature=0.0, max_tokens=20, stop_token_ids=(first,)))[0]
    assert res.finish_reason == "stop"
    assert res.output_tokens == [first]


def test_cancellation(tiny):
    _, params, cfg = tiny
    eng = _make_engine(params, cfg)
    rid = eng.add_request([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=50))
    eng.step()  # prefill + first token
    eng.cancel(rid)
    done = []
    while eng.has_work():
        done.extend(eng.step())
    assert done[0].finish_reason == "cancelled"
    assert eng._allocator.free_count == eng._allocator.num_pages  # pages recycled


def test_pages_exhaustion_queues_requests(tiny):
    _, params, cfg = tiny
    # only 8 pages of 8 tokens: two 20+16-token requests can't both fit
    eng = _make_engine(params, cfg, num_pages=8, max_seq_len=64)
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    prompts = [[1] * 20, [2] * 20, [3] * 20]
    results = eng.generate(prompts, sp)
    assert all(r.finish_reason == "length" for r in results)
    assert all(len(r.output_tokens) == 16 for r in results)
    assert eng._allocator.free_count == eng._allocator.num_pages


def test_sampled_generation_respects_seed_and_temperature(tiny):
    _, params, cfg = tiny
    prompt = list(range(1, 12))
    sp = SamplingParams(temperature=0.8, top_p=0.95, max_tokens=12)
    r1 = _make_engine(params, cfg, rng_seed=7).generate([prompt], sp)[0]
    r2 = _make_engine(params, cfg, rng_seed=7).generate([prompt], sp)[0]
    r3 = _make_engine(params, cfg, rng_seed=8).generate([prompt], sp)[0]
    assert r1.output_tokens == r2.output_tokens  # deterministic per seed
    assert len(r3.output_tokens) == 12


def test_repetition_penalty_discourages_repeats(tiny):
    _, params, cfg = tiny
    prompt = [5] * 10
    base = _make_engine(params, cfg).generate(
        [prompt], SamplingParams(temperature=0.0, max_tokens=16, repetition_penalty=1.0)
    )[0]
    pen = _make_engine(params, cfg).generate(
        [prompt], SamplingParams(temperature=0.0, max_tokens=16, repetition_penalty=1.8)
    )[0]
    assert len(set(pen.output_tokens)) >= len(set(base.output_tokens))


def test_last_page_not_corrupted_by_padding_slots(tiny):
    """Regression: JAX scatter wraps negative indices, so the -1 padding
    slots of inactive rows must not overwrite the last pool slot while a
    live sequence occupies the last page."""
    model, params, cfg = tiny
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, size=20).tolist()
    # exactly 3 pages of 8 -> the sequence owns the LAST page of the pool,
    # and 3 of the 4 batch rows are inactive (slot -1) every decode step
    eng = _make_engine(params, cfg, num_pages=3, page_size=8, max_seq_len=24, max_num_seqs=4)
    res = eng.generate([prompt], SamplingParams(temperature=0.0, max_tokens=4))[0]
    assert res.output_tokens == _hf_greedy(model, prompt, 4)


def test_bad_prompt_reports_error(tiny):
    _, params, cfg = tiny
    eng = _make_engine(params, cfg)
    res = eng.generate([[]], SamplingParams(max_tokens=4))[0]
    assert res.finish_reason == "error"
    assert "prompt" in res.error


def test_request_larger_than_pool_rejected_not_livelocked(tiny):
    """Regression: a request needing more pages than the whole pool must be
    rejected at intake, not spin the engine forever."""
    _, params, cfg = tiny
    eng = _make_engine(params, cfg, num_pages=4, page_size=8, max_seq_len=128)
    res = eng.generate(
        [[1] * 50, [2] * 10],
        [SamplingParams(temperature=0.0, max_tokens=30), SamplingParams(temperature=0.0, max_tokens=4)],
    )
    assert res[0].finish_reason == "error"
    assert "pages" in res[0].error
    assert res[1].finish_reason == "length"  # queue not head-of-line blocked


def test_rejected_request_surfaces_through_step(tiny):
    _, params, cfg = tiny
    eng = _make_engine(params, cfg)
    rid = eng.add_request([], SamplingParams(max_tokens=4))
    assert eng.has_work()
    finished = eng.step()
    assert [r.request_id for r in finished] == [rid]
    assert finished[0].finish_reason == "error"


def test_top_k_sampling(tiny):
    _, params, cfg = tiny
    prompt = list(range(1, 10))
    greedy = _make_engine(params, cfg).generate(
        [prompt], SamplingParams(temperature=0.0, max_tokens=6)
    )[0]
    k1 = _make_engine(params, cfg).generate(
        [prompt], SamplingParams(temperature=5.0, top_k=1, max_tokens=6)
    )[0]
    # top_k=1 at any temperature collapses to greedy
    assert k1.output_tokens == greedy.output_tokens


# ------------------------------------------------------- decode bursts ----


def test_burst_matches_single_step_greedy():
    """A fused 8-step burst must produce exactly the per-token greedy path."""
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params

    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(7))
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_ids=())

    outs = []
    for burst in (1, 8):
        eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=4,
                     max_seq_len=64, decode_burst=burst)
        outs.append([r.output_tokens for r in eng.generate(prompts, sp)])
    assert outs[0] == outs[1]


def test_burst_respects_stop_and_max_tokens():
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params

    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(7))
    # find the greedy continuation first, then set its 3rd token as stop
    eng = Engine(params, cfg, max_num_seqs=1, num_pages=32, page_size=4,
                 max_seq_len=64, decode_burst=8)
    free = eng.generate([[1, 2, 3]], SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=()))[0]
    stop_tok = free.output_tokens[0]  # tiny random models repeat greedily; first token is safe

    eng2 = Engine(params, cfg, max_num_seqs=1, num_pages=32, page_size=4,
                  max_seq_len=64, decode_burst=8)
    res = eng2.generate([[1, 2, 3]], SamplingParams(max_tokens=6, temperature=0.0,
                                                    stop_token_ids=(stop_tok,)))[0]
    assert res.finish_reason == "stop"
    assert res.output_tokens == free.output_tokens[:1]  # stop included, burst tail discarded

    res3 = eng2.generate([[1, 2, 3]], SamplingParams(max_tokens=4, temperature=0.0,
                                                     stop_token_ids=()))[0]
    assert res3.finish_reason == "length" and len(res3.output_tokens) == 4


def test_warmup_precompiles_and_leaves_engine_clean(tiny):
    _, params, cfg = tiny
    eng = _make_engine(params, cfg)
    eng.warmup()
    assert not eng.has_work()
    assert eng._allocator.free_count == eng._allocator.num_pages
    # normal traffic after warmup behaves identically to a fresh engine
    prompt = [1, 2, 3, 4]
    sp = SamplingParams(max_tokens=5, temperature=0.0, stop_token_ids=())
    out = eng.generate([prompt], sp)[0].output_tokens
    ref = _make_engine(params, cfg).generate([prompt], sp)[0].output_tokens
    assert out == ref


def test_mid_decode_admission_keeps_pipeline(tiny):
    """A request arriving while others decode must be admitted WITHOUT
    draining the burst pipeline (free pages suffice), and every request's
    greedy output must match a solo run."""
    _, params, cfg = tiny
    sp = SamplingParams(max_tokens=10, temperature=0.0, stop_token_ids=())
    solo = {}
    for prompt in ([1, 2, 3, 4], [9, 8, 7]):
        eng = Engine(params, cfg, max_num_seqs=4, num_pages=64, page_size=4,
                     max_seq_len=64, decode_burst=4)
        solo[tuple(prompt)] = eng.generate([prompt], sp)[0].output_tokens

    eng = Engine(params, cfg, max_num_seqs=4, num_pages=64, page_size=4,
                 max_seq_len=64, decode_burst=4)
    drains = []  # drains that happened with a request still waiting = stalls
    orig = eng._drain_chain
    eng._drain_chain = lambda fin: (
        drains.append(len(eng._waiting)) if eng._waiting else None,
        orig(fin),
    )[1]

    r1 = eng.add_request([1, 2, 3, 4], sp)
    # a few steps so request 1 is mid-decode with a live chain
    for _ in range(3):
        eng.step()
    assert eng._chain is not None
    r2 = eng.add_request([9, 8, 7], sp)
    done = {}
    while eng.has_work():
        for res in eng.step():
            done[res.request_id] = res
    assert done[r1].output_tokens == solo[(1, 2, 3, 4)]
    assert done[r2].output_tokens == solo[(9, 8, 7)]
    # the admission itself must not have drained a live pipeline: a drain
    # while a request sat in the waiting queue means admission stalled decode
    assert not drains, f"admission drained the pipeline: {drains}"


def test_prefill_co_dispatches_with_decode(tiny):
    """A multi-chunk prompt admitted mid-decode must NOT stall running
    streams: every step that prefills a chunk also dispatches a decode
    burst, and all outputs stay token-identical to solo runs."""
    _, params, cfg = tiny
    sp = SamplingParams(max_tokens=24, temperature=0.0, stop_token_ids=())
    long_prompt = list(range(1, 49))  # 48 tokens -> 6 chunks at chunk=8
    solo = {}
    for prompt in ([1, 2, 3, 4], long_prompt):
        eng = Engine(params, cfg, max_num_seqs=4, num_pages=64, page_size=4,
                     max_seq_len=128, prefill_chunk=8, decode_burst=4)
        solo[tuple(prompt)] = eng.generate([prompt], sp)[0].output_tokens

    eng = Engine(params, cfg, max_num_seqs=4, num_pages=64, page_size=4,
                 max_seq_len=128, prefill_chunk=8, decode_burst=4)
    r1 = eng.add_request([1, 2, 3, 4], sp)
    for _ in range(3):
        eng.step()
    assert eng._chain is not None

    r2 = eng.add_request(long_prompt, sp)
    bursts_during_prefill = 0
    done = {}
    while eng.has_work():
        chain_before = eng._chain
        prefilling = any(r.state == "prefilling" for r in eng._row_req.values())
        for res in eng.step():
            done[res.request_id] = res
        req2 = eng._requests.get(r2)
        still_prefilling = req2 is not None and req2.state == "prefilling"
        if prefilling and still_prefilling and eng._chain is not chain_before:
            bursts_during_prefill += 1
    assert done[r1].output_tokens == solo[(1, 2, 3, 4)]
    assert done[r2].output_tokens == solo[tuple(long_prompt)]
    # r2 takes 6 prefill chunks; r1 must have decoded new bursts meanwhile
    assert bursts_during_prefill >= 3, (
        f"only {bursts_during_prefill} decode bursts dispatched while the "
        "long prompt prefilled — running streams stalled"
    )


def test_cancelled_pending_first_wave_does_not_corrupt_others(tiny):
    """Regression: a request cancelled after its prefill wave was queued but
    before the next decode dispatch has row == -1; the overlay must skip it
    (a negative scatter index would WRAP to the last row and corrupt an
    unrelated stream's last-token state)."""
    _, params, cfg = tiny
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_ids=())
    solo = Engine(params, cfg, max_num_seqs=4, num_pages=64, page_size=4,
                  max_seq_len=64, decode_burst=4).generate([[1, 2, 3, 4]], sp)[0]

    eng = Engine(params, cfg, max_num_seqs=4, num_pages=64, page_size=4,
                 max_seq_len=64, decode_burst=4)
    r1 = eng.add_request([1, 2, 3, 4], sp)
    for _ in range(3):  # r1 mid-decode with a live chain
        eng.step()
    assert eng._chain is not None
    r2 = eng.add_request([9, 8, 7], sp)
    # drive the prefill half of a step by hand: a full step() would consume
    # the wave into the co-dispatched decode burst, and this regression is
    # about a cancel landing in the window between those two dispatches
    eng._try_prefill([])
    assert eng._pending_first
    eng.cancel(r2)

    done = {}
    while eng.has_work():
        for res in eng.step():
            done[res.request_id] = res
    assert done[r2].finish_reason == "cancelled"
    # the victim stream must be byte-identical to its solo run
    assert done[r1].output_tokens == solo.output_tokens


# ------------------------------------- a step is two device programs --


def _join_one_then_several(eng, prompts, sp, ids):
    """A row decodes with a live chain; one row joins it, then three in one
    wave, one of them two chunks long (its second chunk is a wave of its
    own a step later)."""
    ids.append(eng.add_request(prompts[0], sp))
    yield
    yield
    yield
    ids.append(eng.add_request(prompts[1], sp))
    yield
    yield
    ids.extend(eng.add_request(p, sp) for p in prompts[2:5])
    yield
    yield


def _two_waves_before_a_burst(eng, prompts, sp, ids, cancel=False):
    """Under prefill priority a step whose wave leaves a prompt unfinished
    skips its burst: the next burst takes the rows of two waves.  With
    ``cancel`` the first wave's request goes before that burst is built."""
    ids.append(eng.add_request(prompts[0], sp))
    yield
    yield
    yield
    ids.extend(eng.add_request(p, sp) for p in prompts[1:3])  # one chunk, two chunks
    yield
    assert eng._pending_first, "the first wave did not wait for a burst"
    if cancel:
        eng.cancel(ids[1])
    yield
    yield


@pytest.mark.parametrize("options,script,cancelled", [
    pytest.param({}, _join_one_then_several, None, id="one-row-then-several"),
    pytest.param(dict(prefill_priority=True), _two_waves_before_a_burst, None,
                 id="two-waves-before-one-burst"),
    pytest.param(dict(prefill_priority=True),
                 lambda *a: _two_waves_before_a_burst(*a, cancel=True), 1,
                 id="cancelled-before-the-burst"),
])
def test_a_step_is_a_wave_and_a_burst_and_no_eager_op(tiny, options, script, cancelled):
    """Once warm, steps in which prefill waves join running rows apply no
    primitive eagerly and dispatch, after admission's presence helpers, at
    most the wave program and the burst program; greedy tokens are those of
    a request-at-a-time ``generate``."""
    from tests.helpers.step_programs import assert_two_programs_a_step, run_recorded

    _, params, cfg = tiny
    rng = np.random.default_rng(30)
    lens = [9, 7, 12, 40, 5] if cancelled is None and not options else [9, 7, 40]
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist() for n in lens]
    sp = SamplingParams(max_tokens=24, temperature=0.0, stop_token_ids=())

    def engine():
        return _make_engine(params, cfg, decode_burst=4, **options)

    rehearsal = engine()
    run_recorded(rehearsal, script(rehearsal, prompts, sp, []), warm=False)
    eng, ids = engine(), []
    done, steps = run_recorded(eng, script(eng, prompts, sp, ids))
    assert_two_programs_a_step(steps)
    alone = engine()
    for i, (rid, prompt) in enumerate(zip(ids, prompts)):
        if i == cancelled:
            assert done[rid].finish_reason == "cancelled"
        else:
            assert done[rid].output_tokens == alone.generate([prompt], sp)[0].output_tokens


def test_a_burst_says_whether_the_device_had_drained(tiny, monkeypatch):
    """``ahead`` on the burst's annotation, the engine's two counters and
    the Prometheus counter: 0 for a burst dispatched after a forced drain
    (nothing queued on the device), 1 for one dispatched while a program the
    device has not finished is still ahead of it."""
    import githubrepostorag_tpu.serving.engine as engine_mod
    from githubrepostorag_tpu.metrics import BURST_DISPATCH

    _, params, cfg = tiny
    bursts, real = [], engine_mod.annotate

    def annotate(name, **meta):
        if name == "engine.decode_burst":
            bursts.append(meta)
        return real(name, **meta)

    monkeypatch.setattr(engine_mod, "annotate", annotate)
    counted = lambda: (eng.bursts_ahead, eng.bursts_starved,  # noqa: E731
                       BURST_DISPATCH.labels(ahead="1")._value.get(),
                       BURST_DISPATCH.labels(ahead="0")._value.get())
    eng = _make_engine(params, cfg, decode_burst=4)
    eng.add_request([5, 6, 7, 8], SamplingParams(max_tokens=64, temperature=0.0,
                                                 stop_token_ids=()))
    eng.step()
    eng.step()
    eng._drain_chain([])  # the forced drain: the last burst's tokens are on the host
    assert eng._presence.is_ready()
    a1, s0, p1, p0 = counted()
    eng.step()
    assert bursts[-1]["ahead"] == 0 and counted() == (a1, s0 + 1, p1, p0 + 1)

    @jax.jit
    def held_back(presence, x):  # presence, once a long product has run
        y = jax.lax.fori_loop(0, 400, lambda i, a: jnp.tanh(a @ a), x)
        return presence ^ (y[0, 0] > 2.0)

    eng._presence = held_back(eng._presence, jnp.full((384, 384), 0.01))
    assert not eng._presence.is_ready()  # the device is busy: the probe does not wait for it
    eng.step()
    assert bursts[-1]["ahead"] == 1 and counted() == (a1 + 1, s0 + 1, p1 + 1, p0 + 1)
    while eng.has_work():
        eng.step()


def test_prefill_priority_same_outputs(tiny):
    """prefill_priority is a SCHEDULING change only: a wave of requests
    admitted together produces the same tokens as the co-dispatched
    default, and no deadlock occurs when the wave exceeds rows/pages."""
    _, params, cfg = tiny
    from githubrepostorag_tpu.serving import Engine, SamplingParams

    prompts = [[(7 * i + j) % cfg.vocab_size for j in range(6 + i)]
               for i in range(6)]
    sp = SamplingParams(max_tokens=10, temperature=0.0, stop_token_ids=())

    def run(**kw):
        eng = Engine(params, cfg, max_num_seqs=2, num_pages=16, page_size=4,
                     max_seq_len=32, kv_dtype=jnp.float32, decode_burst=4, **kw)
        return [r.output_tokens for r in eng.generate(prompts, sp)]

    assert run(prefill_priority=True) == run()


# ------------------------------------------------ the choice of step path --

_GREEDY = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
_SAMPLED = SamplingParams(max_tokens=8, temperature=0.8, stop_token_ids=())


def test_step_takes_the_one_decode_path(tiny):
    """``Engine.step`` has one decode path, ``_decode_step``, looked up on
    the instance at each call (the benchmark's probe wraps it there): a
    batch of a greedy and a sampled row dispatches it and nothing else, and
    the class defines no other step method to choose."""
    import inspect

    _, params, cfg = tiny
    eng = _make_engine(params, cfg, decode_burst=4)
    calls = count_step_paths(eng)
    results = eng.generate([[5, 6, 7, 8] * 3, [9, 1, 2] * 4], [_GREEDY, _SAMPLED])
    assert [len(r.output_tokens) for r in results] == [8, 8]
    assert calls["_decode_step"] > 0
    dispatchers = [n for n, f in vars(Engine).items() if n.endswith("_step")
                   and list(inspect.signature(f).parameters) == ["self", "finished"]]
    assert dispatchers == list(DECODE_PATHS)


# (local slots, evaluation stack) of the engine's frames that lie under a step
# program's first call, where JAX traces and lowers it
FRAMES_UNDER_A_FIRST_CALL = {
    "generate": (10, 8), "step": (11, 5), "_try_prefill": (29, 8),
    "_prefill_batch": (28, 24), "_decode_step": (24, 17),
}


def test_the_frames_under_a_step_programs_first_call_keep_their_size():
    """A tripwire, not a contract.  CPython keeps a thread's frames in 16 KiB
    chunks, takes a chunk when a frame does not fit and gives it back when
    that frame returns; a loop whose frame ends a chunk pays that for every
    frame it pushes.  JAX lowers a jaxpr in such a loop
    (``mlir.jaxpr_subcomp``), and the hybrids' four- and eight-row waves hold
    bodies of 2,355 and 3,243 equations: lowered from a frame at a chunk's end
    they take 8 and 11 s where they take 0.2 and 0.3 s (PERF.md section 6,
    PR 45).  Where that frame lands is the sum of every frame under it, these
    five among them: two locals fewer in ``step`` moved it there, and warm
    ``setup_s`` of the three hybrid cells rose by 20 s at the same programs
    (PR 44 was refused for it).  So a change to one of these numbers is not
    wrong, but it is a change to measure: a traced pair in a hybrid cell,
    ``setup_trace_lower_s`` on both sides, and then the new numbers here."""
    def frame(fn):
        code = fn.__code__
        return (code.co_nlocals + len(code.co_cellvars) + len(code.co_freevars),
                code.co_stacksize)

    assert {n: frame(getattr(Engine, n)) for n in FRAMES_UNDER_A_FIRST_CALL} \
        == FRAMES_UNDER_A_FIRST_CALL


@pytest.mark.parametrize("config_name", [
    "qwen2-7b-int8", "deepseek-v3-ep16-bf16", "qwen3-next-80b-a3b-ep4-bf16",
    "olmo-hybrid-7b-bf16", "nemotron-3-nano-30b-a3b-ep4-bf16"])
def test_the_cells_run_one_prefill_and_one_decode_path(config_name):
    """Every benchmark cell builds its engine through its family's
    ``build_engine`` with the engine's default options: a mixed batch
    (greedy and sampled rows, a prompt longer than a chunk) dispatches
    ``_prefill_batch`` and ``_decode_step``, the one decode path there is,
    and neither of the other prefill paths.  Built at the configuration
    file's ``rehearse`` widths."""
    import json
    from pathlib import Path

    from benchmarks import families

    root = Path(__file__).resolve().parents[1]
    config = json.loads((root / "benchmarks" / "configs" / f"{config_name}.json").read_text())
    family = families.load(config)
    model = family.model_of(config, True)
    eng = family.build_engine(config, model, config["rehearse"]["engine"], 7)
    calls = count_step_paths(eng)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, model["vocab_size"], n).tolist()
               for n in (eng.prefill_chunk + 9, 12, 30)]
    results = eng.generate(prompts, [_GREEDY, _SAMPLED, _GREEDY])
    assert [len(r.output_tokens) for r in results] == [8, 8, 8]
    assert sorted(n for n, c in calls.items() if c) == ["_decode_step", "_prefill_batch"]
