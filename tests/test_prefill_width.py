"""The width of a prefill wave (ops/prefill_width.py) through both model
families: the rung follows the wave's longest pending chunk, a narrow rung
computes what the whole chunk computes, to the bit, and the rungs are
branches of the one program a row bucket has."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from githubrepostorag_tpu.ops.prefill_width import width_ladder
from githubrepostorag_tpu.serving import Engine, SamplingParams
from tests.helpers.step_programs import ADMISSION, BURST, WAVE, recorded_waves, run_recorded

PAGE, CHUNK = 8, 32  # rungs 32, 16, 8


@pytest.mark.parametrize("chunk,page,ladder", [
    (512, 128, [512, 256, 128]),  # both benchmark configurations
    (512, 16, [512, 256, 128]),  # three rungs at most
    (256, 128, [256, 128]),  # never below a page
    (128, 128, [128]),
    (32, 8, [32, 16, 8]),
    (24, 8, [24, 12]),
    (17, 4, [17]),  # an odd chunk has no half
])
def test_the_ladder_is_the_chunk_halved_down_to_a_page_and_no_further(chunk, page, ladder):
    assert width_ladder(chunk, page) == ladder == width_ladder(chunk, page, rows=2)
    assert width_ladder(chunk, page, rows=4) == [chunk]  # a wave of more rows runs whole


@pytest.fixture(scope="module", params=["qwen2", "deepseek_v3"])
def family(request):
    """(name, cfg, params, the family's model module)."""
    if request.param == "qwen2":
        from githubrepostorag_tpu.models import qwen2 as mod

        cfg = mod.Qwen2Config.tiny()
        return "qwen2", cfg, mod.init_params(cfg, jax.random.PRNGKey(0)), mod
    from githubrepostorag_tpu.models import deepseek_v3 as mod

    cfg = mod.DeepseekV3Config.tiny(experts_held=(4, 12))
    return "deepseek_v3", cfg, mod.init_params(cfg, seed=11), mod


def engine_of(family, **kw):
    _, cfg, params, _ = family
    geo = dict(max_num_seqs=4, num_pages=64, page_size=PAGE, max_seq_len=64,
               prefill_chunk=CHUNK, decode_burst=4, rng_seed=0)
    return Engine(params, cfg, **{**geo, **kw})


def prompts_of(family, lengths, seed):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(2, family[1].vocab_size, size=n))) for n in lengths]


def test_short_rows_run_the_narrow_rung_and_one_long_row_the_full_one(family, monkeypatch):
    eng = engine_of(family)
    assert eng.prefill_width_buckets == [32, 16, 8]
    waves = recorded_waves(monkeypatch)
    sp = SamplingParams(max_tokens=2, temperature=0.0, stop_token_ids=())
    eng.generate(prompts_of(family, (5, 7), 1), sp)
    eng.generate(prompts_of(family, (5, 12), 2), sp)
    eng.generate(prompts_of(family, (30, 7), 3), sp)
    eng.generate(prompts_of(family, (40,), 4), sp)  # 32, then its remainder of 8
    assert [(w["rows"], w["width"], w["padded_tokens"], w["new_tokens"]) for w in waves] == [
        (2, 8, 16, 12), (2, 16, 32, 17), (2, 32, 64, 37), (1, 32, 32, 32), (1, 8, 8, 8)]
    assert eng.prefill_padded_tokens == 16 + 32 + 64 + 32 + 8
    assert eng.prefill_tokens == 12 + 17 + 37 + 40


def test_a_wave_of_more_than_two_rows_runs_whole(family, monkeypatch):
    eng = engine_of(family)
    waves = recorded_waves(monkeypatch)
    sp = SamplingParams(max_tokens=2, temperature=0.0, stop_token_ids=())
    eng.generate(prompts_of(family, (5, 7, 3), 7), sp)
    assert [(w["rows"], w["width"], w["padded_tokens"]) for w in waves] == [(3, 32, 128)]


def test_a_rung_computes_the_whole_chunks_logits_and_pool(family):
    """The chunk through the family's ``forward_paged_impl`` at every rung
    and with no width at all (the plain chunk every other caller runs): rows
    of 5 and 7 new tokens, the second behind a cached page.  The full rung
    is the plain chunk to the bit; a narrower one multiplies fewer rows, so
    the CPU's products block differently and float32 sums round differently
    (a few units in the last place, here 1.5e-7 on logits of 0.2)."""
    name, cfg, params, mod = family
    rows, lens, cached = 2, np.array([5, 7], np.int32), np.array([0, PAGE], np.int32)
    rng = np.random.default_rng(5)
    ids = rng.integers(2, cfg.vocab_size, size=(rows, CHUNK)).astype(np.int32)
    bt = np.arange(rows * 8, dtype=np.int32).reshape(rows, 8)
    pos = cached[:, None] + np.arange(CHUNK, dtype=np.int32)[None, :]
    slots = np.where(np.arange(CHUNK)[None, :] < lens[:, None],
                     np.take_along_axis(bt, pos // PAGE % 8, axis=1) * PAGE + pos % PAGE, -1)
    eng = engine_of(family)  # for pools of the family's shape
    kp = jnp.asarray(rng.standard_normal(eng._k_pages.shape), eng._k_pages.dtype)

    def chunk(width):
        if name == "qwen2":
            return mod.forward_paged_impl(
                params, cfg, ids, pos, kp, kp + 1, slots.astype(np.int32), bt, cached, lens,
                logits_at=lens - 1, width=width)
        return mod.forward_paged_impl(params, cfg, ids, pos, kp, slots.astype(np.int32), bt,
                                      cached, lens, logits_at=lens - 1, width=width)

    whole = jax.jit(lambda: chunk(None))()
    for w in (32, 16, 8, 7):  # 7: the narrowest rung holds any smaller width
        at_rung = jax.jit(lambda w: chunk(w))(np.int32(w))
        for got, want in zip(jax.tree.leaves(at_rung), jax.tree.leaves(whole)):
            got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
            if w == CHUNK:
                np.testing.assert_array_equal(got, want)
            else:
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)


def test_greedy_tokens_are_the_full_width_programs(family, monkeypatch):
    """An engine told to hand every wave the full width is the control.
    Same prompts, same tokens, prompt by prompt, from waves that ran all
    three rungs."""
    prompts = prompts_of(family, (5, 16, 17, 50, 9), 6)
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())
    waves = recorded_waves(monkeypatch)
    eng = engine_of(family)
    got = [eng.generate([p], sp)[0].output_tokens for p in prompts]
    assert {w["width"] for w in waves} == {32, 16, 8}
    del waves[:]
    full = engine_of(family)
    full._dispatch_width = lambda longest_chunk, rows: CHUNK
    assert got == [full.generate([p], sp)[0].output_tokens for p in prompts]
    assert {w["width"] for w in waves} == {32}


def test_a_row_bucket_is_one_compiled_program_however_many_rungs_it_ran(family, monkeypatch):
    """Warm-up runs each row bucket once, at the full width; traffic that then
    runs every rung at one and at two rows compiles nothing and asks the
    device for nothing but a wave and a burst a step."""
    sp = SamplingParams(max_tokens=5, temperature=0.0, stop_token_ids=())
    eng = engine_of(family)
    eng.warmup()
    waves = recorded_waves(monkeypatch)

    def script():
        for seed, lengths in enumerate([(5,), (12,), (30,), (6, 4), (14, 3), (27, 9)]):
            for p in prompts_of(family, lengths, 10 + seed):
                eng.add_request(p, sp)
            yield
            yield

    _, steps = run_recorded(eng, script())  # asserts: no jit gained a cache entry
    assert {(w["rows"], w["width"]) for w in waves} == {
        (r, w) for r in (1, 2) for w in (8, 16, 32)}
    assert sum(s.count(WAVE) for s in steps) == len(waves)
    for calls in steps:
        assert set(calls) <= ADMISSION | {WAVE, BURST}, calls
