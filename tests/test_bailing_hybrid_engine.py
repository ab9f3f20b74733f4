"""Ling-3.0-flash through the ENGINE's own path (admission, prefix cache,
waves, bursts, ``StateSlots``) against the benchmark's plain reference, at the
tiny size of tests/test_bailing_hybrid.py: the first model whose configuration
object states a LATENT page pool and a recurrent state pool at once.  Prefill,
then 16 decode steps through latent pages and state; a prefix hit that
restores latent pages AND a snapshot in one admission and then decodes the
cold run's tokens; the kernels (interpreted) against the array forms.  Tokens
are held to the reference's LOGITS: in float32 every token lies within 1e-3
of a row's spread of the reference's best logit."""

import jax.numpy as jnp
import numpy as np
import pytest

from githubrepostorag_tpu.models import bailing_hybrid as model
from githubrepostorag_tpu.serving import Engine, SamplingParams
from tests.test_bailing_hybrid import CFG, PAGE, SEED, cast, decode_gaps, in_float32  # noqa: F401

RNG = np.random.default_rng(1)
HEAD = [int(t) for t in RNG.integers(1, 500, size=100)]
A = HEAD + [int(t) for t in RNG.integers(1, 500, size=50)]   # 150 tokens: last boundary 144
B = HEAD + [int(t) for t in RNG.integers(1, 500, size=20)]   # shares 6 pages (96) with A
SP = SamplingParams(max_tokens=17, temperature=0.0, stop_token_ids=())  # the first + 16 decoded


def build_engine(act, **kw):
    params = cast(model.init_params(CFG, seed=SEED), act)
    return Engine(params, CFG, **{**dict(
        max_num_seqs=4, num_pages=64, page_size=PAGE, max_seq_len=256, prefill_chunk=64,
        decode_burst=4, kv_dtype=act, state_snapshots=4), **kw})


def run(eng, prompt):
    res = eng.generate([prompt], SP)[0]
    return res.cached_tokens, list(res.output_tokens)


def test_engine_prefill_decode_and_a_restore_of_both_caches_are_the_references(in_float32):
    """The engine's own path in float32: a cold prompt through waves (three
    chunks) and 16 decode steps through latent pages and state, a second prompt
    of the same head that leaves the branch-point snapshot, and both again,
    each from its latent pages AND a snapshot: the cold run's tokens.  The
    kernels (the latent ones and the KDA rule on the pool, interpreted) give the
    array forms' tokens."""
    eng, kernel = build_engine(jnp.float32), build_engine(jnp.float32, use_pallas=True)
    assert eng.value_pool is None and eng.page_pool.shape == (CFG.kv_layers, 1, 64, PAGE, 128)
    assert eng.state_pools["s"].shape == (CFG.state_layers, 4 + 4 + 1, 4, 16, 16)
    cached, cold = run(eng, A)
    assert cached == 0 and max(decode_gaps(A, cold)) < 1e-3
    cached, out_b = run(eng, B)
    assert cached == 0 and eng.page_hit_tokens == 96 and max(decode_gaps(B, out_b)) < 1e-3
    cached, again = run(eng, A)  # latent pages and the snapshot at its last page boundary
    assert cached == 144 and again == cold and eng.state_restored == 1
    cached, again_b = run(eng, B)  # latent pages and the branch-point snapshot
    assert cached == 96 and again_b == out_b and eng.state_restored == 2
    assert (eng.page_hit_tokens, eng.state_hit_tokens) == (96 + 144 + 112, 144 + 96)
    assert eng.moe_stats["burst"][2] == CFG.n_held * CFG.expert_layers * 4 * (
        eng.moe_stats["burst"][2] // (CFG.n_held * CFG.expert_layers * 4))  # whole bursts of slots
    assert eng.moe_max_pairs["burst"] > 0  # the programs return the fullest expert's pairs
    for prompt, want in ((A, cold), (A, cold)):  # cold, then from pages and snapshot
        assert run(kernel, prompt)[1] == want
    assert kernel.state_restored == 1
    # the rows that sat every burst out (one request at a time: rows 1 .. 3) hold what they held
    assert not np.asarray(kernel.state_pools["s"])[:, 1:4].any()


def test_a_model_with_both_pools_refuses_what_either_refuses_and_names_both():
    params = model.init_params(CFG, seed=SEED)
    kw = dict(max_num_seqs=4, num_pages=64, page_size=PAGE, max_seq_len=256, prefill_chunk=64)
    with pytest.raises(ValueError, match="a latent page and a recurrent state pool: kv_quant"):
        Engine(params, CFG, kv_quant="int8", **kw)
    with pytest.raises(ValueError, match="latent page and a recurrent state pool: prefill_chunk"):
        Engine(params, CFG, **{**kw, "prefill_chunk": 40})


def test_a_long_cold_prompt_leaves_a_snapshot_a_stride_and_its_follower_resumes_there(in_float32):
    """Chunks of 16 over pages of 16: a stride is 16 pages.  A cold prompt of
    19 pages owes a snapshot at page 16 beside the one at its last boundary, and
    a second prompt that shares 17 pages with it (a page match with no snapshot
    as deep) resumes from page 16 instead of computing the whole head again; the
    tokens are the cold engine's."""
    rng = np.random.default_rng(2)
    head = [int(t) for t in rng.integers(1, 500, size=17 * PAGE)]
    first = head + [int(t) for t in rng.integers(1, 500, size=40)]
    second = head + [int(t) for t in rng.integers(1, 500, size=25)]
    kw = dict(max_num_seqs=2, num_pages=96, max_seq_len=400, prefill_chunk=16, state_snapshots=6)
    eng, cold = build_engine(jnp.float32, **kw), build_engine(jnp.float32, **kw)
    assert run(eng, first)[0] == 0 and eng.state_snapshots_written == 2  # page 16 and page 19
    cached, out = run(eng, second)
    assert cached == 16 * PAGE and eng.state_restored == 1
    assert (eng.page_hit_tokens, eng.state_hit_tokens) == (17 * PAGE, 16 * PAGE)
    assert out == run(cold, second)[1]


def test_a_follower_waits_for_the_snapshot_its_leader_owes_and_resumes_there(in_float32):
    """Two prompts of one 17-page head submitted TOGETHER: the second is held
    while the first still owes the stride snapshot at page 16 and the one at the
    branch point, then resumes from the deepest (no second cold prefill beside
    the first, on pages of its own); both decode what a cold engine decodes."""
    rng = np.random.default_rng(3)
    head = [int(t) for t in rng.integers(1, 500, size=17 * PAGE)]
    first = head + [int(t) for t in rng.integers(1, 500, size=40)]
    second = head + [int(t) for t in rng.integers(1, 500, size=25)]
    kw = dict(max_num_seqs=2, num_pages=96, max_seq_len=400, prefill_chunk=16, state_snapshots=6)
    eng, cold = build_engine(jnp.float32, **kw), build_engine(jnp.float32, **kw)
    a, b = eng.generate([first, second], SP)
    assert (a.cached_tokens, b.cached_tokens) == (0, 16 * PAGE) and eng.state_restored == 1
    assert list(b.output_tokens) == run(cold, second)[1]
    assert list(a.output_tokens) == run(cold, first)[1]
