"""Falcon-H1 through the ENGINE's own path (admission, prefix cache, waves,
bursts, ``StateSlots``) against the benchmark's plain reference, at the tiny
size of tests/test_falcon_h1.py: prefill, then 16 decode steps through pages
and state; a prefix hit that restores pages AND the snapshot of the same
layers and then decodes; the state kernel on the pool against the array form.
Tokens are held to the reference's LOGITS: in float32 every token lies within
1e-3 of a row's spread of the reference's best logit (rounding alone; a wrong
state or a stale page reads 1 and more); as served, in bfloat16, 0.05 on
average (rounding flips near-ties)."""

import jax.numpy as jnp
import numpy as np
import pytest

from githubrepostorag_tpu.models import falcon_h1 as model
from githubrepostorag_tpu.serving import Engine, SamplingParams
from tests.test_falcon_h1 import CFG, PAGE, SEED, cast, decode_gaps, in_float32  # noqa: F401

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
    chunks) and 16 decode steps through pages and state, a second prompt of
    the same head that leaves the branch-point snapshot, and both again, each
    from its pages AND the snapshot of the same layers.  Every token lies
    within 1e-3 of a row's spread of the reference's best logit.  The kernel on the pool
    (ops/pallas_state.py, interpreted) gives the array form's tokens."""
    eng, kernel = build_engine(jnp.float32), build_engine(jnp.float32, use_pallas=True)
    cached, cold = run(eng, A)
    assert cached == 0 and max(decode_gaps(A, cold)) < 1e-3
    cached, out_b = run(eng, B)
    assert cached == 0 and eng.page_hit_tokens == 96 and max(decode_gaps(B, out_b)) < 1e-3
    cached, again = run(eng, A)  # pages and the snapshot at its last page boundary
    assert cached == 144 and again == cold and eng.state_restored == 1
    cached, again_b = run(eng, B)  # pages and the branch-point snapshot
    assert cached == 96 and again_b == out_b and eng.state_restored == 2
    assert (eng.page_hit_tokens, eng.state_hit_tokens) == (96 + 144 + 112, 144 + 96)
    for prompt, want in ((A, cold), (A, cold)):  # cold, then from pages and snapshot
        assert run(kernel, prompt)[1] == want
    assert kernel.state_restored == 1
    # the rows that sat every burst out (one request at a time: rows 1 .. 3) hold what they held
    assert not np.asarray(kernel.state_pools["s"])[:, 1:4].any()


def test_engine_in_bfloat16_stays_inside_the_decode_tolerance():
    """As served (bfloat16 weights, products and pages, float32 state): the
    tokens of a cold and of a resumed prompt lie 0.05 of a row's spread below
    the reference's best on average at the most (rounding flips near-ties; a
    wrong state or a stale page reads 1 and more)."""
    eng = build_engine(jnp.bfloat16)
    _, cold = run(eng, A)
    run(eng, B)
    cached, again_b = run(eng, B)
    assert cached == 96
    assert np.mean(decode_gaps(A, cold)) < 0.05 and np.mean(decode_gaps(B, again_b)) < 0.05


def test_the_configuration_object_brings_the_programs_and_both_pools_for_every_layer():
    """The engine reads which step programs serve the model and its two
    caches' shapes from the configuration object: no model's name in it, and
    for the first time as many state layers as page layers as layers."""
    import inspect

    from githubrepostorag_tpu.obs.startup import startup_record
    from githubrepostorag_tpu.serving import engine as engine_mod

    eng = build_engine(jnp.bfloat16)
    assert eng._wave_fn is model.forward_paged_wave and eng._decode_burst_fn is model.decode_burst
    assert eng._recurrent and not eng._expert_counters
    assert eng.state_pools["s"].shape == (3, 4 + 4 + 1, 8, 8, 128)  # 16, lane-padded
    assert eng.state_pools["s"].dtype == jnp.float32
    assert eng.state_pools["conv"].shape == (3, 9, 3 * (64 + 2 * 2 * 16))
    assert eng.page_pool.shape == (3, 2, 64, PAGE, 16)
    held = startup_record().snapshot()["notes"]["pool_bytes"]
    assert held["pages"] == 2 * eng.page_pool.nbytes
    assert held["state"] == sum(x.nbytes for x in eng.state_pools.values()) > 0
    assert {model.forward_paged_wave, model.decode_burst} <= set(eng.step_programs())
    assert "falcon" not in inspect.getsource(engine_mod).lower()
    with pytest.raises(ValueError, match="recurrent state pool: .*kv_quant"):
        build_engine(jnp.bfloat16, kv_quant=8)
