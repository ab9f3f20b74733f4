"""The float32 reference against the program's paged path at test widths,
and the control that must come out as not correct."""

import json

import numpy as np
import pytest

from benchmarks import correctness, manifest, reference, system
from benchmarks.run import model_of

CONFIG = json.loads((manifest.HERE / "configs" / "qwen2-7b-int8.json").read_text())
MODEL = model_of(CONFIG, rehearse=True)
LIMITS = CONFIG["rehearse"]["limits"]
SPEC = {"sequences": 3, "decode_tokens": 6, "max_prompt_tokens": 200, "limits": LIMITS}


def _prompts(seed, n=6):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, MODEL["vocab_size"] - 4, size=int(k)).tolist()
            for k in rng.integers(20, 150, size=n)]


@pytest.fixture(scope="module")
def engine():
    eng, _ = system.build_engine(CONFIG, MODEL, {"max_seq_len": 512, **CONFIG["rehearse"]["engine"]},
                                 seed=123)
    return eng


def test_weights_from_the_seed_match_the_programs_initialiser(engine):
    w = reference.Weights(MODEL, system.weight_seed(123), fuse=True)
    wo = engine.params["layers"]["wo"]
    got = np.asarray(w.layer("wo", 1))
    want = np.asarray(wo.q[1], np.float32) * np.asarray(wo.s[1], np.float32)
    assert np.array_equal(got, want)
    head = np.asarray(reference._head_cols(w, 5, 7, MODEL["hidden_size"], MODEL["vocab_size"]))
    lm = engine.params["lm_head"]
    assert np.array_equal(head, np.asarray(lm.q[:, 5:12], np.float32)
                          * np.asarray(lm.s[5:12], np.float32))


def test_large_seeds_fold_into_the_initialisers_range():
    assert system.weight_seed(2**31 + 5) < 65521
    assert system.weight_seed(7) == 7


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_agrees_with_the_reference_through_prefill_and_decode(engine, seed):
    out = correctness.check(engine, MODEL, system.weight_seed(123), True, _prompts(seed),
                            seed, SPEC)
    assert out["correct"], out
    assert out["numbers"]["prefill_logits_rel_rms"] < LIMITS["prefill_logits_rel_rms"]
    assert len(out["lines"]) == 2 and "limit" in out["lines"][0]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_lower_precision_control_fails(engine, seed):
    out = correctness.check(engine, MODEL, system.weight_seed(123), True, _prompts(seed),
                            seed, SPEC, control=CONFIG["correctness"]["precision_control"])
    assert not out["correct"], out
    assert out["numbers"]["prefill_logits_rel_rms"] > 3 * LIMITS["prefill_logits_rel_rms"]


def test_compare_by_hand():
    ref = [np.array([[0.0, 1.0, 3.0], [2.0, 0.0, 1.0]], np.float32)]
    got = correctness.compare(np.array([[0.0, 1.0, 3.0]], np.float32), [[2, 0]], ref)
    assert got["prefill_logits_rel_rms"] == 0 and got["decode_token_gap"] == 0
    off = correctness.compare(np.array([[0.0, 1.0, 2.0]], np.float32), [[1, 2]], ref)
    assert off["prefill_logits_rel_rms"] == pytest.approx(np.sqrt(1 / 3) / np.sqrt(10 / 3))
    row0, row1 = ref[0]
    assert off["decode_token_gap"] == pytest.approx(
        ((3 - 1) / row0.std() + (2 - 1) / row1.std()) / 2)


def test_sample_is_seeded_and_distinct():
    ps = _prompts(9, 12) + _prompts(9, 12)
    a = correctness.sample_prompts(ps, 4, 3, 200)
    assert a == correctness.sample_prompts(ps, 4, 3, 200) and len(a) == 3
    assert len({tuple(x) for x in a}) == 3
