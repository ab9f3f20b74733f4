"""Mellum through ``Engine`` on the CPU at test widths: a model whose sliding
layers keep a window of TWO pages and whose global layers rotate their keys by
a table of their own, served with a pool a kind.  Rows slide past their window
and release the pages behind it, a later request's hit restores both kinds, a
follower is held while its leader prefills, and the third expert count reaches
the annotations.

What the engine computes is held to the module's own un-paged forward
(``models/mellum.forward``: no cache, no kernel, one sequence): every generated
token is looked up in that forward's logits for its position (given the same
history) and must be its best or a near-tie of it, as the benchmark's
``decode_token_gap`` reads; one test holds it to the plain reference too."""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks import reference_mellum as ref
from benchmarks.correctness import token_gap
from githubrepostorag_tpu.models import mellum as program
from githubrepostorag_tpu.models.mellum import MellumConfig
from githubrepostorag_tpu.serving.engine import Engine
from githubrepostorag_tpu.serving.kv_cache import page_kinds
from githubrepostorag_tpu.serving.sampling_params import SamplingParams
from tests.test_mellum import Wide, model_of, wide_params

PS, WINDOW, CHUNK = 16, 32, 32
CFG = MellumConfig.tiny(num_layers=4, sliding_window=WINDOW)
SEED = 3
GREEDY = SamplingParams(max_tokens=10, temperature=0.0, stop_token_ids=())
GAP = 0.05  # standard deviations of a row of logits: a near-tie of the best


@pytest.fixture(scope="module")
def params():
    return wide_params(CFG, SEED)


def engine(params, sliding_pages=40, num_pages=96, seqs=4, **kw):
    return Engine(params, CFG, max_num_seqs=seqs, num_pages=num_pages, page_size=PS,
                  max_seq_len=512, prefill_chunk=CHUNK, decode_burst=4,
                  sliding_pages=sliding_pages, **kw)


def gaps(params, prompts, results) -> list:
    """Each generation's mean distance below the un-paged forward's best logit
    (histories padded to a multiple of 64: causal, so the padding is unseen)."""
    out = []
    for p, r in zip(prompts, results):
        full = list(p) + r.output_tokens[:-1]
        ids = np.zeros((1, -(-len(full) // 64) * 64), np.int32)
        ids[0, :len(full)] = full
        logits = np.asarray(program.forward(params, CFG, jnp.asarray(ids))[0], np.float32)
        out.append(token_gap(logits[len(p) - 1:len(full)], r.output_tokens))
    return out


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    head = rng.integers(2, CFG.vocab_size, 200).tolist()
    return {"long": head + rng.integers(2, CFG.vocab_size, 30).tolist(),
            "short": rng.integers(2, CFG.vocab_size, 37).tolist(),
            "again": head + rng.integers(2, CFG.vocab_size, 9).tolist()}


def test_rows_slide_behind_a_window_of_two_pages_and_a_hit_restores_both_kinds(params, prompts):
    eng = engine(params)
    assert eng.sliding_ledger.window == WINDOW == 2 * PS
    assert page_kinds(CFG) == (("global", 1, None), ("sliding", 3, WINDOW))
    assert eng.sliding_pools[0].shape[:3] == (3, CFG.num_kv_heads, 40)
    assert eng.page_pool.shape[:3] == (1, CFG.num_kv_heads, 96)
    first = [prompts["long"], prompts["short"]]
    res = eng.generate(first, GREEDY)
    sl = eng.sliding_ledger
    # the long row held its window and a chunk at a time, never its 15 pages
    assert sl.freed >= 230 // PS - WINDOW // PS - 1 and sl.in_use == 0
    assert all(g < GAP for g in gaps(params, first, res)), gaps(params, first, res)
    # the same head again: the global kind holds 12 full pages of it (rotated keys, YaRN's
    # factor and all, as they were written); the sliding kind the pages the first row released
    # as it went and the ones it ended on: the hit is as deep
    again = eng.generate([prompts["again"]], GREEDY)[0]
    assert again.cached_tokens == 200 // PS * PS
    assert eng.sliding_hit_tokens == again.cached_tokens == eng.page_hit_tokens
    assert gaps(params, [prompts["again"]], [again])[0] < GAP
    assert sl.alloc.free_count == sl.num_pages and sl.in_use == 0
    # and against the plain reference: the restored pages give the full forward's tokens
    full = prompts["again"] + again.output_tokens[:-1]
    rows = ref.logits_at(model_of(CFG), SEED, [full], [list(range(len(prompts["again"]) - 1,
                                                                   len(full)))],
                         q_block=16, weights=Wide(cfg=CFG, seed=SEED))[0]
    assert token_gap(rows, again.output_tokens) < GAP


def test_a_sliding_pool_smaller_than_a_prompt_still_finishes_it(params, prompts):
    """10 pages of sliding pool (the floor: twice a window of two pages, a
    chunk and one) against a 230-token prompt's 15 and a second row's: only
    because rows release behind their window does it fit."""
    eng = engine(params, sliding_pages=10, seqs=2)
    both = [prompts["long"], prompts["again"]]
    res = eng.generate(both, GREEDY)
    assert [len(r.output_tokens) for r in res] == [10, 10]
    assert eng.sliding_ledger.freed > 0 and eng.sliding_ledger.in_use == 0
    assert all(g < GAP for g in gaps(params, both, res)), gaps(params, both, res)
    with pytest.raises(ValueError, match="sliding_pages"):
        engine(params, sliding_pages=9)


def test_a_follower_is_held_while_its_leader_prefills_and_then_shares_it(params, prompts):
    """Three requests in one step, the queue's order leader / follower /
    another prompt: the follower's next page is the one the leader is
    computing, so it is not admitted beside it; the request BEHIND it is; once
    the leader has written and published the head the follower shares all of
    it."""
    eng = engine(params)
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())
    three = [prompts["long"], prompts["again"], prompts["short"]]
    rids = [eng.add_request(p, sp) for p in three]
    leader, follower, other = (eng._requests[r] for r in rids)
    eng.step()
    assert leader.state == "prefilling" and other.state != "waiting"
    assert follower.state == "waiting" and eng._waiting == [follower]
    done = {}
    while eng.has_work():
        for r in eng.step():
            done[r.request_id] = r
    res = [done[r] for r in rids]
    assert res[1].cached_tokens == 200 // PS * PS and res[0].cached_tokens == 0
    assert all(g < GAP for g in gaps(params, three, res)), gaps(params, three, res)
    assert eng.sliding_ledger.in_use == 0


def test_the_fullest_experts_pairs_reach_the_annotations_and_two_counts_still_do(params, prompts):
    """The programs return [experts hit, pairs, the fullest expert's pairs];
    the engine adds the third up beside the others and writes it on the burst's
    and the wave's annotation.  A program that returns two (the four accepted
    expert families') is read back as before and its annotation has no such
    key."""
    eng = engine(params)
    assert eng.moe_stats == {"burst": [0, 0, 0], "prefill": [0, 0, 0]} and eng.moe_max_pairs == {}
    assert "experts_max_pairs" not in eng._moe_meta("burst")
    eng.generate([prompts["short"], prompts["long"][:60]], GREEDY)
    for prog in ("burst", "prefill"):
        hit, pairs, slots = eng.moe_stats[prog]
        meta = eng._moe_meta(prog)
        fullest = meta["experts_max_pairs"]
        assert meta["expert_tokens"] == pairs and 0 < fullest <= pairs
        # max over mean: at least 1, at most the held experts
        assert 1.0 <= fullest * CFG.n_held / pairs <= CFG.n_held
        assert hit <= slots and slots % (CFG.n_held * CFG.expert_layers) == 0
    # two counts, as models/deepseek_v3.py and the others return them
    before, stats = dict(eng.moe_max_pairs), list(eng.moe_stats["burst"])
    eng._moe_pending.append((0, "burst", jnp.asarray([3, 5], jnp.int32), 64))
    eng._moe_read_back(0)
    assert eng.moe_max_pairs == before
    assert eng.moe_stats["burst"] == [stats[0] + 3, stats[1] + 5, stats[2] + 64]


def test_the_benchmarks_prefill_logits_walk_a_ring_of_sliding_pages(params, prompts):
    """``benchmarks/families/mellum.prefill_logits`` gives a sequence a ring of
    ``cap`` sliding pages (here 5: a window of 2, a chunk of 2 and one more)
    where its 15 pages would not fit the pool beside three more sequences: the
    230-token prompt goes three times round it, and the logits are the plain
    reference's."""
    from benchmarks.correctness import rel_rms
    from benchmarks.families import mellum as family

    eng = engine(params, sliding_pages=20)
    assert eng.sliding_ledger.cap == 5
    seqs = [prompts["long"], prompts["short"], prompts["again"]]
    got = family.prefill_logits(eng, seqs)
    want = np.stack([r[0] for r in ref.logits_at(
        model_of(CFG), SEED, seqs, [[len(s) - 1] for s in seqs], q_block=16,
        weights=Wide(cfg=CFG, seed=SEED))])
    assert rel_rms(got, want) < 0.02
    with pytest.raises(RuntimeError, match="does not fit"):
        family.prefill_logits(engine(params, sliding_pages=19), seqs)
