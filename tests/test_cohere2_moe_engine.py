"""Two kinds of page in one engine, on the CPU at test widths: a model whose
sliding layers keep a window of a few pages, served through ``Engine`` with a
pool a kind.  Rows slide past their window and release pages behind it, a
later request hits the prefix only as deep as the kept pages allow, a sliding
pool too small for a prompt's whole length still finishes it, evicted window
pages make a hit fall back and never give a wrong answer; and the ledger's own
rules (``serving/kv_cache.SlidingPages``) one at a time.

What the engine computes is held to the plain reference
(benchmarks/reference_cohere2_moe.py): every generated token is looked up in
the reference's logits for its position (given the same history) and must be
its best or a near-tie of it, as the benchmark's ``decode_token_gap`` reads."""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks import reference_cohere2_moe as ref
from benchmarks.correctness import token_gap
from githubrepostorag_tpu.models.cohere2_moe import Cohere2MoeConfig, init_params
from githubrepostorag_tpu.serving.engine import Engine
from githubrepostorag_tpu.serving.kv_cache import (
    PrefixCachingAllocator,
    SlidingPages,
    page_hashes,
    page_kinds,
)
from githubrepostorag_tpu.serving.sampling_params import SamplingParams
from tests.test_cohere2_moe import Peaked, model_of

PS, WINDOW, CHUNK = 16, 48, 32
CFG = Cohere2MoeConfig.tiny(num_layers=4, sliding_window=WINDOW, experts_held=(0, 8))
GREEDY = SamplingParams(max_tokens=10, temperature=0.0, stop_token_ids=())
GAP = 0.05  # standard deviations of a row of logits: a near-tie of the best


@pytest.fixture(scope="module")
def params():
    p = init_params(CFG, seed=3)  # with ``tests/test_cohere2_moe.Peaked``'s query and key gain
    h, nkv, hd = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    peak = jnp.concatenate([jnp.full(((h + nkv) * hd,), 8.0), jnp.ones((nkv * hd,))])
    p["layers"]["wqkv"] = p["layers"]["wqkv"] * peak.astype(jnp.bfloat16)
    return p


def engine(params, sliding_pages=40, num_pages=96, seqs=4, **kw):
    return Engine(params, CFG, max_num_seqs=seqs, num_pages=num_pages, page_size=PS,
                  max_seq_len=512, prefill_chunk=CHUNK, decode_burst=4,
                  sliding_pages=sliding_pages, **kw)


def gaps(prompts, results) -> list:
    """Each generation's mean distance below the reference's best logit."""
    out = []
    for p, r in zip(prompts, results):
        full = list(p) + r.output_tokens[:-1]
        at = list(range(len(p) - 1, len(full)))
        rows = ref.logits_at(model_of(CFG), 3, [full], [at], q_block=16, weights=Peaked(cfg=CFG, seed=3))[0]
        out.append(token_gap(rows, r.output_tokens))
    return out


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    head = rng.integers(2, CFG.vocab_size, 200).tolist()
    return {"long": head + rng.integers(2, CFG.vocab_size, 30).tolist(),
            "short": rng.integers(2, CFG.vocab_size, 37).tolist(),
            "again": head + rng.integers(2, CFG.vocab_size, 9).tolist()}


def test_rows_slide_free_pages_and_a_later_prompt_hits_as_deep_as_both_kinds_hold(params, prompts):
    eng = engine(params)
    assert eng.sliding_ledger.window == WINDOW and page_kinds(CFG)[1] == ("sliding", 3, WINDOW)
    assert eng.sliding_pools[0].shape[:3] == (3, CFG.num_kv_heads, 40)
    assert eng.page_pool.shape[:3] == (1, CFG.num_kv_heads, 96)
    first = [prompts["long"], prompts["short"]]
    res = eng.generate(first, GREEDY)
    sl = eng.sliding_ledger
    # the long row held its window and a chunk at a time, never its 15 pages
    assert sl.freed >= 230 // PS - WINDOW // PS - 1 and sl.in_use == 0
    assert all(g < GAP for g in gaps(first, res)), gaps(first, res)
    # the same head again: the global kind holds 12 full pages of it; the sliding kind the
    # pages the first row released as it went and the ones it ended on: the hit is as deep
    again = eng.generate([prompts["again"]], GREEDY)[0]
    assert again.cached_tokens == 200 // PS * PS
    assert eng.sliding_hit_tokens == again.cached_tokens == eng.page_hit_tokens
    assert gaps([prompts["again"]], [again])[0] < GAP
    # the admission shared the window's pages alone, not the prefix's: 3 or 4 pages
    assert sl.alloc.free_count == sl.num_pages


def test_a_sliding_pool_smaller_than_a_prompt_still_finishes_it(params, prompts):
    """12 pages of sliding pool (the floor: twice the window, a chunk and one)
    against a 230-token prompt's 15 and a second row's: only because rows
    release behind their window does it fit, and the answer is the same."""
    eng = engine(params, sliding_pages=12, seqs=2)
    both = [prompts["long"], prompts["again"]]
    res = eng.generate(both, GREEDY)
    assert [len(r.output_tokens) for r in res] == [10, 10]
    assert eng.sliding_ledger.freed > 0
    assert all(g < GAP for g in gaps(both, res)), gaps(both, res)
    with pytest.raises(ValueError, match="sliding_pages"):
        engine(params, sliding_pages=11)


def test_evicted_window_pages_make_the_hit_fall_back_never_a_wrong_answer(params, prompts):
    eng = engine(params, sliding_pages=24)
    eng.generate([prompts["long"]], GREEDY)
    # other traffic turns the small sliding pool over: the first prompt's window pages go,
    # its global pages (96 of them) stay
    rng = np.random.default_rng(1)
    eng.generate([rng.integers(2, CFG.vocab_size, 180).tolist() for _ in range(3)], GREEDY)
    hashes = page_hashes(prompts["again"], PS)[:12]
    assert eng._allocator.match_len(hashes) == 12
    deep = eng.sliding_ledger.depth(hashes, 12)
    assert deep < 12
    before = eng.page_hit_tokens
    again = eng.generate([prompts["again"]], GREEDY)[0]
    assert again.cached_tokens == deep * PS
    assert eng.page_hit_tokens - before == 12 * PS  # what the global pages offered
    assert gaps([prompts["again"]], [again])[0] < GAP


def test_a_follower_is_passed_over_while_its_leader_prefills_and_then_shares_it(params, prompts):
    """Three requests in one step, the queue's order leader / follower /
    another prompt: the follower's next page is the one the leader is
    computing, so it is not admitted beside it; the request BEHIND it is (the
    hold is not the head of the line); once the leader has written and
    published the head the follower is admitted and shares all of it, where admitted at once it
    would have shared nothing (a sliding kind publishes a long prompt's pages
    only behind its window or at its end)."""
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
    assert all(g < GAP for g in gaps(three, res)), gaps(three, res)
    assert eng.sliding_ledger.in_use == 0


def test_a_model_with_a_sliding_kind_refuses_what_cannot_follow_yet(params):
    for kw in ({"kv_quant": 8}, {"kv_tier": "on"}, {"kv_host_pool_pages": 8}, {"preempt": "on"},
               {"prefill_token_budget": 64}, {"sp_prefill_threshold": 64},
               {"prefix_caching": False}):
        with pytest.raises(ValueError, match="sliding page pool"):
            engine(params, **kw)


# ------------------------------------------------------------- the ledger --

def _chain(n):
    return page_hashes(list(range(n * PS)), PS)


def test_depth_is_the_deepest_boundary_whose_window_pages_are_held():
    sl = SlidingPages(32, PS, WINDOW, CHUNK)
    assert (sl.first_page(0), sl.first_page(47), sl.first_page(48), sl.first_page(64)) == (0, 0, 0, 1)
    hashes = _chain(10)
    row = sl.admit(hashes, 0, 12)
    assert row.covered == sl.cap == 6 and row.private == 6  # a window, a chunk and one more
    sl.advance(row, 5 * PS, hashes, 5)  # five pages written; the window needs pages 2..
    assert row.first == 2 and row.pages[:2] == [-1, -1] and sl.freed == 2
    assert row.covered == 8 and row.private == 6  # took two ahead for the two it released
    # what it released was registered as it left; what it holds is its own until it is covered
    assert sl.depth(hashes, 10) == 2
    sl.advance(row, 10 * PS, hashes, 10)
    assert row.covered == row.total == 12 and sl.depth(hashes, 10) == 10
    # a hit of 10 pages shares pages 7, 8, 9: the window that ends there
    assert sl.can_admit(hashes, 10, 11)
    other = sl.admit(hashes, 10, 11)
    assert other.first == 7 and other.pages[7:10] == row.pages[7:10] and other.covered == 11
    sl.release_pages(sl.release(row))
    sl.release_pages(sl.release(other))
    assert sl.in_use == 0 and sl.alloc.free_count == 32


def test_a_hole_in_the_window_stops_the_hit_short():
    sl = SlidingPages(32, PS, WINDOW, CHUNK)
    hashes = _chain(10)
    row = sl.admit(hashes, 0, 10)
    for j in range(1, 11):
        sl.advance(row, j * PS, hashes, j)
    sl.release_pages(sl.release(row))
    assert sl.depth(hashes, 10) == 10
    gone = sl.alloc._hash_to_page.pop(hashes[8])  # page 8 evicted
    del sl.alloc._page_to_hash[gone]
    assert sl.depth(hashes, 10) == 8  # pages 5, 6, 7 are held; 9 and 10 would need page 8
    assert sl.depth(hashes, 4) == 4 and sl.depth(hashes[:0], 0) == 0


def test_a_model_that_states_no_kinds_is_one_global_kind():
    """Every existing family builds the pools, tables and allocator it built
    before: their own engine tests pass unedited; here, what the engine reads."""
    from githubrepostorag_tpu.models.nemotron_h import NemotronHConfig
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config

    dense, hybrid = Qwen2Config.tiny(), NemotronHConfig.tiny()
    assert page_kinds(dense) == (("global", dense.num_layers, None),)
    assert page_kinds(hybrid) == (("global", hybrid.kv_layers, None),)
    assert isinstance(SlidingPages(32, PS, WINDOW, CHUNK).alloc, PrefixCachingAllocator)
