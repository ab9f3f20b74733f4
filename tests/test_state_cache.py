"""The second kind of per-sequence memory in the cache manager: a recurrent
model's state pool, its live slots and its snapshots at page boundaries
(serving/kv_cache.StateSlots, serving/engine.py), on the hybrid family at a
small size in float32, where the engine's greedy tokens can be held to the
reference's best logit (benchmarks/reference_qwen3_next.py): a token that is
not the reference's best by more than 1e-3 of a row's spread would be a wrong
state, not rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_qwen3_next as ref
from githubrepostorag_tpu.models import qwen3_next as model
from githubrepostorag_tpu.serving import Engine, SamplingParams
from githubrepostorag_tpu.serving.kv_cache import PrefixCachingAllocator, StateSlots
from tests.test_qwen3_next import MODEL, SEED

PAGE = 16
RNG = np.random.default_rng(0)
HEAD = [int(t) for t in RNG.integers(1, 500, size=100)]
A = HEAD + [int(t) for t in RNG.integers(1, 500, size=50)]   # 150 tokens: last boundary 144
B = HEAD + [int(t) for t in RNG.integers(1, 500, size=20)]   # shares 6 pages (96) with A
C = [int(t) for t in RNG.integers(1, 500, size=70)]
D = [int(t) for t in RNG.integers(1, 500, size=90)]
SP = SamplingParams(max_tokens=5, temperature=0.0, stop_token_ids=())


@pytest.fixture()
def make(monkeypatch):
    monkeypatch.setattr(model, "ACT", jnp.float32)
    cfg = model.Qwen3NextConfig.tiny(experts_held=(4, 12))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), model.init_params(cfg, seed=SEED))

    def build(**kw):
        return Engine(params, cfg, **{**dict(
            max_num_seqs=4, num_pages=64, page_size=PAGE, max_seq_len=256, prefill_chunk=64,
            decode_burst=4, kv_dtype=jnp.float32, state_snapshots=4), **kw})
    return build


def is_the_references(prompt, tokens) -> bool:
    full = prompt + tokens[:-1]
    rows = ref.logits_at(MODEL, SEED, [full], [list(range(len(prompt) - 1, len(full)))])[0]
    return max(float((r.max() - r[t]) / r.std()) for r, t in zip(rows, tokens)) < 1e-3


def run(eng, prompt):
    res = eng.generate([prompt], SP)[0]
    return res.cached_tokens, list(res.output_tokens)


def test_a_resumed_prefix_gives_the_cold_prefills_tokens_and_a_hit_is_capped_at_a_snapshot(make):
    eng = make()
    cached, cold = run(eng, A)
    assert cached == 0 and is_the_references(A, cold)
    assert eng._state.written == 1  # the prompt's last page boundary, 144: one slot, not one a chunk
    # B's pages match 96 tokens deep, but no snapshot lies there: cold, on pages
    # of its own, and it leaves the branch-point snapshot the match revealed
    cached, out_b = run(eng, B)
    assert cached == 0 and eng.page_hit_tokens == 96 and eng.state_hit_tokens == 0
    assert is_the_references(B, out_b) and eng._state.written == 2
    cached, again = run(eng, A)
    assert cached == 144 and again == cold and eng.state_restored == 1
    # B again: its pages now match 112 deep (7 pages), the deepest snapshot lies at 96
    cached, again_b = run(eng, B)
    assert cached == 96 and again_b == out_b
    assert (eng.page_hit_tokens, eng.state_hit_tokens) == (96 + 144 + 112, 144 + 96)
    assert not eng._state._pins and eng._allocator.free_count == eng._allocator.num_pages


def test_snapshot_eviction_falls_back_shallower_or_cold(make):
    eng = make(state_snapshots=2)
    _, cold = run(eng, A)            # snapshot at 144
    run(eng, B)                      # ... and at 96 (the branch point): both slots taken
    run(eng, C)                      # a third evicts the least recently used: A's at 144
    assert eng._state.evicted == 1 and eng._state.in_use == 2
    cached, again = run(eng, A)      # pages match 144 deep, the snapshot left at 96 is the deepest
    assert cached == 96 and again == cold and is_the_references(A, again)
    run(eng, C), run(eng, D)         # turn both slots over
    cached, again = run(eng, B)
    assert cached == 0 and is_the_references(B, again)  # cold, though its pages are there


def test_page_eviction_drops_the_snapshot():
    slots, alloc = StateSlots(rows=2, snapshots=3), PrefixCachingAllocator(4)
    alloc.on_evict = slots.drop
    pages = alloc.allocate(2)
    alloc.register(b"h0", pages[0]), alloc.register(b"h1", pages[1])
    assert slots.reserve(b"h1") == 2 and slots.reserve(b"h1") is None and slots.depth([b"h0", b"h1"]) == 2
    alloc.release(pages)             # cached, evictable; the tail page goes first
    alloc.allocate(3)                # two free pages, then the coldest cached one: h1's
    assert slots.depth([b"h0", b"h1"]) == 0 and slots.in_use == 0 and slots.evicted == 1
    assert slots.reserve(b"h2") == 2  # the slot is free again


def test_a_pinned_slot_is_not_evicted_and_the_ledger_counts():
    slots = StateSlots(rows=1, snapshots=2)
    assert (slots.trash, slots.total) == (3, 4)
    a, b = slots.reserve(b"a"), slots.reserve(b"b")
    assert {a, b} == {1, 2} and slots.take(b"a") == a  # pinned, and most recently used
    assert slots.reserve(b"c") == b                    # the unpinned one goes
    slots.take(b"c")
    assert slots.reserve(b"d") is None                 # everything pinned: no snapshot this time
    slots.unpin(a)
    assert slots.reserve(b"d") == a
    assert (slots.written, slots.hits, slots.evicted) == (4, 2, 2)


def test_a_released_slots_state_does_not_reach_the_next_request(make):
    eng = make(prefix_caching=False)
    _, want = run(eng, C)
    # whatever the row's slot held, state and history: a fresh request starts from zeros
    eng._state_pools = jax.tree.map(lambda x: jnp.full_like(x, 3.0), eng._state_pools)
    _, got = run(eng, C)
    assert got == want and is_the_references(C, got)
    assert eng._state.written == 0  # no prefix cache, no snapshots


def test_what_the_hybrid_family_refuses_at_construction(make):
    """Parking a victim needs the host tier, which a state pool does not have:
    a parked row's state is neither saved nor its prompt prefilled again, the
    engine is refused.  So are the other paths that know pages alone."""
    for kw, named in ((dict(preempt="on", kv_tier="on"), "kv_tier, preempt"),
                      (dict(kv_quant=8), "kv_quant"),
                      (dict(prefill_token_budget=64), "prefill_token_budget"),
                      (dict(prefill_chunk=40), "prefill_chunk")):
        with pytest.raises(ValueError, match="recurrent state pool: .*" + named):
            make(**kw)
