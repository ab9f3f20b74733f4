"""What ``Engine.step``'s one decode path owes every family, whatever its
step programs: the contracts the speculative paths were held to before
PR 45 took them out, asked of the plain path under each of the six
families' own programs (qwen2, deepseek_v3, qwen3_next, olmo_hybrid,
nemotron_h, falcon_h1) at tiny widths, in float32 so that a token is a statement about
scheduling and not about rounding.

One warm engine a family (prefix cache on) takes the traffic; a second
without the prefix cache gives each prompt's solo tokens.  Both have the
same geometry, so the family's step programs compile once a module.
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from githubrepostorag_tpu.serving import Engine, SamplingParams
from tests.helpers.compile_guard import compile_guard, watchdog_counter

PAGE, CHUNK, ROWS, SEQ, BURST = 16, 64, 2, 160, 4
GEOMETRY = dict(max_num_seqs=ROWS, num_pages=48, page_size=PAGE, max_seq_len=SEQ,
                prefill_chunk=CHUNK, decode_burst=BURST, kv_dtype=jnp.float32)
FAMILIES = ("qwen2", "deepseek_v3", "qwen3_next", "olmo_hybrid", "nemotron_h", "falcon_h1")
# each family's ``tiny()``, a hybrid cut to one period of its layer pattern:
# the contracts are the scheduler's, and every kind of layer is still there
TINY = {"deepseek_v3": dict(experts_held=(4, 12)),
        "qwen3_next": dict(experts_held=(4, 12), num_layers=4),
        "olmo_hybrid": dict(num_layers=4), "nemotron_h": dict(pattern="MEM*E"),
        "falcon_h1": dict(num_layers=2)}  # every layer is a period: both caches in each

RNG = np.random.default_rng(45)
HEAD = [int(t) for t in RNG.integers(3, 500, size=3 * PAGE)]  # three shareable pages


def prompt(n: int, head: bool = False) -> list[int]:
    return (HEAD if head else []) + [int(t) for t in RNG.integers(3, 500, size=n)]


def greedy(n: int, stop=()) -> SamplingParams:
    return SamplingParams(max_tokens=n, temperature=0.0, stop_token_ids=tuple(stop))


SAMPLED = SamplingParams(max_tokens=6, temperature=0.8, top_p=0.9, stop_token_ids=())


class Family:
    """A family's tiny model and the engines the contracts run on."""

    def __init__(self, name: str) -> None:
        self.name = name
        if name == "qwen2":
            from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params

            # an untied head that names the token after its input's: greedy
            # output that depends on the prompt and does not repeat one token
            self.cfg = dataclasses.replace(Qwen2Config.tiny(), tie_word_embeddings=False)
            params = init_params(self.cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
            params["lm_head"] = jnp.roll(params["embed"], 1, axis=0).T
        else:
            model = importlib.import_module(f"githubrepostorag_tpu.models.{name}")
            config = next(v for k, v in vars(model).items() if k.endswith("Config")
                          and getattr(v, "__module__", "") == model.__name__)
            self.cfg = config.tiny(**TINY[name])
            params = model.init_params(self.cfg, seed=7)
        self.params = jax.tree.map(
            lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, params)
        self.own_programs = name != "qwen2"
        self.eng = self.build()
        self.eng.warmup()
        self._solo = self.build(prefix_caching=False)

    def build(self, **kw) -> Engine:
        extra = dict(state_snapshots=4) if getattr(self.cfg, "recurrent_state", False) else {}
        return Engine(self.params, self.cfg, **{**GEOMETRY, **extra, **kw})

    def solo(self, ids: list[int], sp: SamplingParams) -> list[int]:
        """``ids``' tokens alone on an engine with no prefix cache."""
        return list(self._solo.generate([ids], sp)[0].output_tokens)


@pytest.fixture(scope="module", params=FAMILIES)
def fam(request):
    """The family's programs read their activation dtype from a module
    global as they are traced: float32 while this family's cases run."""
    name = request.param
    patch = pytest.MonkeyPatch()
    if name != "qwen2":
        model = importlib.import_module(f"githubrepostorag_tpu.models.{name}")
        patch.setattr(model, "ACT", jnp.float32)
        jax.clear_caches()
    try:
        yield Family(name)
    finally:
        patch.undo()
        if name != "qwen2":
            jax.clear_caches()


def drain(eng: Engine, done: dict, limit: int = 400) -> dict:
    steps = 0
    while eng.has_work():
        for res in eng.step():
            done[res.request_id] = res
        steps += 1
        assert steps < limit, "engine wedged"
    return done


def assert_idle(eng: Engine) -> None:
    """Nothing leaked: every page and row back, no wave, chain or deferral."""
    assert eng._allocator.free_count == eng._allocator.num_pages
    assert sorted(eng._free_rows) == list(range(eng.max_num_seqs))
    assert not eng._row_req and not eng._waiting
    assert eng._chain is None and not eng._pending_first and not eng._deferred


# ------------------------------------------------------------ the contracts --


def test_max_tokens_cuts_at_the_count_whatever_the_burst_width(fam):
    """A budget that is no multiple of the burst: the overshoot is thrown
    away, and a shorter budget is a prefix of a longer one."""
    ids = prompt(21)
    long = fam.eng.generate([ids], greedy(2 * BURST + 3))[0]
    assert long.finish_reason == "length" and len(long.output_tokens) == 2 * BURST + 3
    short = fam.eng.generate([ids], greedy(BURST + 1))[0]
    assert short.finish_reason == "length"
    assert short.output_tokens == long.output_tokens[:BURST + 1]
    assert_idle(fam.eng)


def test_a_stop_id_ends_the_row_at_that_token(fam):
    """The burst runs past a stop token on the device; the commit ends the
    row at it, keeps it, and discards the rest."""
    ids = prompt(19)
    free = fam.solo(ids, greedy(3 * BURST))
    at = max(i for i in range(len(free)) if free[i] not in free[:i])  # its first occurrence
    res = fam.eng.generate([ids], greedy(3 * BURST, stop=[free[at]]))[0]
    assert res.finish_reason == "stop"
    assert res.output_tokens == free[:at + 1]
    assert_idle(fam.eng)


def test_a_sampled_row_leaves_the_greedy_row_its_solo_tokens(fam):
    ids, other = prompt(27), prompt(13)
    want = fam.solo(ids, greedy(9))
    got, sampled = fam.eng.generate([ids, other], [greedy(9), SAMPLED])
    assert got.output_tokens == want
    assert len(sampled.output_tokens) == SAMPLED.max_tokens
    assert all(0 <= t < fam.cfg.vocab_size for t in sampled.output_tokens)
    assert_idle(fam.eng)


def test_prefix_hits_and_continuous_admission_give_the_cold_tokens(fam):
    """Five prompts over two rows, three of them on one head of whole
    pages, admitted while others decode: every one its solo tokens, and
    the later heads served from the cache."""
    prompts = [prompt(30, head=True), prompt(40), prompt(9, head=True), prompt(70),
               prompt(22, head=True)]
    sps = [greedy(7), greedy(5), greedy(BURST + 2), greedy(3), greedy(6)]
    want = [fam.solo(p, sp) for p, sp in zip(prompts, sps)]
    eng, done, rids = fam.eng, {}, []
    hits = eng._allocator.hit_tokens
    for p, sp in zip(prompts, sps):
        rids.append(eng.add_request(p, sp))
        for res in eng.step():  # the next arrives with the batch in flight
            done[res.request_id] = res
    drain(eng, done)
    assert [list(done[r].output_tokens) for r in rids] == want
    assert done[rids[4]].cached_tokens > 0 and eng._allocator.hit_tokens > hits
    assert_idle(eng)


def test_a_row_at_its_limit_ends_and_frees_its_pages(fam):
    """A request that asks past the context is cut where the context ends,
    its pages come back, and the row serves the next request."""
    ids = prompt(SEQ - 2 * BURST - 1)
    res = fam.eng.generate([ids], greedy(10 * BURST))[0]
    assert res.finish_reason == "length"
    assert len(ids) + len(res.output_tokens) == SEQ
    assert res.output_tokens == fam.solo(ids, greedy(len(res.output_tokens)))
    assert_idle(fam.eng)
    nxt = prompt(12)
    assert fam.eng.generate([nxt], greedy(4))[0].output_tokens == fam.solo(nxt, greedy(4))
    assert_idle(fam.eng)


def test_the_same_seed_draws_the_same_sampled_tokens(fam):
    """Sampling folds the engine's seed and its dispatch count into the
    key: two engines that saw the same traffic draw the same tokens, and
    another seed draws others."""
    ids = [prompt(17), prompt(33)]

    def draw(seed):
        out = fam.build(rng_seed=seed).generate(ids, SamplingParams(
            max_tokens=12, temperature=1.0, stop_token_ids=()))
        return [list(r.output_tokens) for r in out]

    first = draw(5)
    assert first == draw(5)
    assert first != draw(6)


def test_a_cancel_mid_burst_frees_the_row_and_spares_its_neighbour(fam):
    keep, drop = prompt(25), prompt(31)
    want = fam.solo(keep, greedy(4 * BURST))
    eng, done = fam.eng, {}
    r_keep = eng.add_request(keep, greedy(4 * BURST))
    r_drop = eng.add_request(drop, greedy(4 * BURST))
    while not all(r.output for r in eng._row_req.values()) or len(eng._row_req) < 2:
        for res in eng.step():
            done[res.request_id] = res
    assert eng._chain is not None  # a burst is in flight over both rows
    eng.cancel(r_drop)
    drain(eng, done)
    assert done[r_drop].finish_reason == "cancelled"
    assert len(done[r_drop].output_tokens) < 4 * BURST
    assert list(done[r_keep].output_tokens) == want
    assert_idle(eng)


def test_tokens_stream_to_the_callback_in_the_order_of_the_output(fam):
    seen: dict[str, list[int]] = {}
    eng = fam.eng
    sps = [greedy(2 * BURST + 1), greedy(3)]
    rids = [eng.add_request(p, sp, on_token=lambda rid, t: seen.setdefault(rid, []).append(t))
            for p, sp in zip([prompt(14), prompt(37)], sps)]
    done = drain(eng, {})
    for rid in rids:
        assert seen[rid] == list(done[rid].output_tokens)
    assert_idle(eng)


def test_no_step_program_compiles_after_warm_up(fam):
    """Both row buckets, every prefill width, the filtered sampling variant,
    a prefix hit, an admission into a running batch and a cancel: all of it
    runs the programs ``warmup`` compiled."""
    eng = fam.eng
    with compile_guard(watchdog_counter(), label=f"{fam.name}: mixed traffic after warm-up"):
        eng.generate([prompt(5)], greedy(3))
        eng.generate([prompt(CHUNK + 9), prompt(20)], [greedy(BURST + 1), SAMPLED])
        eng.generate([prompt(11, head=True), prompt(26, head=True)], greedy(2))
        first = eng.add_request(prompt(40), greedy(3 * BURST))
        eng.step()
        eng.step()
        eng.add_request(prompt(28), SAMPLED)
        eng.step()
        eng.cancel(first)
        drain(eng, {})
    assert_idle(eng)


def test_per_token_stepping_gives_the_bursts_tokens(fam):
    """``decode_burst`` is how many steps a dispatch holds, never what they
    compute: one step a dispatch commits the tokens of four."""
    prompts = [prompt(23), prompt(35)]
    want = [fam.solo(p, greedy(2 * BURST + 1)) for p in prompts]
    one = fam.build(decode_burst=1, prefix_caching=False)
    got = one.generate(prompts, greedy(2 * BURST + 1))
    assert [list(r.output_tokens) for r in got] == want
    assert_idle(one)


def test_a_parked_row_resumes_token_identically_or_the_family_refuses_it(fam):
    """Parking a victim to the host tier: where the family's pages can be
    parked the victim's tokens are its solo tokens; a family with its own
    pools is refused at construction, by name."""
    tiered = dict(num_pages=16, kv_tier="on", kv_host_pool_pages=64, preempt="on")
    if fam.own_programs:
        with pytest.raises(ValueError, match="not built for a .* pool: .*preempt"):
            fam.build(**tiered)
        return
    batch = [prompt(2 * PAGE), prompt(2 * PAGE)]
    hot = prompt(2 * PAGE)
    sp_batch, sp_hot = greedy(5 * PAGE), greedy(8)
    want = [fam.solo(p, sp_batch) for p in batch] + [fam.solo(hot, sp_hot)]
    eng, done = fam.build(**tiered), {}
    rids = [eng.add_request(p, sp_batch, priority="batch") for p in batch]
    for _ in range(3):
        eng.step()
    assert eng.num_running == 2  # the pool is theirs: no third row fits
    rids.append(eng.add_request(hot, sp_hot))
    drain(eng, done)
    eng.flush_kv_migrations()
    assert eng.preemptions >= 1 and eng.preempt_resumes == eng.preemptions
    assert eng.resume_recomputed_prompt_tokens == 0
    assert [list(done[r].output_tokens) for r in rids] == want
    assert eng._allocator.free_count == eng._allocator.num_pages
