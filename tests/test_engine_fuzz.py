"""Randomized scheduling fuzz over the engine's combined features.

The engine now composes continuous batching, co-dispatched mixed
prefill+decode, pipelined bursts, prefix caching and cancellation.  This test drives hundreds of random
scheduling decisions — admissions with shared/unshared prompts at random
times, cancels, varied lengths — against engines in several configurations
and checks the global invariants after every episode:

  - every request finishes with a sane reason,
  - every greedy request's output is byte-identical to a solo run of the
    same prompt on a fresh engine (scheduling must never change tokens),
  - the allocator ends balanced (free_count == num_pages, nothing leaked),
  - the engine ends idle (no stuck rows/waves/chains).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.serving import Engine, SamplingParams

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
    return params, cfg


CONFIGS = [
    dict(),  # bursts + prefix caching (defaults)
    dict(prefix_caching=False),
    dict(decode_burst=1),  # per-token stepping
    dict(prefill_chunk=32),  # three rungs of prefill width: 32, 16, 8
]


@pytest.mark.parametrize(
    "extra", CONFIGS, ids=["default", "nocache", "burst1", "widths"]
)
def test_random_schedule_episode(tiny, extra):
    params, cfg = tiny
    import zlib

    # deterministic per-config seed: a failing episode must replay exactly
    rng = np.random.default_rng(zlib.crc32(repr(sorted(extra.items())).encode()))

    def make():
        return Engine(params, cfg, max_num_seqs=4, num_pages=48, page_size=8,
                      max_seq_len=128, kv_dtype=jnp.float32,
                      **{"prefill_chunk": 16, "decode_burst": 4, **extra})

    run_episode(make, cfg, rng)


@pytest.mark.parametrize("extra", [dict(), dict(state_snapshots=2, prefill_chunk=32)],
                         ids=["hybrid", "hybrid-two-snapshots"])
def test_random_schedule_episode_hybrid(extra, monkeypatch):
    """The same episode over the hybrid family (models/qwen3_next.py: Gated
    DeltaNet state beside K/V pages, in float32 so that a resumed prefix and a
    cold one agree to rounding): prefix hits resume from state snapshots, two
    snapshot slots make them turn over, and no slot stays pinned."""
    from githubrepostorag_tpu.models import qwen3_next

    monkeypatch.setattr(qwen3_next, "ACT", jnp.float32)
    cfg = qwen3_next.Qwen3NextConfig.tiny(num_layers=4, experts_held=(4, 12))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), qwen3_next.init_params(cfg, seed=3))
    made = []

    def make():
        made.append(Engine(params, cfg, max_num_seqs=4, num_pages=48, page_size=8,
                           max_seq_len=128, kv_dtype=jnp.float32,
                           **{"prefill_chunk": 16, "decode_burst": 4, **extra}))
        return made[-1]

    run_episode(make, cfg, np.random.default_rng(34))
    eng = made[0]  # the episode's engine (solo runs come after it)
    assert eng.state_restored > 0 and not eng._state._pins
    assert eng._state.in_use <= eng._state.snapshots


def run_episode(make, cfg, rng):
    """One random schedule over the engine ``make`` builds, checked against
    solo runs on fresh engines from the same factory."""
    # a small pool of prompts, some sharing prefixes (prefix-cache traffic)
    base = rng.integers(0, cfg.vocab_size, 40).tolist()
    prompts = [
        base[:24],
        base[:24] + rng.integers(0, cfg.vocab_size, 9).tolist(),
        rng.integers(0, cfg.vocab_size, 37).tolist(),
        [7, 8, 9, 10] * 7,  # loops
        rng.integers(0, cfg.vocab_size, 5).tolist(),
    ]
    solo_cache: dict[tuple[int, int], list[int]] = {}

    def solo(pi: int, max_tokens: int) -> list[int]:
        key = (pi, max_tokens)
        if key not in solo_cache:
            solo_cache[key] = make().generate(
                [prompts[pi]],
                SamplingParams(max_tokens=max_tokens, temperature=0.0,
                               stop_token_ids=()),
            )[0].output_tokens
        return solo_cache[key]

    eng = make()
    episode = []  # (request_id, prompt_idx, max_tokens, cancelled)
    live: dict[str, tuple[int, int]] = {}
    done: dict[str, object] = {}
    steps = 0
    while steps < 400 and (eng.has_work() or len(episode) < 14):
        action = rng.random()
        if len(episode) < 14 and (action < 0.35 or not eng.has_work()):
            pi = int(rng.integers(0, len(prompts)))
            mt = int(rng.integers(3, 14))
            rid = eng.add_request(
                prompts[pi],
                SamplingParams(max_tokens=mt, temperature=0.0, stop_token_ids=()),
            )
            episode.append([rid, pi, mt, False])
            live[rid] = (pi, mt)
        elif action < 0.40 and live:
            rid = list(live)[int(rng.integers(0, len(live)))]
            eng.cancel(rid)
            for e in episode:
                if e[0] == rid:
                    e[3] = True
        for res in eng.step():
            done[res.request_id] = res
            live.pop(res.request_id, None)
        steps += 1
    assert not eng.has_work(), "engine stuck with work after 400 steps"

    for rid, pi, mt, cancelled in episode:
        res = done[rid]
        if cancelled and res.finish_reason == "cancelled":
            continue  # a cancel that landed before completion
        assert res.finish_reason == "length", (rid, res.finish_reason)
        assert res.output_tokens == solo(pi, mt), (
            f"{rid} (prompt {pi}, max_tokens {mt}) diverged from its solo run"
        )

    # nothing leaked: allocator balanced, no stranded state
    assert eng._allocator.free_count == eng._allocator.num_pages
    assert not eng._row_req and not eng._waiting
    assert eng._chain is None and not eng._pending_first and not eng._deferred


@pytest.mark.parametrize("extra", [
    dict(prefill_chunk=32),  # the mixed top_p traffic flips the
    # filter_sampling burst variant between bursts, over prefill waves
    # at three widths (32, 16, 8)
], ids=["burst-widths"])
def test_random_schedule_sampled_invariants(tiny, extra):
    """Sampled traffic (temperature > 0, top-p, penalties) under random
    scheduling: outputs are seed-dependent, so only the structural
    invariants are asserted — everything finishes, lengths are sane, and
    nothing leaks."""
    params, cfg = tiny
    rng = np.random.default_rng(99)
    eng = Engine(params, cfg, max_num_seqs=4, num_pages=48, page_size=8,
                 max_seq_len=128, kv_dtype=jnp.float32, decode_burst=4,
                 **{"prefill_chunk": 16, **extra})
    want: dict[str, int] = {}
    done: dict[str, object] = {}
    steps = 0
    while steps < 400 and (eng.has_work() or len(want) < 12):
        if len(want) < 12 and (rng.random() < 0.4 or not eng.has_work()):
            mt = int(rng.integers(3, 12))
            rid = eng.add_request(
                rng.integers(0, cfg.vocab_size, int(rng.integers(4, 40))).tolist(),
                SamplingParams(
                    max_tokens=mt,
                    temperature=float(rng.choice([0.0, 0.7, 1.1])),
                    top_p=float(rng.choice([0.8, 0.95, 1.0])),
                    repetition_penalty=float(rng.choice([1.0, 1.2])),
                    stop_token_ids=(),
                ),
            )
            want[rid] = mt
        for res in eng.step():
            done[res.request_id] = res
        steps += 1
    assert not eng.has_work()
    for rid, mt in want.items():
        res = done[rid]
        assert res.finish_reason == "length"
        assert len(res.output_tokens) == mt
        assert all(0 <= t < cfg.vocab_size for t in res.output_tokens)
    assert eng._allocator.free_count == eng._allocator.num_pages
    assert eng._chain is None and not eng._pending_first and not eng._deferred
