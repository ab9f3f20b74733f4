"""The generator offers the same work whatever the seed."""

import json

import pytest

from benchmarks import manifest, traffic

FILES = sorted(p.stem for p in (manifest.HERE / "traffic").glob("*.json"))
SEEDS = (1, 77, 2**31 + 11)


def _load(name):
    return json.loads((manifest.HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", FILES)
def test_same_multiset_and_count_for_different_seeds(name):
    t = _load(name)
    plans = [traffic.make_plan(t, s, 51.0) for s in SEEDS]
    sets = [traffic.window_multiset(p) for p in plans]
    assert sets[0] == sets[1] == sets[2]
    assert len(sets[0]) > 0


@pytest.mark.parametrize("name", FILES)
def test_seed_changes_order_and_phase_not_load(name):
    t = _load(name)
    a, b = traffic.make_plan(t, 1, 51.0), traffic.make_plan(t, 2, 51.0)
    assert json.dumps(a) != json.dumps(b)
    assert json.dumps(a) == json.dumps(traffic.make_plan(t, 1, 51.0))


def test_open_loop_window_count_slices_and_phases():
    t = _load("chat-steady")
    for seed in SEEDS:
        plan = traffic.make_plan(t, seed, 51.0)
        win = [r for r in plan["requests"] if r["phase"] == "window"]
        assert len(win) == round(t["rate_rps"] * 51.0)
        assert all(0.0 <= r["due"] < 51.0 for r in win)
        lead = [r for r in plan["requests"] if r["phase"] == "lead"]
        assert lead and all(-t["lead_in_s"] <= r["due"] < 0 for r in lead)
        tail = [r for r in plan["requests"] if r["phase"] == "tail"]
        assert tail and all(51.0 <= r["due"] < 51.0 + t["tail_s"] for r in tail)
        k = round(51.0 / t["arrival_slice_s"])
        counts = [sum(1 for r in win if 51.0 * j / k <= r["due"] < 51.0 * (j + 1) / k)
                  for j in range(k)]
        assert max(counts) - min(counts) <= 1  # every slice carries the same load
        work = [sum(r["prompt_tokens"] + 4 * r["output_tokens"] for r in win
                    if 51.0 * j / k <= r["due"] < 51.0 * (j + 1) / k) for j in range(k)]
        assert max(work) <= 1.25 * min(work)


def test_lengths_are_quantiles_inside_the_files_limits():
    spec = _load("chat-steady")["prompt_tokens"]
    xs = traffic.quantile_lengths(spec, 143)
    assert xs == sorted(xs) and xs[0] >= spec["min"] and xs[-1] <= spec["max"]
    assert abs(xs[len(xs) // 2] - spec["median"]) <= 5
    assert traffic.quantile_lengths({"dist": "fixed", "value": 256}, 3) == [256, 256, 256]


def test_closed_loop_rag_alternates_kinds_exactly():
    t = _load("rag-answer")
    plan = traffic.make_plan(t, 5, 51.0)
    assert len(plan["clients"]) == t["clients"]
    for c in plan["clients"]:
        kinds = [r["kind"] for r in c["requests"]]
        assert all(a != b for a, b in zip(kinds, kinds[1:]))
        assert -t["lead_in_s"] <= c["start"] < 0
    starts = sorted(c["start"] for c in plan["clients"])
    assert len(set(starts)) == len(starts)  # no two clients begin together


SYNTH = {  # the synthesis traffic PR 23 measured and left out (PERF.md section 7)
    "entry": "openai_chat", "loop": "closed", "clients": 32, "requests_per_client": 12,
    "lead_in_s": 20, "tail_s": 25, "system_prefix_tokens": 1000,
    "blocks": {"pool": 64, "block_tokens": 250, "topics": 4, "topic_blocks": 6,
               "extra_blocks": {"min": 2, "max": 8}, "question_tokens": 24},
    "output_tokens": {"dist": "fixed", "value": 256}}


def test_synth_blocks_share_a_topic_run():
    t = SYNTH
    plan = traffic.make_plan(t, 9, 51.0)
    reqs = [r for c in plan["clients"] for r in c["requests"]]
    run = t["blocks"]["topic_blocks"]
    heads = {tuple(r["blocks"][:run]) for r in reqs}
    assert len(heads) == t["blocks"]["topics"]  # neighbours share their leading pages
    lo, hi = t["blocks"]["extra_blocks"]["min"], t["blocks"]["extra_blocks"]["max"]
    assert all(run + lo <= len(r["blocks"]) <= run + hi for r in reqs)
    assert all(len(set(r["blocks"])) == len(r["blocks"]) for r in reqs)
    same = [traffic.window_multiset(traffic.make_plan(t, s, 51.0)) for s in SEEDS]
    assert same[0] == same[1] == same[2]
