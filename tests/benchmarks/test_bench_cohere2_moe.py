"""The Cohere2-MoE family of the benchmark: its configuration file against the
catalog's row, its counts against the issue's parameter arithmetic and one
hand-computed dispatch each, its five metrics' readings on a hand-made trace,
the traffic file against the accepted one it was made from, and the comparison
that decides ``correct`` at the rehearsal's widths with every control failing.
Pins no total of cells, configurations or metrics."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import cohere2_moe as family
from benchmarks.readers import op_share, sliding_pages

CELL = "command-a-plus-ep8-bf16.repo-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MINE = {"sliding_attn_roofline_frac", "sliding_prefill_attn_roofline_frac", "sliding_hit_share",
        "sliding_pool_move_share", "sliding_burst_hbm_frac"}
ATTENTION, EXPERT, BESIDE = 142_606_336, 50_331_648, 344_461_312  # ISSUE.md's arithmetic


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_width_is_the_published_one_and_the_cut_is_the_chips_share(cell):
    config = cell.config
    cut = {"num_hidden_layers": (4, 32), "num_experts": (16, 128), "vocab_size": (32768, 262144)}
    for key, (here, published) in cut.items():
        assert (config[key], config["published"][key]) == (here, published), key
    if os.path.exists(CATALOG):  # every key of the catalog's row as published, but the three cut
        row = next(json.loads(ln) for ln in open(CATALOG) if "command-a-plus-05-2026" in ln)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value or key in cut, key
    assert config["reduced"] == ["weights", "tokenizer", "num_hidden_layers", "num_experts",
                                 "vocab_size", "vision_tower"]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert (config["router_width"], config["experts_held"], config["num_experts_per_tok"]) \
        == (128, [0, 16], 8)
    assert "8 chips share each layer" in config["deployment"]
    assert {"shared_expert_combination_strategy", "shared_expert_width", "router", "router_draw",
            "weights", "tokenizer", "shared_storage"} <= set(config["assumed"])
    assert {"num_hidden_layers", "num_experts", "vocab_size", "vision_tower", "arithmetic"} \
        <= set(config["cut"])
    assert config["weights"]["seed"] == 20260503 and "218.25 B" in config["published"]["parameters"]
    eng = config["engine"]
    assert (eng["num_pages"], eng["sliding_pages"], eng["max_seq_len"]) == (2560, 1024, 26624)
    assert eng["sliding_pages"] < eng["num_pages"] / 2 and "584 sliding pages" in \
        config["pools"]["working_set"]
    model = family.model_of(config, rehearse=False)
    cfg = family.model_config(model)
    assert cfg.page_kinds == (("global", 1, None), ("sliding", 3, 4096))
    assert cfg.period == ("sliding", "sliding", "sliding", "global") == tuple(
        t.split("_")[0].replace("full", "global") for t in config["layer_types"][:4])
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.n_held) == (128, 8, 128, 16)
    assert cfg.expert_counters and cfg.step_programs.endswith("cohere2_moe")
    assert set(config["correctness"]["limits"]) == {"prefill_logits_rel_rms", "decode_token_gap"}
    assert config["correctness"]["precision_control"] == "fp8"
    tiny = family.model_of(config, rehearse=True)  # what the rehearsal keeps of what is new
    assert tiny["sliding_window"] == 64 and tiny["num_shared_experts"] == 4
    assert tiny["num_experts"] > tiny["experts_held"][1] and tiny["layer_switch"] == 4


def test_the_traffic_is_repo_sessions_with_five_numbers_changed(cell):
    base = manifest.load_cell("deepseek-v3-ep16-bf16.repo-sessions").traffic
    mine, changed = cell.traffic, {}

    def walk(a, b, path=""):
        for k in sorted(set(a) | set(b)):
            if isinstance(a.get(k), dict) and isinstance(b.get(k), dict):
                walk(a[k], b[k], f"{path}{k}.")
            elif a.get(k) != b.get(k):
                changed[f"{path}{k}"] = b.get(k)

    walk(base, mine)
    assert changed == {"blocks.topic_blocks": 48, "blocks.pool": 416, "lead_in_s": 40,
                       "needs.max_seq_len": 26624, "correctness.max_prompt_tokens": 26112}
    assert cell.traffic_name == "repo-longctx" and cell.chips == 1
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"]
    assert MINE <= set(cell.per_layer)
    other = manifest.load_cell("deepseek-v3-ep16-bf16.repo-sessions")
    assert not MINE & set(other.per_layer)
    assert {"moe_experts_hbm_frac", "moe_experts_hit_share", "moe_tokens_per_expert_mean",
            "paged_attn_hbm_frac", "prefix_hit_share", "custom_call_share",
            "setup_compile_s"} <= set(cell.per_layer)
    # the two accepted whole-burst shares hand the family ``rows`` alone, and this router is not
    # the even one a count from rows has to assume: silent here (``sliding_burst_hbm_frac`` reads
    # the engine's own expert counts in their place)
    assert not {"decode_hbm_frac", "burst_hbm_frac"} & set(cell.per_layer)
    manifest_ = manifest.load_manifest()
    for name in MINE:  # listed for this cell alone
        entry = next(m for m in manifest_["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert manifest.metric_spec(name)["reader"] in ("sliding_pages", "op_share")
    manifest.validate(manifest_)


def test_counts_are_the_issues_arithmetic_and_one_dispatch_by_hand(cell):
    model = family.model_of(cell.config, rehearse=False)
    d, v = 4096, 32768
    assert family.attention_params(model) == ATTENTION and family.expert_params(model) == EXPERT
    assert ATTENTION + 4 * EXPERT + d * 128 + d == BESIDE
    # the published model: 32 layers of 128 routed experts, and 8 of them a token
    whole = 32 * (BESIDE + 128 * EXPERT) + 262144 * d
    active = 32 * (BESIDE + 8 * EXPERT) + 262144 * d
    assert round(whole / 1e9, 2) == 218.25 and round(active / 1e9, 2) == 24.98
    assert 4 * (BESIDE + 16 * EXPERT) + v * d == 4_733_288_448  # this chip: 9.47 GB
    assert family.key_bytes(model) == 4096 and family.key_flops(model) == 65536
    # a step streams a layer's attention, router, shared experts and the experts hit, and the head
    hit = family.experts_hit(model, 19)
    assert 11.2 < hit < 11.4  # an even router wakes ~11.3 of 16 at 19 rows, and no constant bends it
    assert family.fixed_weight_bytes(model, 2.0) == 2.0 * (4 * BESIDE + d * v)
    assert family.weight_bytes(model, 2.0, rows=19) == 2.0 * (4 * (BESIDE + hit * EXPERT) + d * v)
    # a burst of 8 steps over 19 live rows at 25k cached tokens each
    kv = 19 * 25000
    total, paged = family.burst_bytes(model, 2.0, rows=19, kv_tokens=kv, steps=8)
    assert paged == sum((kv + 19 * i) * 4096 for i in range(8))  # the global layer alone
    walked = 3 * 19 * 4096 * 4096  # a full window a row, layer and step (25k > 4,096)
    assert total == 8 * family.weight_bytes(model, 2.0, 19) + paged + sum(
        min(19 * 4095 + 19 * i, 19 * 4096) for i in range(8)) * 4096 * 3
    assert 1.9e9 < paged / 8 < 2.0e9 and 0.95e9 < walked < 0.96e9  # ISSUE.md: ~3 GB of attention
    # the same burst from the engine's own counts: 35% of the 16 x 4 x 8 slots it offered were hit
    counted = family.burst_counted_bytes(model, 2.0, 19, kv, 19 * 4095, 8, 0.35)
    assert counted == 8 * family.fixed_weight_bytes(model, 2.0) + paged + sum(
        min(19 * 4095 + 19 * i, 19 * 4096) for i in range(8)) * 4096 * 3 \
        + 0.35 * 16 * 4 * 8 * EXPERT * 2.0
    assert counted < total  # what the even count adds: 5.7 experts a layer and step
    nbytes, flops = family.sliding_attention_work(model, 19, 19 * 4095, 8)
    keys = sum(min(19 * 4095 + 19 * i, 19 * 4096) for i in range(8)) * 3
    assert (nbytes, flops) == (keys * 4096, keys * 65536.0)
    assert nbytes / 819e9 > flops / 197e12  # the burst's kernel is bound by bytes: 16 B a FLOP
    nbytes, flops = family.sliding_prefill_work(model, 512 * 4096, 4095 + 512)
    assert (nbytes, flops) == ((4095 + 512) * 4096 * 3, 512 * 4096 * 65536.0 * 3)
    assert flops / 197e12 > nbytes / 819e9  # the wave's by operations
    pairs = family.causal_pairs(24576, 512)
    assert family.prefill_flops(model, 512, pairs, 1) > 65536.0 * (pairs + 3 * 512 * 4096)
    assert family.expert_op_sizes(model, cell.config) == {
        "tile_rows": 32, "gate_up": 8192, "hidden": 4096}
    assert family.work.bytes_per_weight(cell.config) == 2.0


def _ctx(host, per_op=None, busy=1.0):
    plain = {"devices": {"0": {"ops": [], "modules": [["jit_decode_burst(1)", 1.0, 0.1],
                                                      ["jit_decode_burst(1)", 2.0, 0.1]]}},
             "host": host}
    config = manifest.load_cell(CELL).config
    return SimpleNamespace(
        _host_phases=plain, trace={"per_op": per_op or {}, "busy_first_s": busy},
        trace_span=(0.0, 9.0), family=family, model=family.model_of(config, rehearse=False),
        config=config, chips=1, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_the_five_metrics_read_this_models_stats_and_nothing_on_a_program_without_them():
    wave = lambda t, pairs, keys, pages, served: ["engine.prefill_batch", t, 0.01, {  # noqa: E731
        "rows": 1, "new_tokens": 512, "cached_tokens": 24576, "pairs": 1, "completes": 1,
        "sliding_pairs": pairs, "sliding_keys": keys, "page_hit_tokens": pages,
        "sliding_hit_tokens": served, "sliding_pages_freed": 7}]
    burst = lambda t, tokens, hit, slots: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 19, "kv_tokens": 19 * 25000, "steps": 8, "sliding_tokens": tokens,
        "experts_hit": hit, "expert_tokens": 3 * hit, "expert_slots": slots}]
    host = [burst(0.9, 19 * 4095, 1000, 5120), wave(1.0, 512 * 4096, 4607, 24576, 24576),
            wave(1.2, 100 * 4096, 4195, 2 * 24576, 2 * 24576),
            wave(1.4, 300 * 4096, 4395, 3 * 24576, 2 * 24576 + 20480),
            burst(1.9, 19 * 4095, 1000 + 179, 5120 + 512)]
    ops = {"sliding_attention.36_bf16_32_8_16_128_": 0.012,
           "sliding_attention.37_bf16_32_8_16_128_": 0.012,
           "paged_attention.12_bf16_32_8_16_128_": 5.0,  # the global layer's: the accepted metric's
           "sliding_prefill_attention.6_bf16_1_8_16_128_128_": 0.006,
           "fused_window_attention.5_bf16_1_8_16_128_128_": 5.0,  # the global layer's wave kernel
           "copy.3_bf16_3_8_1024_128_128_": 0.02,  # what the guard is for: the pool copied whole
           "fusion.12_bf16_3_8_8192_16_128_": 5.0}  # a commit's windows, in place: no move
    ctx = _ctx(host, ops, busy=2.0)
    args = lambda name: manifest.metric_spec(name)["args"]  # noqa: E731
    got = sliding_pages.read(ctx, **args("sliding_attn_roofline_frac"))
    nbytes, _ = family.sliding_attention_work(ctx.model, 19, 19 * 4095, 8)
    assert abs(got - 100.0 * 2 * nbytes / 819e9 / 0.024) < 1e-9 and got < 100.0
    got = sliding_pages.read(ctx, **args("sliding_prefill_attn_roofline_frac"))
    allowed = sum(max(b / 819e9, f / 197e12) for b, f in (
        family.sliding_prefill_work(ctx.model, p, k)
        for p, k in ((512 * 4096, 4607), (100 * 4096, 4195), (300 * 4096, 4395))))
    assert abs(got - 100.0 * allowed / 0.006) < 1e-9 and got < 100.0
    # between the first and the last wave the global pages offered 2 x 24,576 tokens and the
    # sliding kind could serve 24,576 + 20,480 of them
    assert sliding_pages.read(ctx, **args("sliding_hit_share")) == 100.0 * 45056 / 49152
    assert abs(op_share.read(ctx, **args("sliding_pool_move_share")) - 100.0 * 0.02 / 2.0) < 1e-9
    # both bursts matched a module event of 0.1 s; between them the engine counted 179 experts hit
    # of the 512 slots a burst offers: the experts' bytes are that share's, no router modelled
    got = sliding_pages.read(ctx, **args("sliding_burst_hbm_frac"))
    one = family.burst_counted_bytes(ctx.model, 2.0, 19, 19 * 25000, 19 * 4095, 8, 179 / 512)
    assert abs(got - 100.0 * 2 * one / 819e9 / 0.2) < 1e-9 and 40.0 < got < 100.0
    ctx.trace["per_op"]["copy.9_bf16_1_8_2560_128_128_"] = 0.04  # the global pool copied whole
    assert abs(op_share.read(ctx, **args("sliding_pool_move_share")) - 100.0 * 0.06 / 2.0) < 1e-9
    # a program that writes no such stats (the parent commit, any other model) reads as nothing
    bare = _ctx([["engine.prefill_batch", 1.0, 0.01, {"rows": 1, "new_tokens": 5}]] * 2
                + [["engine.decode_burst", 0.9, 0.001, {"rows": 4, "kv_tokens": 9, "steps": 8}]],
                ops)
    for name in MINE - {"sliding_pool_move_share"}:
        assert sliding_pages.read(bare, **args(name)) is None, name
    none = SimpleNamespace(trace_span=None, trace=None, family=family, peaks=None,
                           _host_phases=None)
    for name in MINE - {"sliding_pool_move_share"}:
        assert sliding_pages.read(none, **args(name)) is None, name
    assert op_share.read(none, **args("sliding_pool_move_share")) is None
    # a family without the counts (every other one) reads as nothing too
    from benchmarks.families import deepseek_v3

    ctx.family = deepseek_v3
    assert sliding_pages.read(ctx, **args("sliding_attn_roofline_frac")) is None


def test_every_control_is_not_correct_and_the_program_is_at_test_widths(tmp_path, monkeypatch):
    """The comparison that decides ``correct``, on the CPU at the rehearsal's
    widths through BOTH kinds of page (a window of 4 pages: rows slide and
    release): the engine passes its limits; the reference with its weights
    re-rounded to float8 e4m3 stands in the program's place and fails, as does
    the reference with the shared experts knocked out or summed."""
    from benchmarks import correctness, run as run_mod, system, textgen, traffic as traffic_mod

    monkeypatch.setattr(run_mod, "WORK", tmp_path)
    monkeypatch.setattr(run_mod, "log", lambda msg: None)
    ses = run_mod.Session(CELL, 0, rehearse=True)
    tok = system.load_tokenizer(ses.build_tokenizer(), True)
    prompts = textgen.Prompts(tok)
    seed, spec = 5, ses.correctness_spec()
    engine = family.build_engine(ses.config, ses.model, ses.needs, seed)
    plan = traffic_mod.make_plan(ses.traffic, seed, 30.0)
    textgen.render_plan(plan, ses.traffic, prompts)
    ids = [tok.encode_chat(r["messages"]) for c in plan["clients"] for r in c["requests"]][:16]
    assert min(map(len, ids)) > ses.model["sliding_window"]  # every prompt slides
    args = (engine, family, ses.config, ses.model, system.weight_seed(seed), ids, seed, spec)
    readings = {c: correctness.check(*args, control=c)
                for c in (ses.config["correctness"]["precision_control"], "no_shared", "shared_sum")}
    sound = correctness.check(*args)
    print({c: r["numbers"] for c, r in readings.items()}, "sound", sound["numbers"])
    for control, got in readings.items():
        assert got["correct"] is False, control
        assert any(got["numbers"][n] > limit for n, limit in spec["limits"].items()), control
    assert sound["correct"] is True and engine.sliding_ledger.freed > 0
    assert {r["sample"] for r in readings.values()} == {sound["sample"]}
