"""The Mellum family of the benchmark: its configuration file against the
catalog's row, its counts against the issue's parameter arithmetic and one
hand-computed dispatch each, the two metrics this cell brings on a hand-made
trace, the cell's lists, a rehearsal of the cell through ``benchmarks.run``,
and the comparison that decides ``correct`` at the rehearsal's widths with the
controls that those widths can show failing.  Pins no total of cells,
configurations or metrics."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import mellum as family
from benchmarks.readers import moe_counters, moe_load, op_share, sliding_pages

ROOT = manifest.ROOT
CELL = "mellum2-12b-a2.5b-bf16.repo-longctx"
PAIR = "command-a-plus-ep8-bf16.repo-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the four readings of the sliding kind under names of this cell's own: the accepted lists are
# pinned to Command A+'s cell alone by a test this PR may not edit (test_bench_cohere2_moe.py)
TWINS = {"mellum_sliding_attn_roofline_frac": "sliding_attn_roofline_frac",
         "mellum_sliding_prefill_attn_roofline_frac": "sliding_prefill_attn_roofline_frac",
         "mellum_sliding_hit_share": "sliding_hit_share",
         "mellum_sliding_burst_hbm_frac": "sliding_burst_hbm_frac"}
MINE = {"moe_expert_load_max_over_mean", "mellum_pool_move_share", *TWINS}
ATTENTION, EXPERT, LAYER = 21_233_664, 6_193_152, 417_747_712  # ISSUE.md's arithmetic


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_key_is_the_published_one_and_the_cut_is_in_depth_alone(cell):
    config = cell.config
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (8, 28)
    if os.path.exists(CATALOG):  # every key of the catalog's row as published, but the depth
        row = next(json.loads(ln) for ln in open(CATALOG) if "Mellum2-12B-A2.5B" in ln)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value or key == "num_hidden_layers", key
    assert config["reduced"] == ["weights", "tokenizer", "num_hidden_layers", "mtp"]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert (config["num_experts"], config["experts_held"], config["num_experts_per_tok"],
            config["vocab_size"]) == (64, [0, 64], 8, 98304)
    assert "8 + 8 + 8 + 4" in config["deployment"] and "8 of 28" in config["cut"]["num_hidden_layers"]
    assert {"qk_norm", "window", "rotary", "router", "weights", "tokenizer", "precision"} \
        <= set(config["assumed"])
    assert {"num_hidden_layers", "mtp", "arithmetic", "per_expert_tokens"} <= set(config["cut"])
    assert "12.15 B" in config["published"]["parameters"]
    eng = config["engine"]
    assert (eng["num_pages"], eng["sliding_pages"], eng["max_seq_len"]) == (2560, 1024, 26624)
    assert "1,864 global pages" in config["pools"]["working_set"]
    assert "405 sliding pages" in config["pools"]["working_set"]
    model = family.model_of(config, rehearse=False)
    cfg = family.model_config(model)
    assert cfg.page_kinds == (("global", 2, None), ("sliding", 6, 1024))
    assert cfg.period == ("sliding", "sliding", "sliding", "global") == family.period_of(model)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.n_held, cfg.moe_intermediate_size) \
        == (32, 4, 128, 64, 896)
    assert (cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_max, cfg.attention_factor) \
        == (500000.0, 16.0, 8192, 1.2772588722239782)
    assert cfg.expert_counters and cfg.step_programs.endswith("mellum")
    assert set(config["correctness"]["limits"]) == {"prefill_logits_rel_rms", "decode_token_gap"}
    assert config["correctness"]["precision_control"] == "fp8"
    assert config["correctness"]["knock_outs"] == [
        "no_window", "one_rope", "no_attention_factor", "no_topk_norm", "no_qk_norm"]
    tiny = family.model_of(config, rehearse=True)  # what the rehearsal keeps of what is new
    assert tiny["sliding_window"] == 64 and tiny["rope_parameters"]["full_attention"]["factor"] == 8
    assert family.model_config(tiny).page_kinds == (("global", 1, None), ("sliding", 3, 64))
    with pytest.raises(manifest.ManifestError, match="whole periods"):
        family.period_of({**model, "num_hidden_layers": 10})


def test_the_cell_runs_repo_longctx_as_it_stands_and_lists_what_it_can_read(cell):
    pair = manifest.load_cell(PAIR)
    assert cell.traffic == pair.traffic and cell.traffic_name == "repo-longctx" and cell.chips == 1
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"]
    assert MINE <= set(cell.per_layer) and not MINE & set(pair.per_layer)
    # everything Command A+'s cell reports: the guard pinned to ITS pools' shapes as a metric of
    # this cell's shapes, the four sliding readings as twins (same reader, same arguments)
    assert set(pair.per_layer) - set(cell.per_layer) == {"sliding_pool_move_share", *TWINS.values()}
    for mine, accepted in TWINS.items():
        a, b = manifest.metric_spec(mine), manifest.metric_spec(accepted)
        assert a == b
    assert {"paged_attn_hbm_frac", "moe_experts_hbm_frac", "moe_experts_hit_share",
            "moe_tokens_per_expert_mean", "prefill_flops_frac", "cycle_burst_share",
            "cycle_wave_share", "cycle_other_share", "cycle_gap_share"} <= set(cell.per_layer)
    assert not {"decode_hbm_frac", "burst_hbm_frac"} & set(cell.per_layer)
    manifest_ = manifest.load_manifest()
    for name in MINE:  # listed for this cell alone
        entry = next(m for m in manifest_["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
    assert manifest.metric_spec("moe_expert_load_max_over_mean")["reader"] == "moe_load"
    why = next(w["why"] for w in manifest_["workloads"] if w["name"] == CELL)
    assert PAIR in why and "2.4 tokens" in why and len(why) <= 200
    manifest.validate(manifest_)


def test_counts_are_the_issues_arithmetic_and_one_dispatch_by_hand(cell):
    model = family.model_of(cell.config, rehearse=False)
    d, v = 2304, 98304
    assert family.attention_params(model) == ATTENTION and family.expert_params(model) == EXPERT
    assert family.beside_params(model) + 64 * EXPERT == LAYER
    # the published model: 28 layers of 64 experts, and 8 of them a token
    whole = 28 * LAYER + d + 2 * v * d
    active = 28 * (family.beside_params(model) + 8 * EXPERT) + d + 2 * v * d
    assert round(whole / 1e9, 2) == 12.15 and round(active / 1e9, 2) == 2.44
    assert 8 * LAYER + d + 2 * v * d == 3_794_968_832  # this chip: 7.59 GB
    assert family.key_bytes(model) == 2048 and family.key_flops(model) == 16384
    # a step streams a layer's attention, router, norms and the experts hit, and the head
    hit = family.experts_hit(model, 19)
    assert 58.8 < hit < 59.0  # an even router wakes 92% of 64 at 19 rows
    fixed = 2.0 * (8 * family.beside_params(model) + d + d * v)
    assert family.fixed_weight_bytes(model, 2.0) == fixed
    assert family.weight_bytes(model, 2.0, rows=19) == fixed + 2.0 * 8 * hit * EXPERT
    assert 8.7e9 < 2.0 * 12 * hit * EXPERT < 8.8e9  # ISSUE.md: 8.75 GB of experts a step at 12 layers
    # a burst of 8 steps over 19 live rows at 25,100 cached tokens each
    kv = 19 * 25100
    total, paged = family.burst_bytes(model, 2.0, rows=19, kv_tokens=kv, steps=8)
    assert paged == sum((kv + 19 * i) * 2048 * 2 for i in range(8))  # the two global layers
    walked = sum(min(19 * 1023 + 19 * i, 19 * 1024) for i in range(8)) * 2048 * 6
    assert total == 8 * family.weight_bytes(model, 2.0, 19) + paged + walked
    assert 1.9e9 < paged / 8 < 2.0e9 and 0.23e9 < walked / 8 < 0.25e9  # ISSUE.md: 2.93 and 0.36 at 12
    # the same burst from the engine's own counts: 80% of the 64 x 8 x 8 slots it offered hit
    counted = family.burst_counted_bytes(model, 2.0, 19, kv, 19 * 1023, 8, 0.80)
    assert counted == 8 * fixed + paged + walked + 0.80 * 64 * 8 * 8 * EXPERT * 2.0
    assert counted < total
    nbytes, flops = family.sliding_attention_work(model, 19, 19 * 1023, 8)
    assert (nbytes, flops) == (walked, walked / 2048 * 16384.0)
    assert nbytes / 819e9 > flops / 197e12  # the burst's kernel is bound by bytes: 8 B a FLOP
    nbytes, flops = family.sliding_prefill_work(model, 512 * 1024, 1023 + 512)
    assert (nbytes, flops) == ((1023 + 512) * 2048 * 6, 512 * 1024 * 16384.0 * 6)
    assert flops / 197e12 > nbytes / 819e9  # the wave's by operations
    pairs = family.causal_pairs(24576, 512)
    assert family.prefill_flops(model, 512, pairs, 1) > 16384.0 * (2 * pairs + 6 * 512 * 1024)
    assert family.expert_op_sizes(model, cell.config) == {
        "tile_rows": 32, "gate_up": 1792, "hidden": 2304}
    assert family.work.bytes_per_weight(cell.config) == 2.0
    assert family.work.expert_bytes(model, 2.0) == 2.0 * EXPERT


def _ctx(host, per_op=None, busy=1.0):
    plain = {"devices": {"0": {"ops": [], "modules": [["jit_decode_burst(1)", 1.0, 0.1],
                                                      ["jit_decode_burst(1)", 2.0, 0.1]]}},
             "host": host}
    config = manifest.load_cell(CELL).config
    return SimpleNamespace(
        _host_phases=plain, trace={"per_op": per_op or {}, "busy_first_s": busy},
        trace_span=(0.0, 9.0), family=family, model=family.model_of(config, rehearse=False),
        config=config, chips=1, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_the_load_metric_reads_the_third_count_and_nothing_on_a_program_without_it():
    burst = lambda t, hit, pairs, fullest, slots, **kw: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 19, "kv_tokens": 19 * 25100, "steps": 8, "sliding_tokens": 19 * 1023,
        "experts_hit": hit, "expert_tokens": pairs, "expert_slots": slots,
        **({"experts_max_pairs": fullest} if fullest is not None else {}), **kw}]
    # between the trace's first and last burst: 20 bursts of 8 steps over 8 layers (1,280 layer
    # steps), 19 rows x 8 pairs each = 291,840 pairs; the fullest expert held 5.2 pairs a layer
    # step where the mean expert held 152 / 64 = 2.375
    steps = 20 * 8 * 8
    host = [burst(0.9, 1000, 5000, 700, 6144),
            burst(1.9, 1000 + int(0.9 * 64 * steps), 5000 + 152 * steps, 700 + int(5.2 * steps),
                  6144 + 64 * steps)]
    ops = {"copy.3_bf16_6_4_1024_128_128_": 0.02,  # what the guard is for: the sliding pool copied
           "fusion.12_bf16_6_4_8192_16_128_": 5.0,  # a commit's windows, in place: no move
           "copy.3_bf16_3_8_1024_128_128_": 5.0}  # Command A+'s pool: another cell's guard
    ctx = _ctx(host, ops, busy=2.0)
    args = lambda name: manifest.metric_spec(name)["args"]  # noqa: E731
    got = moe_load.read(ctx, **args("moe_expert_load_max_over_mean"))
    assert abs(got - int(5.2 * steps) * 64 / (152 * steps)) < 1e-9 and 2.1 < got < 2.2
    assert abs(moe_counters.read(ctx, **args("moe_experts_hit_share")) - 90.0) < 0.1
    assert abs(op_share.read(ctx, **args("mellum_pool_move_share")) - 100.0 * 0.02 / 2.0) < 1e-9
    ctx.trace["per_op"]["dynamic-update-slice.4_bf16_4_327680_128_"] = 0.04  # a global layer whole
    assert abs(op_share.read(ctx, **args("mellum_pool_move_share")) - 100.0 * 0.06 / 2.0) < 1e-9
    # the accepted whole-burst share takes this family's counts: both bursts matched a module
    # event of 0.1 s
    got = sliding_pages.read(ctx, **args("mellum_sliding_burst_hbm_frac"))
    one = family.burst_counted_bytes(ctx.model, 2.0, 19, 19 * 25100, 19 * 1023, 8, 0.9)
    assert abs(got - 100.0 * 2 * one / 819e9 / 0.2) < 1e-6
    # a program that writes no third count (the parent commit, any other family) reads as nothing
    bare = _ctx([burst(0.9, 1000, 5000, None, 6144), burst(1.9, 2000, 9000, None, 9000)], ops)
    assert moe_load.read(bare, **args("moe_expert_load_max_over_mean")) is None
    assert moe_counters.read(bare, **args("moe_experts_hit_share")) is not None
    none = SimpleNamespace(trace_span=None, trace=None, family=family, peaks=None,
                           _host_phases=None, model=ctx.model)
    assert moe_load.read(none, **args("moe_expert_load_max_over_mean")) is None
    assert op_share.read(none, **args("mellum_pool_move_share")) is None
    with pytest.raises(ValueError):
        moe_load.read(ctx, what="other")


def test_a_rehearsal_of_the_cell_prints_the_contracts_line_with_the_new_metric():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed", str(2**31 + 23),
         "--seconds", "5", "--trace", "1", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    names = set(last["metrics"])
    assert names <= set(manifest.load_cell(CELL).per_layer)
    assert {"moe_expert_load_max_over_mean", "moe_experts_hit_share", "mellum_sliding_hit_share",
            "prefix_hit_share", "tpot_server_p50_ms"} <= names
    assert 1.0 <= last["metrics"]["moe_expert_load_max_over_mean"]["value"] <= 8.0


def test_the_controls_these_widths_can_show_are_not_correct_and_the_program_is(tmp_path,
                                                                               monkeypatch):
    """The comparison that decides ``correct``, on the CPU at the rehearsal's
    widths through BOTH kinds of page: the engine passes its limits; the
    reference with its weights re-rounded to float8 e4m3 stands in the
    program's place and fails, as does the reference with the window, the
    second table or the per-head norm knocked out.  (The two knock-outs of a
    scale, ``no_attention_factor`` and ``no_topk_norm``, need the published
    widths or tests/test_mellum.py's gain to show: the chip's controls and that
    file hold them.)"""
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
                for c in (ses.config["correctness"]["precision_control"], "no_window", "one_rope",
                          "no_qk_norm")}
    sound = correctness.check(*args)
    print({c: r["numbers"] for c, r in readings.items()}, "sound", sound["numbers"])
    for control, got in readings.items():
        assert got["correct"] is False, control
        assert any(got["numbers"][n] > limit for n, limit in spec["limits"].items()), control
    assert sound["correct"] is True
    assert {r["sample"] for r in readings.values()} == {sound["sample"]}
