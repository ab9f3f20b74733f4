"""The Falcon-H1 family of the benchmark: its configuration file against the
source's, its counts against the issue's parameter arithmetic and one
hand-computed dispatch each, its five metrics' selections on a hand-made
trace, and the comparison that decides ``correct`` at the rehearsal's widths
with the fp8 control failing.  Pins no total of cells or metrics."""

import json
import os
import re
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import falcon_h1 as family
from benchmarks.readers import kernel_roofline, op_share, state_cache

CELL = "falcon-h1-34b-bf16.repo-sessions"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAYER, EMBED_AND_HEAD = 430_120_032, 2_673_868_800  # ISSUE.md's arithmetic
MINE = {"falcon_ssm_decode_roofline_frac", "falcon_ssm_prefill_roofline_frac",
        "falcon_state_pool_move_share", "falcon_ssm_branch_share", "falcon_attn_branch_share"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_width_is_the_published_one_and_the_cut_is_depth_alone(cell):
    config = cell.config
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (4, 72)
    for key, value in {"hidden_size": 5120, "intermediate_size": 21504, "vocab_size": 261120,
                       "num_attention_heads": 20, family.KV_HEADS: 4, "head_dim": 128,
                       "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
                       "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
                       "mamba_chunk_size": 128, "rope_theta": 100000000000,
                       "key_multiplier": 0.011048543456039804,
                       "mlp_multipliers": [0.1767766952966369, 0.011160714285714284]}.items():
        assert config[key] == value, key
    if os.path.exists(CATALOG):  # every key of the catalog's row as published, but the depth
        row = next(json.loads(ln) for ln in open(CATALOG) if "Falcon-H1-34B-Instruct" in ln)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value or key == "num_hidden_layers", key
    assert config["reduced"] == ["weights", "tokenizer", "num_hidden_layers"]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert "first of 18 pipeline stages of 4 layers" in config["deployment"]
    assert {"ssm_init", "residual", "state", "multipliers", "gains", "weights", "tokenizer",
            "engine"} <= set(config["assumed"])
    assert {"parameters", "num_hidden_layers", "arithmetic", "rows_not_taken"} <= set(config["cut"])
    model = family.model_of(config, rehearse=False)
    cfg = family.model_config(model)
    assert cfg.kv_layers == cfg.state_layers == cfg.num_layers == 4
    assert cfg.layer_segments == (("B", 4),) and cfg.recurrent_state and not cfg.expert_counters
    assert (cfg.d_inner, cfg.conv_channels, cfg.rope_theta) == (4096, 5120, 1e11)
    assert cfg.ssm_multipliers == tuple(config["ssm_multipliers"])
    shapes = cfg.state_shapes()
    assert shapes["s"][0] == (32, 128, 256) and shapes["conv"][0] == (3 * 5120,)
    # the gains the file states are the program's and the reference's
    from benchmarks import reference_falcon_h1 as ref
    from githubrepostorag_tpu.models import falcon_h1 as program

    stated = config["weights"]["gains"]
    assert stated == {f"{p[0]}.{p[1]}": g for p, _, g in program.leaf_order(cfg) if g != 1.0}
    assert stated == {n: g for n, _, g in ref.leaf_order(model) if g != 1.0}
    assert set(config["correctness"]["limits"]) == {"prefill_logits_rel_rms", "decode_token_gap"}
    assert config["correctness"]["precision_control"] == "fp8"
    tiny = family.model_of(config, rehearse=True)  # what the rehearsal keeps of what is new
    assert tiny["mamba_n_groups"] == 2 and tiny["mamba_d_state"] > tiny["mamba_d_head"]
    assert tiny["num_attention_heads"] // tiny[family.KV_HEADS] == 5


def test_the_cell_runs_repo_sessions_as_it_stands_and_lists_what_it_reads(cell):
    other = manifest.load_cell("olmo-hybrid-7b-bf16.repo-sessions")
    assert cell.traffic == other.traffic and cell.traffic_name == "repo-sessions"
    assert cell.traffic == manifest.load_cell(
        "nemotron-3-nano-30b-a3b-ep4-bf16.repo-sessions").traffic
    assert cell.config["engine"] == {
        "max_num_seqs": 32, "page_size": 128, "num_pages": 1280, "state_snapshots": 63,
        "max_seq_len": 10240, "prefill_chunk": 512, "decode_burst": 8}
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"] and cell.chips == 1
    assert MINE <= set(cell.per_layer) and not MINE & set(other.per_layer)
    # everything the other dense hybrid's cell reports, but its shapes' three
    theirs = {n for n in other.per_layer if n.startswith("olmo_")}
    assert set(cell.per_layer) - MINE == set(other.per_layer) - theirs
    manifest_ = manifest.load_manifest()
    for name in MINE:  # listed for this cell alone
        entry = next(m for m in manifest_["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert manifest.metric_spec(name)["reader"] in ("kernel_roofline", "state_cache", "op_share")
    manifest.validate(manifest_)


def test_counts_are_the_issues_arithmetic_and_one_dispatch_by_hand(cell):
    model = family.model_of(cell.config, rehearse=False)
    d, v = 5120, 261120
    assert family.attention_params(model) == 31_457_280
    assert family.ssm_params(model) == 68_351_072
    assert family.mlp_params(model) == 330_301_440
    assert family.layer_params(model) == LAYER
    assert 2 * v * d == EMBED_AND_HEAD
    assert family.total_params(model) == 4 * LAYER + EMBED_AND_HEAD + d \
        == cell.config["cut"]["parameters"]
    assert 72 * LAYER + EMBED_AND_HEAD + d == 33_642_516_224  # the published "34B"
    assert family.state_bytes(model) == 4_225_024 == 32 * 128 * 256 * 4 + 3 * 5120 * 2
    assert family.kv_token_bytes(model) == 4 * 2048
    # a step streams every layer, the final norm and the head whatever its rows
    assert family.weight_bytes(model, 2.0, rows=1) == family.weight_bytes(model, 2.0, rows=19) \
        == 2.0 * (4 * LAYER + d + d * v)
    # a burst of 8 steps over 18 live rows at 8.7k cached tokens each
    total, paged = family.burst_bytes(model, 2.0, rows=18, kv_tokens=18 * 8700, steps=8)
    assert paged == sum((18 * 8700 + 18 * i) * 8192 for i in range(8))
    assert total == 8 * family.weight_bytes(model, 2.0, 18) + paged + 18 * 4 * 8 * 2 * 4_225_024
    # ISSUE.md's step: 3.4 GB of layers, 2.7 of head, ~1.3 of K/V, ~0.6 of state
    assert 3.4e9 < 2.0 * 4 * LAYER < 3.5e9 and 2.6e9 < 2.0 * d * v < 2.7e9
    assert 1.2e9 < paged / 8 < 1.35e9 and 0.6e9 < 18 * 4 * 2 * 4_225_024 < 0.62e9
    nbytes, flops = family.ssm_decode_work(model, 18, 18 * 8700, 8)
    assert nbytes == 18 * 4 * 8 * 2 * 4_194_304 and flops == 18 * 4 * 8 * 5 * 32 * 128 * 256
    # a wave of 512 new tokens: 4 blocks of 128 a layer, at heads of 128 x 256 in 2 groups
    nbytes, flops = family.ssm_prefill_work(model, 512, 1)
    assert flops == 4 * 512 * (2 * 2 * 128 * 256 + 32 * (2 * 128 * 128 + 4 * 128 * 256))
    assert nbytes == 4 * (512 * (2 * 4096 + 2 * 512) * 4 + (4 + 1) * 2 * 32 * 128 * 256 * 4)
    pairs = family.causal_pairs(8192, 512)
    whole = family.prefill_flops(model, 512, pairs, 1)
    assert whole > flops + 4.0 * 20 * 128 * 4 * pairs and 1.7e12 < whole < 2.1e12
    sizes = family.state_op_sizes(model, cell.config)
    assert (sizes["layers"], sizes["slots"], sizes["rows"]) == (4, 96, 32)
    assert (sizes["g"], sizes["k"], sizes["mp"], sizes["n"], sizes["block"]) == (2, 16, 128, 256, 128)
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


def test_the_five_metrics_read_this_models_shapes_and_not_the_other_hybrids():
    wave = lambda t, tokens, pages, state: ["engine.prefill_batch", t, 0.01, {  # noqa: E731
        "rows": 1, "new_tokens": tokens, "cached_tokens": 8192, "pairs": 1, "completes": 1,
        "page_hit_tokens": pages, "state_hit_tokens": state, "state_restored": 0,
        "state_snapshots": 0, "state_evicted": 0}]
    burst = lambda t: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 16, "kv_tokens": 16 * 8700, "steps": 8}]
    host = [burst(0.9), wave(1.0, 88, 8192, 8192), wave(1.2, 512, 16384, 16384),
            wave(1.4, 600, 16384 + 8192, 16384), burst(1.9)]
    ssm = {"ssm_recurrent.4_f32_32_128_32_": 0.010,  # the kernel, named for its scope and for y
           "copy.9_f32_32_32_128_": 0.002,  # x turned on its side for it
           "fusion.77_f32_1_1_2_16_128_128_": 0.01, "fusion.78_f32_4_2_128_128_": 0.004,
           "multiply_reduce_fusion.3_f32_4_128_2_": 0.003,  # the masked scores times dt x
           "fusion.647_f32_32_4096_": 0.03, "fusion.648_bf16_32_1_5120_": 0.04,  # in_proj
           "constant_dynamic-update-slice_fusion.2_f32_4_96_32_128_256_": 0.003,  # a wave's row
           "dynamic_update_slice.8_bf16_4_96_15360_": 0.001,
           # the burst's shift of a layer's rows of history: the branch's work, but no move
           "select_dynamic-update-slice_fusion.9_bf16_4_96_15360_": 0.005}
    attn = {"paged_attention.5_bf16_32_4_5_128_": 0.2, "fusion.642_f32_32_1_3584_": 0.02,
            "fused_window_attention.2_bf16_1_4_5_512_128_": 0.01,
            "fusion.549_bf16_4_4_1280_128_128_": 0.002}
    other = {"custom-call.2_f32_32_32_128_256_": 5.0,  # a prefetch beside the rule: left out
             "fusion.653_f32_32_21504_": 5.0, "multiply_reduce_fusion.7_f32_32_": 5.0,  # the SwiGLU
             "fusion.9_f32_32_261120_": 5.0,  # the head
             "ssm_recurrent.80_f32_32_64_64_": 5.0,  # Nemotron-H's kernel (its own metric's)
             "dynamic_update_slice.9_bf16_8_96_18432_": 5.0}  # and its history pool
    ops = {**ssm, **attn, **other}
    ctx = _ctx(host, ops, busy=2.0)
    assert state_cache.read(ctx, "resume_share") == 100.0 * 8192 / 16384
    got = kernel_roofline.read(ctx, **manifest.metric_spec("falcon_ssm_decode_roofline_frac")["args"])
    nbytes, _ = family.ssm_decode_work(ctx.model, 16, 0, 8)
    # the kernel's calls and the copies it forces; Nemotron-H's kernel carries the same scope's name
    assert abs(got - 100.0 * 2 * nbytes / 819e9 / (0.010 + 0.002 + 5.0)) < 1e-9
    ctx.trace["per_op"].pop("ssm_recurrent.80_f32_32_64_64_")
    got = kernel_roofline.read(ctx, **manifest.metric_spec("falcon_ssm_decode_roofline_frac")["args"])
    assert abs(got - 100.0 * 2 * nbytes / 819e9 / 0.012) < 1e-9 and got < 100.0
    got = state_cache.read(ctx, **manifest.metric_spec("falcon_ssm_prefill_roofline_frac")["args"])
    allowed = sum(max(b / 819e9, f / 197e12) for b, f in
                  (family.ssm_prefill_work(ctx.model, n, 1) for n in (88, 512, 600)))
    assert abs(got - 100.0 * allowed / 0.017) < 1e-9 and got < 100.0
    spec = manifest.metric_spec("falcon_state_pool_move_share")
    assert abs(op_share.read(ctx, **spec["args"]) - 100.0 * 0.004 / 2.0) < 1e-9
    spec = manifest.metric_spec("falcon_ssm_branch_share")
    assert abs(op_share.read(ctx, **spec["args"]) - 100.0 * sum(ssm.values()) / 2.0) < 1e-9
    spec = manifest.metric_spec("falcon_attn_branch_share")
    assert abs(op_share.read(ctx, **spec["args"]) - 100.0 * sum(attn.values()) / 2.0) < 1e-9
    # the other hybrids' metrics select nothing of this trace's own ops
    for name in ("gdn_decode_roofline_frac", "state_pool_move_share", "ssm_state_pool_move_share",
                 "olmo_gdn_decode_roofline_frac", "olmo_state_pool_move_share"):
        args = manifest.metric_spec(name)["args"]
        pattern = args.get("op") or args["pattern"]
        assert not [op for op in (*ssm, *attn) if re.search(pattern, op)
                    and not op.startswith("ssm_recurrent")], name
    # a program that writes no such counts (the parent commit, any other model) reads as nothing
    bare = _ctx([["engine.prefill_batch", 1.0, 0.01, {"rows": 1, "new_tokens": 5}]] * 2, ops)
    assert state_cache.read(bare, **manifest.metric_spec(
        "falcon_ssm_prefill_roofline_frac")["args"]) is None
    none = SimpleNamespace(trace_span=None, trace=None, family=family, peaks=None, _host_phases=None)
    assert kernel_roofline.read(none, **manifest.metric_spec(
        "falcon_ssm_decode_roofline_frac")["args"]) is None
    assert op_share.read(none, **manifest.metric_spec("falcon_ssm_branch_share")["args"]) is None


def test_the_fp8_control_is_not_correct_and_the_program_is_at_test_widths(tmp_path, monkeypatch):
    """The comparison that decides ``correct``, on the CPU at the rehearsal's
    widths: the engine passes its limits; the reference with its weights
    re-rounded to float8 e4m3 stands in the program's place and fails, as does
    the reference with the Mamba-2 branch zeroed."""
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
    args = (engine, family, ses.config, ses.model, system.weight_seed(seed), ids, seed, spec)
    control = correctness.check(*args, control=ses.config["correctness"]["precision_control"])
    knocked = correctness.check(*args, control="no_ssm")
    sound = correctness.check(*args)
    print("control", control["numbers"], "no_ssm", knocked["numbers"], "sound", sound["numbers"])
    assert control["correct"] is False and knocked["correct"] is False
    assert any(control["numbers"][name] > limit for name, limit in spec["limits"].items())
    assert sound["correct"] is True and sound["sample"] == control["sample"]
