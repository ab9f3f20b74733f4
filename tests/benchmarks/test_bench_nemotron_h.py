"""The Nemotron-H family of the benchmark: its configuration file against the
source's, its counts against the parameter arithmetic and one hand-computed
dispatch each, its three metrics' selections on a hand-made trace, the fp8
control, and ONE rehearsal of its cell on the CPU (twelve one-sublayer blocks
at tiny widths: the three kinds, groups of heads, half the experts held)."""

import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import nemotron_h as family
from benchmarks.readers import kernel_roofline, moe_counters, op_share, state_cache

ROOT = manifest.ROOT
CELL = "nemotron-3-nano-30b-a3b-ep4-bf16.repo-sessions"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = {  # the source's config.json, less the keys that say nothing about its shape
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, family.KV_HEADS: 2,
    "partial_rotary_factor": 1, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True}
CUT = {"num_hidden_layers": (18, 52), "n_routed_experts": (32, 128), "vocab_size": (32768, 131072)}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_width_is_the_published_one_and_the_cut_is_the_issues(cell):
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    for key, (here, published) in CUT.items():
        assert config[key] == here and config["published"][key] == published, key
    if os.path.exists(CATALOG):  # every number of the catalog's row, but the three that are cut
        row = next(json.loads(ln) for ln in open(CATALOG) if "Nemotron-3-Nano-30B" in ln)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value or key in CUT, key
    assert config["reduced"] == ["weights", "tokenizer", *CUT]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert config["router_width"] == 128 and config["experts_held"] == [0, 32]
    assert "rank 0 of 4 chips that share each layer" in config["deployment"]
    assert {"rotary", "gated_norm", "d_inner", "ssm_init", "state", "residual", "router",
            "weights", "tokenizer", "engine"} <= set(config["assumed"])
    model = family.model_of(config, rehearse=False)
    assert family.kinds(model) == "MEMEM*EMEMEM*EMEME" and model["n_routed_experts"] == 128
    cfg = family.model_config(model)
    assert (cfg.state_layers, cfg.expert_layers, cfg.kv_layers, cfg.num_layers) == (8, 8, 2, 18)
    assert (cfg.d_inner, cfg.conv_channels, cfg.n_held, cfg.num_experts) == (4096, 6144, 32, 128)
    assert cfg.expert_counters and cfg.recurrent_state and cfg.rms_norm_eps == 1e-5
    shapes = cfg.state_shapes()
    assert shapes["s"][0] == (64, 64, 128) and shapes["conv"][0] == (3 * 6144,)
    limits = config["correctness"]["limits"]
    assert set(limits) == {"prefill_logits_rel_rms", "decode_token_gap"}
    assert config["correctness"]["precision_control"] == "fp8"
    tiny = family.model_of(config, rehearse=True)  # what the rehearsal keeps of what is new
    assert set(family.kinds(tiny)) == {"M", "E", "*"} and tiny["n_groups"] > 1
    assert tiny["experts_held"] == [0, 8] and tiny["n_routed_experts"] == 16


def test_the_cell_runs_repo_sessions_as_it_stands_and_lists_what_it_reads(cell):
    other = manifest.load_cell("qwen3-next-80b-a3b-ep4-bf16.repo-sessions")
    assert cell.traffic == other.traffic and cell.traffic_name == "repo-sessions"
    engine = cell.config["engine"]
    assert engine == {"max_num_seqs": 32, "page_size": 128, "num_pages": 2048,
                      "state_snapshots": 63, "max_seq_len": 10240, "prefill_chunk": 512,
                      "decode_burst": 8}
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"] and cell.chips == 1
    mine = {"ssm_decode_roofline_frac", "ssm_prefill_roofline_frac", "ssm_state_pool_move_share"}
    theirs = {"gdn_decode_roofline_frac", "gdn_prefill_roofline_frac", "state_pool_move_share"}
    assert mine <= set(cell.per_layer) and not mine & set(other.per_layer)
    # everything the other expert hybrid's cell reports, its experts' three too, but its shapes'
    assert set(cell.per_layer) - mine == set(other.per_layer) - theirs
    manifest_ = manifest.load_manifest()
    for name in mine:  # listed for this cell alone, at the end of the list
        entry = next(m for m in manifest_["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
    assert {m["name"] for m in manifest_["per_layer"][-3:]} == mine
    assert manifest_["workloads"][-1]["name"] == CELL and manifest_["configs"][-1]["name"] == \
        cell.config_name
    manifest.validate(manifest_)


def test_counts_are_the_issues_arithmetic_and_one_dispatch_by_hand(cell):
    model = family.model_of(cell.config, rehearse=False)
    d, v = 2688, 32768
    ssm, attn, expert, shared = 38_744_896, 23_399_040, 9_977_856, 19_955_712  # ISSUE.md's
    # less A_log, dt_bias, D, the gated norm and the block norm, which are no matrices
    assert family.ssm_params(model) == ssm - 192 - 4096 - d
    assert family.attention_params(model) == attn - d
    assert family.expert_params(model) == expert == 2 * d * 1856
    e_layer = 32 * expert + shared + d * 128 + 128 + d
    assert e_layer == 339_593_984
    total = 8 * ssm + 2 * attn + 8 * e_layer + 2 * v * d + d
    assert total == 3_249_672_576  # "3,250 M parameters, 6.50 GB"
    assert family.state_bytes(model) == 2_134_016 == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert family.kv_token_bytes(model) == 2048
    # a step streams the mixers, the shared parts and the head whatever its rows, and of the
    # routed experts only those a row hit: every one at many rows, k of them at one
    base = 2.0 * (8 * (ssm - 192 - 4096 - d) + 2 * (attn - d) + 8 * (shared + d * 128 + 128)
                  + 19 * d + d * v)
    assert family.weight_bytes(model, 2.0, rows=1e6) == pytest.approx(base + 2.0 * 8 * 32 * expert)
    assert family.experts_hit(model, 1) == pytest.approx(32 * 6 / 128)
    assert family.experts_hit(model, 19) == pytest.approx(32 * (1 - (1 - 6 / 128) ** 19))
    assert 0.58 < family.experts_hit(model, 19) / 32 < 0.62  # "~60% of the held experts"
    # a burst of 8 steps over 19 live rows at 8.7k cached tokens each
    total, paged = family.burst_bytes(model, 2.0, rows=19, kv_tokens=19 * 8700, steps=8)
    assert paged == sum((19 * 8700 + 19 * i) * 2048 for i in range(8))
    state, flops = family.ssm_decode_work(model, 19, 19 * 8700, 8)
    assert state == 19 * 8 * 8 * 2 * 2_134_016 and flops == 19 * 8 * 8 * 5 * 64 * 64 * 128
    assert total == 8 * family.weight_bytes(model, 2.0, 19) + paged + state
    assert 0.33e9 < paged / 8 < 0.35e9 and 0.64e9 < state / 8 < 0.66e9  # ISSUE.md's step
    assert 4.0e9 < family.weight_bytes(model, 2.0, 19) < 4.4e9
    # a wave of 512 new tokens: 4 blocks of 128 a layer
    nbytes, flops = family.ssm_prefill_work(model, 512, 1)
    assert flops == 8 * 512 * (8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 64 * 128))
    assert nbytes == 8 * (512 * (2 * 4096 + 2 * 1024) * 4 + (4 + 1) * 2 * 64 * 64 * 128 * 4)
    pairs = family.causal_pairs(8192, 512)
    whole = family.prefill_flops(model, 512, pairs, 1)
    assert whole > flops + 4.0 * 32 * 128 * 2 * pairs and 0.6e12 < whole < 1.2e12
    sizes = family.state_op_sizes(model, cell.config)
    assert (sizes["layers"], sizes["slots"], sizes["rows"]) == (8, 96, 32)
    assert (sizes["g"], sizes["k"], sizes["mp"], sizes["n"], sizes["block"]) == (8, 8, 64, 128, 128)
    assert family.expert_op_sizes(model, cell.config) == {
        "tile_rows": 32, "gate_up": 1856, "hidden": 2688}
    assert family.work.bytes_per_weight(cell.config) == 2.0
    assert family.work.expert_bytes(model, 2.0) == 2 * expert


def _ctx(host, per_op=None, busy=1.0):
    plain = {"devices": {"0": {"ops": [], "modules": [["jit_decode_burst(1)", 1.0, 0.1],
                                                      ["jit_decode_burst(1)", 2.0, 0.1]]}},
             "host": host}
    config = manifest.load_cell(CELL).config
    return SimpleNamespace(
        _host_phases=plain, trace={"per_op": per_op or {}, "busy_first_s": busy},
        trace_span=(0.0, 9.0), family=family, model=family.model_of(config, rehearse=False),
        config=config, chips=1, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_the_three_metrics_read_this_models_shapes_and_not_the_other_hybrids():
    wave = lambda t, tokens, pages, state: ["engine.prefill_batch", t, 0.01, {  # noqa: E731
        "rows": 1, "new_tokens": tokens, "cached_tokens": 8192, "pairs": 1, "completes": 1,
        "page_hit_tokens": pages, "state_hit_tokens": state, "state_restored": 0,
        "state_snapshots": 0, "state_evicted": 0}]
    burst = lambda t, hit, toks, slots: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 16, "kv_tokens": 16 * 8700, "steps": 8, "experts_hit": hit,
        "expert_tokens": toks, "expert_slots": slots}]
    host = [burst(0.9, 0, 0, 0), wave(1.0, 88, 8192, 8192), wave(1.2, 512, 16384, 16384),
            wave(1.4, 600, 16384 + 8192, 16384), burst(1.9, 1200, 1500, 2048)]
    ops = {"multiply_reduce_fusion.12_f32_32_64_64_": 0.004,  # S C off the pool's rows
           "select_dynamic-update-slice_fusion.13_f32_8_96_64_64_128_": 0.007,  # the update, in place
           "broadcast_bitcast_fusion.3_f32_32_64_128_": 0.001,
           "custom-call.2_f32_32_64_64_128_": 5.0,  # a prefetch beside the rule: left out
           "ssm_recurrent.4": 0.002,  # a kernel named after the scope: read
           "fusion.77_f32_4_8_8_8_128_64_": 0.01, "fusion.78_f32_4_8_8_128_128_": 0.004,
           "copy.9_f32_8_4_128_8_8_64_": 0.002, "ssm_chunked.5": 0.001,
           "fusion.5_f32_32_1_4096_": 5.0,  # the gated norm's output: not the rule's
           "fusion.6_f32_32_32_128_": 5.0,  # Qwen3-Next's one-token rule
           "fusion.1152_f32_8_96_64_64_128_": 0.003,  # a wave's row written into the pool
           "dynamic_update_slice.8_bf16_8_96_18432_": 0.001,
           "select_dynamic-update-slice_fusion.9_bf16_8_96_18432_": 5.0,  # the history shifted: computes
           "dynamic_update_slice.9_bf16_6_97_24576_": 5.0,  # another hybrid's history pool
           "fusion.21_bf16_32_1856_": 0.025, "fusion.22_f32_32_2688_": 0.015}
    ctx = _ctx(host, ops, busy=2.0)
    assert state_cache.read(ctx, "resume_share") == 100.0 * 8192 / 16384
    spec = manifest.metric_spec("ssm_decode_roofline_frac")
    got = kernel_roofline.read(ctx, **spec["args"])
    nbytes, _ = family.ssm_decode_work(ctx.model, 16, 0, 8)
    # both passes, the broadcast and the kernel: 0.004 + 0.007 + 0.001 + 0.002
    assert abs(got - 100.0 * 2 * nbytes / 819e9 / 0.014) < 1e-9 and got < 100.0
    spec = manifest.metric_spec("ssm_prefill_roofline_frac")
    got = state_cache.read(ctx, **spec["args"])
    allowed = sum(max(b / 819e9, f / 197e12) for b, f in
                  (family.ssm_prefill_work(ctx.model, n, 1) for n in (88, 512, 600)))
    assert abs(got - 100.0 * allowed / 0.017) < 1e-9 and got < 100.0
    spec = manifest.metric_spec("ssm_state_pool_move_share")
    assert abs(op_share.read(ctx, **spec["args"]) - 100.0 * 0.004 / 2.0) < 1e-9
    # the accepted experts' metric reads the two products of a two-matrix expert
    got = moe_counters.read(ctx, **manifest.metric_spec("moe_experts_hbm_frac")["args"])
    assert abs(got - 100.0 * 1200 * 9_977_856 * 2.0 / (0.040 * 819e9)) < 1e-9 and got < 100.0
    assert moe_counters.read(ctx, "hit_share") == 100.0 * 1200 / 2048
    # the other hybrids' metrics select nothing of this trace's own ops
    mine = [op for op in ops if ops[op] < 1.0]
    for name in ("gdn_decode_roofline_frac", "state_pool_move_share",
                 "olmo_gdn_decode_roofline_frac", "olmo_state_pool_move_share"):
        args = manifest.metric_spec(name)["args"]
        pattern = args.get("op") or args["pattern"]
        assert not [op for op in mine if re.search(pattern, op)], name
    # a program that writes no such counts (the parent commit, any other model) reads as nothing
    bare = _ctx([["engine.prefill_batch", 1.0, 0.01, {"rows": 1, "new_tokens": 5}]] * 2, ops)
    assert state_cache.read(bare, **manifest.metric_spec(
        "ssm_prefill_roofline_frac")["args"]) is None
    none = SimpleNamespace(trace_span=None, trace=None, family=family, peaks=None, _host_phases=None)
    assert kernel_roofline.read(none, **manifest.metric_spec(
        "ssm_decode_roofline_frac")["args"]) is None


@pytest.mark.slow
def test_rehearsal_serves_the_cell_through_the_family():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed", str(2**31 + 41),
         "--seconds", "10", "--trace", "1", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    names = set(last["metrics"])
    assert names <= set(manifest.load_cell(CELL).per_layer)
    assert {"prefix_hit_share", "state_resume_share", "decode_rows_mean", "moe_experts_hit_share",
            "moe_tokens_per_expert_mean"} <= names
    assert last["metrics"]["prefix_hit_share"]["value"] > 40.0  # topics' runs resume from snapshots
    assert last["metrics"]["state_resume_share"]["value"] > 90.0
    assert set(last["checks"]) == {"prefill_logits_rel_rms", "decode_token_gap"}


def test_the_fp8_control_is_not_correct_and_the_program_is_at_test_widths(tmp_path, monkeypatch):
    """The comparison that decides ``correct``, on the CPU at the rehearsal's
    widths: the engine passes its limits; the reference with its weights
    re-rounded to float8 e4m3 stands in the program's place and fails at least
    one."""
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
    print("control", control["numbers"])
    sound = correctness.check(*args)
    print("sound", sound["numbers"])
    assert control["correct"] is False
    assert any(control["numbers"][name] > limit for name, limit in spec["limits"].items())
    assert sound["correct"] is True and sound["sample"] == control["sample"]
