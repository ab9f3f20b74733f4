"""The Olmo-Hybrid family of the benchmark: its configuration file against the
source's, its counts against the parameter arithmetic and one hand-computed
dispatch each, its three metrics' selections on a hand-made trace, the fp8
control, and ONE rehearsal of its cell on the CPU (two periods at tiny widths:
three to one, ``dk != dv``, six key and six value heads, kv heads = query
heads)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import olmo_hybrid as family
from benchmarks.readers import kernel_roofline, op_share, state_cache

ROOT = manifest.ROOT
CELL = "olmo-hybrid-7b-bf16.repo-sessions"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED = {  # https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_attention_heads": 30, family.KV_HEADS: 30,
    "hidden_act": "silu", "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_width_is_the_published_one_and_only_the_depth_is_cut(cell):
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 8 and config["published"]["num_hidden_layers"] == 32
    assert config["reduced"] == ["weights", "tokenizer", "num_hidden_layers"]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert "4 pipeline stages of two periods" in config["deployment"]
    assert {"norm_placement", "qk_norm", "rotary", "state", "tokenizer", "engine"} <= set(
        config["assumed"])
    model = family.model_of(config, rehearse=False)
    assert family.interval_of(model) == 4  # two whole periods of the source's layer_types
    cfg = family.model_config(model)
    assert (cfg.periods, cfg.gdn_layers, cfg.kv_layers, cfg.head_dim) == (2, 6, 2, 128)
    assert cfg.beta_max == 2.0 and cfg.num_kv_heads == cfg.num_heads == 30
    assert not cfg.expert_counters and cfg.recurrent_state
    limits = config["correctness"]["limits"]
    assert set(limits) == {"prefill_logits_rel_rms", "decode_token_gap"}
    assert config["correctness"]["precision_control"] == "fp8"
    tiny = family.model_of(config, rehearse=True)  # what the rehearsal keeps of what is new
    assert tiny["linear_key_head_dim"] != tiny["linear_value_head_dim"]
    assert tiny["linear_num_key_heads"] == tiny["linear_num_value_heads"] == 6
    assert tiny[family.KV_HEADS] == tiny["num_attention_heads"] and family.interval_of(tiny) == 4


def test_the_cell_runs_repo_sessions_as_it_stands_and_lists_what_it_reads(cell):
    other = manifest.load_cell("qwen3-next-80b-a3b-ep4-bf16.repo-sessions")
    assert cell.traffic == other.traffic and cell.traffic_name == "repo-sessions"
    engine = cell.config["engine"]
    assert engine == {"max_num_seqs": 32, "page_size": 128, "num_pages": 1280,
                      "state_snapshots": 64, "max_seq_len": 10240, "prefill_chunk": 512,
                      "decode_burst": 8}
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"] and cell.chips == 1
    mine = {"olmo_gdn_decode_roofline_frac", "olmo_gdn_prefill_roofline_frac",
            "olmo_state_pool_move_share"}
    theirs = {"gdn_decode_roofline_frac", "gdn_prefill_roofline_frac", "state_pool_move_share"}
    assert mine <= set(cell.per_layer) and not mine & set(other.per_layer)
    # everything the other hybrid's cell reports but its experts' three and its shapes' three
    assert set(cell.per_layer) - mine == {
        m for m in other.per_layer if not m.startswith("moe_") and m not in theirs}
    for name in mine:  # listed for this cell alone
        entry = next(m for m in manifest.load_manifest()["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
    cells = manifest.load_manifest()["workloads"]
    assert len(cells) == 5 and all(c["chips"] == 1 for c in cells)


def test_counts_are_the_issues_arithmetic_and_one_dispatch_by_hand(cell):
    model = family.model_of(cell.config, rehearse=False)
    d, ff, v = 3840, 11008, 100352
    gdn, attn, mlp = 88_750_332, 58_990_080, 126_812_160  # ISSUE.md's mixers and MLP
    assert family.gdn_params(model) == gdn - 252  # less A_log, dt_bias and the output norm
    assert family.attention_params(model) == attn - 2 * d  # less the two norms of 3,840
    assert family.mlp_params(model) == mlp == 3 * d * ff
    linear, full = gdn + mlp + 2 * d, attn + mlp + 2 * d
    assert (linear, full, 3 * linear + full) == (215_570_172, 185_809_920, 832_520_436)
    assert 2 * d * v == 770_703_360
    assert family.state_bytes(model) == 2_280_960 == 30 * 96 * 192 * 4 + 3 * 11520 * 2
    assert family.kv_token_bytes(model) == 30_720
    # a step streams every weight but the embedding (and the vectors the count leaves out)
    one = family.weight_bytes(model, 2.0)
    whole = 2 * (3 * linear + full) + 2 * d * v + d
    assert 0 <= 2.0 * (whole - d * v) - one < 2 * 6 * 252 + 2 * d + 1
    assert 4.0e9 < one < 4.2e9  # "weights 4.1 GB"
    assert family.weight_bytes(model, 2.0, rows=19) == one  # dense: whatever the rows
    # a burst of 8 steps over 16 live rows at 8.7k cached tokens each
    total, paged = family.burst_bytes(model, 2.0, rows=16, kv_tokens=16 * 8700, steps=8)
    assert paged == sum((16 * 8700 + 16 * i) * 30_720 for i in range(8))
    state, flops = family.gdn_decode_work(model, 16, 16 * 8700, 8)
    assert state == 16 * 6 * 8 * 2 * 2_280_960 and flops == 16 * 6 * 8 * 6 * 30 * 96 * 192
    assert total == 8 * one + paged + state
    assert 4.2e9 < paged / 8 < 4.4e9 and 0.43e9 < state / 8 < 0.45e9  # "K/V 4.3 GB" a step
    # a wave of 512 new tokens: 8 blocks of 64 a head and layer, products at 96 / 192
    nbytes, flops = family.gdn_prefill_work(model, 512, 1)
    assert flops == 6 * 512 * 30 * (2 * 64 * (3 * 96 + 2 * 192) + 8 * 96 * 192)
    assert nbytes == 6 * (512 * 30 * (2 * 96 + 2 * 192) * 4 + (8 + 1) * 2 * 30 * 96 * 192 * 4)
    pairs = family.causal_pairs(8192, 512)
    assert pairs == 512 * 8192 + 512 * 513 // 2
    whole = family.prefill_flops(model, 512, pairs, 1)
    assert whole > flops + 4.0 * 30 * 128 * 2 * pairs and 1.6e12 < whole < 2.0e12
    sizes = family.state_op_sizes(model, cell.config)
    assert (sizes["layers"], sizes["slots"], sizes["rows"]) == (6, 97, 32)
    assert (sizes["hv"], sizes["dk"], sizes["dv"], sizes["history"]) == (30, 96, 192, 34560)
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


def test_the_three_metrics_read_this_models_shapes_and_not_the_other_hybrids():
    wave = lambda t, tokens, pages, state: ["engine.prefill_batch", t, 0.01, {  # noqa: E731
        "rows": 1, "new_tokens": tokens, "cached_tokens": 8192, "pairs": 1, "completes": 1,
        "page_hit_tokens": pages, "state_hit_tokens": state, "state_restored": 0,
        "state_snapshots": 0, "state_evicted": 0}]
    burst = lambda t: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 16, "kv_tokens": 16 * 8700, "steps": 8}]
    host = [burst(0.9), wave(1.0, 88, 8192, 8192), wave(1.2, 512, 16384, 16384),
            wave(1.4, 600, 16384 + 8192, 16384), burst(1.9)]
    ops = {"multiply_reduce_fusion.12_f32_32_30_192_": 0.004, "add_select_fusion.13_f32_32_30_96_192_": 0.007,
           "slice_bitcast_fusion.3_f32_32_30_96_256_": 0.005,  # the rows read out of the 256-lane pool
           "custom-call.2_f32_32_30_96_192_": 5.0,  # the compiler's prefetch runs beside the rule: left out
           "fusion.77_f32_8_30_64_192_": 0.01, "fusion.78_f32_8_30_64_96_": 0.004,
           "copy.9_f32_1_30_96_192_": 0.002,
           "fusion.5_f32_32_1_30_192_": 5.0,  # the gated norm's output: four axes, not matched
           "fusion.6_f32_32_32_128_": 5.0,  # the other hybrid's one-token rule
           "bitcast_dynamic-update-slice_fusion.4_f32_6_97_30_96_256_": 0.003,
           "dynamic_update_slice.8_bf16_6_97_34560_": 0.001,
           "dynamic_update_slice.9_bf16_6_97_24576_": 5.0}  # the other hybrid's history pool
    ctx = _ctx(host, ops, busy=2.0)
    assert state_cache.read(ctx, "resume_share") == 100.0 * 8192 / 16384
    spec = manifest.metric_spec("olmo_gdn_decode_roofline_frac")
    got = kernel_roofline.read(ctx, **spec["args"])
    nbytes, _ = family.gdn_decode_work(ctx.model, 16, 0, 8)
    # read, two passes of the rule and the pad-and-write into the pool: 0.005 + 0.004 + 0.007 + 0.003
    assert abs(got - 100.0 * 2 * nbytes / 819e9 / 0.019) < 1e-9 and got < 100.0
    spec = manifest.metric_spec("olmo_gdn_prefill_roofline_frac")
    got = state_cache.read(ctx, **spec["args"])
    allowed = sum(max(b / 819e9, f / 197e12) for b, f in
                  (family.gdn_prefill_work(ctx.model, n, 1) for n in (88, 512, 600)))
    assert abs(got - 100.0 * allowed / 0.016) < 1e-9 and got < 100.0
    spec = manifest.metric_spec("olmo_state_pool_move_share")
    assert abs(op_share.read(ctx, **spec["args"]) - 100.0 * 0.004 / 2.0) < 1e-9
    # the other hybrid's three select nothing of this trace's own ops, and the reverse
    for name in ("gdn_decode_roofline_frac", "state_pool_move_share"):
        args = manifest.metric_spec(name)["args"]
        pattern = args.get("op") or args["pattern"]
        mine = [op for op in ops if ops[op] < 1.0]
        assert not [op for op in mine if __import__("re").search(pattern, op)], name
    # a program that writes no such counts (the parent commit, any other model) reads as nothing
    bare = _ctx([["engine.prefill_batch", 1.0, 0.01, {"rows": 1, "new_tokens": 5}]] * 2, ops)
    assert state_cache.read(bare, **manifest.metric_spec(
        "olmo_gdn_prefill_roofline_frac")["args"]) is None
    none = SimpleNamespace(trace_span=None, trace=None, family=family, peaks=None, _host_phases=None)
    assert kernel_roofline.read(none, **manifest.metric_spec(
        "olmo_gdn_decode_roofline_frac")["args"]) is None


@pytest.mark.slow
def test_rehearsal_serves_the_cell_through_the_family():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed", str(2**31 + 39),
         "--seconds", "10", "--trace", "1", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    names = set(last["metrics"])
    assert names <= set(manifest.load_cell(CELL).per_layer)
    assert {"prefix_hit_share", "state_resume_share", "decode_rows_mean"} <= names
    assert not [n for n in names if n.startswith("moe_")]  # no experts, no expert counters
    assert last["metrics"]["prefix_hit_share"]["value"] > 40.0  # topics' runs resume from snapshots
    assert last["metrics"]["state_resume_share"]["value"] > 90.0
    assert set(last["checks"]) == {"prefill_logits_rel_rms", "decode_token_gap"}


def test_the_fp8_control_is_not_correct_and_the_program_is_at_test_widths(tmp_path, monkeypatch):
    """The comparison that decides ``correct``, on the CPU at the rehearsal's
    widths: the engine passes its limits; the reference with its weights
    re-rounded to float8 e4m3 stands in the program's place and fails both."""
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
    for name, limit in spec["limits"].items():
        assert control["numbers"][name] > limit, name
    assert sound["correct"] is True and sound["sample"] == control["sample"]
