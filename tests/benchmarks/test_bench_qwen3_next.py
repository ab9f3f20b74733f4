"""The Qwen3-Next family of the benchmark: its configuration file against
the source's, its counts against one hand-computed dispatch each, its new
readers on a hand-made trace, the fp8 control, and ONE rehearsal of its cell
on the CPU (two periods at tiny widths, 16 experts of which 8 are held)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import qwen3_next as family
from benchmarks.readers import kernel_roofline, op_share, state_cache

ROOT = manifest.ROOT
CELL = "qwen3-next-80b-a3b-ep4-bf16.repo-sessions"
PUBLISHED = {  # https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 10, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_width_is_the_published_one_and_every_cut_is_listed(cell):
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    cut = {"num_hidden_layers": (8, 48), "num_experts": (128, 512), "vocab_size": (37984, 151936)}
    for key, (here, source) in cut.items():
        assert config[key] == here and config["published"][key] == source and key in config["reduced"]
    assert config["router_width"] == 512 and config["experts_held"] == [0, 128]
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0  # whole periods
    assert "4 chips share each layer" in config["deployment"]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and "mtp" in entry["reduced"]
    assert entry["source"] == config["source"]
    model = family.model_of(config, rehearse=False)
    assert model["num_experts"] == 512 and model["experts_held"] == [0, 128]
    limits = config["correctness"]["limits"]
    assert set(limits) == {"prefill_logits_rel_rms", "decode_token_gap"}


def test_the_cell_runs_deepseeks_traffic_file_as_it_stands(cell):
    other = manifest.load_cell("deepseek-v3-ep16-bf16.repo-sessions")
    assert cell.traffic == other.traffic and cell.traffic_name == "repo-sessions"
    engine = cell.config["engine"]
    assert engine["num_pages"] * engine["page_size"] == 262144 and engine["max_seq_len"] == 10240
    assert (engine["max_num_seqs"], engine["state_snapshots"]) == (32, 64)
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"] and cell.chips == 1
    mine = {"gdn_decode_roofline_frac", "gdn_prefill_roofline_frac", "state_pool_move_share",
            "state_resume_share", "paged_attn_hbm_frac"}
    assert mine <= set(cell.per_layer)
    # everything DeepSeek-V3's cell reports but the three that read its latent pool
    assert set(cell.per_layer) - mine == {m for m in other.per_layer if not m.startswith("latent_")}


def test_counts_are_the_issues_arithmetic_and_one_dispatch_by_hand(cell):
    model = family.model_of(cell.config, rehearse=False)
    assert family.gdn_params(model) == 33_718_464 - 192  # less A_log, dt_bias and the output norm
    assert family.attention_params(model) == 27_263_488 - 512  # less the two head norms
    assert family.expert_params(model) == 3_145_728
    assert family.state_bytes(model) == 2_146_304 and family.kv_token_bytes(model) == 4096
    assert 39.0 < family.experts_hit(model, 19) < 41.0  # "~40 of 128 small experts hit"
    one = family.weight_bytes(model, 2.0, rows=19)
    assert 2.6e9 < one < 2.9e9  # "a step streams roughly 2.7 GB of weights"
    assert 7.0e9 < family.weight_bytes(model, 2.0, rows=10_000) < 7.33e9  # all, less the embedding
    # a burst of 8 steps over 19 live rows at 8.7k cached tokens each
    total, attn = family.burst_bytes(model, 2.0, rows=19, kv_tokens=19 * 8700, steps=8)
    assert attn == sum((19 * 8700 + 19 * i) * 4096 for i in range(8))
    state, flops = family.gdn_decode_work(model, 19, 19 * 8700, 8)
    assert state == 19 * 6 * 8 * 2 * 2_146_304 and flops == 19 * 6 * 8 * 6 * 32 * 128 * 128
    assert total == 8 * one + attn + state
    assert 0.45e9 < state / 8 < 0.52e9 and 0.6e9 < attn / 8 < 0.75e9  # "0.5 GB of state, 0.7 of K/V"
    # a wave of 512 new tokens behind 8,192 cached: 8 blocks of 64 a value head and layer
    nbytes, flops = family.gdn_prefill_work(model, 512, 1)
    assert flops == 6 * 512 * 32 * (2 * 64 * (3 * 128 + 2 * 128) + 8 * 128 * 128)
    assert nbytes == 6 * (512 * 32 * 512 * 4 + (8 + 1) * 2 * 32 * 128 * 128 * 4)
    pairs = family.causal_pairs(8192, 512)
    whole = family.prefill_flops(model, 512, pairs, 1)
    assert whole > flops + 4.0 * 16 * 256 * 2 * pairs and 4.0e11 < whole < 6.0e11
    assert family.expert_op_sizes(model, cell.config) == {"tile_rows": 32, "gate_up": 1024,
                                                          "hidden": 2048}
    sizes = family.state_op_sizes(model, cell.config)
    assert (sizes["layers"], sizes["slots"], sizes["rows"]) == (6, 97, 32)


def _ctx(host, per_op=None, busy=1.0):
    plain = {"devices": {"0": {"ops": [], "modules": [["jit_decode_burst(1)", 1.0, 0.1],
                                                      ["jit_decode_burst(1)", 2.0, 0.1]]}},
             "host": host}
    config = manifest.load_cell(CELL).config
    return SimpleNamespace(
        _host_phases=plain, trace={"per_op": per_op or {}, "busy_first_s": busy},
        trace_span=(0.0, 9.0), family=family, model=family.model_of(config, rehearse=False),
        config=config, chips=1, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_the_new_readers_read_the_counters_and_the_cores_ops():
    wave = lambda t, tokens, pages, state: ["engine.prefill_batch", t, 0.01, {  # noqa: E731
        "rows": 1, "new_tokens": tokens, "cached_tokens": 8192, "pairs": 1, "completes": 1,
        "page_hit_tokens": pages, "state_hit_tokens": state, "state_restored": 0,
        "state_snapshots": 0, "state_evicted": 0}]
    burst = lambda t: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 19, "kv_tokens": 19 * 8700, "steps": 8, "experts_hit": 0, "expert_tokens": 0,
        "expert_slots": 0}]
    host = [burst(0.9), wave(1.0, 88, 8192, 8192), wave(1.2, 512, 16384, 16384),
            wave(1.4, 600, 16384 + 8192, 16384), burst(1.9)]
    ops = {"fusion.12_f32_32_32_128_": 0.004, "select_dynamic-update-slice_fusion.3_f32_6_97_32_128_128_": 0.006,
           "fusion.77_f32_8_32_64_128_": 0.01, "copy.9_f32_1_32_128_128_": 0.002,
           "fusion.5_f32_32_1_32_128_": 5.0,  # the gated norm's output: four axes, not matched
           "bitcast_dynamic-update-slice_fusion.4_f32_6_97_32_128_128_": 0.003,
           "dynamic_update_slice.8_bf16_6_97_24576_": 0.001}
    ctx = _ctx(host, ops, busy=2.0)
    # the third wave's pages matched 8,192 tokens deep and no snapshot lay there
    assert state_cache.read(ctx, "resume_share") == 100.0 * 8192 / 16384
    spec = manifest.metric_spec("gdn_decode_roofline_frac")
    got = kernel_roofline.read(ctx, **spec["args"])
    nbytes, _ = family.gdn_decode_work(ctx.model, 19, 0, 8)
    assert abs(got - 100.0 * 2 * nbytes / 819e9 / 0.010) < 1e-9 and got < 100.0
    spec = manifest.metric_spec("gdn_prefill_roofline_frac")
    got = state_cache.read(ctx, **spec["args"])
    allowed = sum(max(b / 819e9, f / 197e12) for b, f in
                  (family.gdn_prefill_work(ctx.model, n, 1) for n in (88, 512, 600)))
    assert abs(got - 100.0 * allowed / 0.012) < 1e-9 and got < 100.0
    spec = manifest.metric_spec("state_pool_move_share")
    assert abs(op_share.read(ctx, **spec["args"]) - 100.0 * 0.004 / 2.0) < 1e-9
    # a program that writes no such counts (the parent commit, any other model) reads as nothing
    bare = _ctx([["engine.prefill_batch", 1.0, 0.01, {"rows": 1, "new_tokens": 5}]] * 2, ops)
    assert state_cache.read(bare, "resume_share") is None
    assert state_cache.read(bare, **manifest.metric_spec("gdn_prefill_roofline_frac")["args"]) is None
    none = SimpleNamespace(trace_span=None, trace=None, family=family, peaks=None, _host_phases=None)
    assert state_cache.read(none, "resume_share") is None


@pytest.mark.slow
def test_rehearsal_serves_the_cell_through_the_family():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed", str(2**31 + 34),
         "--seconds", "10", "--trace", "1", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    names = set(last["metrics"])
    assert names <= set(manifest.load_cell(CELL).per_layer)
    assert {"prefix_hit_share", "state_resume_share", "moe_experts_hit_share",
            "decode_rows_mean"} <= names
    assert last["metrics"]["prefix_hit_share"]["value"] > 40.0  # topics' runs resume from snapshots
    assert last["metrics"]["state_resume_share"]["value"] > 90.0
    assert set(last["checks"]) == {"prefill_logits_rel_rms", "decode_token_gap"}


def test_the_fp8_control_is_not_correct_and_the_program_is_at_test_widths(tmp_path, monkeypatch):
    """The comparison that decides ``correct``, on the CPU at the rehearsal's
    widths: the engine passes its limits; the reference with its weights
    re-rounded to float8 e4m3 stands in the program's place and does not."""
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
    assert control["numbers"]["prefill_logits_rel_rms"] > 2 * spec["limits"]["prefill_logits_rel_rms"]
    assert sound["correct"] is True and sound["sample"] == control["sample"]
