"""The DeepSeek-V3 family of the benchmark: its configuration file against
the source's, its counts, its two readers on a hand-made trace, and ONE
rehearsal of its cell on the CPU (2 layers: 1 dense + 1 expert layer, 8
experts of which 4 are held, kv_lora_rank 16; under a minute alone; a 10 s window of 6
clients, so that a loaded test machine still starts requests inside it)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import deepseek_v3 as family
from benchmarks.readers import kernel_roofline, moe_counters

ROOT = manifest.ROOT
CELL = "deepseek-v3-ep16-bf16.repo-sessions"
PUBLISHED = {  # https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json
    "hidden_size": 7168, "num_attention_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
    "n_group": 8, "topk_group": 4, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
    "rope_theta": 10000, "rms_norm_eps": 1e-06, "num_key_value_heads": 128,
    "max_position_embeddings": 163840, "num_nextn_predict_layers": 1}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_width_is_the_published_one_and_every_cut_is_listed(cell):
    config = cell.config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    cut = {"num_hidden_layers": (5, 61), "first_k_dense_replace": (1, 3),
           "n_routed_experts": (16, 256), "vocab_size": (16160, 129280)}
    for key, (here, source) in cut.items():
        assert config[key] == here and config["published"][key] == source and key in config["reduced"]
    assert config["router_width"] == 256 and config["experts_held"] == [0, 16]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and "mtp" in entry["reduced"]
    model = family.model_of(config, rehearse=False)
    assert model["n_routed_experts"] == 256 and model["experts_held"] == [0, 16]


def test_the_cell_offers_the_traffic_the_issue_gives(cell):
    t = cell.traffic
    assert (t["entry"], t["loop"], t["clients"], t["requests_per_client"]) == \
        ("openai_chat", "closed", 32, 24)
    assert t["blocks"] == {"pool": 160, "topics": 8, "topic_blocks": 16,
                           "extra_blocks": {"min": 0, "max": 1}, "block_tokens": 512,
                           "question_tokens": 48}
    assert t["system_prefix_tokens"] == 40 and t["think_s"] == {"max": 3.0}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32,
                                  "max": 384}
    assert (t["lead_in_s"], t["trace"]) == (24, {"start_s": 12, "seconds": 8})
    assert t["tail_s"] == 14 and "tail_from" not in t
    engine = cell.config["engine"]
    assert engine["num_pages"] * engine["page_size"] == 262144 and engine["max_seq_len"] == 10240
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"]
    assert {"prefix_hit_share", "latent_attn_roofline_frac", "latent_prefill_attn_flops_frac",
            "moe_experts_hbm_frac",
            "moe_experts_hit_share", "moe_tokens_per_expert_mean", "latent_pool_move_share",
            "decode_hbm_frac", "burst_hbm_frac", "prefill_flops_frac"} <= set(cell.per_layer)
    assert not {"pool_copy_share", "pool_move_share", "paged_attn_hbm_frac"} & set(cell.per_layer)


def test_counts_are_the_issues_arithmetic(cell):
    model = family.model_of(cell.config, rehearse=False)
    assert round(family.attention_params(model) / 1e6, 1) == 187.1
    assert round(family.expert_params(model) / 1e6, 2) == 44.04
    assert family.latent_row_bytes(model) == 1152  # the 576 columns used, not the 640 stored
    hit = family.experts_hit(model, 22)
    assert 7.5 < hit < 8.5  # "at ~22 live rows half of the 16 do"
    one = family.weight_bytes(model, 2.0, rows=22)
    all_hit = family.weight_bytes(model, 2.0, rows=10_000)
    assert 5.5e9 < one < 6.6e9 and 8.6e9 < all_hit < 9.0e9  # 9.13 GB less the embedding table
    total, attn = family.burst_bytes(model, 2.0, rows=22, kv_tokens=22 * 8700, steps=8)
    assert attn == sum((22 * 8700 + 22 * i) * 5 * 1152 for i in range(8)) and total > attn
    nbytes, flops = family.latent_attention_work(model, 22, 22 * 8700, 8)
    assert nbytes == attn and 240 < flops / nbytes < 243  # 242 FLOP/B: on the v5e's ridge (240)
    # 512 new tokens after 8,192 cached: the projections and the (query, key) pairs
    pairs = family.causal_pairs(8192, 512)
    assert pairs == 512 * 8192 + 512 * 513 // 2
    flops = family.prefill_flops(model, 512, pairs, 1)
    assert 1.4e12 < flops - 2.0 * 128 * 320 * 5 * pairs < 1.8e12


def _ctx(host, per_op=None):
    plain = {"devices": {"0": {"ops": [], "modules": [["jit_decode_burst(1)", 1.0, 0.1],
                                                      ["jit_decode_burst(1)", 2.0, 0.1]]}},
             "host": host}
    model = family.model_of(manifest.load_cell(CELL).config, rehearse=False)
    return SimpleNamespace(
        _host_phases=plain, trace={"per_op": per_op or {}}, trace_span=(0.0, 9.0),
        family=family, model=model, config=manifest.load_cell(CELL).config, chips=1,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_readers_read_the_counters_and_the_kernels_ops():
    burst = lambda t, hit, tok, slots: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 22, "kv_tokens": 22 * 8700, "steps": 8, "experts_hit": hit,
        "expert_tokens": tok, "expert_slots": slots}]
    host = [burst(0.9, 1000, 1500, 2048), burst(1.9, 1256, 1900, 2560)]
    ctx = _ctx(host, {"latent_attention.7_bf16_32_128_512_": 0.05,
                      "fusion.23_bf16_32_4096_": 0.02, "fusion.9_bf16_32_1_4096_": 9.0})
    assert moe_counters.read(ctx, "hit_share") == 100.0 * 256 / 512
    assert moe_counters.read(ctx, "tokens_per_expert") == 400 / 256
    frac = moe_counters.read(ctx, "experts_hbm_frac",
                             op=manifest.metric_spec("moe_experts_hbm_frac")["args"]["op"])
    assert abs(frac - 100.0 * 256 * 3 * 7168 * 2048 * 2 / (0.02 * 819e9)) < 1e-9
    share = kernel_roofline.read(ctx, op="^latent_attention", work="latent_attention_work")
    nbytes, flops = family.latent_attention_work(ctx.model, 22, 22 * 8700, 8)
    assert abs(share - 100.0 * 2 * max(nbytes / 819e9, flops / 197e12) / 0.05) < 1e-9
    wave = ["engine.prefill_batch", 1.2, 0.01, {"rows": 1, "new_tokens": 512, "cached_tokens": 8192,
                                                "pairs": 512 * 8192 + 512 * 513 // 2, "completes": 1}]
    pre = _ctx(host + [wave], {"latent_prefill_attention.3_bf16_1_128_512_128_": 0.04})
    got = kernel_roofline.read(pre, op="^latent_prefill_attention", work="latent_prefill_work",
                               dispatch="prefill")
    _, flops = family.latent_prefill_work(pre.model, wave[3]["pairs"], 8704)
    assert abs(got - 100.0 * flops / 197e12 / 0.04) < 1e-9 and 3.0e12 < flops < 3.5e12
    # a program that writes no counts (Qwen2, or any commit before PR 27) reads as nothing
    bare = _ctx([["engine.decode_burst", 0.9, 0.001, {"rows": 1, "kv_tokens": 9, "steps": 8}]] * 2)
    assert moe_counters.read(bare, "hit_share") is None
    assert kernel_roofline.read(bare, op="^latent_attention", work="latent_attention_work") is None
    none = SimpleNamespace(trace_span=None, trace=None, family=family, peaks=None, _host_phases=None)
    assert moe_counters.read(none, "hit_share") is None
    assert kernel_roofline.read(none, op="x", work="latent_attention_work") is None


def test_rehearsal_serves_the_cell_through_the_family():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed", str(2**31 + 23),
         "--seconds", "10", "--trace", "1", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads([ln for ln in out.stdout.splitlines() if ln.strip()][-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    names = set(last["metrics"])
    assert names <= set(manifest.load_cell(CELL).per_layer)
    assert {"prefix_hit_share", "moe_experts_hit_share", "moe_tokens_per_expert_mean",
            "decode_rows_mean"} <= names
    assert last["metrics"]["prefix_hit_share"]["value"] > 40.0  # topics' runs are served from pages
    assert 0 < last["metrics"]["moe_experts_hit_share"]["value"] <= 100.0
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
    assert control["correct"] is False
    assert control["numbers"]["prefill_logits_rel_rms"] > 2 * spec["limits"]["prefill_logits_rel_rms"]
    sound = correctness.check(*args)
    assert sound["correct"] is True and sound["sample"] == control["sample"]
