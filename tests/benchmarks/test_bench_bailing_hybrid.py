"""The ``bailing_hybrid`` family of the benchmark (Ling-3.0-flash): its
configuration file against the catalog's row, its counts against the issue's
parameter arithmetic and one hand-computed dispatch each, the cell's lists, and
the readers this cell's own metrics use on a hand-made trace.  Pins no total of
cells, configurations or metrics.  (A rehearsal of the cell through
``benchmarks.run`` is test_bench_run.py's and test_bench_startup.py's, by their
own parametrisation over the cells.)"""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import manifest
from benchmarks.families import bailing_hybrid as family
from benchmarks.readers import kernel_roofline, moe_load, op_share

CELL = "ling-3.0-flash-ep4-bf16.repo-longctx"
PAIR = "mellum2-12b-a2.5b-bf16.repo-longctx"
DEEPSEEK = "deepseek-v3-ep16-bf16.repo-sessions"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MINE = {"kda_decode_roofline_frac", "kda_prefill_roofline_frac", "ling_state_pool_move_share",
        "ling_latent_pool_move_share", "ling_moe_expert_load_max_over_mean"}
CUT = {"num_hidden_layers": (7, 42), "first_k_dense_replace": (1, 2), "num_experts": (128, 512),
       "vocab_size": (39296, 157184)}
KDA, LATENT, EXPERT, DENSE = 63_045_632, 31_965_184, 5_898_240, 47_185_920  # ISSUE.md's sums


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_every_key_is_the_published_one_and_every_cut_is_listed(cell):
    config = cell.config
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG) if '"name": "Ling-3.0-flash"' in ln)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value or key in CUT, key
    for key, (here, published) in CUT.items():
        assert (config[key], config["published"][key]) == (here, published) and key in config["cut"]
    assert config["reduced"] == ["weights", "tokenizer", *CUT, "mtp"]
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert (config["layer_kinds"], config["router_width"], config["experts_held"]) == (
        "RRRRRRA", 512, [0, 128])
    # every reading the source leaves open is stated with what it rules out
    assert {"kda_output_gate", "kda_decay_projection", "kda_gate", "use_qk_norm", "group_norm_size",
            "num_kv_heads_for_linear_attn", "swiglu_limits", "max_window_layers",
            "latent_layer_position", "rope", "q_lora_rank", "state", "weights",
            "tokenizer"} <= set(config["assumed"])
    assert all("uled out" in config["assumed"][k] for k in ("kda_output_gate", "kda_gate",
                                                            "latent_layer_position"))
    assert set(config["correctness"]["knock_outs"]) == {
        "scalar_decay", "unbounded_gate", "no_head_gate", "no_group_limit", "no_route_scale",
        "rotate_half"}
    eng = config["engine"]
    assert (eng["num_pages"], eng["state_snapshots"], eng["max_seq_len"]) == (2560, 63, 26624)
    assert "1,864 latent pages" in config["pools"]["working_set"]
    assert "4 chips share each layer" in config["deployment"]


def test_the_cell_runs_repo_longctx_as_it_stands_and_lists_what_it_can_read(cell):
    pair, deepseek = manifest.load_cell(PAIR), manifest.load_cell(DEEPSEEK)
    assert cell.traffic == pair.traffic and cell.traffic_name == "repo-longctx" and cell.chips == 1
    assert cell.end_to_end == ["tpot_p50_ms", "setup_s"]
    assert MINE <= set(cell.per_layer)
    manifest_ = manifest.load_manifest()
    for name in MINE:  # listed for this cell alone
        entry = next(m for m in manifest_["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
    # DeepSeek-V3's two kernel readings (the work is the family's own count at 32 heads), the
    # hybrids' resume share, the expert counters, and the whole step's share
    assert {"latent_attn_roofline_frac", "latent_prefill_attn_flops_frac", "state_resume_share",
            "prefix_hit_share", "moe_experts_hbm_frac", "moe_experts_hit_share",
            "moe_tokens_per_expert_mean", "decode_hbm_mfu_frac", "prefill_flops_frac",
            "cycle_burst_share", "cycle_wave_share"} <= set(cell.per_layer)
    # the guards pinned to other cells' pool shapes or lists are this cell's under its own names
    assert not {"latent_pool_move_share", "state_pool_move_share", "moe_expert_load_max_over_mean",
                "paged_attn_hbm_frac"} & set(cell.per_layer)
    assert "latent_pool_move_share" in deepseek.per_layer
    twin, accepted = (manifest.metric_spec(n) for n in ("ling_moe_expert_load_max_over_mean",
                                                        "moe_expert_load_max_over_mean"))
    assert twin == accepted and twin["reader"] == "moe_load"
    assert manifest.metric_spec("kda_decode_roofline_frac")["args"]["work"] == "kda_decode_work"
    assert manifest.metric_spec("kda_prefill_roofline_frac")["args"]["work"] == "kda_prefill_work"
    why = next(w["why"] for w in manifest_["workloads"] if w["name"] == CELL)
    assert "counterpart mellum2-12b-a2.5b-bf16" in why and "0.25 tokens" in why and len(why) <= 200
    manifest.validate(manifest_)


def test_counts_are_the_issues_arithmetic_and_one_dispatch_by_hand(cell):
    model = family.model_of(cell.config, rehearse=False)
    d, v = 2560, 39296
    # the mixers' matrices, ISSUE.md's sums, and the convolution's taps beside them
    assert family.kda_params(model) == KDA == 63_045_632 - 0 and KDA - 12288 * 4 == 62_996_480
    assert family.latent_params(model) == LATENT and family.expert_params(model) == EXPERT
    assert 3 * d * 6144 == DENSE
    assert family.state_bytes(model) == 2_170_880 and family.latent_row_bytes(model) == 1152
    # this chip: one dense KDA layer, five KDA expert layers, one latent expert layer, 128 experts
    per_moe = 128 * EXPERT + d * 512 + EXPERT
    chip = 6 * KDA + LATENT + DENSE + 6 * per_moe + 2 * d * v
    assert 5.22e9 < chip < 5.24e9  # ISSUE.md: 5,232 M parameters, 10.46 GB
    # the published model: 42 layers (35 KDA, 7 latent), 2 dense, 40 x 512 experts
    whole = 35 * KDA + 7 * LATENT + 2 * DENSE + 40 * (512 * EXPERT + d * 512 + EXPERT) \
        + 2 * 157184 * d
    active = whole - 40 * (512 - 8) * EXPERT
    assert 123e9 < whole < 127e9 and 5.3e9 < active < 5.7e9  # "~125B", 5.5 B a token
    fixed = 2.0 * (6 * KDA + LATENT + DENSE + 6 * (d * 512 + EXPERT) + d * v)
    assert family.weight_bytes(model, 2.0) == fixed and 1.0e9 < fixed < 1.3e9  # ~1.0 + 0.2 GB
    # a burst of 8 steps over 16 live rows at 25,200 cached tokens each
    kv = 16 * 25200
    rows = family.attention_bytes(model, rows=16, kv_tokens=kv, steps=8)
    assert rows == sum((kv + 16 * i) * 1152 for i in range(8)) and 0.46e9 < rows / 8 < 0.47e9
    state, flops = family.kda_decode_work(model, 16, kv, 8)
    assert state == 2.0 * 16 * 6 * 8 * 2_170_880 and 0.41e9 < state / 8 < 0.42e9  # ~0.4 GB a step
    assert flops == 7.0 * 16 * 6 * 8 * 32 * 128 * 128 and state / 819e9 > flops / 197e12
    counted = family.burst_counted_bytes(model, 2.0, 16, kv, None, 8, 0.22)
    assert counted == 8 * fixed + rows + state + 0.22 * 128 * 6 * 8 * EXPERT * 2.0
    assert 3.8e9 < counted / 8 < 4.3e9  # ISSUE.md: ~4.1 GB a step, 5.0 ms at 819 GB/s
    nbytes, ops = family.latent_attention_work(model, 16, kv, 8)
    assert (nbytes, ops) == (rows, rows / 1152 * 2.0 * 32 * (2 * 512 + 64))
    nbytes, ops = family.latent_prefill_work(model, 1000, 25000)
    assert nbytes == 25000 * 1152 and ops == 32 * (2.0 * 320 * 1000 + 2.0 * 512 * 256 * 25000)
    nbytes, ops = family.kda_prefill_work(model, 512, 1)
    assert ops == 6 * 512 * 32 * (2.0 * 64 * 5 * 128 + 8.0 * 128 * 128)
    assert nbytes == 6 * (512 * 32 * 5 * 128 * 4.0 + (8 + 1) * 2.0 * 32 * 128 * 128 * 4.0)
    pairs = family.causal_pairs(24576, 512)
    assert family.prefill_flops(model, 512, pairs, 1) > 2.0 * 512 * (6 * KDA + LATENT)
    assert family.expert_op_sizes(model, cell.config) == {
        "tile_rows": 32, "gate_up": 1536, "hidden": 2560}
    sizes = family.state_op_sizes(model, cell.config)
    assert (sizes["layers"], sizes["slots"], sizes["hv"], sizes["channels"]) == (6, 96, 32, 12288)
    assert family.work.bytes_per_weight(cell.config) == 2.0
    assert family.work.expert_bytes(model, 2.0) == 2.0 * EXPERT


def _ctx(host, per_op=None):
    plain = {"devices": {"0": {"ops": [], "modules": [["jit_decode_burst(1)", 1.0, 0.1],
                                                      ["jit_decode_burst(1)", 2.0, 0.1]]}},
             "host": host}
    config = manifest.load_cell(CELL).config
    return SimpleNamespace(
        _host_phases=plain, trace={"per_op": per_op or {}, "busy_first_s": 1.0},
        trace_span=(0.0, 9.0), family=family, model=family.model_of(config, rehearse=False),
        config=config, chips=1, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_this_cells_readers_read_a_hand_made_trace_and_nothing_where_nothing_is():
    burst = lambda t, **kw: ["engine.decode_burst", t, 0.001, {  # noqa: E731
        "rows": 16, "kv_tokens": 16 * 25200, "steps": 8, "experts_hit": 100, "expert_tokens": 300,
        "expert_slots": 6144, **kw}]
    host = [burst(0.99, experts_max_pairs=0), burst(1.99, experts_hit=1300, expert_tokens=4300,
                                                   expert_slots=12288, experts_max_pairs=120)]
    model = _ctx(host).model
    state, _ = family.kda_decode_work(model, 16, 16 * 25200, 8)
    ctx = _ctx(host, {"kda_recurrent.3_f32_32_32_128_": 2 * state / 819e9 * 2,
                      "copy.1_f32_6_96_32_128_128_": 0.05})
    spec = manifest.metric_spec("kda_decode_roofline_frac")
    got = kernel_roofline.read(ctx, **spec["args"])
    assert got == pytest.approx(50.0)  # two bursts' allowed seconds over twice as many
    moves = op_share.read(ctx, **manifest.metric_spec("ling_state_pool_move_share")["args"])
    assert moves is not None and moves > 0
    load = moe_load.read(ctx, **manifest.metric_spec("ling_moe_expert_load_max_over_mean")["args"])
    assert load is not None and load > 1.0
    # a program without the kernel (the parent): nothing to read, and no error
    assert kernel_roofline.read(_ctx(host), **spec["args"]) is None
