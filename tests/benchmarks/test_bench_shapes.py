"""The shape functions against counts made by hand at Qwen2-7B's widths."""

import json

import pytest

from benchmarks import manifest, peaks, shapes

CFG = json.loads((manifest.HERE / "configs" / "qwen2-7b-int8.json").read_text())
D, NQ, NKV, HD, INTER, L, V = 3584, 28, 4, 128, 18944, 28, 152064


def test_layer_weights_by_hand():
    qkv = D * (NQ + 2 * NKV) * HD        # 3584 x 4608
    out = NQ * HD * D                    # 3584 x 3584
    mlp = 3 * D * INTER                  # gate, up, down
    assert shapes.layer_matmul_params(CFG) == qkv + out + mlp == 233_046_016


def test_weight_bytes_int8_and_bf16():
    per_layer = 233_046_016
    scales = ((NQ + 2 * NKV) * HD + D + 2 * INTER + D) * 2
    int8 = L * (per_layer + 2 * D * 2 + scales) + D * V + V * 2 + D * 2
    assert shapes.weight_bytes(CFG, 1.0) == pytest.approx(int8)
    assert 7.0e9 < shapes.weight_bytes(CFG, 1.0) < 7.2e9
    bf16 = L * (per_layer * 2 + 2 * D * 2) + D * V * 2 + D * 2
    assert shapes.weight_bytes(CFG, 2.0) == pytest.approx(bf16)


def test_kv_bytes_and_a_decode_step():
    assert shapes.kv_bytes_per_token(CFG) == 2 * L * NKV * HD * 2 == 57_344
    step = shapes.decode_step_bytes(CFG, 1.0, kv_tokens=9 * 400)
    assert step == pytest.approx(shapes.weight_bytes(CFG, 1.0) + 3600 * 57_344)


def test_a_burst_reads_a_context_that_grows_by_one_token_a_row_a_step():
    total, attn = shapes.burst_bytes(CFG, 1.0, rows=2, kv_tokens=100, steps=3)
    assert attn == (100 + 102 + 104) * 57_344
    assert total == pytest.approx(3 * shapes.weight_bytes(CFG, 1.0) + attn)


def test_prefill_flops_count_only_real_tokens():
    assert shapes.causal_pairs(0, 4) == 10
    assert shapes.causal_pairs(512, 100) == 100 * 512 + 5050
    flops = shapes.prefill_flops(CFG, new_tokens=300, context_pairs=shapes.causal_pairs(0, 300),
                                 sequences=1)
    dense = 2 * 233_046_016 * L * 300
    attn = 4 * HD * NQ * L * (300 * 301 // 2)
    head = 2 * D * V
    assert flops == pytest.approx(dense + attn + head)
    # a 300-token prompt is 300 tokens of work, whatever the 512-wide chunk pads
    assert flops < shapes.prefill_flops(CFG, 512, shapes.causal_pairs(0, 512), 1)


def test_peaks_table_is_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
