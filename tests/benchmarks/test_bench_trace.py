"""The trace reducer on a small recorded trace (24 ms of one v5e chip) and on
hand-made intervals."""

import json
from pathlib import Path

import pytest

from benchmarks import trace

SAMPLE = json.loads((Path(__file__).parent / "data" / "trace_v5e_chat_24ms.json").read_text())


def test_union_and_gaps_by_hand():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.union_length([]) == 0
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace.gaps([(0, 10)], 2, 5) == []


def test_gap_attribution_by_hand():
    host = [["engine.decode_burst", 1.0, 0.5], ["engine.prefill_batch", 3.0, 0.2]]
    assert trace.attribute_gap((1.2, 1.3), host) == "engine.decode_burst"
    assert trace.attribute_gap((2.5, 2.99), host) == "before:engine.prefill_batch"
    assert trace.attribute_gap((5.0, 5.1), host) == "unattributed"


def test_short_names_keep_the_shape_and_the_opcode():
    n, op = trace.short_name("%fusion.198 = bf16[32,1,37888]{2,0,1:T(8,128)(2,1)S(1)} "
                             "fusion(bf16[37888]{0} %x), kind=kOutput")
    assert (n, op) == ("fusion.198_bf16_32_1_37888_", "fusion")
    n, op = trace.short_name("%while.37 = (s32[]{:T(128)}, s32[32]{0:T(128)}) "
                             "while((s32[]{:T(128)}, s32[32]{0}) %tuple.1), condition=%c")
    assert op == "while" and op in trace.CONTAINERS
    n, op = trace.short_name("%closed_call.15 = bf16[32,4,7,128]{3,2,1,0} "
                             "custom-call(s32[32,16]{1,0} %a), custom_call_target=\"tpu_custom_call\"")
    assert (n, op) == ("closed_call.15_bf16_32_4_7_128_", "custom-call")
    assert trace.short_name("%all-gather.45 = bf16[28,4,512,128,128]{4,3,2,1,0} "
                            "all-gather(bf16[28,1,512,128,128] %p)")[1] == "all-gather"


def test_recorded_trace_busy_idle_and_sums():
    r = trace.reduce(SAMPLE)
    ops = SAMPLE["devices"]["0"]["ops"]
    assert r["window_s"] == pytest.approx(0.020151562, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.020122222, rel=1e-5)
    assert r["busy_s"] <= r["window_s"]
    assert sum(r["per_op"].values()) == pytest.approx(sum(o[2] for o in ops))
    assert sum(r["per_opcode"].values()) == pytest.approx(sum(o[2] for o in ops))
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_first_s"], abs=1e-9)
    assert r["idle_gaps"][0][0] == "before:engine.decode_burst"
    assert len(r["device_ops"]) == 10 and r["device_ops"][0][1] >= r["device_ops"][1][1]
    assert not any(o[3] in trace.CONTAINERS for o in ops)  # a while's time is its children's


def test_recorded_trace_names_the_programs_and_kernels():
    r = trace.reduce(SAMPLE)
    assert trace.module_seconds(r, "decode_burst") > 0
    assert trace.module_seconds(r, "forward_paged") == 0  # no prefill in these 24 ms
    assert trace.opcode_seconds(r, ["custom-call"]) == pytest.approx(
        trace.op_seconds(r, "^closed_call"))
    assert trace.op_seconds(r, "^fusion\\.198_bf16_32_1_37888_$") > 0


def test_head_rebases_and_cuts():
    h = trace.head(SAMPLE, 0.005)
    assert h["devices"]["0"]["ops"] and max(o[1] for o in h["devices"]["0"]["ops"]) < 0.005
    assert trace.reduce({"devices": {}, "host": []}) == {}
