"""dp-grouped multi-engine serving (serving/multi_engine.py): disjoint
submeshes, token-identical outputs vs a single engine, least-loaded
routing, cancel, and the OpenAI server surface over replicas.

Covers VERDICT r02 next-step #9 (the deferred round-2 idea): one server
process running MESH_SHAPE=tp:2,dp:2-style replica groups on the virtual
8-device CPU mesh.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.parallel import MeshPlan
from githubrepostorag_tpu.serving import Engine, SamplingParams
from githubrepostorag_tpu.serving.multi_engine import MultiAsyncEngine, dp_submeshes


@pytest.fixture(scope="module")
def tiny():
    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    return cfg, params


def _engine(params, cfg, mesh=None):
    return Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=4,
                  max_seq_len=64, kv_dtype=jnp.float32, decode_burst=8,
                  mesh=mesh)


def _prompts(n):
    rng = np.random.default_rng(11)
    return [rng.integers(0, 512, 6 + i).tolist() for i in range(n)]


def test_dp_submeshes_disjoint_devices():
    meshes, groups = dp_submeshes(MeshPlan(tp=2, dp=2))
    assert len(meshes) == 2 and len(groups) == 2
    flat = [d.id for g in groups for d in g]
    assert len(flat) == len(set(flat)) == 4  # disjoint, 2 devices each
    for m in meshes:
        assert dict(m.shape)["tp"] == 2 and dict(m.shape)["dp"] == 1


def test_dp_submeshes_single_device_groups():
    """Pure-dp groups still get real 1-device meshes so each replica's
    params/pools land on ITS device, not the process default device."""
    meshes, groups = dp_submeshes(MeshPlan(dp=4))
    assert all(len(g) == 1 for g in groups)
    mesh_devices = [m.devices.reshape(-1)[0].id for m in meshes]
    assert len(set(mesh_devices)) == 4  # four distinct devices
    assert mesh_devices == [g[0].id for g in groups]


def test_dp_submeshes_rejects_oversubscription():
    with pytest.raises(ValueError, match="devices"):
        dp_submeshes(MeshPlan(tp=8, dp=2))  # 16 > 8 virtual devices


async def test_multi_engine_token_identical_and_balanced(tiny):
    cfg, params = tiny
    prompts = _prompts(4)
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
    expected = [
        r.output_tokens for r in _engine(params, cfg).generate(prompts, sp)
    ]

    meshes, _ = dp_submeshes(MeshPlan(tp=2, dp=2))
    multi = MultiAsyncEngine([_engine(params, cfg, mesh=m) for m in meshes])
    try:
        import asyncio

        results = await asyncio.gather(
            *(multi.generate(p, sp) for p in prompts)
        )
        assert [r.output_tokens for r in results] == expected
        stats = multi.stats()
        assert stats["replicas"] == 2
        assert stats["requests_admitted"] == 4
        # 4 concurrent requests over 2 replicas of max_num_seqs=2: least-
        # loaded admission must have routed work to BOTH replicas
        admitted = [s["requests_admitted"] for s in stats["per_replica"]]
        assert all(a > 0 for a in admitted), admitted
    finally:
        await multi.stop()


async def test_multi_engine_cancel_routes_to_owner(tiny):
    cfg, params = tiny
    sp = SamplingParams(max_tokens=50, temperature=0.0, stop_token_ids=())
    meshes, _ = dp_submeshes(MeshPlan(dp=2))
    multi = MultiAsyncEngine([_engine(params, cfg, mesh=m) for m in meshes])
    try:
        got_tokens = 0
        async for event in multi.stream(_prompts(1)[0], sp, request_id="kill-me"):
            if event.type == "token":
                got_tokens += 1
                await multi.cancel("kill-me")
            if event.type == "final":
                assert event.result.finish_reason == "cancelled"
                break
        assert got_tokens >= 1
    finally:
        await multi.stop()


async def test_openai_server_over_replicas(tiny):
    """The OpenAI surface works unchanged over MultiAsyncEngine (the
    duck-type contract __main__.py relies on for MESH_SHAPE dp>1)."""
    import asyncio
    import json
    import urllib.request

    from githubrepostorag_tpu.serving.openai_api import OpenAIServer
    from githubrepostorag_tpu.serving.tokenizer import ByteTokenizer

    cfg, params = tiny
    meshes, _ = dp_submeshes(MeshPlan(dp=2))
    multi = MultiAsyncEngine([_engine(params, cfg, mesh=m) for m in meshes])
    server = OpenAIServer(multi, ByteTokenizer(), model_name="tiny-dp")
    port = await server.start(host="127.0.0.1", port=0)
    loop = asyncio.get_running_loop()

    def post(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read().decode())

    body = {"model": "tiny-dp", "max_tokens": 4, "temperature": 0,
            "messages": [{"role": "user", "content": "hi"}]}
    out1, out2 = await asyncio.gather(
        loop.run_in_executor(None, post, body),
        loop.run_in_executor(None, post, body),
    )
    assert out1["usage"]["completion_tokens"] == 4
    # same prompt, greedy, replicated weights -> identical replies from
    # whichever replica served each request
    assert out1["choices"][0]["message"]["content"] == \
        out2["choices"][0]["message"]["content"]
    await server.stop()


def test_stats_merge_sums_counters_and_means_rates():
    """Merge-rule regression: counters SUM across replicas, but rate/ratio/
    utilization-suffixed keys merge by MEAN — two replicas at 0.8
    acceptance are at 0.8, not 1.6."""

    class Stub:
        def __init__(self, s):
            self._s = s

        def stats(self):
            return self._s

    multi = MultiAsyncEngine.__new__(MultiAsyncEngine)
    multi._engines = [
        Stub({"requests_admitted": 3, "prefix_hit_rate": 0.8,
              "kv_utilization": 0.5, "deadline_reaps": 1}),
        Stub({"requests_admitted": 1, "prefix_hit_rate": 0.4,
              "kv_utilization": 0.1, "deadline_reaps": 0}),
    ]
    merged = MultiAsyncEngine.stats(multi)
    assert merged["requests_admitted"] == 4  # counter: summed
    assert merged["prefix_hit_rate"] == pytest.approx(0.6)  # rate: mean
    assert merged["kv_utilization"] == pytest.approx(0.3)
    assert merged["deadline_reaps"] == 1  # plain counter, still summed
    assert merged["replicas"] == 2


async def test_multi_engine_propagates_deadline(tiny):
    """Regression: stream()/generate() must accept and forward deadline_s.
    Before the fix the facade lacked the keyword, so llm.py's always-passed
    deadline_s= raised TypeError under dp>1 (swallowed into an error
    completion) and deadline reaping never engaged on replica groups."""
    import time

    cfg, params = tiny
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
    meshes, _ = dp_submeshes(MeshPlan(dp=2))
    multi = MultiAsyncEngine([_engine(params, cfg, mesh=m) for m in meshes])
    try:
        ok = await multi.generate(_prompts(1)[0], sp,
                                  deadline_s=time.monotonic() + 60.0)
        assert ok.finish_reason in ("length", "stop")
        assert len(ok.output_tokens) == 8

        reaped = await multi.generate(_prompts(1)[0], sp,
                                      deadline_s=time.monotonic() - 0.001)
        assert reaped.finish_reason == "deadline"
    finally:
        await multi.stop()
