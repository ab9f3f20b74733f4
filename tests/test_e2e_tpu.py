"""BASELINE eval config #1 at REAL scale: ingest this repository, then
answer a RAG query where the synthesis LLM is the in-tree Qwen2-0.5B
engine running on the actual TPU (random weights — the loop, streaming,
and latency are what's under test; answer text is weight-dependent).

Marked ``integration``: requires a TPU device and ~2 min of compiles.
Run: ``TPU_TESTS=1 pytest -m integration tests/test_e2e_tpu.py``
(the conftest forces the CPU backend unless TPU_TESTS=1).
"""

from pathlib import Path

import pytest

import jax

pytestmark = [pytest.mark.integration, pytest.mark.tpu]


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs a real TPU chip")
def test_config1_e2e_on_tpu(tmp_path, monkeypatch):
    import jax.numpy as jnp

    from githubrepostorag_tpu.agent import GraphAgent
    from githubrepostorag_tpu.embedding import HashingTextEncoder
    from githubrepostorag_tpu.ingest.controller import ingest_component
    from githubrepostorag_tpu.ingest.sources import LocalRepoReader
    from githubrepostorag_tpu.llm import FakeLLM, InProcessLLM
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
    from githubrepostorag_tpu.retrieval import RetrieverFactory
    from githubrepostorag_tpu.serving.async_engine import AsyncEngine
    from githubrepostorag_tpu.serving.engine import Engine
    from githubrepostorag_tpu.serving.tokenizer import ByteTokenizer
    from githubrepostorag_tpu.store import MemoryVectorStore

    monkeypatch.setenv("DATA_DIR", str(tmp_path))
    from githubrepostorag_tpu.config import reload_settings

    reload_settings()

    # --- ingest this repo (extractors scripted: ingest-side LLM quality is
    # not what this test measures; the TPU engine is the QUERY-side LLM)
    root = Path(__file__).resolve().parent.parent
    docs = LocalRepoReader(str(root / "githubrepostorag_tpu")).load()[:30]
    store, enc = MemoryVectorStore(), HashingTextEncoder()
    ingest_component(
        "self", docs=docs, store=store, encoder=enc,
        llm=FakeLLM(script={r".": "summary, title, keywords"}),
    )
    assert store.count("embeddings") > 10

    # --- real TPU decoder behind the sync LLM protocol
    cfg = Qwen2Config.qwen2_0_5b()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    eng = Engine(params, cfg, max_num_seqs=4, num_pages=32, page_size=256,
                 max_seq_len=2048, prefill_chunk=512, use_pallas=True,
                 decode_burst=32)  # prefill on real hardware: the agent's
    # mixed prompt lengths hit both rungs of the width ladder (512, 256)
    llm = InProcessLLM(AsyncEngine(eng), ByteTokenizer(),
                   default_max_tokens=48, context_window=2048)

    deltas: list[str] = []
    stream_calls: list[str] = []
    orig_stream = llm.stream_complete

    def counting_stream(prompt, **kw):
        stream_calls.append(prompt)
        yield from orig_stream(prompt, **kw)

    llm.stream_complete = counting_stream
    agent = GraphAgent(llm, RetrieverFactory(store, enc), namespace="default",
                       max_iters=1)
    result = agent.run(
        "how does the serving engine schedule prefill and decode?",
        token_cb=deltas.append,
    )
    # the full loop ran: retrieval found real chunks of this repo, the TPU
    # decoder generated (and streamed) the synthesis, sources are attributed
    assert result.sources, result.debug
    assert all(s["doc_id"] and s["scope"] for s in result.sources)
    assert result.debug["final_ctx_blocks"] >= 1
    assert isinstance(result.answer, str)
    # synthesis really streamed through the TPU engine (ByteTokenizer drops
    # non-byte ids from a random model, so deltas/answer may be empty text)
    assert stream_calls, "synthesize never hit the engine's streaming path"
