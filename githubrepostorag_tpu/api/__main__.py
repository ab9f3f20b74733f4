"""Run the RAG service: ``python -m githubrepostorag_tpu.api``.

Single-pod mode (default): API + worker + agent share one process over the
in-memory bus, the configured store, and the configured LLM backend
(LLM_BACKEND=fake for smoke tests; =http against a separate model server;
=inprocess with MODEL_WEIGHTS_PATH for the full TPU stack).  With
REDIS_URL set and --redis, the bus/queue ride the in-tree RESP client so
separate API and worker pods interoperate like the reference deployment.
"""

from __future__ import annotations

import argparse
import asyncio

from githubrepostorag_tpu.config import get_settings
from githubrepostorag_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _build_llm():
    s = get_settings()
    backend = s.llm_backend.lower()
    if backend == "inprocess":
        from githubrepostorag_tpu.llm import InProcessLLM
        from githubrepostorag_tpu.models.hf_loader import load_qwen2
        from githubrepostorag_tpu.runtime import on_tpu
        from githubrepostorag_tpu.serving import Engine
        from githubrepostorag_tpu.serving.async_engine import AsyncEngine
        from githubrepostorag_tpu.serving.tokenizer import make_tokenizer

        if not s.model_weights_path:
            raise SystemExit("LLM_BACKEND=inprocess requires MODEL_WEIGHTS_PATH")
        import ml_dtypes

        params, cfg = load_qwen2(
            s.model_weights_path, dtype=ml_dtypes.bfloat16, quantize=s.quantize_weights,
        )
        engine = Engine(
            params, cfg,
            max_num_seqs=s.max_num_seqs,
            num_pages=s.kv_num_pages,
            page_size=s.kv_page_size,
            max_seq_len=s.context_window,
            prefill_chunk=s.prefill_chunk,
            kv_quant=s.kv_quant,
            use_pallas=on_tpu(),
            preempt=s.preempt,
            preempt_headroom_pages=s.preempt_headroom_pages,
            default_priority=s.priority_default_class,
            protected_priority=s.priority_protected_class,
        )
        return InProcessLLM(AsyncEngine(engine), make_tokenizer(s.model_weights_path))
    from githubrepostorag_tpu.llm import get_llm

    return get_llm()


async def serve(host: str, port: int, use_redis: bool, run_worker: bool = True) -> None:
    from githubrepostorag_tpu.api.app import RagApi
    from githubrepostorag_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    if use_redis:
        from githubrepostorag_tpu.events.redis import RedisBus, RedisCancelFlags, RedisJobQueue

        bus, flags, queue = RedisBus(), RedisCancelFlags(), RedisJobQueue()
    else:
        from githubrepostorag_tpu.events import MemoryBus, MemoryCancelFlags, MemoryJobQueue

        bus, flags, queue = MemoryBus(), MemoryCancelFlags(), MemoryJobQueue()

    api = RagApi(bus, flags, queue)
    await api.start(host=host, port=port)
    logger.info("service up — UI at http://%s:%d/static/index.html", host, port)

    if not run_worker:
        # split deployment (rag-api pod): jobs are consumed by a separate
        # ``python -m githubrepostorag_tpu.worker`` pod over Redis, like the
        # reference's rag-api / rag-worker pair
        while True:
            await asyncio.sleep(3600)

    from githubrepostorag_tpu.agent import GraphAgent
    from githubrepostorag_tpu.llm import set_llm
    from githubrepostorag_tpu.metrics import MeteredLLM
    from githubrepostorag_tpu.worker import RagWorker

    raw_llm = _build_llm()
    set_llm(raw_llm)  # health.py probes the shared instance for engine stats
    agent = GraphAgent(MeteredLLM(raw_llm))
    worker = RagWorker(agent, bus, flags, queue)
    await worker.run_forever()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description="Run the RAG API + worker")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument("--redis", action="store_true",
                        help="use Redis (REDIS_URL) for bus/queue instead of in-memory")
    parser.add_argument("--no-worker", action="store_true",
                        help="API only; a separate `python -m githubrepostorag_tpu.worker` "
                             "pod consumes the queue (requires --redis)")
    args = parser.parse_args(argv)
    if args.no_worker and not args.redis:
        parser.error("--no-worker requires --redis (the queue must be shared)")
    asyncio.run(serve(args.host, args.port, args.redis, run_worker=not args.no_worker))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
