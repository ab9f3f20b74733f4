"""Worker-only pod: ``python -m githubrepostorag_tpu.worker``.

Mirrors the reference's rag-worker Deployment (``arq
worker.worker.WorkerSettings`` with a Prometheus server on :9000,
rag_worker/src/worker/worker.py:24-47,182-187): consumes jobs from the
Redis queue, runs the agent, emits progress over the Redis bus, and serves
``/metrics`` on METRICS_PORT for annotation-based Prometheus scraping.

The single-pod mode (``python -m githubrepostorag_tpu.api``) embeds this
worker in-process; this entrypoint exists for the split deployment where
rag-api and rag-worker are separate pods joined by Redis, as in the
reference helm chart.
"""

from __future__ import annotations

import argparse
import asyncio

from aiohttp import web

from githubrepostorag_tpu.config import get_settings
from githubrepostorag_tpu.utils.logging import get_logger

logger = get_logger(__name__)


async def _start_metrics_server(port: int) -> None:
    from githubrepostorag_tpu import metrics

    async def metrics_handler(request: web.Request) -> web.Response:
        return web.Response(body=metrics.render(), content_type="text/plain")

    app = web.Application()
    app.router.add_get("/metrics", metrics_handler)
    runner = web.AppRunner(app)
    await runner.setup()
    await web.TCPSite(runner, "0.0.0.0", port).start()
    logger.info("worker metrics on :%d/metrics", port)


async def serve() -> None:
    from githubrepostorag_tpu.agent import GraphAgent
    from githubrepostorag_tpu.events.redis import RedisBus, RedisCancelFlags, RedisJobQueue
    from githubrepostorag_tpu.llm import set_llm
    from githubrepostorag_tpu.metrics import MeteredLLM
    from githubrepostorag_tpu.worker.worker import RagWorker
    from githubrepostorag_tpu.api.__main__ import _build_llm
    from githubrepostorag_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    s = get_settings()
    await _start_metrics_server(s.metrics_port)
    raw_llm = _build_llm()
    set_llm(raw_llm)
    agent = GraphAgent(MeteredLLM(raw_llm))
    worker = RagWorker(agent, RedisBus(), RedisCancelFlags(), RedisJobQueue())
    await worker.run_forever()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description="RAG worker (Redis queue consumer)")
    parser.parse_args(argv)
    asyncio.run(serve())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
