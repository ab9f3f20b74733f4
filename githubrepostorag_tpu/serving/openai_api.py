"""OpenAI-compatible HTTP front end over the AsyncEngine (aiohttp).

Drop-in replacement for the vLLM server the reference deploys
(helm/templates/qwen-deployment.yaml: ``vllm/vllm-openai`` serving
``POST /v1/chat/completions`` + ``GET /health`` probes): every client in the
system — the worker's QwenLLM (qwen_llm.py:119), ingest's llm_init
(llm_init.py:100), and the Helm health probes — keeps speaking the same
protocol.  Unlike the reference's clients, streaming here is real token
streaming (SSE chunks), not the faked stream_complete of qwen_llm.py:149-151.

Endpoints: POST /v1/chat/completions (stream + non-stream),
POST /v1/completions, GET /v1/models, GET /health.
"""

from __future__ import annotations

import asyncio
import json
import time
import uuid

from aiohttp import web

from githubrepostorag_tpu.obs.engine_profile import record_engine_spans
from githubrepostorag_tpu.obs.trace import current_context, root_span
from githubrepostorag_tpu.serving.async_engine import AsyncEngine
from githubrepostorag_tpu.serving.sampling_params import SamplingParams
from githubrepostorag_tpu.serving.tokenizer import StreamingDetokenizer, Tokenizer
from githubrepostorag_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _sampling_from_request(body: dict, tokenizer: Tokenizer, default_max: int) -> SamplingParams:
    stop = body.get("stop") or ()
    if isinstance(stop, str):
        stop = (stop,)
    return SamplingParams(
        temperature=float(body.get("temperature", 0.7)),
        top_p=float(body.get("top_p", 0.9)),
        top_k=int(body.get("top_k", 0)),
        max_tokens=int(
            body.get("max_completion_tokens") or body.get("max_tokens") or default_max
        ),
        repetition_penalty=float(body.get("repetition_penalty", 1.0)),
        stop_token_ids=(tokenizer.eos_token_id,) if tokenizer.eos_token_id is not None else (),
        stop=tuple(stop),
    )


class OpenAIServer:
    def __init__(
        self,
        async_engine: AsyncEngine,
        tokenizer: Tokenizer,
        model_name: str = "githubrepostorag-tpu",
        default_max_tokens: int = 1024,
    ) -> None:
        self.engine = async_engine
        self.tokenizer = tokenizer
        self.model_name = model_name
        self.default_max_tokens = default_max_tokens
        self._runner: web.AppRunner | None = None

    # ------------------------------------------------------------- wiring

    def make_app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_get("/v1/models", self.models)
        app.router.add_get("/health", self.health)
        app.router.add_get("/debug/slo", self.debug_slo)
        app.router.add_get("/debug/fleet", self.debug_fleet)
        app.router.add_get("/debug/index", self.debug_index)
        app.router.add_get("/debug/hbm", self.debug_hbm)
        app.router.add_get("/debug/timeline", self.debug_timeline)
        app.router.add_post("/debug/fleet/drain", self.fleet_drain)
        app.router.add_post("/debug/fleet/activate", self.fleet_activate)
        return app

    async def start(self, host: str = "0.0.0.0", port: int = 8000) -> int:
        """Start serving; returns the bound port (pass port=0 for ephemeral)."""
        await self.engine.start()
        self._runner = web.AppRunner(self.make_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        bound = site._server.sockets[0].getsockname()[1]  # noqa: SLF001
        logger.info("OpenAI-compatible server on %s:%d", host, bound)
        return bound

    async def stop(self) -> None:
        # capture-and-clear before awaiting: two concurrent stop() calls must
        # not both see the runner and double-cleanup it
        runner, self._runner = self._runner, None
        if runner is not None:
            await runner.cleanup()
        await self.engine.stop()

    # ------------------------------------------------------------ handlers

    async def health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok", **self.engine.stats()})

    async def debug_slo(self, request: web.Request) -> web.Response:
        from githubrepostorag_tpu.obs.slo import get_slo_plane

        return web.json_response(get_slo_plane().slo_payload())

    async def debug_fleet(self, request: web.Request) -> web.Response:
        from githubrepostorag_tpu.obs.slo import get_slo_plane

        return web.json_response(get_slo_plane().fleet_payload())

    async def debug_index(self, request: web.Request) -> web.Response:
        from githubrepostorag_tpu.retrieval.live_index import live_index_payload

        return web.json_response(live_index_payload())

    async def debug_hbm(self, request: web.Request) -> web.Response:
        from githubrepostorag_tpu.obs.hbm import get_hbm_plane

        return web.json_response(get_hbm_plane().payload())

    async def debug_timeline(self, request: web.Request) -> web.Response:
        """One Perfetto trace for the recent past (?window_s= bounds it);
        save the body and open it in ui.perfetto.dev."""
        from githubrepostorag_tpu.obs.timeline import build_timeline

        try:
            window_s = float(request.query["window_s"]) \
                if "window_s" in request.query else None
        except ValueError:
            return _error_response("window_s must be a number", status=400)
        return web.json_response(build_timeline(window_s=window_s))

    async def _fleet_lifecycle(self, request: web.Request, verb: str) -> web.Response:
        """Shared body for POST /debug/fleet/{drain,activate}: duck-typed on
        the engine being a MultiAsyncEngine (single-engine servers 404)."""
        action = getattr(self.engine, verb, None)
        if action is None:
            return _error_response("fleet lifecycle requires replica groups",
                                   status=404)
        try:
            body = await request.json()
            replica = body["replica"]
        except (json.JSONDecodeError, KeyError, TypeError) as exc:
            return _error_response(f"invalid request body: {exc}", status=400)
        try:
            return web.json_response(await action(replica))
        except KeyError:
            return _error_response(f"unknown replica {replica!r}", status=404)

    async def fleet_drain(self, request: web.Request) -> web.Response:
        return await self._fleet_lifecycle(request, "drain")

    async def fleet_activate(self, request: web.Request) -> web.Response:
        return await self._fleet_lifecycle(request, "activate")

    async def models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {"id": self.model_name, "object": "model", "owned_by": "githubrepostorag-tpu"}
                ],
            }
        )

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        recv_t = time.monotonic()  # the request record's first stamp
        with _request_span(request):
            try:
                body = await request.json()
                messages = body["messages"]
            except (json.JSONDecodeError, KeyError) as exc:
                return _error_response(f"invalid request body: {exc}", status=400)
            if hasattr(self.tokenizer, "encode_chat"):
                prompt_ids = self.tokenizer.encode_chat(messages)
            else:  # pragma: no cover - all in-tree tokenizers have encode_chat
                prompt_ids = self.tokenizer.encode(
                    self.tokenizer.apply_chat_template(messages)
                )
            return await self._serve(request, body, prompt_ids, chat=True, recv_t=recv_t)

    async def completions(self, request: web.Request) -> web.StreamResponse:
        recv_t = time.monotonic()
        with _request_span(request):
            try:
                body = await request.json()
                prompt = body["prompt"]
            except (json.JSONDecodeError, KeyError) as exc:
                return _error_response(f"invalid request body: {exc}", status=400)
            prompt_ids = self.tokenizer.encode(prompt)
            return await self._serve(request, body, prompt_ids, chat=False, recv_t=recv_t)

    # ------------------------------------------------------------- core

    async def _serve(
        self, request: web.Request, body: dict, prompt_ids: list[int], chat: bool,
        recv_t: float,
    ) -> web.StreamResponse:
        sampling = _sampling_from_request(body, self.tokenizer, self.default_max_tokens)
        rid = f"chatcmpl-{uuid.uuid4().hex}" if chat else f"cmpl-{uuid.uuid4().hex}"
        # SLO priority class; unknown strings are just new classes (the
        # monitor keys on them), so no validation beyond type
        from githubrepostorag_tpu.config import get_settings

        priority = str(
            body.get("priority") or get_settings().priority_default_class)
        if body.get("stream"):
            return await self._serve_stream(request, sampling, prompt_ids, rid, chat,
                                            priority=priority, recv_t=recv_t)

        detok = StreamingDetokenizer(self.tokenizer)
        text_parts: list[str] = []
        result = None
        stopped_on_string = False
        async for event in self.engine.stream(prompt_ids, sampling, request_id=rid,
                                              priority=priority, recv_t=recv_t):
            if event.type == "token":
                text_parts.append(detok.push(event.token_id))
                full = "".join(text_parts)
                hit = _find_stop(full, sampling.stop)
                if hit is not None:
                    await self.engine.cancel(rid)
                    text_parts = [full[:hit]]
                    stopped_on_string = True
            elif event.type == "final":
                result = event.result
                record_engine_spans(result, parent=current_context())
            # "parked" (preempt-to-host) is advisory: the request resumes
            # token-identically, so just keep waiting
        text_parts.append("" if stopped_on_string else detok.flush())
        text = "".join(text_parts)
        finish = "stop" if stopped_on_string else _map_finish(result)
        usage = {
            "prompt_tokens": len(prompt_ids),
            "completion_tokens": len(result.output_tokens) if result else 0,
            "total_tokens": len(prompt_ids) + (len(result.output_tokens) if result else 0),
        }
        if result is not None and result.finish_reason == "error":
            return _error_response(result.error or "generation failed", status=400)
        if chat:
            payload = {
                "id": rid,
                "object": "chat.completion",
                "created": int(time.time()),
                "model": self.model_name,
                "choices": [
                    {
                        "index": 0,
                        "message": {"role": "assistant", "content": text},
                        "finish_reason": finish,
                    }
                ],
                "usage": usage,
            }
        else:
            payload = {
                "id": rid,
                "object": "text_completion",
                "created": int(time.time()),
                "model": self.model_name,
                "choices": [{"index": 0, "text": text, "finish_reason": finish}],
                "usage": usage,
            }
        return web.json_response(payload)

    async def _serve_stream(
        self,
        request: web.Request,
        sampling: SamplingParams,
        prompt_ids: list[int],
        rid: str,
        chat: bool,
        priority: str = "interactive",
        recv_t: float | None = None,
    ) -> web.StreamResponse:
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
            },
        )
        await resp.prepare(request)

        async def send(obj: dict) -> None:
            await resp.write(f"data: {json.dumps(obj, ensure_ascii=False)}\n\n".encode())

        detok = StreamingDetokenizer(self.tokenizer)
        emitted = ""
        finish = None
        try:
            async for event in self.engine.stream(prompt_ids, sampling, request_id=rid,
                                                  priority=priority, recv_t=recv_t):
                if event.type == "token":
                    delta = detok.push(event.token_id)
                    emitted += delta
                    hit = _find_stop(emitted, sampling.stop)
                    if hit is not None:
                        overshoot = len(emitted) - hit
                        if overshoot < len(delta):
                            delta = delta[: len(delta) - overshoot]
                            if delta:
                                await send(self._chunk(rid, chat, delta, None))
                        await self.engine.cancel(rid)
                        finish = "stop"
                        continue
                    if delta and finish is None:
                        await send(self._chunk(rid, chat, delta, None))
                elif event.type == "final":
                    record_engine_spans(event.result, parent=current_context())
                    if finish is None:
                        tail = detok.flush()
                        if tail:
                            await send(self._chunk(rid, chat, tail, None))
                        finish = _map_finish(event.result)
            await send(self._chunk(rid, chat, None, finish or "stop"))
            await resp.write(b"data: [DONE]\n\n")
        except asyncio.CancelledError:
            await self.engine.cancel(rid)
            raise
        except (ConnectionError, OSError):  # client went away mid-stream
            await self.engine.cancel(rid)
            logger.info("client disconnected mid-stream, cancelled %s", rid)
            return resp
        await resp.write_eof()
        return resp

    def _chunk(self, rid: str, chat: bool, content: str | None, finish: str | None) -> dict:
        if chat:
            delta = {"content": content} if content is not None else {}
            return {
                "id": rid,
                "object": "chat.completion.chunk",
                "created": int(time.time()),
                "model": self.model_name,
                "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
            }
        return {
            "id": rid,
            "object": "text_completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [{"index": 0, "text": content or "", "finish_reason": finish}],
        }


def _request_span(request: web.Request):
    """Root span per generation request, as the API pod opens one per /rag
    request (api/app.py): an incoming ``traceparent`` is continued, and
    TRACE_SAMPLE decides whether the flight recorder keeps it."""
    return root_span(f"http {request.method} {request.path}",
                     wire=request.headers.get("traceparent"))


def _find_stop(text: str, stops: tuple[str, ...]) -> int | None:
    best = None
    for s in stops:
        if not s:
            continue
        idx = text.find(s)
        if idx != -1 and (best is None or idx < best):
            best = idx
    return best


def _map_finish(result) -> str:
    if result is None:
        return "stop"
    return {"stop": "stop", "length": "length", "cancelled": "stop", "error": "error"}.get(
        result.finish_reason, "stop"
    )


def _error_response(message: str, status: int = 400) -> web.Response:
    return web.json_response(
        {"error": {"message": message, "type": "invalid_request_error"}}, status=status
    )
