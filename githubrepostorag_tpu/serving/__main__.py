"""Standalone model-server pod: ``python -m githubrepostorag_tpu.serving``.

This is the in-tree replacement for the reference's vLLM Deployment
(helm/templates/qwen-deployment.yaml:19-71 runs ``vllm/vllm-openai`` with
``--model ... --max-model-len 11712 --max-num-seqs 4``): the same
OpenAI-compatible surface (/v1/chat/completions, /v1/completions,
/v1/models, /health) served by the JAX paged-KV engine on TPU.  Worker and
ingest pods point QWEN_ENDPOINT here and set LLM_BACKEND=http, exactly as
their reference counterparts pointed at the vLLM service.
"""

from __future__ import annotations

import argparse
import asyncio

from githubrepostorag_tpu.config import get_settings
from githubrepostorag_tpu.utils.logging import get_logger

logger = get_logger(__name__)


async def serve(host: str, port: int) -> None:
    import jax
    import ml_dtypes

    from githubrepostorag_tpu.models.hf_loader import load_qwen2
    from githubrepostorag_tpu.runtime import enable_compile_cache, on_tpu
    from githubrepostorag_tpu.serving.async_engine import AsyncEngine
    from githubrepostorag_tpu.serving.engine import Engine
    from githubrepostorag_tpu.serving.openai_api import OpenAIServer
    from githubrepostorag_tpu.serving.tokenizer import make_tokenizer

    from githubrepostorag_tpu.parallel import (
        MeshPlan,
        make_mesh,
        maybe_initialize_distributed,
        plan_for_devices,
    )

    enable_compile_cache()
    maybe_initialize_distributed()  # multi-host pod -> global device list
    pallas = on_tpu()  # raises if JAX fell back to a CPU nobody asked for
    s = get_settings()
    if not s.model_weights_path:
        raise SystemExit("model server requires MODEL_WEIGHTS_PATH (a local HF checkpoint dir)")
    logger.info(
        "loading weights from %s%s", s.model_weights_path,
        f" (int{s.quantize_weights} weight-only)" if s.quantize_weights else "",
    )
    n = len(jax.devices())
    # Plan the mesh from config.json ALONE, before any weights move: the
    # plan decides both the sharding below and whether load_qwen2 should
    # pre-fuse the projection weights (single-chip serving layout) while
    # the tree is the only thing on the device — one source of truth for
    # both decisions.  MESH_SHAPE overrides the automatic plan (vLLM's
    # --tensor-parallel-size equivalent; reference runs TP=1 on one GPU —
    # helm/templates/qwen-deployment.yaml:44-46).
    import json as _json
    from pathlib import Path as _Path

    from githubrepostorag_tpu.models.hf_loader import config_from_hf

    cfg = config_from_hf(
        _json.loads((_Path(s.model_weights_path) / "config.json").read_text()))
    if s.mesh_shape:
        from githubrepostorag_tpu.parallel import plan_from_string

        plan = plan_from_string(s.mesh_shape)
        if plan.pp > 1:
            # the serving engine shards over tp (params/pools/kernel), sp
            # (ring prefill), ep (MoE expert stacks), and dp (in-process
            # engine replicas); pipeline stages have no serving schedule
            raise SystemExit(
                f"MESH_SHAPE={s.mesh_shape!r}: serving supports tp/sp/ep/dp "
                "axes — pp is a training-side axis (training/pipeline.py)"
            )
        if plan.ep > 1 and cfg.num_experts == 0:
            raise SystemExit(
                f"MESH_SHAPE={s.mesh_shape!r}: ep shards the expert stacks of "
                f"an MoE checkpoint, but {s.model_weights_path} is a dense "
                "model (num_experts=0) — ep chips would replicate its work; "
                "use tp/sp instead"
            )
    else:
        plan = plan_for_devices(
            n, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads, role="serve"
        )
        plan = MeshPlan(tp=plan.tp)

    params, cfg = load_qwen2(
        s.model_weights_path, dtype=ml_dtypes.bfloat16, quantize=s.quantize_weights,
        fuse=plan.n_devices == 1,  # mesh=None below iff the plan is one chip
    )

    # tokenizer first: a broken tokenizer config must fail fast, not after
    # minutes of XLA warmup compiles
    tokenizer = make_tokenizer(s.model_weights_path)
    logger.info("tokenizer: %s", type(tokenizer).__name__)

    def build_engine(mesh) -> Engine:
        from githubrepostorag_tpu.serving.engine import derive_sp_prefill_threshold

        sp_threshold = derive_sp_prefill_threshold(
            sp=mesh.shape.get("sp", 1) if mesh is not None else 1,
            explicit=s.sp_prefill_threshold,
            env_set=s.sp_prefill_threshold_set,
            prefill_chunk=s.prefill_chunk,
            max_seq_len=s.context_window,
        )
        return Engine(
            params, cfg,
            max_num_seqs=s.max_num_seqs,
            num_pages=s.kv_num_pages,
            page_size=s.kv_page_size,
            max_seq_len=s.context_window,
            prefill_chunk=s.prefill_chunk,
            prefill_token_budget=s.prefill_token_budget or None,
            use_pallas=pallas,
            kv_quant=s.kv_quant,
            mesh=mesh,
            prefix_caching=s.prefix_caching,
            kv_tier=s.kv_tier,
            kv_host_pool_pages=s.kv_host_pool_pages,
            kv_migrate_burst=s.kv_migrate_burst,
            prefill_priority=s.prefill_priority,
            sp_prefill_threshold=sp_threshold,
            sp_ring_buckets=s.sp_ring_buckets,
            preempt=s.preempt,
            preempt_headroom_pages=s.preempt_headroom_pages,
            default_priority=s.priority_default_class,
            protected_priority=s.priority_protected_class,
        )

    if plan.dp > 1:
        # dp-grouped in-process replicas, one per disjoint submesh
        # (serving/multi_engine.py); requests load-balance at admission
        from githubrepostorag_tpu.serving.multi_engine import (
            MultiAsyncEngine,
            dp_submeshes,
        )

        meshes, groups = dp_submeshes(plan)
        logger.info(
            "dp serving: %d engine replicas x %d devices each (%s)",
            plan.dp, len(groups[0]), dict(meshes[0].shape),
        )
        engines = []
        for i, m in enumerate(meshes):
            logger.info("precompiling engine replica %d/%d", i + 1, plan.dp)
            eng = build_engine(m)
            eng.warmup()
            engines.append(eng)
        # FLEET_SPARES trailing replicas boot warm (weights loaded,
        # programs compiled) but admit nothing until the controller — or
        # POST /debug/fleet/activate — promotes them
        spares = max(0, min(s.fleet_spares, plan.dp - 1))
        if spares:
            logger.info("fleet: %d active + %d warm spare replica(s)",
                        plan.dp - spares, spares)
        async_engine = MultiAsyncEngine(engines, spares=spares)
    else:
        mesh = make_mesh(plan) if plan.n_devices > 1 else None
        if mesh is not None:
            logger.info("serving mesh %s over %d devices", dict(mesh.shape), n)
            if plan.n_devices < n:
                axes = [
                    f"{name}:{size}"
                    for name, size in plan.shape().items()
                    if size > 1
                ] + [f"dp:{n // plan.n_devices}"]
                logger.info(
                    "%d devices idle (MESH_SHAPE=%s would run %d engine "
                    "replicas in this process)",
                    n - plan.n_devices, ",".join(axes), n // plan.n_devices,
                )
        logger.info("precompiling engine programs (prefill buckets + decode burst)")
        engine = build_engine(mesh)
        engine.warmup()
        async_engine = AsyncEngine(engine)
    server = OpenAIServer(async_engine, tokenizer, model_name=s.qwen_model)
    bound = await server.start(host=host, port=port)
    controller = None
    if s.ctrl == "on" and plan.dp > 1:
        # close the SLO loop: sense (ledger/burn/liveness) -> decide
        # (guarded action ladder) -> act (grow pool / shift spec-k /
        # spread affinity / fence + warm-spare failover).  Fleet-shaped
        # only: a single replica has no spare to fail over to.
        from githubrepostorag_tpu.serving.controller import FleetController

        restore = None
        if s.ctrl_snapshot_dir:
            from githubrepostorag_tpu.retrieval.snapshot import (
                restore_for_activation)
            from githubrepostorag_tpu.store.factory import get_store

            restore = lambda: restore_for_activation(  # noqa: E731
                s.ctrl_snapshot_dir, get_store())
        controller = FleetController(async_engine, restore=restore)
        await controller.start()
        logger.info("fleet controller up (tick %.2fs)", controller.tick_s)
    logger.info("model server up on %s:%d (backend=%s pallas=%s)",
                host, bound, jax.default_backend(), pallas)
    while True:  # serve until the pod is killed
        await asyncio.sleep(3600)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description="OpenAI-compatible TPU model server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    args = parser.parse_args(argv)
    asyncio.run(serve(args.host, args.port))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
