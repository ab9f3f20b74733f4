"""Store backend selection (STORE_BACKEND env: memory | native | cassandra)."""

from __future__ import annotations

from githubrepostorag_tpu.config import get_settings
from githubrepostorag_tpu.store.base import VectorStore

_store: VectorStore | None = None


def get_store() -> VectorStore:
    global _store
    if _store is None:
        _store = _build()
    return _store


def reset_store() -> None:
    global _store
    applier = getattr(_store, "applier", None)
    if applier is not None:  # live-index front: stop the drain thread
        from githubrepostorag_tpu.retrieval.live_index import register_live_applier

        applier.stop()
        register_live_applier(None)
    _store = None


def set_store(store: VectorStore) -> None:
    """Inject a store (tests / embedded deployments)."""
    global _store
    _store = store


def _device_index_enabled(s) -> bool:
    """DEVICE_INDEX=auto wraps the store on TPU only; on/off force it."""
    mode = s.device_index.strip().lower()
    if mode in {"on", "1", "true", "yes"}:
        return True
    if mode not in {"auto", ""}:
        return False
    from githubrepostorag_tpu.runtime import on_tpu

    return on_tpu()


def _build() -> VectorStore:
    s = get_settings()
    backend = s.store_backend.lower()
    if backend == "memory":
        from githubrepostorag_tpu.store.memory import MemoryVectorStore

        store: VectorStore = MemoryVectorStore(persist_dir=s.store_path or None)
    elif backend == "native":
        from githubrepostorag_tpu.store.native import NativeVectorStore

        store = NativeVectorStore(persist_dir=s.store_path or None)
    elif backend == "cassandra":
        from githubrepostorag_tpu.store.cassandra import CassandraVectorStore

        store = CassandraVectorStore(
            hosts=[s.cassandra_host],
            port=s.cassandra_port,
            username=s.cassandra_username,
            password=s.cassandra_password,
            keyspace=s.cassandra_keyspace,
            embed_dim=s.embed_dim,
        )
    else:
        raise ValueError(f"Unknown STORE_BACKEND: {s.store_backend!r}")
    if _device_index_enabled(s):
        import jax

        from githubrepostorag_tpu.retrieval.device_index import DeviceIndexedStore

        mesh = None
        if jax.device_count() > 1:
            from githubrepostorag_tpu.parallel import make_mesh, plan_for_devices

            mesh = make_mesh(plan_for_devices(jax.device_count(), role="ingest"))
        store = DeviceIndexedStore(
            store,
            mesh=mesh,
            k_bucket=s.device_index_k_bucket,
            max_wave=s.retrieval_max_wave,
        )
    if s.live_index.strip().lower() in {"on", "1", "true", "yes"}:
        store = _wrap_live_index(store, s)
    return store


def _wrap_live_index(store: VectorStore, s) -> VectorStore:
    """LIVE_INDEX=on: writes append to the watermarked mutation log, a
    daemon apply loop drains them into the wrapped store while queries
    run, and the applier registers for /debug/index."""
    import os

    from githubrepostorag_tpu.ingest.stream import MutationLog
    from githubrepostorag_tpu.retrieval.live_index import (
        LiveIndexApplier,
        LiveIndexedStore,
        register_live_applier,
    )

    log_path = s.live_index_log_path or (
        os.path.join(s.data_dir, "mutation_log.jsonl") if s.data_dir else "")
    log = MutationLog(path=log_path or None)
    applier = LiveIndexApplier(
        log,
        store,
        apply_batch=s.live_index_apply_batch,
        compact_interval_s=s.index_compact_interval_s,
        compact_min_holes=s.index_compact_min_holes,
        compact_max_hole_fraction=s.index_compact_max_hole_fraction,
    ).start()
    register_live_applier(applier)
    return LiveIndexedStore(store, log, applier)
