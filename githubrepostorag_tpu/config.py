"""Unified typed configuration for every service in the framework.

The reference scatters configuration across three places with duplicated and
conflicting definitions (rag_shared/config.py defines MAX_RAG_ATTEMPTS three
times and REDIS_URL twice with different defaults; ingest/src/app/config.py
has its own frozen dataclass; helm injects env vars per pod).  This module
consolidates everything into one frozen dataclass built from the *same
environment variable names* so existing deployments carry over unchanged.

Reference surface being unified (file:line in /root/reference):
  - rag_shared/config.py:1-47       (api + worker constants)
  - ingest/src/app/config.py:13-47  (SettingsConfig)
  - ingest/src/app/config.py:50-84  (EXTENSION_TO_LANGUAGE)
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields


def _env_bool(name: str, default: bool = False) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return str(val).strip().lower() in {"1", "true", "t", "yes", "y", "on"}


def _parse_quant_bits() -> int:
    """QUANTIZE_WEIGHTS -> bit width (0 = off).  Raises on typos rather
    than silently loading full-precision weights."""
    raw = os.environ.get("QUANTIZE_WEIGHTS", "")
    val = str(raw).strip().lower()
    if val in {"", "0", "false", "f", "no", "n", "off"}:
        return 0
    if val in {"1", "true", "t", "yes", "y", "on", "int8", "8"}:
        return 8
    if val in {"int4", "4", "awq"}:
        return 4
    raise ValueError(
        f"QUANTIZE_WEIGHTS={raw!r} not understood; use int4, int8, or a boolean"
    )


def _parse_kv_quant() -> int:
    """KV_QUANT -> page bit width (0 = full precision, 8 = int8, 4 = int4
    nibble-packed pages).  Int values stay truthiness-compatible with the
    historical boolean knob (`if kv_quant:` sites keep working); typos
    raise rather than silently serving full-precision pages."""
    raw = os.environ.get("KV_QUANT", "")
    val = str(raw).strip().lower()
    if val in {"", "0", "false", "f", "no", "n", "off"}:
        return 0
    if val in {"1", "true", "t", "yes", "y", "on", "int8", "8"}:
        return 8
    if val in {"int4", "4"}:
        return 4
    raise ValueError(
        f"KV_QUANT={raw!r} not understood; use int4, int8, or a boolean"
    )


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


@dataclass(frozen=True)
class Settings:
    """All knobs, one place.  Field defaults match the reference's env names
    and values exactly (last-definition-wins where the reference conflicted)."""

    # --- Logging ---
    log_level: str = field(default_factory=lambda: os.getenv("LOG_LEVEL", "INFO"))

    # --- Event bus / job queue (Redis-compatible; in-memory fake for tests) ---
    redis_url: str = field(default_factory=lambda: os.getenv("REDIS_URL", "redis://redis-master:6379/0"))
    sse_ping_seconds: int = field(default_factory=lambda: _env_int("SSE_PING_SECONDS", 15))
    # API-side SSE heartbeat: a ``: heartbeat`` comment frame is written
    # whenever the bus stream stays silent this long, so proxies and
    # EventSource clients never see a dead-quiet connection even when the
    # bus itself is wedged (bus pings stop when its connection dies)
    sse_heartbeat_seconds: float = field(default_factory=lambda: _env_float("SSE_HEARTBEAT_SECONDS", 15.0))

    # --- Resilience (resilience/ package) ---
    # admission bound: create_job sheds with 429 + Retry-After once the
    # queue holds this many undequeued jobs
    job_queue_max_depth: int = field(default_factory=lambda: _env_int("JOB_QUEUE_MAX_DEPTH", 256))
    # jittered-exponential retry schedule for supervised paths (bus emit,
    # worker dequeue): delay(n) = uniform(d/2, d), d = min(cap, base*2^n)
    retry_max_attempts: int = field(default_factory=lambda: _env_int("RETRY_MAX_ATTEMPTS", 4))
    retry_base_seconds: float = field(default_factory=lambda: _env_float("RETRY_BASE_SECONDS", 0.05))
    retry_cap_seconds: float = field(default_factory=lambda: _env_float("RETRY_CAP_SECONDS", 2.0))
    # per-dependency circuit breakers: open after N consecutive failures,
    # probe again after reset_seconds (resilience/policy.py)
    breaker_failure_threshold: int = field(default_factory=lambda: _env_int("BREAKER_FAILURE_THRESHOLD", 5))
    breaker_reset_seconds: float = field(default_factory=lambda: _env_float("BREAKER_RESET_SECONDS", 30.0))
    # deterministic fault injection spec, e.g.
    # "redis.send:drop@3;cql.exchange:error@0.5;llm.complete:delay=2"
    # (resilience/faults.py; empty = injection compiled out of the hot path)
    faults: str = field(default_factory=lambda: os.getenv("FAULTS", ""))
    faults_seed: int = field(default_factory=lambda: _env_int("FAULTS_SEED", 0))

    # --- Agent loop budget ---
    max_rag_attempts: int = field(default_factory=lambda: _env_int("MAX_RAG_ATTEMPTS", 3))
    min_source_nodes: int = field(default_factory=lambda: _env_int("MIN_SOURCE_NODES", 1))
    router_top_k: int = field(default_factory=lambda: _env_int("ROUTER_TOP_K", 5))
    # whole-repo long-context answer mode: architecture-class questions
    # skip chunk RAG and feed the assembled repo (retrieval/assembler.py)
    # through the serving stack's ring-prefill path as ONE prompt
    agent_longctx: bool = field(default_factory=lambda: _env_bool("AGENT_LONGCTX", True))
    # token budget for an assembled repo prompt; an over-budget repo falls
    # back to chunk RAG.  0 = derive from the serving context window,
    # leaving room for the answer (retrieval/assembler.py)
    longctx_token_budget: int = field(
        default_factory=lambda: _env_int("LONGCTX_TOKEN_BUDGET", 0))

    # --- Vector store (Cassandra-compatible; in-memory / native store for local) ---
    cassandra_host: str = field(default_factory=lambda: os.getenv("CASSANDRA_HOST", "localhost"))
    cassandra_port: int = field(default_factory=lambda: _env_int("CASSANDRA_PORT", 9042))
    cassandra_username: str = field(default_factory=lambda: os.getenv("CASSANDRA_USERNAME", "cassandra"))
    cassandra_password: str = field(default_factory=lambda: os.getenv("CASSANDRA_PASSWORD", "cassandra"))
    cassandra_keyspace: str = field(default_factory=lambda: os.getenv("CASSANDRA_KEYSPACE", "vector_store"))
    store_backend: str = field(default_factory=lambda: os.getenv("STORE_BACKEND", "memory"))  # memory|native|cassandra
    store_path: str = field(default_factory=lambda: os.getenv("STORE_PATH", ""))  # persistence dir for memory/native

    # Five-level hierarchy tables (cassandra-initdb-configmap.yaml:14-102)
    embeddings_table_catalog: str = field(default_factory=lambda: os.getenv("EMBEDDINGS_TABLE_CATALOG", "embeddings_catalog"))
    embeddings_table_repo: str = field(default_factory=lambda: os.getenv("EMBEDDINGS_TABLE_REPO", "embeddings_repo"))
    embeddings_table_module: str = field(default_factory=lambda: os.getenv("EMBEDDINGS_TABLE_MODULE", "embeddings_module"))
    embeddings_table_file: str = field(default_factory=lambda: os.getenv("EMBEDDINGS_TABLE_FILE", "embeddings_file"))
    embeddings_table_chunk: str = field(
        default_factory=lambda: os.getenv("EMBEDDINGS_TABLE_CHUNK", os.getenv("EMBEDDINGS_TABLE", "embeddings"))
    )

    # --- Embeddings ---
    embed_model: str = field(default_factory=lambda: os.getenv("EMBED_MODEL", "intfloat/e5-small-v2"))
    embed_dim: int = field(default_factory=lambda: _env_int("EMBED_DIM", 384))

    # --- Retrieval (device index + query coalescing) ---
    # "auto" = wrap the store in the device-resident top-k index
    # (retrieval/device_index.py) when running on TPU; "on"/"off" force it.
    # CPU auto stays off: per-bucket XLA compiles cost more than they save
    # at dev scale, and tests construct DeviceIndexedStore explicitly.
    device_index: str = field(default_factory=lambda: os.getenv("DEVICE_INDEX", "auto"))
    # coalesce concurrent retrieve() calls into one encoder forward + one
    # search dispatch per wave (retrieval/coalescer.py); a wave of one is
    # identical to the direct path, so this defaults ON
    retrieval_coalesce: bool = field(default_factory=lambda: _env_bool("RETRIEVAL_COALESCE", True))
    # max queries per coalesced wave AND the top query-bucket the device
    # index warms (power-of-two buckets 1..max_wave)
    retrieval_max_wave: int = field(default_factory=lambda: _env_int("RETRIEVAL_MAX_WAVE", 16))
    # static k for the jitted top-k program; requests with k above this
    # fall back to the host store (counted in rag_device_index_searches_total)
    device_index_k_bucket: int = field(default_factory=lambda: _env_int("DEVICE_INDEX_K_BUCKET", 16))
    # --- Live index (ingest/stream.py + retrieval/live_index.py) ---
    # "on" routes store writes through the watermarked mutation log and
    # starts the background apply loop + compactor (get_store() returns
    # the LiveIndexedStore front); "off" (default) keeps direct writes.
    live_index: str = field(default_factory=lambda: os.getenv("LIVE_INDEX", "off"))
    # durable JSONL append file for the log; empty = in-memory only
    # (DATA_DIR/mutation_log.jsonl when DATA_DIR is set)
    live_index_log_path: str = field(default_factory=lambda: os.getenv("LIVE_INDEX_LOG_PATH", ""))
    # max mutation ops per apply drain (one batch = one watermark advance)
    live_index_apply_batch: int = field(default_factory=lambda: _env_int("LIVE_INDEX_APPLY_BATCH", 64))
    # background compactor: idle-scan period, and the two hole triggers —
    # absolute count OR fraction of the table's capacity bucket
    index_compact_interval_s: float = field(
        default_factory=lambda: _env_float("INDEX_COMPACT_INTERVAL_S", 5.0))
    index_compact_min_holes: int = field(
        default_factory=lambda: _env_int("INDEX_COMPACT_MIN_HOLES", 64))
    index_compact_max_hole_fraction: float = field(
        default_factory=lambda: _env_float("INDEX_COMPACT_MAX_HOLE_FRACTION", 0.25))

    # --- LLM serving (in-tree TPU engine; endpoint kept for split deploys) ---
    qwen_endpoint: str = field(default_factory=lambda: os.getenv("QWEN_ENDPOINT", "http://qwen:8000"))
    qwen_model: str = field(default_factory=lambda: os.getenv("QWEN_MODEL", "Qwen/Qwen2.5-3B-Instruct"))
    qwen_max_output: int = field(default_factory=lambda: _env_int("QWEN_MAX_OUTPUT", 4096))
    qwen_temperature: float = field(default_factory=lambda: _env_float("QWEN_TEMPERATURE", 0.7))
    qwen_top_p: float = field(default_factory=lambda: _env_float("QWEN_TOP_P", 0.9))
    context_window: int = field(default_factory=lambda: _env_int("CONTEXT_WINDOW", 11712))
    llm_backend: str = field(default_factory=lambda: os.getenv("LLM_BACKEND", "inprocess"))  # inprocess|http|fake
    model_weights_path: str = field(default_factory=lambda: os.getenv("MODEL_WEIGHTS_PATH", ""))
    # Weight-only quantization at load (fits 7B on one 16 GB chip; the
    # reference deploys 4-bit AWQ, values.yaml:67).  QUANTIZE_WEIGHTS
    # accepts int4 / int8 / the usual booleans (true -> int8); value is the
    # bit width (0 = off) and stays truthy/falsy for boolean callers.
    # Unrecognized values raise: a typo silently loading a 7B as bf16
    # would OOM the chip with no hint the env var was ignored.
    quantize_weights: int = field(default_factory=lambda: _parse_quant_bits())

    # --- Observability ---
    # trace sampling rate [0, 1]; 0 disables root-span creation entirely
    # (the span() fast path becomes a single contextvar read)
    trace_sample: float = field(default_factory=lambda: _env_float("TRACE_SAMPLE", 1.0))
    # flight-recorder ring-buffer bounds: O(traces * spans) memory, period
    trace_max_traces: int = field(default_factory=lambda: _env_int("TRACE_MAX_TRACES", 256))
    trace_max_spans: int = field(default_factory=lambda: _env_int("TRACE_MAX_SPANS", 128))
    # json (trace-stamped structured lines) | plain (human format)
    log_format: str = field(default_factory=lambda: os.getenv("LOG_FORMAT", "json"))
    # --- Deep observability (obs/continuous.py + obs/timeline.py) ---
    # continuous profiler: every Nth driver step captures a full step
    # anatomy + queue depths + pool snapshot into a bounded ring (0 = off);
    # the non-sampled steps pay one int increment + modulo
    profile_sample_every: int = field(
        default_factory=lambda: _env_int("PROFILE_SAMPLE_EVERY", 32))
    # continuous-profiler ring capacity (samples retained per replica)
    profile_ring: int = field(
        default_factory=lambda: _env_int("PROFILE_RING", 512))
    # default /debug/timeline export window when the request doesn't pass
    # ?window_s= (seconds of history merged into the Perfetto trace)
    timeline_window_s: float = field(
        default_factory=lambda: _env_float("TIMELINE_WINDOW_S", 120.0))
    # hard cap on exported trace events per timeline build; overflow is
    # reported in the trace metadata, never silently dropped
    timeline_max_events: int = field(
        default_factory=lambda: _env_int("TIMELINE_MAX_EVENTS", 20000))
    # --- SLO plane (obs/slo.py) + token ledger (obs/ledger.py) ---
    # objectives per priority class; thresholds in ms.  p50 objective gets a
    # 50% error budget (median), p99 a 1% budget, deadline-miss its own budget
    slo_ttft_p50_ms: float = field(default_factory=lambda: _env_float("SLO_TTFT_P50_MS", 1500.0))
    slo_ttft_p99_ms: float = field(default_factory=lambda: _env_float("SLO_TTFT_P99_MS", 5000.0))
    slo_tpot_ms: float = field(default_factory=lambda: _env_float("SLO_TPOT_MS", 100.0))
    slo_deadline_miss_budget: float = field(
        default_factory=lambda: _env_float("SLO_DEADLINE_MISS_BUDGET", 0.05))
    # the ``longctx`` priority class (whole-repo ring-prefill answers) gets
    # its own latency objectives: a packed ring pass over hundreds of KLoC
    # legitimately takes seconds of TTFT that would instantly burn the
    # interactive budget, while its decode phase is ordinary paged decode
    # and stays near the interactive TPOT.  These feed the same burn-rate
    # monitor/admission ladder as every other class (obs/slo.py), so
    # longctx traffic is throttled and preempted AGAINST, never allowed to
    # starve the protected class.
    slo_longctx_ttft_p50_ms: float = field(
        default_factory=lambda: _env_float("SLO_LONGCTX_TTFT_P50_MS", 15000.0))
    slo_longctx_ttft_p99_ms: float = field(
        default_factory=lambda: _env_float("SLO_LONGCTX_TTFT_P99_MS", 45000.0))
    slo_longctx_tpot_ms: float = field(
        default_factory=lambda: _env_float("SLO_LONGCTX_TPOT_MS", 150.0))
    # "short,long" rolling windows in seconds for multi-window burn rates
    slo_windows: str = field(default_factory=lambda: os.getenv("SLO_WINDOWS", "60,300"))
    # burn-rate thresholds (SRE canonical 14.4x/6x); a state transition fires
    # only when BOTH windows cross — the short window alone is too noisy
    slo_burn_warn: float = field(default_factory=lambda: _env_float("SLO_BURN_WARN", 6.0))
    slo_burn_critical: float = field(default_factory=lambda: _env_float("SLO_BURN_CRITICAL", 14.4))
    # token-ledger rolling window for goodput / MFU / limiter attribution
    slo_ledger_window_s: float = field(default_factory=lambda: _env_float("SLO_LEDGER_WINDOW_S", 60.0))
    # static FLOPs/token for MFU; 0 = derive ~2x param count from the model
    # config at engine construction (dense approximation, good to ~5%)
    model_flops_per_token: float = field(
        default_factory=lambda: _env_float("MODEL_FLOPS_PER_TOKEN", 0.0))

    # --- Priority classes & preempt-to-host scheduling ---
    # SLO class stamped on requests that arrive unlabeled (API job
    # envelope, OpenAI body, direct add_request)
    priority_default_class: str = field(
        default_factory=lambda: os.getenv("PRIORITY_DEFAULT_CLASS", "interactive"))
    # the protected latency class: headroom reservations and preemption
    # act FOR this class and AGAINST every other class
    priority_protected_class: str = field(
        default_factory=lambda: os.getenv("PRIORITY_PROTECTED_CLASS", "interactive"))
    # KV pages a batch-class admission must leave allocatable for the
    # protected class (0 = no reservation); doubles while the protected
    # class is in SLO warn
    preempt_headroom_pages: int = field(
        default_factory=lambda: _env_int("PREEMPT_HEADROOM_PAGES", 0))
    # page-granularity preempt-to-host: "on" requires the KV host tier,
    # "off" disables, "auto" enables iff the tier is on (resume rides the
    # claim/fault-in machinery, so a host pool is a hard prerequisite)
    preempt: str = field(default_factory=lambda: os.getenv("PREEMPT", "auto"))

    # --- Fleet router (serving/multi_engine.py) ---
    # auto = affinity when any replica runs a prefix-caching allocator,
    # on = always score prefixes, off = pure weighted least-loaded
    route_affinity: str = field(default_factory=lambda: os.getenv("ROUTE_AFFINITY", "auto"))
    # min interval between per-replica chain-digest rebuilds on the driver
    route_digest_interval_s: float = field(
        default_factory=lambda: _env_float("ROUTE_DIGEST_INTERVAL_S", 0.25))
    # shortest matchable prefix run (in pages) that counts as an affinity hit
    route_min_prefix_pages: int = field(
        default_factory=lambda: _env_int("ROUTE_MIN_PREFIX_PAGES", 1))
    # how many dp replicas start as warm spares (admit nothing until
    # activated — the controller's failover target); clamped so at least
    # one replica stays active
    fleet_spares: int = field(
        default_factory=lambda: _env_int("FLEET_SPARES", 0))

    # --- Self-healing fleet controller (serving/controller.py) ---
    # "on" starts the reconcile loop beside the serving pod; "off"
    # (default) leaves every actuator manual (POST /debug/fleet/*)
    ctrl: str = field(default_factory=lambda: os.getenv("CTRL", "off"))
    # reconcile cadence: sense -> decide -> act once per tick
    ctrl_tick_s: float = field(
        default_factory=lambda: _env_float("CTRL_TICK_S", 1.0))
    # consecutive agreeing ticks before a decision becomes an action
    ctrl_hysteresis_ticks: int = field(
        default_factory=lambda: _env_int("CTRL_HYSTERESIS_TICKS", 2))
    # per (replica, action) quiet period after an action executes
    ctrl_cooldown_s: float = field(
        default_factory=lambda: _env_float("CTRL_COOLDOWN_S", 30.0))
    # runaway-remediation budget: at most N actions per sliding window
    ctrl_max_actions: int = field(
        default_factory=lambda: _env_int("CTRL_MAX_ACTIONS", 4))
    ctrl_action_window_s: float = field(
        default_factory=lambda: _env_float("CTRL_ACTION_WINDOW_S", 300.0))
    # driver-step heartbeat older than this marks a replica wedged
    ctrl_liveness_timeout_s: float = field(
        default_factory=lambda: _env_float("CTRL_LIVENESS_TIMEOUT_S", 5.0))
    # hbm_pages remediation: host-pool growth factor and hard cap
    # (0 = 8x the device pool, matching the allocator's own scale)
    ctrl_host_pool_grow: float = field(
        default_factory=lambda: _env_float("CTRL_HOST_POOL_GROW", 1.5))
    ctrl_host_pool_max_pages: int = field(
        default_factory=lambda: _env_int("CTRL_HOST_POOL_MAX_PAGES", 0))
    # per-replica stat-collection deadline: a wedged driver lock yields a
    # stale_since row instead of hanging /debug/fleet
    ctrl_stats_timeout_s: float = field(
        default_factory=lambda: _env_float("CTRL_STATS_TIMEOUT_S", 0.25))
    # where the controller looks for the latest index snapshot when it
    # activates a warm spare ("" = activate cold, no restore)
    ctrl_snapshot_dir: str = field(
        default_factory=lambda: os.getenv("CTRL_SNAPSHOT_DIR", ""))

    # --- Disaggregated prefill/decode serving (serving/disagg.py) ---
    # "on" splits a >=2-replica tiered fleet into prefill-specialized and
    # decode-specialized replicas with KV page handoff between them;
    # "off" (default) runs every replica fused exactly as before.  Fleets
    # that can't disaggregate (single replica, non-tiered allocators)
    # stay fused regardless.
    disagg: str = field(default_factory=lambda: os.getenv("DISAGG", "off"))
    # how many active replicas specialize as prefill (the rest decode);
    # clamped so at least one decode replica remains
    disagg_prefill_replicas: int = field(
        default_factory=lambda: _env_int("DISAGG_PREFILL_REPLICAS", 1))
    # KV pages per transport send during a handoff (host-side chunking of
    # the shipped payload list; device pack/unpack always rides the
    # KV_MIGRATE_BURST gather/scatter ladder so no new shapes compile)
    disagg_transfer_burst: int = field(
        default_factory=lambda: _env_int("DISAGG_TRANSFER_BURST", 32))

    # --- Worker ---
    default_namespace: str = field(default_factory=lambda: os.getenv("DEFAULT_NAMESPACE", "default"))
    metrics_port: int = field(default_factory=lambda: _env_int("METRICS_PORT", 9000))
    worker_max_jobs: int = field(default_factory=lambda: _env_int("WORKER_MAX_JOBS", 10))
    job_timeout_seconds: int = field(default_factory=lambda: _env_int("JOB_TIMEOUT_SECONDS", 300))
    keep_result_seconds: int = field(default_factory=lambda: _env_int("KEEP_RESULT_SECONDS", 3600))

    # --- Ingest ---
    github_token: str = field(default_factory=lambda: os.getenv("GITHUB_TOKEN", ""))
    github_user: str = field(default_factory=lambda: os.getenv("GITHUB_USER", ""))
    data_dir: str = field(default_factory=lambda: os.getenv("DATA_DIR", ""))
    default_branch: str = field(default_factory=lambda: os.getenv("DEFAULT_BRANCH", "main"))
    default_collection: str = field(default_factory=lambda: os.getenv("DEFAULT_COLLECTION", "misc"))
    dev_force_standalone: bool = field(default_factory=lambda: _env_bool("DEV_MODE", False))
    pushgateway_url: str = field(default_factory=lambda: os.getenv("PUSHGATEWAY_URL", ""))

    # --- TPU / parallelism ---
    mesh_shape: str = field(default_factory=lambda: os.getenv("MESH_SHAPE", ""))  # e.g. "dp:2,tp:4"
    dtype: str = field(default_factory=lambda: os.getenv("MODEL_DTYPE", "bfloat16"))
    # page_size x num_pages = KV token capacity (default 32k slots).
    # 128-token pages are what the benchmark's cells run (PERF.md
    # section 4).  Two granularity tradeoffs ride the same knob: prefix caching shares WHOLE pages, so shared
    # prefixes shorter than one page stop caching; and with KV_QUANT=1 a
    # page's int8 scale is fixed by its first write, so up to
    # page_size-1 later appends clip against it (greedy still tracks
    # bf16 >= 32 tokens deep at 128 — test_kv_quant).  Match page size
    # to min(typical prompt, shared-prefix length) — helm kvPageSize.
    kv_page_size: int = field(default_factory=lambda: _env_int("KV_PAGE_SIZE", 128))
    kv_num_pages: int = field(default_factory=lambda: _env_int("KV_NUM_PAGES", 256))
    max_num_seqs: int = field(default_factory=lambda: _env_int("MAX_NUM_SEQS", 64))
    prefill_chunk: int = field(default_factory=lambda: _env_int("PREFILL_CHUNK", 512))
    # >0: token-budget PACKED prefill — every prefilling row's next chunk
    # packs into one [budget] buffer with segment-ID attention instead of
    # the padded [row_bucket, width] dispatch; prefill FLOPs scale with
    # real tokens on heterogeneous prompt-heavy waves
    # (serving/engine.py prefill_token_budget).  0 = padded, whose waves
    # run at chunk, chunk/2 or chunk/4 columns by their longest row.
    prefill_token_budget: int = field(
        default_factory=lambda: _env_int("PREFILL_TOKEN_BUDGET", 0)
    )
    # "native" = in-tree C++ byte-level BPE (serving/bpe_native.py) when the
    # checkpoint has a tokenizer.json; "hf" = transformers AutoTokenizer
    tokenizer_backend: str = field(
        default_factory=lambda: os.getenv("TOKENIZER_BACKEND", "native")
    )
    # automatic prefix caching (page-aligned KV reuse across requests)
    prefix_caching: bool = field(
        default_factory=lambda: _env_bool("PREFIX_CACHING", True)
    )
    # vLLM-style prefill-prioritized scheduling: give admission steps to
    # prompt waves instead of interleaving decode bursts (p50 TTFT under
    # simultaneous arrival; running streams stall during the wave)
    prefill_priority: bool = field(
        default_factory=lambda: _env_bool("PREFILL_PRIORITY", False)
    )
    # prompts at least this long prefill sequence-parallel over the mesh's
    # sp axis (serving/long_prefill.py).  An EXPLICIT 0 disables; leaving
    # the variable unset auto-derives a threshold whenever the mesh has
    # sp > 1 (serving/engine.derive_sp_prefill_threshold) — the
    # set/unset distinction rides sp_prefill_threshold_set below
    sp_prefill_threshold: int = field(
        default_factory=lambda: _env_int("SP_PREFILL_THRESHOLD", 0)
    )
    sp_prefill_threshold_set: bool = field(
        default_factory=lambda: os.environ.get("SP_PREFILL_THRESHOLD") is not None
    )
    # ring-width buckets kept in the compiled ladder, widest down
    # (Engine.sp_ring_bucket_ladder); 0 = the full power-of-two ladder
    # from the threshold bucket to bucketed context_window
    sp_ring_buckets: int = field(
        default_factory=lambda: _env_int("SP_RING_BUCKETS", 0)
    )
    # quantized KV cache pages with per-page dequant scales
    # (kv_cache.quantize_kv_paged; scales ride the decode kernel's
    # scalar-prefetch channel).  KV_QUANT=int8 (or any truthy boolean)
    # halves KV reads and doubles effective page capacity; KV_QUANT=int4
    # nibble-packs two head components per byte (ops/fused_decode.py
    # dequantizes in-kernel) for ~4x the bf16 page count at equal HBM.
    # 0 = off, 8 = int8, 4 = int4 — int is truthiness-compatible with the
    # historical bool.
    kv_quant: int = field(default_factory=_parse_kv_quant)
    # host-RAM KV page tier (serving/kv_cache.TieredPageAllocator): cold
    # registered prefix pages write back to host RAM at step boundaries
    # and fault back in on re-admission, so the prefix cache extends past
    # HBM under oversubscribed concurrency.  "on" forces it, "off"
    # disables, "auto" enables iff KV_HOST_POOL_PAGES > 0.
    kv_tier: str = field(default_factory=lambda: os.getenv("KV_TIER", "auto"))
    # host-tier capacity in pages; 0 with KV_TIER=on sizes it at
    # 4x KV_NUM_PAGES (v5e-8: ~192 GB host RAM vs 16 GB HBM/chip — the
    # host pool is bounded by RAM you give the container, see README)
    kv_host_pool_pages: int = field(
        default_factory=lambda: _env_int("KV_HOST_POOL_PAGES", 0)
    )
    # pages per migration dispatch; compiled migration shapes are the
    # power-of-two buckets up to this (warmup-precompiled)
    kv_migrate_burst: int = field(
        default_factory=lambda: _env_int("KV_MIGRATE_BURST", 8)
    )

    @property
    def scope_tables(self) -> dict[str, str]:
        """scope name -> table name, the 5-level hierarchy."""
        return {
            "catalog": self.embeddings_table_catalog,
            "repo": self.embeddings_table_repo,
            "module": self.embeddings_table_module,
            "file": self.embeddings_table_file,
            "chunk": self.embeddings_table_chunk,
        }


# Map file extensions to language names for the AST-aware chunker
# (ingest/src/app/config.py:50-84 in the reference).
EXTENSION_TO_LANGUAGE: dict[str, str] = {
    ".js": "javascript",
    ".jsx": "javascript",
    ".ts": "typescript",
    ".tsx": "typescript",
    ".py": "python",
    ".java": "java",
    ".cpp": "cpp",
    ".cc": "cpp",
    ".cxx": "cpp",
    ".c": "c",
    ".h": "c",
    ".cs": "c_sharp",
    ".php": "php",
    ".rb": "ruby",
    ".go": "go",
    ".rs": "rust",
    ".swift": "swift",
    ".kt": "kotlin",
    ".scala": "scala",
    ".sh": "bash",
    ".bash": "bash",
    ".sql": "sql",
    ".html": "html",
    ".htm": "html",
    ".css": "css",
    ".json": "json",
    ".xml": "xml",
    ".yaml": "yaml",
    ".yml": "yaml",
    ".toml": "toml",
    ".md": "markdown",
    ".dockerfile": "dockerfile",
}


_settings: Settings | None = None


def get_settings() -> Settings:
    """Process-wide settings singleton (env read once, first use)."""
    global _settings
    if _settings is None:
        _settings = Settings()
    return _settings


def reload_settings() -> Settings:
    """Re-read the environment (used by tests that monkeypatch env vars)."""
    global _settings
    _settings = Settings()
    return _settings
