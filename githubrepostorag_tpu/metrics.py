"""Prometheus metrics shared across the API, worker, and serving engine.

Mirrors the reference's three patterns (SURVEY.md §5.5): pull on the API
(request count/latency middleware + /metrics — rest_api main.py:21-62),
pull on the worker (job/LLM/retrieval counters — worker.py:36-47), push
from the batch ingest job (ingest/controller.py handles that side).  Adds
the serving metrics BASELINE needs: TTFT and decode-throughput histograms.
"""

from __future__ import annotations

import time
from typing import Iterator

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

REGISTRY = CollectorRegistry()

HTTP_REQUESTS = Counter(
    "rag_api_requests_total", "API requests", ["method", "path", "status"], registry=REGISTRY
)
HTTP_LATENCY = Histogram(
    "rag_api_request_seconds", "API request latency", ["method", "path"], registry=REGISTRY
)
JOBS_TOTAL = Counter(
    "rag_jobs_total", "RAG jobs processed", ["status"], registry=REGISTRY
)
JOB_DURATION = Histogram(
    "rag_job_seconds", "RAG job wall-clock", registry=REGISTRY,
    buckets=(0.5, 1, 2, 5, 10, 30, 60, 120, 300),
)
LLM_CALLS = Counter("rag_llm_calls_total", "LLM completions", ["status"], registry=REGISTRY)
LLM_LATENCY = Histogram("rag_llm_call_seconds", "LLM completion latency", registry=REGISTRY)
RETRIEVAL_HITS = Histogram(
    "rag_retrieval_hits", "Docs returned per retrieval", registry=REGISTRY,
    buckets=(0, 1, 2, 3, 5, 8, 10, 20),
)
RETRIEVAL_SECONDS = Histogram(
    "rag_retrieval_seconds",
    "Per-request retrieval latency through the coalescer (queue + encode + search)",
    registry=REGISTRY,
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
)
RETRIEVAL_WAVE_SIZE = Histogram(
    "rag_retrieval_wave_size",
    "Queries coalesced into one encoder forward + search dispatch",
    registry=REGISTRY,
    buckets=(1, 2, 4, 8, 16, 32),
)
DEVICE_INDEX_SEARCHES = Counter(
    "rag_device_index_searches_total",
    "Vector searches by execution path (device = fused on-accelerator top-k, "
    "fallback = host store outside the warmed bucket contract)",
    ["path"],
    registry=REGISTRY,
)
# Engine-owned series carry a `replica` label: under MultiAsyncEngine each
# AsyncEngine driver binds its own child (r0, r1, ...) so dp>1 fleets write
# distinct series instead of aliasing one; fleet totals are the label sum
# (counter_value() sums across label sets).  MeteredLLM's API-side TTFT /
# token observations use replica="api" — they measure the worker's view
# through the whole stack, not one engine's step loop.
TTFT = Histogram(
    "rag_ttft_seconds", "Time to first generated token", ["replica"], registry=REGISTRY,
    buckets=(0.1, 0.25, 0.5, 1.0, 1.5, 2.0, 3.0, 5.0, 10.0),
)
DECODE_TOKENS = Counter("rag_decode_tokens_total", "Generated tokens", ["replica"], registry=REGISTRY)
ENGINE_RUNNING = Gauge("rag_engine_running_seqs", "Sequences in the decode batch", ["replica"], registry=REGISTRY)
ENGINE_WAITING = Gauge("rag_engine_waiting_seqs", "Queued requests", ["replica"], registry=REGISTRY)
PREFIX_CACHE_HITS = Counter(
    "rag_prefix_cache_hit_tokens_total",
    "Prompt tokens served from the KV prefix cache instead of prefill",
    ["replica"],
    registry=REGISTRY,
)
PACKED_PREFILL_TOKENS = Counter(
    "rag_packed_prefill_tokens_total",
    "Real prompt tokens dispatched by the token-budget packed prefill",
    ["replica"],
    registry=REGISTRY,
)
PACKED_PREFILL_PADDING = Counter(
    "rag_packed_prefill_padding_total",
    "Unused packed-prefill budget slots (buffer padding dispatched)",
    ["replica"],
    registry=REGISTRY,
)
WORKER_DEQUEUE_ERRORS = Counter(
    "rag_worker_dequeue_errors_total",
    "queue.dequeue() failures survived by the worker's backoff loop",
    registry=REGISTRY,
)
JOBS_SHED = Counter(
    "rag_jobs_shed_total",
    "Jobs rejected with 429 by the bounded-queue admission check",
    registry=REGISTRY,
)
JOBS_IN_FLIGHT = Gauge(
    "rag_jobs_in_flight", "Jobs currently executing in this worker", registry=REGISTRY
)
EVENT_EMIT_DROPS = Counter(
    "rag_bus_emit_drops_total",
    "Progress events dropped after the supervised emit exhausted retries",
    ["event"],
    registry=REGISTRY,
)
BUS_RECONNECTS = Counter(
    "rag_bus_reconnects_total",
    "SSE subscriber re-subscribes after a bus connection loss",
    registry=REGISTRY,
)
FAULTS_INJECTED = Counter(
    "rag_faults_injected_total",
    "Faults fired by the FAULTS injection registry",
    ["site", "action"],
    registry=REGISTRY,
)
BREAKER_TRANSITIONS = Counter(
    "rag_breaker_transitions_total",
    "Circuit breaker state transitions",
    ["dep", "to_state"],
    registry=REGISTRY,
)
ENGINE_DEADLINE_REAPS = Counter(
    "rag_engine_deadline_reaps_total",
    "Generation requests reaped at a step boundary for exceeding their deadline",
    ["replica"],
    registry=REGISTRY,
)
ENGINE_PREEMPTIONS = Counter(
    "rag_engine_preemptions_total",
    "Batch-class victims parked to the KV host tier so protected-class "
    "admission could proceed (serving/engine.py preempt-to-host)",
    ["replica"],
    registry=REGISTRY,
)
ENGINE_PREEMPT_RESUMES = Counter(
    "rag_engine_preempt_resumes_total",
    "Parked victims re-admitted via prefix share + fault-in (decode "
    "continues token-identically, no recomputed prompt prefill)",
    ["replica"],
    registry=REGISTRY,
)
ADMISSION_FAILOPEN = Counter(
    "rag_admission_failopen_total",
    "Admission decisions that failed open (the SLO-plane provider raised "
    "or returned garbage; the request was accepted anyway)",
    registry=REGISTRY,
)
XLA_COMPILES = Counter(
    "rag_xla_compiles_total",
    "Step programs that went through the back end (compiled, or read from "
    "the cache) during live engine stepping (warmup should make this zero; "
    "see obs/engine_profile.py)",
    ["replica"],
    registry=REGISTRY,
)
XLA_COMPILE_SECONDS = Counter(
    "rag_xla_compile_seconds_total",
    "Seconds JAX spent tracing, lowering and back-end compiling (a cache hit's "
    "seconds are the read), before mark_warm() (startup) and after it (live): "
    "the compile ledger of obs/engine_profile.py",
    ["when"],
    registry=REGISTRY,
)
STARTUP_SECONDS = Gauge(
    "rag_startup_seconds",
    "Seconds from process start to ready by phase of the start-up record "
    "(obs/startup.py), with phase=\"total\" the whole; set once, at mark_warm()",
    ["phase"],
    registry=REGISTRY,
)
TPOT = Histogram(
    "rag_engine_tpot_seconds",
    "Time per output token after the first (decode seconds / decode tokens)",
    ["replica"],
    registry=REGISTRY,
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0),
)
SCHED_STALL = Gauge(
    "rag_engine_sched_stall_seconds",
    "Gap between consecutive engine steps while work exists "
    "(scheduler stall; 0 when idle)",
    ["replica"],
    registry=REGISTRY,
)
KV_TIER_DEVICE_PAGES = Gauge(
    "rag_kv_tier_device_free_pages",
    "Allocatable device KV pages (free list + evictable cached pages)",
    ["replica"],
    registry=REGISTRY,
)
KV_TIER_HOST_PAGES = Gauge(
    "rag_kv_tier_host_pages",
    "KV pages resident in the host-RAM swap tier (by chain hash)",
    ["replica"],
    registry=REGISTRY,
)
KV_FAULT_INS = Counter(
    "rag_kv_tier_fault_ins_total",
    "Prefix pages re-admitted host->device instead of recomputed",
    ["replica"],
    registry=REGISTRY,
)
KV_WRITEBACKS = Counter(
    "rag_kv_tier_writebacks_total",
    "Cold device pages saved device->host at step boundaries",
    ["replica"],
    registry=REGISTRY,
)
KV_DEDUP_HITS = Counter(
    "rag_kv_tier_dedup_hits_total",
    "share() hits on pages other concurrent requests actively hold "
    "(cross-user prefix-page dedup)",
    ["replica"],
    registry=REGISTRY,
)
KV_DEDUP_HOLDS = Counter(
    "rag_kv_tier_dedup_holds_total",
    "Admissions held one registration for an identical prefix mid-prefill "
    "instead of duplicating its footprint",
    ["replica"],
    registry=REGISTRY,
)
KV_MIGRATION_SECONDS = Histogram(
    "rag_kv_tier_migration_seconds",
    "Per-step host time spent planning/dispatching/landing page migration "
    "(writeback gathers + fault-in scatters)",
    ["replica"],
    registry=REGISTRY,
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1),
)
# --- SLO plane: token ledger + burn-rate monitor (obs/ledger.py, obs/slo.py)
LEDGER_GOODPUT = Gauge(
    "rag_engine_goodput_tokens_per_s",
    "Rolling committed-token throughput over the ledger window",
    ["replica"],
    registry=REGISTRY,
)
LEDGER_MFU = Gauge(
    "rag_engine_mfu_ratio",
    "Rolling model FLOPs utilization: (committed+prefill tokens) x "
    "flops/token over elapsed x peak chip FLOPs",
    ["replica"],
    registry=REGISTRY,
)
LEDGER_LIMITER = Gauge(
    "rag_engine_limiter",
    "One-hot windowed bottleneck attribution "
    "(hbm_pages | stall | compile | swap_wait | kv_transfer | none)",
    ["replica", "limiter"],
    registry=REGISTRY,
)
LEDGER_STEP_SECONDS = Counter(
    "rag_engine_step_seconds_total",
    "Engine step wall time classified into phase buckets (prefill | decode "
    "| kv_migration | kv_transfer | sched_stall | compile)",
    ["replica", "bucket"],
    registry=REGISTRY,
)
LEDGER_TOKENS = Counter(
    "rag_engine_tokens_total",
    "Token outcomes: committed | deadline_reaped",
    ["replica", "outcome"],
    registry=REGISTRY,
)
ENGINE_STEP_DISPATCHES = Gauge(
    "rag_engine_step_dispatches",
    "Rolling main-model programs dispatched per engine step (1.0 = every "
    "step fused into one program; the unfused mixed path issues 2+)",
    ["replica"],
    registry=REGISTRY,
)
SLO_BURN = Gauge(
    "rag_slo_burn_rate",
    "Error-budget burn rate per objective/class over each rolling window",
    ["replica", "objective", "klass", "window"],
    registry=REGISTRY,
)
SLO_STATE = Gauge(
    "rag_slo_state",
    "SLO state machine per objective/class: 0=ok 1=warn 2=critical",
    ["replica", "objective", "klass"],
    registry=REGISTRY,
)
SLO_TRANSITIONS = Counter(
    "rag_slo_state_transitions_total",
    "SLO state machine transitions, labeled by the state entered",
    ["replica", "objective", "klass", "state"],
    registry=REGISTRY,
)
ROUTER_DECISIONS = Counter(
    "rag_router_decisions_total",
    "Fleet router outcomes: affinity_hit / affinity_miss / "
    "skipped_breaker_open / skipped_limiter",
    ["decision"],
    registry=REGISTRY,
)
ROUTER_PREFIX_PAGES = Counter(
    "rag_router_prefix_pages_total",
    "Prefix pages the router matched against the chosen replica's digest, "
    "by tier the match came from",
    ["replica", "tier"],
    registry=REGISTRY,
)
ROUTER_ROUTED = Counter(
    "rag_router_routed_total",
    "Requests routed to each replica",
    ["replica"],
    registry=REGISTRY,
)
FLEET_LIFECYCLE = Gauge(
    "rag_fleet_replica_lifecycle",
    "Replica lifecycle: 0=active 1=draining 2=drained 3=spare",
    ["replica"],
    registry=REGISTRY,
)
# --- Self-healing fleet controller (serving/controller.py)
CTRL_ACTIONS = Counter(
    "rag_ctrl_actions_total",
    "Fleet-controller remediation actions executed, by action ladder rung "
    "(failover / grow_host_pool / spread_affinity) and the "
    "sensed reason that justified it",
    ["action", "reason"],
    registry=REGISTRY,
)
CTRL_FAILOPEN = Counter(
    "rag_ctrl_failopen_total",
    "Controller-internal exceptions survived by failing open (the tick or "
    "action was abandoned, the fleet kept serving; a rising rate means the "
    "controller is observe-only in practice)",
    registry=REGISTRY,
)
CTRL_SUPPRESSED = Counter(
    "rag_ctrl_suppressed_total",
    "Controller decisions withheld by a guard: hysteresis (ticks not yet "
    "agreeing), cooldown, action-window budget, or an in-flight action on "
    "the same replica",
    ["guard"],
    registry=REGISTRY,
)
# --- Deep observability (obs/hbm.py + obs/continuous.py + obs/timeline.py)
HBM_HELD_PAGES = Gauge(
    "rag_hbm_held_pages",
    "Refcount claims currently held on device pages per replica (each "
    "block-table listing is one claim; the page observatory integrates "
    "this over time into page-seconds)",
    ["replica"],
    registry=REGISTRY,
)
HBM_PAGE_SECONDS = Counter(
    "rag_hbm_page_seconds_total",
    "Page-seconds attributed to finished requests per replica and "
    "priority class (the memory analogue of the token ledger)",
    ["replica", "priority"],
    registry=REGISTRY,
)
PROFILE_SAMPLES = Counter(
    "rag_profile_samples_total",
    "Continuous-profiler step samples captured into the ring per replica",
    ["replica"],
    registry=REGISTRY,
)
TIMELINE_EXPORTS = Counter(
    "rag_timeline_exports_total",
    "Perfetto timeline builds served (/debug/timeline + bench dumps)",
    registry=REGISTRY,
)
TIMELINE_EVENTS_DROPPED = Counter(
    "rag_timeline_events_dropped_total",
    "Trace events dropped by the timeline_max_events cap across exports",
    registry=REGISTRY,
)
# --- Disaggregated prefill/decode serving (serving/disagg.py)
FLEET_ROLE = Gauge(
    "rag_fleet_replica_role",
    "Replica serving role under disaggregation: 0=fused 1=prefill 2=decode",
    ["replica"],
    registry=REGISTRY,
)
DISAGG_HANDOFFS = Counter(
    "rag_disagg_handoffs_total",
    "Prefill->decode handoff attempts by outcome: shipped (KV landed on a "
    "decode replica and the request resumed there) or fallback_<reason> "
    "(finished fused on the prefill replica)",
    ["outcome"],
    registry=REGISTRY,
)
DISAGG_PAGES = Counter(
    "rag_disagg_pages_total",
    "KV pages on the handoff path: shipped (packed + transferred) or "
    "deduped (decode replica already held the content hash — zero bytes "
    "moved)",
    ["kind"],
    registry=REGISTRY,
)
DISAGG_TRANSFER_SECONDS = Counter(
    "rag_disagg_transfer_seconds_total",
    "Host wall time packing/unpacking handoff payloads per replica "
    "(the ledger charges the same time to its kv_transfer bucket)",
    ["replica"],
    registry=REGISTRY,
)
# --- Live device index (ingest/stream.py + retrieval/live_index.py +
# retrieval/device_index.py): fragmentation gauges the background
# compactor triggers on, watermark/lag gauges the apply loop publishes,
# and the full-sync counter tests pin at zero on the churn hot path.
INDEX_LIVE_ROWS = Gauge(
    "rag_index_live_rows",
    "Live (non-tombstoned) rows mirrored per device-index table",
    ["table"],
    registry=REGISTRY,
)
INDEX_HOLES = Gauge(
    "rag_index_tombstoned_holes",
    "Tombstoned hole rows awaiting compaction per device-index table",
    ["table"],
    registry=REGISTRY,
)
INDEX_CAPACITY = Gauge(
    "rag_index_capacity_rows",
    "Allocated capacity-bucket rows per device-index table",
    ["table"],
    registry=REGISTRY,
)
INDEX_COMPACTIONS = Counter(
    "rag_index_compactions_total",
    "In-place hole-reclaim compactions per device-index table "
    "(warmed gather repack, same capacity bucket)",
    ["table"],
    registry=REGISTRY,
)
INDEX_FULL_SYNCS = Counter(
    "rag_index_full_syncs_total",
    "Whole-table transpose re-puts of a device-index corpus (initial "
    "seeding and capacity growth; must NOT happen on the churn hot path)",
    ["table"],
    registry=REGISTRY,
)
INDEX_WATERMARK = Gauge(
    "rag_index_watermark",
    "Mutation-stream watermark by scope: kind=appended is the producers' "
    "log head, kind=applied is the seq the live index has absorbed",
    ["scope", "kind"],
    registry=REGISTRY,
)
INDEX_APPLY_LAG = Gauge(
    "rag_index_apply_lag_ops",
    "Appended-minus-applied mutation ops per scope (stream backlog)",
    ["scope"],
    registry=REGISTRY,
)
INDEX_OPS_APPLIED = Counter(
    "rag_index_ops_applied_total",
    "Mutation ops the live-index apply loop drained into the store",
    ["table", "kind"],
    registry=REGISTRY,
)
BURST_DISPATCH = Counter(
    "rag_engine_burst_dispatch_total",
    "Decode bursts dispatched, by whether the device still had work queued "
    "when the step's programs went out (ahead=1: the host ran ahead) or had "
    "drained and waited for the host (ahead=0)",
    ["ahead"],
    registry=REGISTRY,
)
ENGINE_CYCLE = Histogram(
    "rag_engine_cycle_seconds",
    "The decode cycle: seconds from one decode burst's tokens landing on the "
    "host to the next burst's, which every live row waits for its next "
    "burst of tokens, by the prefill waves dispatched inside it (waves=0: "
    "the burst alone; 1; 2+): the distance between the labels is what "
    "prefill costs the rows that are decoding",
    ["waves"],
    registry=REGISTRY,
    buckets=(0.005, 0.01, 0.025, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3, 0.5, 1.0, 2.5),
)
PREFILL_WAVE = Counter(
    "rag_engine_prefill_wave_total",
    "Padded prefill waves dispatched, by the columns a row the wave program "
    "ran at: the narrowest rung of the width ladder that held the wave's "
    "longest pending chunk",
    ["width"],
    registry=REGISTRY,
)
PREFILL_ATTN_TILES = Counter(
    "rag_engine_prefill_attn_tiles_total",
    "(query tile, key step) pairs of the prefill waves' attention grid, over "
    "the rows a wave program ran, by what the latent family's kernel did with "
    "them: run, or skipped (no real query in the tile: the wave's padding; or "
    "no key of the row in the step)",
    ["kind"],
    registry=REGISTRY,
)
MOE_EXPERTS_HIT = Counter(
    "rag_moe_experts_hit_total",
    "Held experts that received a token, summed over expert layers and steps "
    "(each streams its weights once); read back with each burst's tokens",
    ["program"],
    registry=REGISTRY,
)
MOE_EXPERT_TOKENS = Counter(
    "rag_moe_expert_tokens_total",
    "(token, expert) pairs routed to an expert held on this chip; none is dropped",
    ["program"],
    registry=REGISTRY,
)
STATE_SNAPSHOTS = Counter(
    "rag_state_snapshots_total",
    "Snapshots of a recurrent model's per-sequence state at page boundaries "
    "(serving/kv_cache.StateSlots): written by a prefill wave, hit by an "
    "admission that resumed from one, evicted by LRU or with their page",
    ["event"],
    registry=REGISTRY,
)
STATE_SLOTS_IN_USE = Gauge(
    "rag_state_slots_in_use",
    "Snapshot slots of the state pool that hold a snapshot",
    registry=REGISTRY,
)
SLIDING_PAGES_FREED = Counter(
    "rag_kv_sliding_pages_freed_total",
    "Pages of a sliding kind (serving/kv_cache.SlidingPages) that rows released "
    "once every key in them lay behind the window of the row's next token",
    registry=REGISTRY,
)
KV_PAGES_IN_USE = Gauge(
    "rag_kv_pages_in_use",
    "Pages that sequences hold, by kind of page, of an engine whose model states "
    "a sliding kind beside the global one",
    ["kind"],
    registry=REGISTRY,
)


def render() -> bytes:
    return generate_latest(REGISTRY)


def counter_value(metric, **labels) -> float:
    """Read a Counter/Gauge's current value through the public collect()
    API (tests and the health report; avoids prometheus_client privates).
    Sums every sample matching the given labels, so a partial label set
    aggregates across the rest — e.g. ``counter_value(DECODE_TOKENS)`` is
    the fleet total over all replicas."""
    want = {k: str(v) for k, v in labels.items()}
    total = 0.0
    for sample in metric.collect()[0].samples:
        if sample.name.endswith("_created"):
            continue
        if all(sample.labels.get(k) == v for k, v in want.items()):
            total += sample.value
    return total


class MeteredLLM:
    """LLM wrapper recording call counts + latency (worker.py:73-88), and a
    ``llm.complete``/``llm.stream`` span per call when a trace is active."""

    def __init__(self, inner) -> None:
        self._inner = inner

    def complete(self, prompt, **kw) -> str:
        from githubrepostorag_tpu.obs.trace import span as trace_span

        with trace_span("llm.complete", prompt_chars=len(prompt)) as sp:
            start = time.monotonic()
            text = self._inner.complete(prompt, **kw)
            LLM_LATENCY.observe(time.monotonic() - start)
            status = "error" if text.startswith("Error:") else "ok"
            LLM_CALLS.labels(status=status).inc()
            if status != "ok":
                sp.set_status("error: llm")
            sp.set_attr("completion_chars", len(text))
        return text

    def complete_batch(self, prompts, **kw) -> list[str]:
        from githubrepostorag_tpu.obs.trace import span as trace_span

        batch = getattr(self._inner, "complete_batch", None)
        with trace_span("llm.complete_batch", batch_size=len(prompts)) as sp:
            start = time.monotonic()
            if callable(batch):
                out = batch(prompts, **kw)
            else:
                out = [self._inner.complete(p, **kw) for p in prompts]
            LLM_LATENCY.observe(time.monotonic() - start)
            errors = 0
            for text in out:
                bad = text.startswith("Error:")
                errors += bad
                LLM_CALLS.labels(status="error" if bad else "ok").inc()
            if errors:
                sp.set_status("error: llm")
                sp.set_attr("errors", errors)
        return out

    def stream_complete(self, prompt, **kw) -> Iterator[str]:
        from githubrepostorag_tpu.obs.trace import current_context
        from githubrepostorag_tpu.obs.trace import Span as TraceSpan

        # a generator's body runs lazily on the consumer's schedule, so the
        # span is managed by hand (opened under the caller's context at
        # first pull) instead of via the contextmanager
        ctx = current_context()
        sp = TraceSpan("llm.stream", ctx) if ctx is not None and ctx.sampled else None
        start = time.monotonic()
        first = True
        status = "ok"
        deltas = 0
        try:
            for delta in self._inner.stream_complete(prompt, **kw):
                if first:
                    TTFT.labels(replica="api").observe(time.monotonic() - start)
                    first = False
                if delta.startswith("Error:"):
                    # backends yield errors as text, never raise — an
                    # "Error:" delta IS the failure signal
                    status = "error"
                deltas += 1
                DECODE_TOKENS.labels(replica="api").inc()
                yield delta
        except GeneratorExit:
            status = "cancelled"  # consumer closed the stream early
            raise
        except BaseException:
            status = "error"
            raise
        finally:
            LLM_LATENCY.observe(time.monotonic() - start)
            LLM_CALLS.labels(status=status).inc()
            if sp is not None:
                sp.set_attr("deltas", deltas)
                if status != "ok":
                    sp.set_status(f"error: stream {status}")
                sp.finish()
