"""The TPU generation engine: chunked prefill + batched decode over the paged
KV cache, with continuous batching (new requests join the running batch at
any step boundary, finished ones leave and their pages are recycled).

This is the in-tree replacement for vLLM's scheduler+engine
(helm/templates/qwen-deployment.yaml runs vllm-openai with
``--max-num-seqs 4``; the MAX_NUM_SEQS env default is 64 per the v5e-8
target in BASELINE.json config #5 — the constructor default stays small
for tests, deployments pass Settings.max_num_seqs).

Design notes (TPU-first):
  - Every device computation has a fixed shape: decode is always
    [max_num_seqs, 1]; prefill chunks are bucketed to powers of two, so XLA
    compiles a handful of programs total, once.
  - The page pools are donated through every step and never moved by
    one: donation only lets a program reuse the buffer, it is the commit
    (kv_cache.commit_paged: a burst row's steps and a wave row's chunk
    written as the few aligned windows of slots they fall in, each an
    update-slice in the layout the pool lives in; one [hd] row per scatter
    index where the slots are not runs or the pool is quantized) and the
    pools riding each layer scan as carry that keep the compiler from
    transposing, slicing or copying them (tests/test_tpu_compile.py).
    Block tables / slot mappings are tiny host-computed int32 arrays
    shipped per step.
  - Scheduling (which request prefills, who decodes, page allocation) is
    host-side Python — control flow stays off the device; compute stays on.
  - Sampling runs on-device with per-row parameters so one fused kernel
    serves heterogeneous requests (greedy judge calls batched with
    temperature-0.7 synthesis calls).
  - Inside ``step`` the host asks the device for two programs and nothing
    else: a prefill wave (the chunk and its first-token tail) and a decode
    burst (which overlays the rows fresh from a wave itself).  Both take
    host arrays and the device state they hand each other (pools, presence
    mask, first-token array, chained tokens and lengths, a base key), so
    the host runs ahead of the device and only ``engine.commit_fetch``
    waits for it; admission's two presence helpers go out before the wave.
"""

from __future__ import annotations

import importlib
import itertools
import time
from collections import deque
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from githubrepostorag_tpu.models.qwen2 import (
    Qwen2Config,
    forward_paged_packed,
    forward_paged_wave,
)
from githubrepostorag_tpu.ops.packed_prefill import ring_segment_layout
from githubrepostorag_tpu.ops.prefill_width import width_ladder
from githubrepostorag_tpu.ops.sampling import mark_presence_chunks, sample_tokens
from githubrepostorag_tpu.ops.page_migration import (
    gather_pages,
    migrate_buckets,
    scatter_pages,
    split_page_payloads,
)
from githubrepostorag_tpu.serving.kv_cache import (
    OutOfPages,
    PageAllocator,
    PrefixCachingAllocator,
    SlidingPages,
    SlidingRow,
    StateSlots,
    TieredPageAllocator,
    make_page_pools,
    make_state_pools,
    packed_slot_mapping,
    page_hashes,
    page_kinds,
    pages_needed,
    quant_bits,
    slot_mapping,
)
from githubrepostorag_tpu.serving.sampling_params import SamplingParams
from githubrepostorag_tpu.metrics import (
    BURST_DISPATCH,
    ENGINE_CYCLE,
    KV_PAGES_IN_USE,
    PREFILL_ATTN_TILES,
    PREFILL_WAVE,
    SLIDING_PAGES_FREED,
    STATE_SLOTS_IN_USE,
    STATE_SNAPSHOTS,
)
from githubrepostorag_tpu.obs.engine_profile import compile_ledger
from githubrepostorag_tpu.obs.startup import records as startup_records, startup_record
from githubrepostorag_tpu.utils.logging import get_logger
from githubrepostorag_tpu.utils.profiling import annotate

logger = get_logger(__name__)

TokenCallback = Callable[[str, int], None]  # (request_id, token_id)

CYCLE_RING = 4096  # burst landings kept with their cycle: some minutes of traffic
# prefill chunks between the snapshots a recurrent model's cold stretch leaves (_admit_state):
# 8,192 tokens at 512-token chunks, 64 pages of 128
SNAPSHOT_STRIDE_CHUNKS = 16


@dataclass
class GenerationResult:
    request_id: str
    prompt_tokens: list[int]
    output_tokens: list[int]
    finish_reason: str  # "stop" | "length" | "cancelled" | "deadline" | "error"
    ttft_s: float | None = None
    decode_time_s: float = 0.0
    error: str | None = None
    # monotonic phase stamps, in order: recv_t (handler entry), enqueue_t
    # (before the driver lock), submit_t, prefill_start_t, prefill_end_t
    # (completing chunk dispatched), first_token_t, first_emit_t (first
    # token off the stream queue; AsyncEngine.stream adds it), done_t —
    # obs/engine_profile.record_engine_spans turns them into spans.  Beside
    # them the request's decode, one count a burst landing and not a token:
    # last_token_t (the landing that brought its last token), decode_cycles
    # (landings that brought it a token), decode_wave_cycles (of those after
    # its first, the cycles that carried a prefill wave), decode_wave_tokens
    # (those waves' new tokens): what its time between tokens was made of
    timings: dict | None = None
    # KV tiering: prefix pages this request re-admitted from the host tier
    # instead of recomputing (0 on untiered engines)
    faulted_pages: int = 0
    # times this request was preempt-parked to the host tier and resumed
    # (0 = never preempted; output is token-identical either way)
    preempted: int = 0
    # prompt tokens served from the prefix cache at (the last) admission
    cached_tokens: int = 0


@dataclass
class _Request:
    request_id: str
    prompt: list[int]
    sampling: SamplingParams
    on_token: TokenCallback | None
    state: str = "waiting"  # waiting -> prefilling -> running -> done
    row: int = -1  # seq slot in the batch
    pages: list[int] = field(default_factory=list)
    seq_len: int = 0  # tokens currently in the KV cache
    prefill_pos: int = 0
    page_hashes: list[bytes] = field(default_factory=list)  # full prompt pages
    pages_registered: int = 0  # prefix-cache pages published so far
    cached_tokens: int = 0  # prompt tokens served from the prefix cache
    output: list[int] = field(default_factory=list)
    cancelled: bool = False
    error: str | None = None
    submit_t: float = field(default_factory=time.monotonic)
    prefill_start_t: float | None = None  # admission: waiting -> prefilling
    prefill_end_t: float | None = None  # completing chunk dispatched (host)
    first_token_t: float | None = None
    # the request's decode by burst landing (Engine._cycle_landed)
    last_token_t: float | None = None
    decode_cycles: int = 0
    decode_wave_cycles: int = 0
    decode_wave_tokens: int = 0
    recv_t: float | None = None  # caller's stamps (AsyncEngine.stream)
    enqueue_t: float | None = None
    # absolute time.monotonic() budget; past it the request is reaped at
    # the next step boundary (pages freed) instead of decoding on for a
    # caller that stopped waiting
    deadline_ts: float | None = None
    deadline_expired: bool = False
    # KV tiering: chain hashes this admission promised to register (the
    # pending-claim dedup contract — released claims unblock followers)
    # and prefix pages served by host->device fault-in
    claimed_hashes: list[bytes] = field(default_factory=list)
    faulted_pages: int = 0
    # priority class (SLO dimension AND scheduler input: headroom applies
    # to every class except the engine's protected one, and only
    # non-protected requests are preemption victims)
    priority: str = "interactive"
    # preempt-to-host state: after a park, ``prompt`` holds the full KV
    # stream (original prompt + tokens generated so far) so resume is an
    # ordinary prefix-cached admission; the original split is kept for the
    # final GenerationResult
    preempted: int = 0  # times parked
    resume_pending: bool = False  # parked->waiting, first re-admission ahead
    orig_prompt_len: int = 0  # original prompt length (0 = never parked path)
    prior_output: list[int] = field(default_factory=list)
    # a model with recurrent state (serving/kv_cache.StateSlots): the prompt
    # pages the prefix cache holds (a hit resumes only as deep as a snapshot
    # lies), the snapshot slot the first wave resumes from (-1: none, zeros),
    # and the page counts at which this prefill still owes a snapshot
    page_match: int = 0
    state_src: int = -1
    snap_at: list[int] = field(default_factory=list)
    # a model with a sliding kind of page (serving/kv_cache.SlidingPages): the
    # row's pages of that kind, by absolute page index
    sliding: SlidingRow | None = None


from githubrepostorag_tpu.utils import next_bucket as _bucket


def derive_sp_prefill_threshold(
    *,
    sp: int,
    explicit: int,
    env_set: bool,
    prefill_chunk: int,
    max_seq_len: int,
) -> int | None:
    """Resolve the ring-prefill routing threshold for an engine build.

    ``SP_PREFILL_THRESHOLD`` historically defaulted to 0 — ring prefill
    stayed dark even on meshes with sp > 1 unless the operator knew the
    knob.  Now: an EXPLICIT value wins (0 opts out, the historical
    behavior); unset with sp > 1 auto-derives 4x the prefill chunk — a
    prompt that would take >= 4 chunked passes amortizes the ring's
    rotation cost — clamped into [sp, max_seq_len // 2] so tiny test
    geometries still route something and the threshold never chases the
    context cap.  Returns None for "disabled" (the Engine convention)."""
    if sp <= 1:
        return None
    if env_set:
        return explicit if explicit > 0 else None
    derived = max(sp, min(4 * prefill_chunk, max_seq_len // 2))
    return derived


class Engine:
    def __init__(
        self,
        params: dict,
        cfg: Qwen2Config,
        *,
        max_num_seqs: int = 8,
        num_pages: int = 512,
        page_size: int = 16,
        max_seq_len: int = 2048,
        prefill_chunk: int = 512,
        prefill_token_budget: int | None = None,  # token-budget PACKED
        # prefill: flatten every prefilling row's next chunk into one
        # [budget] buffer with per-token segment IDs instead of the
        # padded [row_bucket, width] dispatch.  Prefill dense-layer FLOPs
        # scale with real tokens, not rows x max-chunk — the win on
        # heterogeneous waves (mixed prompt lengths, tail chunks, short
        # uncached suffixes after prefix-cache hits).  Chunks that don't
        # fit the budget split mid-chunk and resume next step.  One
        # compiled prefill shape per row bucket, as the padded path has
        # (whose waves follow their longest chunk down the width ladder
        # inside that program).  None = padded path.
        kv_dtype=jnp.bfloat16,
        kv_quant: bool | int = False,  # quantized KV pages with per-page
        # scales (kv_cache.quantize_kv_paged; scales ride the decode
        # kernel's scalar-prefetch channel, costing zero extra operand
        # DMAs).  True/8 = int8 (halves cache reads, doubles page
        # capacity); 4 = nibble-packed int4 (ops/fused_decode.py
        # dequantizes in-kernel; ~4x the bf16 page count at equal HBM)
        use_pallas: bool = False,
        rng_seed: int = 0,
        decode_burst: int = 8,
        layer_unroll: int = 1,  # unroll factor for the decode burst's
        # layer scan (serving/decode_burst.py) — small-batch decode is
        # weight-stream-bound and the per-layer scan bookkeeping is a
        # fixed tax; >1 trades compile time for step latency
        mesh=None,  # jax.sharding.Mesh -> TP-shard params, KV pools, compute
        prefix_caching: bool = True,  # vLLM automatic-prefix-caching analog
        kv_tier: str = "auto",  # host-RAM KV page tier behind the block
        # tables (serving/kv_cache.TieredPageAllocator): "on" forces it,
        # "off" disables, "auto" enables iff kv_host_pool_pages > 0.
        # Requires prefix_caching — tier residency is keyed by the prefix
        # chain hashes.  Cold registered pages write back to host RAM at
        # step boundaries and fault back in on re-admission, so "free"
        # host memory extends the prefix cache past HBM.
        kv_host_pool_pages: int = 0,  # host-tier capacity in pages; with
        # kv_tier="on" and 0 the engine sizes it at 4x num_pages (v5e-8
        # host RAM is ~12x a chip's HBM — see README sizing note)
        kv_migrate_burst: int = 8,  # pages per migration dispatch; the
        # compiled-shape set is the power-of-two bucket ladder up to this
        # (warmup precompiles gather + scatter at every bucket)
        prefill_priority: bool = False,  # skip the decode burst on steps
        # where a prefill chunk ran and prompts are still pending — the
        # vLLM prefill-prioritized schedule.  Running streams stall while
        # a prompt wave admits (their tokens arrive later), but p50 TTFT
        # under simultaneous-arrival load (eval config #5) drops: a big
        # model's multi-step burst otherwise blocks admission for ~1 s
        # between chunks.  Default False = co-dispatched mixing
        # (admissions never stall running streams).
        sp_prefill_threshold: int | None = None,  # prompts this long prefill
        # sequence-parallel over the mesh's sp axis (serving/long_prefill.py)
        sp_ring_buckets: int = 0,  # SP_RING_BUCKETS: number of ring-width
        # buckets kept in the compiled ladder, counted from the widest
        # down (0 = the full power-of-two ladder from the threshold
        # bucket to bucketed max_seq_len).  Fewer buckets = fewer
        # compiled ring programs, more padding on small passes;
        # sp_ring_bucket_ladder() is the single source of truth warmup
        # and dispatch both read.
        preempt: str = "auto",  # page-granularity preempt-to-host: park a
        # batch-class victim's KV pages in the host tier (priority
        # writeback) so a protected-class admission can proceed, and
        # resume it later via prefix share + fault-in — decode continues
        # token-identically with zero recomputed prompt prefill.  "on"
        # requires the KV host tier, "off" disables, "auto" enables iff
        # the tier is on.
        preempt_headroom_pages: int = 0,  # KV pages a non-protected
        # admission must leave allocatable (the protected class's
        # reservation); doubles while the protected class is in SLO warn
        default_priority: str = "interactive",  # class stamped on
        # unlabeled add_request calls (PRIORITY_DEFAULT_CLASS)
        protected_priority: str = "interactive",  # the class headroom and
        # preemption act FOR; its requests are never victims
        state_snapshots: int | None = None,  # snapshot slots of a recurrent
        # model's state pool (serving/kv_cache.StateSlots), sized beside
        # num_pages; None = two a row.  Unused by every other model
        sliding_pages: int | None = None,  # pages of the sliding kind's pool
        # (serving/kv_cache.SlidingPages) of a model that states one, sized
        # beside num_pages (the global kind's); None = num_pages
    ) -> None:
        # startup.engine_init: pools, allocator, state slots (obs/startup.py)
        init_phase = startup_record().begin("startup.engine_init")
        self.mesh = mesh
        # which model runs is read from the configuration object, never from a
        # model's name: ``step_programs`` names the module whose two step
        # programs (qwen2's contracts) serve it, ``latent_kv`` says its pages
        # are one latent pool with no V pool, ``recurrent_state`` that it keeps
        # a state pool beside its K/V pools (``kv_layers``, ``state_layers``,
        # ``state_shapes()``), ``expert_counters`` that its programs return the
        # expert layers' counts, ``page_kinds`` that some of its layers page the
        # last ``window`` keys only, in a pool of their own.  Without
        # ``step_programs`` the model is served by qwen2's programs
        programs = getattr(cfg, "step_programs", None)
        self._own_programs = programs is not None
        self._latent = bool(getattr(cfg, "latent_kv", False))
        self._recurrent = bool(getattr(cfg, "recurrent_state", False))
        self._expert_counters = bool(getattr(cfg, "expert_counters", False))
        kinds = page_kinds(cfg)
        if len(kinds) > 2 or (len(kinds) == 2 and (kinds[0][2] or not kinds[1][2]
                                                   or not self._own_programs)):
            raise ValueError("page kinds: one global kind, then at most one with a window, "
                             "served by the model's own step programs")
        sliding_kind = kinds[1] if len(kinds) == 2 else None
        self._wave_fn = forward_paged_wave
        # a family whose wave kernel decides its work a (query tile, key step)
        # at a time says how by ``wave_attention_tiles`` (_count_wave_tiles)
        self._wave_tiles = None
        if self._own_programs:
            family = importlib.import_module(programs)
            self._wave_tiles = getattr(family, "wave_attention_tiles", None)

            unsupported = {
                "mesh": mesh is not None, "kv_quant": bool(quant_bits(kv_quant)),
                "prefill_token_budget": prefill_token_budget is not None,
                "sp_prefill_threshold": sp_prefill_threshold is not None,
                "kv_tier": kv_tier == "on" or kv_host_pool_pages > 0,
                "preempt": preempt == "on",  # parks pages in the host tier
                # a snapshot lies at a page boundary between two blocks of a chunk
                "prefill_chunk": self._recurrent and prefill_chunk % page_size != 0,
                # a tier, a park and a handoff name a page by its hash alone;
                # of a sliding kind that says nothing of whether its window is held
                "prefix_caching": sliding_kind is not None and not prefix_caching,
            }
            if any(unsupported.values()):
                pools = [name for name, has in (("latent page", self._latent),
                                                ("recurrent state", self._recurrent)) if has]
                pool = " and a ".join(pools or ["sliding page"])
                raise ValueError(f"not built for a {pool} pool: "
                                 + ", ".join(k for k, v in unsupported.items() if v))
            self._wave_fn = family.forward_paged_wave
            self._decode_burst_fn = family.decode_burst
        else:
            from githubrepostorag_tpu.serving import decode_burst as burst_program

            self._decode_burst_fn = burst_program.decode_burst
        if mesh is not None:
            from githubrepostorag_tpu.parallel.sharding import (
                qwen2_param_specs,
                shard_params,
            )

            tp = mesh.shape.get("tp", 1)
            if tp > 1 and (cfg.num_kv_heads % tp or cfg.num_heads % tp):
                # the Pallas shard_map island hard-shards the head dims; fail
                # at construction, not mid-first-request (plan_for_devices
                # caps tp by the head counts — direct mesh builders must too)
                raise ValueError(
                    f"tp={tp} must divide num_heads={cfg.num_heads} and "
                    f"num_kv_heads={cfg.num_kv_heads}; use plan_for_devices("
                    "..., num_heads=..., num_kv_heads=..., role='serve')"
                )
            params = shard_params(params, mesh, qwen2_param_specs(cfg, mesh, params))
        elif not self._own_programs:
            from githubrepostorag_tpu.models.quant import fuse_projections

            # single-chip: fuse wq|wk|wv and wg|wu so each layer runs 4
            # projection matmuls per decode step instead of 7 (~60 us fixed
            # cost per quantized matmul measured at 7B shapes); sharded
            # meshes keep per-projection leaves — see fuse_projections
            params = fuse_projections(params)
        if self._expert_counters:
            # experts hit / pairs routed to held experts / expert slots
            # offered, per step program, cumulative; a dispatch's counts are
            # read back once a later burst's tokens prove the device is past it
            self.moe_stats = {"burst": [0, 0, 0], "prefill": [0, 0, 0]}
            # the fullest held expert's pairs, summed over expert layers and
            # steps: only for the programs that return that third count
            self.moe_max_pairs: dict[str, int] = {}
            self._moe_pending: list[tuple[int, str, jnp.ndarray, int]] = []
        self.params = params
        self.cfg = cfg
        self.max_num_seqs = max_num_seqs
        self.page_size = page_size
        self.max_seq_len = max_seq_len
        self.max_pages_per_seq = pages_needed(max_seq_len, page_size)
        self.prefill_chunk = prefill_chunk
        # the widths a padded wave runs at, largest first: branches of the
        # one wave program a row bucket has (ops/prefill_width.py)
        self.prefill_width_buckets = width_ladder(prefill_chunk, page_size)
        if prefill_token_budget is not None and prefill_token_budget < 1:
            raise ValueError("prefill_token_budget must be >= 1 when set")
        self.prefill_token_budget = prefill_token_budget
        # static per-segment chunk cap in the packed buffer: no segment
        # ever contributes more than a prefill chunk (or the whole budget)
        self.packed_chunk = (
            min(prefill_chunk, prefill_token_budget)
            if prefill_token_budget is not None else 0
        )
        self.packed_prefill_tokens = 0  # stats: real tokens dispatched
        self.packed_prefill_padding = 0  # stats: unused budget slots
        self.use_pallas = use_pallas
        # decode iterations fused per device dispatch (serving/decode_burst.py);
        # 1 reproduces plain per-token stepping
        self.decode_burst = max(1, decode_burst)
        self.layer_unroll = max(1, layer_unroll)

        # normalized bit width: 0 off, 8 int8, 4 nibble-packed int4 — all
        # historical `if self.kv_quant:` truthiness sites keep working
        self.kv_quant = quant_bits(kv_quant)
        # int4 weights route to the Pallas GEMM only when unsharded (an
        # opaque pallas_call has no GSPMD partitioning rule); TP meshes
        # take the partitionable XLA formulation (quant.Layered4XLA)
        self._int4_kernel = mesh is None or mesh.shape.get("tp", 1) == 1
        pools = make_page_pools(cfg, num_pages, page_size, dtype=kv_dtype,
                                quant=self.kv_quant)
        self._k_pages, self._v_pages = pools.k, pools.v
        self._k_scales, self._v_scales = pools.ks, pools.vs
        # a recurrent model's second kind of per-sequence memory: the state
        # pool on the device and the ledger of its slots on the host
        self._state = self._state_pools = None
        if self._recurrent:
            self._state = StateSlots(
                max_num_seqs, 2 * max_num_seqs if state_snapshots is None else state_snapshots)
            self._state_pools = make_state_pools(cfg, self._state.total)
            self._state_published: dict[str, int] = {}  # what the counters have been told
        # a second kind of page: the pool of the layers that attend a window,
        # its ledger (which releases a row's pages behind its window) and the
        # rows' tables, indexed by absolute page as the global kind's are
        self._sliding = self._sk_pages = self._sv_pages = None
        if sliding_kind is not None:
            _, layers, window = sliding_kind
            pages = num_pages if sliding_pages is None else sliding_pages
            self._sliding = SlidingPages(pages, page_size, window, prefill_chunk)
            if pages < 2 * self._sliding.cap:
                raise ValueError(f"sliding_pages={pages} cannot hold one row's window and its "
                                 f"own pages ({2 * self._sliding.cap})")
            sl = make_page_pools(cfg, pages, page_size, dtype=kv_dtype, layers=layers)
            self._sk_pages, self._sv_pages = sl.k, sl.v
            self._sliding_tables = np.zeros(
                (max_num_seqs, self.max_pages_per_seq), dtype=np.int32)
            self._deferred_sliding: list[int] = []
            self._m_sliding = (SLIDING_PAGES_FREED, KV_PAGES_IN_USE.labels(kind="sliding"),
                               KV_PAGES_IN_USE.labels(kind="global"))
            self._sliding_published = 0
        self.sliding_hit_tokens = 0  # stats: of page_hit_tokens, those the sliding kind held too
        self.state_restored = 0  # stats: prefills resumed from a snapshot
        self.page_hit_tokens = 0  # stats: prompt tokens whose pages the prefix cache held
        self.state_hit_tokens = 0  # ... and of those, the tokens a snapshot let a prefill skip
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            kv_tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
            kv_sharding = NamedSharding(mesh, PS(None, kv_tp, None, None, None))
            self._k_pages = jax.device_put(self._k_pages, kv_sharding)
            self._v_pages = jax.device_put(self._v_pages, kv_sharding)
            if self.kv_quant:
                # per-page scales [L, n_kv, P]: sharded with the kv-head axis
                s_sharding = NamedSharding(mesh, PS(None, kv_tp, None))
                self._k_scales = jax.device_put(self._k_scales, s_sharding)
                self._v_scales = jax.device_put(self._v_scales, s_sharding)
            self._replicated = NamedSharding(mesh, PS())
        self.prefix_caching = prefix_caching
        self.prefill_priority = prefill_priority
        if kv_tier not in ("auto", "on", "off"):
            raise ValueError(f"kv_tier must be 'auto'|'on'|'off', got {kv_tier!r}")
        if kv_tier == "on" and not prefix_caching:
            raise ValueError(
                "kv_tier='on' requires prefix_caching (host-tier residency "
                "is keyed by prefix chain hashes)"
            )
        self._kv_tier_on = prefix_caching and (
            kv_tier == "on" or (kv_tier == "auto" and kv_host_pool_pages > 0)
        )
        self.kv_migrate_burst = max(1, kv_migrate_burst)
        if self._kv_tier_on:
            self._allocator = TieredPageAllocator(
                num_pages,
                host_pool_pages=(
                    kv_host_pool_pages if kv_host_pool_pages > 0 else 4 * num_pages
                ),
                migrate_burst=self.kv_migrate_burst,
            )
        elif prefix_caching:
            self._allocator = PrefixCachingAllocator(num_pages)
        else:
            self._allocator = PageAllocator(num_pages)
        if self._state is not None and prefix_caching:
            # a snapshot whose page is evicted goes with it
            self._allocator.on_evict = self._state.drop
        # in-flight writeback gathers: [(device bufs tuple, hashes)] — the
        # gather + copy_to_host_async dispatch at step N, the np reads (and
        # allocator complete_writeback calls) happen at step N+1, so the
        # driver thread never waits on a device->host DMA it just started
        self._wb_pending: list[tuple[tuple, list[bytes]]] = []
        self.kv_migrations = 0  # stats: writeback bursts dispatched
        self.kv_fault_dispatches = 0  # stats: fault-in scatter bursts
        self.dedup_holds = 0  # stats: admissions held for a pending twin
        self.migration_seconds_total = 0.0  # writeback plan/dispatch/land
        self.fault_in_seconds_total = 0.0  # fault-in stage/dispatch
        # disagg handoff economics (serving/disagg.py drives these)
        self.kv_pages_exported = 0  # pages packed for a peer replica
        self.kv_pages_imported = 0  # transferred pages admitted host-side
        self.transfer_seconds_total = 0.0  # export pack + import unpack
        self.sp_prefill_threshold = sp_prefill_threshold
        self._sp = mesh.shape.get("sp", 1) if mesh is not None else 1
        self.sp_prefills = 0  # stats: ring-prefill passes dispatched
        self.sp_ring_bucket_count = max(0, sp_ring_buckets)
        # fixed segment-row count of the packed ring program: per-segment
        # arrays (logits_at, presence rows) always dispatch at this many
        # rows, so the compiled-program set is exactly one per ring width.
        # Every segment is >= threshold tokens, so the widest pass bounds
        # how many can ever pack.
        _thr = max(sp_prefill_threshold or 1, 1)
        _cap = -(-_bucket(max_seq_len, max_seq_len, minimum=max(1, self._sp))
                 // max(1, self._sp)) * max(1, self._sp)
        self.sp_ring_segs = _bucket(
            max(1, min(max_num_seqs, _cap // _thr)), max_num_seqs, minimum=1
        )
        self.sp_ring_segments = 0  # stats: prompts packed into ring passes
        self.sp_ring_tokens = 0  # stats: real tokens through ring passes
        self.sp_ring_padding = 0  # stats: unused ring-buffer slots
        if self._sp > 1 and sp_prefill_threshold is not None:
            logger.info(
                "sp prefill: threshold=%d tokens over sp=%d (segment-packed, ladder %s)",
                sp_prefill_threshold, self._sp, self.sp_ring_bucket_ladder(),
            )
        # stats: main-model programs issued.  Also every dispatch's NUMBER:
        # ``seq`` on the dispatch annotations, in the burst's chain entry and
        # on its landing, and what the expert counts are read back up to
        self.step_dispatches_total = 0
        self.requests_admitted = 0  # cumulative add_request count
        self.deadline_reaps = 0  # requests reaped past their deadline

        # ---- priority classes & preempt-to-host scheduling ----
        if preempt not in ("auto", "on", "off"):
            raise ValueError(f"preempt must be 'auto'|'on'|'off', got {preempt!r}")
        if preempt == "on" and not self._kv_tier_on:
            raise ValueError(
                "preempt='on' requires the KV host tier (kv_tier) — resume "
                "rides the claim/fault-in machinery, so parked victims need "
                "a tier to survive in"
            )
        self._preempt_on = self._kv_tier_on and preempt != "off"
        self.preempt_headroom_pages = max(0, preempt_headroom_pages)
        self.default_priority = default_priority
        self.protected_priority = protected_priority
        # class-aware queue ordering engages only when the knobs give
        # classes teeth; otherwise intake stays strictly FCFS
        self._priority_sched = self._preempt_on or self.preempt_headroom_pages > 0
        self._parked: list[_Request] = []
        self._park_events: list[str] = []  # rids parked since last drain
        self._class_pressure: dict[str, int] = {}  # klass -> 0 ok/1 warn/2 crit
        self.preemptions = 0  # victims parked to the host tier
        self.preempted_pages = 0  # pages those victims held at park time
        self.preempt_resumes = 0  # parked victims re-admitted
        self.resume_faulted_pages = 0  # resume pages restored by fault-in
        self.resume_recomputed_tokens = 0  # parked-KV tokens re-prefilled
        self.resume_recomputed_prompt_tokens = 0  # of those, PROMPT tokens
        # (the zero-recomputed-prefill acceptance gate reads this)

        # SLO-plane token economics + per-phase step time (cumulative;
        # obs/ledger.py snapshots these each driver step and differences
        # them into rolling goodput / MFU / limiter attribution)
        self.committed_tokens = 0  # tokens landed in request outputs
        self.prefill_tokens = 0  # real (non-padding) prompt tokens advanced
        self.reaped_tokens = 0  # output tokens discarded by deadline reaps
        self.admission_blocked_steps = 0  # steps with waiters the pool couldn't admit
        self.prefill_seconds_total = 0.0
        self.decode_seconds_total = 0.0

        # host-side batch state
        self._block_tables = np.zeros((max_num_seqs, self.max_pages_per_seq), dtype=np.int32)
        self._seq_lens = np.zeros((max_num_seqs,), dtype=np.int32)
        self._row_limits = np.zeros((max_num_seqs,), dtype=np.int32)  # page capacity per row
        self._free_rows = list(range(max_num_seqs - 1, -1, -1))
        self._row_req: dict[int, _Request] = {}

        # per-row sampling params (host mirror; pushed to device when dirty)
        self._temp = np.full((max_num_seqs,), 1.0, dtype=np.float32)
        self._top_p = np.ones((max_num_seqs,), dtype=np.float32)
        self._top_k = np.zeros((max_num_seqs,), dtype=np.int32)
        self._rep_pen = np.ones((max_num_seqs,), dtype=np.float32)
        self._sampling_dirty = True
        self._temp_d = self._top_p_d = self._top_k_d = self._rep_pen_d = None

        # token-presence mask for repetition penalty [rows, V]
        self._presence = jnp.zeros((max_num_seqs, cfg.vocab_size), dtype=bool)
        # each row's first token, where the prefill wave that completed its
        # prompt scattered it: the next burst overlays it on its chained
        # state and the commit fetches it, the host touches it nowhere else
        self._first_d = jnp.zeros((max_num_seqs,), dtype=jnp.int32)
        # the step programs' base key; they fold the dispatch counter in
        # themselves, so a step splits no key on the host
        self._rng = jax.random.PRNGKey(rng_seed)
        self._key_step = 0
        if mesh is not None:
            self._presence = jax.device_put(self._presence, self._replicated)
            self._first_d = jax.device_put(self._first_d, self._replicated)
        # bursts dispatched while the device still had work queued (the host
        # ran ahead) / after it had drained (the device waited for the host)
        self.bursts_ahead = 0
        self.bursts_starved = 0
        self._step_starved = False
        self._m_burst = [BURST_DISPATCH.labels(ahead=a) for a in ("0", "1")]
        # columns the padded prefill waves multiplied (row bucket x width),
        # against prefill_tokens' real ones, and the waves by width
        self.prefill_padded_tokens = 0
        self._m_wave = {w: PREFILL_WAVE.labels(width=str(w))
                        for w in self.prefill_width_buckets}
        if self._wave_tiles is not None:
            self.prefill_attn_tiles = {"run": 0, "skipped": 0}
            self._m_tiles = {k: PREFILL_ATTN_TILES.labels(kind=k) for k in self.prefill_attn_tiles}
        self._waiting: list[_Request] = []
        self._rejected: list[_Request] = []
        self._requests: dict[str, _Request] = {}
        self._ids = itertools.count()

        # Pipelined decode: while only decoding, burst k+1 is dispatched
        # BEFORE burst k's tokens are fetched, so the device->host sync
        # overlaps the next burst's compute (sized for a slow host link;
        # not re-measured on an attached chip).  ``_chain`` holds the device-side continuation state
        # (last tokens + seq lens from the in-flight burst) and the pending
        # unfetched result; ``_deferred`` holds finished rows whose pages
        # can't be recycled until the in-flight burst that still references
        # them has landed.
        #
        # Mixed prefill+decode: admissions do NOT drain the pipeline —
        # deferred pages never re-enter the allocator while a burst is in
        # flight, so a new request can only receive pages no in-flight
        # computation references.  Prefill waves dispatch between bursts
        # with no host sync: first tokens stay on device in the first-token
        # array, the next burst overlays them on its chained last/lens
        # state, and they commit with that burst's fetch.  A
        # ``_pending_first`` entry is (the array as its wave left it, the
        # wave's (request, row) pairs).
        self._chain: dict | None = None
        # (row, pages, request_id): the rid rides along so the page
        # observatory attributes page-seconds until the TRUE recycle time
        # in _drain_chain, keeping its per-request integral consistent
        # with the allocator-side occupancy integral
        self._deferred: list[tuple[int, list[int], str]] = []
        self._pending_first: list[tuple[jnp.ndarray, list[tuple[_Request, int]]]] = []
        # The decode cycle: from one burst's tokens landing on the host to the
        # next burst's.  With the host a burst ahead the landings are paced by
        # the device, so a cycle is the device's time for everything
        # dispatched between two bursts (the later burst included) plus
        # whatever it sat idle, and every live row's gap between tokens is the
        # cycle over the burst's steps.  A wave adds its new tokens to
        # ``_wave_tokens_since_burst``; a burst takes the sum into its chain
        # entry beside its dispatch number; its landing (``_cycle_landed``)
        # writes both, the waves between (every burst lands, in order: the
        # dispatch numbers between this burst's and the last landed one's),
        # the stamp and the cycle's seconds into ``cycle_ring`` (found beside
        # ``AsyncEngine.request_ring``).  ``cycle_programs`` names the XLA
        # modules of the two step programs, for whoever splits a device trace
        # by cycle: which events are this engine's burst and wave is the
        # engine's to say
        self._wave_tokens_since_burst = 0
        self._landed_seq = 0  # the last burst to land
        self._landed_t: float | None = None  # None: no burst before this one in flight
        self.cycle_ring: deque[dict] = deque(maxlen=CYCLE_RING)
        self.cycle_programs = {"burst": "jit_" + self._decode_burst_fn.__name__,
                               "wave": "jit_" + self._wave_fn.__name__}
        self._m_cycle = [ENGINE_CYCLE.labels(waves=w) for w in ("0", "1", "2+")]

        # advisory page observatory (obs/hbm.py) — request-attribution seams
        self._page_obs = None
        # the open host-phase annotation (utils/profiling.annotate)
        self._phase_ann = None
        # host seconds of the current step by phase name: a slow step's
        # warning (serving/async_engine.py) says which phase held it
        self._phase_name, self._phase_t0 = None, 0.0
        self.step_phase_s: dict[str, float] = {}
        # the compile ledger (obs/engine_profile.py) learns which functions
        # are step programs from the engine that dispatches them
        compile_ledger().watch(self.step_programs())
        init_phase.settles(self._presence)  # the last array made: the pools are before it
        startup_record().finish(init_phase)
        # what each of the two caches holds, beside the phase that made them
        held = lambda tree: sum(int(x.nbytes) for x in jax.tree.leaves(tree))  # noqa: E731
        startup_record().note("pool_bytes", {
            "pages": held((self._k_pages, self._v_pages, self._k_scales, self._v_scales)),
            "state": held(self._state_pools),
            **({"sliding": held((self._sk_pages, self._sv_pages))} if self._sliding else {})})

    def step_programs(self) -> list:
        """Every jitted callable a step of THIS engine can dispatch: the
        jits this module imports and defines, the family's wave and burst,
        and the programs of the modes it was built with.  A back-end compile
        of one of them under traffic is a stall the warm-up owed."""
        programs = [v for v in globals().values() if callable(getattr(v, "_cache_size", None))]
        programs += [self._wave_fn, self._decode_burst_fn]
        if self._sp > 1 and self.sp_prefill_threshold is not None:
            from githubrepostorag_tpu.serving.long_prefill import ring_prefill_packed

            programs.append(ring_prefill_packed)
        return programs

    # ------------------------------------------------------- host phases --

    @property
    def page_pool(self) -> jnp.ndarray:
        """The pool the step programs commit into: the K pool, or a latent
        model's one pool.  Settable, for a caller that runs a step program
        itself on the engine's pool (the benchmark's correctness sample)."""
        return self._k_pages

    @page_pool.setter
    def page_pool(self, pool: jnp.ndarray) -> None:
        self._k_pages = pool

    @property
    def value_pool(self) -> jnp.ndarray | None:
        """The V pool beside ``page_pool`` (None for a latent model)."""
        return self._v_pages

    @value_pool.setter
    def value_pool(self, pool: jnp.ndarray) -> None:
        self._v_pages = pool

    @property
    def sliding_pools(self) -> tuple | None:
        """The (K, V) pools of a model's sliding kind of page (None for every
        other model), ``sliding_ledger`` their ledger: settable like ``page_pool``."""
        return None if self._sliding is None else (self._sk_pages, self._sv_pages)

    @sliding_pools.setter
    def sliding_pools(self, pools: tuple) -> None:
        self._sk_pages, self._sv_pages = pools

    @property
    def sliding_ledger(self):
        return self._sliding

    @property
    def state_pools(self) -> dict | None:
        """A recurrent model's state pools (None for every other model), and
        ``state_slots`` their ledger: settable like ``page_pool``."""
        return self._state_pools

    @state_pools.setter
    def state_pools(self, pools: dict) -> None:
        self._state_pools = pools

    @property
    def state_slots(self):
        return self._state

    def _phase(self, name: str | None, **meta):
        """Name the host work from here to the next ``_phase`` call: one
        profiler annotation, opened after closing the one before it, so the
        phases of a step never overlap whatever calls what (``None`` closes
        without opening).  Returns the annotation (``set_metadata`` adds
        counts known only at its end).  With no trace being taken this is
        two C++ calls and no formatting."""
        now = time.monotonic()
        if self._phase_ann is not None:
            self._phase_ann.__exit__(None, None, None)
            held = self.step_phase_s
            held[self._phase_name] = held.get(self._phase_name, 0.0) + now - self._phase_t0
        self._phase_name, self._phase_t0 = name, now
        self._phase_ann = ann = annotate(name, **meta) if name else None
        if ann is not None:
            ann.__enter__()
        return ann

    # ------------------------------------------------- page observability --

    def attach_page_observer(self, obs) -> None:
        """Register a page observatory: the allocator reports claim deltas
        and tier events, the engine reports per-request holds/releases.
        Both directions are advisory — observability must never break
        serving, so every call is fenced."""
        self._page_obs = obs
        self._allocator.attach_observer(obs)

    def _obs_hold(self, req: "_Request") -> None:
        if self._page_obs is not None:
            try:
                self._page_obs.on_request_hold(
                    req.request_id, req.priority, len(req.pages))
            except Exception:  # noqa: BLE001 - advisory seam
                pass

    def _obs_release(self, rid: str) -> None:
        if self._page_obs is not None:
            try:
                self._page_obs.on_request_release(rid)
            except Exception:  # noqa: BLE001 - advisory seam
                pass

    # ------------------------------------------------------------- intake --

    def add_request(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams | None = None,
        on_token: TokenCallback | None = None,
        request_id: str | None = None,
        deadline_s: float | None = None,
        priority: str | None = None,
        recv_t: float | None = None,
        enqueue_t: float | None = None,
    ) -> str:
        rid = request_id or f"req-{next(self._ids)}"
        sampling = sampling or SamplingParams()
        req = _Request(request_id=rid, prompt=list(prompt_ids), sampling=sampling,
                       on_token=on_token, deadline_ts=deadline_s,
                       priority=priority or self.default_priority,
                       recv_t=recv_t, enqueue_t=enqueue_t)
        req.orig_prompt_len = len(req.prompt)
        if len(req.prompt) + sampling.max_tokens > self.max_seq_len:
            req.sampling = sampling.clamped(self.max_seq_len - len(req.prompt))
        self._requests[rid] = req
        self.requests_admitted += 1
        error = None
        if not req.prompt or len(req.prompt) >= self.max_seq_len:
            error = "prompt empty or exceeds max_seq_len"
        else:
            need = pages_needed(
                min(len(req.prompt) + req.sampling.max_tokens, self.max_seq_len), self.page_size
            )
            if need > self._allocator.num_pages:
                error = (
                    f"request needs {need} KV pages but the pool has only "
                    f"{self._allocator.num_pages}; raise num_pages or shorten the request"
                )
        if error is not None:
            # rejected at intake: surface through the next step() so streaming
            # consumers driving add_request()/step() see a completion
            req.state = "done"
            req.error = error
            self._rejected.append(req)
            return rid
        self._enqueue_waiting(req)
        return rid

    def _enqueue_waiting(self, req: _Request) -> None:
        """Queue a fresh arrival.  With priority scheduling on, protected-
        class arrivals insert ahead of every batch-class waiter (FCFS within
        the class); otherwise intake is strictly FCFS."""
        if self._priority_sched and req.priority == self.protected_priority:
            for i, other in enumerate(self._waiting):
                if other.priority != self.protected_priority:
                    self._waiting.insert(i, req)
                    return
        self._waiting.append(req)

    def cancel(self, request_id: str) -> None:
        req = self._requests.get(request_id)
        if req is not None:
            req.cancelled = True

    def has_work(self) -> bool:
        return bool(self._waiting or self._row_req or self._rejected
                    or self._parked)

    @property
    def num_running(self) -> int:
        return len(self._row_req)

    @property
    def is_admitting(self) -> bool:
        """True while a prompt wave is still being admitted — requests are
        queued or mid-prefill.  Drives prefill-priority scheduling and lets
        callers classify the next step without reaching into engine
        privates."""
        return bool(self._waiting) or any(
            r.state == "prefilling" for r in self._row_req.values())

    @property
    def num_waiting(self) -> int:
        return len(self._waiting)

    @property
    def num_parked(self) -> int:
        return len(self._parked)

    # --------------------------------------------------------- scheduling --

    def step(self) -> list[GenerationResult]:
        """One engine iteration: admit + prefill one chunk AND decode every
        running row — both dispatched in the same step with no host sync in
        between (vLLM's chunked-prefill mixing).  The device serializes the
        two programs on the donated pools, so a long multi-chunk prompt
        never stalls running streams: each of its prefill steps rides along
        with a full decode burst.  Returns requests finished this step."""
        finished: list[GenerationResult] = []
        self.step_phase_s = {}
        self._step_starved = False
        self._phase("engine.admit")
        for req in self._rejected:
            res = self._result(req, "error")
            res.error = req.error
            finished.append(res)
        self._rejected.clear()
        self._reap_expired()
        self._reap_cancelled(finished)
        self._reap_parked(finished)
        if self._kv_tier_on:
            self._migrate_pages()
        if self._preempt_on:
            self._maybe_preempt(finished)
        self._unpark_ready()

        t_pf = time.monotonic()
        prefilled = self._try_prefill(finished)
        self.prefill_seconds_total += time.monotonic() - t_pf
        if self._waiting:
            # a request is still queued after an admission attempt: blocked
            # on rows/pages/dedup-hold this step (ledger's hbm_pages signal)
            self.admission_blocked_steps += 1
        running = [r for r in self._row_req.values() if r.state == "running"]
        if self.prefill_priority and prefilled and self.is_admitting:
            # prefill-priority: a chunk ran and prompts remain — give the
            # next step to admission instead of a decode burst.  No
            # starvation: once nothing can prefill, ``prefilled`` is False
            # and decode always runs (which is also what frees pages).
            running = []
        if running:
            t_run = time.monotonic()
            # the one decode path, looked up on the instance at each call (the
            # benchmark's probe wraps it there).  ``path`` and ``dt`` also keep
            # this frame the size it had: see tests/test_engine.py::
            # test_the_frames_under_a_step_programs_first_call_keep_their_size
            path = self._decode_step
            path(finished)
            dt = time.monotonic() - t_run
            self.decode_seconds_total += dt
        if not self._row_req:
            # nothing left running: land any in-flight burst (its tokens
            # belong to already-finished rows) and recycle deferred pages
            self._drain_chain(finished)
        self._phase(None)
        return finished

    def _reap_expired(self) -> None:
        """Mark past-deadline requests cancelled so the cancel/reap path
        below returns their pages this step — a job whose caller already
        timed out must not keep decoding to max_tokens on the device
        (the orphaned-work half of the scheduler-stall argument)."""
        now = time.monotonic()
        for req in itertools.chain(self._waiting, self._row_req.values(),
                                   self._parked):
            if (
                req.deadline_ts is not None
                and not req.cancelled
                and now >= req.deadline_ts
            ):
                req.cancelled = True
                req.deadline_expired = True
                self.deadline_reaps += 1

    def _reap_cancelled(self, finished: list[GenerationResult]) -> None:
        for req in [r for r in self._waiting if r.cancelled]:
            self._waiting.remove(req)
            req.state = "done"
            if req.deadline_expired:
                self.reaped_tokens += len(req.output)
            finished.append(self._result(
                req, "deadline" if req.deadline_expired else "cancelled"))
        for row, req in list(self._row_req.items()):
            if req.cancelled:
                self._release(req)
                if req.deadline_expired:
                    self.reaped_tokens += len(req.output) + len(req.prior_output)
                finished.append(self._result(
                    req, "deadline" if req.deadline_expired else "cancelled"))

    def _reap_parked(self, finished: list[GenerationResult]) -> None:
        """Finish cancelled/expired parked requests.  Their device pages
        were returned at park time and their host copies are plain cache
        entries the LRU trims — both tiers freed exactly once, nothing to
        release here beyond the bookkeeping."""
        for req in [r for r in self._parked if r.cancelled]:
            self._parked.remove(req)
            req.state = "done"
            if req.deadline_expired:
                self.reaped_tokens += len(req.output) + len(req.prior_output)
            finished.append(self._result(
                req, "deadline" if req.deadline_expired else "cancelled"))

    # ------------------------------------------- preempt-to-host (parking) --

    def set_class_pressure(self, states: dict[str, int]) -> None:
        """Install the SLO plane's per-class burn-rate states (0 ok / 1 warn
        / 2 critical).  AsyncEngine pushes this from its drive loop; a bare
        engine never sees pressure and preempts only on the direct trigger
        (protected head-of-queue infeasible)."""
        self._class_pressure = dict(states)

    def drain_park_events(self) -> list[str]:
        """Return-and-clear the rids parked since the last drain (AsyncEngine
        turns these into ``parked`` stream events for disagg fallback)."""
        events, self._park_events = self._park_events, []
        return events

    def _class_headroom(self, req: _Request) -> int:
        """KV pages this request's admission must leave allocatable.  The
        protected class never pays its own reservation; batch admission pays
        double while the protected class is in SLO warn (the ladder's
        throttle rung)."""
        if req.priority == self.protected_priority:
            return 0
        hr = self.preempt_headroom_pages
        if hr and self._class_pressure.get(self.protected_priority, 0) >= 1:
            hr *= 2
        return hr

    def _maybe_preempt(self, finished: list[GenerationResult]) -> None:
        """Park batch-class victims to the host tier until the trigger is
        satisfied.  Two triggers: the direct one (a protected-class request
        heads the queue but cannot be admitted) and the SLO one (the
        protected class burns critically — clear the headroom reservation
        proactively so the next arrival admits without waiting a step).

        Draining the in-flight chain can finish (and free) the would-be
        victim, so each iteration drains + re-checks capacity BEFORE picking
        a victim; parking therefore always happens with no live chain, which
        keeps the row teardown identical to ``_release``'s immediate path."""
        target: _Request | None = None
        if self._waiting and self._waiting[0].priority == self.protected_priority:
            target = self._waiting[0]
        critical = self._class_pressure.get(self.protected_priority, 0) >= 2
        if target is None and not critical:
            return
        guard = 2 * self.max_num_seqs + 8  # paranoia bound, never binds
        while guard > 0:
            guard -= 1
            if target is not None:
                need, hashes = self._head_need_hashes(target)
                if self._free_rows and self._allocator.can_admit(hashes, need):
                    return
            elif self._allocator.can_admit(
                    [], max(1, self.preempt_headroom_pages)):
                return
            if self._chain is not None or self._deferred:
                # land the burst first: its commits may finish the victim
                # we'd otherwise park, and deferred pages may be enough
                self._drain_chain(finished)
                self._phase("engine.admit")
                continue
            victim = self._pick_victim()
            if victim is None:
                return
            self._park_victim(victim)
            # dispatch the priority writebacks NOW so the parked pages
            # unpin within this step — otherwise the admission this park
            # enables would stall a boundary behind its own victim
            while (self._allocator.pending_park_writebacks
                   and self._migrate_pages()):
                pass

    def _pick_victim(self) -> _Request | None:
        """Choose the running batch-class request to park: latest deadline
        (no deadline sorts last == most preemptible), then most pages.
        Only page-aligned victims qualify — a victim whose committed KV
        doesn't cover its prompt would need prompt re-prefill on resume,
        violating the zero-recomputed-prefill contract."""
        ps = self.page_size
        best: _Request | None = None
        best_key: tuple = ()
        for req in self._row_req.values():
            if req.state != "running" or req.cancelled:
                continue
            if req.priority == self.protected_priority:
                continue
            if (req.seq_len // ps) * ps < len(req.prompt):
                continue  # mid-prompt: resume would recompute prefill
            key = (req.deadline_ts is None, req.deadline_ts or 0.0,
                   len(req.pages))
            if best is None or key > best_key:
                best, best_key = req, key
        return best

    def _park_victim(self, req: _Request) -> None:
        """Evict a running request's KV to the host tier and park it.

        The full token stream so far (prompt + committed output) becomes the
        request's NEW prompt; on resume, admission prefix-shares the full
        pages back (device hit or host fault-in) and prefill recomputes only
        the partial tail page — decode then continues token-identically.
        ``max_tokens`` shrinks by the tokens already produced, so the
        combined budget (and every stop condition) is unchanged."""
        ps = self.page_size
        stream = req.prompt + req.output
        full = req.seq_len // ps  # pages whose KV is fully committed
        hashes = page_hashes(stream[: full * ps], ps)
        if req.claimed_hashes:
            self._allocator.unclaim(req.claimed_hashes)
            req.claimed_hashes = []
        for j in range(req.pages_registered, full):
            # first-writer-wins: registering an already-known hash is a no-op
            self._allocator.register(hashes[j], req.pages[j])
        pages, req.pages = req.pages, []
        self.preempted_pages += len(pages)
        self._allocator.park(pages)
        # park ends this hold; the resume re-admission opens a new one
        # under the same rid (the observatory merges the two)
        self._obs_release(req.request_id)
        row = req.row
        self._free_rows.append(row)
        self._row_req.pop(row, None)
        self._seq_lens[row] = 0
        self._block_tables[row] = 0
        self._row_limits[row] = 0
        self._temp[row] = 1.0
        self._top_p[row] = 1.0
        self._top_k[row] = 0
        self._rep_pen[row] = 1.0
        req.row = -1
        produced = len(req.output)
        req.prior_output.extend(req.output)
        req.prompt = stream
        req.output = []
        req.page_hashes = []  # stale: recomputed from the folded prompt
        req.pages_registered = 0
        req.cached_tokens = 0
        req.prefill_pos = 0
        req.seq_len = 0
        if produced:
            remaining = max(1, req.sampling.max_tokens - produced)
            req.sampling = replace(req.sampling, max_tokens=remaining)
        req.state = "parked"
        req.preempted += 1
        req.resume_pending = False
        self._parked.append(req)
        self._park_events.append(req.request_id)
        self.preemptions += 1

    def _unpark_ready(self) -> None:
        """Move parked requests whose pages fit back to the waiting queue,
        earliest deadline first.  Holds everything while the protected class
        is still critical (anti-thrash: un-parking into the pressure that
        caused the park just cycles pages through the tier)."""
        if not self._parked:
            return
        if self._class_pressure.get(self.protected_priority, 0) >= 2:
            return
        self._parked.sort(
            key=lambda r: (r.deadline_ts is None, r.deadline_ts or 0.0))
        while self._parked:
            req = self._parked[0]
            need, hashes = self._head_need_hashes(req)
            if not self._free_rows or not self._allocator.can_admit(
                    hashes, need, headroom=self._class_headroom(req)):
                break  # deadline order: later victims don't jump the head
            self._parked.pop(0)
            req.state = "waiting"
            req.resume_pending = True
            self._requeue_resumed(req)

    def _requeue_resumed(self, req: _Request) -> None:
        """Resumed victims queue behind the protected block but ahead of
        queued batch arrivals — they already ran once and hold host-tier
        state worth reusing soon."""
        for i, other in enumerate(self._waiting):
            if (other.priority != self.protected_priority
                    or req.priority == self.protected_priority):
                self._waiting.insert(i, req)
                return
        self._waiting.append(req)

    def _migrate_pages(self) -> bool:
        """Step-boundary device->host page migration (tiered engines only).

        Two halves, neither blocking the device:
          1. LAND the previous boundary's in-flight writeback gathers.
             Their ``copy_to_host_async`` DMAs had a whole engine step to
             stream out, so the host reads here wait (if at all) on
             transfers that are already done, and each page payload
             publishes to the allocator's host map under its chain hash.
          2. PLAN + DISPATCH a new gather burst over the coldest parked
             pages not yet saved (``TieredPageAllocator.evict`` — a
             residency transition, not a release: the pages stay device
             shareable until ``allocate`` reclaims them).  Dispatch-only;
             the result is read at the NEXT boundary (half 1).

        Returns True if any work happened (flush_kv_migrations loops on it).
        """
        t0 = time.monotonic()
        moved = False
        alloc = self._allocator
        for bufs, hashes in self._wb_pending:
            payloads = split_page_payloads(bufs, len(hashes))
            for h, payload in zip(hashes, payloads):
                alloc.complete_writeback(h, payload)
            moved = True
        self._wb_pending.clear()
        plan = alloc.evict(self.kv_migrate_burst)
        if plan:
            nb = _bucket(len(plan), self.kv_migrate_burst, minimum=1)
            idx_np = np.full((nb,), -1, dtype=np.int32)
            idx_np[: len(plan)] = [p for p, _ in plan]
            idx = jnp.asarray(idx_np)
            k, v, ks, vs = gather_pages(
                self._k_pages, self._v_pages, idx, self._k_scales, self._v_scales
            )
            bufs = (k, v, ks, vs)
            for arr in bufs:
                if arr is not None and hasattr(arr, "copy_to_host_async"):
                    arr.copy_to_host_async()
            self._wb_pending.append((bufs, [h for _, h in plan]))
            self.kv_migrations += 1
            moved = True
        if moved:
            self.migration_seconds_total += time.monotonic() - t0
        return moved

    def _dispatch_fault_ins(self) -> None:
        """Scatter staged host->device page payloads into the pools.

        MUST dispatch before any program that could read the faulted pages
        this step (_try_prefill calls it right after the admission loop):
        the device serializes programs on the donated pools, so dispatch
        order alone makes the faulted content visible to the admitted rows'
        prefill and every later decode — no host sync, decode never stalls
        on migration."""
        staged = self._allocator.fault_in()
        if not staged:
            return
        t0 = time.monotonic()
        # stored head width comes from the pool, not the config: int4
        # pages nibble-pack two components per byte (head_dim // 2)
        ps, hd = self.page_size, self._k_pages.shape[-1]
        L, n_kv = self.cfg.num_layers, self.cfg.num_kv_heads
        quant = self._k_scales is not None
        while staged:
            burst = staged[: self.kv_migrate_burst]
            staged = staged[self.kv_migrate_burst:]
            nb = _bucket(len(burst), self.kv_migrate_burst, minimum=1)
            idx = np.full((nb,), -1, dtype=np.int32)
            k_vals = np.zeros((L, n_kv, nb, ps, hd), dtype=self._k_pages.dtype)
            v_vals = np.zeros_like(k_vals)
            ks_vals = np.zeros((L, n_kv, nb), dtype=np.float32) if quant else None
            vs_vals = np.zeros((L, n_kv, nb), dtype=np.float32) if quant else None
            for i, (page, payload) in enumerate(burst):
                pk, pv, pks, pvs = payload
                idx[i] = page
                k_vals[:, :, i] = pk
                v_vals[:, :, i] = pv
                if quant:
                    ks_vals[:, :, i] = pks
                    vs_vals[:, :, i] = pvs
            idx_d = jnp.asarray(idx)
            (self._k_pages, self._v_pages, self._k_scales,
             self._v_scales) = scatter_pages(
                self._k_pages, self._v_pages, idx_d, jnp.asarray(k_vals),
                self._k_scales, self._v_scales,
                v_vals=jnp.asarray(v_vals),
                ks_vals=None if ks_vals is None else jnp.asarray(ks_vals),
                vs_vals=None if vs_vals is None else jnp.asarray(vs_vals),
            )
            self.kv_fault_dispatches += 1
        self.fault_in_seconds_total += time.monotonic() - t0

    def flush_kv_migrations(self) -> None:
        """Run migration boundaries until quiescent — every plannable
        writeback dispatched AND landed.  Tests use this for a
        deterministic host-tier state between traffic phases; the serving
        loop never needs it (step() makes incremental progress)."""
        if not self._kv_tier_on:
            return
        while self._migrate_pages():
            pass

    # -------------------------------------------- disagg export / import --

    def export_kv_pages(self, hashes: list[bytes]) -> list[tuple[bytes, object]]:
        """Pack the KV payloads for ``hashes`` for shipment to a peer
        replica (disaggregated prefill->decode handoff; caller holds the
        driver lock).  Host-tier copies serve directly; device-resident
        pages gather through the SAME power-of-two migration-burst ladder
        warmup precompiled, so an export can never mint a live XLA
        program.  Hashes in neither tier are silently skipped — the
        importer recomputes that tail, token-identically.

        Unlike ``_migrate_pages`` this reads the gathers back synchronously
        (the payload leaves this replica now); that device sync is the
        price of the handoff and is charged to ``transfer_seconds_total``
        (the ledger's ``kv_transfer`` bucket), never to a decode replica's
        step loop."""
        if not self._kv_tier_on or not hashes:
            return []
        t0 = time.monotonic()
        alloc = self._allocator
        out: list[tuple[bytes, object]] = []
        to_gather: list[tuple[bytes, int]] = []
        for h in hashes:
            payload = alloc.host_payload(h)
            if payload is not None:
                out.append((h, payload))
                continue
            page = alloc.device_page_of(h)
            if page is not None:
                to_gather.append((h, page))
        while to_gather:
            burst = to_gather[: self.kv_migrate_burst]
            to_gather = to_gather[self.kv_migrate_burst:]
            nb = _bucket(len(burst), self.kv_migrate_burst, minimum=1)
            idx_np = np.full((nb,), -1, dtype=np.int32)
            idx_np[: len(burst)] = [p for _, p in burst]
            idx = jnp.asarray(idx_np)
            k, v, ks, vs = gather_pages(
                self._k_pages, self._v_pages, idx, self._k_scales, self._v_scales
            )
            payloads = split_page_payloads((k, v, ks, vs), len(burst))
            out.extend((h, p) for (h, _), p in zip(burst, payloads))
        self.kv_pages_exported += len(out)
        self.transfer_seconds_total += time.monotonic() - t0
        return out

    def import_kv_pages(self, pages: list[tuple[bytes, object]]) -> int:
        """Admit transferred page payloads into the host tier (decode-side
        half of the handoff; caller holds the driver lock).  Pure host-dict
        work — the device is untouched until an admission ``share``s the
        hash and the ordinary fault-in scatter (warmed shapes) lands it.
        A hash this replica already serves from either tier is dropped by
        the allocator, so a prefix it holds content-hash-deduped costs
        nothing.  Returns how many payloads were stored."""
        if not self._kv_tier_on or not pages:
            return 0
        t0 = time.monotonic()
        alloc = self._allocator
        stored = 0
        for h, payload in pages:
            stored += bool(alloc.import_page(h, payload))
        self.kv_pages_imported += stored
        self.transfer_seconds_total += time.monotonic() - t0
        return stored

    def _register_full_pages(self, req: _Request) -> None:
        """Publish every prompt page prefill has completed so far: its KV is
        final (decode writes land past the prompt), so identical prefixes
        admitted from now on skip recomputing it.  Shared by the chunked and
        sp-prefill paths."""
        if not self.prefix_caching:
            return
        if not req.page_hashes:
            req.page_hashes = page_hashes(req.prompt, self.page_size)
        full = min(req.prefill_pos // self.page_size, len(req.page_hashes))
        while req.pages_registered < full:
            j = req.pages_registered
            self._allocator.register(req.page_hashes[j], req.pages[j])
            if req.claimed_hashes and req.claimed_hashes[0] == req.page_hashes[j]:
                # the registration this admission promised has landed —
                # drop the pending claim so held followers can share it
                req.claimed_hashes.pop(0)
                self._allocator.unclaim([req.page_hashes[j]])
            req.pages_registered = j + 1

    def is_longctx(self, prompt_len: int) -> bool:
        """Would a prompt of this length take the ring-prefill path?  The
        async driver classifies such requests into the ``longctx`` SLO
        class (obs/slo.py per-class thresholds) with the SAME conditions
        the scheduler routes by — one predicate, no drift."""
        return (
            self.sp_prefill_threshold is not None
            and self._sp > 1
            and prompt_len >= self.sp_prefill_threshold
        )

    def _sp_eligible(self, req: _Request) -> bool:
        """Long prompts take the sequence-parallel ring-prefill path: the
        whole prompt in one program, attention sharded over sp."""
        return self.is_longctx(len(req.prompt))

    def _commit_first_now(self, others_running: bool) -> bool:
        """Whether a freshly-prefilled row's first token commits with an
        immediate host sync (best TTFT) instead of queueing on device into
        ``_pending_first`` for the next decode dispatch.  The single source
        of truth for all three prefill paths: a chain in flight holds stale
        device state that must not race a fresh commit, and while other
        rows are running an admission never stalls streams on a host sync."""
        return self._chain is None and not others_running

    def _dispatch_width(self, longest_chunk: int, rows: int) -> int:
        """The width a wave of ``rows`` rows (its row bucket) whose longest
        pending chunk is ``longest_chunk`` runs at: the narrowest rung of
        the bucket's ladder that holds it.  The ONLY width-selection rule on
        the host; the wave program is handed the result and cuts its chunk
        to that rung."""
        ladder = width_ladder(self.prefill_chunk, self.page_size, rows)
        return min(w for w in ladder if w >= longest_chunk)

    def packed_prefill_buckets(self) -> list[int]:
        """The exact set of segment-count row buckets the packed prefill
        can dispatch at — one compiled ``forward_paged_packed`` program per
        entry, nothing else.  A dispatch packs at most
        min(max_num_seqs, budget) segments (every segment carries >= 1
        token), and segment counts bucket through the same ``_bucket``
        call ``_prefill_batch_packed`` uses, so warmup() and live traffic
        can never desynchronize."""
        cap = min(self.max_num_seqs, self.prefill_token_budget or 0)
        out: list[int] = []
        b = 1
        while cap:
            out.append(_bucket(min(b, cap), self.max_num_seqs, minimum=1))
            if b >= cap:
                break
            b *= 2
        return list(dict.fromkeys(out))

    def sp_ring_bucket_ladder(self) -> list[int]:
        """The exact set of ring-buffer widths the sequence-parallel prefill
        can dispatch at — one compiled ring program per entry, nothing else
        (the SP_RING_BUCKETS ladder).  Powers of two from the threshold
        bucket up to bucketed max_seq_len, each rounded up to a multiple of
        sp (shard_map needs sp | width); ``sp_ring_buckets`` > 0 keeps only
        that many from the widest down.  warmup() precompiles every entry
        and ``_ring_width`` selects from the same list, so live traffic can
        never reach an unwarmed ring shape."""
        if self.sp_prefill_threshold is None or self._sp <= 1:
            return []
        floor = max(self.sp_prefill_threshold, self._sp, 1)
        w = 1
        while w < floor:
            w *= 2
        out: list[int] = []
        cap = _bucket(self.max_seq_len, self.max_seq_len, minimum=self._sp)
        while True:
            width = -(-min(w, cap) // self._sp) * self._sp
            out.append(width)
            if w >= cap:
                break
            w *= 2
        out = list(dict.fromkeys(out))
        if self.sp_ring_bucket_count > 0:
            out = out[-self.sp_ring_bucket_count:]
        return out

    def _ring_width(self, total: int) -> int:
        """Ring dispatch width for a pass carrying ``total`` real tokens:
        the smallest ladder entry covering it.  The ONLY width-selection
        rule for the packed ring path — warmup() iterates the same ladder,
        so the two can never desynchronize."""
        ladder = self.sp_ring_bucket_ladder()
        for w in ladder:
            if w >= total:
                return w
        return ladder[-1]

    def _head_need_hashes(self, req: _Request) -> tuple[int, list[bytes]]:
        """Total page need for ``req`` and the chain hashes of the prefix
        pages an admission would be allowed to share (capped so at least one
        prompt token still runs through prefill)."""
        need = pages_needed(
            min(len(req.prompt) + req.sampling.max_tokens, self.max_seq_len), self.page_size
        )
        hashes: list[bytes] = []
        # ring prefill runs the prompt from position 0 in one program — it
        # cannot resume at a cached boundary, so sp-bound prompts skip the
        # prefix cache (they may still REGISTER their pages for others)
        if self.prefix_caching and not self._sp_eligible(req):
            if not req.page_hashes:
                req.page_hashes = page_hashes(req.prompt, self.page_size)
            shareable = min(len(req.page_hashes), (len(req.prompt) - 1) // self.page_size)
            hashes = req.page_hashes[:shareable]
            if self._state is not None:
                # a hit is only as deep as the deepest matched page boundary
                # that also has a state snapshot; pages past it are computed
                # again on pages of the request's own
                req.page_match = self._allocator.match_len(hashes)
                hashes = hashes[:self._state.depth(hashes[:req.page_match])]
            if self._sliding is not None:
                # nor deeper than where the sliding kind still holds the pages
                # of the window that ends there
                req.page_match = self._allocator.match_len(hashes)
                hashes = hashes[:self._sliding.depth(hashes, req.page_match)]
        return need, hashes

    def _pools_admit(self, hashes: list[bytes], need: int, headroom: int = 0,
                     drained: bool = False) -> bool:
        """``can_admit`` of every kind of page: the global kind's allocator, and
        the sliding kind's ledger for the window's pages and the row's own
        (``drained``: counting what a drain of the chain would recycle)."""
        extra = extra_s = 0
        if drained:
            # only deferred pages nobody else shares actually free on drain
            extra = sum(self._allocator.releasable_count(pages)
                        for _, pages, _ in self._deferred)
            if self._sliding is not None:
                extra_s = self._sliding.alloc.releasable_count(self._deferred_sliding)
        if not self._allocator.can_admit(hashes, need, extra_free=extra, headroom=headroom):
            return False
        return self._sliding is None or self._sliding.can_admit(
            hashes, len(hashes), need, extra_free=extra_s)

    def _admission_feasible(self) -> bool:
        """True when the head-of-queue request could actually be admitted
        (row + pages available, counting prefix-cache shares and rows/pages
        that a chain drain would recycle).  Draining the decode pipeline is
        expensive — don't do it for an admission the allocator would refuse
        anyway."""
        if not self._waiting:
            return False
        req = self._waiting[0]
        need, hashes = self._head_need_hashes(req)
        rows_avail = bool(self._free_rows) or bool(self._deferred)
        return rows_avail and self._pools_admit(
            hashes, need, headroom=self._class_headroom(req), drained=True)

    def _try_prefill(self, finished: list[GenerationResult]) -> bool:
        """Admit every waiting request the pool can back, then run ONE
        batched prefill chunk over all prefilling rows.  Returns True if a
        prefill chunk ran.

        Runs WITHOUT draining the decode pipeline: free rows/pages are by
        construction unreferenced by any in-flight burst (finished rows sit
        in ``_deferred`` until a drain).  The chain is drained only when the
        head-of-queue request needs those deferred resources (see
        _admission_feasible)."""
        if self._waiting:
            req0 = self._waiting[0]
            need0, hashes0 = self._head_need_hashes(req0)
            can_free = bool(self._free_rows) and self._pools_admit(
                hashes0, need0, headroom=self._class_headroom(req0))
            if not can_free and self._admission_feasible():
                self._drain_chain(finished)
        # admit as many waiting requests as rows + pages allow
        admit = self._phase("engine.admit")
        admitted = 0
        cached_admits: list[_Request] = []  # batched presence marking below
        at = 0  # requests held for a leader (``_held_for_leader``) are passed over
        while at < len(self._waiting) and self._free_rows:
            req = self._waiting[at]
            need, hashes = self._head_need_hashes(req)
            assert need <= self.max_pages_per_seq, "intake clamp must bound the page need"
            if (req.priority != self.protected_priority and self._preempt_on
                    and self._class_pressure.get(
                        self.protected_priority, 0) >= 2):
                # ladder rung 3: while the protected class burns critically,
                # batch admission pauses entirely — every free page belongs
                # to the class we're preempting FOR
                break
            if not self._pools_admit(hashes, need, headroom=self._class_headroom(req)):
                break  # headroom reservation: batch leaves protected room
            if self._held_for_leader(req, hashes, need):
                at += 1
                continue
            faults_before = (
                self._allocator.fault_ins if self._kv_tier_on else 0
            )
            shared = self._allocator.share(hashes) if hashes else []
            if self._state is not None and shared:
                deep = self._state.depth(hashes[:len(shared)])
                self._allocator.release(shared[deep:])
                shared = shared[:deep]
            try:
                pages = shared + self._allocator.allocate(need - len(shared))
            except OutOfPages:
                self._allocator.release(shared)
                break  # wait for running requests to finish
            self._waiting.pop(at)
            admitted += 1
            row = self._free_rows.pop()
            req.row, req.pages, req.state = row, pages, "prefilling"
            req.prefill_start_t = time.monotonic()
            self._obs_hold(req)
            if self._kv_tier_on:
                req.faulted_pages += self._allocator.fault_ins - faults_before
                claimed = hashes[len(shared):]
                if claimed:
                    # promise the pages this prefill will register, so
                    # identical-prefix followers can wait for one
                    # registration instead of allocating twins
                    self._allocator.claim(claimed)
                    req.claimed_hashes = list(claimed)
            # cache hit: prefill resumes after the shared pages' tokens
            req.cached_tokens = len(shared) * self.page_size
            req.prefill_pos = req.cached_tokens
            req.seq_len = req.cached_tokens
            req.pages_registered = len(shared)
            if shared:
                self._allocator.hit_tokens += req.cached_tokens
            if self._state is not None:
                self._admit_state(req, len(shared))
            if self._sliding is not None:
                self.page_hit_tokens += req.page_match * self.page_size
                self.sliding_hit_tokens += req.cached_tokens
                req.sliding = self._sliding.admit(req.page_hashes, len(shared), need)
            if req.resume_pending:
                # a parked victim is back: its folded prompt prefix-shared
                # the full pages it parked (device hit or host fault-in);
                # prefill recomputes only the partial tail page.  The gate
                # counters below prove the zero-recomputed-prefill contract.
                req.resume_pending = False
                self.preempt_resumes += 1
                if self._kv_tier_on:
                    self.resume_faulted_pages += (
                        self._allocator.fault_ins - faults_before)
                kv_at_park = len(req.prompt) - 1  # KV the victim had parked
                self.resume_recomputed_tokens += max(
                    0, kv_at_park - req.cached_tokens)
                self.resume_recomputed_prompt_tokens += max(
                    0, req.orig_prompt_len - req.cached_tokens)
            self._row_req[row] = req
            self._block_tables[row, : len(pages)] = pages
            self._seq_lens[row] = req.cached_tokens
            # device-side decode guard: a burst may never scatter past this
            # row's allocated pages (nor past the cache-length cap)
            self._row_limits[row] = min(len(pages) * self.page_size, self.max_seq_len - 1)
            if req.sliding is not None:
                self._sliding_row(req)
            self._set_row_sampling(row, req.sampling)
            if req.cached_tokens:
                cached_admits.append(req)
        if self._kv_tier_on:
            # scatter any fault-ins share() staged during admission BEFORE
            # the prefill/decode programs below can read those pages
            self._dispatch_fault_ins()
        if cached_admits:
            # skipped prefixes still count for repetition penalty: mark
            # their tokens in the presence mask — ONE batched dispatch per
            # admission wave at a power-of-two row bucket (the per-request
            # [1, max_seq] call made a warm 64-stream wave pay 64
            # sequential device round-trips, measurably WORSE TTFT than
            # the cache-miss path over a slow host link; bucketing
            # keeps the single-hit payload at [1, max_seq], not
            # [max_num_seqs, max_seq])
            nr = _bucket(len(cached_admits), self.max_num_seqs, minimum=1)
            ids = np.zeros((nr, self.max_seq_len), dtype=np.int32)
            rows = np.zeros((nr,), dtype=np.int32)
            lens = np.zeros((nr,), dtype=np.int32)
            for i, req in enumerate(cached_admits):
                ids[i, : req.cached_tokens] = req.prompt[: req.cached_tokens]
                rows[i] = req.row
                lens[i] = req.cached_tokens
            self._presence = _mark_presence_chunks(
                self._presence,
                jnp.asarray(rows),
                jnp.asarray(ids),
                jnp.asarray(lens),
                self.cfg.vocab_size,
            )
        admit.set_metadata(admitted=admitted, waiting=len(self._waiting))
        prefilling = [r for r in self._row_req.values() if r.state == "prefilling"]
        if not prefilling:
            return False
        long_reqs = [r for r in prefilling if self._sp_eligible(r) and r.prefill_pos == 0]
        if long_reqs:
            self._phase("engine.prefill_batch")  # the ring pass annotates inside
            # segment-packed: every waiting long prompt that fits the ring
            # token budget shares ONE pass (a prompt alone is a pass of one
            # segment); the rest keep their rows and ride the next step's
            # pass (step() re-enters _try_prefill every iteration, so
            # nothing starves)
            self._sp_prefill_packed(long_reqs, finished)
            # served or not, ring-bound rows never fall through to the
            # chunked path below — a leftover would lose its from-position-0
            # ring contract the moment a chunk advanced its prefill_pos
            for req in long_reqs:
                prefilling.remove(req)
        if prefilling:
            # a latent model caps the rows of one wave at the largest row
            # bucket its prefill program is warmed for; the rest, admitted
            # later, ride the next step's wave: step() re-enters here
            cap = self.cfg.prefill_rows_cap if self._own_programs else len(prefilling)
            self._prefill_batch(prefilling[:cap], finished)
        return True

    # ------------------------------------------------------------ compute --

    def _prefill_batch(self, reqs: list[_Request], finished: list[GenerationResult]) -> None:
        """One prefill dispatch covering a chunk of EVERY prefilling row —
        vLLM-style batched prefill compute rather than one program per
        request.  Rows at different prompt offsets ride the same program via
        per-row positions / cached_lens / slot mappings; rows whose prompt
        completes this chunk get their first token sampled in the same
        program, which scatters it into the first-token array.  This method
        ends at that dispatch: when decode is running, the sampled tokens are
        NOT fetched — the wave is queued on device and commits with the next
        burst, so admissions never stall running streams on a host sync."""
        if self.prefill_token_budget is not None:
            # the packed dispatch keeps its own annotation inside the phase
            self._phase("engine.prefill_batch")
            self._prefill_batch_packed(reqs, finished)
            return
        others_running = any(r.state == "running" for r in self._row_req.values())
        n = len(reqs)
        wave_ann = self._phase("engine.prefill_batch")
        # Shape discipline: row count buckets to powers of two and every
        # array is prefill_chunk wide, so a row bucket is ONE compiled
        # program (a distinct device shape is a multi-second XLA compile;
        # steady-state traffic must only ever see shapes that warmup() has
        # compiled).  The wave's longest chunk picks a rung of the width
        # ladder; the program is handed it and runs that many columns.
        rb = _bucket(n, self.max_num_seqs, minimum=1)
        chunk = self.prefill_chunk
        width = self._dispatch_width(
            max(min(len(r.prompt) - r.prefill_pos, chunk) for r in reqs), rb)

        ids = np.zeros((rb, chunk), dtype=np.int32)
        pos = np.zeros((rb, chunk), dtype=np.int32)
        slots = np.full((rb, chunk), -1, dtype=np.int32)
        bt = np.zeros((rb, self.max_pages_per_seq), dtype=np.int32)
        cached = np.zeros((rb,), dtype=np.int32)
        new_lens = np.zeros((rb,), dtype=np.int32)
        # logits only at each row's last valid position: full-position
        # prefill logits are [rb, width, V] float32 — GBs at 64 rows
        last_idx = np.zeros((rb,), dtype=np.int32)
        row_idx = np.zeros((rb,), dtype=np.int32)  # the engine row of each wave row
        done_mask = np.zeros((rb,), dtype=bool)  # this chunk completes the prompt
        valids = []
        for i, req in enumerate(reqs):
            start = req.prefill_pos
            valid = min(len(req.prompt) - start, chunk)
            valids.append(valid)
            ids[i, :valid] = req.prompt[start : start + valid]
            pos[i] = np.arange(start, start + chunk)
            slots[i] = slot_mapping(self._block_tables[req.row], start, valid, self.page_size, chunk)
            bt[i] = self._block_tables[req.row]
            cached[i] = start
            new_lens[i] = valid
            last_idx[i] = valid - 1
            row_idx[i] = req.row
            done_mask[i] = start + valid >= len(req.prompt)
        # the wave's real work, for whoever reads the trace: tokens already in
        # the cache, tokens this chunk adds (of the row bucket x width the
        # program multiplies), the (query, key) pairs they attend, and the
        # prompts this chunk completes
        starts = [int(c) for c in cached[:n]]
        wave_ann.set_metadata(
            rows=n, new_tokens=sum(valids), cached_tokens=sum(starts),
            pairs=sum(v * c + v * (v + 1) // 2 for v, c in zip(valids, starts)),
            completes=int(done_mask.sum()), width=width, padded_tokens=rb * width,
            seq=self.step_dispatches_total + 1)
        self.prefill_padded_tokens += rb * width
        self._m_wave[width].inc()
        if self._wave_tiles is not None:
            self._count_wave_tiles(wave_ann, cached, new_lens, width)
        state_args = {}
        if self._state is not None:
            state_args = self._wave_state(reqs, valids, rb)
            wave_ann.set_metadata(**self._state_meta())
        if self._sliding is not None:  # the second kind of page rides where the state does
            state_args = {**state_args, **self._wave_sliding(reqs, valids, starts, rb, wave_ann)}

        # ONE program: the chunk, then its tail (prompt tokens into the
        # presence mask, the first token of every completed row drawn, marked
        # and scattered into the first-token array).  Its inputs are host
        # arrays and device state the step programs hand each other, so the
        # host returns from here while the wave is still queued: nothing of
        # the wave is read before _commit_first_tokens fetches it
        self.step_dispatches_total += 1
        self._note_dispatch()
        self._wave_tokens_since_burst += sum(valids)
        self._first_d, self._presence, *cache = self._wave_fn(
            self.params, self.cfg,
            ids, pos,
            self._k_pages, self._v_pages, self._presence, self._first_d,
            slots, bt, cached, new_lens,
            last_idx, row_idx, done_mask, np.int32(width),
            self._rng, self._next_key_step(),
            self._temp, self._top_p, self._top_k, self._rep_pen,
            use_pallas=self.use_pallas,
            k_scales=self._k_scales, v_scales=self._v_scales,
            int4_kernel=self._int4_kernel, mesh=self.mesh, **state_args,
        )
        if self.kv_quant:
            self._k_pages, self._v_pages, self._k_scales, self._v_scales = cache
        else:
            # a model's own programs hand back, after the pools, the expert
            # layers' counts, the state pool and the sliding kind's pools, where
            # it has them
            if self._sliding is not None:
                self._sv_pages, self._sk_pages = cache.pop(), cache.pop()
            if self._recurrent:
                self._state_pools = cache.pop()
            if self._expert_counters:
                self._moe_dispatched("prefill", cache.pop(), 1)
                wave_ann.set_metadata(**self._moe_meta("prefill"))
            self._k_pages, self._v_pages = cache

        done: list[_Request] = []
        for i, req in enumerate(reqs):
            req.prefill_pos += valids[i]
            self.prefill_tokens += int(valids[i])
            req.seq_len = req.prefill_pos
            self._seq_lens[req.row] = req.seq_len
            self._register_full_pages(req)
            if req.sliding is not None:
                self._sliding_row(req)
            if done_mask[i]:
                done.append(req)
        if done:
            self._rows_join(done, others_running, finished)

    def _held_for_leader(self, req: _Request, hashes: list[bytes], need: int) -> bool:
        """The scheduler's ONE rule for a follower: a request whose next page
        (the page after what the cache can serve it, ``hashes``) a row still
        prefilling is about to publish is not admitted this step, because it
        would compute that row's prefix a second time beside it, page for
        page.  It is passed over, not waited behind: requests after it in the
        queue are admitted in their order.  The wait is bounded: the leader
        always advances, and its end, cancelled or reaped, drops the hold.

        How the leader is found, and when holding pays, is the page kind's:
        with a host tier the leader's admission CLAIMS the hashes it will
        register (``pending_claim_pages``) and a follower is held only when
        pages are too tight to back a twin (it can share each page as the
        leader registers it); with a sliding kind a long prompt's pages are
        published only behind its window or at its prefill's end, so a
        follower can share nothing the leader has just written and is held
        whatever the pools have free, and the leader is the prefilling row
        whose chain hash at that page is the follower's; with a state pool a
        follower can resume only where a snapshot lies, so it is held while a
        prefilling row of its prefix still OWES one (``snap_at``) deeper than
        the cache serves it now and no deeper than the two share: admitted
        beside that row it would compute the head again on pages of its own
        (four clients a topic did, at 197 pages each: PERF.md, PR 57)."""
        if self._state is not None:
            depth = len(hashes)
            shareable = min(len(req.page_hashes), (len(req.prompt) - 1) // self.page_size)
            return any(
                r.state == "prefilling" and any(
                    depth < d <= min(shareable, len(r.page_hashes))
                    and r.page_hashes[d - 1] == req.page_hashes[d - 1] for d in r.snap_at)
                for r in self._row_req.values())
        if self._sliding is not None:
            depth = len(hashes)
            return depth < len(req.page_hashes) and any(
                r.state == "prefilling" and len(r.page_hashes) > depth
                and r.page_hashes[depth] == req.page_hashes[depth]
                for r in self._row_req.values())
        if self._kv_tier_on and hashes:
            if (self._allocator.pending_claim_pages(hashes)
                    and self._allocator.plain_free_count < need):
                self.dedup_holds += 1
                return True
        return False

    def _sliding_row(self, req: _Request) -> None:
        """The row's next token lies at ``req.seq_len``: its sliding pages
        behind that token's window are released, the pages it is owed ahead
        taken (``SlidingPages.advance``), and its row of the sliding table and
        its limit follow.  Every program in flight or to come has its queries
        at or past that position, so none reads a page released here."""
        sl, row = self._sliding, req.sliding
        full = min(req.prefill_pos // self.page_size, len(req.page_hashes))
        sl.advance(row, req.seq_len, req.page_hashes, full)
        table = self._sliding_tables[req.row]
        table[:row.first] = 0
        table[row.first:row.covered] = row.pages[row.first:]
        self._row_limits[req.row] = min(
            len(req.pages) * self.page_size, row.covered * self.page_size, self.max_seq_len - 1)

    def _publish_sliding(self) -> None:
        """The ledger's counts to the metrics, once a wave and once a burst's commit."""
        freed, in_use, in_use_global = self._m_sliding
        freed.inc(self._sliding.freed - self._sliding_published)
        self._sliding_published = self._sliding.freed
        in_use.set(self._sliding.in_use)
        in_use_global.set(self._allocator.num_pages - self._allocator.free_count)

    def _count_wave_tiles(self, wave_ann, cached: np.ndarray, new_lens: np.ndarray,
                          width: int) -> None:
        """What the family's wave kernel does with this wave, by the rule its
        wrapper builds the grid from: the (query tile, key step) pairs over the
        rows the program runs (padding rows too) and how many of them it runs
        and how many it skips; on the wave's annotation and the engine's
        counters."""
        counts = self._wave_tiles(cached, new_lens, width, self.max_pages_per_seq, self.page_size)
        wave_ann.set_metadata(attn_tiles=sum(counts.values()),
                              **{f"attn_tiles_{kind}": n for kind, n in counts.items()})
        for kind, n in counts.items():
            self.prefill_attn_tiles[kind] += n
            self._m_tiles[kind].inc(n)

    def _burst_sliding_meta(self) -> dict:
        """``sliding_tokens`` of a burst's annotation: over the running rows,
        the keys of a row that a sliding layer's window holds (what its kernel
        walks in the pool), beside ``kv_tokens`` (what a global layer's does)."""
        w = self._sliding.window - 1
        return {"sliding_tokens": sum(min(r.seq_len, w) for r in self._row_req.values()
                                      if r.state == "running")}

    def _wave_sliding(self, reqs: list[_Request], valids: list[int], starts: list[int],
                      rb: int, wave_ann) -> dict:
        """The sliding kind's arguments of one wave: its pools, the rows' tables
        and slots of that kind; and its stats on the wave's annotation: the
        (query, key) pairs inside the window, the keys the rows walk there, and
        the cumulative counts of the ledger."""
        chunk, w = self.prefill_chunk, self._sliding.window
        slots = np.full((rb, chunk), -1, dtype=np.int32)
        bt = np.zeros((rb, self.max_pages_per_seq), dtype=np.int32)
        pairs = keys = 0
        for i, req in enumerate(reqs):
            table = self._sliding_tables[req.row]
            slots[i] = slot_mapping(table, starts[i], valids[i], self.page_size, chunk)
            bt[i] = table
            seen = np.minimum(np.arange(starts[i] + 1, starts[i] + valids[i] + 1), w)
            pairs += int(seen.sum())
            keys += starts[i] + valids[i] - max(0, starts[i] - w + 1)
        wave_ann.set_metadata(
            sliding_pairs=pairs, sliding_keys=keys, page_hit_tokens=self.page_hit_tokens,
            sliding_hit_tokens=self.sliding_hit_tokens,
            sliding_pages_freed=self._sliding.freed)
        self._publish_sliding()
        return {"sliding_k": self._sk_pages, "sliding_v": self._sv_pages,
                "sliding_slots": slots, "sliding_tables": bt}

    def _admit_state(self, req: _Request, shared_pages: int) -> None:
        """A recurrent model's admission: the snapshot the first wave resumes
        from (the one after the last shared page: ``_head_need_hashes`` shared
        no deeper), and THE SNAPSHOT POLICY: this prefill owes a snapshot at
        the branch point the page match revealed (pages the cache held deeper
        than a snapshot lay: the next prompt of that prefix resumes there) and
        at the prompt's last shareable page boundary (a repeat, or a longer
        prompt of the same head), and, through a stretch it computes cold, at
        every ``SNAPSHOT_STRIDE_CHUNKS`` chunks of absolute depth: a state layer
        cannot resume from pages, so without them the SECOND prompt of a long
        head computes all of it again beside the pages the first left (at 24,576
        shared tokens every follower of a topic did, on 197 pages of its own:
        PERF.md, Findings, PR 57), and with them it recomputes a stride at the
        most.  A slot a stride, not one a chunk: a 25k-token head costs three."""
        ps = self.page_size
        self.page_hit_tokens += req.page_match * ps
        self.state_hit_tokens += shared_pages * ps
        req.state_src = -1
        if shared_pages:
            req.state_src = self._state.take(req.page_hashes[shared_pages - 1])
            self.state_restored += 1
        last = min(len(req.page_hashes), (len(req.prompt) - 1) // ps)
        stride = max(1, SNAPSHOT_STRIDE_CHUNKS * self.prefill_chunk // ps)
        strides = range(stride * (shared_pages // stride + 1), last, stride)
        req.snap_at = sorted({d for d in (req.page_match, last, *strides) if d > shared_pages})

    def _wave_state(self, reqs: list[_Request], valids: list[int], rb: int) -> dict:
        """The state arguments of one wave (models/hybrid.py): per wave
        row, the slot its state comes from (the snapshot it resumes from or
        none on a request's first wave, its own row's after), its own row's
        slot for the state after the chunk, and where one is owed inside this
        chunk, a snapshot slot and the column it is caught at (one a row and
        wave: the shallowest owed, the branch point before the prompt's own
        last boundary).  Rows of the bucket past the wave write to the slot
        nobody reads."""
        st, ps = self._state, self.page_size
        src = np.full((rb,), -1, dtype=np.int32)
        dst = np.full((rb,), st.trash, dtype=np.int32)
        snap = np.full((rb,), st.trash, dtype=np.int32)
        snap_col = np.zeros((rb,), dtype=np.int32)
        for i, req in enumerate(reqs):
            start, end = req.prefill_pos, req.prefill_pos + valids[i]
            dst[i] = req.row
            if start == req.cached_tokens:  # the request's first wave
                src[i] = req.state_src
                if req.state_src >= 0:
                    st.unpin(req.state_src)
                    req.state_src = -1
            else:
                src[i] = req.row
            owed = [d for d in req.snap_at if start < d * ps <= end]
            if owed:
                slot = st.reserve(req.page_hashes[owed[0] - 1])
                if slot is not None:
                    snap[i], snap_col[i] = slot, owed[0] * ps - start
                req.snap_at = [d for d in req.snap_at if d * ps > end]
        for event, total in (("written", st.written), ("hit", st.hits), ("evicted", st.evicted)):
            counter = STATE_SNAPSHOTS.labels(event=event)
            counter.inc(total - self._state_published.get(event, 0))
            self._state_published[event] = total
        STATE_SLOTS_IN_USE.set(st.in_use)
        return {"state": self._state_pools, "state_src": src, "state_dst": dst,
                "state_snap": snap, "snap_col": snap_col}

    @property
    def state_snapshots_written(self) -> int:
        return self._state.written if self._state is not None else 0

    @property
    def state_snapshots_evicted(self) -> int:
        return self._state.evicted if self._state is not None else 0

    def _state_meta(self) -> dict:
        """The state cache's cumulative counts, as an annotation's stats."""
        return {"state_restored": self.state_restored, "state_snapshots": self._state.written,
                "state_evicted": self._state.evicted, "page_hit_tokens": self.page_hit_tokens,
                "state_hit_tokens": self.state_hit_tokens}

    def _prefill_batch_packed(
        self, reqs: list[_Request], finished: list[GenerationResult]
    ) -> None:
        """Token-budget packed prefill dispatch (``prefill_token_budget``).

        Greedy packing: walk the prefilling rows in order and give each its
        next chunk — whole, or split to whatever budget remains — until the
        [budget] buffer is full.  Rows that don't fit wait for the next
        step's dispatch (step() re-enters _try_prefill every iteration, so
        nothing starves).  Per-token segment IDs carry each token's block
        table / cached length into the segment-masked attention path
        (ops/packed_prefill.py); first tokens sample at per-segment last
        positions via the generalized ``logits_at``, and the
        no-host-sync handoff into ``_pending_first``/the decode chain is
        identical to the padded path.

        Shape discipline: the token buffer is ALWAYS [1, budget]; only the
        segment-count row bucket varies, so the compiled-prefill set is
        exactly one program per bucket in packed_prefill_buckets() —
        warmup() compiles each, live traffic adds none."""
        others_running = any(r.state == "running" for r in self._row_req.values())
        meta = self._build_packed_wave(reqs)

        ids_d, pos_d = jnp.asarray(meta["ids"]), jnp.asarray(meta["pos"])
        slots_d, bt_d = jnp.asarray(meta["slots"]), jnp.asarray(meta["bt"])
        cached_d = jnp.asarray(meta["cached"])
        new_lens_d = jnp.asarray(meta["new_lens"])
        seg_d, last_idx_d = jnp.asarray(meta["seg"]), jnp.asarray(meta["last_idx"])
        tq = self.packed_chunk
        self.step_dispatches_total += 1
        self._wave_tokens_since_burst += int(meta["new_lens"].sum())
        with annotate("engine.prefill_packed"):
            out = forward_paged_packed(
                self.params, self.cfg,
                ids_d, pos_d,
                self._k_pages, self._v_pages,
                slots_d, bt_d,
                cached_d, new_lens_d,
                seg_d, last_idx_d,
                tq=tq, use_pallas=self.use_pallas,
                k_scales=self._k_scales, v_scales=self._v_scales,
                int4_kernel=self._int4_kernel,
            )
            if self.kv_quant:
                (logits, self._k_pages, self._v_pages,
                 self._k_scales, self._v_scales) = out
            else:
                logits, self._k_pages, self._v_pages = out
        self._finish_packed_wave(meta, logits, finished, others_running)

    def _build_packed_wave(self, reqs: list[_Request]) -> dict:
        """Greedy-pack the prefilling rows' next chunks into the [budget]
        token buffer and build every host array the packed program needs,
        at the segment count's row bucket.  Pure array construction — the
        caller dispatches and then runs ``_finish_packed_wave`` for the
        bookkeeping."""
        budget = self.prefill_token_budget
        tq = self.packed_chunk
        packed: list[tuple[_Request, int]] = []  # (request, tokens granted)
        used = 0
        for req in reqs:
            if used >= budget:
                break
            share = min(len(req.prompt) - req.prefill_pos, tq, budget - used)
            packed.append((req, share))
            used += share
        n = len(packed)
        rb = _bucket(n, self.max_num_seqs, minimum=1)

        ids = np.zeros((1, budget), dtype=np.int32)
        pos = np.zeros((1, budget), dtype=np.int32)
        slots = np.full((budget,), -1, dtype=np.int32)
        seg = np.full((budget,), rb, dtype=np.int32)  # sentinel: padding
        bt = np.zeros((rb, self.max_pages_per_seq), dtype=np.int32)
        cached = np.zeros((rb,), dtype=np.int32)
        new_lens = np.zeros((rb,), dtype=np.int32)
        last_idx = np.zeros((rb,), dtype=np.int32)
        # presence marking reuses the padded path's [row bucket, width]
        # scatter at the fixed width tq — one shape per row bucket
        seg_ids_2d = np.zeros((rb, tq), dtype=np.int32)
        off = 0
        for i, (req, share) in enumerate(packed):
            start = req.prefill_pos
            chunk = req.prompt[start : start + share]
            ids[0, off : off + share] = chunk
            pos[0, off : off + share] = np.arange(start, start + share)
            packed_slot_mapping(
                self._block_tables[req.row], start, share, self.page_size,
                slots, off,
            )
            seg[off : off + share] = i
            seg_ids_2d[i, :share] = chunk
            bt[i] = self._block_tables[req.row]
            cached[i] = start
            new_lens[i] = share
            last_idx[i] = off + share - 1
            off += share
        self.packed_prefill_tokens += used
        self.packed_prefill_padding += budget - used
        row_idx = np.zeros((rb,), dtype=np.int32)
        row_idx[:n] = [req.row for req, _ in packed]
        return {
            "packed": packed, "rb": rb, "ids": ids, "pos": pos,
            "slots": slots, "seg": seg, "bt": bt, "cached": cached,
            "new_lens": new_lens, "last_idx": last_idx,
            "seg_ids_2d": seg_ids_2d, "row_idx": row_idx,
        }

    def _finish_packed_wave(
        self,
        meta: dict,
        logits: jnp.ndarray,  # [rb, 1, V] per-segment last-position logits
        finished: list[GenerationResult],
        others_running: bool,
    ) -> None:
        """Post-dispatch bookkeeping for a packed prefill wave: presence
        marks, per-request advance/page registration, and first-token
        sampling for rows whose prompt completed."""
        packed, rb = meta["packed"], meta["rb"]
        row_d = jnp.asarray(meta["row_idx"])
        self._presence = _mark_presence_chunks(
            self._presence, row_d, jnp.asarray(meta["seg_ids_2d"]),
            jnp.asarray(meta["new_lens"]), self.cfg.vocab_size,
        )

        done_idx: list[int] = []
        for i, (req, share) in enumerate(packed):
            req.prefill_pos += share
            self.prefill_tokens += int(share)
            req.seq_len = req.prefill_pos
            self._seq_lens[req.row] = req.seq_len
            self._register_full_pages(req)
            if req.prefill_pos >= len(req.prompt):
                done_idx.append(i)

        if not done_idx:
            return

        done_mask = np.zeros((rb,), dtype=bool)
        done_mask[done_idx] = True

        self._push_sampling()
        self._rng, key = jax.random.split(self._rng)
        last_logits = logits[:, 0]  # [rb, V] — logits_at already selected
        tokens_d = sample_tokens(
            last_logits, key,
            self._temp_d[row_d], self._top_p_d[row_d], self._top_k_d[row_d],
            self._rep_pen_d[row_d], self._presence[row_d],
        )
        safe = jnp.where(jnp.asarray(done_mask), tokens_d, self.cfg.vocab_size)
        self._presence = _mark_presence_rows(self._presence, row_d, safe)
        self._first_wave(tokens_d, [(packed[i][0], i) for i in done_idx],
                         others_running, finished)

    def _sp_prefill_packed(
        self, reqs: list[_Request], finished: list[GenerationResult]
    ) -> list[_Request]:
        """Segment-packed ring prefill: as many waiting long prompts as fit
        one ring pass, flattened back to back into a [1, width] buffer with
        per-token segment ids (serving/long_prefill.ring_prefill_packed).
        Greedy front-pack in admission order — FIFO, no overtaking: packing
        stops at the first prompt that doesn't fit the widest ladder entry
        or the fixed segment-row count.  Every segment's K/V commits to its
        own pages through the shared flat-slot scatter; first tokens sample
        at the per-segment ``logits_at`` positions in one batched dispatch.

        Shape discipline: width comes from ``_ring_width`` (the
        SP_RING_BUCKETS ladder) and every per-segment array is fixed at
        ``sp_ring_segs`` rows, so the compiled set is exactly one ring
        program per ladder entry — warmup() compiles each, live traffic
        adds none.  Returns the requests actually served this pass."""
        from githubrepostorag_tpu.serving.long_prefill import ring_prefill_packed

        others_running = any(
            r.state == "running" for r in self._row_req.values()
        )
        cap = self.sp_ring_bucket_ladder()[-1]
        rb = self.sp_ring_segs
        packed: list[_Request] = []
        total = 0
        for req in reqs:
            n = len(req.prompt)
            if packed and (len(packed) >= rb or total + n > cap):
                break
            packed.append(req)
            total += n
        width = self._ring_width(total)

        # shared layout (ops/packed_prefill.ring_segment_layout): seg ids with
        # the rb sentinel, per-segment restarting positions, last-token gather
        seg, pos_flat, logits_at, starts = ring_segment_layout(
            [len(req.prompt) for req in packed], width, rb
        )
        ids = np.zeros((1, width), dtype=np.int32)
        pos = pos_flat[None]
        slots = np.full((width,), -1, dtype=np.int32)
        for req, off in zip(packed, starts):
            n = len(req.prompt)
            ids[0, off : off + n] = req.prompt
            packed_slot_mapping(
                self._block_tables[req.row], 0, n, self.page_size, slots, int(off)
            )
        self.sp_prefills += 1
        self.sp_ring_segments += len(packed)
        self.sp_ring_tokens += total
        self.sp_ring_padding += width - total
        self.prefill_tokens += total

        self.step_dispatches_total += 1
        self._wave_tokens_since_burst += total
        with annotate("engine.sp_prefill_packed"):
            (logits, self._k_pages, self._v_pages,
             self._k_scales, self._v_scales) = ring_prefill_packed(
                self.params, self.cfg,
                jnp.asarray(ids), jnp.asarray(pos),
                self._k_pages, self._v_pages,
                jnp.asarray(slots[None]), jnp.asarray(seg[None]),
                jnp.asarray(logits_at), self.mesh,
                k_scales=self._k_scales, v_scales=self._v_scales,
            )

        # whole prompts into the repetition-penalty presence mask — ONE
        # batched dispatch at the fixed [rb, max_seq] shape
        ids_full = np.zeros((rb, self.max_seq_len), dtype=np.int32)
        rows = np.zeros((rb,), dtype=np.int32)
        lens = np.zeros((rb,), dtype=np.int32)
        for i, req in enumerate(packed):
            n = len(req.prompt)
            ids_full[i, :n] = req.prompt
            rows[i] = req.row
            lens[i] = n
            req.prefill_pos = req.seq_len = n
            self._seq_lens[req.row] = n
            # can't RESUME from the cache, but others can resume from us
            self._register_full_pages(req)
        row_d = jnp.asarray(rows)
        self._presence = _mark_presence_chunks(
            self._presence, row_d, jnp.asarray(ids_full),
            jnp.asarray(lens), self.cfg.vocab_size,
        )

        self._push_sampling()
        self._rng, key = jax.random.split(self._rng)
        tokens_d = sample_tokens(
            logits[:, 0], key,
            self._temp_d[row_d], self._top_p_d[row_d], self._top_k_d[row_d],
            self._rep_pen_d[row_d], self._presence[row_d],
        )
        live = np.zeros((rb,), dtype=bool)
        live[: len(packed)] = True
        safe = jnp.where(jnp.asarray(live), tokens_d, self.cfg.vocab_size)
        self._presence = _mark_presence_rows(self._presence, row_d, safe)
        self._first_wave(tokens_d, [(req, i) for i, req in enumerate(packed)],
                         others_running, finished)
        return packed

    def _decode_step(self, finished: list[GenerationResult]) -> None:
        """One decode dispatch: a fused burst of up to ``self.decode_burst``
        iterations (serving/decode_burst.py) — tokens feed the next step on
        device.  Bursts are PIPELINED: this dispatch reuses the in-flight
        burst's device-side last-token/seq-len state, and only then fetches
        the previous burst's tokens — so the device->host sync overlaps the
        new burst's compute.  Stop/length bookkeeping therefore lags the
        device by one burst; tokens a row produced past its stop are
        discarded at commit, and its pages are recycled once no in-flight
        burst references them (``_drain_chain``)."""
        self._phase("engine.burst_prepare")
        b = self.max_num_seqs
        active = np.zeros((b,), dtype=bool)
        remaining = 1
        live_rows = kv_tokens = 0  # running rows and their cached tokens, for the trace
        for row, req in self._row_req.items():
            active[row] = req.state == "running"  # mid-prefill rows sit out
            if req.state == "running":  # mid-prefill budgets don't hold the
                # drain shortcut open: they can't consume burst tokens yet
                remaining = max(remaining, req.sampling.max_tokens - len(req.output))
                live_rows += 1
                kv_tokens += req.seq_len
        # ONE compiled burst shape: always decode_burst steps.  Overshoot
        # past a row's max_tokens is discarded at commit — with continuous
        # batching the "wasted" steps still serve every other running row,
        # and a single shape means a single multi-second XLA compile.
        n_steps = self.decode_burst

        if self._chain is not None and remaining <= self._chain["pending"].shape[1]:
            # the in-flight burst already covers every row's token budget
            # (host's `remaining` is stale by exactly that burst): land it
            # instead of dispatching a speculative extra burst that would be
            # discarded at drain
            self._drain_chain(finished)
            return

        if self._chain is None:
            last = np.zeros((b,), dtype=np.int32)
            for row, req in self._row_req.items():
                last[row] = req.output[-1] if req.output else req.prompt[-1]
            # device arrays like the chained ones: the burst's call signature
            # (one cache entry, one warm-up) is the same either way
            last_d = jnp.asarray(last)
            lens_d = jnp.asarray(self._seq_lens)
        else:
            last_d = self._chain["last"]
            lens_d = self._chain["lens"]

        # freshly-prefilled rows: their first token lives on device
        # (uncommitted, in the first-token array) and their cache length is
        # the host-known prompt length — neither is in the chained state from
        # the in-flight burst.  The burst overlays both itself from two host
        # masks of fixed shape, whatever the number of waves or rows joining
        first_waves = self._pending_first
        self._pending_first = []
        fresh = np.zeros((b,), dtype=bool)
        fresh_lens = np.zeros((b,), dtype=np.int32)
        for _, wave in first_waves:
            for req, row in wave:
                # skip requests released/cancelled since their wave was
                # queued: their row is free (or reassigned), and must not
                # take a token that is not its occupant's
                if req.state == "running" and req.row == row:
                    fresh[row] = True
                    fresh_lens[row] = self._seq_lens[row]

        self.step_dispatches_total += 1
        self._note_dispatch()
        ahead = not self._step_starved
        self.bursts_ahead += ahead
        self.bursts_starved += not ahead
        self._m_burst[ahead].inc()
        self._phase("engine.decode_burst", rows=live_rows, kv_tokens=kv_tokens,
                    steps=n_steps, ahead=int(ahead), seq=self.step_dispatches_total,
                    **(self._moe_meta("burst") if self._expert_counters else {}),
                    **(self._burst_sliding_meta() if self._sliding else {}))
        out = self._decode_burst_fn(
            self.params, self.cfg,
            last_d, lens_d,
            self._k_pages, self._v_pages, self._presence,
            # copies: the host rewrites a row of these at the next release or
            # admission while this burst may still be running, and the CPU
            # backend hands a numpy argument to the program without copying it
            active, self._row_limits.copy(), self._block_tables.copy(), self._rng,
            self._temp, self._top_p, self._top_k, self._rep_pen,
            n_steps=n_steps, use_pallas=self.use_pallas, mesh=self.mesh,
            layer_unroll=self.layer_unroll,
            # sort-free sampling whenever no SAMPLING row filters —
            # greedy rows (temp <= 0) take the exact argmax regardless
            # of their top_p/top_k, so an all-greedy batch (e.g. the
            # ingest extractors) skips the candidate sort even at the
            # default top_p=0.9.  Free rows are reset at release, so
            # this is exactly the running set.
            filter_sampling=bool(
                np.any(
                    (self._temp > 0.0)
                    & ((self._top_p < 1.0) | (self._top_k > 0))
                )
            ),
            k_scales=self._k_scales, v_scales=self._v_scales,
            first_tokens=self._first_d, fresh=fresh, fresh_lens=fresh_lens,
            key_step=self._next_key_step(),
            **({"state": self._state_pools} if self._recurrent else {}),
            **({"sliding_k": self._sk_pages, "sliding_v": self._sv_pages,
                "sliding_tables": self._sliding_tables.copy()} if self._sliding else {}),
        )
        if self.kv_quant:
            (toks, _, self._k_pages, self._v_pages, self._presence,
             out_lens, last, self._k_scales, self._v_scales) = out
        else:
            if self._sliding is not None:
                *out, self._sk_pages, self._sv_pages = out
            if self._recurrent:
                *out, self._state_pools = out
            if self._expert_counters:
                *out, moe = out
                self._moe_dispatched("burst", moe, n_steps)
            (toks, _, self._k_pages, self._v_pages, self._presence,
             out_lens, last) = out
        prev = self._chain
        self._chain = {
            "last": last, "lens": out_lens, "pending": toks,
            "first": first_waves,
            # for the cycle this burst's landing closes (_commit_burst)
            "seq": self.step_dispatches_total, "wave_tokens": self._take_wave_tokens(),
        }
        if prev is not None:
            self._commit_burst(prev, finished)

    def _first_wave(
        self,
        tokens_d: jnp.ndarray,
        wave: list[tuple[_Request, int]],
        others_running: bool,
        finished: list[GenerationResult],
    ) -> None:
        """First tokens sampled by a path that draws them outside its prefill
        program (packed, ring): ``tokens_d[i]`` is ``req``'s for each
        ``(req, i)`` of ``wave``.  They are scattered by row into the
        first-token array, where ``_prefill_batch``'s wave program puts its
        own, and the rows join the running set."""
        rows = np.full((self.max_num_seqs,), self.max_num_seqs, dtype=np.int32)  # dropped
        idxs = np.zeros((self.max_num_seqs,), dtype=np.int32)
        for j, (req, i) in enumerate(wave):
            rows[j], idxs[j] = req.row, i
        self._first_d = _scatter_first_tokens(self._first_d, tokens_d, rows, idxs)
        self._rows_join([req for req, _ in wave], others_running, finished)

    def _rows_join(
        self,
        reqs: list[_Request],
        others_running: bool,
        finished: list[GenerationResult],
    ) -> None:
        """The chunk that completed these prompts is dispatched and their
        first tokens are in (this version of) the first-token array: the rows
        join the running set.  With the engine otherwise idle (nothing to
        overlap the sync with) the tokens commit now (best TTFT); else the
        wave stays on device and commits with the next burst's fetch, so
        admissions never stall running streams."""
        now = time.monotonic()
        for req in reqs:
            req.state = "running"
            if req.prefill_end_t is None:  # a resumed request keeps its first
                req.prefill_end_t = now
        wave = (self._first_d, [(req, req.row) for req in reqs])
        if self._commit_first_now(others_running):
            self._commit_first_tokens([wave], finished)
        else:
            self._pending_first.append(wave)

    def _next_key_step(self) -> np.ndarray:
        """The dispatch counter a step program folds into the base key."""
        self._key_step = (self._key_step + 1) % (1 << 31)
        return np.asarray(self._key_step, dtype=np.uint32)

    def _note_dispatch(self) -> None:
        """Before a step program goes out: had the device already drained?
        ``presence`` is an output of the newest program of any kind (wave,
        burst, the admission helpers); ready, the device has nothing left
        queued and waits for this dispatch.  Non-blocking.  A step whose
        wave or burst found the device drained counts its burst as starved."""
        if self._presence.is_ready():
            self._step_starved = True

    def _take_wave_tokens(self) -> int:
        """A burst takes what the waves since the burst before it added."""
        taken, self._wave_tokens_since_burst = self._wave_tokens_since_burst, 0
        return taken

    def _commit_first_tokens(
        self,
        waves: list[tuple[jnp.ndarray, list[tuple[_Request, int]]]],
        finished: list[GenerationResult],
    ) -> None:
        """Fetch + commit deferred prefill first-token waves."""
        for tokens_d, wave in waves:
            live = [(req, i) for req, i in wave
                    if req.state == "running" and not req.output]
            if not live:
                continue  # cancelled/released, or already committed
            self._phase("engine.commit_fetch")
            tokens = np.asarray(tokens_d)
            self._phase("engine.commit_host", tokens=len(live))
            for req, i in live:
                self._commit_token(req, int(tokens[i]), finished)

    def _commit_burst(self, entry: dict, finished: list[GenerationResult]) -> None:
        """Fetch a burst's packed tokens — ONE [B, n_steps] transfer, the
        single device->host round trip per burst — and apply stop/length
        bookkeeping.  First-token waves attached to this burst (rows that
        joined it fresh from prefill) commit before its tokens.  Position
        (row, i) holds -1 where the row was inactive; rows already released
        ignore their tokens."""
        self._commit_first_tokens(entry.get("first", []), finished)
        self._phase("engine.commit_fetch")
        toks = np.asarray(entry["pending"])  # [B, n_steps]
        if self._expert_counters:
            self._moe_read_back(entry["seq"])
        got = toks >= 0
        # What this landing says of the cycle it closes: the burst's dispatch
        # number, the prefill waves dispatched since the burst before it (the
        # numbers between the two) and their new tokens.  This annotation's
        # start is the cycle's end on the trace's clock (a first-token wave's
        # commit_host carries no ``seq``)
        facts = {"seq": entry["seq"], "waves": entry["seq"] - self._landed_seq - 1,
                 "wave_tokens": entry["wave_tokens"]}
        self._phase("engine.commit_host", tokens=int(got.sum()),
                    chained=int(self._landed_t is not None), **facts)
        self._cycle_landed(facts, got.any(axis=1))
        for i in range(toks.shape[1]):
            for row in sorted(self._row_req):
                req = self._row_req.get(row)
                if req is None or req.state != "running" or toks[row, i] < 0:
                    continue
                req.seq_len += 1
                self._seq_lens[row] = req.seq_len
                self._commit_token(req, int(toks[row, i]), finished)
        if self._sliding is not None:
            for req in self._row_req.values():
                if req.state == "running":
                    self._sliding_row(req)
            self._publish_sliding()

    def _cycle_landed(self, facts: dict, got: np.ndarray) -> None:
        """A burst's tokens are on the host (``_phase`` has just stamped the
        end of the fetch): write the cycle it closes, and each row's share.
        A landing with no burst before it in flight (the first after the
        engine was empty or after ``_drain_chain``) starts the clock and
        records no cycle.  ``got``: the rows this burst brought a token.  A row
        counts the landing; it counts the cycle's waves too unless this is
        its first landing: the burst a row joins in follows the wave that
        completed its prompt, which ran before its first token."""
        landed, before = self._phase_t0, self._landed_t
        self._landed_t, self._landed_seq = landed, facts["seq"]
        waves, wave_tokens = facts["waves"], facts["wave_tokens"]
        if before is not None:
            self.cycle_ring.append({**facts, "landed_t": landed, "cycle_s": landed - before})
            self._m_cycle[min(waves, 2)].observe(landed - before)
        for row, req in self._row_req.items():
            if req.state == "running" and got[row]:
                if waves and req.decode_cycles:
                    req.decode_wave_cycles += 1
                    req.decode_wave_tokens += wave_tokens
                req.decode_cycles += 1
                req.last_token_t = landed

    def _moe_dispatched(self, program: str, counts: jnp.ndarray, steps: int) -> None:
        """A step program that ran expert layers was dispatched: keep its
        device-side [experts hit, expert tokens] (and, where the program
        returns it, the fullest held expert's pairs) until they can be read
        without waiting, and book the expert slots it offered."""
        cfg = self.cfg
        slots = cfg.n_held * cfg.expert_layers * steps
        self._moe_pending.append((self.step_dispatches_total, program, counts, slots))

    def _moe_read_back(self, upto: int) -> None:
        """Add the counts of every dispatch up to ``upto`` (a burst whose
        tokens were just fetched, so the device is past all of them)."""
        from githubrepostorag_tpu.metrics import MOE_EXPERT_TOKENS, MOE_EXPERTS_HIT

        while self._moe_pending and self._moe_pending[0][0] <= upto:
            _, program, counts, slots = self._moe_pending.pop(0)
            hit, tokens, *fullest = (int(x) for x in np.asarray(counts))
            acc = self.moe_stats[program]
            acc[0], acc[1], acc[2] = acc[0] + hit, acc[1] + tokens, acc[2] + slots
            if fullest:  # a program that also counts its fullest held expert's pairs
                self.moe_max_pairs[program] = self.moe_max_pairs.get(program, 0) + fullest[0]
            MOE_EXPERTS_HIT.labels(program=program).inc(hit)
            MOE_EXPERT_TOKENS.labels(program=program).inc(tokens)

    def _moe_meta(self, program: str) -> dict:
        """The cumulative counts, as an annotation's stats."""
        hit, tokens, slots = self.moe_stats[program]
        meta = {"experts_hit": hit, "expert_tokens": tokens, "expert_slots": slots}
        if program in self.moe_max_pairs:
            meta["experts_max_pairs"] = self.moe_max_pairs[program]
        return meta

    def _drain_chain(self, finished: list[GenerationResult]) -> None:
        """Land the in-flight burst (if any), commit its tokens and any
        deferred first-token waves, and recycle every deferred row/page now
        that nothing on device references them."""
        if self._chain is not None:
            entry = self._chain
            self._chain = None  # releases during this commit recycle directly
            self._commit_burst(entry, finished)
        self._landed_t = None  # the next burst has none before it in flight
        if self._pending_first:
            waves = self._pending_first
            self._pending_first = []
            self._commit_first_tokens(waves, finished)
        for row, pages, rid in self._deferred:
            self._allocator.release(pages)
            self._obs_release(rid)
            self._free_rows.append(row)
        self._deferred.clear()
        if self._sliding is not None and self._deferred_sliding:
            self._sliding.release_pages(self._deferred_sliding)
            self._deferred_sliding = []

    def _push_sampling(self) -> None:
        """Mirror host sampling params to device arrays when dirty."""
        if self._sampling_dirty:
            self._temp_d = jnp.asarray(self._temp)
            self._top_p_d = jnp.asarray(self._top_p)
            self._top_k_d = jnp.asarray(self._top_k)
            self._rep_pen_d = jnp.asarray(self._rep_pen)
            self._sampling_dirty = False

    # ---------------------------------------------------------- lifecycle --

    def _commit_token(self, req: _Request, token: int, finished: list[GenerationResult]) -> None:
        if req.first_token_t is None:
            req.first_token_t = time.monotonic()
        req.output.append(token)
        self.committed_tokens += 1
        if req.on_token is not None:
            try:
                req.on_token(req.request_id, token)
            except Exception:  # noqa: BLE001 - callbacks must not kill the engine
                logger.exception("on_token callback failed for %s", req.request_id)
        stop_ids = req.sampling.stop_token_ids
        if token in stop_ids:
            self._release(req)
            finished.append(self._result(req, "stop"))
        elif len(req.output) >= req.sampling.max_tokens or req.seq_len + 1 >= self.max_seq_len:
            self._release(req)
            finished.append(self._result(req, "length"))

    def _release(self, req: _Request) -> None:
        if req.state_src >= 0:  # admitted to resume from a snapshot, never dispatched
            self._state.unpin(req.state_src)
            req.state_src = -1
        if req.claimed_hashes:
            # an unfinished prefill abandons its registration promises
            # (reap/cancel mid-prefill) so held followers aren't stranded
            self._allocator.unclaim(req.claimed_hashes)
            req.claimed_hashes = []
        if req.sliding is not None:
            held, req.sliding = self._sliding.release(req.sliding), None
            if self._chain is not None:
                self._deferred_sliding += held
            else:
                self._sliding.release_pages(held)
            self._sliding_tables[req.row] = 0
        if req.row >= 0:
            if self._chain is not None:
                # an in-flight burst still reads this row's pages; recycle
                # only after the chain drains
                self._deferred.append((req.row, req.pages, req.request_id))
            else:
                self._allocator.release(req.pages)
                self._obs_release(req.request_id)
                self._free_rows.append(req.row)
            self._row_req.pop(req.row, None)
            self._seq_lens[req.row] = 0
            self._block_tables[req.row] = 0
            self._row_limits[req.row] = 0
            # reset the HOST sampling mirrors to the no-filter defaults so
            # a stale top_p/top_k on a FREE row can't pin later bursts onto
            # the filtered (sort-carrying) sampling variant.  Deliberately
            # NOT marking _sampling_dirty: the device-side params of a
            # freed row are never read (its burst tokens are discarded via
            # the act mask) and _set_row_sampling dirties before any
            # reassignment — pushing four arrays per completed request
            # would put needless transfers on the hot burst path
            self._temp[req.row] = 1.0
            self._top_p[req.row] = 1.0
            self._top_k[req.row] = 0
            self._rep_pen[req.row] = 1.0
            req.row = -1
        req.state = "done"

    def _set_row_sampling(self, row: int, sp: SamplingParams) -> None:
        self._temp[row] = sp.temperature
        self._top_p[row] = sp.top_p
        self._top_k[row] = sp.top_k
        self._rep_pen[row] = sp.repetition_penalty
        self._sampling_dirty = True
        # fresh presence row for the new occupant
        self._presence = _clear_presence_row(self._presence, row)

    def _result(self, req: _Request, reason: str) -> GenerationResult:
        # the request is finished; drop the engine's reference so a
        # long-running server doesn't accumulate every prompt ever served
        self._requests.pop(req.request_id, None)
        ttft = (req.first_token_t - req.submit_t) if req.first_token_t else None
        done_t = time.monotonic()
        # a parked request folded prompt+output into its prompt; report the
        # caller's original prompt and the full contiguous output stream
        output = req.prior_output + req.output if req.prior_output else req.output
        prompt = req.prompt
        if req.orig_prompt_len and req.orig_prompt_len < len(req.prompt):
            prompt = req.prompt[: req.orig_prompt_len]
        return GenerationResult(
            request_id=req.request_id,
            prompt_tokens=prompt,
            output_tokens=output,
            finish_reason=reason,
            ttft_s=ttft,
            decode_time_s=(done_t - req.first_token_t) if req.first_token_t else 0.0,
            timings={
                "recv_t": req.recv_t,
                "enqueue_t": req.enqueue_t,
                "submit_t": req.submit_t,
                "prefill_start_t": req.prefill_start_t,
                "prefill_end_t": req.prefill_end_t,
                "first_token_t": req.first_token_t,
                "done_t": done_t,
                "last_token_t": req.last_token_t,
                "decode_cycles": req.decode_cycles,
                "decode_wave_cycles": req.decode_wave_cycles,
                "decode_wave_tokens": req.decode_wave_tokens,
            },
            cached_tokens=req.cached_tokens,
            faulted_pages=req.faulted_pages,
            preempted=req.preempted,
        )

    # --------------------------------------------------------- convenience --

    @startup_records("startup.warmup")
    def warmup(self) -> None:
        """Precompile every steady-state device program — the prefill wave at
        each row bucket (first-token sampling is part of it), the decode
        burst in both sampling variants — so live traffic
        never hits a multi-second XLA compile mid-request (vLLM warms up its
        CUDA graphs the same way; a cold shape of a 28-layer step program
        costs tens of seconds).  Runs tiny throwaway requests through
        the public step loop and leaves the engine state clean."""
        buckets = []
        b = 1
        while True:
            buckets.append(min(b, self.max_num_seqs))
            if b >= self.max_num_seqs:
                break
            b *= 2
        sp = SamplingParams(max_tokens=2, temperature=0.0, stop_token_ids=())
        wave = 0  # distinct prompt content per wave: identical prompts
        # across waves would hit the prefix cache and resume PAST the
        # prefill program this wave is meant to compile
        if self.prefill_token_budget is not None:
            # packed prefill: the token buffer is always [1, budget], so
            # the only varying axis is the segment-count row bucket — one
            # wave per packed_prefill_buckets() entry compiles the whole
            # packed shape set (nb can exceed the packable segment cap —
            # the first dispatch then packs cap segments at exactly the
            # bucket this entry names, and the leftovers re-dispatch at
            # buckets earlier entries already compiled)
            prefill_buckets = self.packed_prefill_buckets()
        else:
            # padded prefill: a row bucket's one program holds every rung
            # of the width ladder, whichever this wave runs
            prefill_buckets = buckets
        for nb in prefill_buckets:
            # ONE prompt of a whole chunk, the other nb-1 rows short, so the
            # page pool never forces the wave into fewer rows than live
            # traffic could hit
            short_pages = pages_needed(3 + sp.max_tokens, self.page_size)
            long_budget = (
                self._allocator.num_pages - (nb - 1) * short_pages
            ) * self.page_size - sp.max_tokens
            plen = min(self.prefill_chunk, self.max_seq_len - 3, long_budget)
            if self.sp_prefill_threshold is not None and self._sp > 1:
                # stay below the ring-prefill routing threshold — this
                # loop warms the CHUNKED shapes; ring widths are warmed
                # by the dedicated loop below
                plen = min(plen, self.sp_prefill_threshold - 1)
            if plen <= 0:
                # Skipping is provably safe, not a warm-coverage gap
                # (an all-short fallback wave is unnecessary):
                # plen<=0 via the page budget needs
                # num_pages <= (nb-1)*short_pages, i.e. no page left
                # for an nb-th row — live traffic can never run nb
                # simultaneous rows either, so (nb, *) is unreachable.
                # The only other source is an sp_prefill_threshold <= 1
                # clamp, where EVERY live prompt routes to ring prefill
                # (warmed by the dedicated loop below), never to these
                # chunked shapes.
                continue
            wave += 1
            tok = 2 + wave % max(2, self.cfg.vocab_size - 2)
            self.generate([[tok] * plen] + [[tok] * 3] * (nb - 1), sp)
        # both burst sampling variants must be warm: the bucket loop above
        # compiled the no-filter (Gumbel-argmax) burst; one filtered request
        # compiles the sample_tokens_capped burst (in-vocab tokens — tiny
        # test configs have single-digit vocabs)
        wave += 1
        tok = 2 + wave % max(2, self.cfg.vocab_size - 2)
        self.generate(
            [[tok] * 3],
            SamplingParams(max_tokens=2, temperature=0.7, top_p=0.9,
                           stop_token_ids=()),
        )
        if self.sp_prefill_threshold is not None and self._sp > 1:
            # precompile the ring-prefill program at every ladder width a
            # live pass can dispatch at (without this, the first
            # above-threshold prompt — and each new width — pays a
            # multi-second-to-minutes XLA compile mid-request, violating
            # the warmed-shapes discipline stated in _prefill_batch).
            # sp_ring_bucket_ladder() is the same list _ring_width selects
            # from, so warmup and dispatch can never desynchronize.  One
            # prompt per width suffices: the program's per-segment arrays
            # are fixed at sp_ring_segs rows regardless of how many
            # segments a live pass actually carries.
            for width in self.sp_ring_bucket_ladder():
                n = min(width, self.max_seq_len - 2)  # room for 2 tokens
                if n >= self.sp_prefill_threshold:
                    self.generate([[1] * n], sp)
        if self.prefix_caching:
            # the cached-prefix presence-marking program ([row bucket,
            # max_seq] — one dispatch per admission wave) only runs on
            # cache hits; compile every row bucket now with zero-length
            # marks (each is a trivial scatter — compiles are cheap)
            for nb in buckets:
                self._presence = _mark_presence_chunks(
                    self._presence,
                    jnp.zeros((nb,), dtype=jnp.int32),
                    jnp.zeros((nb, self.max_seq_len), dtype=jnp.int32),
                    jnp.zeros((nb,), dtype=jnp.int32),
                    self.cfg.vocab_size,
                )
        if self._kv_tier_on:
            # compile the migration ladder — one gather + one scatter per
            # power-of-two burst bucket (per pool set).  All-(-1) indices
            # make the scatters drop every row and the gathers read page 0,
            # so each call is a pure shape compile over the live pools
            # (donated -> rebind); live migration can then never mint a
            # new program mid-traffic (CompileWatchdog-enforced in tests)
            # pool-stored head width (int4 pages pack head_dim // 2 bytes)
            ps, hd = self.page_size, self._k_pages.shape[-1]
            L, n_kv = self.cfg.num_layers, self.cfg.num_kv_heads
            quant = self._k_scales is not None
            for nb in migrate_buckets(self.kv_migrate_burst):
                idx = jnp.asarray(np.full((nb,), -1, dtype=np.int32))
                gather_pages(self._k_pages, self._v_pages, idx,
                             self._k_scales, self._v_scales)
                (self._k_pages, self._v_pages, self._k_scales,
                 self._v_scales) = scatter_pages(
                    self._k_pages, self._v_pages, idx,
                    jnp.zeros((L, n_kv, nb, ps, hd), self._k_pages.dtype),
                    self._k_scales, self._v_scales,
                    v_vals=jnp.zeros((L, n_kv, nb, ps, hd), self._v_pages.dtype),
                    ks_vals=(jnp.zeros((L, n_kv, nb), jnp.float32)
                             if quant else None),
                    vs_vals=(jnp.zeros((L, n_kv, nb), jnp.float32)
                             if quant else None),
                )
        logger.info("engine warmup complete (%d prefill row buckets)", len(buckets))

    def generate(
        self,
        prompts: list[list[int]],
        sampling: SamplingParams | list[SamplingParams] | None = None,
    ) -> list[GenerationResult]:
        """Synchronous batch generation (tests, ingest extractors)."""
        if isinstance(sampling, list):
            sps = sampling
        else:
            sps = [sampling or SamplingParams()] * len(prompts)
        order = [self.add_request(p, sp) for p, sp in zip(prompts, sps)]
        done: dict[str, GenerationResult] = {}
        while self.has_work():
            for res in self.step():
                done[res.request_id] = res
        return [done[rid] for rid in order]


# ---- small jitted presence-mask helpers ----------------------------------


@partial(jax.jit, static_argnames=("vocab",))
def _mark_presence_chunks(
    presence: jnp.ndarray,  # [rows, V] bool
    row_idx: jnp.ndarray,  # [R] int32
    ids: jnp.ndarray,  # [R, W] int32 prompt-chunk tokens (right-padded)
    lens: jnp.ndarray,  # [R] valid tokens per row
    vocab: int,
) -> jnp.ndarray:
    """Batched prompt-token presence marking (cached-prefix admission; a
    prefill wave marks its own chunk inside its program)."""
    return mark_presence_chunks(presence, row_idx, ids, lens)


@jax.jit
def _scatter_first_tokens(first: jnp.ndarray, tokens: jnp.ndarray, rows: jnp.ndarray,
                          idxs: jnp.ndarray) -> jnp.ndarray:
    """``first[rows[j]] = tokens[idxs[j]]``; rows past the array are dropped."""
    return first.at[rows].set(tokens[idxs], mode="drop")


@jax.jit
def _mark_presence_rows(presence: jnp.ndarray, rows: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    return presence.at[rows, tokens].set(True, mode="drop")


@jax.jit
def _clear_presence_row(presence: jnp.ndarray, row: int) -> jnp.ndarray:
    return presence.at[row].set(False)
