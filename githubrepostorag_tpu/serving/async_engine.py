"""Async facade over the synchronous Engine: a dedicated driver thread turns
engine.step() into per-request asyncio streams.

The TPU never waits on the event loop and the event loop never blocks on the
TPU: the driver thread spins steps while work exists (continuous batching),
and token/final events hop into asyncio queues via call_soon_threadsafe —
the same one-way thread->loop bridge the reference uses for progress events
(worker.py:55-70, asyncio.run_coroutine_threadsafe), generalized to token
granularity.
"""

from __future__ import annotations

import asyncio
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, AsyncIterator

from githubrepostorag_tpu.obs.engine_profile import EngineStepProfiler
from githubrepostorag_tpu.obs.startup import startup_record
from githubrepostorag_tpu.serving.engine import Engine, GenerationResult
from githubrepostorag_tpu.serving.routing import ReplicaDigest
from githubrepostorag_tpu.serving.sampling_params import SamplingParams
from githubrepostorag_tpu.utils.logging import get_logger
from githubrepostorag_tpu.utils.profiling import annotate

logger = get_logger(__name__)

REQUEST_RING = 4096  # finished requests kept with their stamps
SLOW_STEP_S = 2.0  # a step, or a gap between two steps with work, worth a warning

# replica lifecycle states (serving/multi_engine.py drives transitions;
# gauge encoding matches metrics.FLEET_LIFECYCLE)
LIFECYCLE_STATES = ("active", "draining", "drained", "spare")


@dataclass
class StreamEvent:
    type: str  # "token" | "parked" | "final"
    token_id: int | None = None
    result: GenerationResult | None = None


class AsyncEngine:
    def __init__(self, engine: Engine, replica: str = "r0") -> None:
        self.engine = engine
        self.replica = replica
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._thread: threading.Thread | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._queues: dict[str, asyncio.Queue[StreamEvent]] = {}
        # priority class per in-flight request (SLO monitor dimension)
        self._priority: dict[str, str] = {}
        # last engine-counter values already exported to prometheus —
        # instance state, so a stop()/start() relaunch doesn't re-export
        # the full cumulative totals
        self._exported = {"hit": 0, "packed_tok": 0, "packed_pad": 0, "reaps": 0,
                          "kv_fault": 0, "kv_wb": 0, "kv_dedup": 0, "kv_hold": 0,
                          "kv_mig_s": 0.0, "xfer_s": 0.0, "preempts": 0, "resumes": 0}
        # step profiler: scheduler-stall gauge + what the compile ledger saw
        # since the last step, on the driver thread (obs/engine_profile)
        self.profiler = EngineStepProfiler(replica=replica)
        # startup.serve: from here to mark_warm() in start() (obs/startup.py)
        self._serve_phase = startup_record().begin("startup.serve")
        # SLO plane: token ledger + burn-rate monitor, registered under this
        # replica id so MultiAsyncEngine fleets federate per-replica
        from githubrepostorag_tpu.config import get_settings
        from githubrepostorag_tpu.obs.ledger import TokenLedger, flops_per_token
        from githubrepostorag_tpu.obs.slo import SLOMonitor, get_slo_plane
        from githubrepostorag_tpu.runtime import chip_peak_flops

        s = get_settings()
        fpt = s.model_flops_per_token or (
            flops_per_token(engine.cfg) if getattr(engine, "cfg", None) else 0.0
        )
        self.ledger = TokenLedger(
            replica, flops_per_tok=fpt,
            peak_flops=chip_peak_flops(),
            window_s=s.slo_ledger_window_s,
        )
        self.slo = SLOMonitor(replica)
        # chain-hash digest for the fleet router: the driver publishes the
        # allocator's resident/host populations, the router snapshots them
        # (serving/routing.py owns the cross-domain handoff)
        self.digest = ReplicaDigest(replica)
        # deep observability: page-pool observatory + always-on sampled
        # step profiler (obs/hbm.py, obs/continuous.py), federated per
        # replica exactly like the SLO plane
        from githubrepostorag_tpu.obs.continuous import (
            ContinuousProfiler, register_profiler)
        from githubrepostorag_tpu.obs.hbm import PageObservatory, get_hbm_plane

        self.page_obs = PageObservatory(replica)
        if hasattr(engine, "attach_page_observer"):
            engine.attach_page_observer(self.page_obs)
        self.page_obs.attach_pool_view(self._pool_view)
        get_hbm_plane().register(replica, self.page_obs)
        self.continuous = ContinuousProfiler(replica)
        register_profiler(replica, self.continuous)
        # the request record: every finished request's id, token counts and
        # ``timings`` (the dict itself: stream() adds first_emit_t to it on
        # the event loop).  Appended by the driver outside its lock; readers
        # reach it through the profiler registry (continuous.profilers()).
        self.request_ring: deque[dict] = deque(maxlen=REQUEST_RING)
        self.continuous.request_ring = self.request_ring
        # the engine's own record of its decode cycles hangs beside it
        self.continuous.cycle_ring = getattr(engine, "cycle_ring", None)
        self.continuous.cycle_programs = getattr(engine, "cycle_programs", None)
        # lifecycle is event-loop state: MultiAsyncEngine transitions it and
        # its _pick reads it, both on the loop; other threads only render it
        self.lifecycle = "active"
        # liveness probe state: the driver stamps ``heartbeat`` (a
        # time.monotonic reading) at the top of every iteration; the fleet
        # controller reads its age cross-thread (GIL-atomic float) and a
        # fault-killed driver leaves its terminal error in ``driver_error``
        self.heartbeat: float | None = None
        self.driver_error: str | None = None
        # last successfully collected stats + collection time, served with
        # a ``stale_since`` age when the driver lock can't be acquired
        # within the stats deadline (a wedged driver must not hang /debug)
        self._last_stats: dict[str, Any] | None = None
        self._last_stats_t: float | None = None
        # serving role under disaggregation ("fused" | "prefill" | "decode");
        # MultiAsyncEngine assigns it at fleet construction and it never
        # changes while the replica is active, so reads are safe anywhere
        self.role = "fused"
        get_slo_plane().register(
            replica, ledger=self.ledger, monitor=self.slo, stats=self.stats,
            digest=self.digest,
        )

    def _pool_view(self) -> dict:
        """Advisory allocator snapshot for the page observatory's payload
        renders.  Deliberately lock-free: every read is a GIL-atomic
        attribute load or a one-bytecode list copy, and /debug/hbm must
        render even when the driver is wedged holding its lock."""
        alloc = self.engine._allocator
        free = list(getattr(alloc, "_free", ()))
        lru = getattr(alloc, "_lru", None)
        out = {
            "num_pages": alloc.num_pages,
            "free": alloc.free_count,
            "plain_free": len(free),
            "cached_lru": len(lru) if lru is not None else 0,
            "host_pages": getattr(alloc, "host_pages", 0),
            "free_pages": free,
            "hit_tokens": getattr(alloc, "hit_tokens", 0),
        }
        for k in ("fault_ins", "writebacks", "dedup_hits", "host_evictions",
                  "tier_drops", "page_imports", "import_dedup_skips",
                  "preempt_parked_pages"):
            out[k] = getattr(alloc, k, 0)
        return out

    # ------------------------------------------------------------ lifecycle

    async def start(self) -> None:
        if self._thread is not None:
            return
        # tpulint: disable=WPA002 -- written before Thread.start() below; the thread launch is the happens-before edge that publishes it to the driver
        self._stop = False  # allow stop() -> start() relaunch
        # rebaseline the compile watchdog: programs compiled before serve
        # start (warmup, imports) are expected — only compiles during live
        # stepping should count
        startup_record().finish(self._serve_phase)
        self.profiler.mark_warm()
        # tpulint: disable=WPA002 -- written before Thread.start() below; the thread launch is the happens-before edge that publishes it to the driver
        self._loop = asyncio.get_running_loop()
        self._thread = threading.Thread(target=self._drive, name="engine-driver", daemon=True)
        self._thread.start()

    async def stop(self) -> None:
        # tpulint: disable=WPA002 -- GIL-atomic bool store signaling the driver loop; it re-checks every iteration and _wake.set() bounds the latency, while a lock here would serialize stop() against a multi-second step
        self._stop = True
        self._wake.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            # the driver may be mid-step (a cold compile holds it for
            # seconds); joining inline would freeze every coroutine in the
            # process for up to the timeout — wait off-loop instead
            await asyncio.get_running_loop().run_in_executor(
                None, thread.join, 10
            )

    def _drive(self) -> None:
        from githubrepostorag_tpu.metrics import (
            DECODE_TOKENS,
            ENGINE_DEADLINE_REAPS,
            ENGINE_RUNNING,
            ENGINE_WAITING,
            KV_DEDUP_HITS,
            KV_DEDUP_HOLDS,
            KV_FAULT_INS,
            KV_MIGRATION_SECONDS,
            KV_TIER_DEVICE_PAGES,
            KV_TIER_HOST_PAGES,
            KV_WRITEBACKS,
            PACKED_PREFILL_PADDING,
            PACKED_PREFILL_TOKENS,
            PREFIX_CACHE_HITS,
            TTFT,
        )

        from githubrepostorag_tpu.metrics import TPOT
        from githubrepostorag_tpu.obs.ledger import engine_snapshot

        # engine stats are cumulative ints; export deltas to the counters.
        # every engine-owned series is bound to this driver's replica child
        # once, outside the hot loop (labels() does a dict lookup + lock)
        last = self._exported
        R = self.replica
        m_ttft = TTFT.labels(replica=R)
        m_tokens = DECODE_TOKENS.labels(replica=R)
        m_tpot = TPOT.labels(replica=R)
        m_running = ENGINE_RUNNING.labels(replica=R)
        m_waiting = ENGINE_WAITING.labels(replica=R)
        m_prefix = PREFIX_CACHE_HITS.labels(replica=R)
        m_ptok = PACKED_PREFILL_TOKENS.labels(replica=R)
        m_ppad = PACKED_PREFILL_PADDING.labels(replica=R)
        m_reaps = ENGINE_DEADLINE_REAPS.labels(replica=R)
        m_kv_fault = KV_FAULT_INS.labels(replica=R)
        m_kv_wb = KV_WRITEBACKS.labels(replica=R)
        m_kv_dedup = KV_DEDUP_HITS.labels(replica=R)
        m_kv_hold = KV_DEDUP_HOLDS.labels(replica=R)
        m_kv_mig = KV_MIGRATION_SECONDS.labels(replica=R)
        m_kv_dev = KV_TIER_DEVICE_PAGES.labels(replica=R)
        m_kv_host = KV_TIER_HOST_PAGES.labels(replica=R)

        def export_counters() -> None:
            hit = getattr(self.engine._allocator, "hit_tokens", 0)
            ptok = getattr(self.engine, "packed_prefill_tokens", 0)
            ppad = getattr(self.engine, "packed_prefill_padding", 0)
            m_prefix.inc(hit - last["hit"])
            m_ptok.inc(ptok - last["packed_tok"])
            m_ppad.inc(ppad - last["packed_pad"])
            reaps = self.engine.deadline_reaps
            m_reaps.inc(reaps - last["reaps"])
            alloc = self.engine._allocator
            fi = getattr(alloc, "fault_ins", 0)
            wb = getattr(alloc, "writebacks", 0)
            dd = getattr(alloc, "dedup_hits", 0)
            hold = getattr(self.engine, "dedup_holds", 0)
            mig_s = (
                getattr(self.engine, "migration_seconds_total", 0.0)
                + getattr(self.engine, "fault_in_seconds_total", 0.0)
            )
            m_kv_fault.inc(fi - last["kv_fault"])
            m_kv_wb.inc(wb - last["kv_wb"])
            m_kv_dedup.inc(dd - last["kv_dedup"])
            m_kv_hold.inc(hold - last["kv_hold"])
            if mig_s > last["kv_mig_s"]:
                # one observation per step that migrated: this step's
                # migration host time (the cumulative totals' delta)
                m_kv_mig.observe(mig_s - last["kv_mig_s"])
            m_kv_dev.set(alloc.free_count)
            m_kv_host.set(getattr(alloc, "host_pages", 0))
            xfer_s = getattr(self.engine, "transfer_seconds_total", 0.0)
            if xfer_s > last["xfer_s"]:
                from githubrepostorag_tpu.metrics import DISAGG_TRANSFER_SECONDS

                DISAGG_TRANSFER_SECONDS.labels(replica=R).inc(
                    xfer_s - last["xfer_s"])
            pre = getattr(self.engine, "preemptions", 0)
            res = getattr(self.engine, "preempt_resumes", 0)
            if pre > last["preempts"]:
                from githubrepostorag_tpu.metrics import ENGINE_PREEMPTIONS

                ENGINE_PREEMPTIONS.labels(replica=R).inc(pre - last["preempts"])
            if res > last["resumes"]:
                from githubrepostorag_tpu.metrics import ENGINE_PREEMPT_RESUMES

                ENGINE_PREEMPT_RESUMES.labels(replica=R).inc(
                    res - last["resumes"])
            last.update(hit=hit, packed_tok=ptok, packed_pad=ppad, reaps=reaps,
                        kv_fault=fi, kv_wb=wb, kv_dedup=dd, kv_hold=hold,
                        kv_mig_s=mig_s, xfer_s=xfer_s, preempts=pre,
                        resumes=res)

        from githubrepostorag_tpu.config import get_settings

        digest_interval = get_settings().route_digest_interval_s
        digest_next = 0.0
        pressure_next = 0.0  # SLO class-state push, rate-limited like digest

        from githubrepostorag_tpu.resilience.faults import (
            InjectedFault, fire_sync)

        # per-replica chaos seam: ``fleet.step.rN:delay=S`` wedges this
        # driver (it sleeps holding the lock), ``error`` kills it (the
        # thread records the fault and exits — a dead replica); paired
        # with @window=N:M a test scripts healthy-then-dies deterministically
        fault_site = f"fleet.step.{R}"

        while not self._stop:
            step_start = time.monotonic()
            # tpulint: disable=WPA002 -- GIL-atomic float stamp; the controller's liveness probe only compares its age against a multi-second timeout, so torn ordering is harmless
            self.heartbeat = step_start
            with self._lock:
                try:
                    fire_sync(fault_site)
                except InjectedFault as exc:
                    # a killed driver is the chaos model for a dead replica:
                    # leave the evidence and exit; the controller's liveness
                    # probe sees thread-dead + stale heartbeat and fails over
                    self.driver_error = str(exc)
                    logger.error("replica %s driver killed: %s", R, exc)
                    return
                if (time.monotonic() >= pressure_next
                        and hasattr(self.engine, "set_class_pressure")):
                    # burn-rate states feed the engine's preempt triggers
                    # and headroom doubling (warn) — the monitor's lock is
                    # fine to take here, the plane's federation is not
                    self.engine.set_class_pressure(self.slo.class_states())
                    pressure_next = time.monotonic() + 0.25
                has_work = self.engine.has_work()
                finished = []
                if has_work:
                    # mono_ns anchors every time.monotonic() stamp of the
                    # program (request timings, obs/) to the trace's clock:
                    # trace time = this event's start + (stamp - mono_ns)
                    with annotate("driver.step", mono_ns=time.monotonic_ns()):
                        finished = self.engine.step()
                with annotate("driver.export", work=int(has_work)):
                    parked = (self.engine.drain_park_events()
                              if hasattr(self.engine, "drain_park_events") else [])
                    m_running.set(self.engine.num_running)
                    m_waiting.set(self.engine.num_waiting)
                    export_counters()
                    snap = engine_snapshot(self.engine) if has_work else None
                    # queue/pool depths for the continuous profiler, read under
                    # the driver lock so a sample is internally consistent
                    q_depths = (self.engine.num_running, self.engine.num_waiting,
                                getattr(self.engine, "num_parked", 0))
                    pool_alloc = self.engine._allocator
                    pool_depths = (pool_alloc.free_count,
                                   getattr(pool_alloc, "host_pages", 0))
                    # rate-limited chain-digest rebuild for the fleet router —
                    # allocator maps are driver-lock state, so build here and
                    # publish the frozen view through the digest's own lock
                    now = time.monotonic()
                    if now >= digest_next:
                        alloc = self.engine._allocator
                        res_fn = getattr(alloc, "resident_chain_hashes", None)
                        host_fn = getattr(alloc, "host_chain_hashes", None)
                        if res_fn is not None or host_fn is not None:
                            resident = res_fn() if res_fn else frozenset()
                            host = host_fn() if host_fn else frozenset()
                            self.digest.publish(
                                resident, host, time.monotonic() - now)
                        digest_next = now + digest_interval
            with annotate("driver.export", work=int(has_work)):
                if has_work:
                    step_end = time.monotonic()
                    compiles = self.profiler.on_step(step_start, step_end)
                    self.ledger.on_step(snap, step_start, step_end,
                                        compiles=compiles)
                    rec = self.ledger.last_rec or {}
                    if rec.get("wall", 0.0) + rec.get("sched_stall", 0.0) > SLOW_STEP_S:
                        # a stall names itself: which phase held the step, or
                        # that the driver did not step at all (sched_stall)
                        logger.warning(
                            "slow engine step: wall %.2f s (prefill %.2f, decode %.2f, "
                            "compiles %d%s), %.2f s since the step before; running %d, "
                            "waiting %d, free pages %d; host phases %s", rec.get("wall", 0.0),
                            rec.get("prefill", 0.0), rec.get("decode", 0.0),
                            int(rec.get("compiles", 0)),
                            ": " + ", ".join(self.profiler.watchdog.grown) if compiles else "",
                            rec.get("sched_stall", 0.0),
                            q_depths[0], q_depths[1], pool_depths[0],
                            {k: round(v, 3) for k, v in
                             getattr(self.engine, "step_phase_s", {}).items()})
                    # always-on sampled anatomy: every Nth step lands in the
                    # continuous ring (PROFILE_SAMPLE_EVERY); off the lock, so
                    # a flush can never stretch the locked section
                    self.continuous.on_step(step_end, self.ledger.last_rec or {},
                                            queue=q_depths, pool=pool_depths)
                else:
                    self.profiler.idle()
                    self.ledger.idle()
            with annotate("driver.emit", finished=len(finished)):
                for rid in parked:
                    # advisory event: the request is parked (KV in the host
                    # tier) and will resume token-identically.  Disagg decode
                    # consumers use it to fall back fused pre-first-token;
                    # ordinary consumers just keep waiting for tokens.
                    self._emit(rid, StreamEvent(type="parked"))
                for res in finished:
                    m_tokens.inc(len(res.output_tokens))
                    if res.ttft_s is not None:
                        m_ttft.observe(res.ttft_s)
                    decoded = len(res.output_tokens) - 1  # first token is prefill's
                    tpot = None
                    if decoded > 0 and res.decode_time_s > 0:
                        tpot = res.decode_time_s / decoded
                        m_tpot.observe(tpot)
                    self.slo.observe(
                        self._priority.pop(res.request_id, None) or "interactive",
                        ttft_s=res.ttft_s, tpot_s=tpot,
                        deadline_missed=res.finish_reason == "deadline",
                    )
                    self.request_ring.append({
                        "request_id": res.request_id,
                        "prompt_tokens": len(res.prompt_tokens),
                        "cached_tokens": res.cached_tokens,
                        "output_tokens": len(res.output_tokens),
                        "reason": res.finish_reason,
                        "timings": res.timings,
                    })
                    self._emit(res.request_id, StreamEvent(type="final", result=res))
                # keep burn rates decaying while no requests finish (recovery
                # back to ok must not wait for the next completion)
                self.slo.maybe_refresh()
            if not has_work:
                with annotate("driver.wait"):
                    self._wake.wait(timeout=0.02)
                self._wake.clear()

    def driver_alive(self) -> bool:
        """True while the driver thread exists and is running.  A FAULTS-
        killed driver (InjectedFault at ``fleet.step.rN``) exits its thread,
        so this flips false without stop() ever being called."""
        t = self._thread
        return t is not None and t.is_alive()

    def fail_in_flight(self, reason: str) -> list[str]:
        """Fail every in-flight request with the standard error frame (a
        final GenerationResult with finish_reason="error") so no caller
        ever hangs on a dead or wedged driver.

        Runs on the event loop and deliberately does NOT take the driver
        lock — the whole point is that the driver may be wedged holding
        it.  The queues dict is only mutated under the GIL; a racing final
        from a still-twitching driver is harmless (the consumer returns on
        whichever final arrives first and drops its queue)."""
        failed: list[str] = []
        for rid, q in list(self._queues.items()):
            res = GenerationResult(
                request_id=rid, prompt_tokens=[], output_tokens=[],
                finish_reason="error", error=reason,
            )
            q.put_nowait(StreamEvent(type="final", result=res))
            failed.append(rid)
        return failed

    def _emit(self, rid: str, event: StreamEvent) -> None:
        q = self._queues.get(rid)
        if q is None or self._loop is None:
            return
        self._loop.call_soon_threadsafe(q.put_nowait, event)

    # ------------------------------------------------------------- serving

    async def stream(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams | None = None,
        request_id: str | None = None,
        deadline_s: float | None = None,
        priority: str | None = None,
        on_admit=None,
        recv_t: float | None = None,
    ) -> AsyncIterator[StreamEvent]:
        """Submit a request and yield token events then the final event.
        ``deadline_s`` (absolute time.monotonic()) lets the engine reap the
        request at a step boundary once its caller's budget is gone.
        ``priority`` is the SLO class the request's TTFT/TPOT/deadline
        events count against (obs/slo.py).  ``on_admit(rid)`` fires on the
        event loop the moment the request is queued on the engine — the
        router uses it to retire its pending-admission claim exactly when
        the load becomes visible in num_running/num_waiting.  ``recv_t`` is
        when the caller received the request (an HTTP handler's first line);
        without one, this call's entry.  The final result's ``timings``
        carry it with ``enqueue_t`` and ``first_emit_t`` from here."""
        if recv_t is None:
            recv_t = time.monotonic()
        await self.start()
        q: asyncio.Queue[StreamEvent] = asyncio.Queue()

        def on_token(rid: str, token_id: int) -> None:
            self._emit(rid, StreamEvent(type="token", token_id=token_id))

        if priority is None:
            is_longctx = getattr(self.engine, "is_longctx", None)
            if callable(is_longctx) and is_longctx(len(prompt_ids)):
                # ring-prefill-bound request: judged against the longctx
                # SLO thresholds, throttled/preempted like any batch class
                priority = "longctx"
        priority = priority or getattr(
            self.engine, "default_priority", "interactive")
        # the driver holds this lock through all of engine.step(): the wait
        # for it stalls the whole event loop, so it has a stamp and a name
        enqueue_t = time.monotonic()
        with annotate("server.submit_wait"):
            self._lock.acquire()
        try:
            rid = self.engine.add_request(
                prompt_ids, sampling, on_token=on_token, request_id=request_id,
                deadline_s=deadline_s, priority=priority,
                recv_t=recv_t, enqueue_t=enqueue_t,
            )
            self._queues[rid] = q
            self._priority[rid] = priority
        finally:
            self._lock.release()
        if on_admit is not None:
            on_admit(rid)
        self._wake.set()
        first_emit_t = None
        try:
            while True:
                event = await q.get()
                if event.type == "token":
                    if first_emit_t is None:
                        first_emit_t = time.monotonic()
                elif event.type == "final":
                    if event.result.timings is not None:
                        event.result.timings["first_emit_t"] = first_emit_t
                yield event
                if event.type == "final":
                    return
        finally:
            self._queues.pop(rid, None)

    async def generate(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams | None = None,
        request_id: str | None = None,
        deadline_s: float | None = None,
        priority: str | None = None,
    ) -> GenerationResult:
        async for event in self.stream(prompt_ids, sampling, request_id,
                                       deadline_s=deadline_s, priority=priority):
            if event.type == "final":
                return event.result
        raise RuntimeError("stream ended without a final event")  # pragma: no cover

    async def cancel(self, request_id: str) -> None:
        with self._lock:
            self.engine.cancel(request_id)
        self._wake.set()

    # ------------------------------------------------- disagg KV handoff

    async def export_kv_pages(self, hashes: list[bytes]) -> list[tuple[bytes, object]]:
        """Pack the KV payloads for ``hashes`` for shipment to a peer
        replica.  Runs off-loop (the device readback can take milliseconds)
        while holding the driver lock so the pages can't migrate or evict
        out from under the gather — same executor+lock pattern as
        MultiAsyncEngine's host-tier writeback."""

        def work() -> list[tuple[bytes, object]]:
            with self._lock:
                return self.engine.export_kv_pages(hashes)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def import_kv_pages(self, pages: list[tuple[bytes, object]]) -> int:
        """Admit transferred page payloads into this replica's host tier
        (pure host-dict work, but the allocator is driver-lock state)."""

        def work() -> int:
            with self._lock:
                return self.engine.import_kv_pages(pages)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    def stats(self) -> dict[str, Any]:
        from githubrepostorag_tpu.config import get_settings
        from githubrepostorag_tpu.resilience.policy import Deadline
        from githubrepostorag_tpu.runtime import device_facts

        # bounded collection: a wedged driver holds the lock for seconds;
        # /debug/fleet must render the last good row with its age instead
        # of hanging behind it (Deadline: resilience/policy.py)
        deadline = Deadline(get_settings().ctrl_stats_timeout_s)
        if not self._lock.acquire(timeout=max(0.0, deadline.remaining())):
            now = time.monotonic()
            stale: dict[str, Any] = (
                dict(self._last_stats) if self._last_stats
                else {"role": self.role})
            since = (self._last_stats_t if self._last_stats_t is not None
                     else (self.heartbeat if self.heartbeat is not None
                           else now))
            stale["stale_since"] = round(now - since, 3)
            return stale
        try:
            out = {
                "role": self.role,
                "running": self.engine.num_running,
                "waiting": self.engine.num_waiting,
                "requests_admitted": self.engine.requests_admitted,
                "free_pages": self.engine._allocator.free_count,
                "total_pages": self.engine._allocator.num_pages,
                "prefix_cache_hit_tokens": getattr(
                    self.engine._allocator, "hit_tokens", 0
                ),
                "sp_prefills": getattr(self.engine, "sp_prefills", 0),
                "sp_ring_segments": getattr(self.engine, "sp_ring_segments", 0),
                "sp_ring_tokens": getattr(self.engine, "sp_ring_tokens", 0),
                "deadline_reaps": self.engine.deadline_reaps,
                "kv_host_pages": getattr(self.engine._allocator, "host_pages", 0),
                "kv_fault_ins": getattr(self.engine._allocator, "fault_ins", 0),
                "kv_writebacks": getattr(self.engine._allocator, "writebacks", 0),
                "kv_dedup_hits": getattr(self.engine._allocator, "dedup_hits", 0),
                "kv_dedup_holds": getattr(self.engine, "dedup_holds", 0),
                "kv_pages_exported": getattr(self.engine, "kv_pages_exported", 0),
                "kv_pages_imported": getattr(self.engine, "kv_pages_imported", 0),
                "parked": getattr(self.engine, "num_parked", 0),
                "preemptions": getattr(self.engine, "preemptions", 0),
                "preempted_pages": getattr(self.engine, "preempted_pages", 0),
                "preempt_resumes": getattr(self.engine, "preempt_resumes", 0),
                "resume_faulted_pages": getattr(
                    self.engine, "resume_faulted_pages", 0),
                "resume_recomputed_tokens": getattr(
                    self.engine, "resume_recomputed_tokens", 0),
                "resume_recomputed_prompt_tokens": getattr(
                    self.engine, "resume_recomputed_prompt_tokens", 0),
                "pallas": bool(getattr(self.engine, "use_pallas", False)),
                "live_compiles": self.profiler.live_compiles,
            }
        finally:
            self._lock.release()
        # where it runs: a CPU fallback or a gather-path engine must be
        # visible on /health, not only in the start-up log (read outside
        # the driver lock — it asks the runtime, not the engine)
        out.update(device_facts())
        self._last_stats = out
        self._last_stats_t = time.monotonic()
        return dict(out)
