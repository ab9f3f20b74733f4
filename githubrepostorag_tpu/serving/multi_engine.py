"""dp-grouped multi-engine serving: several Engine replicas in ONE server
process, each on its own disjoint submesh.

``MESH_SHAPE=tp:4,dp:2`` on a v5e-8 runs two tp=4 engine replicas sharing
the host — the single-process analog of running two model-server pods
(which remains the cross-host scaling story; SURVEY.md §2.3 DP row).
Small models leave chips idle under pure TP (tp is capped by the KV-head
count — a Qwen2-0.5B with 2 KV heads can use at most tp=2 of 8 chips);
dp groups put the rest to work on independent traffic.

Routing is prefix-affinity first: the request's chain hashes (the same
content-chain identity ``TieredPageAllocator`` uses — serving/chain_hash)
are scored against each replica's published digest and the request goes to
the replica with the longest matchable prefix run, so a shared RAG prefix
warms ONE replica instead of every one.  With no meaningful hit the router
falls back to least-loaded weighted by each replica's ledger limiter
attribution (a replica limited by ``hbm_pages`` or ``swap_wait`` is a bad
target even with a short queue) and skips replicas whose circuit breaker
is open.  A request never migrates once routed — except under
``DISAGG=on``, where it migrates exactly once by design: a prefill
replica computes the prompt's KV, the finished pages ship to an
affinity-chosen decode replica through ``serving/disagg.py``'s transport
seam, and the request resumes there token-identically (any handoff
failure finishes fused on the prefill replica instead).

Replicas have a lifecycle (active | draining | drained | spare): ``drain``
stops admission, lets in-flight work finish, and writes cached pages back
to the host tier; ``activate`` brings a drained or warm-spare replica back
into rotation.  ``/debug/fleet`` renders all of it.

Duck-types AsyncEngine for OpenAIServer: start/stop/stream/generate/
cancel/stats.
"""

from __future__ import annotations

import asyncio
import itertools
import time
from collections import deque
from typing import Any, AsyncIterator

from githubrepostorag_tpu import metrics
from githubrepostorag_tpu.config import get_settings
from githubrepostorag_tpu.obs.trace import NOOP_SPAN, current_span
from githubrepostorag_tpu.resilience.faults import InjectedFault, fire_async
from githubrepostorag_tpu.resilience.policy import get_breaker
from githubrepostorag_tpu.serving.async_engine import AsyncEngine, StreamEvent
from githubrepostorag_tpu.serving.chain_hash import chain_hashes
from githubrepostorag_tpu.serving.disagg import InProcessTransport, assign_roles
from githubrepostorag_tpu.serving.engine import Engine, GenerationResult
from githubrepostorag_tpu.serving.routing import (AFFINITY_LOAD_SLACK,
                                                  score_prefix, weighted_load)
from githubrepostorag_tpu.serving.sampling_params import SamplingParams
from githubrepostorag_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_LIFECYCLE_GAUGE = {"active": 0, "draining": 1, "drained": 2, "spare": 3}


def _span():
    """Active flight-recorder span, or the no-op sink outside a trace."""
    return current_span() or NOOP_SPAN

DECISIONS = ("affinity_hit", "affinity_miss",
             "skipped_breaker_open", "skipped_limiter")


def dp_submeshes(plan, devices=None):
    """Split ``devices`` into ``plan.dp`` disjoint groups and build one
    per-group Mesh with the non-dp axes of ``plan`` (tp/sp/ep; pp is
    rejected by the serving entrypoint).  Group i gets the i-th contiguous
    block of devices, matching the dp-major device order make_mesh would
    use for the full mesh — on a real pod, contiguous blocks are the
    ICI-adjacent ones, so each replica's tp collectives stay on-ring."""
    import dataclasses

    import jax

    from githubrepostorag_tpu.parallel import MeshPlan, make_mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    group_plan = dataclasses.replace(plan, dp=1)
    per = group_plan.n_devices
    if plan.dp * per > len(devices):
        raise ValueError(
            f"mesh plan {plan.shape()} needs {plan.dp * per} devices, "
            f"only {len(devices)} available"
        )
    groups = [devices[i * per : (i + 1) * per] for i in range(plan.dp)]
    # even a 1-device group gets a real mesh: Engine only device_puts
    # params/pools when a mesh is present, so returning None here would
    # silently stack every replica on the default device
    return [make_mesh(group_plan, devices=g) for g in groups], groups


class MultiAsyncEngine:
    """Prefix-affinity fleet router over dp engine replicas.

    Every method runs on the event loop; the only cross-thread reads are
    GIL-atomic engine counters and ``ReplicaDigest.snapshot()`` (which is
    lock-protected on both sides).  ``policy`` pins the routing policy for
    A/B benches ("affinity" | "least_loaded" | "round_robin"); ``spares``
    marks the last N replicas as warm spares that admit nothing until
    ``activate``d."""

    def __init__(self, engines: list[Engine], *, spares: int = 0,
                 policy: str | None = None) -> None:
        if not engines:
            raise ValueError("need at least one engine")
        if spares >= len(engines):
            raise ValueError("spares must leave at least one active replica")
        # replica ids r0..rN-1: each driver writes its own metric series
        # and registers its own ledger/monitor/digest with the SLO plane
        self._engines = [
            AsyncEngine(e, replica=f"r{i}") for i, e in enumerate(engines)
        ]
        self._by_id = {ae.replica: ae for ae in self._engines}
        # bounded fleet-event ring for /debug/timeline: router picks,
        # lifecycle transitions, fences (with victim request ids), disagg
        # handoffs.  Appends are GIL-atomic deque ops on the event loop;
        # the timeline exporter snapshots from any thread.  Created before
        # the spare-marking loop below — _set_lifecycle records into it.
        self._timeline_events: deque[dict] = deque(maxlen=512)
        self._route: dict[str, AsyncEngine] = {}
        # in-flight lifecycle operation per replica: a second drain() or
        # activate() awaits the running task instead of racing it (the
        # controller retries on every tick, so idempotence is load-bearing)
        self._ops: dict[str, asyncio.Task] = {}
        # affinity load-slack is a controller actuator: lowering it makes
        # the router abandon a prefix-hot replica sooner, spreading hot
        # tenants when a replica's limiter says it stalls on swap_wait
        self.affinity_slack: float = AFFINITY_LOAD_SLACK
        self._ids = itertools.count()
        self._rr = itertools.count()  # round_robin policy cursor
        self._policy = policy
        # picked-but-not-yet-admitted requests per replica: incremented at
        # _pick (before any await can interleave another pick), retired by
        # AsyncEngine.stream's on_admit when the engine queues the request
        self._pending: dict[str, int] = {ae.replica: 0 for ae in self._engines}
        self._breakers = {
            ae.replica: get_breaker(f"replica-{ae.replica}")
            for ae in self._engines
        }
        self._decisions = {d: 0 for d in DECISIONS}
        # per-replica routed / prefix-hit request counts + matched pages
        self._routed = {ae.replica: 0 for ae in self._engines}
        self._prefix_hits = {ae.replica: 0 for ae in self._engines}
        self._matched_resident = {ae.replica: 0 for ae in self._engines}
        self._matched_host = {ae.replica: 0 for ae in self._engines}
        for ae in self._engines[len(engines) - spares:]:
            self._set_lifecycle(ae, "spare")
        for ae in self._engines:
            metrics.FLEET_LIFECYCLE.labels(replica=ae.replica).set(
                _LIFECYCLE_GAUGE[ae.lifecycle])
        # disaggregated prefill/decode split (serving/disagg.py): roles are
        # assigned once at fleet construction; the handoff counters and
        # transport live here because the router owns the request lifecycle
        # the handoff threads through
        self._disagg = assign_roles(self._engines, get_settings())
        self._transport = (
            InProcessTransport(get_settings().disagg_transfer_burst)
            if self._disagg else None
        )
        self._handoffs = 0
        self._handoff_pages_shipped = 0
        self._handoff_pages_deduped = 0
        self._handoff_fallbacks: dict[str, int] = {}
        from githubrepostorag_tpu.obs.slo import get_slo_plane

        get_slo_plane().set_router_info(self.router_stats)
        # the timeline exporter reads the fleet-event ring through the same
        # provider inversion as set_router_info above
        from githubrepostorag_tpu.obs.timeline import set_fleet_events_provider

        set_fleet_events_provider(lambda: list(self._timeline_events))

    def _tl(self, kind: str, **attrs: Any) -> None:
        ev = {"t": time.monotonic(), "kind": kind}
        ev.update(attrs)
        self._timeline_events.append(ev)

    # ------------------------------------------------------------ lifecycle

    async def start(self) -> None:
        for eng in self._engines:
            if eng.lifecycle != "spare":
                await eng.start()

    async def stop(self) -> None:
        for eng in self._engines:
            await eng.stop()

    def _set_lifecycle(self, ae: AsyncEngine, state: str) -> None:
        ae.lifecycle = state
        metrics.FLEET_LIFECYCLE.labels(replica=ae.replica).set(
            _LIFECYCLE_GAUGE[state])
        self._tl("fleet.lifecycle", replica=ae.replica, state=state)

    def _in_flight(self, ae: AsyncEngine) -> int:
        return (ae.engine.num_running + ae.engine.num_waiting
                + self._pending.get(ae.replica, 0))

    async def _lifecycle_op(self, replica: str, verb: str,
                            impl) -> dict[str, Any]:
        """Serialize lifecycle verbs per replica and make repeats no-ops:
        a second ``drain`` (or ``activate``) while one is in flight awaits
        the SAME task and returns its result; an opposing verb queues
        behind the running one instead of interleaving with it.  Shielded
        so one cancelled caller can't abort the shared operation."""
        name = f"{verb}-{replica}"
        while True:
            op = self._ops.get(replica)
            if op is None or op.done():
                break
            if op.get_name() == name:
                return await asyncio.shield(op)
            # drain-then-activate (or the reverse) race: let the running
            # op finish, then re-check state from scratch
            try:
                await asyncio.shield(op)
            except Exception:  # noqa: BLE001 - the first caller surfaces it
                pass
        task = asyncio.get_running_loop().create_task(impl(), name=name)
        self._ops[replica] = task
        return await asyncio.shield(task)

    async def drain(self, replica: str) -> dict[str, Any]:
        """Stop admitting on ``replica``, let in-flight requests finish,
        then write cached pages back to the host tier so a later activate
        (or a peer's fault-in path, once pages are cross-replica) starts
        warm.  Resolves even if the replica dies mid-drain (chaos seam
        ``fleet.drain``): the corpse is force-stopped and still counts as
        drained — it admits nothing either way.  Idempotent: a concurrent
        drain of the same replica joins the in-flight one."""
        ae = self._by_id[replica]
        return await self._lifecycle_op(
            replica, "drain", lambda: self._drain_impl(ae))

    async def _drain_impl(self, ae: AsyncEngine) -> dict[str, Any]:
        replica = ae.replica
        if ae.lifecycle == "drained":
            return {"replica": replica, "lifecycle": "drained", "waited": 0}
        self._set_lifecycle(ae, "draining")
        span = _span()
        span.add_event("fleet.drain", replica=replica)
        waited = 0
        try:
            await fire_async("fleet.drain")
            while self._in_flight(ae) > 0:
                waited += 1
                await asyncio.sleep(0.01)
                await fire_async("fleet.drain")
            # writeback runs under the driver lock off-loop: evict plans +
            # flush_kv_migrations are allocator/engine state
            await asyncio.get_running_loop().run_in_executor(
                None, self._writeback_host_tier, ae)
        except InjectedFault as exc:
            self._breakers[replica].record_failure()
            span.add_event("fleet.drain.fault", replica=replica,
                           error=str(exc))
            await ae.stop()
            self._set_lifecycle(ae, "drained")
            return {"replica": replica, "lifecycle": "drained",
                    "waited": waited, "fault": str(exc)}
        self._set_lifecycle(ae, "drained")
        return {"replica": replica, "lifecycle": "drained", "waited": waited}

    def _writeback_host_tier(self, ae: AsyncEngine) -> None:
        engine = ae.engine
        with ae._lock:
            if not getattr(engine, "_kv_tier_on", False):
                return
            # drain the whole LRU into the host pool (bounded by its cap),
            # then run migration boundaries until every DMA has landed
            engine.flush_kv_migrations()

    async def activate(self, replica: str) -> dict[str, Any]:
        """Bring a warm spare or drained replica (back) into rotation.
        Idempotent: activating an already-active replica is a no-op, and a
        concurrent activate joins the in-flight one."""
        ae = self._by_id[replica]
        return await self._lifecycle_op(
            replica, "activate", lambda: self._activate_impl(ae))

    async def _activate_impl(self, ae: AsyncEngine) -> dict[str, Any]:
        replica = ae.replica
        if ae.lifecycle == "active" and ae.driver_alive():
            return {"replica": replica, "lifecycle": "active"}
        self._set_lifecycle(ae, "active")
        await ae.start()
        _span().add_event("fleet.activate", replica=replica)
        return {"replica": replica, "lifecycle": "active"}

    async def fence(self, replica: str) -> dict[str, Any]:
        """Emergency isolation for a dead/wedged replica: stop admission
        (lifecycle -> draining, so ``_pick`` skips it) and fail its
        in-flight work with the standard error frame — the hand-back that
        lets callers retry through the router instead of hanging on a
        driver that will never step again.  Unlike ``drain`` this never
        waits on the victim."""
        ae = self._by_id[replica]
        if ae.lifecycle in ("active", "spare"):
            self._set_lifecycle(ae, "draining")
        failed = ae.fail_in_flight(
            f"replica {replica} fenced by fleet controller")
        for rid in failed:
            self._route.pop(rid, None)
        self._breakers[replica].record_failure()
        _span().add_event("fleet.fence", replica=replica, failed=len(failed))
        # the victim rids ride the event (capped) so the timeline can mark
        # each fenced request on the dead replica's own track
        self._tl("fleet.fence", replica=replica, failed=len(failed),
                 failed_requests=failed[:32])
        return {"replica": replica, "lifecycle": ae.lifecycle,
                "failed": len(failed)}

    async def retire(self, replica: str) -> dict[str, Any]:
        """Force-stop a fenced corpse without waiting for in-flight work
        (``fence`` already failed it) — ``drain``'s escape hatch for a
        driver that can no longer make progress."""
        ae = self._by_id[replica]
        await ae.stop()
        self._set_lifecycle(ae, "drained")
        _span().add_event("fleet.retire", replica=replica)
        return {"replica": replica, "lifecycle": "drained"}

    def replicas(self) -> list[AsyncEngine]:
        """The fleet's AsyncEngine rows (the controller's sense loop reads
        lifecycle/heartbeat/driver_alive off them)."""
        return list(self._engines)

    def spare_replicas(self) -> list[str]:
        return [ae.replica for ae in self._engines
                if ae.lifecycle == "spare"]

    def set_affinity_slack(self, slack: float) -> float:
        """Controller actuator for ``swap_wait`` remediation: clamp and set
        the affinity load-slack (floor 0.5 keeps affinity from degrading
        into pure least-loaded)."""
        self.affinity_slack = max(0.5, float(slack))
        return self.affinity_slack

    # ------------------------------------------------------------- routing

    def _affinity_enabled(self) -> bool:
        if self._policy == "affinity":
            return True
        if self._policy in ("least_loaded", "round_robin"):
            return False
        mode = get_settings().route_affinity
        if mode == "on":
            return True
        if mode == "off":
            return False
        # auto: affinity iff any replica can actually serve a prefix hit
        return any(
            hasattr(ae.engine._allocator, "resident_chain_hashes")
            for ae in self._engines
        )

    def _pick(self, prompt_ids: list[int],
              roles: tuple[str, ...] | None = None) -> tuple[AsyncEngine, bool]:
        """Choose a replica; returns (target, breaker_granted).

        Ranking first, breaker second: ``allow()`` consumes the single
        half-open probe, so it is only asked about the replica we are about
        to use — probing every candidate would wedge the ones not chosen.
        ``roles`` restricts candidates under disaggregation; when every
        replica of the wanted role is gone, any active replica still
        serves the request fused rather than failing it."""
        cands = [ae for ae in self._engines if ae.lifecycle == "active"
                 and (roles is None or ae.role in roles)]
        if not cands and roles is not None:
            cands = [ae for ae in self._engines if ae.lifecycle == "active"]
        if not cands:
            raise RuntimeError("no active replicas (all drained or spare)")

        decision = None
        matched = {}
        if self._policy == "round_robin":
            ranked = [cands[next(self._rr) % len(cands)]]
            ranked += [ae for ae in cands if ae is not ranked[0]]
        elif self._affinity_enabled():
            min_pages = get_settings().route_min_prefix_pages
            hashes_by_ps: dict[int, list[bytes]] = {}
            scored = []
            for ae in cands:
                ps = ae.engine.page_size
                if ps not in hashes_by_ps:
                    hashes_by_ps[ps] = chain_hashes(prompt_ids, ps)
                res, hst, score = score_prefix(
                    hashes_by_ps[ps], *ae.digest.snapshot())
                matched[ae.replica] = (res, hst)
                scored.append((ae, res + hst, score))
            hits = [t for t in scored if t[1] >= max(1, min_pages)]
            if hits:
                # longest weighted run wins; ties go to the lighter replica
                ranked = [t[0] for t in sorted(
                    hits, key=lambda t: (-t[2], self._load(t[0])))]
                floor = min(self._load(ae) for ae in cands)
                if self._load(ranked[0]) - floor > self.affinity_slack:
                    # the hit replica is saturated: the queue wait behind
                    # the whole burst costs more than the saved prefill
                    decision = "affinity_miss"
                    ranked = self._rank_fallback(cands)
                else:
                    decision = "affinity_hit"
                    ranked += [ae for ae in cands if ae not in ranked]
            else:
                decision = "affinity_miss"
                ranked = self._rank_fallback(cands)
        else:
            ranked = self._rank_fallback(cands)

        target, granted = ranked[0], False
        for ae in ranked:
            if self._breakers[ae.replica].allow():
                target, granted = ae, True
                break
            self._count("skipped_breaker_open")
        # all breakers refused: fail open to the best-ranked replica — a
        # fleet-wide outage should degrade to normal routing, not a 500

        if decision is not None:
            self._count(decision)
        self._routed[target.replica] += 1
        metrics.ROUTER_ROUTED.labels(replica=target.replica).inc()
        res, hst = matched.get(target.replica, (0, 0))
        if res + hst > 0:
            self._prefix_hits[target.replica] += 1
            self._matched_resident[target.replica] += res
            self._matched_host[target.replica] += hst
            if res:
                metrics.ROUTER_PREFIX_PAGES.labels(
                    replica=target.replica, tier="resident").inc(res)
            if hst:
                metrics.ROUTER_PREFIX_PAGES.labels(
                    replica=target.replica, tier="host").inc(hst)
        _span().add_event(
            "router.pick", replica=target.replica,
            decision=decision or self._policy or "least_loaded",
            resident_pages=res, host_pages=hst,
            breaker_granted=granted,
        )
        self._tl("router.pick", replica=target.replica,
                 decision=decision or self._policy or "least_loaded",
                 resident_pages=res, host_pages=hst,
                 breaker_granted=granted)
        return target, granted

    def _load(self, ae: AsyncEngine) -> float:
        """Load snapshot in request units: queue depth, plus picks not yet
        visible as queue depth, plus claimed-but-unregistered prefill pages
        (normalized to sequences) so a simultaneous-admission burst doesn't
        all land on one replica that still *looks* idle."""
        e = ae.engine
        load = float(e.num_running + e.num_waiting
                     + self._pending.get(ae.replica, 0))
        claim_fn = getattr(e._allocator, "pending_claim_pages", None)
        if callable(claim_fn):
            pages_per_seq = max(1, e.max_seq_len // max(1, e.page_size))
            load += claim_fn() / pages_per_seq
        return load

    def _rank_fallback(self, cands: list[AsyncEngine]) -> list[AsyncEngine]:
        """Least-loaded weighted by the ledger's limiter attribution."""
        raw = min(cands, key=self._load)

        def key(ae: AsyncEngine) -> float:
            return weighted_load(self._load(ae),
                                 ae.ledger.current_limiter())

        ranked = sorted(cands, key=key)
        if ranked[0] is not raw:
            # the shortest queue was passed over because its limiter says
            # admissions there stall on pages/swap, not compute
            self._count("skipped_limiter")
        return ranked

    def _count(self, decision: str) -> None:
        self._decisions[decision] += 1
        metrics.ROUTER_DECISIONS.labels(decision=decision).inc()

    # ------------------------------------------------------------- serving

    async def stream(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams | None = None,
        request_id: str | None = None,
        deadline_s: float | None = None,
        priority: str | None = None,
        recv_t: float | None = None,
    ) -> AsyncIterator[StreamEvent]:
        # ``recv_t`` (the caller's receipt stamp) rides to the serving
        # replica's request record; a disaggregated request's two legs
        # each stamp their own entry instead.
        # engines generate per-engine "req-N" ids that would collide across
        # replicas; mint a process-unique id when the caller didn't
        rid = request_id or f"mreq-{next(self._ids)}"
        priority = priority or getattr(
            self._engines[0].engine, "default_priority", "interactive")
        if self._disagg:
            events = self._stream_disagg(prompt_ids, sampling, rid,
                                         deadline_s, priority)
        else:
            target, granted = self._pick(prompt_ids)
            events = self._stream_on(target, granted, prompt_ids, sampling,
                                     rid, deadline_s, priority, recv_t=recv_t)
        async for event in events:
            yield event

    async def _stream_on(
        self,
        target: AsyncEngine,
        granted: bool,
        prompt_ids: list[int],
        sampling: SamplingParams | None,
        rid: str,
        deadline_s: float | None,
        priority: str,
        recv_t: float | None = None,
    ) -> AsyncIterator[StreamEvent]:
        """Run ``rid`` on the already-picked ``target``, owning the route
        map, pending-claim, and breaker bookkeeping end to end."""
        self._route[rid] = target
        self._pending[target.replica] += 1
        admitted = False

        def on_admit(_rid: str) -> None:
            nonlocal admitted
            if not admitted:
                admitted = True
                self._pending[target.replica] -= 1

        breaker = self._breakers[target.replica]
        recorded = False
        try:
            async for event in target.stream(
                prompt_ids, sampling, request_id=rid, deadline_s=deadline_s,
                priority=priority, on_admit=on_admit, recv_t=recv_t,
            ):
                if event.type == "final":
                    # settle breaker + route eagerly at the final token, not
                    # in the finally below: generator finalization is
                    # deferred, so cleanup there could land arbitrarily late
                    if granted and not recorded:
                        recorded = True
                        breaker.record_success()
                    self._route.pop(rid, None)
                yield event
        except Exception:
            if granted and not recorded:
                recorded = True
                breaker.record_failure()
            raise
        finally:
            # abandoned/cancelled streams are caller choices, not replica
            # faults — and a granted half-open probe MUST resolve or the
            # breaker wedges with _probing set forever
            if granted and not recorded:
                breaker.record_success()
            if not admitted:
                on_admit(rid)
            self._route.pop(rid, None)

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
        target = self._route.get(request_id)
        if target is not None:
            await target.cancel(request_id)

    # ------------------------------------------------------ disagg handoff

    async def _stream_disagg(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams | None,
        rid: str,
        deadline_s: float | None,
        priority: str,
    ) -> AsyncIterator[StreamEvent]:
        """Prefill on a prefill replica, ship the KV, decode elsewhere.

        The prefill pass is a 1-token greedy request: its sampled token is
        discarded — the full prefix pages it leaves in the prefill
        replica's cache are the product.  The decode replica re-admits the
        ORIGINAL request against the shipped pages (``share`` + the warmed
        fault-in scatters), recomputes only the tail partial page, and
        emits every token the fused path would have: sampling never sees
        different logits, so the two modes are token-identical.  Any
        failure before the decode replica has emitted anything finishes
        the request fused on the prefill replica instead — which holds the
        whole prefix in its own cache, so the retry's prefill is nearly
        free."""
        # disagg fleets are page-size-homogeneous (assign_roles requires
        # every replica tiered); chain hashes computed at this page size
        # are the identity on BOTH ends of the wire
        ps = self._engines[0].engine.page_size
        # only FULL pages ship: the tail partial page (and the page the
        # prompt's last token lands on) is recomputed by the decode
        # replica's admission — same cap share() itself applies
        shippable = max(0, (len(prompt_ids) - 1) // ps)
        if shippable == 0:
            # nothing a peer could reuse: skip the handoff, a decode
            # replica does its own (tiny) prefill
            target, tgrant = self._pick(prompt_ids, roles=("decode",))
            async for event in self._stream_on(
                target, tgrant, prompt_ids, sampling, rid, deadline_s,
                priority,
            ):
                yield event
            return
        pre, granted = self._pick(prompt_ids, roles=("prefill",))
        if pre.role != "prefill":
            # the prefill tier is gone and _pick fell back: serve fused
            # on whatever it chose
            async for event in self._stream_on(
                pre, granted, prompt_ids, sampling, rid, deadline_s,
                priority,
            ):
                yield event
            return
        hashes = chain_hashes(prompt_ids, ps)[:shippable]

        pre_sampling = SamplingParams(temperature=0.0, max_tokens=1)
        final = None
        try:
            async for event in self._stream_on(
                pre, granted, prompt_ids, pre_sampling, f"{rid}-pre",
                deadline_s, priority,
            ):
                if event.type == "final":
                    final = event.result
        except Exception as exc:
            # the prefill replica itself failed: retry fused anywhere
            self._handoff_fallback("prefill_error")
            _span().add_event("disagg.prefill.fault", error=str(exc))
            target, tgrant = self._pick(prompt_ids)
            async for event in self._stream_on(
                target, tgrant, prompt_ids, sampling, rid, deadline_s,
                priority,
            ):
                yield event
            return
        if final is None or final.finish_reason == "deadline":
            # reaped mid-pass: the caller's budget is gone either way; let
            # the fused path produce the authoritative deadline result
            self._handoff_fallback("prefill_deadline")
            async for event in self._fallback_fused(
                pre, prompt_ids, sampling, rid, deadline_s, priority,
            ):
                yield event
            return

        dest, dgrant = self._pick_decode(hashes)
        if dest is None:
            self._handoff_fallback("no_decode_replica")
            async for event in self._fallback_fused(
                pre, prompt_ids, sampling, rid, deadline_s, priority,
            ):
                yield event
            return

        # ship only what the destination can't already serve: a decode
        # replica holding the prefix content-hash-deduped pays nothing
        res, hst = dest.digest.snapshot()
        need = [h for h in hashes if h not in res and h not in hst]
        try:
            exported, stored = await self._transport.transfer(pre, dest, need)
        except Exception as exc:  # InjectedFault or a dead peer
            if dgrant:
                # the granted half-open probe must resolve (cf. stream())
                self._breakers[dest.replica].record_failure()
            self._handoff_fallback("transfer_error")
            _span().add_event("disagg.transfer.fault", decode=dest.replica,
                              error=str(exc))
            async for event in self._fallback_fused(
                pre, prompt_ids, sampling, rid, deadline_s, priority,
            ):
                yield event
            return

        deduped = (len(hashes) - len(need)) + (exported - stored)
        self._handoffs += 1
        self._handoff_pages_shipped += stored
        self._handoff_pages_deduped += deduped
        metrics.DISAGG_HANDOFFS.labels(outcome="shipped").inc()
        if stored:
            metrics.DISAGG_PAGES.labels(kind="shipped").inc(stored)
        if deduped:
            metrics.DISAGG_PAGES.labels(kind="deduped").inc(deduped)
        _span().add_event("disagg.handoff", prefill=pre.replica,
                          decode=dest.replica, shipped=stored,
                          deduped=deduped)
        self._tl("disagg.handoff", prefill=pre.replica,
                 decode=dest.replica, shipped=stored, deduped=deduped)

        yielded = False
        parked = False
        try:
            async for event in self._stream_on(
                dest, dgrant, prompt_ids, sampling, rid, deadline_s,
                priority,
            ):
                if event.type == "parked" and not yielded:
                    # the decode replica preempted this request before its
                    # first token: rather than wait out the park, cancel it
                    # there and finish fused on the prefill replica, which
                    # still holds the whole prefix hot.  Once tokens have
                    # flowed, a park is just latency — the resume is
                    # token-identical, so keep consuming.
                    parked = True
                    break
                yielded = True
                yield event
            if not parked:
                return
            await dest.cancel(rid)
            self._handoff_fallback("preempted")
        except Exception:
            if yielded:
                # tokens already reached the caller: replaying from the
                # prefill replica would duplicate them — surface the error
                raise
            self._handoff_fallback("decode_error")
        async for event in self._fallback_fused(
            pre, prompt_ids, sampling, rid, deadline_s, priority,
        ):
            yield event

    async def _fallback_fused(
        self, pre: AsyncEngine, prompt_ids, sampling, rid, deadline_s,
        priority,
    ) -> AsyncIterator[StreamEvent]:
        """Finish ``rid`` fused on the prefill replica that already holds
        its prefix (the handoff's universal escape hatch)."""
        granted = self._breakers[pre.replica].allow()
        async for event in self._stream_on(
            pre, granted, prompt_ids, sampling, rid, deadline_s, priority,
        ):
            yield event

    def _pick_decode(self, hashes: list[bytes]) -> tuple[AsyncEngine | None, bool]:
        """Decode-side target: longest matchable run of the shipped hashes
        first (a replica already holding the prefix imports nothing), then
        limiter-weighted load.  Mirrors ``_pick``'s ranking-then-breaker
        fail-open; returns (None, False) only when no decode replica is
        active."""
        cands = [ae for ae in self._engines
                 if ae.lifecycle == "active" and ae.role == "decode"]
        if not cands:
            return None, False

        def key(ae: AsyncEngine) -> tuple[float, float]:
            _, _, score = score_prefix(hashes, *ae.digest.snapshot())
            return (-score, weighted_load(self._load(ae),
                                          ae.ledger.current_limiter()))

        ranked = sorted(cands, key=key)
        target, granted = ranked[0], False
        for ae in ranked:
            if self._breakers[ae.replica].allow():
                target, granted = ae, True
                break
            self._count("skipped_breaker_open")
        self._routed[target.replica] += 1
        metrics.ROUTER_ROUTED.labels(replica=target.replica).inc()
        self._tl("router.pick_decode", replica=target.replica,
                 breaker_granted=granted)
        return target, granted

    def _handoff_fallback(self, reason: str) -> None:
        self._handoff_fallbacks[reason] = (
            self._handoff_fallbacks.get(reason, 0) + 1)
        metrics.DISAGG_HANDOFFS.labels(outcome=f"fallback_{reason}").inc()  # tpulint: disable=OBS003 -- reason is the closed set of handoff fallback causes
        _span().add_event("disagg.fallback", reason=reason)
        self._tl("disagg.fallback", reason=reason)

    def disagg_stats(self) -> dict[str, Any]:
        """Handoff economics + role census (router_stats and /debug/fleet
        render this)."""
        return {
            "enabled": self._disagg,
            "prefill_replicas": [ae.replica for ae in self._engines
                                 if ae.role == "prefill"],
            "decode_replicas": [ae.replica for ae in self._engines
                                if ae.role == "decode"],
            "handoffs": self._handoffs,
            "pages_shipped": self._handoff_pages_shipped,
            "pages_deduped": self._handoff_pages_deduped,
            "fallbacks": dict(self._handoff_fallbacks),
            "transport": (self._transport.payload()
                          if self._transport is not None else None),
        }

    # ------------------------------------------------------------ reading --

    def router_stats(self) -> dict[str, Any]:
        """Decision counters + per-replica routing view (stats(), the SLO
        plane's fleet payload, and /debug/fleet all render this)."""
        per = {}
        for ae in self._engines:
            r = ae.replica
            routed = self._routed[r]
            per[r] = {
                "lifecycle": ae.lifecycle,
                "role": ae.role,
                "routed": routed,
                "prefix_hit_rate": self._prefix_hits[r] / max(1, routed),
                "matched_resident_pages": self._matched_resident[r],
                "matched_host_pages": self._matched_host[r],
                "pending": self._pending[r],
                "breaker": self._breakers[r].state,
                "digest": ae.digest.payload(),
            }
        return {
            "policy": self._policy or get_settings().route_affinity,
            "affinity_slack": self.affinity_slack,
            "decisions": dict(self._decisions),
            "per_replica": per,
            "disagg": self.disagg_stats(),
        }

    @staticmethod
    def _merge_rows(rows: list[dict], mean_rows: list[dict] | None = None
                    ) -> dict[str, Any]:
        """Union of keys; numeric values merge across replicas — counters
        SUM, but rate/ratio-style keys would turn into nonsense summed
        (two replicas at 0.8 acceptance are not at 1.6), so they merge by
        MEAN — over ``mean_rows`` when given: the fleet merge passes only
        decode-capable replicas there, so a prefill-only replica's idle
        decode-side rates don't drag the fleet means.  A non-numeric or
        replica-local stat stays visible under per_replica."""
        mean_rows = rows if mean_rows is None else mean_rows
        keys = sorted(set().union(*(s.keys() for s in rows))) if rows else []
        merged: dict[str, Any] = {}
        for key in keys:
            is_mean = key.endswith(("_rate", "_ratio", "_utilization"))
            nums = [
                s[key] for s in (mean_rows if is_mean else rows)
                if isinstance(s.get(key), (int, float))
                and not isinstance(s.get(key), bool)
            ]
            if nums:
                merged[key] = sum(nums) / len(nums) if is_mean else sum(nums)
        return merged

    def stats(self) -> dict[str, Any]:
        per = [eng.stats() for eng in self._engines]
        roles = [s.get("role", "fused") for s in per]
        # prefill-specialized replicas never decode: excluding them from
        # the mean-merged keys keeps fleet TPOT/acceptance honest (on a
        # fused fleet every role is "fused", so this is the old merge)
        decodeish = [s for s, r in zip(per, roles) if r != "prefill"] or per
        merged = self._merge_rows(per, mean_rows=decodeish)
        merged["replicas"] = len(per)
        merged["per_replica"] = per
        if getattr(self, "_disagg", False):
            by_role: dict[str, list[dict]] = {}
            for s, r in zip(per, roles):
                by_role.setdefault(r, []).append(s)
            merged["per_role"] = {
                r: self._merge_rows(rows) for r, rows in by_role.items()
            }
        if hasattr(self, "_decisions"):  # absent on bare merge-rule stubs
            merged["router"] = self.router_stats()
        return merged

    def fleet(self) -> dict[str, Any]:
        """Pod-at-a-glance: per-replica ledgers + SLO states + router
        decisions federated via the process SLO plane (same payload as GET
        /debug/fleet)."""
        from githubrepostorag_tpu.obs.slo import get_slo_plane

        return get_slo_plane().fleet_payload()
