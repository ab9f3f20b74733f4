#!/usr/bin/env python
"""CI gate: the /debug/slo and /debug/fleet JSON shapes must match the
committed golden.

Dashboards and the fleet rollout tooling parse these payloads; a silent
field rename would break them without any test noticing.  This script
builds one deterministic replica (ledger steps + SLO observations with
explicit timestamps) through the real obs API, renders both payloads with
the same functions the API handlers call (``SLOPlane.slo_payload`` /
``fleet_payload``), reduces them to a type-shape schema, and diffs
against ``tests/golden/debug_slo_schema.json``.

    python scripts/check_slo_schema.py            # verify (CI)
    python scripts/check_slo_schema.py --write    # intentional change

An intentional schema change regenerates the golden with --write and
ships the diff in the same PR.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

GOLDEN = REPO / "tests" / "golden" / "debug_slo_schema.json"


def shape(value):
    """Recursive type-shape: dict keys are part of the schema, values
    reduce to type names, lists reduce to the first element's shape."""
    if isinstance(value, dict):
        return {k: shape(v) for k, v in sorted(value.items())}
    if isinstance(value, list):
        return [shape(value[0])] if value else []
    if isinstance(value, bool):
        return "bool"
    if isinstance(value, (int, float)):
        return "number"
    if value is None:
        return "null"
    return type(value).__name__


def build_payloads():
    """One synthetic replica exercising every field both payloads can
    emit: ledger steps touching every bucket and token outcome, SLO
    observations against every objective (hit and miss), a chain digest,
    and the fleet router's decision/per-replica view."""
    from githubrepostorag_tpu.obs.ledger import SNAPSHOT_FIELDS, TokenLedger
    from githubrepostorag_tpu.obs.slo import SLOMonitor, SLOPlane
    from githubrepostorag_tpu.serving.routing import ReplicaDigest

    now = time.monotonic()
    ledger = TokenLedger("r0", flops_per_tok=1e9, peak_flops=1e12,
                         window_s=60.0)
    snap = {f: 0.0 for f in SNAPSHOT_FIELDS}
    ledger.on_step(dict(snap), now - 1.0, now - 0.8, compiles=1)
    snap.update(committed_tokens=8, prefill_tokens=16, reaped_tokens=1,
                admission_blocked_steps=1,
                prefill_seconds_total=0.1, decode_seconds_total=0.1,
                migration_seconds_total=0.01, fault_in_seconds_total=0.01,
                step_dispatches_total=2)
    ledger.on_step(dict(snap), now - 0.7, now - 0.2)

    monitor = SLOMonitor("r0")
    monitor.observe("interactive", ttft_s=0.01, tpot_s=0.01,
                    deadline_missed=False, now=now - 0.5)
    monitor.observe("batch", ttft_s=99.0, tpot_s=99.0,
                    deadline_missed=True, now=now - 0.4)

    digest = ReplicaDigest("r0")
    digest.publish(frozenset([b"a"]), frozenset([b"b"]), 0.001)

    plane = SLOPlane()  # a private plane: no admission-hint registration
    plane.register("r0", ledger=ledger, monitor=monitor,
                   stats=lambda: {"role": "fused", "num_running": 0,
                                  "num_waiting": 0, "free_pages": 32},
                   digest=digest)
    # the same shape MultiAsyncEngine.router_stats() renders (the router
    # registers it via SLOPlane.set_router_info)
    plane.set_router_info(lambda: {
        "policy": "auto",
        "affinity_slack": 4.0,
        "decisions": {"affinity_hit": 1, "affinity_miss": 1,
                      "skipped_breaker_open": 0, "skipped_limiter": 0},
        "per_replica": {"r0": {
            "lifecycle": "active", "role": "fused", "routed": 2,
            "prefix_hit_rate": 0.5,
            "matched_resident_pages": 3, "matched_host_pages": 1,
            "pending": 0, "breaker": "closed",
            "digest": digest.payload(),
        }},
        # MultiAsyncEngine.disagg_stats(): handoff economics + role census
        "disagg": {
            "enabled": True,
            "prefill_replicas": ["r0"],
            "decode_replicas": ["r1"],
            "handoffs": 1,
            "pages_shipped": 4,
            "pages_deduped": 2,
            "fallbacks": {"transfer_error": 1},
            "transport": {"kind": "in_process", "burst": 32,
                          "transfers": 1, "chunks": 1},
        },
    })
    # the same shape FleetController.payload() renders (the controller
    # registers it via SLOPlane.set_controller_info): action-log ring with
    # the ledger-window + burn-state justification stamp, guard counters,
    # cooldowns, hysteresis state
    plane.set_controller_info(lambda: {
        "tick_s": 1.0,
        "ticks": 12,
        "running": True,
        "actions_total": 1,
        "failopen": 0,
        "suppressed": {"hysteresis": 1, "cooldown": 0, "budget": 0,
                       "inflight": 0},
        "budget": {"max_actions": 4, "window_s": 300.0, "used": 1},
        "hysteresis": {"required_ticks": 2,
                       "pending": {"r0:failover:dead": 1}},
        "cooldowns": {"r0:failover": 28.5},
        "log": [{
            "t": 12.0, "replica": "r0", "action": "failover",
            "reason": "dead", "status": "dispatched",
            "justification": {
                "ledger": ledger.justification(now),
                "burn": monitor.burn_state(now),
                "liveness": {"started": True, "thread_alive": False,
                             "heartbeat_age_s": 6.2, "driver_error": None,
                             "breaker": "closed"},
                # page-pool evidence (obs/hbm.PageObservatory.justification)
                "hbm": {"held_pages": 12, "held_peak": 20,
                        "occupancy_page_s": 42.5, "live_requests": 2,
                        "plain_free": 18, "host_pages": 4},
            },
            "detail": {"victim": "r0", "spare": "r2", "no_spare": False,
                       "trigger": "dead"},
        }],
    })
    return plane.slo_payload(), plane.fleet_payload()


def main() -> int:
    slo, fleet = build_payloads()
    current = {
        "GET /debug/slo": shape(slo),
        "GET /debug/fleet": shape(fleet),
    }
    if "--write" in sys.argv:
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN.write_text(json.dumps(current, indent=2, sort_keys=True) + "\n")
        print(f"wrote {GOLDEN.relative_to(REPO)}")
        return 0
    if not GOLDEN.exists():
        print(f"missing golden {GOLDEN.relative_to(REPO)}; run with --write", file=sys.stderr)
        return 1
    golden = json.loads(GOLDEN.read_text())
    if golden != current:
        print("/debug/slo schema drifted from the committed golden.", file=sys.stderr)
        print("golden:  " + json.dumps(golden, sort_keys=True), file=sys.stderr)
        print("current: " + json.dumps(current, sort_keys=True), file=sys.stderr)
        print("If intentional: python scripts/check_slo_schema.py --write", file=sys.stderr)
        return 1
    print("debug/slo schema matches golden")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
