"""Self-healing fleet controller (serving/controller.py): the guarded
sense -> decide -> act ladder under a fully simulated clock.

Every test drives ``tick(now=...)`` directly against a fake fleet, so
hysteresis, cooldown, the action budget, and liveness ages are exact
clock arithmetic — no sleeping, no flakes.  The chaos e2e (a FAULTS-
killed replica recovering through a real MultiAsyncEngine) lives in
test_chaos.py; this file proves the decision logic itself.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import threading
from types import SimpleNamespace

from githubrepostorag_tpu.config import reload_settings
from githubrepostorag_tpu.metrics import (
    CTRL_ACTIONS,
    CTRL_FAILOPEN,
    CTRL_SUPPRESSED,
    counter_value,
)
from githubrepostorag_tpu.obs.slo import get_slo_plane
from githubrepostorag_tpu.resilience.faults import reset_faults
from githubrepostorag_tpu.serving.controller import FleetController


class _FakeReplica:
    """Just the surface the controller touches on an AsyncEngine row."""

    def __init__(self, rid: str, lifecycle: str = "active") -> None:
        self.replica = rid
        self.lifecycle = lifecycle
        self.heartbeat: float | None = None
        self.driver_error: str | None = None
        self.alive = False
        self._lock = threading.Lock()
        self.engine = SimpleNamespace(
            _allocator=SimpleNamespace(host_pool_pages=4, num_pages=8),
        )

    def driver_alive(self) -> bool:
        return self.alive


class _FakeFleet:
    """Records every actuator call the controller makes, in order."""

    def __init__(self, replicas: list[_FakeReplica]) -> None:
        self._engines = replicas
        self._by_id = {ae.replica: ae for ae in replicas}
        self.affinity_slack = 4.0
        self.calls: list[tuple[str, str]] = []

    def replicas(self) -> list[_FakeReplica]:
        return list(self._engines)

    def spare_replicas(self) -> list[str]:
        return [ae.replica for ae in self._engines if ae.lifecycle == "spare"]

    def set_affinity_slack(self, slack: float) -> float:
        self.affinity_slack = max(0.5, float(slack))
        return self.affinity_slack

    async def fence(self, replica: str) -> dict:
        self.calls.append(("fence", replica))
        return {"replica": replica, "lifecycle": "draining", "failed": 2}

    async def activate(self, replica: str) -> dict:
        self.calls.append(("activate", replica))
        self._by_id[replica].lifecycle = "active"
        return {"replica": replica, "lifecycle": "active"}

    async def retire(self, replica: str) -> dict:
        self.calls.append(("retire", replica))
        self._by_id[replica].lifecycle = "drained"
        return {"replica": replica, "lifecycle": "drained"}


def _ctrl(monkeypatch, fleet, *, restore=None, **env: str) -> FleetController:
    env.setdefault("CTRL_HYSTERESIS_TICKS", "2")
    env.setdefault("CTRL_COOLDOWN_S", "10")
    env.setdefault("CTRL_MAX_ACTIONS", "4")
    env.setdefault("CTRL_ACTION_WINDOW_S", "100")
    env.setdefault("CTRL_LIVENESS_TIMEOUT_S", "5")
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    reload_settings()
    reset_faults()
    return FleetController(fleet, clock=lambda: 0.0, restore=restore)


def _sensed(rid: str, *, lifecycle: str = "active", thread_alive: bool = True,
            heartbeat_age_s: float | None = 0.1, breaker: str = "closed",
            burn: str = "ok", limiter: str = "none") -> dict:
    return {rid: {
        "ledger": {"limiter": limiter, "window_s": 60.0, "steps": 5,
                   "goodput_tok_s": 100.0},
        "burn": {"state": burn, "classes": {"interactive": burn}},
        "lifecycle": lifecycle,
        "liveness": {"started": True, "thread_alive": thread_alive,
                     "heartbeat_age_s": heartbeat_age_s,
                     "driver_error": None, "breaker": breaker},
    }}


def _script(monkeypatch, ctrl, snap: dict) -> None:
    monkeypatch.setattr(ctrl, "_sense", lambda now: snap)


# ------------------------------------------------------------------- sense --


def test_sense_merges_liveness_with_plane_snapshot(monkeypatch):
    r0, r1 = _FakeReplica("r0"), _FakeReplica("r1")
    ctrl = _ctrl(monkeypatch, _FakeFleet([r0, r1]))
    r0.heartbeat, r0.alive = 10.0, True
    r1.heartbeat, r1.alive, r1.driver_error = 3.0, False, "injected"
    sensed = ctrl._sense(now=11.0)
    assert sensed["r0"]["liveness"] == {
        "started": True, "thread_alive": True, "heartbeat_age_s": 1.0,
        "driver_error": None, "breaker": "closed"}
    assert sensed["r1"]["liveness"]["thread_alive"] is False
    assert sensed["r1"]["liveness"]["heartbeat_age_s"] == 8.0
    assert sensed["r1"]["liveness"]["driver_error"] == "injected"
    # no ledger registered on the (reset) plane: keys present, values None
    assert sensed["r0"]["ledger"] is None and sensed["r0"]["burn"] is None


def test_never_started_replica_is_not_declared_dead(monkeypatch):
    """A spare that has not run a driver yet has no heartbeat; the ladder
    must not failover a replica that was never alive."""
    r0 = _FakeReplica("r0")  # active but heartbeat None, thread dead
    ctrl = _ctrl(monkeypatch, _FakeFleet([r0]))
    assert ctrl.tick(now=0.0) == []
    assert ctrl.tick(now=1.0) == []
    assert ctrl._pending == {}


# -------------------------------------------------------------- hysteresis --


def test_failover_needs_two_consecutive_agreeing_ticks(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0"),
                        _FakeReplica("r1", lifecycle="spare")])
    ctrl = _ctrl(monkeypatch, fleet)
    _script(monkeypatch, ctrl, _sensed("r0", thread_alive=False))
    before = counter_value(CTRL_SUPPRESSED, guard="hysteresis")
    before_acts = counter_value(CTRL_ACTIONS, action="failover", reason="dead")

    assert ctrl.tick(now=0.0) == []  # first agreeing tick: suppressed
    assert counter_value(CTRL_SUPPRESSED, guard="hysteresis") == before + 1
    assert ctrl.payload()["hysteresis"]["pending"] == {"r0:failover:dead": 1}

    acted = ctrl.tick(now=1.0)  # second agreeing tick: the ladder fires
    assert [a["action"] for a in acted] == ["failover"]
    assert acted[0]["reason"] == "dead" and acted[0]["ticks_agreed"] == 2
    # fence victim -> activate spare -> retire corpse, in that order
    assert fleet.calls == [("fence", "r0"), ("activate", "r1"),
                           ("retire", "r0")]
    assert counter_value(CTRL_ACTIONS, action="failover",
                         reason="dead") == before_acts + 1
    # the action log entry carries the justification that fired it
    entry = ctrl.payload()["log"][-1]
    assert entry["status"] == "dispatched"
    assert entry["justification"]["liveness"]["thread_alive"] is False
    assert entry["detail"]["spare"] == "r1"


def test_hysteresis_resets_when_the_decision_vanishes(monkeypatch):
    ctrl = _ctrl(monkeypatch, _FakeFleet([_FakeReplica("r0"),
                                          _FakeReplica("r1", lifecycle="spare")]))
    dead = _sensed("r0", thread_alive=False)
    healthy = _sensed("r0")
    monkeypatch.setattr(ctrl, "_sense",
                        lambda now: dead if now != 1.0 else healthy)
    assert ctrl.tick(now=0.0) == []    # dead: pending 1
    assert ctrl.tick(now=1.0) == []    # healthy: pending reset
    assert ctrl.payload()["hysteresis"]["pending"] == {}
    assert ctrl.tick(now=2.0) == []    # dead again: back to pending 1
    assert [a["action"] for a in ctrl.tick(now=3.0)] == ["failover"]


# ------------------------------------------------- cooldown / budget guards --


def test_cooldown_absorbs_oscillation_then_allows_refire(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0")])
    ctrl = _ctrl(monkeypatch, fleet, CTRL_COOLDOWN_S="10",
                 CTRL_HOST_POOL_MAX_PAGES="64")
    _script(monkeypatch, ctrl, _sensed("r0", limiter="hbm_pages"))
    alloc = fleet._by_id["r0"].engine._allocator

    ctrl.tick(now=0.0)
    acted = ctrl.tick(now=1.0)
    assert [a["action"] for a in acted] == ["grow_host_pool"]
    assert alloc.host_pool_pages == 6  # 4 * 1.5
    before = counter_value(CTRL_SUPPRESSED, guard="cooldown")
    for t in (2.0, 5.0, 10.9):  # inside now=1 + 10s cooldown
        assert ctrl.tick(now=t) == []
    assert counter_value(CTRL_SUPPRESSED, guard="cooldown") == before + 3
    assert alloc.host_pool_pages == 6
    # cooldown expired: a fresh hysteresis run is still required
    assert ctrl.tick(now=11.1) == []
    acted = ctrl.tick(now=12.1)
    assert [a["action"] for a in acted] == ["grow_host_pool"]
    assert alloc.host_pool_pages == 9  # 6 * 1.5


def test_budget_caps_actions_per_sliding_window(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0"), _FakeReplica("r1"),
                        _FakeReplica("r2", lifecycle="spare")])
    ctrl = _ctrl(monkeypatch, fleet, CTRL_MAX_ACTIONS="1",
                 CTRL_ACTION_WINDOW_S="100")
    both = {**_sensed("r0", thread_alive=False),
            **_sensed("r1", thread_alive=False)}
    _script(monkeypatch, ctrl, both)
    before = counter_value(CTRL_SUPPRESSED, guard="budget")

    ctrl.tick(now=0.0)
    acted = ctrl.tick(now=1.0)  # budget of 1: only the first decision fires
    assert [(a["replica"], a["action"]) for a in acted] == [("r0", "failover")]
    assert counter_value(CTRL_SUPPRESSED, guard="budget") == before + 1
    assert ctrl.payload()["budget"]["used"] == 1
    # past the window the budget refills and the starved decision fires
    acted = ctrl.tick(now=102.0)
    assert [(a["replica"], a["action"]) for a in acted] == [("r1", "failover")]


def test_inflight_failover_suppresses_stacked_actions(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0"),
                        _FakeReplica("r1", lifecycle="spare")])
    ctrl = _ctrl(monkeypatch, fleet)
    _script(monkeypatch, ctrl, _sensed("r0", thread_alive=False))
    blocker: concurrent.futures.Future = concurrent.futures.Future()
    ctrl._inflight["r0"] = blocker
    before = counter_value(CTRL_SUPPRESSED, guard="inflight")
    assert ctrl.tick(now=0.0) == []
    assert ctrl.tick(now=1.0) == []
    assert counter_value(CTRL_SUPPRESSED, guard="inflight") == before + 2
    assert fleet.calls == []
    blocker.set_result(None)  # the in-flight failover lands
    ctrl.tick(now=2.0)
    assert [a["action"] for a in ctrl.tick(now=3.0)] == ["failover"]


# ------------------------------------------------------------- the ladder --


def test_hbm_pages_grows_the_pool_until_capped(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0")])
    ctrl = _ctrl(monkeypatch, fleet, CTRL_HOST_POOL_MAX_PAGES="6",
                 CTRL_COOLDOWN_S="0")
    _script(monkeypatch, ctrl, _sensed("r0", limiter="hbm_pages"))
    alloc = fleet._by_id["r0"].engine._allocator

    ctrl.tick(now=0.0)
    acted = ctrl.tick(now=1.0)
    assert [a["action"] for a in acted] == ["grow_host_pool"]
    assert alloc.host_pool_pages == 6
    # at the cap nothing is left to try: no action, nothing logged
    logged = len(ctrl.payload()["log"])
    assert ctrl.tick(now=2.0) == [] and ctrl.tick(now=3.0) == []
    assert alloc.host_pool_pages == 6 and len(ctrl.payload()["log"]) == logged


def test_swap_wait_halves_affinity_slack_with_floor(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0")])
    ctrl = _ctrl(monkeypatch, fleet, CTRL_COOLDOWN_S="0")
    _script(monkeypatch, ctrl, _sensed("r0", limiter="swap_wait"))
    ctrl.tick(now=0.0)
    acted = ctrl.tick(now=1.0)
    assert [a["action"] for a in acted] == ["spread_affinity"]
    assert fleet.affinity_slack == 2.0
    for t in (2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0):
        ctrl.tick(now=t)
    assert fleet.affinity_slack == 0.5  # clamped, never degenerate


def test_breaker_open_and_critical_burn_both_mean_failover(monkeypatch):
    for sensed, reason in (
        (_sensed("r0", breaker="open"), "breaker_open"),
        (_sensed("r0", burn="critical"), "burn_critical"),
        (_sensed("r0", heartbeat_age_s=6.0), "wedged"),
    ):
        fleet = _FakeFleet([_FakeReplica("r0"),
                            _FakeReplica("r1", lifecycle="spare")])
        ctrl = _ctrl(monkeypatch, fleet)
        _script(monkeypatch, ctrl, sensed)
        ctrl.tick(now=0.0)
        acted = ctrl.tick(now=1.0)
        assert [(a["action"], a["reason"]) for a in acted] == [
            ("failover", reason)]
        assert ("fence", "r0") in fleet.calls


def test_failover_without_spare_still_fences_and_retires(monkeypatch):
    """A dead driver with no spare must still be fenced and retired — its
    in-flight callers get error frames, never a hang."""
    fleet = _FakeFleet([_FakeReplica("r0")])
    ctrl = _ctrl(monkeypatch, fleet)
    _script(monkeypatch, ctrl, _sensed("r0", thread_alive=False))
    ctrl.tick(now=0.0)
    acted = ctrl.tick(now=1.0)
    assert acted and acted[0]["action"] == "failover"
    assert fleet.calls == [("fence", "r0"), ("retire", "r0")]
    assert ctrl.payload()["log"][-1]["detail"]["no_spare"] is True


def test_failover_restores_snapshot_before_activating_spare(monkeypatch):
    order: list[str] = []
    fleet = _FakeFleet([_FakeReplica("r0"),
                        _FakeReplica("r1", lifecycle="spare")])

    async def activate(replica):
        order.append(f"activate-{replica}")
        return {"replica": replica, "lifecycle": "active"}

    monkeypatch.setattr(fleet, "activate", activate)
    ctrl = _ctrl(monkeypatch, fleet,
                 restore=lambda: order.append("restore") or {"replayed": 3})
    _script(monkeypatch, ctrl, _sensed("r0", thread_alive=False))
    ctrl.tick(now=0.0)
    ctrl.tick(now=1.0)
    fut = ctrl.inflight()["r0"]
    assert fut.done() and order == ["restore", "activate-r1"]
    assert fut.result()["restored"] == {"replayed": 3}


def test_restore_failure_downgrades_to_cold_activate(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0"),
                        _FakeReplica("r1", lifecycle="spare")])

    def broken_restore():
        raise RuntimeError("snapshot dir lost")

    ctrl = _ctrl(monkeypatch, fleet, restore=broken_restore)
    _script(monkeypatch, ctrl, _sensed("r0", thread_alive=False))
    ctrl.tick(now=0.0)
    ctrl.tick(now=1.0)
    out = ctrl.inflight()["r0"].result(timeout=5)
    assert out["restored"] == {"error": "snapshot dir lost"}
    # the spare still activated: degraded warm-up beats a down fleet
    assert ("activate", "r1") in fleet.calls


# ---------------------------------------------------------------- fail-open --


def test_sense_exception_fails_open_and_keeps_observing(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0"),
                        _FakeReplica("r1", lifecycle="spare")])
    ctrl = _ctrl(monkeypatch, fleet)
    boom = {"on": True}

    def sense(now):
        if boom["on"]:
            raise RuntimeError("plane exploded")
        return _sensed("r0", thread_alive=False)

    monkeypatch.setattr(ctrl, "_sense", sense)
    before = counter_value(CTRL_FAILOPEN)
    assert ctrl.tick(now=0.0) == []
    assert ctrl.tick(now=1.0) == []
    assert counter_value(CTRL_FAILOPEN) == before + 2
    assert ctrl.payload()["failopen"] == 2
    assert ctrl.payload()["log"][-1]["status"] == "failopen"
    # the loop recovers the moment sensing does
    boom["on"] = False
    ctrl.tick(now=2.0)
    assert [a["action"] for a in ctrl.tick(now=3.0)] == ["failover"]


def test_action_exception_fails_open_without_poisoning_others(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0"), _FakeReplica("r1")])
    ctrl = _ctrl(monkeypatch, fleet, CTRL_COOLDOWN_S="0")
    both = {**_sensed("r0", limiter="swap_wait"),
            **_sensed("r1", limiter="hbm_pages")}
    _script(monkeypatch, ctrl, both)
    monkeypatch.setattr(ctrl, "_act_spread_affinity",
                        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    before = counter_value(CTRL_FAILOPEN)
    ctrl.tick(now=0.0)
    acted = ctrl.tick(now=1.0)
    # r0's broken rung failed open; r1's grow still executed this tick
    assert [(a["replica"], a["action"]) for a in acted] == [
        ("r1", "grow_host_pool")]
    assert counter_value(CTRL_FAILOPEN) == before + 1
    assert fleet._by_id["r1"].engine._allocator.host_pool_pages == 6


def test_controller_act_fault_seam_drops_the_action(monkeypatch):
    from tests.test_chaos import _enable

    fleet = _FakeFleet([_FakeReplica("r0"),
                        _FakeReplica("r1", lifecycle="spare")])
    ctrl = _ctrl(monkeypatch, fleet)
    _enable(monkeypatch, "fleet.controller.act:drop")
    _script(monkeypatch, ctrl, _sensed("r0", thread_alive=False))
    ctrl.tick(now=0.0)
    assert ctrl.tick(now=1.0) == []  # cleared the guards, dropped at the seam
    assert fleet.calls == []
    entry = ctrl.payload()["log"][-1]
    assert entry["status"] == "dropped" and entry["action"] == "failover"


# -------------------------------------------------------------- publication --


def test_payload_reaches_debug_fleet_via_the_plane(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0"),
                        _FakeReplica("r1", lifecycle="spare")])
    ctrl = _ctrl(monkeypatch, fleet)
    _script(monkeypatch, ctrl, _sensed("r0", thread_alive=False))
    ctrl.tick(now=0.0)
    ctrl.tick(now=1.0)
    section = get_slo_plane().fleet_payload()["controller"]
    assert section["actions_total"] == 1 and section["ticks"] == 2
    assert section["log"][-1]["action"] == "failover"
    assert "r0:failover" in section["cooldowns"]
    assert section["budget"]["max_actions"] == 4


async def test_start_stop_runs_the_reconcile_thread(monkeypatch):
    fleet = _FakeFleet([_FakeReplica("r0")])
    for key, value in (("CTRL_TICK_S", "0.01"),):
        monkeypatch.setenv(key, value)
    reload_settings()
    ctrl = FleetController(fleet)  # real clock: thread smoke test
    r0 = fleet._by_id["r0"]
    r0.heartbeat, r0.alive = __import__("time").monotonic(), True
    await ctrl.start()
    try:
        for _ in range(200):
            if ctrl.payload()["ticks"] >= 3:
                break
            await asyncio.sleep(0.01)
        assert ctrl.payload()["ticks"] >= 3
        assert ctrl.payload()["running"] is True
    finally:
        ctrl.stop()
    ticks = ctrl.payload()["ticks"]
    await asyncio.sleep(0.05)
    assert ctrl.payload()["ticks"] == ticks  # genuinely stopped
    assert fleet.calls == []  # a healthy fleet gets no actions
