"""Test harness: force JAX onto a virtual 8-device CPU mesh before any jax
import so sharding tests (pjit/shard_map over a Mesh) run without TPUs, and
give every test a clean in-process bus/store.
"""

import os
import sys

# Tests always run on the CPU backend with eight virtual devices (kernels
# in Pallas interpret mode); the chip is for chip_smoke.py and the bench.
# Both env vars must be set before jax initializes its backends.
_TPU_TESTS = os.environ.get("TPU_TESTS") == "1"  # tests/test_e2e_tpu.py on the chip

if not _TPU_TESTS:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

if not _TPU_TESTS:
    assert jax.devices()[0].platform == "cpu", "tests must run on the CPU backend"
    assert jax.device_count() == 8, "tests expect the virtual 8-device CPU mesh"

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (no pytest-asyncio in image)."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(func(**kwargs))
        return True
    return None


@pytest.fixture(autouse=True)
def _fresh_state():
    """Reset process-wide singletons (bus hub, store, settings) per test."""
    from githubrepostorag_tpu.config import reload_settings
    from githubrepostorag_tpu.events.memory import reset_memory_hub
    from githubrepostorag_tpu.obs.continuous import reset_profilers
    from githubrepostorag_tpu.obs.hbm import reset_hbm_plane
    from githubrepostorag_tpu.obs.slo import reset_slo_plane
    from githubrepostorag_tpu.obs.timeline import reset_fleet_events_provider
    from githubrepostorag_tpu.resilience.faults import reset_faults
    from githubrepostorag_tpu.resilience.policy import reset_breakers
    from githubrepostorag_tpu.store.factory import reset_store

    def _reset_obs():
        reset_profilers()
        reset_hbm_plane()
        reset_fleet_events_provider()

    reload_settings()
    reset_memory_hub()
    reset_store()
    reset_faults()
    reset_breakers()
    reset_slo_plane()
    _reset_obs()
    yield
    reset_memory_hub()
    reset_store()
    reset_faults()
    reset_breakers()
    reset_slo_plane()
    _reset_obs()
