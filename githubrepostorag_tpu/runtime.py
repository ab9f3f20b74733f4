"""Where this process runs: the one place that decides "am I on the chip",
where compiled programs are cached, and what the chip's peak is.

Two ways the program runs: on an attached TPU (servers, ingest, bench,
chip_smoke.py) and on the CPU backend for tests and rehearsals, where the
process says so itself with ``JAX_PLATFORMS=cpu``.  A CPU backend reached
any other way means JAX looked for a chip, found none and fell back with a
warning; every caller here treats that as an error, so the Pallas kernels
never drop to interpret mode (and the device index, int4 kernel and Pallas
attention never switch off) because a chip went missing.
"""

from __future__ import annotations

import os
from pathlib import Path

# bf16 peak FLOP/s of one chip by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip).
CHIP_PEAK_FLOPS: dict[str, float] = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
}


def _pinned_to_cpu() -> bool:
    """Whether this process asked for the CPU backend (JAX_PLATFORMS=cpu or
    the equivalent jax.config update).  Reading the config value does not
    initialize a backend."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def on_tpu() -> bool:
    """True on the TPU backend; False only in a process explicitly pinned to
    the CPU backend.  Raises when JAX ended up anywhere else."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu" and _pinned_to_cpu():
        return False
    raise RuntimeError(
        f"JAX is on the {backend!r} backend but this process was not pinned "
        f"to it (JAX_PLATFORMS={jax.config.jax_platforms!r}): no TPU was "
        "found.  Refusing the silent CPU fallback — attach a chip, or set "
        "JAX_PLATFORMS=cpu for tests and rehearsals."
    )


def device_facts() -> dict:
    """Which device this process computes on, as JAX reports it, plus the
    peak bytes it has held (None where the backend keeps no such count, as
    the CPU backend does).  Servers put this on /health so a caller can see
    the backend without reading logs."""
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "hbm_peak_bytes": stats.get("peak_bytes_in_use"),
    }


def chip_peak_flops() -> float | None:
    """Peak bf16 FLOP/s of the first device, or None for a device kind the
    table does not know (MFU is then reported as null, never guessed)."""
    import jax

    return CHIP_PEAK_FLOPS.get(jax.devices()[0].device_kind)


def compile_cache_dir() -> str:
    """The one rule for where compiled programs are kept: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``.  The
    path is part of the cache key, so it is never a temporary name.  Needs
    no JAX (chip_smoke.py's parent reads it too)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parent.parent / ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache at ``compile_cache_dir()``;
    every entry point calls this before it compiles.  Where the environment
    variable is set JAX already honours it and no directory is set in code.
    Returns the directory.

    Off (None) in a process pinned to the CPU backend: those are the tests
    and rehearsals, whose ahead-of-time compiles for a described TPU write
    entries that cannot be read back without a chip."""
    import jax

    if _pinned_to_cpu():
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return compile_cache_dir()
