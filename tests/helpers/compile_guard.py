"""The one way tests assert the zero-live-recompile contract.

Every compile-discipline test in the suite used to hand-roll the same
before/after dance around some program-cache counter (a jitted fn's
``_cache_size``, a store's ``search_program_cache_size``, a
``CompileWatchdog``).  ``compile_guard`` is that dance as a context
manager, so the assertion text, the off-by-warmup bugs, and the counter
plumbing live in exactly one place:

    with compile_guard(forward._cache_size, expect=len(buckets), label="warmup"):
        eng.warmup()
    with compile_guard(forward._cache_size):   # expect=0: live traffic
        eng.generate(prompts, sp)

``counter`` is any zero-arg callable returning the current cumulative
program count.  For engine-wide checks, ``watchdog_counter()`` wraps a
``CompileWatchdog`` over the compile ledger: every back-end compile of a
function some engine of this process named as its step program.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Iterator


class _Guard:
    """Records the counter delta over the guarded block (``.delta``)."""

    def __init__(self) -> None:
        self.before = 0
        self.after = 0
        self.delta = 0


@contextlib.contextmanager
def compile_guard(
    counter: Callable[[], int],
    *,
    expect: int | None = 0,
    label: str = "guarded block",
) -> Iterator[_Guard]:
    """Assert exactly ``expect`` XLA programs compile inside the block.

    ``expect=0`` (the default) is the zero-live-recompile contract:
    traffic after warmup must hit only precompiled shapes.  ``expect=N``
    pins a warmup to its exact bucket-ladder size.  ``expect=None`` only
    records the delta (read it off the yielded guard) without asserting.
    """
    g = _Guard()
    g.before = int(counter())
    yield g
    g.after = int(counter())
    g.delta = g.after - g.before
    if expect is not None:
        assert g.delta == expect, (
            f"{label}: compiled {g.delta} new XLA program(s), expected "
            f"{expect} (cache {g.before} -> {g.after}) — a shape escaped "
            f"the bucket ladder"
        )


def watchdog_counter() -> Callable[[], int]:
    """Engine-wide counter: step programs that went through the back end so
    far, as the compile ledger counts them (what the serving watchdog reads)."""
    from githubrepostorag_tpu.obs.engine_profile import CompileWatchdog

    return CompileWatchdog().cache_size
