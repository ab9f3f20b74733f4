"""Count which of the engine's step paths a run dispatches.

``Engine.step`` looks its paths up on the instance, so a counting wrapper
set as an instance attribute sees every dispatch (the benchmark's probe
wraps ``_decode_step`` and ``_prefill_batch`` the same way)."""

from __future__ import annotations

from typing import Callable

DECODE_PATHS = ("_decode_step",)
PREFILL_PATHS = ("_prefill_batch", "_prefill_batch_packed", "_sp_prefill_packed")


def count_step_paths(eng, before: Callable[[str], None] | None = None) -> dict:
    """Wrap every step path of ``eng``; the returned dict holds the calls of
    each by name.  ``before(name)`` runs ahead of each dispatch."""
    calls = dict.fromkeys(DECODE_PATHS + PREFILL_PATHS, 0)

    def counted(name, bound):
        def call(*args, **kw):
            if before is not None:
                before(name)
            calls[name] += 1
            return bound(*args, **kw)
        return call

    for name in calls:
        setattr(eng, name, counted(name, getattr(eng, name)))
    return calls
