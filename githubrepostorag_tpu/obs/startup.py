"""The start-up record: where the seconds from process start to ready go.

One process-wide list of named phases with ``time.monotonic()`` start and
end, written by the functions that do the work (never by their callers), so
the pods (``serving/__main__``, ``api/__main__``) and the benchmark's
families fill the same record; a reader needs no handle, as with
``obs.continuous.profilers()``.  Its zero is the process's start as the OS
gives it (``PROCESS_START``, on the same monotonic clock), so the phases can
be laid against seconds-to-ready.  Phases and their writers:

    startup.tokenizer      serving/tokenizer.make_tokenizer
    startup.weights        models/quant.init_params_quantized,
                           models/{deepseek_v3,qwen3_next}.init_params,
                           models/hf_loader.load_qwen2
    startup.engine_init    serving/engine.Engine.__init__ (pools, allocator,
                           state slots)
    startup.warmup         serving/engine.Engine.warmup (the pod's ladder)
    startup.encoder        embedding.JaxBertTextEncoder.from_pretrained
                           and .warmup
    startup.ingest.<stage> ingest/controller.stage_timer
    startup.index_build    retrieval/device_index: a table's first upload
    startup.serve          serving/async_engine.AsyncEngine: construction
                           to ``mark_warm()``

No phase adds a synchronisation.  Set-up overlaps host tracing with device
work (weights are made by dispatched programs), so a phase that ends in
asynchronous work hands its last array to ``settles``: the phase is stamped
``dispatched`` when its function returns and closed at the first later
stamp of the record or the compile ledger that finds the array ready (a
non-blocking ``is_ready()``), at the latest by ``mark_warm()``.  After
``mark_warm()`` the record is closed: a phase is stamped and dropped.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Iterator

from githubrepostorag_tpu.utils.profiling import annotate


def _process_start() -> float:
    """The process's start on ``time.monotonic()``'s clock: field 22 of
    ``/proc/self/stat`` counts ticks from boot on the clock that does not
    stop in a suspend; the two clocks' difference now takes that out.  This
    module's import where the OS does not say."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        boot_now = time.clock_gettime(time.CLOCK_BOOTTIME)
        start = ticks / os.sysconf("SC_CLK_TCK") - (boot_now - now)
        if 0.0 <= now - start < 86400.0:
            return start
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return now


PROCESS_START = _process_start()


class Phase:
    """One named interval.  ``end`` stays None while the phase is open or
    its device work is still in flight (then ``dispatched`` is set)."""

    __slots__ = ("name", "start", "dispatched", "end", "_pending")

    def __init__(self, name: str, start: float) -> None:
        self.name, self.start = name, start
        self.dispatched: float | None = None
        self.end: float | None = None
        self._pending: Any = None

    def settles(self, arrays: Any) -> None:
        """The phase ends when ``arrays`` (what it dispatched: an array, or a
        tree or list of them) are ready on the device, not when its function
        returns."""
        leaves = [arrays] if hasattr(arrays, "is_ready") else _arrays_of(arrays)
        self._pending = leaves or None

    def ready(self) -> bool:
        """Non-blocking: has everything ``settles`` was given finished?"""
        pending = self._pending or []
        while pending:
            try:
                if not pending[-1].is_ready():
                    return False
            except Exception:  # noqa: BLE001 - a deleted (donated) array has finished
                pass
            pending.pop()
        self._pending = None
        return True

    def as_dict(self) -> dict:
        return {"name": self.name, "start": self.start, "dispatched": self.dispatched,
                "end": self.end}


def _arrays_of(tree: Any) -> list:
    try:
        import jax

        return [x for x in jax.tree.leaves(tree) if hasattr(x, "is_ready")]
    except Exception:  # noqa: BLE001 - no jax, no device work to wait for
        return []


class StartupRecord:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.phases: list[Phase] = []
        self.warm_t: float | None = None
        self.notes: dict[str, Any] = {}  # sizes a phase settled on, by name

    def note(self, name: str, value: Any) -> None:
        """A fact of the start-up beside its seconds (the bytes each cache pool
        holds, written by ``startup.engine_init``); the last one of a name stands."""
        with self._lock:
            self.notes[name] = value

    def begin(self, name: str) -> Phase:
        """Open a phase that no ``with`` block spans (``finish`` closes it).
        Once the record is closed the phase is handed out and not kept."""
        now = time.monotonic()
        ph = Phase(name, now)
        if self.warm_t is None:
            self.poll(now)
            with self._lock:
                self.phases.append(ph)
        return ph

    def finish(self, ph: Phase) -> None:
        if ph.end is not None or ph.dispatched is not None:
            return
        now = time.monotonic()
        if ph._pending is None:
            ph.end = now
        else:
            ph.dispatched = now
        self.poll(now)

    @contextmanager
    def phase(self, name: str) -> Iterator[Phase]:
        ph = self.begin(name)
        try:
            with annotate(name):  # for a start-up traced by hand
                yield ph
        finally:
            self.finish(ph)

    def poll(self, now: float | None = None) -> None:
        """Close every phase whose device work has finished.  Called where a
        stamp is taken anyway (a phase's edges, the compile ledger's
        events): never blocks."""
        if not self._lock.acquire(blocking=False):
            return  # another thread is looking
        try:
            for ph in self.phases:
                if ph.dispatched is not None and ph.end is None and ph.ready():
                    ph.end = time.monotonic() if now is None else now
        finally:
            self._lock.release()

    def mark_warm(self, now: float | None = None) -> bool:
        """Close the record; True for the call that did (the first)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if self.warm_t is not None:
                return False
            self.warm_t = now
        for ph in self.phases:
            if ph.end is None and ph.dispatched is not None:
                ph.end, ph._pending = now, None  # ready has been served from: it has finished
        return True

    def seconds_by_phase(self) -> dict[str, float]:
        """Closed phases' seconds, summed by name."""
        out: dict[str, float] = {}
        for ph in list(self.phases):
            if ph.end is not None:
                out[ph.name] = out.get(ph.name, 0.0) + (ph.end - ph.start)
        return out

    def snapshot(self) -> dict:
        return {"process_start": PROCESS_START, "warm_t": self.warm_t,
                "phases": [ph.as_dict() for ph in list(self.phases)], "notes": dict(self.notes)}


_record = StartupRecord()


def startup_record() -> StartupRecord:
    return _record


def phase(name: str):
    """``with startup.phase("startup.weights") as ph: ...; ph.settles(leaf)``"""
    return _record.phase(name)


def records(name: str, settle: bool = False) -> Callable:
    """Decorator: a call of the function is the phase ``name``; with
    ``settle`` it ends when the arrays of its result are ready."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kw):
            with _record.phase(name) as ph:
                out = fn(*args, **kw)
                if settle:
                    ph.settles(out)
                return out
        return inner
    return wrap


def reset_startup_record() -> StartupRecord:
    """A fresh record (tests)."""
    global _record
    _record = StartupRecord()
    return _record
