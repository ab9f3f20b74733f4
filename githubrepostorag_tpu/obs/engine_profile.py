"""Serving-engine step instrumentation.

Three concerns, all driven from the engine driver thread
(``AsyncEngine._drive``) so the event loop never pays for them:

* **Per-request phase attribution** — the serving path stamps monotonic
  timestamps as a request moves received -> enqueued -> waiting ->
  prefilling -> first token -> first emit -> done
  (``GenerationResult.timings``); ``record_engine_spans`` turns those
  into retroactive spans under the request's trace.  Time to first token
  is the six in a row ``server.tokenize``, ``server.submit_wait``,
  ``engine.queue_wait``, ``engine.prefill_dispatch``,
  ``engine.first_token_lag``, ``server.emit_lag``; ``engine.prefill``
  (admission to first token) and ``engine.decode`` are as before, so a
  flight-recorder dump shows exactly where a slow TTFT went.

* **Scheduler-stall gauge + TPOT histogram** — the gap between
  consecutive steps while work exists is scheduler stall (vLLM's
  throughput killer per PAPERS.md, invisible in aggregate latency
  histograms); TPOT is decode seconds per generated token after the
  first.

* **The compile ledger** — one process-wide listener on the events JAX
  itself announces for every trace, lowering and back-end compile
  (``jax.monitoring``: ``jaxpr_trace_duration``,
  ``jaxpr_to_mlir_module_duration``, ``backend_compile_duration``, each
  with its seconds and ``fun_name``; the compilation cache's
  ``cache_hits``).  ``CompileLedger`` keeps, per function: programs that
  went through the back end, seconds of tracing, lowering and back-end
  compile (on a cache hit that is the read), cache hits; and every event
  with its ``time.monotonic()`` stamp, the clock every other stamp in
  ``obs/`` uses.  ``mark_warm()`` draws the line: before it is start-up
  (``obs/startup.py`` has the phases; one log line, ``rag_startup_seconds``
  and ``rag_xla_compile_seconds_total{when}`` tell an operator where the
  seconds to ready went), after it is live traffic.  A back-end compile
  after the line, cache hit or not, of one of the engine's STEP PROGRAMS
  (the callables ``Engine.step_programs()`` hands over at construction,
  whatever module defines them) means live traffic just stalled for a
  program the warm-up failed to predict: ``rag_xla_compiles_total``
  increments, ``live_compiles`` rises, every registered in-flight span
  gets an ``xla_compile`` event and the warning names the function and
  its seconds.  Every other event after the line (an eager scatter of a
  new row count, an encoder batch of a new shape) is kept under
  ``when="live"`` and raises nothing.  ``CompileWatchdog`` reads the
  ledger as deltas: no list of modules, no ``_cache_size()`` on a step.
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import TYPE_CHECKING, Any, Iterable, NamedTuple

from githubrepostorag_tpu.metrics import (
    SCHED_STALL,
    STARTUP_SECONDS,
    XLA_COMPILE_SECONDS,
    XLA_COMPILES,
)
from githubrepostorag_tpu.obs.startup import PROCESS_START, startup_record
from githubrepostorag_tpu.obs.trace import TraceContext, record_span
from githubrepostorag_tpu.utils.logging import get_logger

if TYPE_CHECKING:  # pragma: no cover - typing only
    from githubrepostorag_tpu.obs.trace import Span

logger = get_logger(__name__)

TRACE, LOWER, COMPILE = "trace", "lower", "compile"
_KINDS = {"/jax/core/compile/jaxpr_trace_duration": TRACE,
          "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
          "/jax/core/compile/backend_compile_duration": COMPILE}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_COLUMN = {TRACE: 2, LOWER: 3, COMPILE: 4}  # of a by_fun / totals row
_WRAPPED = re.compile(r"^\w+\((.*)\)$")  # lowering and compile say "jit(name)", tracing "name"
TAIL = 4096  # events kept for readers of what arrived lately
STARTUP_EVENTS = 1 << 17  # ... and of a start-up (a job that never serves never draws the line)


class CompileEvent(NamedTuple):
    t: float        # time.monotonic() when the event arrived: its end
    seconds: float  # its own: what nested traces took is theirs
    kind: str       # TRACE | LOWER | COMPILE
    fun: str
    hit: bool       # COMPILE: the program was read from the persistent cache
    step: bool      # the function is one of the engines' step programs
    wall: float     # seconds from its start to ``t``, nested work included


def program_name(fn: Any) -> str:
    """The name JAX's events give a jitted callable."""
    return getattr(fn, "__name__", None) or repr(fn)


class CompileLedger:
    """What JAX traced, lowered and compiled in this process, by function and
    by when.  Written by the listener on whichever thread compiles; read from
    any thread."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()  # a cache hit waiting for its compile event; open traces
        self.seq = 0  # events so far
        self.tail: deque[CompileEvent] = deque(maxlen=TAIL)
        self.startup_events: list[CompileEvent] = []  # every event before mark_warm()
        self.warm_t: float | None = None
        self.step_programs: set[str] = set()
        self.step_compiles = 0  # back-end compiles of step programs, ever
        # fun -> [programs, cache hits, trace s, lower s, compile s]
        self.by_fun: dict[str, list] = {}
        # when -> the same five over every function
        self.totals = {"startup": [0, 0, 0.0, 0.0, 0.0], "live": [0, 0, 0.0, 0.0, 0.0]}

    def watch(self, programs: Iterable[Any]) -> None:
        """``programs``: the jitted callables an engine dispatches from."""
        names = {program_name(p) for p in programs}
        with self._lock:
            self.step_programs |= names

    # ---------------------------------------------------------- listener --

    def on_cache_hit(self) -> None:
        self._local.hit = True  # inside the compile event that follows on this thread

    def on_duration(self, kind: str, fun_name: str, seconds: float) -> None:
        now = time.monotonic()
        local = self._local
        own, hit = seconds, False
        if kind == TRACE:
            # an inner jit is traced inside its caller's trace and announced
            # before it: what this event covers of earlier ones is theirs
            opened = local.__dict__.setdefault("traces", [])
            start = now - seconds
            while opened and opened[-1][0] >= start:
                own -= opened.pop()[1]
            opened.append((now, seconds))
            own = max(0.0, own)
        else:
            local.traces = []  # a program's tracing is over once it is lowered
            if kind == COMPILE:
                hit, local.hit = getattr(local, "hit", False), False
        m = _WRAPPED.match(fun_name)
        fun = m.group(1) if m else fun_name
        col = _COLUMN[kind]
        with self._lock:
            when = "startup" if self.warm_t is None else "live"
            ev = CompileEvent(now, own, kind, fun, hit, fun in self.step_programs, seconds)
            self.seq += 1
            self.tail.append(ev)
            if when == "startup" and len(self.startup_events) < STARTUP_EVENTS:
                self.startup_events.append(ev)
            for row in (self.by_fun.setdefault(fun, [0, 0, 0.0, 0.0, 0.0]), self.totals[when]):
                row[col] += own
                if kind == COMPILE:
                    row[0] += 1
                    row[1] += hit
            if kind == COMPILE and ev.step:
                self.step_compiles += 1
        XLA_COMPILE_SECONDS.labels(when=when).inc(own)
        if when == "startup":
            startup_record().poll(now)

    # ----------------------------------------------------------- readers --

    def since(self, seq: int) -> tuple[int, list[CompileEvent]]:
        """(events so far, the events after the first ``seq`` of them that
        the tail still holds)."""
        with self._lock:
            n = min(self.seq - seq, len(self.tail))
            return self.seq, (list(self.tail)[-n:] if n > 0 else [])

    def programs_of(self, names: Iterable[str]) -> int:
        with self._lock:
            return sum(self.by_fun[n][0] for n in names if n in self.by_fun)

    def snapshot(self) -> dict:
        """Plain data for a reader: the start-up's events whole, then the
        tail of what came after the line."""
        with self._lock:
            events = self.startup_events + [e for e in self.tail
                                            if self.warm_t is not None and e.t >= self.warm_t]
            return {"warm_t": self.warm_t, "events": [list(e) for e in events]}

    def mark_warm(self) -> None:
        """Draw the line between start-up and live traffic (the first call
        does; a relaunch's is a no-op) and tell the operator once where the
        seconds to ready went."""
        now = time.monotonic()
        with self._lock:
            if self.warm_t is not None:
                return
            self.warm_t = now
            programs, hits, trace_s, lower_s, compile_s = self.totals["startup"]
            traced = sum(1 for e in self.startup_events if e.kind == TRACE)
            slowest = sorted(((sum(row[2:]), fun) for fun, row in self.by_fun.items()),
                             reverse=True)[:5]
        record = startup_record()
        record.mark_warm(now)
        phases = record.seconds_by_phase()
        STARTUP_SECONDS.labels(phase="total").set(now - PROCESS_START)
        for name, seconds in phases.items():
            STARTUP_SECONDS.labels(phase=name).set(seconds)
        logger.info(
            "ready %.1f s after process start; phases %s; jax traced %d function(s) %.1f s, "
            "lowered %.1f s, back end %d program(s) %.1f s (%d read from the cache); slowest %s; "
            "cache pools hold %s bytes",
            now - PROCESS_START, {k: round(v, 2) for k, v in phases.items()}, traced, trace_s,
            lower_s, programs, compile_s, hits,
            ", ".join(f"{fun} {s:.1f} s" for s, fun in slowest), record.notes.get("pool_bytes"))


_ledger = CompileLedger()


def _on_duration(event: str, seconds: float, fun_name: str = "", **_kw) -> None:
    kind = _KINDS.get(event)
    if kind is not None:
        _ledger.on_duration(kind, fun_name, seconds)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _ledger.on_cache_hit()


try:  # once, at the first import: set-up compiles before any engine exists
    from jax import monitoring as _monitoring

    _monitoring.register_event_duration_secs_listener(_on_duration)
    _monitoring.register_event_listener(_on_event)
except Exception:  # noqa: BLE001 - a build without jax compiles nothing
    pass


def compile_ledger() -> CompileLedger:
    """The process's ledger (whatever module imports this one first has
    registered its listener)."""
    return _ledger


def reset_compile_ledger() -> CompileLedger:
    """A fresh ledger behind the same listener (tests)."""
    global _ledger
    _ledger = CompileLedger()
    return _ledger


class CompileWatchdog:
    """The ledger's back-end compiles read as deltas between samples: of the
    engines' step programs, or of ``programs`` (jitted callables) alone."""

    def __init__(self, programs: Iterable[Any] | None = None) -> None:
        self._names = None if programs is None else {program_name(p) for p in programs}
        # resync() runs on the event loop (serve start / mark_warm) while
        # sample() runs on the driver thread every step; the cursor needs a
        # lock or a resync racing a sample books warm-up's compiles to live
        # traffic
        self._lock = threading.Lock()
        self.grown: list[str] = []  # "function 1.234 s" of the last sample's compiles
        self._seq = compile_ledger().seq

    def _mine(self, ev: CompileEvent) -> bool:
        return ev.kind == COMPILE and (ev.step if self._names is None else ev.fun in self._names)

    def cache_size(self) -> int:
        """Programs of the watched functions that went through the back end
        so far, cache hits included."""
        ledger = compile_ledger()
        return ledger.step_compiles if self._names is None else ledger.programs_of(self._names)

    def resync(self) -> None:
        """Rebaseline — called at serve start so warmup's own compiles
        (expected, pre-traffic) never count as live-traffic compiles."""
        with self._lock:
            self._seq = compile_ledger().seq

    def sample(self) -> int:
        """Watched programs compiled since the previous sample (>= 0);
        ``grown`` names them with their seconds.  No new event: one int
        compared."""
        ledger = compile_ledger()
        with self._lock:
            if ledger.seq == self._seq:
                self.grown = []
                return 0
            self._seq, events = ledger.since(self._seq)
            mine = [ev for ev in events if self._mine(ev)]
            self.grown = [f"{ev.fun} {ev.seconds:.3f} s" + (" (read from the cache)" if ev.hit
                                                           else "") for ev in mine]
        return len(mine)


class EngineStepProfiler:
    """Per-step hook owned by ``AsyncEngine``.  ``on_step`` runs once per
    engine step on the driver thread; in-flight request spans register so
    compile events land on the request that was stalled by them."""

    def __init__(self, watchdog: CompileWatchdog | None = None,
                 replica: str = "r0") -> None:
        self.watchdog = watchdog or CompileWatchdog()
        self.replica = replica
        self._lock = threading.Lock()
        self._live: dict[int, "Span"] = {}
        self._last_step_end: float | None = None
        self.live_compiles = 0  # step programs through the back end under live traffic

    # ----------------------------------------------------- live requests --

    def register(self, span: "Span") -> None:
        with self._lock:
            self._live[id(span)] = span

    def unregister(self, span: "Span") -> None:
        with self._lock:
            self._live.pop(id(span), None)

    def mark_warm(self) -> None:
        """Declare warmup finished: compiles observed after this are
        live-traffic compiles."""
        self.watchdog.resync()
        compile_ledger().mark_warm()
        with self._lock:
            self._last_step_end = None

    # ------------------------------------------------------------- steps --

    def on_step(self, step_start: float, step_end: float) -> int:
        """Record stall + compile telemetry for one completed engine step.
        Returns the number of fresh compiles observed (for tests)."""
        with self._lock:
            prev = self._last_step_end
            self._last_step_end = step_end
        if prev is not None:
            SCHED_STALL.labels(replica=self.replica).set(max(0.0, step_start - prev))

        delta = self.watchdog.sample()
        if delta > 0:
            self.live_compiles += delta  # driver thread only; GIL-atomic read
            XLA_COMPILES.labels(replica=self.replica).inc(delta)
            grown = ", ".join(self.watchdog.grown)
            with self._lock:
                live = list(self._live.values())
            for sp in live:
                sp.add_event("xla_compile", new_programs=delta, programs=grown,
                             step_s=round(step_end - step_start, 6))
            logger.warning(
                "xla compile during live traffic: %d new program(s) in a %.3fs step: %s "
                "(warmup should have predicted this shape)",
                delta, step_end - step_start, grown,
            )
        return delta

    def idle(self) -> None:
        """The driver found no work — the next gap is idleness, not stall."""
        with self._lock:
            self._last_step_end = None
        SCHED_STALL.labels(replica=self.replica).set(0.0)


# time to first token, received to emitted, as (span, from stamp, to stamp):
# consecutive, so the six sum to first_emit_t - recv_t exactly
TTFT_PARTS = (
    ("server.tokenize", "recv_t", "enqueue_t"),
    ("server.submit_wait", "enqueue_t", "submit_t"),
    ("engine.queue_wait", "submit_t", "prefill_start_t"),
    ("engine.prefill_dispatch", "prefill_start_t", "prefill_end_t"),
    ("engine.first_token_lag", "prefill_end_t", "first_token_t"),
    ("server.emit_lag", "first_token_t", "first_emit_t"),
)


# counts in the same record that ride on the ``engine.decode`` span
DECODE_COUNTS = ("decode_cycles", "decode_wave_cycles", "decode_wave_tokens")


def record_engine_spans(result: Any, parent: TraceContext | None) -> None:
    """Turn a ``GenerationResult``'s monotonic phase stamps into spans
    under ``parent``: the six parts of time to first token, and prefill /
    decode.  Tolerates partial timings (errored or reaped requests may
    never prefill; a caller that left before the first token never saw
    one emitted)."""
    timings = getattr(result, "timings", None)
    if not timings or parent is None or not parent.sampled:
        return
    pstart = timings.get("prefill_start_t")
    ftok = timings.get("first_token_t")
    done = timings.get("done_t", time.monotonic())
    attrs = {"request_id": getattr(result, "request_id", "")}
    for name, a, b in TTFT_PARTS:
        t0, t1 = timings.get(a), timings.get(b)
        # (a resumed request's second admission lies after its first token)
        if t0 is not None and t1 is not None and t1 >= t0:
            record_span(name, t0, t1, parent=parent, attrs=attrs)
    if pstart is not None and ftok is not None:
        psp = record_span("engine.prefill", pstart, ftok, parent=parent, attrs={
            **attrs, "prompt_tokens": len(getattr(result, "prompt_tokens", ()) or ()),
        })
        if psp is not None:
            # KV tiering: prefix pages this admission swapped in from the
            # host tier instead of recomputing — the flight recorder shows
            # the swap right on the request's prefill timeline
            faulted = getattr(result, "faulted_pages", 0)
            if faulted:
                psp.add_event("kv_fault_in", pages=faulted)
    if ftok is not None and done > ftok:
        # the request's decode by burst landing (Engine._cycle_landed): how many
        # brought it tokens, and how many of those cycles carried a prefill wave
        cycles = {k: timings[k] for k in DECODE_COUNTS if k in timings}
        record_span("engine.decode", ftok, done, parent=parent, attrs={
            **attrs, "output_tokens": len(getattr(result, "output_tokens", ()) or ()),
            "finish_reason": getattr(result, "finish_reason", ""), **cycles,
        })
