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

* **XLA compile watchdog** — sums ``_cache_size()`` over every jitted
  callable in the serving/model modules each step.  A positive delta
  while serving means live traffic just paid an XLA compile the warmup
  ladder failed to predict: ``rag_xla_compiles_total`` increments and
  every registered in-flight span gets an ``xla_compile`` event, so the
  one request that stalled for the length of a TPU compile says so in
  its own timeline.
"""

from __future__ import annotations

import importlib
import threading
import time
from typing import TYPE_CHECKING, Any, Iterable

from githubrepostorag_tpu.obs.trace import TraceContext, record_span
from githubrepostorag_tpu.utils.logging import get_logger

if TYPE_CHECKING:  # pragma: no cover - typing only
    from githubrepostorag_tpu.obs.trace import Span

logger = get_logger(__name__)

# every module that defines top-level jit objects the engine dispatches;
# importing lazily and tolerantly — a module missing its accelerator dep
# simply contributes no jits
DEFAULT_JIT_MODULES = (
    "githubrepostorag_tpu.serving.engine",
    "githubrepostorag_tpu.serving.decode_burst",
    "githubrepostorag_tpu.serving.spec_burst",
    "githubrepostorag_tpu.serving.fused_step",
    "githubrepostorag_tpu.serving.draft_spec",
    "githubrepostorag_tpu.serving.long_prefill",
    "githubrepostorag_tpu.models.qwen2",
    "githubrepostorag_tpu.models.deepseek_v3",
    "githubrepostorag_tpu.ops.sampling",
    "githubrepostorag_tpu.ops.packed_prefill",
    "githubrepostorag_tpu.ops.fused_decode",
    "githubrepostorag_tpu.ops.page_migration",
)


def discover_jits(module_names: Iterable[str] = DEFAULT_JIT_MODULES) -> list[tuple[str, Any]]:
    """Find every module-level object exposing jit's ``_cache_size`` in the
    serving/model modules — the complete set of programs live traffic can
    trigger a compile through."""
    jits: list[tuple[str, Any]] = []
    for name in module_names:
        try:
            mod = importlib.import_module(name)
        except Exception:  # noqa: BLE001 - optional accelerator deps
            continue
        for attr, obj in vars(mod).items():
            if callable(getattr(obj, "_cache_size", None)):
                jits.append((f"{name}.{attr}", obj))
    return jits


class CompileWatchdog:
    """Tracks the total jit program count and reports fresh compiles as
    deltas between samples."""

    def __init__(self, jits: list[tuple[str, Any]] | None = None) -> None:
        self._jits = discover_jits() if jits is None else list(jits)
        # resync() runs on the event loop (serve start / mark_warm) while
        # sample() runs on the driver thread every step; _last needs a lock
        # or a resync racing a sample mis-attributes warmup compiles to
        # live traffic
        self._lock = threading.Lock()
        self.grown: list[str] = []
        self._last = self.sizes()

    def sizes(self) -> dict[str, int]:
        out = {}
        for name, obj in self._jits:
            try:
                out[name] = int(obj._cache_size())
            except Exception:  # noqa: BLE001 - a torn-down jit reads as 0
                out[name] = 0
        return out

    def cache_size(self) -> int:
        return sum(self.sizes().values())

    def resync(self) -> None:
        """Rebaseline — called at serve start so warmup's own compiles
        (expected, pre-traffic) never count as live-traffic compiles."""
        sizes = self.sizes()
        with self._lock:
            self._last = sizes

    def sample(self) -> int:
        """New programs compiled since the previous sample (>= 0); ``grown``
        names the jits that gained one."""
        sizes = self.sizes()
        with self._lock:
            delta = sum(sizes.values()) - sum(self._last.values())
            self.grown = [n for n, v in sizes.items() if v > self._last.get(n, 0)]
            self._last = sizes
        return max(0, delta)


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
        self.live_compiles = 0  # programs compiled under live traffic

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
        with self._lock:
            self._last_step_end = None

    # ------------------------------------------------------------- steps --

    def on_step(self, step_start: float, step_end: float) -> int:
        """Record stall + compile telemetry for one completed engine step.
        Returns the number of fresh compiles observed (for tests)."""
        from githubrepostorag_tpu.metrics import SCHED_STALL, XLA_COMPILES

        with self._lock:
            prev = self._last_step_end
            self._last_step_end = step_end
        if prev is not None:
            SCHED_STALL.labels(replica=self.replica).set(max(0.0, step_start - prev))

        delta = self.watchdog.sample()
        if delta > 0:
            self.live_compiles += delta  # driver thread only; GIL-atomic read
            XLA_COMPILES.labels(replica=self.replica).inc(delta)
            with self._lock:
                live = list(self._live.values())
            for sp in live:
                sp.add_event("xla_compile", new_programs=delta,
                             step_s=round(step_end - step_start, 6))
            logger.warning(
                "xla compile during live traffic: %d new program(s) in a %.3fs step, of %s "
                "(warmup should have predicted this shape)",
                delta, step_end - step_start, ", ".join(self.watchdog.grown),
            )
        return delta

    def idle(self) -> None:
        """The driver found no work — the next gap is idleness, not stall."""
        with self._lock:
            self._last_step_end = None
        from githubrepostorag_tpu.metrics import SCHED_STALL

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
        sp = record_span("engine.decode", ftok, done, parent=parent, attrs={
            **attrs, "output_tokens": len(getattr(result, "output_tokens", ()) or ()),
            "finish_reason": getattr(result, "finish_reason", ""),
        })
        if sp is not None:
            # speculative-decoding outcome as events on the decode span:
            # the flight recorder then shows per-request acceptance and
            # any controller fallback right in the request's timeline
            proposed = getattr(result, "spec_proposed", 0)
            if proposed:
                sp.add_event(
                    "spec", proposed=proposed,
                    accepted=getattr(result, "spec_accepted", 0),
                    acceptance=round(
                        getattr(result, "spec_accepted", 0) / proposed, 4),
                )
            fallback = getattr(result, "spec_fallback", None)
            if fallback:
                sp.add_event("spec_fallback", reason=fallback)
