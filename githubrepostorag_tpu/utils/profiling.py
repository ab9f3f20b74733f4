"""jax.profiler integration (SURVEY.md §5.1).

``annotate(name, **meta)`` — a TraceAnnotation context manager: a host
span on the device trace's own clock.  ``meta`` (ints, floats, short
strings) rides as the event's stats; it is encoded only while a trace
is being taken, so with tracing off an annotation is one C++ object
and no formatting.  The names that exist (PERF.md §3 has the metric
that reads each):

    driver.step        the locked section of AsyncEngine._drive with
                       work; ``mono_ns`` anchors time.monotonic() stamps
                       (GenerationResult.timings, obs/) to the trace
    driver.export      counters, gauges, ledger, rings (under the
                       lock, then after it)
    driver.emit        parked/final events to the event loop, SLO monitor
    driver.wait        the driver asleep with no work
    server.submit_wait a submission waiting for the driver's lock, in the
                       executor: the event loop stays free
    engine.admit       reaping, preemption, admission, page allocation
    engine.prefill_batch (+ prefill_packed, sp_prefill_packed)
                       one prefill wave: host arrays and the dispatch of
                       its one program (chunk + first-token tail);
                       ``width``: the columns a row it runs at, and
                       ``padded_tokens`` = row bucket x width, of which
                       ``new_tokens`` are real
    engine.burst_prepare  the active mask and the masks of fresh rows
    engine.decode_burst   the dispatch call; ``ahead``: the device still
                       had work queued when the step's programs went out;
                       ``seq``: the dispatch's number (a wave's too), which
                       the burst's landing carries back
    engine.commit_fetch   the blocking device->host fetch of a burst
    engine.commit_host    per-token bookkeeping, callbacks, results; a
                       BURST's starts where its tokens landed, the end of a
                       decode cycle, and says which: ``seq``, ``waves`` /
                       ``wave_tokens`` (the prefill waves dispatched since the
                       burst before), ``chained`` (a burst was in flight
                       before it: the cycle is whole)
    embed.batch        one encoder batch, dispatch to vectors on host
    index.search       one device-index wave, dispatch to hits on host
    encoder.warmup, ingest.<stage>

Start-up (obs/startup.py: each is also a phase of the start-up record,
stamped by the function that does the work, until ``mark_warm()``; the
annotation is there for a start-up traced by hand):

    startup.tokenizer      serving/tokenizer.make_tokenizer
    startup.weights        the families' initialisers, hf_loader.load_qwen2
    startup.engine_init    Engine.__init__: pools, allocator, state slots
    startup.warmup         Engine.warmup: the pod's ladder
    startup.encoder        JaxBertTextEncoder.from_pretrained and .warmup
    startup.ingest.<stage> ingest/controller.stage_timer
    startup.index_build    the device index's first upload of a table
    (``startup.serve``, AsyncEngine's construction to ``mark_warm()``, spans
    two functions and is in the record alone)
"""

from __future__ import annotations

try:  # resolved once: annotate() sits on the engine's step path
    from jax.profiler import TraceAnnotation as _Annotation
except Exception:  # noqa: BLE001 - a build without profiler support
    _Annotation = None


class _NoAnnotation:
    """What annotate() returns on a build without a profiler."""

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **meta) -> None:
        pass


def annotate(name: str, **meta):
    """TraceAnnotation for the named region, ``meta`` as its stats (more can
    follow through ``set_metadata`` before it closes); a no-op context if
    this build has no profiler.  Never raises."""
    if _Annotation is None:
        return _NoAnnotation()
    return _Annotation(name, **meta)
