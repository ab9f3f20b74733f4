"""The compile ledger (obs/engine_profile.py) and the start-up record
(obs/startup.py): JAX's own events by function and by when, the line
``mark_warm()`` draws, what counts as a live compile, and the phases with the
device work they end in."""

import time

import jax
import jax.numpy as jnp
import pytest

from githubrepostorag_tpu.metrics import STARTUP_SECONDS, XLA_COMPILE_SECONDS, XLA_COMPILES
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.obs.engine_profile import (
    COMPILE, LOWER, TRACE, CompileWatchdog, EngineStepProfiler, compile_ledger,
    reset_compile_ledger)
from githubrepostorag_tpu.obs.trace import Span, TraceContext
from tests.helpers.compile_guard import compile_guard, watchdog_counter


@pytest.fixture()
def ledger():
    """A fresh ledger and record behind the one listener; the process's own
    are put back (other tests' engines have named their step programs there)."""
    from githubrepostorag_tpu.obs import engine_profile

    saved, saved_record = engine_profile._ledger, startup._record
    startup.reset_startup_record()
    yield reset_compile_ledger()
    engine_profile._ledger, startup._record = saved, saved_record


def fresh_jit(name):
    def fn(x):
        return jnp.tanh(x) * 3 + 1
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def counter(metric, **labels):
    return metric.labels(**labels)._value.get()


def test_one_call_of_a_fresh_jit_is_a_trace_a_lowering_and_a_compile_under_its_name(ledger):
    f = fresh_jit("ledger_probe_a")
    before = counter(XLA_COMPILE_SECONDS, when="startup")
    f(jnp.zeros((3,), jnp.float32))
    programs, hits, trace_s, lower_s, compile_s = ledger.by_fun["ledger_probe_a"]
    assert programs == 1 and hits == 0 and trace_s > 0 and lower_s > 0 and compile_s > 0
    mine = [e for e in ledger.startup_events if e.fun == "ledger_probe_a"]
    assert [e.kind for e in mine] == [TRACE, LOWER, COMPILE]
    now = time.monotonic()
    assert all(now - 60 < e.t <= now and e.wall >= e.seconds for e in mine)  # obs/'s clock
    assert counter(XLA_COMPILE_SECONDS, when="startup") > before
    seq = ledger.seq
    f(jnp.zeros((3,), jnp.float32))  # the same shapes: jit's own cache, nothing announced
    assert ledger.seq == seq and ledger.by_fun["ledger_probe_a"][0] == 1


def test_a_jit_traced_inside_another_keeps_its_own_seconds(ledger):
    inner = fresh_jit("ledger_probe_inner")

    @jax.jit
    def ledger_probe_outer(x):
        return inner(x) + inner(x * 2)

    ledger_probe_outer(jnp.zeros((5,), jnp.float32))
    traces = {e.fun: e for e in ledger.startup_events if e.kind == TRACE}
    outer, nested = traces["ledger_probe_outer"], traces["ledger_probe_inner"]
    assert outer.wall >= outer.seconds + nested.seconds - 1e-6  # the inner trace is not booked twice
    assert ledger.by_fun["ledger_probe_inner"][0] == 0  # traced, never compiled on its own
    total = sum(e.seconds for e in ledger.startup_events if e.kind == TRACE)
    assert total <= sum(e.wall for e in ledger.startup_events
                        if e.kind == TRACE and e.fun == "ledger_probe_outer") + 1e-6 or \
        len(traces) > 2  # (eager helpers traced beside it)


def test_after_mark_warm_a_step_programs_compile_is_live_and_lands_on_the_span(ledger):
    f, g = fresh_jit("ledger_probe_step"), fresh_jit("ledger_probe_other")
    ledger.watch([f])
    prof = EngineStepProfiler(replica="t-ledger")
    f(jnp.zeros((2,), jnp.float32))  # warm-up's compile
    prof.mark_warm()
    assert ledger.warm_t is not None and prof.on_step(0.0, 0.1) == 0
    sp = Span("llm.generate", TraceContext.new_root())
    prof.register(sp)
    before = counter(XLA_COMPILES, replica="t-ledger")
    g(jnp.zeros((2,), jnp.float32))  # no step program: kept under "live", raises nothing
    assert prof.on_step(0.1, 0.2) == 0 and prof.live_compiles == 0
    f(jnp.zeros((7,), jnp.float32))  # a shape the warm-up did not predict
    assert prof.on_step(0.2, 0.5) == 1 and prof.live_compiles == 1
    assert counter(XLA_COMPILES, replica="t-ledger") == before + 1
    events = [e for e in sp.events if e["name"] == "xla_compile"]
    assert len(events) == 1 and events[0]["new_programs"] == 1
    assert events[0]["programs"].startswith("ledger_probe_step ")  # the function and its seconds
    assert prof.on_step(0.5, 0.6) == 0  # a delta, not a level
    assert ledger.totals["live"][0] >= 2 and ledger.totals["startup"][0] >= 1
    live = [e for e in ledger.snapshot()["events"] if e[0] >= ledger.warm_t and e[2] == COMPILE]
    assert {"ledger_probe_other", "ledger_probe_step"} <= {e[3] for e in live}  # eager fills too
    assert [e[5] for e in live if e[3].startswith("ledger_probe")] == [False, True]


def test_a_qwen3_next_step_program_compiled_after_mark_warm_is_counted(ledger, monkeypatch):
    """The hole: the polled watchdog listed modules and lacked this one.  The
    ledger is told by the engine that dispatches the programs; no module is
    named here or there."""
    from githubrepostorag_tpu.models import qwen3_next as model
    from githubrepostorag_tpu.serving import Engine
    from githubrepostorag_tpu.serving.sampling_params import SamplingParams

    monkeypatch.setattr(model, "ACT", jnp.float32)
    cfg = model.Qwen3NextConfig.tiny(experts_held=(4, 12))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), model.init_params(cfg, seed=3))
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=16, max_seq_len=128,
                 prefill_chunk=64, decode_burst=2, kv_dtype=jnp.float32, state_snapshots=4)
    assert {"forward_paged_wave", "decode_burst"} <= ledger.step_programs
    jax.clear_caches()  # whatever another test left of these programs at these widths
    prof = EngineStepProfiler(replica="t-hole")
    prof.mark_warm()
    eng.generate([[5, 6, 7, 8]], SamplingParams(max_tokens=3, temperature=0.0,
                                                stop_token_ids=()))
    assert prof.on_step(0.0, 1.0) >= 2 and prof.live_compiles >= 2
    grown = " ".join(prof.watchdog.grown)
    assert "forward_paged_wave" in grown and "decode_burst" in grown


def test_compile_guard_over_the_ledger(ledger):
    f = fresh_jit("ledger_probe_guard")
    ledger.watch([f])
    f(jnp.zeros((2,), jnp.float32))
    with compile_guard(watchdog_counter(), label="warm shape"):
        f(jnp.zeros((2,), jnp.float32))
    with compile_guard(watchdog_counter(), expect=2, label="two new shapes") as g:
        f(jnp.zeros((3,), jnp.float32))
        f(jnp.zeros((4,), jnp.float32))
        jnp.zeros((9,)) + 1  # an eager op is no step program
    assert g.delta == 2
    with pytest.raises(AssertionError, match="escaped"):
        with compile_guard(watchdog_counter(), label="live"):
            f(jnp.zeros((5,), jnp.float32))
    dog = CompileWatchdog(programs=[f])
    assert dog.cache_size() == 4 and dog.sample() == 0


def test_phases_are_stamped_by_the_functions_that_do_the_work(ledger, tmp_path):
    record = startup.startup_record()
    from githubrepostorag_tpu.models import deepseek_v3
    from githubrepostorag_tpu.serving.tokenizer import make_tokenizer

    t0 = time.monotonic()
    params = deepseek_v3.init_params(deepseek_v3.DeepseekV3Config.tiny(), seed=1)
    with pytest.raises(Exception):  # noqa: B017 - no checkpoint there: the phase is still written
        make_tokenizer(str(tmp_path))
    names = [ph.name for ph in record.phases]
    assert names == ["startup.weights", "startup.tokenizer"]
    weights = record.phases[0]
    assert t0 <= weights.start <= weights.dispatched  # stamped at its dispatch ...
    jax.block_until_ready(params)
    record.poll()
    assert weights.end is not None and weights.end >= weights.dispatched  # ... closed when ready
    assert startup.PROCESS_START < t0 and t0 - startup.PROCESS_START < 3600
    by_name = record.seconds_by_phase()
    assert by_name["startup.weights"] > 0 and by_name["startup.tokenizer"] >= 0


def test_mark_warm_closes_the_record_and_tells_the_operator_once(ledger, caplog):
    record = startup.startup_record()
    with startup.phase("startup.engine_init"):
        fresh_jit("ledger_probe_ready")(jnp.zeros((2,), jnp.float32))
    left_open = record.begin("startup.encoder")
    with caplog.at_level("INFO"):
        EngineStepProfiler(replica="t-ready").mark_warm()
        EngineStepProfiler(replica="t-ready2").mark_warm()  # a relaunch: no second line
    lines = [r.getMessage() for r in caplog.records if r.getMessage().startswith("ready ")]
    assert len(lines) == 1 and "startup.engine_init" in lines[0]
    assert "ledger_probe_ready" in lines[0] and "read from the cache" in lines[0]
    assert left_open.end is None and "startup.encoder" not in record.seconds_by_phase()
    assert STARTUP_SECONDS.labels(phase="startup.engine_init")._value.get() > 0
    assert STARTUP_SECONDS.labels(phase="total")._value.get() >= \
        STARTUP_SECONDS.labels(phase="startup.engine_init")._value.get()
    with startup.phase("startup.weights"):  # after ready the record takes nothing
        pass
    assert [ph.name for ph in record.phases] == ["startup.engine_init", "startup.encoder"]
