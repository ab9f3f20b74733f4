"""What the host asks of the device inside ``Engine.step``, program by
program.

JAX calls a post-hook after every call of a jitted function that hits the
call cache, an eagerly applied primitive (``x[idx]``, ``x.at[i].set``,
``jnp.where`` on device values, ``jax.random.split``: each is a jitted
function of its own) included.  A first call with a new signature goes
through Python and is not reported, so a run must be warm before it is
recorded: ``run_recorded`` checks that no jit of the serving modules gained
a cache entry while it listened."""

from __future__ import annotations

import contextlib
from typing import Iterator

WAVE, BURST = "forward_paged_wave", "decode_burst"
# admission's two helpers run before the wave, on host inputs
ADMISSION = {"_mark_presence_chunks", "_clear_presence_row"}


@contextlib.contextmanager
def jit_calls() -> Iterator[list[str]]:
    """The names of the jitted functions called in the block, in order."""
    from jax._src import api

    calls: list[str] = []
    prev = api._post_hook_state.swap_local(
        lambda fun, args, kwargs, out: calls.append(getattr(fun, "__name__", repr(fun))))
    try:
        yield calls
    finally:
        api._post_hook_state.set_local(prev)


def run_recorded(eng, script, warm: bool = True) -> tuple[dict, list[list[str]]]:
    """Drive ``eng`` by ``script`` (a generator: it adds and cancels requests
    and yields once for each ``step()`` it wants) and record every step's
    device calls.  Returns (results by request id, calls per step).
    ``warm=False`` is the rehearsal that compiles: it asserts nothing."""
    from tests.helpers.compile_guard import watchdog_counter

    cache = watchdog_counter()
    before = cache()
    done, steps = {}, []

    def step():
        with jit_calls() as calls:
            for res in eng.step():
                done[res.request_id] = res
        steps.append(list(calls))

    for _ in script:
        step()
    while eng.has_work():
        step()
    assert not warm or cache() == before, "not warm: a call went unrecorded"
    return done, steps


def assert_two_programs_a_step(steps: list[list[str]]) -> None:
    """No primitive applied eagerly; after admission's helpers at most a
    wave and a burst, in that order, and nothing after the wave but the
    burst."""
    assert any(WAVE in s and BURST in s for s in steps), "no wave ever joined running rows"
    for calls in steps:
        assert set(calls) <= ADMISSION | {WAVE, BURST}, calls
        assert calls.count(WAVE) <= 1 and calls.count(BURST) <= 1, calls
        programs = [c for c in calls if c in (WAVE, BURST)]
        assert programs == sorted(programs, key=(WAVE, BURST).index), calls
        if WAVE in calls:
            assert set(calls[calls.index(WAVE) + 1:]) <= {BURST}, calls


def recorded_waves(monkeypatch) -> list[dict]:
    """The list that receives, from here on, the metadata of every
    ``engine.prefill_batch`` annotation (``rows``, ``new_tokens``, ``width``,
    ``padded_tokens``, ...), what ``set_metadata`` adds included."""
    import githubrepostorag_tpu.serving.engine as engine_mod

    waves, real = [], engine_mod.annotate

    class Recorded:
        def __init__(self, ann, meta):
            self.ann, self.meta = ann, meta

        def __enter__(self):
            self.ann.__enter__()
            return self

        def __exit__(self, *exc):
            return self.ann.__exit__(*exc)

        def set_metadata(self, **meta):
            self.meta.update(meta)
            self.ann.set_metadata(**meta)

    def annotate(name, **meta):
        ann = real(name, **meta)
        if name != "engine.prefill_batch":
            return ann
        waves.append(meta)
        return Recorded(ann, meta)

    monkeypatch.setattr(engine_mod, "annotate", annotate)
    return waves


def burst_call_shapes(eng) -> list[tuple]:
    """Wrap the burst program of ``eng``; the returned list receives, for
    every dispatch, the (shape, dtype) of each array it was called with."""
    import jax

    seen: list[tuple] = []
    inner = eng._decode_burst_fn

    def call(*args, **kw):
        leaves = jax.tree.leaves((args[2:], {k: v for k, v in kw.items() if k != "mesh"}))
        seen.append(tuple((getattr(x, "shape", None), str(getattr(x, "dtype", type(x))))
                          for x in leaves))
        return inner(*args, **kw)

    eng._decode_burst_fn = call
    return seen
