"""The Gated DeltaNet rule in its three forms: the chunked (WY) form of a
prefill wave, the one-token form of the burst (ops/gated_delta.py) and the
benchmark's reference recurrence (benchmarks/reference_qwen3_next.py, which
imports nothing of the program) agree; across block and chunk boundaries,
from a state that is not zero, with padded columns at each of the wave's
rungs, and with rows that sit a burst step out.  And the one-token form as a
kernel that steps a state pool where it lies (ops/pallas_state.py), under the
interpreter, against the array form.

Tolerances: everything here is float32 on the CPU, and the three forms sum
the same terms in different orders; 2e-5 absolute on outputs and states of
order one is a few hundred roundings, not a different formula (a missing
decay or a transposed state reads 1e-1 and more)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_qwen3_next as ref
from githubrepostorag_tpu.ops import gated_delta as gd

TOL = 2e-5


def inputs(rows, t, h=3, dk=16, dv=8, seed=0, alike=0.0, beta=(0.05, 1.0), g=(0.001, 0.6)):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    base = rng.normal(size=(rows, 1, h, dk))
    q = gd.l2norm(f(rows, t, h, dk)) * dk ** -0.5
    k = gd.l2norm(jnp.asarray(alike * base, jnp.float32) + f(rows, t, h, dk))
    g = -jnp.asarray(rng.uniform(*g, size=(rows, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(*beta, size=(rows, t, h)), jnp.float32)
    return q, k, f(rows, t, h, dv), g, beta, f(rows, h, dk, dv)


def by_steps(q, k, v, g, beta, state, n):
    outs = []
    for t in range(n):
        o, state = gd.gated_delta_step(state, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("alike", [0.0, 3.0], ids=["keys-apart", "keys-alike"])
def test_chunked_one_token_and_reference_forms_agree_from_a_nonzero_state(alike):
    """192 tokens are three blocks of 64: the state crosses two block
    boundaries.  ``keys-alike`` puts neighbouring keys at cosine ~0.9, where a
    Neumann series over the whole block cancels (4e-3); the halved inverse
    does not."""
    q, k, v, g, beta, s0 = inputs(2, 192, alike=alike)
    o_c, s_c, _ = gd.gated_delta_chunked(s0, q, k, v, g, beta)
    o_s, s_s = by_steps(q, k, v, g, beta, s0, 192)
    np.testing.assert_allclose(o_c, o_s, atol=TOL)
    np.testing.assert_allclose(s_c, s_s, atol=TOL)
    for r in range(2):
        o_r, s_r = ref.recurrence(q[r], k[r], v[r], g[r], beta[r], state=s0[r])
        np.testing.assert_allclose(o_c[r], o_r, atol=TOL)
        np.testing.assert_allclose(s_c[r], s_r, atol=TOL)


@pytest.mark.parametrize("beta", [(0.05, 2.0), (1.9, 2.0)], ids=["beta-to-2", "beta-near-2"])
@pytest.mark.parametrize("alike", [0.0, 3.0], ids=["keys-apart", "keys-alike"])
def test_chunked_and_one_token_forms_agree_at_write_strengths_up_to_two(alike, beta):
    """A model that allows ``I - beta k k^T`` a negative eigenvalue writes with
    ``beta`` in (0, 2): 30 heads (no power of two), keys of 96 (three quarters
    of a lane tile) against values of 192, two blocks from a state that is not
    zero, with the block of the Neumann product ``beta_max`` = 2 asks for.
    Keys alike (cosine 0.9 between neighbours) at ``beta`` near 2 with little
    decay are the worst case of the triangular inverse: its terms reach
    ``3^7`` there and float32 keeps 1e-4 of them (ops/gated_delta.py), so that
    case alone is held to 2e-4 on outputs of order one; at 16 x 16 it reads
    4e-3 and more."""
    q, k, v, g, beta, s0 = inputs(2, 128, h=30, dk=96, dv=192, seed=7, alike=alike, beta=beta,
                                  g=(0.0, 0.05))
    o_c, s_c, _ = gd.gated_delta_chunked(s0, q, k, v, g, beta, beta_max=2.0)
    o_s, s_s = by_steps(q, k, v, g, beta, s0, 128)
    tol = 2e-4 if alike else TOL
    np.testing.assert_allclose(o_c, o_s, atol=tol)
    np.testing.assert_allclose(s_c, s_s, atol=10 * tol)  # states of order ten here
    if alike and beta[0, 0, 0] > 1.9:
        o_16, _, _ = gd.gated_delta_chunked(s0, q, k, v, g, beta)  # the block beta <= 1 takes
        assert float(jnp.abs(o_16 - o_s).max()) > 10 * tol


def test_the_neumann_block_follows_the_write_strength():
    assert gd.neumann_size() == gd.neumann_size(1.0) == 16 == gd.NEUMANN_MAX
    assert gd.neumann_size(2.0) == 8
    for beta_max in (1.0, 2.0):
        assert (1 + beta_max) ** (gd.neumann_size(beta_max) - 1) <= gd.NEUMANN_TERMS


def test_a_prompt_cut_into_chunks_is_the_prompt_whole_and_a_snapshot_is_its_state_there():
    """Two chunks of 128 hand the state on as one of 256 carries it, and the
    snapshot caught at column 64 / 128 is the state the one-token form has
    after that many tokens (a row that asks for none gets its state back)."""
    q, k, v, g, beta, s0 = inputs(3, 256, seed=1)
    o_w, s_w, _ = gd.gated_delta_chunked(s0, q, k, v, g, beta)
    cut = lambda x, a, b: x[:, a:b]  # noqa: E731
    o_1, s_1, snap = gd.gated_delta_chunked(
        s0, *(cut(x, 0, 128) for x in (q, k, v, g, beta)), snap_col=jnp.asarray([64, 128, 0]))
    o_2, s_2, _ = gd.gated_delta_chunked(s_1, *(cut(x, 128, 256) for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(jnp.concatenate([o_1, o_2], axis=1), o_w, atol=TOL)
    np.testing.assert_allclose(s_2, s_w, atol=TOL)
    np.testing.assert_allclose(snap[0], by_steps(q, k, v, g, beta, s0, 64)[1][0], atol=TOL)
    np.testing.assert_allclose(snap[1], s_1[1], atol=TOL)
    assert bool((snap[2] == s0[2]).all())


@pytest.mark.parametrize("width", [128, 256, 512])
def test_padded_columns_of_a_rung_leave_the_state_bit_identical(width):
    """A wave's row of 70 real tokens at each rung of the width ladder: the
    state after it is the state after 70 one-token steps, and a row with no
    real token keeps its state bit for bit."""
    q, k, v, g, beta, s0 = inputs(2, width, seed=width, h=2, dk=8, dv=8)
    live = jnp.arange(width)[None, :] < jnp.asarray([70, 0])[:, None]
    km, gm, bm = gd.mask_padding(live, k, g, beta)
    o, s, _ = gd.gated_delta_chunked(s0, q, km, v, gm, bm)
    o_s, s_s = by_steps(q, k, v, g, beta, s0, 70)
    np.testing.assert_allclose(o[0, :70], o_s[0], atol=TOL)
    np.testing.assert_allclose(s[0], s_s[0], atol=TOL)
    assert bool((s[1] == s0[1]).all())


def bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("dk,dv,stored", [(96, 192, 256), (128, 128, 128)],
                         ids=["96x192-in-256", "128x128-in-128"])
def test_the_rule_at_the_stored_width_is_the_rule_and_its_padding_stays_zero(dk, dv, stored):
    """The burst's use of the rule (models/hybrid.py:burst), 64 steps over rows
    that sit steps out: the state handed in as the pool stores it (``stored``
    lanes, lanes ``dv:`` zero) against the state at ``dv``.  Lanes ``:dv`` are
    the narrow rule's after every step (the same operations; 1e-6 where the
    backend sums a wider row in another order), lanes ``dv:`` are zero bit for
    bit with nobody writing zeros, and a row that sits a step out keeps every
    bit of its slot."""
    rows, steps = 4, 64
    q, k, v, g, beta, s0 = inputs(rows, steps, h=2, dk=dk, dv=dv, seed=dv, beta=(0.05, 2.0))
    act = jnp.asarray(np.random.default_rng(7).uniform(size=(steps, rows)) < 0.6)
    act = act.at[:, 3].set(False)  # one row never steps

    @jax.jit
    def step(state, t):
        args = (q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        o, new = gd.gated_delta_step(state, *args)
        return o, jnp.where(act[t][:, None, None, None], new, state)

    wide0 = jnp.pad(s0, ((0, 0),) * 3 + ((0, stored - dv),))
    wide, narrow = wide0, s0
    for t in range(steps):
        before = bits(wide)
        o_w, wide = step(wide, t)
        o_n, narrow = step(narrow, t)
        assert wide.shape == (rows, 2, dk, stored) and o_w.shape == (rows, 2, dv)
        np.testing.assert_allclose(o_w, o_n, atol=1e-6, rtol=0)
        np.testing.assert_allclose(wide[..., :dv], narrow, atol=1e-6, rtol=0)
        assert not bits(wide[..., dv:]).any(), t  # not even a negative zero
        idle = ~np.asarray(act[t])
        assert (bits(wide)[idle] == before[idle]).all(), t
    assert (bits(wide)[3] == bits(wide0)[3]).all()
    assert float(jnp.abs(wide[:3, ..., :dv] - s0[:3]).max()) > 0.1  # the others did step


# ----------------------------------- the one-token rule as a kernel on the pool --

POOL_ROWS = 6  # the engine's rows: the pool's first slots; 2 more stand for a snapshot and the spare
WIDTHS = {"96x192-in-256": (96, 192, 256), "128x128-in-128": (128, 128, 128)}
SOME = np.isin(np.arange(POOL_ROWS), [1, 2, 4])
LIVE = {"all-live": [np.ones(POOL_ROWS, bool)] * 3, "some-live": [SOME, ~SOME, SOME, SOME],
        "none-live": [np.zeros(POOL_ROWS, bool)] * 2}


def _pool_case(widths, live, layers=2, h=4, seed=11):
    """A pool [layers, rows + 2, H, dk, stored] (lanes ``dv:`` zero, the rest
    not) and the inputs of ``gated_delta_step`` a step, ``beta`` up to 2."""
    dk, dv, stored = WIDTHS[widths]
    q, k, v, g, beta, _ = inputs(POOL_ROWS, len(LIVE[live]), h=h, dk=dk, dv=dv, seed=seed,
                                 beta=(0.05, 2.0))
    pool = np.zeros((layers, POOL_ROWS + 2, h, dk, stored), np.float32)
    pool[..., :dv] = np.random.default_rng(seed).normal(size=pool.shape[:-1] + (dv,))
    return jnp.asarray(pool), (q, k, v, g, beta), [jnp.asarray(m) for m in LIVE[live]]


def _chain(pool, ins, live_steps, layer, step_fn):
    """Every step's (o, pool) from ``step_fn`` beside the array form's."""
    want = pool
    for t, act in enumerate(live_steps):
        before = np.asarray(pool)
        args = tuple(x[:, t] for x in ins)
        o, pool = step_fn(pool, layer, act, *args)
        o_w, s_w = gd.gated_delta_step(want[layer, :POOL_ROWS], *args)
        want = want.at[layer, :POOL_ROWS].set(
            jnp.where(act[:, None, None, None], s_w, want[layer, :POOL_ROWS]))
        yield np.asarray(act), before, (np.asarray(o), np.asarray(pool)), (o_w, np.asarray(want))


def heads_a_block(monkeypatch, heads, widths):
    from githubrepostorag_tpu.ops import pallas_state

    dk, _, stored = WIDTHS[widths]
    monkeypatch.setattr(pallas_state, "BLOCK_BYTES", heads * dk * stored * 4)


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("widths", WIDTHS)
def test_the_kernel_steps_live_rows_as_the_array_form_and_touches_nothing_else(
        monkeypatch, widths, live):
    """ops/pallas_state.gated_delta_step_in_place under the interpreter, at
    Olmo-Hybrid's widths (values of 192 stored at 256 lanes) and at
    Qwen3-Next's (128 at 128), two blocks of heads a row, chained over steps
    from a state that is not zero: a live row's state and output are
    ``gated_delta_step``'s to rounding (the elementwise operations are the
    same; the sum down ``dk`` sublanes may be taken in another order); a dead
    row's slot, every slot past the rows and the other layer are the bits that
    were there; lanes ``dv:`` stay zero bit for bit; a dead row's output is
    zero."""
    from githubrepostorag_tpu.ops.pallas_state import gated_delta_step_in_place

    pool, ins, live_steps = _pool_case(widths, live)
    dv, layer = WIDTHS[widths][1], 1
    heads_a_block(monkeypatch, 2, widths)
    step = jax.jit(functools.partial(gated_delta_step_in_place, interpret=True))
    for act, before, (o, got), (o_w, want) in _chain(pool, ins, live_steps, layer, step):
        assert o.shape == o_w.shape
        np.testing.assert_allclose(o[act], np.asarray(o_w)[act], atol=TOL)
        np.testing.assert_allclose(got[layer, :POOL_ROWS][act], want[layer, :POOL_ROWS][act],
                                   atol=TOL)
        kept = np.ones(got.shape[:2], bool)
        kept[layer, :POOL_ROWS] = ~act
        assert (bits(got)[kept] == bits(before)[kept]).all()
        assert not bits(got[..., dv:]).any()  # not even a negative zero
        assert not o[~act].any()
    if live != "none-live":
        assert float(np.abs(got[layer] - np.asarray(pool)[layer]).max()) > 0.1  # they did step


@pytest.mark.parametrize("widths,block_heads", [
    pytest.param("96x192-in-256", 4, id="96x192-in-256-one-block-a-row"),
    pytest.param("128x128-in-128", 2, id="128x128-in-128-two-blocks-a-row")])
def test_the_kernels_dmas_land_before_they_are_read_and_never_meet(monkeypatch, widths,
                                                                   block_heads):
    """Plain interpret mode copies at ``start()``; the TPU interpreter runs a
    DMA when it is waited for and watches every buffer for races (tests/
    test_ssd.py has the same walk under Mamba-2's body): a block computed
    before its wait, a slot refilled while its write is still out, a read that
    meets a write of the pool, or a wait with no start (a hang) show here.  An
    odd count of items (three live rows of one block), then an even one."""
    from jax.experimental.pallas import tpu as pltpu
    from githubrepostorag_tpu.ops.pallas_state import gated_delta_step_in_place

    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this jax has no TPU interpreter")
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter

    pool, ins, live_steps = _pool_case(widths, "some-live")
    heads_a_block(monkeypatch, block_heads, widths)
    step = jax.jit(functools.partial(gated_delta_step_in_place, interpret=pltpu.InterpretParams(
        detect_races=True, dma_execution_mode="on_wait")))
    for act, before, (o, got), (o_w, want) in _chain(pool, ins, live_steps[:2], 1, step):
        np.testing.assert_allclose(o[act], np.asarray(o_w)[act], atol=TOL)
        np.testing.assert_allclose(got, want, atol=TOL)
        assert (bits(got)[1, :POOL_ROWS][~act] == bits(before)[1, :POOL_ROWS][~act]).all()
        assert not tpu_interpreter.races.races_found


def test_at_equal_widths_the_rule_traces_to_what_it_always_did():
    """Qwen3-Next's pool stores 128 lanes for values of 128: its burst, and the
    metric that finds its two passes by name, must see the same program.  The
    rule as it stood before a state could be wider than ``v``, traced beside
    the rule as it is: the same equations, no pad, no slice."""
    def as_it_was(state, q, k, v, g, beta):
        decay = jnp.exp(g)[..., None]
        kv = decay * jnp.sum(state * k[..., None], axis=-2)
        qv = decay * jnp.sum(state * q[..., None], axis=-2)
        delta = beta[..., None] * (v - kv)
        o = qv + jnp.sum(k * q, axis=-1, keepdims=True) * delta
        return o, state * decay[..., None] + k[..., None] * delta[..., None, :]

    q, k, v, g, beta, s0 = inputs(2, 1, h=3, dk=16, dv=8)
    args = (s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    now = str(jax.make_jaxpr(gd.gated_delta_step)(*args))
    assert now == str(jax.make_jaxpr(as_it_was)(*args))
    assert " pad[" not in now and " slice[" not in now
    wide = str(jax.make_jaxpr(gd.gated_delta_step)(jnp.pad(s0, ((0, 0),) * 3 + ((0, 120),)),
                                                   *args[1:]))
    assert wide.count(" pad[") == 1 and wide.count(" slice[") == 1  # delta; one cut both sums read


def test_a_small_page_sets_the_block_and_the_snapshot_falls_between_blocks():
    """Pages of 16 (the rehearsal's): blocks of 16, a snapshot at column 48."""
    q, k, v, g, beta, s0 = inputs(1, 64, seed=5)
    o, s, snap = gd.gated_delta_chunked(s0, q, k, v, g, beta, snap_col=jnp.asarray([48]), block=16)
    o_s, s_s = by_steps(q, k, v, g, beta, s0, 64)
    np.testing.assert_allclose(o, o_s, atol=TOL)
    np.testing.assert_allclose(s, s_s, atol=TOL)
    np.testing.assert_allclose(snap, by_steps(q, k, v, g, beta, s0, 48)[1], atol=TOL)


def test_the_convolution_carries_its_history_across_chunks_and_steps():
    """Chunk + chunk + one-token steps = the reference's plain causal
    convolution over the whole sequence; the history after a row's real
    tokens is its last three inputs, and a row with none keeps its history."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 40, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    taps0 = jnp.zeros((2, 3, 6), jnp.float32)
    want = jnp.stack([ref.causal_conv(x[r], w) for r in range(2)])
    y1, taps, snap = gd.causal_conv(x[:, :16], taps0, w, jnp.asarray([16, 16]),
                                    snap_col=jnp.asarray([8, 0]))
    np.testing.assert_allclose(snap[0], x[0, 5:8], atol=0)
    np.testing.assert_allclose(snap[1], taps0[1], atol=0)
    # the second chunk is padded: 20 real tokens of 32 columns, and none in row 1
    pad = jnp.concatenate([x[:, 16:36], jnp.ones((2, 12, 6))], axis=1)
    y2, taps2, _ = gd.causal_conv(pad, taps, w, jnp.asarray([20, 0]))
    np.testing.assert_allclose(taps2[0], x[0, 33:36], atol=0)
    assert bool((taps2[1] == taps[1]).all())
    got, hist = [y1[0], y2[0, :20]], taps2[:1]
    for t in range(36, 40):
        y, hist = gd.causal_conv_step(x[:1, t], hist, w)
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got), want[0], atol=TOL)


def test_l2norm_is_the_published_kernels():
    x = jnp.asarray([[3.0, 4.0]])
    np.testing.assert_allclose(gd.l2norm(x), x / np.sqrt(25.0 + 1e-6), rtol=1e-6)
    assert jax.numpy.isfinite(gd.l2norm(jnp.zeros((1, 4)))).all()
