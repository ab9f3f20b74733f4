"""Mamba-2's state-space rule in its three forms: the chunked form of a
prefill wave, the one-token form of the burst (ops/ssd.py) and the benchmark's
reference recurrence (benchmarks/reference_nemotron_h.py, which imports
nothing of the program) agree; across block and chunk boundaries, from a
state that is not zero, with padded columns, a snapshot between two blocks,
rows that sit a step out, ``B`` / ``C`` shared by a group of heads, and the
skip ``D``.  Where ``torch`` and ``transformers`` import, the whole mixer is
held to ``transformers``' own Mamba-2 modules on the same weights, an
implementation nobody here wrote.

Tolerances: everything here is float32 on the CPU, and the forms sum the same
terms in different orders; 2e-5 on outputs and states of order one is a few
hundred roundings, not a different formula (a missing decay, a head reading
the wrong group or a dropped skip reads 1e-1 and more)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_nemotron_h as ref
from githubrepostorag_tpu.ops import ssd
from githubrepostorag_tpu.ops.gated_delta import causal_conv, causal_conv_step
from githubrepostorag_tpu.ops.norms import rms_norm_gate_first

TOL = 2e-5


def inputs(rows, t, h=8, p=4, g=2, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(rows, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, size=(h,)), jnp.float32)
    return f(rows, t, h, p), dt, a, f(rows, t, g, n), f(rows, t, g, n), f(h), f(rows, h, p, n)


def by_steps(x, dt, a, b, c, d, state, n):
    outs = []
    for t in range(n):
        y, state = ssd.ssd_step(state, x[:, t], dt[:, t], a, b[:, t], c[:, t], d)
        outs.append(y)
    return jnp.stack(outs, axis=1), state


def naive(x, dt, a, b, c, d, state):
    """The recurrence as the module docstring writes it, head by head."""
    t, h, p = x.shape
    g = b.shape[1]
    s, ys = np.array(state, np.float64), np.zeros((t, h, p))
    x, dt, a, b, c, d = (np.asarray(v, np.float64) for v in (x, dt, a, b, c, d))
    for i in range(t):
        for j in range(h):
            grp = j // (h // g)
            s[j] = np.exp(dt[i, j] * a[j]) * s[j] + dt[i, j] * np.outer(x[i, j], b[i, grp])
            ys[i, j] = s[j] @ c[i, grp] + d[j] * x[i, j]
    return ys, s


def test_chunked_one_token_naive_and_reference_forms_agree_from_a_nonzero_state():
    """96 tokens are three blocks of 32: the state crosses two block
    boundaries; two groups of four heads, a skip that is not one."""
    x, dt, a, b, c, d, s0 = inputs(2, 96)
    y_c, s_c, _ = ssd.ssd_chunked(s0, x, dt, a, b, c, d, block=32)
    y_s, s_s = by_steps(x, dt, a, b, c, d, s0, 96)
    np.testing.assert_allclose(y_c, y_s, atol=TOL)
    np.testing.assert_allclose(s_c, s_s, atol=TOL)
    for r in range(2):
        y_n, s_n = naive(x[r], dt[r], a, b[r], c[r], d, s0[r])
        np.testing.assert_allclose(y_c[r], y_n, atol=TOL)
        np.testing.assert_allclose(s_c[r], s_n, atol=TOL)
        y_r, s_r = ref.recurrence(x[r], dt[r], a, b[r], c[r], d, state=s0[r])
        np.testing.assert_allclose(y_c[r], y_r, atol=TOL)
        np.testing.assert_allclose(s_c[r], s_r, atol=TOL)


def test_a_head_reads_its_own_group_and_the_skip_counts():
    x, dt, a, b, c, d, s0 = inputs(1, 32)
    y, _, _ = ssd.ssd_chunked(s0, x, dt, a, b, c, d, block=32)
    swapped, _, _ = ssd.ssd_chunked(s0, x, dt, a, b[:, :, ::-1], c[:, :, ::-1], d, block=32)
    no_skip, _, _ = ssd.ssd_chunked(s0, x, dt, a, b, c, jnp.zeros_like(d), block=32)
    assert float(jnp.abs(y - swapped).max()) > 0.1
    np.testing.assert_allclose(y - no_skip, d[None, None, :, None] * x, atol=TOL)


@pytest.mark.parametrize("width", [128, 64, 32])
def test_padded_columns_leave_the_state_bit_for_bit(width):
    """A wave's row of 21 real tokens in a rung of ``width`` columns: the
    columns past it are masked to a step of zero; outputs and the state after
    are those of the 21 tokens alone, and a row with no real token keeps its
    state bit for bit."""
    x, dt, a, b, c, d, s0 = inputs(2, width, seed=1)
    lens = jnp.asarray([21, 0])
    live = jnp.arange(width)[None, :] < lens[:, None]
    y, s, _ = ssd.ssd_chunked(s0, x, ssd.mask_padding(live, dt), a, b, c, d, block=32)
    y_s, s_s = by_steps(x[:1], dt[:1], a, b[:1], c[:1], d, s0[:1], 21)
    np.testing.assert_allclose(y[0, :21], y_s[0], atol=TOL)
    np.testing.assert_allclose(s[0], s_s[0], atol=TOL)
    assert bool((s[1] == s0[1]).all())


def test_a_snapshot_falls_between_two_blocks():
    """``snap_col`` a row: 64 (after the second block), 0 (none: the state that
    came in), 96 (the chunk's end: the state after)."""
    x, dt, a, b, c, d, s0 = inputs(3, 96, seed=2)
    _, s, snap = ssd.ssd_chunked(s0, x, dt, a, b, c, d, jnp.asarray([64, 0, 96]), block=32)
    _, s_64 = by_steps(x[:1], dt[:1], a, b[:1], c[:1], d, s0[:1], 64)
    np.testing.assert_allclose(snap[0], s_64[0], atol=TOL)
    assert bool((snap[1] == s0[1]).all())
    assert bool((snap[2] == s[2]).all())


def test_one_token_step_at_a_state_stored_wider_than_it_is():
    """A pool stores its last axis at a whole lane tile: the step reads the
    first N lanes and leaves the rest zero, bit for bit."""
    x, dt, a, b, c, d, s0 = inputs(2, 1, seed=3)
    wide = jnp.pad(s0, ((0, 0),) * 3 + ((0, 112),))
    y_w, s_w = ssd.ssd_step(wide, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
    y, s = ssd.ssd_step(s0, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
    np.testing.assert_allclose(y_w, y, atol=1e-6)
    np.testing.assert_allclose(s_w[..., :16], s, atol=1e-6)
    assert not np.asarray(s_w[..., 16:]).view(np.uint32).any()


# ----------------------------------- the one-token rule as a kernel on the pool --

ROWS = 32  # the engine's rows: the pool's first slots; 3 more stand for snapshots and the spare


def _pool_case(live_steps, limits=None, seed=4, layers=3, rows=ROWS, h=8, p=8, g=2, n=16):
    """A pool [layers, rows + 3, H, P, 128] (N = 16 stored at a lane tile, the
    padding zero) and, a step, the inputs of ``ssd_step`` and the rows that
    step: ``live_steps`` [steps][rows] bools, or ``limits`` (``row_limits``
    against lengths that start at 5 and grow with every step a row takes, as
    models/hybrid.burst masks them)."""
    steps = len(live_steps) if limits is None else 4
    x, dt, a, b, c, d, _ = inputs(rows, steps, h, p, g, n, seed)
    rng = np.random.default_rng(seed)
    pool = np.zeros((layers, rows + 3, h, p, 128), np.float32)
    pool[..., :n] = rng.normal(size=pool.shape[:-1] + (n,))
    if limits is not None:
        lens, live_steps = np.full(rows, 5), []
        for _ in range(steps):
            live_steps.append(lens < np.asarray(limits))
            lens = lens + live_steps[-1]
    return jnp.asarray(pool), (x, dt, a, b, c, d), [jnp.asarray(m, bool) for m in live_steps]


BETWEEN = np.isin(np.arange(ROWS), [1, 2, 7, 19, 30])  # live rows between dead ones

POOL_CASES = [
    pytest.param(dict(live_steps=[np.zeros(ROWS, bool)] * 2), id="no-live-row"),
    pytest.param(dict(live_steps=[np.arange(ROWS) == 13] * 3), id="one-live-row"),
    pytest.param(dict(live_steps=[BETWEEN, BETWEEN, ~BETWEEN, BETWEEN]),
                 id="live-rows-between-dead-ones"),
    pytest.param(dict(live_steps=[np.ones(ROWS, bool)] * 3), id="all-32"),
    pytest.param(dict(live_steps=None, limits=[7, 5, 6, 99] * 8),
                 id="rows-that-reach-their-row-limits"),
]


def _chain(pool, ins, live_steps, layer, step_fn):
    """Every step's (y, pool) from ``step_fn`` beside the array form's."""
    x, dt, a, b, c, d = ins
    rows = x.shape[0]
    want = pool
    for t, act in enumerate(live_steps):
        before = pool
        y, pool = step_fn(pool, layer, act, x[:, t], dt[:, t], a, b[:, t], c[:, t], d)
        y_w, s_w = ssd.ssd_step(want[layer, :rows], x[:, t], dt[:, t], a, b[:, t], c[:, t], d)
        want = want.at[layer, :rows].set(
            jnp.where(act[:, None, None, None], s_w, want[layer, :rows]))
        yield np.asarray(act), np.asarray(before), (y, pool), (y_w, want)


def heads_a_block(monkeypatch, heads, p=8):
    """``heads`` heads of [p, 128] float32 a block of the kernel's walk."""
    from githubrepostorag_tpu.ops import pallas_state

    monkeypatch.setattr(pallas_state, "BLOCK_BYTES", heads * p * 128 * 4)


@pytest.mark.parametrize("case", POOL_CASES)
def test_the_kernel_steps_live_rows_as_ssd_step_and_touches_nothing_else(monkeypatch, case):
    """ops/pallas_state.ssd_step_in_place under the interpreter, chained over
    steps: a live row's state and output are ``ssd_step``'s to rounding (the
    elementwise operations are the same; the 128 lanes of ``S C`` may be summed
    in another order); a dead row's slot, every slot past the rows and every
    other layer are the bits that were there; the padding lanes stay zero."""
    from githubrepostorag_tpu.ops.pallas_state import ssd_step_in_place

    pool, ins, live_steps = _pool_case(**case)
    rows, layer = ins[0].shape[0], 1
    heads_a_block(monkeypatch, 2)  # 4 blocks a row
    step = functools.partial(ssd_step_in_place, interpret=True)
    for act, before, (y, got), (y_w, want) in _chain(pool, ins, live_steps, layer, step):
        got = np.asarray(got)
        np.testing.assert_allclose(np.asarray(y)[act], np.asarray(y_w)[act], atol=TOL)
        np.testing.assert_allclose(got[layer, :rows][act], np.asarray(want)[layer, :rows][act],
                                   atol=TOL)
        kept = np.ones(got.shape[:2], bool)
        kept[layer, :rows] = ~act
        assert (got.view(np.uint32)[kept] == before.view(np.uint32)[kept]).all()
        assert not got[..., 16:].view(np.uint32).any()
        assert np.isfinite(np.asarray(y)).all()  # a dead row's output is unused, not undefined


@pytest.mark.parametrize("case,block_heads", [
    pytest.param(POOL_CASES[2].values[0], None, id="between-dead-ones-one-block-a-row"),
    pytest.param(POOL_CASES[4].values[0], 4, id="row-limits-two-blocks-a-row")])
def test_the_kernels_dmas_land_before_they_are_read_and_never_meet(monkeypatch, case, block_heads):
    """Plain interpret mode copies at ``start()``; the TPU interpreter runs a
    DMA when it is waited for and watches every buffer for races: a block
    computed before its wait, a slot refilled while its write is still out, a
    read that meets a write of the pool, or a wait with no start (a hang)
    show here (tests/test_pallas_paged.py has the burst attention's).  One
    block a row (as many items as live rows: an odd count too), then two."""
    from jax.experimental.pallas import tpu as pltpu
    from githubrepostorag_tpu.ops.pallas_state import ssd_step_in_place

    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this jax has no TPU interpreter")
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter

    pool, ins, live_steps = _pool_case(**case, layers=2)
    pool = jnp.concatenate([pool[:, :8], pool[:, ROWS:]], axis=1)  # 8 rows, and the 3 slots past
    ins = tuple(v[:8] if v.shape[0] == ROWS else v for v in ins)
    live_steps = [m[:8] for m in live_steps][:2]
    if block_heads:
        heads_a_block(monkeypatch, block_heads)
    step = functools.partial(ssd_step_in_place, interpret=pltpu.InterpretParams(
        detect_races=True, dma_execution_mode="on_wait"))
    for act, before, (y, got), (y_w, want) in _chain(pool, ins, live_steps, 1, step):
        np.testing.assert_allclose(np.asarray(y)[act], np.asarray(y_w)[act], atol=TOL)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)
        assert (np.asarray(got)[1, :8][~act] == before[1, :8][~act]).all()
        assert not tpu_interpreter.races.races_found


def test_the_convolution_with_a_bias_in_both_forms():
    """``causal_conv`` / ``causal_conv_step`` with Mamba-2's bias against the
    reference's, the history carried across two chunks and then token by token."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 40, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    want = ref.causal_conv(x[0], w, bias)
    taps = jnp.zeros((1, 3, 6), jnp.float32)
    y1, taps, _ = causal_conv(x[:, :16], taps, w, jnp.asarray([16]), bias=bias)
    y2, taps, _ = causal_conv(x[:, 16:32], taps, w, jnp.asarray([16]), bias=bias)
    ys = [y1[0], y2[0]]
    for t in range(32, 40):
        y, taps = causal_conv_step(x[:, t], taps, w, bias=bias)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys), want, atol=TOL)
    plain, _, _ = causal_conv(x[:, :16], jnp.zeros((1, 3, 6)), w, jnp.asarray([16]))
    assert float(jnp.abs(plain - y1).max()) > 0.1  # the bias is not nothing


def test_the_gate_first_grouped_norm():
    rng = np.random.default_rng(5)
    x, z = (jnp.asarray(rng.normal(size=(3, 24)), jnp.float32) for _ in range(2))
    w = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    gated = np.asarray(x * jax.nn.silu(z)).reshape(3, 4, 6)
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(rms_norm_gate_first(x, z, w, 4, 1e-5),
                               want.reshape(3, 24) * np.asarray(w), rtol=1e-5, atol=1e-6)
    whole = rms_norm_gate_first(x, z, w, 1, 1e-5)
    assert float(jnp.abs(whole - want.reshape(3, 24) * w).max()) > 1e-2


# ---- an independent witness: transformers' own Mamba-2 mixers on the same weights

def _mixer(x, wts, h, p, g, n, eps, groups):
    """The mixer as models/nemotron_h.py wires it, in float32, from the ops."""
    t = x.shape[1]
    proj = x @ wts["in_proj"].T
    di, cdim = h * p, h * p + 2 * g * n
    z, xbc, dt = proj[..., :di], proj[..., di:di + cdim], proj[..., di + cdim:]
    dt = jax.nn.softplus(dt + wts["dt_bias"])
    y, _, _ = causal_conv(xbc, jnp.zeros((1, 3, cdim)), wts["conv_w"], jnp.asarray([t]),
                          bias=wts["conv_b"])
    xs = y[..., :di].reshape(1, t, h, p)
    b = y[..., di:di + g * n].reshape(1, t, g, n)
    c = y[..., di + g * n:].reshape(1, t, g, n)
    o, _, _ = ssd.ssd_chunked(jnp.zeros((1, h, p, n)), xs, dt, -jnp.exp(wts["A_log"]), b, c,
                              wts["D"], block=16)
    o = rms_norm_gate_first(o.reshape(1, t, di), z, wts["norm"], groups, eps)
    return o @ wts["out_proj"].T


@pytest.mark.parametrize("which", ["mamba2", "zamba2"])
def test_the_mixer_against_transformers_own_torch_forward(which):
    """``Mamba2Mixer.torch_forward`` (gate-first norm over the whole width:
    one group) and ``Zamba2MambaMixer.torch_forward`` (gate-first norm BY
    GROUP, as Nemotron-H's) on random weights: in_proj's column order ``z | x
    B C | dt``, the convolution's bias, ``softplus(dt + dt_bias)``, a head's
    group, the skip and the norm all have to match for 1e-4."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    h, p, g, n, d, t, eps = 8, 4, 2, 16, 16, 48, 1e-5  # d_inner = 2 x hidden
    torch.manual_seed(0)
    if which == "mamba2":
        from transformers import Mamba2Config
        from transformers.models.mamba2.modeling_mamba2 import Mamba2Mixer

        cfg = Mamba2Config(num_heads=h, head_dim=p, hidden_size=d, state_size=n, n_groups=g,
                           expand=2, conv_kernel=4, chunk_size=16, layer_norm_epsilon=eps,
                           use_bias=False, use_conv_bias=True, vocab_size=32, num_hidden_layers=1)
        mixer, groups = Mamba2Mixer(cfg, layer_idx=0), 1
    else:
        from transformers import Zamba2Config
        from transformers.models.zamba2.modeling_zamba2 import Zamba2MambaMixer

        cfg = Zamba2Config(hidden_size=d, mamba_expand=2,
                           mamba_d_state=n, mamba_d_conv=4, mamba_ngroups=g, n_mamba_heads=h,
                           mamba_headdim=p, chunk_size=16, rms_norm_eps=eps, vocab_size=32,
                           num_hidden_layers=1, add_bias_linear=False, use_conv_bias=True)
        mixer, groups = Zamba2MambaMixer(cfg, layer_idx=0), g
    with torch.no_grad():
        for prm in mixer.parameters():
            prm.copy_(torch.randn_like(prm) * 0.3)
        mixer.A_log.copy_(torch.log(torch.rand(h) * 15 + 1))
        x = torch.randn(1, t, d)
        want = mixer.torch_forward(x).numpy()
    sd = {k: jnp.asarray(v.detach().numpy()) for k, v in mixer.state_dict().items()}
    wts = {"in_proj": sd["in_proj.weight"], "out_proj": sd["out_proj.weight"],
           "conv_w": sd["conv1d.weight"][:, 0, :], "conv_b": sd["conv1d.bias"],
           "dt_bias": sd["dt_bias"], "A_log": sd["A_log"], "D": sd["D"], "norm": sd["norm.weight"]}
    got = _mixer(jnp.asarray(x.numpy()), wts, h, p, g, n, eps, groups)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
