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
