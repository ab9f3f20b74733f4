"""The delta rule with a decay a key CHANNEL (Kimi Delta Attention) in
ops/gated_delta.py and ops/pallas_state.py: the step form against the chunked
form against the plain reference's token scan (benchmarks/reference_bailing_hybrid
.recurrence, which imports nothing of the program), with the gate at its bound
(-5 on every channel for 512 tokens: ``exp(-320)`` a block of 64, what the
16-token reference span is for), near zero, and mixed; padding masked; a
snapshot at a page boundary; the Pallas body (interpreted) against the array
step with dead rows untouched bit for bit; and with ``g`` constant over a
head's channels the rule IS ``gated_delta_step``'s, which ties the new code to
the old."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_bailing_hybrid as ref
from githubrepostorag_tpu.ops.gated_delta import (
    channel_span,
    gated_delta_chunked,
    gated_delta_step,
    l2norm,
    mask_padding,
)
from githubrepostorag_tpu.ops.pallas_state import kda_step_in_place

R, H, DK, DV = 2, 2, 16, 16
GATES = {
    "bound": lambda key, shape: jnp.full(shape, -5.0),
    "near_zero": lambda key, shape: jnp.full(shape, -1e-4),
    "mixed": lambda key, shape: -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(key, shape)),
}


def inputs(t, gate, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (R, t, H, DK))) * DK ** -0.5
    k = l2norm(jax.random.normal(ks[1], (R, t, H, DK)))
    v = jax.random.normal(ks[2], (R, t, H, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (R, t, H)))
    s0 = jax.random.normal(ks[4], (R, H, DK, DV))
    return s0, q, k, v, GATES[gate](ks[5], (R, t, H, DK)), beta


def scanned(s0, q, k, v, g, beta):
    """The reference's token scan, a row at a time: (o [R, T, H, dv], states)."""
    outs = [ref.recurrence(q[r], k[r], v[r], g[r], beta[r], s0[r]) for r in range(R)]
    return jnp.stack([o for o, _ in outs]), jnp.stack([s for _, s in outs])


def test_the_span_keeps_the_factors_inside_float32():
    assert channel_span(-5.0) == 16 and channel_span(-1.0) == 64 and channel_span(-5.0, 8) == 8
    assert channel_span(-100.0) == 1 and np.isfinite(np.float32(np.exp(15 * 5.0)))
    with pytest.raises(ValueError, match="lower bound"):
        gated_delta_chunked(*inputs(64, "mixed"))


@pytest.mark.parametrize("gate", list(GATES))
def test_chunked_step_and_the_references_scan_agree(gate):
    """512 tokens in blocks of 64 with a snapshot at the fourth page of 64."""
    s0, q, k, v, g, beta = inputs(512, gate)
    o_ref, s_ref = scanned(s0, q, k, v, g, beta)
    snap_col = jnp.asarray([256, 0])
    o, s, snap = gated_delta_chunked(s0, q, k, v, g, beta, snap_col, block=64, g_min=-5.0)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(s, s_ref, atol=2e-5)
    _, s_half = scanned(s0, *(x[:, :256] for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(snap[0], s_half[0], atol=2e-5)
    np.testing.assert_array_equal(snap[1], s0[1])  # no snapshot asked for: the state that came in

    def step(s, x):
        o, s = gated_delta_step(s, *x)
        return s, o

    s_step, o_step = jax.lax.scan(step, s0, tuple(jnp.moveaxis(x, 1, 0)
                                                  for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(jnp.moveaxis(o_step, 0, 1), o_ref, atol=2e-5)
    np.testing.assert_allclose(s_step, s_ref, atol=2e-5)


def test_padding_leaves_the_state_bit_for_bit():
    s0, q, k, v, g, beta = inputs(128, "mixed")
    live = jnp.arange(128)[None, :] < jnp.asarray([70, 0])[:, None]
    k, g, beta = mask_padding(live, k, g, beta)
    _, s, _ = gated_delta_chunked(s0, q, k, v, g, beta, block=64, g_min=-5.0)
    np.testing.assert_array_equal(s[1], s0[1])  # a row of padding alone
    _, s_ref = scanned(s0, *(x[:, :70] for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(s[0], s_ref[0], atol=2e-5)


def test_a_decay_constant_over_a_heads_channels_is_the_gated_delta_rule():
    s0, q, k, v, _, beta = inputs(64, "mixed")
    g_head = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(9), (R, 64, H)))
    g_chan = jnp.broadcast_to(g_head[..., None], (R, 64, H, DK))
    o_a, s_a = gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0], g_chan[:, 0], beta[:, 0])
    o_b, s_b = gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0], g_head[:, 0], beta[:, 0])
    np.testing.assert_allclose(o_a, o_b, atol=1e-6)
    np.testing.assert_allclose(s_a, s_b, atol=1e-6)
    chan = gated_delta_chunked(s0, q, k, v, g_chan, beta, block=64, g_min=-20.0)
    head = gated_delta_chunked(s0, q, k, v, g_head, beta, block=64)
    for a, b in zip(chan, head):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("width", [DV, 128])
def test_the_kernel_on_the_pool_is_the_array_step_and_touches_no_dead_row(width):
    """Interpreted: layer 1's live rows stepped where they lie (a pool stored at
    its values' width, and at a whole lane tile with the padding lanes zero)."""
    s0, q, k, v, g, beta = inputs(4, "mixed", seed=3)
    b = 4
    qq, kk, vv, gg, bb = (x[:, :2].reshape(R * 2, *x.shape[2:])[:b] for x in (q, k, v, g, beta))
    pool = jax.random.normal(jax.random.PRNGKey(5), (2, 7, H, DK, width)) \
        * (jnp.arange(width) < DV)
    act = jnp.asarray([True, False, True, True])
    o, new = kda_step_in_place(pool, jnp.int32(1), act, qq, kk, vv, gg, bb, interpret=True)
    o_ref, s_ref = gated_delta_step(pool[1, :b], qq, kk, vv, gg, bb)
    np.testing.assert_allclose(o, jnp.where(act[:, None, None], o_ref, 0.0), atol=1e-6)
    np.testing.assert_allclose(new[1, :b][act], s_ref[act], atol=1e-6)
    np.testing.assert_array_equal(new[0], pool[0])  # another layer
    np.testing.assert_array_equal(new[1, 1], pool[1, 1])  # a dead row
    np.testing.assert_array_equal(new[1, b:], pool[1, b:])  # snapshots and the spare
    assert not np.asarray(new[..., DV:]).any()  # the padding lanes stay zero
