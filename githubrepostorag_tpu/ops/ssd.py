"""Mamba-2's state-space layer (SSD, arXiv:2405.21060), the recurrent layer of
the Nemotron-H hybrids (models/nemotron_h.py over models/hybrid.py): a
fixed-size state a head and sequence, a diagonal (scalar a head) decay, and
input and output maps ``B`` and ``C`` that a GROUP of heads shares.

Per head ``h`` of group ``g`` and token, with ``S`` [P, N] float32 (``P`` the
head width, ``N`` the state size), ``x`` [P], ``B``, ``C`` [N], ``dt > 0`` the
step size (softplus'd by the caller), ``A < 0`` and the skip ``D`` scalars:

    S <- exp(dt_t A) S + dt_t x_t (x) B_t;   y_t = S C_t + D x_t

Two forms of the same recurrence:

* ``ssd_step``: one token a row (the decode burst).  The state is read once
  (``S C`` and the update are two results of one pass over what came in:
  ``y = exp(dt A) S C + dt (B . C) x + D x`` is the new state's) and written
  once.
* ``ssd_chunked``: a prefill chunk in blocks of ``BLOCK`` tokens.  Inside a
  block the outputs are one masked product, ``((C B^T) * L) (dt x)`` with
  ``L[i, j] = exp(sum_{j < l <= i} dt_l A)``; across blocks the state is
  carried by a scan, so a chunk of 512 columns reads and writes its state 4
  times and not 512.  ``C B^T`` is a product a GROUP (8 of them, not 64).

Tokens that are not real (the padding of a wave's row) arrive with ``dt = 0``
(``mask_padding``): they multiply the state by ``exp(0)`` and add zero to it,
bit for bit.  A row that sits a decode step out is the caller's to keep
(models/hybrid.burst selects the old rows).

All products run at ``Precision.HIGHEST`` in float32, as ops/gated_delta.py's:
the state outlives thousands of tokens, and a chunk's products are a few
GFLOP beside the experts' hundreds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 128  # tokens of one block of the chunked form (the published chunk_size)
HI = jax.lax.Precision.HIGHEST


def mask_padding(live: jnp.ndarray, dt: jnp.ndarray) -> jnp.ndarray:
    """``live`` [R, T] marks real tokens; the others get a step of zero and
    leave the state as it is."""
    return jnp.where(live[..., None], dt, 0.0)


def ssd_step(state, x, dt, a, b, c, d):
    """One token a row.  ``state`` [B, H, P, N] float32; ``x`` [B, H, P];
    ``dt`` [B, H]; ``a``, ``d`` [H]; ``b``, ``c`` [B, G, N] (head ``h`` reads
    group ``h // (H / G)``).  Returns (y [B, H, P], the new state).

    ``state`` may be WIDER than ``N`` along its last axis (a pool stores its
    rows at a whole number of lane tiles, models/hybrid.lane_padded, the lanes
    past ``N`` zero): ``b`` and ``c`` are then padded with zeros, so a padding
    lane is read as nothing and comes out as ``0 * decay + x * 0``, zero
    again."""
    bsz, h, p, n = state.shape
    g = b.shape[1]
    if b.shape[-1] != n:
        b, c = (jnp.pad(v, ((0, 0), (0, 0), (0, n - v.shape[-1]))) for v in (b, c))
    s = state.reshape(bsz, g, h // g, p, n)
    decay = jnp.exp(dt * a).reshape(bsz, g, h // g, 1)
    xg = x.reshape(bsz, g, h // g, p)
    dtx = dt.reshape(bsz, g, h // g, 1) * xg
    sc = jnp.sum(s * c[:, :, None, None, :], axis=-1)  # S C of the state that came in
    bc = jnp.sum(b * c, axis=-1)[:, :, None, None]
    y = decay * sc + bc * dtx + d.reshape(g, h // g, 1) * xg
    new = s * decay[..., None] + dtx[..., None] * b[:, :, None, None, :]
    return y.reshape(bsz, h, p), new.reshape(state.shape)


def ssd_chunked(state, x, dt, a, b, c, d, snap_col=None, block: int = BLOCK):
    """A chunk of T tokens a row, T a multiple of ``block``.  ``state``
    [R, H, P, N] float32; ``x`` [R, T, H, P]; ``dt`` [R, T, H], padding masked
    (``mask_padding``); ``a``, ``d`` [H]; ``b``, ``c`` [R, T, G, N].  Returns
    (y [R, T, H, P], the state after the chunk, the state after ``snap_col``
    [R] tokens of it: a multiple of ``block``; the state that came in where it
    is not positive or not given)."""
    r, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    k, nb, cs = h // g, t // block, block

    def blocks(v, *tail):  # [R, T, ...] -> [NB, R, ..., C] + tail
        v = v.astype(jnp.float32).reshape(r, nb, cs, *v.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(v, 2, -1 - len(tail)), 1, 0)

    xb = blocks(x.reshape(r, t, g, k, p), p)  # [NB, R, G, K, C, P]
    dtb = blocks(dt.reshape(r, t, g, k))  # [NB, R, G, K, C]
    bb, cb = blocks(b, n), blocks(c, n)  # [NB, R, G, C, N]
    ac = jnp.cumsum(dtb * a.reshape(g, k, 1), axis=-1)  # log decay from the block's start
    lower = jnp.tril(jnp.ones((cs, cs), bool))
    decay = jnp.exp(jnp.where(lower, ac[..., :, None] - ac[..., None, :], -jnp.inf))
    scores = jnp.einsum("...in,...jn->...ij", cb, bb, precision=HI)[:, :, :, None] * decay
    xdt = xb * dtb[..., None]
    y = jnp.einsum("...ij,...jp->...ip", scores, xdt, precision=HI) \
        + d.reshape(g, k, 1, 1) * xb
    c_in = jnp.exp(ac)  # a token's view of the state carried in
    x_out = xdt * jnp.exp(ac[..., -1:] - ac)[..., None]  # a token's share of the state carried out
    snap_col = jnp.zeros((r,), jnp.int32) if snap_col is None else snap_col

    def step(carry, xs):
        s, snap = carry
        i, cb_i, bb_i, c_in_i, x_out_i, a_end = xs
        y_in = jnp.einsum("rgkpn,rgcn->rgkcp", s, cb_i, precision=HI) * c_in_i[..., None]
        s = s * jnp.exp(a_end)[..., None, None] \
            + jnp.einsum("rgkcp,rgcn->rgkpn", x_out_i, bb_i, precision=HI)
        snap = jnp.where((snap_col == (i + 1) * cs)[:, None, None, None, None], s, snap)
        return (s, snap), y_in

    s0 = state.reshape(r, g, k, p, n)
    (s, snap), y_in = jax.lax.scan(
        step, (s0, s0), (jnp.arange(nb), cb, bb, c_in, x_out, ac[..., -1]))
    y = jnp.moveaxis(jnp.moveaxis(y + y_in, 0, 1), 4, 2)  # [R, NB, C, G, K, P]
    return y.reshape(r, t, h, p), s.reshape(state.shape), snap.reshape(state.shape)
