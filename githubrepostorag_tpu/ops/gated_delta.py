"""Gated DeltaNet (arXiv:2412.06464), the linear-attention layer of the
hybrid models (models/hybrid.py): a fixed-size matrix state a head and
sequence in place of keys and values, and a short causal convolution in
front of it.

Per head and token, with ``S`` [dk, dv] float32 (``dk`` and ``dv`` need not be
equal), ``q`` and ``k`` L2-normalised (``q`` scaled by dk^-1/2), ``g <= 0`` the
log of the decay and ``beta`` the write strength, in (0, 1) or, where a model
allows ``I - beta k k^T`` a negative eigenvalue, in (0, 2):

    S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t

Two forms of the same recurrence:

* ``gated_delta_step``: one token a row (the decode burst).  The state is
  read and written once; everything is elementwise or a reduction over it.
* ``gated_delta_chunked``: a prefill chunk in blocks of ``BLOCK`` tokens
  (the WY form of the delta rule).  Inside a block the 64 rank-one updates
  are one triangular system ``(I + A) U = beta V``, ``A`` the strictly lower
  part of ``(beta K K^T) * decay``; its inverse is built by halving (the
  inverse of a block-triangular matrix from its blocks' inverses) down to
  small blocks (``neumann_size``: 16 x 16 at ``beta <= 1``, 8 x 8 at ``beta
  <= 2``), each the finite Neumann product ``(I - A)(I + A^2)(I + A^4)...``
  of a nilpotent matrix: small products, no substitution loop of 64 steps.
  Across blocks the
  state is carried by a scan, so a chunk of 512 columns reads and writes
  its state 8 times and not 512.  The per-token scan the step form would
  give over such a chunk moves 4 MB of state a token, row and layer.

Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) is the same rule with the
decay a key CHANNEL and not a head: ``g`` [.., H, dk], ``S <- diag(exp(g_t))
S``.  Both forms take either shape of ``g`` and trace, for a head's scalar,
to what they always did.  The step form scales the state's rows instead of
the whole matrix.  The chunked form cannot keep ``decay = exp(gc_i - gc_j)``
outside ``K K^T``: it goes inside the contraction as ``(k_i exp(gc_i - r)) .
(k_j exp(r - gc_j))`` about a reference ``r``, and the second factor overflows
float32 unless ``r`` is near: with the gate bounded below (``g >= g_min`` a
token: what a model's lower-bounded gate is FOR) the factors of a pair are
taken from a reference token at most ``channel_span(g_min)`` tokens away (16
at -5: ``exp(75)``).  Inside a diagonal sub-block of that many tokens the
reference is the sub-block's first token; between sub-blocks it is the LATER
sub-block's first token, where both factors are at most one.

Tokens that are not real (the padding of a wave's row, past ``new_lens``)
arrive with ``k = 0``, ``beta = 0`` and ``g = 0`` (``mask_padding``): they
multiply the state by one and add zero to it, bit for bit.

``causal_conv`` / ``causal_conv_step`` are the depthwise convolution of
``taps`` inputs with its carried history (the last ``taps - 1`` inputs of
the sequence so far), followed by SiLU.

All products here run at ``Precision.HIGHEST`` in float32: they are a few
GFLOP a chunk (the experts' products are hundreds), and the triangular
inverse cancels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 64  # tokens of one block of the chunked form
HI = jax.lax.Precision.HIGHEST


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """x / sqrt(sum x^2 + eps) over the last axis (the published kernel's)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def mask_padding(live: jnp.ndarray, k, g, beta):
    """``live`` [R, T] marks real tokens; the others leave the state as it is
    (``g`` a head's [R, T, H] or a channel's [R, T, H, dk])."""
    g_live = live[..., None, None] if g.ndim == k.ndim else live[..., None]
    return (jnp.where(live[..., None, None], k, 0.0), jnp.where(g_live, g, 0.0),
            jnp.where(live[..., None], beta, 0.0))


def gated_delta_step(state, q, k, v, g, beta):
    """One token a row.  ``state`` [B, H, dk, dv] float32; ``q``, ``k``
    [B, H, dk]; ``v`` [B, H, dv]; ``g``, ``beta`` [B, H].  Returns
    (o [B, H, dv], new state).  Written so that the state is read twice and
    written once: ``S^T k`` and ``S^T q`` come from one pass over the state
    that came in (``o = exp(g) S^T q + (k . q) delta`` is ``S_new^T q``), the
    update is the second.

    ``state`` may be WIDER than ``v`` along its last axis: a pool stores its
    rows at a whole number of lane tiles (models/hybrid.lane_padded), lanes
    ``dv:`` zero.  The reductions then read lanes ``:dv`` alone, ``delta`` is
    padded with zeros, and the update is made, and returned, at the stored
    width: a padding lane comes out as ``0 * decay + k * 0``, zero bit for
    bit, so nobody cuts the padding off or writes it back, and the caller
    lays what it gets straight into the pool.  At equal widths this traces
    to what it always did."""
    if g.ndim == k.ndim:
        return _channel_step(state, q, k, v, g, beta)
    dv = v.shape[-1]
    read = state[..., :dv]  # the whole state at equal widths: traces to nothing
    decay = jnp.exp(g)[..., None]
    kv = decay * jnp.sum(read * k[..., None], axis=-2)
    qv = decay * jnp.sum(read * q[..., None], axis=-2)
    delta = beta[..., None] * (v - kv)
    o = qv + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    if state.shape[-1] != dv:
        delta = jnp.pad(delta, ((0, 0),) * (delta.ndim - 1) + ((0, state.shape[-1] - dv),))
    return o, state * decay[..., None] + k[..., None] * delta[..., None, :]


def _channel_step(state, q, k, v, g, beta):
    """``gated_delta_step`` with the decay a key channel (``g`` [B, H, dk]): the
    state's ROWS are scaled, so ``S^T k`` and ``S^T q`` of the decayed state are
    ``S^T (a k)`` and ``S^T (a q)`` of the one that came in, ``a = exp(g)``."""
    dv = v.shape[-1]
    read = state[..., :dv]
    a = jnp.exp(g)
    kv = jnp.sum(read * (a * k)[..., None], axis=-2)
    qv = jnp.sum(read * (a * q)[..., None], axis=-2)
    delta = beta[..., None] * (v - kv)
    o = qv + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    if state.shape[-1] != dv:
        delta = jnp.pad(delta, ((0, 0),) * (delta.ndim - 1) + ((0, state.shape[-1] - dv),))
    return o, state * a[..., None] + k[..., None] * delta[..., None, :]


# The Neumann product of an n x n block sums the powers (-a)^k, k < n, whose
# entries reach beta^k C(n - 1, k) where neighbouring keys are alike, while the
# inverse they cancel to stays of order one: terms up to (1 + beta)^(n - 1), of
# which float32 keeps 2^-24.  2^15 at n = 16 and beta <= 1 (held to 2e-5 at
# cosine 0.9 by tests/test_gated_delta.py); 3^15 at n = 16 and beta <= 2 read
# 0.2 - 0.9 of error (128 alike tokens, beta 1.9 - 2, no decay), so beta up to 2
# takes n = 8 (3^7).  ``NEUMANN_TERMS`` is the bound either keeps.  On a v5e n = 8
# is no slower at either model's shapes (PERF.md, PR 39: 0 - 3% faster); 16 stays
# at beta <= 1 for the compiled wave and the metric pattern that are pinned to it.
NEUMANN_MAX = 16
NEUMANN_TERMS = 2.0 ** 15


def neumann_size(beta_max: float = 1.0) -> int:
    """The largest power of two n, ``NEUMANN_MAX`` at the most, whose Neumann
    product's terms stay inside ``NEUMANN_TERMS`` at write strengths up to
    ``beta_max``: 16 at 1, 8 at 2."""
    n = NEUMANN_MAX
    while n > 2 and (1.0 + beta_max) ** (n - 1) > NEUMANN_TERMS:
        n //= 2
    return n


def _unit_lower_inverse(a: jnp.ndarray, neumann: int = NEUMANN_MAX) -> jnp.ndarray:
    """(I + a)^-1 for strictly lower-triangular ``a`` [..., C, C].  Halved
    down to ``neumann`` x ``neumann``: ``[[P, 0], [X, Q]]^-1 = [[P^-1,
    0], [-Q^-1 X P^-1, Q^-1]]``; a small block is the Neumann series of a
    nilpotent matrix, ``(I - a)(I + a^2)(I + a^4)...``.  The series alone over
    64 columns cancels badly when neighbouring keys are alike (terms up to
    C(63, k) in size: 4e-3 of error in float32 at cosine 0.9, beta <= 1)."""
    c = a.shape[-1]
    mm = lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y, precision=HI)  # noqa: E731
    if c > neumann:
        half = c // 2
        p = _unit_lower_inverse(a[..., :half, :half], neumann)
        q = _unit_lower_inverse(a[..., half:, half:], neumann)
        low = -mm(mm(q, a[..., half:, :half]), p)
        top = jnp.concatenate([p, jnp.zeros_like(low).swapaxes(-1, -2)], axis=-1)
        return jnp.concatenate([top, jnp.concatenate([low, q], axis=-1)], axis=-2)
    m = -a
    inv = jnp.eye(c, dtype=a.dtype) + m
    power = 2
    while power < c:
        m = mm(m, m)
        inv = inv + mm(inv, m)
        power *= 2
    return inv


CHANNEL_EXPONENT = 80.0  # the largest exponent a factor of the per-channel form may take


def channel_span(g_min: float, block: int = BLOCK) -> int:
    """Tokens of a diagonal sub-block of the per-channel chunked form: the
    largest power of two n, ``block`` at the most, with ``(n - 1) |g_min|``
    inside ``CHANNEL_EXPONENT`` (16 at -5)."""
    n = block
    while n > 1 and (n - 1) * abs(g_min) > CHANNEL_EXPONENT:
        n //= 2
    return n


def _channel_pairs(q, k, kb, gc, span: int):
    """``(beta K K^T) * decay`` and ``(Q K^T) * decay`` [..., C, C] of a block
    whose decay is a channel's: ``gc`` [..., C, dk] the log decay from the
    block's start.  Rows go a sub-block of ``span`` tokens at a time, about the
    sub-block's first token ``r``: the left factor ``x_i exp(gc_i - r)`` is at
    most one; the right, ``k_j exp(r - gc_j)``, is at most one before the
    sub-block, at most ``exp((span - 1) |g_min|)`` inside it, and zero after it
    (the strictly upper part, which nobody reads, and where it would overflow)."""
    c, dk = gc.shape[-2:]
    n = c // span
    lead = gc.shape[:-2]
    sub = lambda x: x.reshape(*lead, n, span, dk)  # noqa: E731
    ref = sub(gc)[..., :1, :]  # [..., n, 1, dk]
    left = jnp.exp(sub(gc) - ref)
    upto = (jnp.arange(c)[None, :] < (jnp.arange(n)[:, None] + 1) * span)[..., None]  # [n, C, 1]
    right = k[..., None, :, :] * jnp.exp(jnp.where(upto, ref - gc[..., None, :, :], -jnp.inf))
    pairs = lambda x: jnp.einsum(  # noqa: E731
        "...nik,...njk->...nij", sub(x) * left, right, precision=HI).reshape(*lead, c, c)
    return pairs(kb), pairs(q)


def gated_delta_chunked(state, q, k, v, g, beta, snap_col=None, block: int = BLOCK,
                        beta_max: float = 1.0, g_min: float | None = None):
    """A chunk of T tokens a row, T a multiple of ``block``.  ``state``
    [R, H, dk, dv] float32; ``q``, ``k`` [R, T, H, dk]; ``v`` [R, T, H, dv];
    ``g``, ``beta`` [R, T, H], padding masked (``mask_padding``); ``beta_max``
    the most ``beta`` can be (it sets the Neumann block, ``neumann_size``).  Returns
    (o [R, T, H, dv], the state after the chunk, the state after
    ``snap_col`` [R] tokens of it: a multiple of ``block``; the state that
    came in where it is not positive or not given).

    ``g`` [R, T, H, dk] is a decay a key channel (module docstring), bounded
    below by ``g_min`` a token."""
    if g.ndim == q.ndim:
        return _channel_chunked(state, q, k, v, g, beta, snap_col, block, beta_max, g_min)
    r, t, h, dk = q.shape
    n, c = t // block, block

    def blocks(x):  # [R, T, H, ...] -> [N, R, H, C, ...]
        x = x.reshape(r, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (blocks(x.astype(jnp.float32)) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # [N, R, H, C]: log decay from the block's start
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kb, vb = k * beta[..., None], v * beta[..., None]
    a = jnp.einsum("...ik,...jk->...ij", kb, k, precision=HI) * decay
    inv = _unit_lower_inverse(jnp.where(jnp.tril(lower, -1), a, 0.0), neumann_size(beta_max))
    u = jnp.einsum("...ij,...jv->...iv", inv, vb, precision=HI)
    w = jnp.einsum("...ij,...jk->...ik", inv, kb * jnp.exp(gc)[..., None], precision=HI)
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=HI) * decay
    q_in = q * jnp.exp(gc)[..., None]  # the query against the state carried in
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]  # a key's share of the state carried out
    snap_col = jnp.zeros((r,), jnp.int32) if snap_col is None else snap_col

    def step(carry, xs):
        s, snap = carry
        i, u_i, w_i, qk_i, q_i, k_i, g_end = xs
        v_new = u_i - jnp.einsum("rhck,rhkv->rhcv", w_i, s, precision=HI)
        o = jnp.einsum("rhck,rhkv->rhcv", q_i, s, precision=HI) \
            + jnp.einsum("rhij,rhjv->rhiv", qk_i, v_new, precision=HI)
        s = s * jnp.exp(g_end)[..., None, None] \
            + jnp.einsum("rhck,rhcv->rhkv", k_i, v_new, precision=HI)
        snap = jnp.where((snap_col == (i + 1) * c)[:, None, None, None], s, snap)
        return (s, snap), o

    (state, snap), o = jax.lax.scan(
        step, (state, state), (jnp.arange(n), u, w, qk, q_in, k_out, gc[..., -1]))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [R, N, C, H, dv]
    return o.reshape(r, t, h, -1), state, snap


def _channel_chunked(state, q, k, v, g, beta, snap_col, block, beta_max, g_min):
    """``gated_delta_chunked`` with the decay a key channel: the same WY form,
    the decay inside the two products of pairs (``_channel_pairs``) and along
    the key axis of everything that meets the carried state."""
    if g_min is None:
        raise ValueError("a decay a channel needs its lower bound a token (g_min)")
    r, t, h, dk = q.shape
    n, c = t // block, block

    def blocks(x):  # [R, T, H, ...] -> [N, R, H, C, ...]
        x = x.reshape(r, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (blocks(x.astype(jnp.float32)) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)  # [N, R, H, C, dk]: log decay from the block's start
    lower = jnp.tril(jnp.ones((c, c), bool))
    kb, vb = k * beta[..., None], v * beta[..., None]
    a, qk = _channel_pairs(q, k, kb, gc, channel_span(g_min, c))
    inv = _unit_lower_inverse(jnp.where(jnp.tril(lower, -1), a, 0.0), neumann_size(beta_max))
    u = jnp.einsum("...ij,...jv->...iv", inv, vb, precision=HI)
    w = jnp.einsum("...ij,...jk->...ik", inv, kb * jnp.exp(gc), precision=HI)
    qk = jnp.where(lower, qk, 0.0)
    q_in = q * jnp.exp(gc)  # the query against the state carried in
    k_out = k * jnp.exp(gc[..., -1:, :] - gc)  # a key's share of the state carried out
    snap_col = jnp.zeros((r,), jnp.int32) if snap_col is None else snap_col

    def step(carry, xs):
        s, snap = carry
        i, u_i, w_i, qk_i, q_i, k_i, g_end = xs
        v_new = u_i - jnp.einsum("rhck,rhkv->rhcv", w_i, s, precision=HI)
        o = jnp.einsum("rhck,rhkv->rhcv", q_i, s, precision=HI) \
            + jnp.einsum("rhij,rhjv->rhiv", qk_i, v_new, precision=HI)
        s = s * jnp.exp(g_end)[..., None] \
            + jnp.einsum("rhck,rhcv->rhkv", k_i, v_new, precision=HI)
        snap = jnp.where((snap_col == (i + 1) * c)[:, None, None, None], s, snap)
        return (s, snap), o

    (state, snap), o = jax.lax.scan(
        step, (state, state), (jnp.arange(n), u, w, qk, q_in, k_out, gc[..., -1, :]))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [R, N, C, H, dv]
    return o.reshape(r, t, h, -1), state, snap


def causal_conv(x, taps, weight, new_lens, snap_col=None, bias=None):
    """Depthwise causal convolution, then SiLU, over a chunk with its carried
    history.  ``x`` [R, T, C]; ``taps`` [R, K - 1, C]: the K - 1 inputs before
    the chunk; ``weight`` [C, K] (tap K - 1 multiplies the current input);
    ``bias`` [C] or None, added before the SiLU (Mamba-2's has one, the Gated
    DeltaNets' none); ``new_lens`` [R] real tokens.  Returns (y [R, T, C]
    float32, the history after the row's real tokens, the history after
    ``snap_col`` of them), the histories in ``taps``' type.  A row with no real token keeps its history."""
    kk = weight.shape[1]
    ext = jnp.concatenate([taps.astype(x.dtype), x], axis=1)  # [R, K - 1 + T, C]
    wf = weight.astype(jnp.float32)
    t = x.shape[1]
    y = sum(ext[:, j:j + t].astype(jnp.float32) * wf[:, j] for j in range(kk))
    if bias is not None:
        y = y + bias.astype(jnp.float32)

    def history(at):  # the K - 1 inputs that end at chunk column ``at``
        idx = at[:, None] + jnp.arange(kk - 1)[None, :]
        return jnp.take_along_axis(ext, idx[..., None], axis=1).astype(taps.dtype)

    snap_col = jnp.zeros_like(new_lens) if snap_col is None else snap_col
    return jax.nn.silu(y), history(new_lens), history(jnp.maximum(snap_col, 0))


def causal_conv_step(x, taps, weight, bias=None):
    """One token a row: ``x`` [B, C], ``taps`` [B, K - 1, C]; ``bias`` as
    ``causal_conv``'s.  Returns (y [B, C] float32, the history with ``x``
    shifted in)."""
    ext = jnp.concatenate([taps.astype(x.dtype), x[:, None]], axis=1)  # [B, K, C]
    y = jnp.einsum("bkc,ck->bc", ext.astype(jnp.float32), weight.astype(jnp.float32),
                   precision=HI)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y), ext[:, 1:].astype(taps.dtype)
