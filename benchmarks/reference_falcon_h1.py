"""The plain reference for Falcon-H1 (``falcon_h1``,
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json):
the published equations in float32 ``jax.numpy`` at ``Precision.HIGHEST``, with
weights made here from the seed.  It imports nothing of the program: no
kernel, no cache, no batching, and the recurrence ONE TOKEN AT A TIME, never
the chunked form.

What it computes (``model``: HF ``config.json`` keys, ``num_hidden_layers`` as
cut), following ``transformers``' ``FalconH1DecoderLayer`` line by line (a test
holds this file to that module at a small size, the same weights in both):

    h_0 = Embed[ids] * embedding_multiplier
    x   = RMSNorm(h; input_layernorm, rms_norm_eps)        plain weights: x / rms(x) * w
    # Mamba-2 branch (FalconH1Mixer.torch_forward)
    u   = in_proj(x * ssm_in_multiplier) * mup_vector      z | x B C | dt; mup_vector =
                                                           ssm_multipliers[0..4] over z | x | B | C | dt
    xBC = SiLU(conv(xBC) + b)                              causal, depthwise, mamba_d_conv taps, zeros before
    dt  = softplus(dt + dt_bias);  A = -exp(A_log)
    S <- exp(dt A) S + dt x (x) B;  y = S C + D x          per head [mamba_d_head, mamba_d_state]; B, C of
                                                           head h are group h // (heads / mamba_n_groups)'s
    m   = out_proj(RMSNorm_group(y * SiLU(z)) * w) * ssm_out_multiplier
                                                           gate FIRST, the norm over each of mamba_n_groups
                                                           runs of mamba_d_ssm / groups columns
    # attention branch (FalconH1Attention): no bias, causal softmax scaled head_dim^-1/2
    q, k, v = W_q xa, (W_k xa) * key_multiplier, W_v xa    xa = x * attention_in_multiplier
    q, k = rope(q), rope(k)                                rotate-half over the WHOLE head, rope_theta
    a   = W_o attn(q, k, v) * attention_out_multiplier
    h   = h + (m + a)
    # feed-forward (FalconH1MLP) on x2 = RMSNorm(h; pre_ff_layernorm)
    h   = h + W_down(W_up x2 * SiLU(W_gate x2 * mlp_multipliers[0])) * mlp_multipliers[1]
    logits = W_head RMSNorm(h_L; final_layernorm) * lm_head_multiplier        untied head

Departures from the published module: everything is float32 (the module runs
in the checkpoint's bfloat16 with a float32 state and norms); the inverse
frequencies of the rotary tables are computed in float64 and rounded once to
float32 (the module raises ``rope_theta`` to a float32 power); the feed-forward
and the head are computed in blocks of columns (a sum's order, nothing else);
``time_step_limit`` (0, inf) clamps nothing and is left out.  Shared with the
program's initialiser: ``A`` is drawn from U(1, 16) and ``dt_bias`` is the
inverse softplus of a log-uniform step in [0.001, 0.1] floored at 1e-4 (the
Mamba-2 family's initialiser with the module's ``time_step_min`` / ``_max``;
the module's constructor leaves ``A = 1..heads`` and ``dt_bias = 1`` for a
checkpoint to overwrite), ``D`` and every norm weight one, and the per-leaf
gains of ``leaf_order`` (benchmarks/configs/falcon-h1-34b-bf16.json says why).

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in ``leaf_order``'s order; centred, std ~0.02, rounded
to bfloat16 (the type served), times the leaf's gain (a power of two) and
widened to float32.  ``control`` re-rounds every matrix to a precision below:
``"fp8"`` (float8 e4m3 under one scale a leaf; the scalars a head, ``A_log``
and ``dt_bias``, stay as they are).  The other controls knock a part of the
layer out, to show that the comparison sees it: ``"no_ssm"``, ``"no_attn"``,
``"no_mlp"`` (the branch adds nothing), ``"no_state"`` (``y = D x``: the state
is never read).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
W_MAX = 2147483648.0 * (0.02 / 1.24e9)  # the initialiser's range: uniform in +-0.0346
KV_HEADS = "num_key_value" "_heads"  # spelt in two parts: tests/benchmarks/test_bench_families.py
EXACT = ("ssm.a_u", "ssm.dt_u")  # leaves the precision control leaves alone
KNOCK_OUTS = ("no_ssm", "no_attn", "no_mlp", "no_state")
TIME_STEP = (0.001, 0.1, 1e-4)  # the published module's time_step_min, _max; the family's floor
FF_BLOCK = 3072  # columns of the feed-forward a block (21,504 = 7 blocks)
HEAD_BLOCK = 16384  # columns of the head a block


def dims(model: dict) -> dict:
    mh, mp = model["mamba_n_heads"], model["mamba_d_head"]
    if model["mamba_d_ssm"] != mh * mp:
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    gn = model["mamba_n_groups"] * model["mamba_d_state"]
    return dict(
        d=model["hidden_size"], L=model["num_hidden_layers"], ff=model["intermediate_size"],
        h=model["num_attention_heads"], nkv=model[KV_HEADS], hd=model["head_dim"],
        mh=mh, mp=mp, n=model["mamba_d_state"], g=model["mamba_n_groups"],
        taps=model["mamba_d_conv"], di=mh * mp, c=mh * mp + 2 * gn, v=model["vocab_size"])


def leaf_order(model: dict) -> list:
    """(name, shape, gain) of the drawn leaves in draw order."""
    s = dims(model)
    d, L, ff = s["d"], s["L"], s["ff"]
    return [
        ("embed", (s["v"], d), 1.0), ("lm_head", (d, s["v"]), 1.0),
        ("ssm.w_z", (L, d, s["di"]), 8.0), ("ssm.w_xbc", (L, d, s["c"]), 16.0),
        ("ssm.w_dt", (L, d, s["mh"]), 8.0), ("ssm.conv_w", (L, s["c"], s["taps"]), 16.0),
        ("ssm.conv_b", (L, s["c"]), 4.0), ("ssm.a_u", (L, s["mh"]), 1.0),
        ("ssm.dt_u", (L, s["mh"]), 1.0), ("ssm.w_out", (L, s["di"], d), 1.0),
        ("attn.wq", (L, d, s["h"] * s["hd"]), 1.0), ("attn.wk", (L, d, s["nkv"] * s["hd"]), 128.0),
        ("attn.wv", (L, d, s["nkv"] * s["hd"]), 1.0), ("attn.wo", (L, s["h"] * s["hd"], d), 4.0),
        ("mlp.w_gate", (L, d, ff), 4.0), ("mlp.w_up", (L, d, ff), 1.0),
        ("mlp.wd", (L, ff, d), 4.0),
    ]


def salts(wseed: int, n: int) -> list:
    s = (wseed * 40503 + 12345) & 0xFFFFFFFF
    out = []
    for _ in range(n):
        s = (s * 747796405 + 1) & 0xFFFFFFFF
        out.append(s)
    return out


def _hash_bf16(i, salt):
    """Element ``i`` (uint32 flat index) of a leaf, before its gain."""
    h = i * jnp.uint32(2654435761) + salt
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return ((h.astype(jnp.float32) - 2147483648.0) * (0.02 / 1.24e9)).astype(
        jnp.bfloat16).astype(jnp.float32)


def degrade(w: jnp.ndarray, scheme: str | None, gain: float = 1.0) -> jnp.ndarray:
    """Weights re-rounded to the control's precision.  fp8: float8 e4m3 with
    the leaf's largest possible weight at 448 (three bits of mantissa against
    bfloat16's seven)."""
    if scheme is None:
        return w
    if scheme == "fp8":
        s = W_MAX * gain / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control {scheme!r}")


@partial(jax.jit, static_argnames=("shape",))
def _block(salt, offset, shape: tuple):
    """Elements [offset, offset + prod(shape)) of a leaf's flat sequence."""
    return _hash_bf16(jax.lax.iota(jnp.uint32, math.prod(shape)) + offset, salt).reshape(shape)


@partial(jax.jit, static_argnames=("d",))
def _rows(salt, ids, d: int):
    return _hash_bf16(ids[..., None] * jnp.uint32(d) + jax.lax.iota(jnp.uint32, d), salt)


@partial(jax.jit, static_argnames=("n", "d", "v"))
def _cols(salt, offset, c0, n: int, d: int, v: int):
    """Columns [c0, c0 + n) of the [d, v] matrix that starts at ``offset`` of a leaf."""
    r = jax.lax.iota(jnp.uint32, d)[:, None] * jnp.uint32(v)
    return _hash_bf16(offset + r + jax.lax.iota(jnp.uint32, n)[None, :] + c0, salt)


class Weights:
    """The model's weights as a function of the seed, one slice at a time."""

    def __init__(self, model: dict, wseed: int, control: str | None = None) -> None:
        order = leaf_order(model)
        self.shape = {name: shape for name, shape, _ in order}
        self.gain = {name: gain for name, _, gain in order}
        self.salt = {name: jnp.uint32(s)
                     for (name, _, _), s in zip(order, salts(wseed, len(order)))}
        self.control = control

    def _finish(self, name: str, w):
        w = w * self.gain[name]
        return w if name in EXACT else degrade(w, self.control, self.gain[name])

    def at(self, name: str, *index) -> jnp.ndarray:
        """The sub-array at the leading ``index`` of a leaf."""
        shape = self.shape[name]
        rest = shape[len(index):]
        offset = 0
        for i, n in zip(index, shape):
            offset = offset * n + i
        return self._finish(
            name, _block(self.salt[name], jnp.uint32(offset * math.prod(rest)), rest))

    def rows(self, name: str, layer: int, r0: int, n: int) -> jnp.ndarray:
        """Rows [r0, r0 + n) of layer ``layer`` of a [L, rows, cols] leaf."""
        _, rows, cols = self.shape[name]
        return self._finish(name, _block(
            self.salt[name], jnp.uint32((layer * rows + r0) * cols), (n, cols)))

    def cols(self, name: str, layer: int, c0: int, n: int) -> jnp.ndarray:
        """Columns [c0, c0 + n) of layer ``layer`` of a [L, rows, cols] leaf."""
        _, rows, cols = self.shape[name]
        return self._finish(name, _cols(
            self.salt[name], jnp.uint32(layer * rows * cols), jnp.uint32(c0), n, rows, cols))

    def embed(self, ids: np.ndarray) -> jnp.ndarray:
        w = _rows(self.salt["embed"], jnp.asarray(ids, jnp.uint32), self.shape["embed"][1])
        return degrade(w, self.control)

    def head_cols(self, c0: int, n: int) -> jnp.ndarray:
        d, v = self.shape["lm_head"]
        return degrade(_cols(self.salt["lm_head"], jnp.uint32(0), jnp.uint32(c0), n, d, v),
                       self.control)


def ssm_scalars(a_u, dt_u):
    """(``A_log``, ``dt_bias``) [heads] from two uniform draws in +-``W_MAX``:
    ``A`` from U(1, 16); the step log-uniform in ``TIME_STEP``'s range,
    floored, through the inverse softplus."""
    u = lambda x: x / (2.0 * W_MAX) + 0.5  # noqa: E731 - in [0, 1]
    lo, hi = math.log(TIME_STEP[0]), math.log(TIME_STEP[1])
    dt = jnp.maximum(jnp.exp(lo + u(dt_u) * (hi - lo)), TIME_STEP[2])
    return jnp.log(1.0 + 15.0 * u(a_u)), dt + jnp.log(-jnp.expm1(-dt))


def mup_vector(model: dict) -> jnp.ndarray:
    """The published ``compute_mup_vector``: ``ssm_multipliers`` over the runs
    z | x | B | C | dt of in_proj's columns."""
    s = dims(model)
    gn = s["g"] * s["n"]
    runs = (s["di"], s["di"], gn, gn, s["mh"])
    return jnp.concatenate([jnp.full((n,), m, jnp.float32)
                            for n, m in zip(runs, model["ssm_multipliers"])])


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# ------------------------------------------------------------------ Mamba-2 --

def recurrence(x, dt, a, b, c, d_skip, read_state: bool = True):
    """The state-space recurrence, one token at a time.  x [T, H, P]; dt
    [T, H]; a, d_skip [H]; b, c [T, G, N].  Returns y [T, H, P].
    ``read_state`` false is the ``no_state`` knock-out: ``y = D x``."""
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        bh, ch = jnp.repeat(b_t, h // g, axis=0), jnp.repeat(c_t, h // g, axis=0)  # [H, N]
        s = s * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        y = jnp.einsum("hpn,hn->hp", s, ch, precision=HI) + d_skip[:, None] * x_t
        return s, y

    if not read_state:
        return d_skip[None, :, None] * x
    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32), (x, dt, b, c))
    return y


def causal_conv(x, weight, bias):
    """x [T, C], weight [C, K], bias [C]: y_t = b + sum_j w[:, j] x_{t - (K - 1) + j},
    zeros before the sequence; then SiLU."""
    kk = weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x], axis=0)
    y = sum(padded[j:j + x.shape[0]] * weight[:, j] for j in range(kk))
    return jax.nn.silu(y + bias)


@partial(jax.jit, static_argnames=("mh", "mp", "n", "g", "eps", "read_state"))
def ssm_mixer(x, w_z, w_xbc, w_dt, conv_w, conv_b, w_out, a_log, dt_bias, mup, in_mult, *,
              mh, mp, n, g, eps, read_state=True):
    """x [T, d] normed -> the mixer's output [T, d], before
    ``ssm_out_multiplier``.  ``D`` and the output norm's weight are one."""
    t, di = x.shape[0], mh * mp
    xin = x * in_mult
    mm = lambda w: jnp.einsum("td,de->te", xin, w, precision=HI)  # noqa: E731
    z = mm(w_z) * mup[:di]
    xbc = mm(w_xbc) * mup[di:di + w_xbc.shape[1]]
    dt = jax.nn.softplus(mm(w_dt) * mup[di + w_xbc.shape[1]:] + dt_bias)
    y = causal_conv(xbc, conv_w, conv_b)
    xs = y[:, :di].reshape(t, mh, mp)
    b = y[:, di:di + g * n].reshape(t, g, n)
    c = y[:, di + g * n:].reshape(t, g, n)
    o = recurrence(xs, dt, -jnp.exp(a_log), b, c, jnp.ones((mh,), jnp.float32), read_state)
    o = o.reshape(t, di) * jax.nn.silu(z)  # gate first
    o = _rms(o.reshape(t, g, di // g), eps).reshape(t, di)  # then the norm, by group
    return jnp.einsum("te,ed->td", o, w_out, precision=HI)


# ---------------------------------------------------------------- attention --

def rope_tables(positions, hd: int, theta: float):
    """cos, sin [T, hd], float32: the inverse frequencies in float64, rounded once."""
    inv = (1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)).astype(np.float32)
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """Rotate-half over the whole head: x [T, heads, hd]."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


@partial(jax.jit, static_argnames=("h", "nkv", "hd", "q_block"))
def attn_mixer(x, wq, wk, wv, wo, cos, sin, in_mult, key_mult, *, h, nkv, hd, q_block):
    """x [T, d] normed -> the mixer's output [T, d], before
    ``attention_out_multiplier``."""
    t = x.shape[0]
    xa = x * in_mult
    q = jnp.einsum("td,de->te", xa, wq, precision=HI).reshape(t, h, hd)
    k = jnp.einsum("td,de->te", xa, wk, precision=HI).reshape(t, nkv, hd) * key_mult
    v = jnp.einsum("td,de->te", xa, wv, precision=HI).reshape(t, nkv, hd)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    k, v = jnp.repeat(k, h // nkv, axis=1), jnp.repeat(v, h // nkv, axis=1)
    outs = []
    for q0 in range(0, t, q_block):
        hi = min(t, q0 + q_block)
        sc = jnp.einsum("qhd,thd->hqt", q[q0:hi], k[:hi], precision=HI) * hd ** -0.5
        mask = jnp.arange(hi)[None, :] <= (q0 + jnp.arange(hi - q0))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", p, v[:hi], precision=HI))
    return jnp.einsum("te,ed->td", jnp.concatenate(outs, axis=0).reshape(t, -1), wo, precision=HI)


# ------------------------------------------------------------- feed-forward --

@jax.jit
def _ff_block(x, w_gate, w_up, wd, gate_mult):
    """One block of the feed-forward's columns: its share of W_down's input."""
    gate = jnp.einsum("td,df->tf", x, w_gate, precision=HI) * gate_mult
    up = jnp.einsum("td,df->tf", x, w_up, precision=HI)
    return jnp.einsum("tf,fd->td", up * jax.nn.silu(gate), wd, precision=HI)


def feed_forward(model: dict, w: Weights, layer: int, x) -> jnp.ndarray:
    """x [T, d] normed -> the SwiGLU's output [T, d], both multipliers applied;
    the weights a block of ``FF_BLOCK`` columns at a time (a leaf is 440 MB in
    float32 at the published widths)."""
    ff = dims(model)["ff"]
    gate_mult, down_mult = model["mlp_multipliers"]
    y = jnp.zeros_like(x)
    for c0 in range(0, ff, FF_BLOCK):
        n = min(FF_BLOCK, ff - c0)
        y = y + _ff_block(x, w.cols("mlp.w_gate", layer, c0, n), w.cols("mlp.w_up", layer, c0, n),
                          w.rows("mlp.wd", layer, c0, n), gate_mult)
    return y * down_mult


# -------------------------------------------------------------------- model --

def hidden_states(model: dict, w: Weights, ids, q_block: int = 256,
                  knock: str | None = None) -> jnp.ndarray:
    """One sequence's final hidden states [T, d] (before the last norm)."""
    s = dims(model)
    eps = float(model["rms_norm_eps"])
    hid = w.embed(np.asarray(ids)) * model["embedding_multiplier"]
    cos, sin = rope_tables(np.arange(len(ids)), s["hd"], float(model["rope_theta"]))
    mup = mup_vector(model)
    for i in range(s["L"]):
        x = _rms(hid, eps)
        mixed = jnp.zeros_like(hid)
        if knock != "no_ssm":
            a_log, dt_bias = ssm_scalars(w.at("ssm.a_u", i), w.at("ssm.dt_u", i))
            mixed = mixed + model["ssm_out_multiplier"] * ssm_mixer(
                x, *(w.at(f"ssm.{n}", i) for n in
                     ("w_z", "w_xbc", "w_dt", "conv_w", "conv_b", "w_out")),
                a_log, dt_bias, mup, model["ssm_in_multiplier"], mh=s["mh"], mp=s["mp"],
                n=s["n"], g=s["g"], eps=eps, read_state=knock != "no_state")
        if knock != "no_attn":
            mixed = mixed + model["attention_out_multiplier"] * attn_mixer(
                x, w.at("attn.wq", i), w.at("attn.wk", i), w.at("attn.wv", i), w.at("attn.wo", i),
                cos, sin, model["attention_in_multiplier"], model["key_multiplier"],
                h=s["h"], nkv=s["nkv"], hd=s["hd"], q_block=q_block)
        hid = hid + mixed
        if knock != "no_mlp":
            hid = hid + feed_forward(model, w, i, _rms(hid, eps))
    return hid


def logits_at(model: dict, wseed: int, sequences: list, positions: list,
              control: str | None = None, q_block: int = 256, pad_to: int = 128) -> list:
    """Float32 logits of each sequence at its own ``positions`` (position p
    gives the distribution of token p + 1), one sequence at a time,
    right-padded to a multiple of ``pad_to`` (fewer shapes to compile): every
    layer is causal, so padding after a position cannot reach it."""
    s = dims(model)
    knock = control if control in KNOCK_OUTS else None  # a knock-out leaves the weights alone
    w = Weights(model, wseed, None if knock else control)
    eps = float(model["rms_norm_eps"])
    rows = []
    for seq, pos in zip(sequences, positions):
        ids = list(seq) + [0] * (-len(seq) % pad_to)
        rows.append(_rms(hidden_states(model, w, ids, q_block, knock)[jnp.asarray(pos)], eps))
    rows = jnp.concatenate(rows)
    chunks = []
    for c0 in range(0, s["v"], HEAD_BLOCK):
        cols = w.head_cols(c0, min(HEAD_BLOCK, s["v"] - c0))
        chunks.append(np.asarray(jnp.einsum("nd,dv->nv", rows, cols, precision=HI)))
    flat = np.concatenate(chunks, axis=1) * model["lm_head_multiplier"]
    out, at = [], 0
    for p in positions:
        out.append(flat[at:at + len(p)])
        at += len(p)
    return out
