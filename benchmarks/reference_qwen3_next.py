"""The plain reference for Qwen3-Next (``Qwen3NextForCausalLM``,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct): the published
equations in float32 ``jax.numpy`` at ``Precision.HIGHEST``, with weights made
here from the seed.  It imports nothing of the program: no kernel, no cache,
no batching, no chunked form.

What it computes, for a share of the model (``model``: HF ``config.json``
keys, with ``num_hidden_layers`` / ``vocab_size`` as cut, ``num_experts`` the
router's width and ``experts_held`` = [first, past the last) of the routed
experts), layer ``i`` a Gated DeltaNet layer unless ``(i + 1) %
full_attention_interval == 0``:

* every norm but one is zero-centred: ``y = x / rms(x) * (1 + w)``;
  ``h += mixer(norm1(h)); h += moe(norm2(h))``;
* Gated DeltaNet mixer (``Qwen3NextGatedDeltaNet``): ``qkvz = x W_qkvz``
  grouped by key head into ``q | k | v (r value heads) | z (r)``, ``ba = x
  W_ba`` grouped into ``b (r) | a (r)``; ``[q | k | v]`` through a causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps (no bias, left
  padded with zeros), then SiLU; q and k repeated to the value heads,
  L2-normalised (``x * rsqrt(sum x^2 + 1e-6)``), q times ``dk^-1/2``; ``beta
  = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; then THE
  RECURRENCE, one token at a time (``torch_recurrent_gated_delta_rule``):
  ``S <- exp(g_t) S; S <- S + k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T
  q_t``; ``o <- rmsnorm(o) * w * silu(z)`` a head (plain weight); ``W_out``;
* gated attention (``Qwen3NextAttention``): ``W_q`` gives query and gate a
  head (``[q | gate]``), zero-centred RMSNorm over the head on q and k,
  rotary position on the first ``head_dim * partial_rotary_factor`` columns
  (rotate-half inside them), causal softmax scaled ``head_dim^-1/2``, ``o *
  sigmoid(gate)``, ``W_o``; queries a block at a time;
* mixture of experts (``Qwen3NextSparseMoeBlock``): float32 softmax over all
  the router's logits, the ``num_experts_per_tok`` largest renormalised to
  sum 1; a loop over the experts HELD adds ``w_e E_e(x)`` (``E = W_d(silu(W_g
  x) * W_u x)``), experts that are not held add nothing (their chips
  would); ``sigmoid(x w_sg) * shared(x)`` is added once.

Departures from the published model, shared with the program: the
multi-token-prediction module is not built; gate and up of an expert lie
side by side in one ``[d, 2f]`` matrix; ``A_log`` is a ladder and not a draw
from U(0, 16) (``decay_ladder``: so that some heads remember thousands of
tokens, as a trained model's do); the router's draw is scaled by 4 and the
convolution's by 16 (``leaf_order``'s gains).

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in ``leaf_order``'s order; centred, std ~0.02, rounded
to bfloat16 (the type served), times the leaf's gain (a power of two) and
widened to float32.  ``control`` re-rounds every matrix to a precision below:
``"fp8"`` (float8 e4m3 under one scale a leaf).  Norm weights are zero (one
for the Gated DeltaNet output norm), ``dt_bias`` one.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
W_MAX = 2147483648.0 * (0.02 / 1.24e9)  # the initialiser's range: uniform in +-0.0346


def dims(model: dict) -> dict:
    lo, hi = model["experts_held"]
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    L, interval = model["num_hidden_layers"], model["full_attention_interval"]
    return dict(
        d=model["hidden_size"], L=L, interval=interval, periods=L // interval,
        gdn=L // interval * (interval - 1), h=model["num_attention_heads"],
        nkv=model["num_key_value" "_heads"], hd=model["head_dim"],
        rot=int(model["head_dim"] * model["partial_rotary_factor"]), hk=hk, hv=hv, dk=dk, dv=dv,
        taps=model["linear_conv_kernel_dim"], channels=2 * hk * dk + hv * dv,
        e=model["num_experts"], k=model["num_experts_per_tok"], lo=lo, n=hi - lo,
        ffe=model["moe_intermediate_size"], ffs=model["shared_expert_intermediate_size"],
        v=model["vocab_size"])


def leaf_order(model: dict) -> list:
    """(name, shape, gain) of the drawn leaves in draw order."""
    s = dims(model)
    d, L, P, G = s["d"], s["L"], s["periods"], s["gdn"]
    return [
        ("embed", (s["v"], d), 1.0), ("lm_head", (d, s["v"]), 1.0),
        ("gdn.w_qkvz", (G, d, 2 * s["hk"] * s["dk"] + 2 * s["hv"] * s["dv"]), 1.0),
        ("gdn.w_ba", (G, d, 2 * s["hv"]), 1.0),
        ("gdn.conv_w", (G, s["channels"], s["taps"]), 16.0),
        ("gdn.w_out", (G, s["hv"] * s["dv"], d), 1.0),
        ("attn.wq", (P, d, s["h"] * 2 * s["hd"]), 1.0),
        ("attn.wk", (P, d, s["nkv"] * s["hd"]), 1.0),
        ("attn.wv", (P, d, s["nkv"] * s["hd"]), 1.0),
        ("attn.wo", (P, s["h"] * s["hd"], d), 1.0),
        ("moe.router", (L, d, s["e"]), 4.0),
        ("moe.e_wgu", (L, s["n"], d, 2 * s["ffe"]), 1.0),
        ("moe.e_wd", (L, s["n"], s["ffe"], d), 1.0),
        ("moe.s_wgu", (L, d, 2 * s["ffs"]), 1.0),
        ("moe.s_wd", (L, s["ffs"], d), 1.0),
        ("moe.s_gate", (L, d, 1), 1.0),
    ]


def decay_ladder(model: dict) -> jnp.ndarray:
    """``A_log`` [Hv]: ``A`` from 0.001 to 1 in equal ratios over the heads."""
    return jnp.linspace(math.log(1e-3), 0.0, model["linear_num_value_heads"], dtype=jnp.float32)


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
    bfloat16's seven).  int8 (127 steps to the largest weight) is no precision
    below bfloat16 for weights drawn uniformly from one range (PERF.md section
    4) and is not offered here."""
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
def _cols(salt, c0, n: int, d: int, v: int):
    """Columns [c0, c0 + n) of a [d, v] leaf."""
    r = jax.lax.iota(jnp.uint32, d)[:, None] * jnp.uint32(v)
    return _hash_bf16(r + jax.lax.iota(jnp.uint32, n)[None, :] + c0, salt)


class Weights:
    """The share's weights as a function of the seed, one slice at a time."""

    def __init__(self, model: dict, wseed: int, control: str | None = None) -> None:
        order = leaf_order(model)
        self.shape = {name: shape for name, shape, _ in order}
        self.gain = {name: gain for name, _, gain in order}
        self.salt = {name: jnp.uint32(s)
                     for (name, _, _), s in zip(order, salts(wseed, len(order)))}
        self.control = control

    def at(self, name: str, *index) -> jnp.ndarray:
        """The sub-array at the leading ``index`` of a leaf."""
        shape = self.shape[name]
        rest = shape[len(index):]
        offset = 0
        for i, n in zip(index, shape):
            offset = offset * n + i
        w = _block(self.salt[name], jnp.uint32(offset * math.prod(rest)), rest) * self.gain[name]
        return degrade(w, self.control, self.gain[name])

    def embed(self, ids: np.ndarray) -> jnp.ndarray:
        w = _rows(self.salt["embed"], jnp.asarray(ids, jnp.uint32), self.shape["embed"][1])
        return degrade(w, self.control)

    def head_cols(self, c0: int, n: int) -> jnp.ndarray:
        d, v = self.shape["lm_head"]
        return degrade(_cols(self.salt["lm_head"], jnp.uint32(c0), n, d, v), self.control)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope(x, pos, rot: int, theta: float):
    """x [S, heads, hd]: rotate-half on the leading ``rot`` columns; pos [S]."""
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], axis=-1)
    return jnp.concatenate([xr * jnp.cos(ang) + turned * jnp.sin(ang), rest], axis=-1)


# ----------------------------------------------------------- Gated DeltaNet --

def recurrence(q, k, v, g, beta, state=None):
    """The gated delta rule, one token at a time.  q, k [T, Hv, dk]; v [T, Hv,
    dv]; g, beta [T, Hv].  Returns (o [T, Hv, dv], the state after)."""
    t, hv, dk = q.shape
    state = jnp.zeros((hv, dk, v.shape[-1]), jnp.float32) if state is None else state

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        kv = jnp.einsum("hk,hkv->hv", k_t, s, precision=HI)
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - kv))[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=HI)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def causal_conv(x, weight):
    """x [T, C], weight [C, K]: y_t = sum_j w[:, j] x_{t - (K - 1) + j}, zeros
    before the sequence; then SiLU."""
    kk = weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x], axis=0)
    y = sum(padded[j:j + x.shape[0]] * weight[:, j] for j in range(kk))
    return jax.nn.silu(y)


@partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "eps"))
def gdn_mixer(x, w_qkvz, w_ba, conv_w, w_out, a_log, *, hk, hv, dk, dv, eps):
    """x [T, d] normed -> the mixer's output [T, d]."""
    t, r = x.shape[0], hv // hk
    qkvz = jnp.einsum("td,de->te", x, w_qkvz, precision=HI).reshape(t, hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk].reshape(t, -1), qkvz[..., dk:2 * dk].reshape(t, -1)
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, -1)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    ba = jnp.einsum("td,de->te", x, w_ba, precision=HI).reshape(t, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(t, hv))
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., r:].reshape(t, hv) + 1.0)  # dt_bias = 1
    y = causal_conv(jnp.concatenate([q, k, v], axis=-1), conv_w)
    q = _l2(y[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    k = _l2(y[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    v = y[:, 2 * hk * dk:].reshape(t, hv, dv)
    o, _ = recurrence(jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, g, beta)
    o = _rms(o, eps) * jax.nn.silu(z)  # the output norm's weight is one
    return jnp.einsum("te,ed->td", o.reshape(t, -1), w_out, precision=HI)


# ---------------------------------------------------------- gated attention --

@partial(jax.jit, static_argnames=("h", "nkv", "hd", "rot", "theta", "eps", "q_block"))
def attn_mixer(x, wq, wk, wv, wo, *, h, nkv, hd, rot, theta, eps, q_block):
    """x [T, d] normed -> the mixer's output [T, d]; the norms' weights are zero."""
    t = x.shape[0]
    pos = jnp.arange(t)
    qg = jnp.einsum("td,de->te", x, wq, precision=HI).reshape(t, h, 2 * hd)
    q, gate = _rope(_rms(qg[..., :hd], eps), pos, rot, theta), qg[..., hd:]
    k = _rope(_rms(jnp.einsum("td,de->te", x, wk, precision=HI).reshape(t, nkv, hd), eps),
              pos, rot, theta)
    v = jnp.einsum("td,de->te", x, wv, precision=HI).reshape(t, nkv, hd)
    k, v = jnp.repeat(k, h // nkv, axis=1), jnp.repeat(v, h // nkv, axis=1)
    outs = []
    for q0 in range(0, t, q_block):
        hi = min(t, q0 + q_block)
        sc = jnp.einsum("qhd,thd->hqt", q[q0:hi], k[:hi], precision=HI) * hd ** -0.5
        mask = jnp.arange(hi)[None, :] <= (q0 + jnp.arange(hi - q0))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", p, v[:hi], precision=HI))
    o = jnp.concatenate(outs, axis=0) * jax.nn.sigmoid(gate)
    return jnp.einsum("te,ed->td", o.reshape(t, -1), wo, precision=HI)


# -------------------------------------------------------------------- experts --

@jax.jit
def _swiglu(x, wgu, wd):
    f = wgu.shape[-1] // 2
    hid = jax.nn.silu(jnp.einsum("td,de->te", x, wgu[:, :f], precision=HI)) \
        * jnp.einsum("td,de->te", x, wgu[:, f:], precision=HI)
    return jnp.einsum("te,ed->td", hid, wd, precision=HI)


def route(x, router, top_k: int, norm: bool = True):
    """The dense weight matrix [T, E]: float32 softmax over all the router's
    logits, zero where an expert is not among the ``top_k`` largest."""
    probs = jax.nn.softmax(jnp.einsum("td,de->te", x, router, precision=HI), axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    w = jnp.where(probs >= kth, probs, 0.0)
    return w / w.sum(axis=-1, keepdims=True) if norm else w


def moe_layer(model: dict, x, router, expert, shared) -> jnp.ndarray:
    """x [T, d] normed -> the layer's feed-forward output for this share.
    ``expert(e)`` returns held expert ``e``'s (wgu, wd); ``shared`` is (wgu, wd,
    gate [d, 1]) or None (leave it out: the share test adds it once)."""
    s = dims(model)
    w = route(x, router, s["k"], bool(model.get("norm_topk_prob", True)))
    y = jnp.zeros_like(x)
    for e in range(s["n"]):
        y = y + w[:, s["lo"] + e][:, None] * _swiglu(x, *expert(e))
    if shared is not None:
        wgu, wd, gate = shared
        y = y + jax.nn.sigmoid(jnp.einsum("td,de->te", x, gate, precision=HI)) * _swiglu(x, wgu, wd)
    return y


# ---------------------------------------------------------------------- model --

def hidden_states(model: dict, w: Weights, ids, q_block: int = 256) -> jnp.ndarray:
    """One sequence's final hidden states [T, d] (before the last norm)."""
    s = dims(model)
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    a_log = decay_ladder(model)
    hid = w.embed(np.asarray(ids))
    for li in range(s["L"]):
        pi, j = divmod(li, s["interval"])
        x = _rms(hid, eps)
        if j < s["interval"] - 1:
            g = pi * (s["interval"] - 1) + j
            hid = hid + gdn_mixer(x, w.at("gdn.w_qkvz", g), w.at("gdn.w_ba", g),
                                  w.at("gdn.conv_w", g), w.at("gdn.w_out", g), a_log,
                                  hk=s["hk"], hv=s["hv"], dk=s["dk"], dv=s["dv"], eps=eps)
        else:
            hid = hid + attn_mixer(x, w.at("attn.wq", pi), w.at("attn.wk", pi),
                                   w.at("attn.wv", pi), w.at("attn.wo", pi), h=s["h"],
                                   nkv=s["nkv"], hd=s["hd"], rot=s["rot"], theta=theta, eps=eps,
                                   q_block=q_block)
        hid = hid + moe_layer(
            model, _rms(hid, eps), w.at("moe.router", li),
            lambda e, li=li: (w.at("moe.e_wgu", li, e), w.at("moe.e_wd", li, e)),
            (w.at("moe.s_wgu", li), w.at("moe.s_wd", li), w.at("moe.s_gate", li)))
    return hid


def logits_at(model: dict, wseed: int, sequences: list, positions: list,
              control: str | None = None, q_block: int = 256, pad_to: int = 128) -> list:
    """Float32 logits of each sequence at its own ``positions`` (position p
    gives the distribution of token p + 1), one sequence at a time,
    right-padded to a multiple of ``pad_to`` (fewer shapes to compile): every
    layer is causal, so padding after a position cannot reach it."""
    s = dims(model)
    w = Weights(model, wseed, control)
    eps = float(model["rms_norm_eps"])
    rows = []
    for seq, pos in zip(sequences, positions):
        ids = list(seq) + [0] * (-len(seq) % pad_to)
        rows.append(_rms(hidden_states(model, w, ids, q_block)[jnp.asarray(pos)], eps))
    rows = jnp.concatenate(rows)
    chunks, step = [], -(-s["v"] // 4)
    for c0 in range(0, s["v"], step):
        cols = w.head_cols(c0, min(step, s["v"] - c0))
        chunks.append(np.asarray(jnp.einsum("nd,dv->nv", rows, cols, precision=HI)))
    flat = np.concatenate(chunks, axis=1)
    out, at = [], 0
    for p in positions:
        out.append(flat[at:at + len(p)])
        at += len(p)
    return out
