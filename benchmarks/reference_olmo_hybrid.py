"""The plain reference for Olmo-Hybrid (``olmo_hybrid``,
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json): the
published equations in float32 ``jax.numpy`` at ``Precision.HIGHEST``, with
weights made here from the seed.  It imports nothing of the program: no
kernel, no cache, no batching, no chunked form.

What it computes (``model``: HF ``config.json`` keys, with
``num_hidden_layers`` as cut), layer ``i`` by ``layer_types[i]``, a period of 3
``linear_attention`` then 1 ``full_attention``:

* block: ``h = x + RMSNorm(mixer(x)); out = h + RMSNorm(MLP(h))``, ``MLP(h) =
  W_d (SiLU(W_g h) * W_u h)``; plain norm weights (``y = x / rms(x) * w``);
* linear-attention mixer (Gated DeltaNet as ``flash-linear-attention``
  publishes it): ``q = W_q x``, ``k = W_k x``, ``v = W_v x``, each through a
  causal depthwise convolution of ``linear_conv_kernel_dim`` taps (no bias,
  left padded with zeros) and SiLU; ``z = W_g x``; ``a = W_a x``, ``b = W_b x``;
  ``beta = 2 sigmoid(b)`` with ``linear_allow_neg_eigval`` (else ``sigmoid(b)``),
  ``g = -exp(A_log) softplus(a + dt_bias)``; q and k L2-normalised a head
  (``x * rsqrt(sum x^2 + 1e-6)``), q times ``dk^-1/2``; one key head a value
  head; then THE RECURRENCE, one token at a time: ``S <- exp(g_t) S; S <- S +
  k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t`` with ``S`` [dk, dv] a head;
  ``y = W_o (RMSNorm_dv(o) * w * SiLU(z))``;
* full-attention mixer (Olmo 3's): as many key/value heads as query heads;
  ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over the WHOLE projection;
  causal softmax scaled ``head_dim^-1/2``; ``W_o``; queries a block at a time.

Departures from the published description.  THE THREE CONVENTIONS THE CONFIG
HAS NO KEY FOR, taken from the Olmo 2 / Olmo 3 family (the configuration's
file lists them under ``assumed``): (1) each sublayer's output is normed before
the residual add and there is no input norm; (2) the QK-norm runs over the
whole projection; (3) ``rope_parameters.rope_theta`` is null, so there is no
rotary and positions do not enter.  Shared with the program's initialiser:
``A_log`` is a ladder and not a draw from U(0, 16) (``decay_ladder``: so that
some heads remember thousands of tokens, as a trained model's do), and the
convolution's draw is scaled by 16 (``leaf_order``'s gain).

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in ``leaf_order``'s order; centred, std ~0.02, rounded
to bfloat16 (the type served), times the leaf's gain (a power of two) and
widened to float32.  ``control`` re-rounds every matrix to a precision below:
``"fp8"`` (float8 e4m3 under one scale a leaf).  Norm weights and ``dt_bias``
are one.
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


def dims(model: dict) -> dict:
    kinds = layer_kinds(model)
    return dict(
        d=model["hidden_size"], L=len(kinds), gdn=kinds.count("linear_attention"),
        full=kinds.count("full_attention"), h=model["num_attention_heads"],
        nkv=model[KV_HEADS],
        hd=model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"],
        hk=model["linear_num_key_heads"], hv=model["linear_num_value_heads"],
        dk=model["linear_key_head_dim"], dv=model["linear_value_head_dim"],
        taps=model["linear_conv_kernel_dim"], ff=model["intermediate_size"],
        channels=2 * model["linear_num_key_heads"] * model["linear_key_head_dim"]
        + model["linear_num_value_heads"] * model["linear_value_head_dim"],
        v=model["vocab_size"])


def layer_kinds(model: dict) -> list:
    """``layer_types`` of the layers that are kept (the first ``num_hidden_layers``)."""
    return list(model["layer_types"][:model["num_hidden_layers"]])


def leaf_order(model: dict) -> list:
    """(name, shape, gain) of the drawn leaves in draw order."""
    s = dims(model)
    d, L, P, G = s["d"], s["L"], s["full"], s["gdn"]
    return [
        ("embed", (s["v"], d), 1.0), ("lm_head", (d, s["v"]), 1.0),
        ("gdn.w_q", (G, d, s["hk"] * s["dk"]), 1.0), ("gdn.w_k", (G, d, s["hk"] * s["dk"]), 1.0),
        ("gdn.w_v", (G, d, s["hv"] * s["dv"]), 1.0), ("gdn.w_g", (G, d, s["hv"] * s["dv"]), 1.0),
        ("gdn.w_b", (G, d, s["hv"]), 1.0), ("gdn.w_a", (G, d, s["hv"]), 1.0),
        ("gdn.conv_w", (G, s["channels"], s["taps"]), 16.0),
        ("gdn.w_out", (G, s["hv"] * s["dv"], d), 1.0),
        ("attn.wq", (P, d, s["h"] * s["hd"]), 1.0), ("attn.wk", (P, d, s["nkv"] * s["hd"]), 1.0),
        ("attn.wv", (P, d, s["nkv"] * s["hd"]), 1.0), ("attn.wo", (P, s["h"] * s["hd"], d), 1.0),
        ("mlp.w_gate", (L, d, s["ff"]), 1.0), ("mlp.w_up", (L, d, s["ff"]), 1.0),
        ("mlp.wd", (L, s["ff"], d), 1.0),
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


@partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "eps", "beta_max"))
def gdn_mixer(x, w_q, w_k, w_v, w_g, w_b, w_a, conv_w, w_out, a_log, *, hk, hv, dk, dv, eps,
              beta_max):
    """x [T, d] (the residual stream, not normed) -> the mixer's output [T, d]."""
    t = x.shape[0]
    mm = lambda w: jnp.einsum("td,de->te", x, w, precision=HI)  # noqa: E731
    z = mm(w_g).reshape(t, hv, dv)
    beta = beta_max * jax.nn.sigmoid(mm(w_b))
    g = -jnp.exp(a_log) * jax.nn.softplus(mm(w_a) + 1.0)  # dt_bias = 1
    y = causal_conv(jnp.concatenate([mm(w_q), mm(w_k), mm(w_v)], axis=-1), conv_w)
    q = _l2(y[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    k = _l2(y[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    v = y[:, 2 * hk * dk:].reshape(t, hv, dv)
    r = hv // hk
    o, _ = recurrence(jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, g, beta)
    o = _rms(o, eps) * jax.nn.silu(z)  # the output norm's weight is one
    return jnp.einsum("te,ed->td", o.reshape(t, -1), w_out, precision=HI)


# ------------------------------------------------------------- full attention --

@partial(jax.jit, static_argnames=("h", "nkv", "hd", "eps", "q_block"))
def attn_mixer(x, wq, wk, wv, wo, *, h, nkv, hd, eps, q_block):
    """x [T, d] -> the mixer's output [T, d]; q and k normed over the whole
    projection (weights one), no rotary."""
    t = x.shape[0]
    q = _rms(jnp.einsum("td,de->te", x, wq, precision=HI), eps).reshape(t, h, hd)
    k = _rms(jnp.einsum("td,de->te", x, wk, precision=HI), eps).reshape(t, nkv, hd)
    v = jnp.einsum("td,de->te", x, wv, precision=HI).reshape(t, nkv, hd)
    k, v = jnp.repeat(k, h // nkv, axis=1), jnp.repeat(v, h // nkv, axis=1)
    outs = []
    for q0 in range(0, t, q_block):
        hi = min(t, q0 + q_block)
        sc = jnp.einsum("qhd,thd->hqt", q[q0:hi], k[:hi], precision=HI) * hd ** -0.5
        mask = jnp.arange(hi)[None, :] <= (q0 + jnp.arange(hi - q0))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", p, v[:hi], precision=HI))
    return jnp.einsum("te,ed->td", jnp.concatenate(outs, axis=0).reshape(t, -1), wo, precision=HI)


@jax.jit
def mlp(x, w_gate, w_up, wd):
    hid = jax.nn.silu(jnp.einsum("td,de->te", x, w_gate, precision=HI)) \
        * jnp.einsum("td,de->te", x, w_up, precision=HI)
    return jnp.einsum("te,ed->td", hid, wd, precision=HI)


# ---------------------------------------------------------------------- model --

def hidden_states(model: dict, w: Weights, ids, q_block: int = 256) -> jnp.ndarray:
    """One sequence's final hidden states [T, d] (before the last norm)."""
    s = dims(model)
    eps = float(model["rms_norm_eps"])
    beta_max = 2.0 if model.get("linear_allow_neg_eigval") else 1.0
    a_log = decay_ladder(model)
    hid = w.embed(np.asarray(ids))
    g = pi = 0
    for li, kind in enumerate(layer_kinds(model)):
        if kind == "linear_attention":
            y = gdn_mixer(hid, *(w.at(f"gdn.{n}", g) for n in
                                 ("w_q", "w_k", "w_v", "w_g", "w_b", "w_a", "conv_w", "w_out")),
                          a_log, hk=s["hk"], hv=s["hv"], dk=s["dk"], dv=s["dv"], eps=eps,
                          beta_max=beta_max)
            g += 1
        elif kind == "full_attention":
            y = attn_mixer(hid, w.at("attn.wq", pi), w.at("attn.wk", pi), w.at("attn.wv", pi),
                           w.at("attn.wo", pi), h=s["h"], nkv=s["nkv"], hd=s["hd"], eps=eps,
                           q_block=q_block)
            pi += 1
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        hid = hid + _rms(y, eps)
        hid = hid + _rms(mlp(hid, w.at("mlp.w_gate", li), w.at("mlp.w_up", li),
                             w.at("mlp.wd", li)), eps)
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
    chunks, step = [], -(-s["v"] // 8)
    for c0 in range(0, s["v"], step):
        cols = w.head_cols(c0, min(step, s["v"] - c0))
        chunks.append(np.asarray(jnp.einsum("nd,dv->nv", rows, cols, precision=HI)))
    flat = np.concatenate(chunks, axis=1)
    out, at = [], 0
    for p in positions:
        out.append(flat[at:at + len(p)])
        at += len(p)
    return out
