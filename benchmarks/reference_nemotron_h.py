"""The plain reference for Nemotron-H (``nemotron_h``,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json):
the published equations in float32 ``jax.numpy`` at ``Precision.HIGHEST``, with
weights made here from the seed.  It imports nothing of the program: no
kernel, no cache, no batching, and the recurrence ONE TOKEN AT A TIME, never
the chunked form.

What it computes (``model``: HF ``config.json`` keys, ``num_hidden_layers`` /
``vocab_size`` as cut, ``n_routed_experts`` the ROUTER's width, ``experts_held``
the range of experts this share computes): block ``i`` is ONE sublayer of the
kind ``hybrid_override_pattern[i]`` says, ``h = h + mixer(RMSNorm(h))``, plain
norm weights (``y = x / rms(x) * w``), eps ``layer_norm_epsilon``; then a final
norm and an untied head.

* ``M``, Mamba-2 (the published ``NemotronHMamba2Mixer``'s plain path):
  ``z | xBC | dt = in_proj(x)``, ``d_inner = mamba_num_heads * mamba_head_dim``;
  ``xBC = SiLU(conv(xBC) + b)``, a causal depthwise convolution of
  ``conv_kernel`` taps left padded with zeros; ``x [H, P] | B [G, N] | C [G, N] =
  xBC``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h`` of
  group ``h // (H / G)``: ``S <- exp(dt A) S + dt x (x) B; y = S C + D x`` with
  ``S`` [P, N]; ``y = RMSNorm_group(y * SiLU(z)) * w`` over each of the ``G``
  groups of ``d_inner / G`` columns (gate FIRST, ``norm_before_gate=False``);
  ``out_proj``;
* ``E``, experts: ``s = sigmoid(W_g x)`` in float32; the ``num_experts_per_tok``
  largest of ``s + e_score_correction_bias`` are chosen (``n_group`` =
  ``topk_group`` = 1: no group limit); their weights are the UNBIASED ``s``,
  normalised to sum 1, times ``routed_scaling_factor``; an expert is ``W_down
  relu(W_up x)^2`` (``mlp_hidden_act`` ``relu2``, not gated); one shared expert
  of the same form is added unweighted.  Experts outside ``experts_held`` add
  nothing: they are another chip's share;
* ``*``, attention: ``num_attention_heads`` query and ``num_key_value`` heads
  of ``head_dim``, no bias, causal softmax scaled ``head_dim^-1/2``, NO rotary
  (the published module applies none); queries a block at a time.

Departures from the published module: the residual stream is float32 here as
everything is (``residual_in_fp32`` is false in the config); ``rope_theta`` and
``partial_rotary_factor`` are read by nothing, as in the published module.
Shared with the program's initialiser: ``A`` is drawn from U(1, 16) and
``dt_bias`` is the inverse softplus of a log-uniform step in
[``time_step_min``, ``time_step_max``] floored at ``time_step_floor`` (the
family's initialiser), ``D`` and every norm weight one; the convolution's
draw is scaled by 16, its bias by 4, the router's by 2 (``leaf_order``'s
gains); ``e_score_correction_bias`` is a draw of std 0.02 and not zeros.

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in ``leaf_order``'s order; centred, std ~0.02, rounded
to bfloat16 (the type served), times the leaf's gain (a power of two) and
widened to float32.  ``control`` re-rounds every matrix to a precision below:
``"fp8"`` (float8 e4m3 under one scale a leaf); the scalars a head (``A_log``,
``dt_bias``) and the selection bias stay as they are.
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
EXACT = ("ssm.a_u", "ssm.dt_u", "moe.e_bias")  # leaves the control leaves alone


def kinds(model: dict) -> str:
    """The pattern's letters of the blocks that are kept."""
    return model["hybrid_override_pattern"][:model["num_hidden_layers"]]


def dims(model: dict) -> dict:
    k = kinds(model)
    lo, hi = model["experts_held"]
    di = model["mamba_num_heads"] * model["mamba_head_dim"]
    gn = model["n_groups"] * model["ssm_state_size"]
    return dict(
        d=model["hidden_size"], L=len(k), M=k.count("M"), A=k.count("*"), E=k.count("E"),
        h=model["num_attention_heads"], nkv=model[KV_HEADS], hd=model["head_dim"],
        mh=model["mamba_num_heads"], mp=model["mamba_head_dim"], n=model["ssm_state_size"],
        g=model["n_groups"], taps=model["conv_kernel"], di=di, c=di + 2 * gn,
        e=model["n_routed_experts"], k=model["num_experts_per_tok"], lo=lo, held=hi - lo,
        ffe=model["moe_intermediate_size"], ffs=model["moe_shared_expert_intermediate_size"],
        v=model["vocab_size"])


def leaf_order(model: dict) -> list:
    """(name, shape, gain) of the drawn leaves in draw order."""
    s = dims(model)
    d, M, A, E = s["d"], s["M"], s["A"], s["E"]
    return [
        ("embed", (s["v"], d), 1.0), ("lm_head", (d, s["v"]), 1.0),
        ("ssm.w_z", (M, d, s["di"]), 1.0), ("ssm.w_xbc", (M, d, s["c"]), 1.0),
        ("ssm.w_dt", (M, d, s["mh"]), 1.0), ("ssm.conv_w", (M, s["c"], s["taps"]), 16.0),
        ("ssm.conv_b", (M, s["c"]), 4.0), ("ssm.a_u", (M, s["mh"]), 1.0),
        ("ssm.dt_u", (M, s["mh"]), 1.0), ("ssm.w_out", (M, s["di"], d), 1.0),
        ("attn.wq", (A, d, s["h"] * s["hd"]), 1.0), ("attn.wk", (A, d, s["nkv"] * s["hd"]), 1.0),
        ("attn.wv", (A, d, s["nkv"] * s["hd"]), 1.0), ("attn.wo", (A, s["h"] * s["hd"], d), 1.0),
        ("moe.router", (E, d, s["e"]), 2.0), ("moe.e_bias", (E, s["e"]), 1.0),
        ("moe.e_wu", (E, s["held"], s["ffe"], d), 1.0),  # W_up as published: [out, in]
        ("moe.e_wd", (E, s["held"], s["ffe"], d), 1.0),
        ("moe.s_wu", (E, s["ffs"], d), 1.0), ("moe.s_wd", (E, s["ffs"], d), 1.0),
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
        return w if name in EXACT else degrade(w, self.control, self.gain[name])

    def embed(self, ids: np.ndarray) -> jnp.ndarray:
        w = _rows(self.salt["embed"], jnp.asarray(ids, jnp.uint32), self.shape["embed"][1])
        return degrade(w, self.control)

    def head_cols(self, c0: int, n: int) -> jnp.ndarray:
        d, v = self.shape["lm_head"]
        return degrade(_cols(self.salt["lm_head"], jnp.uint32(c0), n, d, v), self.control)


def ssm_scalars(model: dict, a_u, dt_u):
    """(``A_log``, ``dt_bias``) [heads] from two uniform draws in +-``W_MAX``:
    ``A`` from U(1, 16); the step log-uniform in [``time_step_min``,
    ``time_step_max``], floored, through the inverse softplus."""
    u = lambda x: x / (2.0 * W_MAX) + 0.5  # noqa: E731 - in [0, 1]
    lo, hi = math.log(model["time_step_min"]), math.log(model["time_step_max"])
    dt = jnp.maximum(jnp.exp(lo + u(dt_u) * (hi - lo)), model["time_step_floor"])
    return jnp.log(1.0 + 15.0 * u(a_u)), dt + jnp.log(-jnp.expm1(-dt))


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# ------------------------------------------------------------------ Mamba-2 --

def recurrence(x, dt, a, b, c, d_skip, state=None):
    """The state-space recurrence, one token at a time.  x [T, H, P]; dt
    [T, H]; a, d_skip [H]; b, c [T, G, N].  Returns (y [T, H, P], the state
    after [H, P, N])."""
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    state = jnp.zeros((h, p, n), jnp.float32) if state is None else state

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        bh, ch = jnp.repeat(b_t, h // g, axis=0), jnp.repeat(c_t, h // g, axis=0)  # [H, N]
        s = s * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        y = jnp.einsum("hpn,hn->hp", s, ch, precision=HI) + d_skip[:, None] * x_t
        return s, y

    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state


def causal_conv(x, weight, bias):
    """x [T, C], weight [C, K], bias [C]: y_t = b + sum_j w[:, j] x_{t - (K - 1) + j},
    zeros before the sequence; then SiLU."""
    kk = weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x], axis=0)
    y = sum(padded[j:j + x.shape[0]] * weight[:, j] for j in range(kk))
    return jax.nn.silu(y + bias)


@partial(jax.jit, static_argnames=("mh", "mp", "n", "g", "eps"))
def ssm_mixer(x, w_z, w_xbc, w_dt, conv_w, conv_b, w_out, a_log, dt_bias, *, mh, mp, n, g, eps):
    """x [T, d] normed -> the mixer's output [T, d].  ``D`` and the output
    norm's weight are one."""
    t = x.shape[0]
    mm = lambda w: jnp.einsum("td,de->te", x, w, precision=HI)  # noqa: E731
    z, dt = mm(w_z), jax.nn.softplus(mm(w_dt) + dt_bias)
    y = causal_conv(mm(w_xbc), conv_w, conv_b)
    di = mh * mp
    xs = y[:, :di].reshape(t, mh, mp)
    b = y[:, di:di + g * n].reshape(t, g, n)
    c = y[:, di + g * n:].reshape(t, g, n)
    o, _ = recurrence(xs, dt, -jnp.exp(a_log), b, c, jnp.ones((mh,), jnp.float32))
    o = o.reshape(t, di) * jax.nn.silu(z)  # gate first
    o = _rms(o.reshape(t, g, di // g), eps).reshape(t, di)  # then the norm, by group
    return jnp.einsum("te,ed->td", o, w_out, precision=HI)


# ---------------------------------------------------------------- attention --

@partial(jax.jit, static_argnames=("h", "nkv", "hd", "q_block"))
def attn_mixer(x, wq, wk, wv, wo, *, h, nkv, hd, q_block):
    """x [T, d] normed -> the mixer's output [T, d]; no bias, no rotary."""
    t = x.shape[0]
    q = jnp.einsum("td,de->te", x, wq, precision=HI).reshape(t, h, hd)
    k = jnp.einsum("td,de->te", x, wk, precision=HI).reshape(t, nkv, hd)
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


# ------------------------------------------------------------------ experts --

@jax.jit
def relu2_ffn(x, wu, wd):
    """``W_down relu(W_up x)^2``; ``wu`` [f, d] as published ([out, in]), ``wd`` [f, d]."""
    u = jax.nn.relu(jnp.einsum("td,ed->te", x, wu, precision=HI))
    return jnp.einsum("te,ed->td", u * u, wd, precision=HI)


def route(x, router, bias, top_k: int, scale: float, norm: bool = True):
    """The dense weight matrix [T, E]: sigmoid scores in float32; the ``top_k``
    largest of score + bias are chosen; a chosen expert's weight is its
    UNBIASED score, normalised over the chosen and scaled; zero elsewhere."""
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router, precision=HI))
    biased = scores + bias[None, :]
    kth = jnp.sort(biased, axis=-1)[:, -top_k][:, None]
    w = jnp.where(biased >= kth, scores, 0.0)
    if norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * scale


def moe_layer(model: dict, x, router, bias, expert, shared) -> jnp.ndarray:
    """x [T, d] normed -> the layer's output for this share.  ``expert(e)``
    returns held expert ``e``'s (w_up, w_down); ``shared`` is (w_up, w_down) or
    None (leave it out: the share test adds it once)."""
    s = dims(model)
    w = route(x, router, bias, s["k"], float(model["routed_scaling_factor"]),
              bool(model.get("norm_topk_prob", True)))
    y = jnp.zeros_like(x)
    for e in range(s["held"]):
        y = y + w[:, s["lo"] + e][:, None] * relu2_ffn(x, *expert(e))
    if shared is not None:
        y = y + relu2_ffn(x, *shared)
    return y


# -------------------------------------------------------------------- model --

def hidden_states(model: dict, w: Weights, ids, q_block: int = 256) -> jnp.ndarray:
    """One sequence's final hidden states [T, d] (before the last norm)."""
    s = dims(model)
    eps = float(model["layer_norm_epsilon"])
    hid = w.embed(np.asarray(ids))
    m = a = e = 0
    for kind in kinds(model):
        x = _rms(hid, eps)
        if kind == "M":
            a_log, dt_bias = ssm_scalars(model, w.at("ssm.a_u", m), w.at("ssm.dt_u", m))
            y = ssm_mixer(x, *(w.at(f"ssm.{n}", m) for n in
                               ("w_z", "w_xbc", "w_dt", "conv_w", "conv_b", "w_out")),
                          a_log, dt_bias, mh=s["mh"], mp=s["mp"], n=s["n"], g=s["g"], eps=eps)
            m += 1
        elif kind == "*":
            y = attn_mixer(x, w.at("attn.wq", a), w.at("attn.wk", a), w.at("attn.wv", a),
                           w.at("attn.wo", a), h=s["h"], nkv=s["nkv"], hd=s["hd"],
                           q_block=q_block)
            a += 1
        elif kind == "E":
            y = moe_layer(model, x, w.at("moe.router", e), w.at("moe.e_bias", e),
                          lambda i, e=e: (w.at("moe.e_wu", e, i), w.at("moe.e_wd", e, i)),
                          (w.at("moe.s_wu", e), w.at("moe.s_wd", e)))
            e += 1
        else:
            raise ValueError(f"unknown block kind {kind!r}")
        hid = hid + y
    return hid


def logits_at(model: dict, wseed: int, sequences: list, positions: list,
              control: str | None = None, q_block: int = 256, pad_to: int = 128) -> list:
    """Float32 logits of each sequence at its own ``positions`` (position p
    gives the distribution of token p + 1), one sequence at a time,
    right-padded to a multiple of ``pad_to`` (fewer shapes to compile): every
    layer is causal, so padding after a position cannot reach it."""
    s = dims(model)
    w = Weights(model, wseed, control)
    eps = float(model["layer_norm_epsilon"])
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
