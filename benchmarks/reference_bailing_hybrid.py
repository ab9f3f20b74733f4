"""The plain reference for Ling-3.0-flash (inclusionAI, ``model_type``
``bailing_hybrid``, https://huggingface.co/inclusionAI/Ling-3.0-flash): the
equations below in float32 ``jax.numpy`` at ``Precision.HIGHEST``, with weights
made here from the seed, a layer at a time.  It imports nothing of the
program: no kernel, no cache, no batching, no chunked form, no absorbed form.

What it computes, for a share of the model (``model``: the source's
``config.json`` keys, with ``num_hidden_layers`` / ``vocab_size`` /
``first_k_dense_replace`` as cut, ``num_experts`` the router's width,
``experts_held`` = [first, past the last) of the routed experts and, where a
cut states its own pattern, ``layer_kinds``, one letter a layer: ``R`` a Kimi
Delta Attention layer, ``A`` a latent-attention layer; absent, the LAST layer of
every ``layer_group_size`` is the latent one).  On the residual stream ``h``:
``h += mixer(rmsnorm(h)); h += ffn(rmsnorm(h))``, every norm's weight one.

* Kimi Delta Attention (Kimi Linear, arXiv:2510.26692, with this config's
  switches): ``q~, k~, v~ = W_q x, W_k x, W_v x`` (H heads of ``head_dim``);
  each through a causal depthwise convolution of ``short_conv_kernel_size`` taps
  (no bias, zeros before the sequence), then SiLU; ``q = l2norm(q~) dk^-1/2``,
  ``k = l2norm(k~)`` a head; ``a = W_f x`` (ONE full projection:
  ``no_kda_lora``); the log decay a key CHANNEL ``g = kda_lower_bound *
  sigmoid(exp(A_log_h) (a + dt_bias))`` in (-5, 0) (``kda_safe_gate``); ``beta =
  sigmoid(W_beta x)`` a head; then THE RECURRENCE, one token at a time: ``S <-
  diag(exp(g_t)) S; S <- S + k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t``, ``S``
  [dk, dv] a head; ``out = W_o (rmsnorm_head(o) * sigmoid(W_g x))``, the gate
  elementwise;
* latent attention (DeepSeek's MLA, ``q_lora_rank`` null), MATERIALISED: ``q =
  W_q x`` -> H x (nope | rope); ``[c | k_r] = W_kva x``, ``c <- rmsnorm(c)``;
  rotary position on ``q_rope`` and ``k_r`` in INTERLEAVED pairs (columns 2i and
  2i + 1), theta ``rope_theta``, no scaling, ``k_r`` shared by the heads; ``k_nope_h
  = W_uk,h c``, ``v_h = W_uv,h c`` for every key; causal softmax of ``(q_nope .
  k_nope + q_rope . k_r) / sqrt(nope + rope)``, a block of queries at a time;
  ``o_h <- o_h * sigmoid(W_gate x)_h`` (a gate a HEAD); ``W_o``;
* feed-forward: a dense SwiGLU in the first ``first_k_dense_replace`` layers;
  after them DeepSeek-V3's ``noaux_tc`` experts: ``s = sigmoid(W_r y)``, the
  choice on ``s + bias``: ``n_group`` groups, a group's score its two largest,
  the best ``topk_group`` groups, the ``num_experts_per_tok`` largest inside
  them; weights ``s_chosen / sum(s_chosen) * routed_scaling_factor``; a loop
  over the experts HELD adds ``w_e E_e(y)``, absent experts add nothing; the
  shared expert is added once, ungated.

``control`` is the next precision below (``"fp8"``: float8 e4m3 weights under
one scale a leaf) or a knock-out that breaks ONE thing the program must get
right: ``"scalar_decay"`` (a head's mean ``g`` on all its channels: what the
Gated DeltaNet code would compute), ``"unbounded_gate"`` (Kimi Linear's
published gate ``-exp(A_log) softplus(a + dt_bias)``), ``"no_head_gate"``,
``"no_group_limit"`` (the plain top k of all experts), ``"no_route_scale"`` (2.5
-> 1), ``"rotate_half"`` (half-split pairs for interleaved).

Departures from the published model, shared with the program: no
multi-token-prediction module; gate and up of an expert side by side in one
``[d, 2f]`` matrix; ``W_kvb`` as its two halves ``W_uk`` [H, nope, rank] and ``W_uv``
[H, rank, v]; ``A_log`` and ``dt_bias`` a ladder (``gate_ladder``); the router's
draw times 2, the convolution's times 16, the latent layers' ``W_q`` times 4 and
their ``W_o`` times 8 (``leaf_order``'s gains: at a plain draw the softmax over 25k
keys is flat and one latent layer of seven decides nothing).

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in ``leaf_order``'s order; centred, std ~0.02, rounded to
bfloat16 (the type served), times the leaf's gain and widened to float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
W_MAX = 2147483648.0 * (0.02 / 1.24e9)  # the initialiser's range: uniform in +-0.0346
KNOCK_OUTS = ("scalar_decay", "unbounded_gate", "no_head_gate", "no_group_limit",
              "no_route_scale", "rotate_half")


def kinds_of(model: dict) -> str:
    stated = model.get("layer_kinds")
    if stated:
        return stated
    return "".join("A" if (i + 1) % model["layer_group_size"] == 0 else "R"
                   for i in range(model["num_hidden_layers"]))


def dims(model: dict) -> dict:
    lo, hi = model["experts_held"]
    kinds = kinds_of(model)
    return dict(
        d=model["hidden_size"], L=model["num_hidden_layers"], kinds=kinds, G=kinds.count("R"),
        P=kinds.count("A"), D=model["first_k_dense_replace"], h=model["num_attention_heads"],
        dk=model["head_dim"], taps=model["short_conv_kernel_size"],
        lower=float(model["kda_lower_bound"]), rank=model["kv_lora_rank"],
        nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"], vd=model["v_head_dim"],
        theta=float(model["rope_theta"]), e=model["num_experts"], k=model["num_experts_per_tok"],
        ng=model["n_group"], tg=model["topk_group"],
        scale=float(model["routed_scaling_factor"]), lo=lo, n=hi - lo,
        ff=model["intermediate_size"], ffe=model["moe_intermediate_size"],
        ffs=model["moe_shared_expert_intermediate_size"], v=model["vocab_size"],
        eps=float(model["rms_norm_eps"]))


def leaf_order(model: dict) -> list:
    """(name, shape, gain) of the drawn leaves in draw order."""
    s = dims(model)
    d, G, P, D, M, h, dk = s["d"], s["G"], s["P"], s["D"], s["L"] - s["D"], s["h"], s["dk"]
    leaves = [
        ("embed", (s["v"], d), 1.0), ("lm_head", (d, s["v"]), 1.0),
        ("kda.w_qkv", (G, d, 3 * h * dk), 1.0), ("kda.w_f", (G, d, h * dk), 1.0),
        ("kda.w_g", (G, d, h * dk), 1.0), ("kda.w_beta", (G, d, h), 1.0),
        ("kda.conv_w", (G, 3 * h * dk, s["taps"]), 16.0), ("kda.w_out", (G, h * dk, d), 1.0),
        ("attn.wq", (P, d, h * (s["nope"] + s["rope"])), 4.0),
        ("attn.wkva", (P, d, s["rank"] + s["rope"]), 1.0),
        ("attn.wuk", (P, h, s["nope"], s["rank"]), 1.0),
        ("attn.wuv", (P, h, s["rank"], s["vd"]), 1.0),
        ("attn.w_gate", (P, d, h), 1.0), ("attn.wo", (P, h * s["vd"], d), 8.0),
    ]
    if D:
        leaves += [("dense.wgu", (D, d, 2 * s["ff"]), 1.0), ("dense.wd", (D, s["ff"], d), 1.0)]
    if M:
        leaves += [
            ("moe.router", (M, d, s["e"]), 2.0), ("moe.e_bias", (M, s["e"]), 1.0),
            ("moe.e_wgu", (M, s["n"], d, 2 * s["ffe"]), 1.0),
            ("moe.e_wd", (M, s["n"], s["ffe"], d), 1.0),
            ("moe.s_wgu", (M, d, 2 * s["ffs"]), 1.0), ("moe.s_wd", (M, s["ffs"], d), 1.0)]
    return leaves


def gate_ladder(model: dict):
    """(``A_log`` [H], ``dt_bias`` [H, dk]): ``A`` from 0.5 to 2 in equal ratios over
    the heads; ``A dt_bias`` from -8.5 to 2.5 in equal steps (a token's decay from
    0.999 to 0.01 at ``W_f x = 0``)."""
    h, dk = model["num_attention_heads"], model["head_dim"]
    a_log = jnp.linspace(math.log(0.5), math.log(2.0), h, dtype=jnp.float32)
    z0 = jnp.linspace(-8.5, 2.5, h, dtype=jnp.float32)
    return a_log, jnp.repeat((z0 / jnp.exp(a_log))[:, None], dk, axis=1)


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
    """Weights re-rounded to the control's precision: float8 e4m3 with the
    leaf's largest possible weight at 448 (three bits of mantissa against
    bfloat16's seven).  A knock-out keeps the weights."""
    if scheme != "fp8":
        return w
    s = W_MAX * gain / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


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
        """The sub-array at the leading ``index`` of a leaf (the selection bias,
        float32 in the program, is not re-rounded)."""
        shape = self.shape[name]
        rest = shape[len(index):]
        offset = 0
        for i, n in zip(index, shape):
            offset = offset * n + i
        w = _block(self.salt[name], jnp.uint32(offset * math.prod(rest)), rest) * self.gain[name]
        return w if name == "moe.e_bias" else degrade(w, self.control, self.gain[name])

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


def _mm(x, w):
    return jnp.einsum("td,de->te", x, w, precision=HI)


# ------------------------------------------------------ Kimi Delta Attention --

def recurrence(q, k, v, g, beta, state=None):
    """The delta rule with a decay a key channel, one token at a time.  q, k, g
    [T, H, dk]; v [T, H, dv]; beta [T, H].  Returns (o [T, H, dv], the state
    after)."""
    t, h, dk = q.shape
    state = jnp.zeros((h, dk, v.shape[-1]), jnp.float32) if state is None else state

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]
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


def kda_gate(a, a_log, dt_bias, lower: float, control: str | None = None):
    """The log decay [T, H, dk] from ``a = W_f x`` [T, H, dk]."""
    if control == "unbounded_gate":
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(a + dt_bias)
    else:
        g = lower * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * (a + dt_bias))
    if control == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    return g


@partial(jax.jit, static_argnames=("h", "dk", "lower", "eps", "control"))
def kda_mixer(x, w_qkv, w_f, w_g, w_beta, conv_w, w_out, a_log, dt_bias, *, h, dk, lower, eps,
              control=None):
    """x [T, d] normed -> the mixer's output [T, d]; the output norm's weight is one."""
    t = x.shape[0]
    y = causal_conv(_mm(x, w_qkv), conv_w)
    q, k, v = (y[:, i * h * dk:(i + 1) * h * dk].reshape(t, h, dk) for i in range(3))
    g = kda_gate(_mm(x, w_f).reshape(t, h, dk), a_log, dt_bias, lower, control)
    beta = jax.nn.sigmoid(_mm(x, w_beta))
    o, _ = recurrence(_l2(q) * dk ** -0.5, _l2(k), v, g, beta)
    o = _rms(o, eps) * jax.nn.sigmoid(_mm(x, w_g).reshape(t, h, dk))
    return _mm(o.reshape(t, -1), w_out)


# ---------------------------------------------------------- latent attention --

def _rope(x, pos, theta: float, interleaved: bool = True):
    """x [S, ..., r] rotated at positions ``pos`` [S]: pair i is columns 2i and
    2i + 1 (``rope_interleave``), or, as the knock-out, i and i + r / 2."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos[:, None].astype(jnp.float32) * inv  # [S, r/2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("h", "nope", "rope", "rank", "theta", "eps", "q_block",
                                   "control"))
def latent_mixer(x, wq, wkva, wuk, wuv, w_gate, wo, *, h, nope, rope, rank, theta, eps, q_block,
                 control=None):
    """x [S, d] normed -> the mixer's output [S, d], S a multiple of
    ``q_block``; keys and values up-projected for every position (not absorbed)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    inter = control != "rotate_half"
    q = _mm(x, wq).reshape(s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta, inter)
    ckv = _mm(x, wkva)
    c, k_rope = _rms(ckv[:, :rank], eps), _rope(ckv[:, rank:], pos, theta, inter)
    k_nope = jnp.einsum("tc,hnc->thn", c, wuk, precision=HI)
    v = jnp.einsum("tc,hcv->thv", c, wuv, precision=HI)
    scale = (nope + rope) ** -0.5

    def block(q0):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, q_block)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, q0, q_block)
        sc = (jnp.einsum("qhn,thn->hqt", qn, k_nope, precision=HI)
              + jnp.einsum("qhr,tr->hqt", qr, k_rope, precision=HI)) * scale
        mask = jnp.arange(s)[None, :] <= (q0 + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thv->qhv", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, s, q_block)).reshape(s, h, -1)
    if control != "no_head_gate":
        o = o * jax.nn.sigmoid(_mm(x, w_gate))[..., None]
    return _mm(o.reshape(s, -1), wo)


# -------------------------------------------------------------------- experts --

@jax.jit
def _swiglu(x, wgu, wd):
    f = wgu.shape[-1] // 2
    return _mm(jax.nn.silu(_mm(x, wgu[:, :f])) * _mm(x, wgu[:, f:]), wd)


def route(scores, bias, top_k: int, n_group: int, topk_group: int, scaling: float):
    """Group-limited top k, written out: ``scores`` [T, E] are the sigmoid
    affinities.  Returns the dense weight matrix [T, E] (zero where an expert is
    not chosen)."""
    t, e = scores.shape
    biased = scores + bias[None, :]
    if topk_group < n_group:
        groups = biased.reshape(t, n_group, e // n_group)
        group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(axis=-1)
        kth_group = jnp.sort(group_score, axis=-1)[:, -topk_group][:, None]
        allowed = jnp.repeat(group_score >= kth_group, e // n_group, axis=1)
        biased = jnp.where(allowed, biased, -jnp.inf)
    kth = jnp.sort(biased, axis=-1)[:, -top_k][:, None]
    w = jnp.where(biased >= kth, scores, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scaling


def moe_layer(model: dict, x, router, bias, expert, shared, control=None) -> jnp.ndarray:
    """x [T, d] normed -> the layer's feed-forward output for this share.
    ``expert(e)`` returns held expert ``e``'s (wgu, wd); ``shared`` is the shared
    expert's pair or None (leave it out: the share test adds it once)."""
    s = dims(model)
    scores = jax.nn.sigmoid(_mm(x, router))
    w = route(scores, bias, s["k"], s["ng"], s["ng"] if control == "no_group_limit" else s["tg"],
              1.0 if control == "no_route_scale" else s["scale"])
    y = jnp.zeros_like(x)
    for e in range(s["n"]):
        y = y + w[:, s["lo"] + e][:, None] * _swiglu(x, *expert(e))
    if shared is not None:
        y = y + _swiglu(x, *shared)
    return y


# ---------------------------------------------------------------------- model --

def hidden_states(model: dict, w: Weights, ids, control=None, q_block: int = 128) -> jnp.ndarray:
    """One sequence's final hidden states [T, d] (before the last norm); T a
    multiple of ``q_block``."""
    s = dims(model)
    eps = s["eps"]
    a_log, dt_bias = gate_ladder(model)
    hid = w.embed(np.asarray(ids))
    g = pi = 0
    for li, kind in enumerate(s["kinds"]):
        x = _rms(hid, eps)
        if kind == "R":
            hid = hid + kda_mixer(
                x, *(w.at(f"kda.{k}", g) for k in ("w_qkv", "w_f", "w_g", "w_beta", "conv_w",
                                                   "w_out")),
                a_log, dt_bias, h=s["h"], dk=s["dk"], lower=s["lower"], eps=eps, control=control)
            g += 1
        else:
            hid = hid + latent_mixer(
                x, *(w.at(f"attn.{k}", pi) for k in ("wq", "wkva", "wuk", "wuv", "w_gate", "wo")),
                h=s["h"], nope=s["nope"], rope=s["rope"], rank=s["rank"], theta=s["theta"],
                eps=eps, q_block=q_block, control=control)
            pi += 1
        y = _rms(hid, eps)
        if li < s["D"]:
            hid = hid + _swiglu(y, w.at("dense.wgu", li), w.at("dense.wd", li))
        else:
            mi = li - s["D"]
            hid = hid + moe_layer(
                model, y, w.at("moe.router", mi), w.at("moe.e_bias", mi),
                lambda e, mi=mi: (w.at("moe.e_wgu", mi, e), w.at("moe.e_wd", mi, e)),
                (w.at("moe.s_wgu", mi), w.at("moe.s_wd", mi)), control)
    return hid


def logits_at(model: dict, wseed: int, sequences: list, positions: list,
              control: str | None = None, q_block: int = 128) -> list:
    """Float32 logits of each sequence at its own ``positions`` (position p gives
    the distribution of token p + 1), one sequence at a time, all right-padded
    to one multiple of ``q_block`` (one shape to compile): every layer is causal,
    so padding after a position cannot reach it."""
    if control not in (None, "fp8", *KNOCK_OUTS):
        raise ValueError(f"unknown control {control!r}")
    s = dims(model)
    w = Weights(model, wseed, control)
    pad_to = -(-max(len(seq) for seq in sequences) // q_block) * q_block
    rows = []
    for seq, pos in zip(sequences, positions):
        ids = list(seq) + [0] * (pad_to - len(seq))
        rows.append(_rms(hidden_states(model, w, ids, control, q_block)[jnp.asarray(pos)],
                         s["eps"]))
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
