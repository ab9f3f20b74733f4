"""The plain reference for Cohere2-MoE (``model_type`` ``cohere2_moe``, Command
A+; the dense sibling's published module is ``transformers.models.cohere2``,
which tests/test_cohere2_moe.py holds this file to): the layer equations in
float32 ``jax.numpy`` at ``Precision.HIGHEST``, with weights made here from
the seed.  No kernel, no cache, no batching.  It imports nothing of the
program.

What it computes, for a share of the model (``model``: the keys of the
published ``config.json``, with ``num_hidden_layers`` / ``vocab_size`` as cut,
``num_experts`` the router's width and ``experts_held`` = [first, past the
last) of the routed experts), on the residual stream ``h``:

    x = (h - mean h) / sqrt(var h + eps) * w          one norm a layer, no bias
    q, k, v = W_q x, W_k x, W_v x                     no bias, no QK norm, scale head_dim^-0.5
    sliding layer: q, k rotated in INTERLEAVED pairs (columns 2i, 2i + 1), whole
                   head, theta ``rope_theta``; query i sees key j iff 0 <= i - j < sliding_window
    global layer:  no positional term; plain causal
    a = W_o attn(q, k, v)
    s = sigmoid(W_r x); T = top-k of s; w_e = s_e / sum_T s
    f = sum_{e in T, e held} w_e E_e(x) + (1 / S) sum_{s < S} S_s(x)
    h = h + a + f                                     both branches read the same x
    logits = Embed LayerNorm(h_L) * logit_scale       the head is the embedding, tied

``layer_switch - 1`` sliding layers, then a global one, and so on.  Experts
that are not held add nothing (their chips would); the shared experts are kept
APART here, four products and a mean (the program stores them as one).
Attention runs a kv head's group at a time and a block of queries at a time,
so that a 25k-token prompt at 128 heads fits beside the engine.

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in ``leaf_order``'s order (restated from the
configuration's ``weights.init``); centred, std ~0.02 times the leaf's gain,
rounded to bfloat16 (the type served) and widened to float32.

Controls (``control``): ``"fp8"`` re-rounds every weight matrix to float8 e4m3
under one scale, the next precision below the one the configuration states;
the others knock one piece of the mathematics out, and the comparison must
see each: ``"no_window"`` (sliding layers attend everything),
``"rope_everywhere"`` (the global layers rotate too), ``"no_shared"`` (no shared
experts), ``"shared_sum"`` (their sum and not their mean).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# the source's key for the K/V heads, spelt in two parts (tests/benchmarks/
# test_bench_families.py greps benchmarks/ for dense Qwen2's names)
KV_HEADS = "num_key_value" "_heads"
KNOCK_OUTS = ("no_window", "rope_everywhere", "no_shared", "shared_sum")
ROUTER_GAIN = 2.0  # the router's draw, times this (the configuration's weights.init)


def dims(model: dict) -> dict:
    lo, hi = model["experts_held"]
    return dict(
        d=model["hidden_size"], h=model["num_attention_heads"],
        nkv=model[KV_HEADS], hd=model["head_dim"], ff=model["intermediate_size"],
        e=model["num_experts"], k=model["num_experts_per_tok"], lo=lo, n=hi - lo,
        shared=model["num_shared_experts"], L=model["num_hidden_layers"],
        switch=model["layer_switch"], window=model["sliding_window"], v=model["vocab_size"])


def is_sliding(model: dict, li: int) -> bool:
    """``layer_types``: every ``layer_switch``-th layer is global."""
    return (li + 1) % model["layer_switch"] != 0


def leaf_order(model: dict) -> list:
    """(name, shape, gain) of the drawn leaves in draw order."""
    s = dims(model)
    d, L, ffs = s["d"], s["L"], s["ff"] * s["shared"]
    return [("embed", (s["v"], d), 1.0),
            ("wq", (L, d, s["h"] * s["hd"]), 1.0), ("wk", (L, d, s["nkv"] * s["hd"]), 1.0),
            ("wv", (L, d, s["nkv"] * s["hd"]), 1.0), ("wo", (L, s["h"] * s["hd"], d), 1.0),
            ("router", (L, d, s["e"]), ROUTER_GAIN),
            ("e_wgu", (L, s["n"], d, 2 * s["ff"]), 1.0), ("e_wd", (L, s["n"], s["ff"], d), 1.0),
            ("s_wgu", (L, d, 2 * ffs), 1.0), ("s_wd", (L, ffs, d), 1.0)]


def salts(wseed: int, n: int) -> list:
    s = (wseed * 40503 + 12345) & 0xFFFFFFFF
    out = []
    for _ in range(n):
        s = (s * 747796405 + 1) & 0xFFFFFFFF
        out.append(s)
    return out


def _hash_bf16(i, salt, gain):
    """Element ``i`` (uint32 flat index) of a leaf: a Knuth hash of index and
    salt, centred and scaled to std ~0.02, rounded to bfloat16, times the
    leaf's gain (a power of two: exact), rounded again as the program's is."""
    h = i * jnp.uint32(2654435761) + salt
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    w = ((h.astype(jnp.float32) - 2147483648.0) * (0.02 / 1.24e9)).astype(jnp.bfloat16)
    return (w.astype(jnp.float32) * gain).astype(jnp.bfloat16).astype(jnp.float32)


@partial(jax.jit, static_argnames=("nr", "nc", "width", "gain"))
def _sub(salt, base, r0, c0, nr: int, nc: int, width: int, gain: float):
    """Rows [r0, r0 + nr) x columns [c0, c0 + nc) of a [*, width] matrix whose
    first element has flat index ``base``."""
    r = (jax.lax.iota(jnp.uint32, nr)[:, None] + r0) * jnp.uint32(width)
    return _hash_bf16(base + r + jax.lax.iota(jnp.uint32, nc)[None, :] + c0, salt, gain)


@partial(jax.jit, static_argnames=("d",))
def _rows(salt, ids, d: int):
    return _hash_bf16(ids[..., None] * jnp.uint32(d) + jax.lax.iota(jnp.uint32, d), salt, 1.0)


W_MAX = 2147483648.0 * (0.02 / 1.24e9)  # the initialiser's range: uniform in +-0.0346


def degrade(w: jnp.ndarray, scheme: str | None, gain: float = 1.0) -> jnp.ndarray:
    """Weights re-rounded to the control's precision: float8 e4m3, the leaf's
    largest weight at 448 (benchmarks/reference_deepseek_v3.degrade says why
    int8 is no precision below bfloat16 for weights drawn from one range)."""
    if scheme != "fp8":
        return w
    s = W_MAX * gain / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class Weights:
    """The share's weights as a function of the seed, one slice at a time.
    ``mat(name, *index, rows, cols)``: rows ``(first, count)`` and columns
    ``(first, count)`` of the [in, out] matrix at the leading ``index`` of a
    leaf; ``embed(ids)``: rows of the embedding.  A test that holds the file to
    a published module hands in an object with the same two methods."""

    def __init__(self, model: dict, wseed: int, control: str | None = None) -> None:
        order = leaf_order(model)
        self.shape = {name: shape for name, shape, _ in order}
        self.gain = {name: gain for name, _, gain in order}
        self.salt = {name: jnp.uint32(s)
                     for (name, _, _), s in zip(order, salts(wseed, len(order)))}
        self.control = control if control == "fp8" else None

    def mat(self, name: str, *index, rows: tuple = None, cols: tuple = None) -> jnp.ndarray:
        shape = self.shape[name]
        n_in, n_out = shape[-2:]
        offset = 0
        for i, n in zip(index, shape):
            offset = offset * n + i
        r0, nr = rows or (0, n_in)
        c0, nc = cols or (0, n_out)
        w = _sub(self.salt[name], jnp.uint32(offset * n_in * n_out), jnp.uint32(r0),
                 jnp.uint32(c0), nr, nc, n_out, self.gain[name])
        return degrade(w, self.control, self.gain[name])

    def embed(self, ids) -> jnp.ndarray:
        w = _rows(self.salt["embed"], jnp.asarray(ids, jnp.uint32), self.shape["embed"][1])
        return degrade(w, self.control)


def layer_norm(x, eps: float, weight=None):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return y if weight is None else y * weight


def rope_interleaved(x, pos, theta: float):
    """x [S, heads, hd], pos [S]: pair i is columns (2i, 2i + 1), rotated by
    ``pos * theta^(-2i / hd)``."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv_freq  # [S, 1, hd / 2]
    pairs = x.reshape(*x.shape[:-1], hd // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


@partial(jax.jit, static_argnames=("hd", "rope", "theta", "window", "q_block"))
def _group(x, wq, wk, wv, wo, *, hd, rope, theta, window, q_block):
    """One kv head's group of query heads over a whole sequence, already
    through its rows of W_o: x [S, d] normed -> [S, d], S a multiple of
    ``q_block``.  A block of queries at a time: a global layer's block scores
    every key and masks the later ones, a sliding layer's the keys from
    ``window - 1`` (up to whole blocks) before its first query on."""
    s = x.shape[0]
    pos = jnp.arange(s)
    q = jnp.einsum("sd,de->se", x, wq, precision=HI).reshape(s, -1, hd)
    k = jnp.einsum("sd,de->se", x, wk, precision=HI).reshape(s, 1, hd)
    v = jnp.einsum("sd,de->se", x, wv, precision=HI)
    if rope:
        q, k = rope_interleaved(q, pos, theta), rope_interleaved(k, pos, theta)
    k = k[:, 0]
    back = s if window is None else min(s, -(-(window - 1) // q_block) * q_block)
    span = s if window is None else back + q_block  # keys one block of queries is scored on
    if window is not None:
        k, v = (jnp.pad(t, ((back, 0), (0, 0))) for t in (k, v))

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, q_block)
        first = 0 if window is None else q0 - back  # the position of the span's first key
        kb, vb = k, v
        if window is not None:
            kb, vb = (jax.lax.dynamic_slice_in_dim(t, q0, span) for t in (k, v))
        sc = jnp.einsum("qhe,te->hqt", qb, kb, precision=HI) * hd ** -0.5
        at, qp = first + jnp.arange(span)[None, :], q0 + jnp.arange(q_block)[:, None]
        mask = (at <= qp) & (at >= 0)
        if window is not None:
            mask = mask & (qp - at < window)
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,te->qhe", p, vb, precision=HI)

    attn = jax.lax.map(block, jnp.arange(0, s, q_block)).reshape(s, -1)
    return jnp.einsum("se,ed->sd", attn, wo, precision=HI)


def attention(w, model: dict, li: int, x, control: str | None, q_block: int):
    """W_o attn(...) for layer ``li``: x [S, d] normed -> [S, d]."""
    s = dims(model)
    sliding = is_sliding(model, li)
    window = s["window"] if sliding and control != "no_window" else None
    rope = sliding or control == "rope_everywhere"
    group = s["h"] // s["nkv"] * s["hd"]
    a = jnp.zeros_like(x)
    for g in range(s["nkv"]):
        a = a + _group(
            x, w.mat("wq", li, cols=(g * group, group)), w.mat("wk", li, cols=(g * s["hd"], s["hd"])),
            w.mat("wv", li, cols=(g * s["hd"], s["hd"])), w.mat("wo", li, rows=(g * group, group)),
            hd=s["hd"], rope=rope, theta=float(model["rope_theta"]), window=window,
            q_block=q_block)
    return a


@jax.jit
def _gated(x, wg, wu, wd):
    h = jax.nn.silu(jnp.einsum("...d,de->...e", x, wg, precision=HI)) \
        * jnp.einsum("...d,de->...e", x, wu, precision=HI)
    return jnp.einsum("...e,ed->...d", h, wd, precision=HI)


def route(scores, top_k: int, norm: bool = True):
    """``scores`` [T, E] sigmoid affinities -> the dense weights [T, E]: the
    scores of the top k, normalised to sum one, zero elsewhere."""
    kth = jnp.sort(scores, axis=-1)[:, -top_k][:, None]
    w = jnp.where(scores >= kth, scores, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) if norm else w


def routed_part(w, model: dict, li: int, x):
    """sum over the held experts of w_e E_e(x): x [T, d] normed -> [T, d]."""
    s = dims(model)
    y = jnp.zeros_like(x)
    if not s["n"]:
        return y
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x, w.mat("router", li), precision=HI))
    dense = route(scores, s["k"], bool(model.get("norm_topk_prob", True)))
    for e in range(s["n"]):
        y = y + dense[:, s["lo"] + e][:, None] * _gated(
            x, w.mat("e_wgu", li, e, cols=(0, s["ff"])), w.mat("e_wgu", li, e, cols=(s["ff"], s["ff"])),
            w.mat("e_wd", li, e))
    return y


def shared_part(w, model: dict, li: int, x, control: str | None = None):
    """(1 / S) sum of the shared experts, each a product of its own: expert
    ``j``'s gate and up columns are the j-th run of each half of ``s_wgu``."""
    s = dims(model)
    y = jnp.zeros_like(x)
    if control == "no_shared" or not s["shared"]:
        return y
    ff, ffs = s["ff"], s["ff"] * s["shared"]
    for j in range(s["shared"]):
        y = y + _gated(x, w.mat("s_wgu", li, cols=(j * ff, ff)),
                       w.mat("s_wgu", li, cols=(ffs + j * ff, ff)),
                       w.mat("s_wd", li, rows=(j * ff, ff)))
    return y if control == "shared_sum" else y / s["shared"]


def forward(model: dict, w, ids, control: str | None = None, q_block: int = 128,
            token_block: int = 4096, norms=None):
    """The final hidden states [S, d] (before the last norm) of one sequence of
    token ids, right-padded to whole blocks of queries (causal attention hides
    the padding from every real position).  ``norms``: the layers' norm weights
    [L, d] where they are not ones."""
    s = dims(model)
    eps = float(model["layer_norm_eps"])
    ids = np.asarray(ids)
    ids = np.pad(ids, (0, -len(ids) % q_block))
    hid = w.embed(ids)
    for li in range(s["L"]):
        x = layer_norm(hid, eps, None if norms is None else norms[li])
        hid = hid + attention(w, model, li, x, control, q_block)
        blocks = []
        for t0 in range(0, x.shape[0], token_block):  # the experts' intermediates, a block of tokens
            xb = x[t0:t0 + token_block]
            blocks.append(routed_part(w, model, li, xb) + shared_part(w, model, li, xb, control))
        hid = hid + jnp.concatenate(blocks, axis=0)
    return hid


def logits_at(model: dict, wseed: int, sequences: list, positions: list,
              control: str | None = None, q_block: int = 128, weights=None) -> list:
    """Float32 logits of each sequence at its own ``positions`` (position p
    gives the distribution of token p + 1), one sequence at a time: causal
    attention needs no padding, and the weights are a function of the seed,
    made again for each (``weights``: an object with ``Weights``' two methods
    in their place, for a test)."""
    if control not in (None, "fp8", *KNOCK_OUTS):
        raise ValueError(f"unknown control {control!r}")
    s = dims(model)
    w = weights or Weights(model, wseed, control)
    eps, scale = float(model["layer_norm_eps"]), float(model.get("logit_scale", 1.0))
    out = []
    pad_to = -(-max(len(seq) for seq in sequences) // q_block) * q_block  # one shape to compile
    for seq, at in zip(sequences, positions):
        hid = forward(model, w, list(seq) + [0] * (pad_to - len(seq)), control, q_block)
        rows = layer_norm(hid[jnp.asarray(at)], eps)
        chunks, step = [], -(-s["v"] // 4)
        for c0 in range(0, s["v"], step):  # the tied head: rows of the embedding
            table = w.embed(np.arange(c0, min(c0 + step, s["v"])))
            chunks.append(np.asarray(jnp.einsum("nd,vd->nv", rows, table, precision=HI)))
        out.append(np.concatenate(chunks, axis=1) * scale)
    return out
