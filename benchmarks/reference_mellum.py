"""The plain reference for Mellum (``model_type`` ``mellum``,
Mellum2-12B-A2.5B): the layer equations in float32 ``jax.numpy`` at
``Precision.HIGHEST``, with weights made here from the seed.  No kernel, no
cache, no batching.  It imports nothing of the program.

What it computes, for a share of the model (``model``: the keys of the
published ``config.json``, with ``num_hidden_layers`` as cut, ``num_experts``
the router's width and ``experts_held`` = [first, past the last) of the routed
experts), on the residual stream ``h``:

    x = h / sqrt(mean h^2 + eps) * w                   RMSNorm
    q, k, v = W_q x, W_k x, W_v x                      no bias, scale head_dim^-0.5
    q, k = RMSNorm over each head's dims, gains g_q, g_k (a layer's, shared by its heads)
    sliding layer (``layer_types``): q, k rotated, rotate-half over the whole head, by
                   cos / sin of pos * theta^(-2i / hd); query i sees key j iff
                   0 <= i - j < sliding_window
    global layer:  q, k rotated by a * cos, a * sin of pos * f_i, f YaRN's frequencies
                   (``rope_parameters.full_attention``), a its ``attention_factor``; plain causal
    h = h + W_o attn(q, k, v)
    y = RMSNorm(h)
    p = softmax(W_r y); T = top-k of p; w_e = p_e / sum_T p     (``norm_topk_prob``)
    h = h + sum_{e in T, e held} w_e W_down,e (silu(W_gate,e y) * W_up,e y)
    logits = W_head RMSNorm(h_L)                       the head is a matrix of its own

Experts that are not held add nothing (their chips would).  Attention runs a kv
head's group at a time and a block of queries at a time, and an expert over the
whole sequence with its weights made once, so that a 25k-token prompt fits
beside the engine.

YaRN (the published ``_compute_yarn_parameters``): with ``d(r) = hd ln(L0 / (2
pi r)) / (2 ln theta)``, ``low = floor d(beta_fast)``, ``high = ceil d(beta_slow)``,
pair i keeps ``theta^(-2i/hd)`` below ``low``, takes it over ``factor`` above
``high``, and a linear ramp between.

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in ``leaf_order``'s order (restated from the
configuration's ``weights.init``); centred, std ~0.02 times the leaf's gain,
rounded to bfloat16 (the type served) and widened to float32.  The per-head
norms' gains are ``1 + draw``, rounded to bfloat16; the block norms are one.

Controls (``control``): ``"fp8"`` re-rounds every weight matrix to float8 e4m3
under one scale, the next precision below the one the configuration states;
the others knock one piece of the mathematics out, and the comparison must see
each: ``"no_window"`` (sliding layers attend everything), ``"one_rope"`` (the
sliding layers' table on the global layers too, factor and all),
``"no_attention_factor"`` (YaRN's frequencies with plain cos and sin),
``"no_topk_norm"`` (the chosen experts' probabilities as they are),
``"no_qk_norm"`` (q and k as projected).
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
KNOCK_OUTS = ("no_window", "one_rope", "no_attention_factor", "no_topk_norm", "no_qk_norm")
ROUTER_GAIN = 2.0  # the router's draw, times this (the configuration's weights.init)
QK_NORM_GAIN = 16.0  # the per-head norms' gains are 1 + a draw times this


def dims(model: dict) -> dict:
    lo, hi = model["experts_held"]
    return dict(
        d=model["hidden_size"], h=model["num_attention_heads"],
        nkv=model[KV_HEADS], hd=model["head_dim"], ff=model["moe_intermediate_size"],
        e=model["num_experts"], k=model["num_experts_per_tok"], lo=lo, n=hi - lo,
        L=model["num_hidden_layers"], window=model["sliding_window"], v=model["vocab_size"])


def is_sliding(model: dict, li: int) -> bool:
    return model["layer_types"][li] == "sliding_attention"


def leaf_order(model: dict) -> list:
    """(name, shape, gain) of the drawn leaves in draw order."""
    s = dims(model)
    d, L = s["d"], s["L"]
    return [("embed", (s["v"], d), 1.0), ("lm_head", (d, s["v"]), 1.0),
            ("wq", (L, d, s["h"] * s["hd"]), 1.0), ("wk", (L, d, s["nkv"] * s["hd"]), 1.0),
            ("wv", (L, d, s["nkv"] * s["hd"]), 1.0), ("wo", (L, s["h"] * s["hd"], d), 1.0),
            ("q_norm", (L, s["hd"]), QK_NORM_GAIN), ("k_norm", (L, s["hd"]), QK_NORM_GAIN),
            ("router", (L, d, s["e"]), ROUTER_GAIN),
            ("e_wgu", (L, s["n"], d, 2 * s["ff"]), 1.0), ("e_wd", (L, s["n"], s["ff"], d), 1.0)]


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
    leaf; ``embed(ids)``: rows of the embedding; ``head_gain(name, li)``: a
    layer's per-head norm gains [hd].  A test hands in an object with the same
    three methods."""

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

    def head_gain(self, name: str, li: int) -> jnp.ndarray:
        """``1 + draw`` of row ``li`` of the [L, hd] leaf, rounded to bfloat16 as
        the program stores it (a gain, not a weight matrix: no control re-rounds it)."""
        hd = self.shape[name][1]
        draw = _sub(self.salt[name], jnp.uint32(0), jnp.uint32(li), jnp.uint32(0), 1, hd, hd,
                    self.gain[name])[0]
        return (1.0 + draw).astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, eps: float, weight=None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if weight is None else y * weight


def plain_inv_freq(hd: int, theta: float):
    return 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)


def yarn_inv_freq(hd: int, theta: float, factor: float, original_max: int, beta_fast: float,
                  beta_slow: float):
    """YaRN's frequencies [hd / 2] (the module's docstring)."""
    def correction_dim(rotations):
        return hd * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), hd - 1)
    plain = plain_inv_freq(hd, theta)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0, 1)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_table(model: dict, sliding: bool, control: str | None):
    """(inv_freq [hd / 2], the factor on cos and sin) of a layer's kind."""
    hd = model["head_dim"]
    rp = model["rope_parameters"]
    local, full = rp["sliding_attention"], rp["full_attention"]
    if sliding or control == "one_rope":
        return plain_inv_freq(hd, float(local["rope_theta"])), 1.0
    freq = yarn_inv_freq(hd, float(full["rope_theta"]), float(full["factor"]),
                         int(full["original_max_position_embeddings"]),
                         float(full["beta_fast"]), float(full["beta_slow"]))
    return freq, 1.0 if control == "no_attention_factor" else float(full["attention_factor"])


def rope(x, pos, inv_freq, factor):
    """x [S, heads, hd], pos [S]: rotate-half over the whole head, the pair
    (i, i + hd / 2) turned by ``pos * inv_freq[i]``, cos and sin times ``factor``."""
    ang = pos[:, None].astype(jnp.float32) * inv_freq  # [S, hd / 2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(ang) * factor) + turned * (jnp.sin(ang) * factor)


@partial(jax.jit, static_argnames=("hd", "factor", "window", "q_block", "eps", "qk_norm"))
def _group(x, wq, wk, wv, wo, gq, gk, inv_freq, *, hd, factor, window, q_block, eps, qk_norm):
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
    if qk_norm:
        q, k = rms_norm(q, eps, gq), rms_norm(k, eps, gk)
    q, k = rope(q, pos, inv_freq, factor), rope(k, pos, inv_freq, factor)
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
    inv_freq, factor = rope_table(model, sliding, control)
    group = s["h"] // s["nkv"] * s["hd"]
    gq, gk = w.head_gain("q_norm", li), w.head_gain("k_norm", li)
    a = jnp.zeros_like(x)
    for g in range(s["nkv"]):
        a = a + _group(
            x, w.mat("wq", li, cols=(g * group, group)), w.mat("wk", li, cols=(g * s["hd"], s["hd"])),
            w.mat("wv", li, cols=(g * s["hd"], s["hd"])), w.mat("wo", li, rows=(g * group, group)),
            gq, gk, inv_freq, hd=s["hd"], factor=factor, window=window, q_block=q_block,
            eps=float(model["rms_norm_eps"]), qk_norm=control != "no_qk_norm")
    return a


@jax.jit
def _gated(x, wg, wu, wd, weight):
    """``weight`` [T] times one expert's SwiGLU of x [T, d]."""
    h = jax.nn.silu(jnp.einsum("td,de->te", x, wg, precision=HI)) \
        * jnp.einsum("td,de->te", x, wu, precision=HI)
    return weight[:, None] * jnp.einsum("te,ed->td", h, wd, precision=HI)


def route(probs, top_k: int, norm: bool = True):
    """``probs`` [T, E] softmax probabilities -> the dense weights [T, E]: the
    probabilities of the top k, normalised to sum one, zero elsewhere."""
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    w = jnp.where(probs >= kth, probs, 0.0)
    return w / w.sum(axis=-1, keepdims=True) if norm else w


def routed_part(w, model: dict, li: int, x, control: str | None = None):
    """sum over the held experts of w_e E_e(x): x [T, d] normed -> [T, d], an
    expert at a time over every token (its weight is zero where a token did
    not choose it)."""
    s = dims(model)
    y = jnp.zeros_like(x)
    if not s["n"]:
        return y
    probs = jax.nn.softmax(jnp.einsum("td,de->te", x, w.mat("router", li), precision=HI), axis=-1)
    norm = bool(model.get("norm_topk_prob", True)) and control != "no_topk_norm"
    dense = route(probs, s["k"], norm)
    for e in range(s["n"]):
        y = y + _gated(
            x, w.mat("e_wgu", li, e, cols=(0, s["ff"])), w.mat("e_wgu", li, e, cols=(s["ff"], s["ff"])),
            w.mat("e_wd", li, e), dense[:, s["lo"] + e])
    return y


def forward(model: dict, w, ids, control: str | None = None, q_block: int = 128, norms=None):
    """The final hidden states [S, d] (before the last norm) of one sequence of
    token ids, right-padded to whole blocks of queries (causal attention hides
    the padding from every real position).  ``norms``: the block norms' weights
    ([L, d] for the first, [L, d] for the second) where they are not ones."""
    s = dims(model)
    eps = float(model["rms_norm_eps"])
    ids = np.asarray(ids)
    ids = np.pad(ids, (0, -len(ids) % q_block))
    hid = w.embed(ids)
    for li in range(s["L"]):
        x = rms_norm(hid, eps, None if norms is None else norms[0][li])
        hid = hid + attention(w, model, li, x, control, q_block)
        y = rms_norm(hid, eps, None if norms is None else norms[1][li])
        hid = hid + routed_part(w, model, li, y, control)
    return hid


def logits_at(model: dict, wseed: int, sequences: list, positions: list,
              control: str | None = None, q_block: int = 128, weights=None) -> list:
    """Float32 logits of each sequence at its own ``positions`` (position p
    gives the distribution of token p + 1), one sequence at a time: causal
    attention needs no padding, and the weights are a function of the seed,
    made again for each (``weights``: an object with ``Weights``' methods in
    their place, for a test)."""
    if control not in (None, "fp8", *KNOCK_OUTS):
        raise ValueError(f"unknown control {control!r}")
    s = dims(model)
    w = weights or Weights(model, wseed, control)
    eps = float(model["rms_norm_eps"])
    out = []
    pad_to = -(-max(len(seq) for seq in sequences) // q_block) * q_block  # one shape to compile
    for seq, at in zip(sequences, positions):
        hid = forward(model, w, list(seq) + [0] * (pad_to - len(seq)), control, q_block)
        rows = rms_norm(hid[jnp.asarray(at)], eps)
        chunks, step = [], -(-s["v"] // 4)
        for c0 in range(0, s["v"], step):  # the head, a quarter of its columns at a time
            table = w.mat("lm_head", cols=(c0, min(step, s["v"] - c0)))
            chunks.append(np.asarray(jnp.einsum("nd,dv->nv", rows, table, precision=HI)))
        out.append(np.concatenate(chunks, axis=1))
    return out
