"""The plain reference for DeepSeek-V3 (arXiv:2412.19437): the published
equations in float32 ``jax.numpy`` at ``Precision.HIGHEST``, with weights
made here from the seed.  It imports nothing of the program.

What it computes, for a share of the model (``model``: HF ``config.json``
keys, with ``num_hidden_layers`` / ``first_k_dense_replace`` / ``vocab_size``
as cut, and ``experts_held`` = [first, past the last) of the routed experts):

* attention, every layer, NOT absorbed and with no cache:
  ``c_q = RMSNorm(x W_dq)``, ``[q_nope | q_rope] = c_q W_uq`` per head;
  ``[c_kv | k_rope] = x W_dkv``, ``c_kv = RMSNorm(c_kv)``, one ``k_rope`` for
  all heads; ``k_nope_h = c_kv W_uk,h^T``, ``v_h = c_kv W_uv,h``;
  ``score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) * (nope + rope)^-0.5
  * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax;
  ``o = concat_h(softmax v_h) W_o`` (computed 16 heads at a time, each block
  through its own rows of ``W_o``: a sum in another order).  RoPE on ``q_rope`` / ``k_rope`` only, YaRN
  frequencies (linear ramp between the correction dimensions of ``beta_fast``
  and ``beta_slow``; ``mscale == mscale_all_dim``, so cos and sin scale by 1);
* feed-forward: the leading layers SwiGLU at ``intermediate_size``; the
  others ``s = sigmoid(x W_r)``, selection on ``s + e_score_correction_bias``
  (a group's score the sum of its two largest, the ``topk_group`` best groups
  kept, top ``num_experts_per_tok`` inside them), weights ``s`` at the chosen
  experts normalised to sum 1 and times ``routed_scaling_factor``; a loop over
  the experts held adds ``w_e E_e(x)``, experts that are not held add nothing
  (their chips would), and the shared expert (no gate) is added once.

Departures from the published model, shared with the program: the
multi-token-prediction module is not built (the main model's logits do not
depend on it); the rope's pairs are half-split (rotate-half: column i pairs
with column i + rope/2), where the published checkpoint stores them
interleaved: with weights from a seed either layout is the model, as long as
program and reference agree, and they agree on half-split; ``W_ukv`` is held
as two leaves, ``W_uk`` [H, nope, rank] and ``W_uv`` [H, rank, v].

Weights: each leaf is a Knuth-hashed iota of its flat index and a salt that
advances once a leaf, in the order ``leaf_order`` restates from the
configuration's ``weights.init``; centred, std ~0.02, rounded to bfloat16
(the type served) and widened to float32.  ``control`` re-rounds every weight
to the next precision below: ``"fp8"`` (float8 e4m3 under one scale, what the
configuration uses) or ``"int8"`` (127 steps to the largest weight of the
initialiser's range, the scale every output channel would get).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def dims(model: dict) -> dict:
    lo, hi = model["experts_held"]
    return dict(
        d=model["hidden_size"], h=model["num_attention_heads"], q_rank=model["q_lora_rank"],
        rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
        rope=model["qk_rope_head_dim"], vd=model["v_head_dim"], ff=model["intermediate_size"],
        ffe=model["moe_intermediate_size"], ffs=model["moe_intermediate_size"]
        * model["n_shared_experts"], e=model["n_routed_experts"], lo=lo, n=hi - lo,
        ld=model["first_k_dense_replace"],
        lm=model["num_hidden_layers"] - model["first_k_dense_replace"], v=model["vocab_size"])


def leaf_order(model: dict) -> list:
    """(name, shape) of the drawn leaves in draw order."""
    s = dims(model)
    d, h = s["d"], s["h"]

    def attn(stack, layers):
        return [(f"{stack}.wdq", (layers, d, s["q_rank"])),
                (f"{stack}.wuq", (layers, s["q_rank"], h * (s["nope"] + s["rope"]))),
                (f"{stack}.wdkv", (layers, d, s["rank"] + s["rope"])),
                (f"{stack}.wuk", (layers, h, s["nope"], s["rank"])),
                (f"{stack}.wuv", (layers, h, s["rank"], s["vd"])),
                (f"{stack}.wo", (layers, h * s["vd"], d))]

    leaves = [("embed", (s["v"], d)), ("lm_head", (d, s["v"]))]
    if s["ld"]:
        leaves += attn("dense", s["ld"]) + [("dense.wgu", (s["ld"], d, 2 * s["ff"])),
                                            ("dense.wd", (s["ld"], s["ff"], d))]
    if s["lm"]:
        lm = s["lm"]
        leaves += attn("moe", lm) + [
            ("moe.router", (lm, d, s["e"])), ("moe.e_bias", (lm, s["e"])),
            ("moe.e_wgu", (lm, s["n"], d, 2 * s["ffe"])), ("moe.e_wd", (lm, s["n"], s["ffe"], d)),
            ("moe.s_wgu", (lm, d, 2 * s["ffs"])), ("moe.s_wd", (lm, s["ffs"], d))]
    return leaves


def salts(wseed: int, n: int) -> list:
    s = (wseed * 40503 + 12345) & 0xFFFFFFFF
    out = []
    for _ in range(n):
        s = (s * 747796405 + 1) & 0xFFFFFFFF
        out.append(s)
    return out


def _hash_bf16(i, salt):
    """Element ``i`` (uint32 flat index) of a leaf: a Knuth hash of index and
    salt, centred and scaled to std ~0.02, rounded to bfloat16."""
    h = i * jnp.uint32(2654435761) + salt
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return ((h.astype(jnp.float32) - 2147483648.0) * (0.02 / 1.24e9)).astype(
        jnp.bfloat16).astype(jnp.float32)


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


W_MAX = 2147483648.0 * (0.02 / 1.24e9)  # the initialiser's range: uniform in +-0.0346


def degrade(w: jnp.ndarray, scheme: str | None) -> jnp.ndarray:
    """Weights re-rounded to the control's precision.  int8: symmetric, 127
    steps to the largest weight.  Every channel of a leaf drawn uniformly from
    one range has that same largest weight, so the scale a channel would be
    given is one number, and any block of a matrix can be re-rounded alone.
    For weights drawn uniformly from one range that is no coarser than
    bfloat16 itself (a step of 1/127 of the range against 1/128 to 1/256 of
    the value: PERF.md section 4), so the control that is a precision BELOW
    bfloat16 is fp8: three bits of mantissa, scaled like a block of the
    published checkpoint."""
    if scheme is None:
        return w
    if scheme == "int8":
        s = W_MAX / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if scheme == "fp8":  # float8 e4m3 (the published checkpoint's type), largest weight at 448
        s = W_MAX / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control {scheme!r}")


@partial(jax.jit, static_argnames=("nr", "nc", "width"))
def _sub(salt, base, r0, c0, nr: int, nc: int, width: int):
    """Rows [r0, r0 + nr) x columns [c0, c0 + nc) of a [*, width] matrix whose
    first element has flat index ``base``."""
    r = (jax.lax.iota(jnp.uint32, nr)[:, None] + r0) * jnp.uint32(width)
    return _hash_bf16(base + r + jax.lax.iota(jnp.uint32, nc)[None, :] + c0, salt)


class Weights:
    """The share's weights as a function of the seed, one slice at a time."""

    def __init__(self, model: dict, wseed: int, control: str | None = None) -> None:
        order = leaf_order(model)
        self.shape = dict(order)
        self.salt = {name: jnp.uint32(s) for (name, _), s in zip(order, salts(wseed, len(order)))}
        self.control = control

    def at(self, name: str, *index) -> jnp.ndarray:
        """The sub-array at the leading ``index`` of a leaf ([in, out] last)."""
        shape = self.shape[name]
        rest = shape[len(index):]
        offset = 0
        for i, n in zip(index, shape):
            offset = offset * n + i
        w = _block(self.salt[name], jnp.uint32(offset * math.prod(rest)), rest)
        return degrade(w, self.control) if len(rest) >= 2 else w

    def sub(self, name: str, i: int, rows: tuple = None, cols: tuple = None) -> jnp.ndarray:
        """Rows ``(first, count)`` and columns ``(first, count)`` of layer
        ``i`` of a [L, in, out] leaf: a 7,168 x 36,864 matrix in float32 is
        1 GB, and the reference runs beside the engine."""
        _, n_in, n_out = self.shape[name]
        r0, nr = rows or (0, n_in)
        c0, nc = cols or (0, n_out)
        w = _sub(self.salt[name], jnp.uint32(i * n_in * n_out), jnp.uint32(r0), jnp.uint32(c0),
                 nr, nc, n_out)
        return degrade(w, self.control)

    def embed(self, ids: np.ndarray) -> jnp.ndarray:
        w = _rows(self.salt["embed"], jnp.asarray(ids, jnp.uint32), self.shape["embed"][1])
        return degrade(w, self.control)

    def head_cols(self, c0: int, n: int) -> jnp.ndarray:
        d, v = self.shape["lm_head"]
        return degrade(_cols(self.salt["lm_head"], jnp.uint32(c0), n, d, v), self.control)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(dim: int, base: float, scaling: dict) -> np.ndarray:
    factor, orig = float(scaling["factor"]), int(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0, 1)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(model: dict) -> float:
    sc = model["rope_scaling"]
    m = 0.1 * float(sc["mscale_all_dim"]) * math.log(float(sc["factor"])) + 1.0
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, pos, inv_freq):
    """x [B, S, ..., rope], rotate-half pairs; pos [B, S]."""
    ang = pos[..., None].astype(jnp.float32) * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape(*ang.shape[:2], *([1] * (x.ndim - 3)), ang.shape[-1])
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@partial(jax.jit, static_argnames=("rank", "eps"))
def _latents(hid, wdq, wdkv, inv_freq, *, rank, eps):
    """x -> (c_q normed, c_kv normed, k_rope rotated): what all heads share."""
    b, s, _ = hid.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _rms(hid, eps)
    c_q = _rms(jnp.einsum("bsd,dr->bsr", x, wdq, precision=HI), eps)
    ckv = jnp.einsum("bsd,dr->bsr", x, wdkv, precision=HI)
    return c_q, _rms(ckv[..., :rank], eps), _rope(ckv[..., rank:], pos, inv_freq)


@partial(jax.jit, static_argnames=("nope", "scale", "q_block"))
def _heads(c_q, c_kv, k_rope, wuq, wuk, wuv, wo, inv_freq, *, nope, scale, q_block):
    """Some heads' share of the attention output, already through their rows
    of W_o: [B, S, d].  Not absorbed: K and V are built from c_kv."""
    b, s, _ = c_q.shape
    h = wuk.shape[0]
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = jnp.einsum("bsr,re->bse", c_q, wuq, precision=HI).reshape(b, s, h, -1)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, inv_freq)
    k_nope = jnp.einsum("btc,hnc->bthn", c_kv, wuk, precision=HI)
    v = jnp.einsum("btc,hcv->bthv", c_kv, wuv, precision=HI)
    outs = []
    for q0 in range(0, s, q_block):
        hi = min(s, q0 + q_block)
        sc = jnp.einsum("bqhn,bthn->bhqt", q_nope[:, q0:hi], k_nope[:, :hi], precision=HI) \
            + jnp.einsum("bqhr,btr->bhqt", q_rope[:, q0:hi], k_rope[:, :hi], precision=HI)
        mask = jnp.arange(hi)[None, :] <= (q0 + jnp.arange(hi - q0))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], sc * scale, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqt,bthv->bqhv", p, v[:, :hi], precision=HI))
    attn = jnp.concatenate(outs, axis=1).reshape(b, s, -1)
    return jnp.einsum("bse,ed->bsd", attn, wo, precision=HI)


def attention(w: "Weights", stack: str, i: int, hid, inv_freq, s: dict, scale: float,
              eps: float, q_block: int, head_block: int = 16):
    """hid + attention(hid) for layer ``i`` of ``stack``, ``head_block`` heads
    at a time (all 128 at 9k tokens are 2.5 GB of K, V and scores)."""
    c_q, c_kv, k_rope = _latents(hid, w.at(f"{stack}.wdq", i), w.at(f"{stack}.wdkv", i),
                                 inv_freq, rank=s["rank"], eps=eps)
    wuk, wuv = w.at(f"{stack}.wuk", i), w.at(f"{stack}.wuv", i)
    per_q, hb = s["nope"] + s["rope"], min(head_block, s["h"])
    for h0 in range(0, s["h"], hb):
        hid = hid + _heads(
            c_q, c_kv, k_rope, w.sub(f"{stack}.wuq", i, cols=(h0 * per_q, hb * per_q)),
            wuk[h0:h0 + hb], wuv[h0:h0 + hb],
            w.sub(f"{stack}.wo", i, rows=(h0 * s["vd"], hb * s["vd"])), inv_freq,
            nope=s["nope"], scale=scale, q_block=q_block)
    return hid


def dense_mlp(w: "Weights", i: int, x, s: dict, blocks: int = 4):
    """SwiGLU at ``intermediate_size``, a block of its columns at a time."""
    step = -(-s["ff"] // blocks)
    y = jnp.zeros_like(x)
    for c0 in range(0, s["ff"], step):
        n = min(step, s["ff"] - c0)
        y = y + _gated(x, w.sub("dense.wgu", i, cols=(c0, n)),
                       w.sub("dense.wgu", i, cols=(s["ff"] + c0, n)),
                       w.sub("dense.wd", i, rows=(c0, n)))
    return y


@jax.jit
def _gated(x, wg, wu, wd):
    h = jax.nn.silu(jnp.einsum("...d,de->...e", x, wg, precision=HI)) \
        * jnp.einsum("...d,de->...e", x, wu, precision=HI)
    return jnp.einsum("...e,ed->...d", h, wd, precision=HI)


def _swiglu(x, wgu, wd):
    """SwiGLU with gate and up side by side in one [d, 2f] matrix."""
    f = wgu.shape[-1] // 2
    return _gated(x, wgu[:, :f], wgu[:, f:], wd)


def route(scores, bias, top_k: int, n_group: int, topk_group: int, scaling: float):
    """Group-limited top-k, written out: ``scores`` [T, E] are the sigmoid
    affinities.  Returns the dense weight matrix [T, E] (zero where an expert
    is not chosen)."""
    t, e = scores.shape
    biased = scores + bias[None, :]
    groups = biased.reshape(t, n_group, e // n_group)
    group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(axis=-1)
    kth_group = jnp.sort(group_score, axis=-1)[:, -topk_group][:, None]
    allowed = jnp.repeat(group_score >= kth_group, e // n_group, axis=1)
    masked = jnp.where(allowed, biased, -jnp.inf)
    kth = jnp.sort(masked, axis=-1)[:, -top_k][:, None]
    chosen = masked >= kth
    w = jnp.where(chosen, scores, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scaling


def moe_layer(model: dict, x, router, bias, expert, shared) -> jnp.ndarray:
    """x [T, d] normed -> the layer's feed-forward output for this share.
    ``expert(e)`` returns held expert ``e``'s (wgu, wd); ``shared`` is the
    shared expert's pair or None (leave it out: the share test adds it once)."""
    s = dims(model)
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router, precision=HI))
    w = route(scores, bias, model["num_experts_per_tok"], model["n_group"],
              model["topk_group"], float(model["routed_scaling_factor"]))
    y = jnp.zeros_like(x)
    for e in range(s["n"]):
        y = y + w[:, s["lo"] + e][:, None] * _swiglu(x, *expert(e))
    if shared is not None:
        y = y + _swiglu(x, *shared)
    return y


def logits_at(model: dict, wseed: int, sequences: list, positions: list,
              control: str | None = None, q_block: int = 256, pad_to: int = 128) -> list:
    """Float32 logits of each sequence at its own ``positions`` (position p
    gives the distribution of token p + 1).  Sequences are right-padded to one
    length; causal attention hides the padding from every real position."""
    s = dims(model)
    w = Weights(model, wseed, control)
    s_pad = -(-max(len(q) for q in sequences) // pad_to) * pad_to
    ids = np.zeros((len(sequences), s_pad), np.int32)
    for i, seq in enumerate(sequences):
        ids[i, :len(seq)] = seq
    eps = float(model["rms_norm_eps"])
    inv_freq = jnp.asarray(yarn_inv_freq(s["rope"], float(model["rope_theta"]),
                                         model["rope_scaling"]))
    scale = softmax_scale(model)
    # one sequence at a time: at 9k tokens its hidden states are 0.26 GB,
    # and the weights are a function of the seed, made again for each
    rows = []
    for j in range(len(sequences)):
        hid = w.embed(ids[j:j + 1])
        for li in range(s["ld"] + s["lm"]):
            stack, i = ("dense", li) if li < s["ld"] else ("moe", li - s["ld"])
            hid = attention(w, stack, i, hid, inv_freq, s, scale, eps, q_block)
            x = _rms(hid, eps)
            if stack == "dense":
                hid = hid + dense_mlp(w, i, x, s)
            else:
                y = moe_layer(model, x.reshape(-1, s["d"]), w.at("moe.router", i),
                              w.at("moe.e_bias", i),
                              lambda e, i=i: (w.at("moe.e_wgu", i, e), w.at("moe.e_wd", i, e)),
                              (w.at("moe.s_wgu", i), w.at("moe.s_wd", i)))
                hid = hid + y.reshape(hid.shape)
        rows.append(_rms(hid[0, jnp.asarray(positions[j])], eps))
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
