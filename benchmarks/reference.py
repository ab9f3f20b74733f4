"""The plain reference: Qwen2's forward pass in float32 ``jax.numpy``, with
weights made here from the seed.

Nothing the program has made enters it: no weights, scales or tables.  The
weights are defined by the seed through the same rule the configuration
names (``weights.init``: a Knuth-hashed iota per leaf, int8 values with one
bfloat16 scale per channel), re-stated below from its description, and are
dequantised one layer at a time, so a 7B model fits beside the engine.  All
products run at ``Precision.HIGHEST`` (on a TPU a float32 matmul is
otherwise computed in bfloat16 passes).

No kernels, no cache, no batching tricks: full causal attention over the
whole sequence, computed in query blocks only to bound memory.

``degrade`` is the control of "How correct is decided": the same forward
with the weights re-rounded to the next precision below the one the
configuration states (int4 for int8, int8 for bfloat16).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
INT8_SCALE = 0.02 / 73.0  # the initialiser's: dequantised std ~ 0.02


def _dims(model: dict) -> tuple:
    d = model["hidden_size"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return d, nq, nkv, d // nq, model["intermediate_size"], model["num_hidden_layers"], \
        model["vocab_size"]


def leaf_order(model: dict, fuse: bool) -> list:
    """(name, shape) of the random leaves in the order the initialiser draws
    them; each draw advances the salt once."""
    d, nq, nkv, hd, inter, L, v = _dims(model)
    leaves = [("wo", (L, nq * hd, d)), ("wd", (L, inter, d))]
    if fuse:
        leaves += [("wqkv", (L, d, (nq + 2 * nkv) * hd)), ("wgu", (L, d, 2 * inter))]
    else:
        leaves += [("wq", (L, d, nq * hd)), ("wk", (L, d, nkv * hd)), ("wv", (L, d, nkv * hd)),
                   ("wg", (L, d, inter)), ("wu", (L, d, inter))]
    leaves.append(("embed", (v, d)))
    if not model["tie_word_embeddings"]:
        leaves.append(("lm_head", (d, v)))
    return leaves


def salts(wseed: int, n: int) -> list:
    s = (wseed * 40503 + 12345) & 0xFFFFFFFF
    out = []
    for _ in range(n):
        s = (s * 747796405 + 1) & 0xFFFFFFFF
        out.append(s)
    return out


def _hash_int8(i, salt):
    """The initialiser's rule for element ``i`` (uint32 flat index) of a leaf:
    a Knuth hash of the index and the leaf's salt, its low byte read as int8,
    -128 clamped to -127.  Returned as float32."""
    h = i * jnp.uint32(2654435761) + salt
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    q = jax.lax.bitcast_convert_type((h & jnp.uint32(0xFF)).astype(jnp.uint8), jnp.int8)
    return jnp.maximum(q, jnp.int8(-127)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("shape",))
def _int8_block(salt, offset, shape: tuple):
    """Elements [offset, offset + prod(shape)) of a leaf's flat hash
    sequence, as int8 values in float32."""
    n = 1
    for s_ in shape:
        n *= s_
    return _hash_int8(jax.lax.iota(jnp.uint32, n) + offset, salt).reshape(shape)


def _scale() -> jnp.ndarray:
    return jnp.asarray(INT8_SCALE, jnp.bfloat16).astype(jnp.float32)


def degrade(q: jnp.ndarray, scheme: str | None) -> jnp.ndarray:
    """int8 values (as float32) re-rounded to the control's precision."""
    if scheme is None:
        return q
    if scheme == "int4":
        return jnp.clip(jnp.round(q * (7.0 / 127.0)), -7, 7) * (127.0 / 7.0)
    raise ValueError(f"unknown control {scheme!r}")


class Weights:
    """The model's weights as a function of the seed, one slice at a time."""

    def __init__(self, model: dict, wseed: int, fuse: bool, control: str | None = None) -> None:
        self.model, self.control = model, control
        order = leaf_order(model, fuse)
        self.shape = dict(order)
        self.salt = {name: jnp.uint32(s) for (name, _), s in zip(order, salts(wseed, len(order)))}

    def layer(self, name: str, li: int) -> jnp.ndarray:
        _, a, b = self.shape[name]
        q = _int8_block(self.salt[name], jnp.uint32(li * a * b), (a, b))
        return degrade(q, self.control) * _scale()

    def embed(self, ids: np.ndarray) -> jnp.ndarray:
        d = self.shape["embed"][1]
        q = _int8_rows(self.salt["embed"], jnp.asarray(ids, jnp.uint32), d)
        return degrade(q, self.control) * _scale()


@partial(jax.jit, static_argnames=("d",))
def _int8_rows(salt, ids, d: int):
    """Rows ``ids`` of a [*, d] leaf: element (t, c) has flat index t * d + c."""
    return _hash_int8(ids[..., None] * jnp.uint32(d) + jax.lax.iota(jnp.uint32, d), salt)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@partial(jax.jit, static_argnames=("nq", "nkv", "theta", "eps", "q_block"))
def _layer(h, wqkv, wo, wgu, wd, *, nq, nkv, theta, eps, q_block):
    """One block on [B, S, d] float32, as the published description has it:
    pre-norm, grouped-query causal attention with rotate-half RoPE, SwiGLU.
    Biases are zero in this initialisation and are left out."""
    b, s, d = h.shape
    hd = wo.shape[0] // nq
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _rms(h, eps)
    qkv = jnp.einsum("bsd,de->bse", x, wqkv, precision=HI)
    q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
    q = _rope(q.reshape(b, s, nq, hd), pos, theta)
    k = _rope(k.reshape(b, s, nkv, hd), pos, theta)
    v = v.reshape(b, s, nkv, hd)
    g = nq // nkv
    q = q.reshape(b, s, nkv, g, hd)
    outs = []
    for q0 in range(0, s, q_block):
        qb = q[:, q0:q0 + q_block]
        hi = min(s, q0 + q_block)  # keys past the block's last query are masked anyway
        sc = jnp.einsum("bqkgh,btkh->bkgqt", qb, k[:, :hi], precision=HI) / jnp.sqrt(
            jnp.float32(hd))
        mask = (jnp.arange(hi)[None, :] <= (q0 + jnp.arange(qb.shape[1]))[:, None])
        sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bkgqt,btkh->bqkgh", p, v[:, :hi], precision=HI))
    attn = jnp.concatenate(outs, axis=1).reshape(b, s, nq * hd)
    h = h + jnp.einsum("bse,ed->bsd", attn, wo, precision=HI)
    x = _rms(h, eps)
    gu = jnp.einsum("bsd,de->bse", x, wgu, precision=HI)
    gate, up = jnp.split(gu, 2, axis=-1)
    return h + jnp.einsum("bse,ed->bsd", jax.nn.silu(gate) * up, wd, precision=HI)


@jax.jit
def _head(x, w):
    return jnp.einsum("nd,dv->nv", x, w, precision=HI)


def logits_at(model: dict, wseed: int, fuse: bool, sequences: list, positions: list,
              control: str | None = None, q_block: int = 512, pad_to: int = 128) -> list:
    """Float32 logits of each sequence at its own ``positions`` (indices
    into the sequence; position p gives the distribution of token p + 1).
    Sequences are right-padded to one length; causal attention makes the
    padding invisible to every real position."""
    d, nq, nkv, hd, inter, L, v = _dims(model)
    w = Weights(model, wseed, fuse, control)
    s_max = max(len(s) for s in sequences)
    s_pad = -(-s_max // pad_to) * pad_to
    ids = np.zeros((len(sequences), s_pad), np.int32)
    for i, seq in enumerate(sequences):
        ids[i, :len(seq)] = seq
    h = w.embed(ids)
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    for li in range(L):
        if fuse:
            wqkv, wgu = w.layer("wqkv", li), w.layer("wgu", li)
        else:
            wqkv = jnp.concatenate([w.layer(n, li) for n in ("wq", "wk", "wv")], axis=-1)
            wgu = jnp.concatenate([w.layer(n, li) for n in ("wg", "wu")], axis=-1)
        h = _layer(h, wqkv, w.layer("wo", li), wgu, w.layer("wd", li),
                   nq=nq, nkv=nkv, theta=theta, eps=eps, q_block=q_block)
    rows = jnp.concatenate([_rms(h[i, jnp.asarray(p)], eps) for i, p in enumerate(positions)])
    if model["tie_word_embeddings"]:
        raise NotImplementedError("tied output head: not needed by a configuration yet")
    chunks, step = [], -(-v // 8)
    for c0 in range(0, v, step):
        n = min(step, v - c0)
        # lm_head is [d, v] row-major: element (r, c) has flat index r * v + c
        cols = _head_cols(w, c0, n, d, v)
        chunks.append(np.asarray(_head(rows, cols)))
    flat = np.concatenate(chunks, axis=1)
    out, at = [], 0
    for p in positions:
        out.append(flat[at:at + len(p)])
        at += len(p)
    return out


@partial(jax.jit, static_argnames=("n", "d", "v"))
def _head_block(salt, c0, n: int, d: int, v: int):
    r = jax.lax.iota(jnp.uint32, d)[:, None] * jnp.uint32(v)
    c = jax.lax.iota(jnp.uint32, n)[None, :] + c0
    return _hash_int8(r + c, salt)


def _head_cols(w: Weights, c0: int, n: int, d: int, v: int) -> jnp.ndarray:
    q = _head_block(w.salt["lm_head"], jnp.uint32(c0), n, d, v)
    return degrade(q, w.control) * _scale()
