"""Rotary position embeddings (rotate-half convention, Llama/Qwen2 family; the
interleaved one, Cohere's ``rope_gptj``, at the end).

cos/sin are computed in float32 from integer positions so decode steps at
position 30k+ keep full precision, then applied in the activation dtype.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_inv_freq(head_dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> jnp.ndarray:
    """YaRN inverse frequencies [head_dim/2]: dimensions that turn more than
    ``beta_fast`` times inside the original context keep their frequency,
    those that turn less than
    ``beta_slow`` times are interpolated (divided by ``factor``), and a linear
    ramp blends the two between the correction dimensions.  Two users, who
    differ in where YaRN's temperature goes: DeepSeek-V3 (models/deepseek_v3.py)
    folds ``yarn_mscale`` squared into its softmax scale and rotates by the plain
    cos and sin; Mellum2's global layers (models/mellum.py) are handed the
    source's ``attention_factor`` and hand it to ``rope_cos_sin(factor=)``, so
    the rotated queries AND the keys its pool stores carry it."""
    def correction_dim(rotations: float) -> float:
        return head_dim * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    extrapolated = 1.0 / theta ** exponents
    interpolated = extrapolated / factor
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(high - low, 0.001)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)  # 1 where the frequency is extrapolated
    return interpolated * (1.0 - keep) + extrapolated * keep


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0,
                 inv_freq: jnp.ndarray | None = None, factor: float = 1.0):
    """positions [B, S] (int32) -> cos, sin each [B, S, head_dim].
    ``inv_freq`` [head_dim/2] replaces the plain theta ladder (YaRN, both users
    of ``yarn_inv_freq``); ``factor`` multiplies both tables (a YaRN
    ``attention_factor`` that the source states for the table itself: whatever
    is rotated by them, a stored key too, is ``factor`` times as long)."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, hd/2]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [B, S, hd]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos, sin) if factor == 1.0 else (cos * factor, sin * factor)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(q: jnp.ndarray, k: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """q [B, S, n_q, hd], k [B, S, n_kv, hd]; cos/sin [B, S, hd]."""
    cos = cos[:, :, None, :].astype(q.dtype)
    sin = sin[:, :, None, :].astype(q.dtype)
    q_out = q * cos + _rotate_half(q) * sin
    k_out = k * cos + _rotate_half(k) * sin
    return q_out, k_out


def rope_rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """One tensor [..., hd] with cos/sin already broadcastable to it."""
    return x * cos.astype(x.dtype) + _rotate_half(x) * sin.astype(x.dtype)


def rope_rotate_leading(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotary position on the leading ``cos.shape[-1]`` columns of ``x``
    [..., hd] (``partial_rotary_factor``: rotate-half inside that slice), the
    rest passed through."""
    r = cos.shape[-1]
    return jnp.concatenate([rope_rotate(x[..., :r], cos, sin), x[..., r:]], axis=-1)


def rope_cos_sin_interleaved(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0):
    """positions [B, S] -> cos, sin each [B, S, head_dim] float32 for the
    INTERLEAVED form (``rope_gptj``: pair i is columns 2i and 2i + 1, so each
    frequency stands twice side by side)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.repeat(positions[..., None].astype(jnp.float32) * inv_freq, 2, axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def rope_rotate_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """One tensor [..., hd] rotated in interleaved pairs, in float32, cast back:
    ``x * cos + rot(x) * sin`` with ``rot(x)[2i] = -x[2i + 1]``, ``rot(x)[2i + 1]
    = x[2i]``.  The partner of a column is its neighbour, so two rolls along the
    lanes and a select by parity stand for the reshape to pairs."""
    xf = x.astype(jnp.float32)
    even = (jnp.arange(x.shape[-1]) % 2) == 0
    rot = jnp.where(even, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    return (xf * cos + rot * sin).astype(x.dtype)
