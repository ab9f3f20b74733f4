"""RMSNorm (the Qwen2/Llama family normalization).

Computed in float32 regardless of input dtype — the variance accumulation
underflows in bfloat16 — then cast back before the weight multiply,
matching HF's Qwen2RMSNorm numerics so logits-parity tests against the
reference model hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return normed.astype(orig_dtype) * weight


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Cohere's LayerNorm: the mean taken off, no bias, ``(x - mean x) /
    sqrt(var x + eps) * w``; statistics and the weight multiply in float32 (the
    published module casts back after the weight)."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def rms_norm_zero_centered(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """Qwen3-Next's RMSNorm: the stored weight is the scale's distance from
    one, ``y = x / rms(x) * (1 + w)``, all of it in float32 (the published
    module multiplies before it casts back)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def rms_norm_gated(x: jnp.ndarray, gate: jnp.ndarray, weight: jnp.ndarray,
                   eps: float = 1e-6, gate_fn=jax.nn.silu) -> jnp.ndarray:
    """The Gated DeltaNet output norm: ``rmsnorm(x) * w * silu(gate)`` over
    the last axis (one head), norm before gate, plain weight; float32 out
    (``gate_fn``: Kimi Delta Attention's gate is a sigmoid)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return normed * gate_fn(gate.astype(jnp.float32))


def rms_norm_gate_first(x: jnp.ndarray, gate: jnp.ndarray, weight: jnp.ndarray, groups: int,
                        eps: float = 1e-6) -> jnp.ndarray:
    """Mamba-2's output norm as Nemotron-H publishes it (``norm_before_gate=False``,
    ``group_size = width / groups``): GATE FIRST, then the norm BY GROUP,
    ``rmsnorm_group(x * silu(gate)) * w`` over each of ``groups`` runs of the last
    axis; float32 out.  ``rms_norm_gated`` above is norm-first over one head."""
    y = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    yg = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(y.shape) * weight.astype(jnp.float32)
