"""Weight-only int8 quantization for the decoder.

Fills the role AWQ fills in the reference deployment (vLLM serves
Qwen2.5-Coder-7B-Instruct-AWQ on an 8 GB GPU — helm/values.yaml:67): a 7B
bf16 checkpoint (~15.2 GB) does not fit a 16 GB v5e chip next to its KV
pools, but int8 weights (~7.6 GB) do.  Decode is HBM-bandwidth-bound, so
halving weight bytes is also the main single-chip speed lever.

Scheme: per-output-channel symmetric int8 —
    scale[o] = max_i |W[i, o]| / 127        (bf16 scales)
    W_q[i, o] = round(W[i, o] / scale[o])   (int8)
Quantized tensors are ``QuantizedLinear(q, s)`` pytree nodes; matmuls go
through :func:`qmatmul`, which dequantizes inside the XLA program — the
convert+scale fuses into the dot's operand read on TPU (measured ~590 GB/s
effective weight bandwidth for 7B decode, i.e. no materialized bf16 copy),
so no hand-written dequant kernel is needed.

The embedding table quantizes too (per-ROW scales — ``quantize_embedding``):
a tied-weight model reads it in full every decode step for logits, so at
0.5B it is ~27 % of per-step weight traffic.  Token lookups go through
``embedding_lookup`` (gather int8 rows, scale per row).  Norms and biases
stay bf16.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.runtime import on_tpu


class QuantizedLinear(NamedTuple):
    """Weight-only int8 projection: ``q`` int8 [.., in, out], ``s`` bf16
    per OUTPUT channel [.., out]."""

    q: jnp.ndarray
    s: jnp.ndarray


class QuantizedEmbedding(NamedTuple):
    """Weight-only int8 embedding table: ``q`` int8 [V, d], ``s`` bf16 per
    vocab ROW [V].  A distinct type from QuantizedLinear because the scale
    axis differs — generic linear consumers (qmatmul/dequantize) must not
    silently apply row scales as column scales."""

    q: jnp.ndarray
    s: jnp.ndarray


class QuantizedLinear4(NamedTuple):
    """Weight-only 4-bit projection (the AWQ-class scheme the reference
    actually deploys — vLLM serves Qwen2.5-Coder-7B-Instruct-AWQ,
    /root/reference/helm/values.yaml:67).  Group-wise ASYMMETRIC uint4:

        w[i, o] ≈ q[i, o] * s[g(i), o] - zs[g(i), o]

    with g(i) = i // group_size over the INPUT axis — matching AWQ's
    group-128/64 geometry (scales+zeros per input group per output channel).

    ``q`` packs two nibbles per byte plane-wise WITHIN each group: for
    group g of size gsz, byte row j holds original rows (g*gsz + j) in the
    low nibble and (g*gsz + j + gsz/2) in the high nibble.  Unpacking is
    two shifts + one concat on the in-group axis — no interleave/transpose
    — so XLA fuses the dequant into the consuming dot's operand stream
    like the int8 path.  Packing within groups (not across the whole input
    axis) keeps row-parallel TP shards self-contained: any shard boundary
    that lands on a group boundary owns whole groups of bytes AND their
    scales, so GSPMD never has to redistribute the dequantized weight.

    Fields: ``q`` uint8 [.., in/2, out]; ``s`` bf16 [.., in/group, out];
    ``zs`` bf16 [.., in/group, out] with dequant ``w = q*s - zs``
    (zs = -group_min; storing the product form makes dequant a fused
    multiply-subtract)."""

    q: jnp.ndarray
    s: jnp.ndarray
    zs: jnp.ndarray


def _quantize_symmetric(w, axis: int):
    """Shared symmetric-int8 recipe: reduce |w| over ``axis``, scale to
    127, round/clip, bf16 scales with the reduced axis squeezed out.

    Computed HOST-side in numpy: quantizing a 7B tree with eager device ops
    would transiently materialize ~15 GB of f32 on the 16 GB chip this
    feature exists to fit — only the int8 weights and bf16 scales ever
    reach the device."""
    import ml_dtypes
    import numpy as np

    w_np = np.asarray(w, dtype=np.float32)  # pulls device arrays to host
    amax = np.max(np.abs(w_np), axis=axis, keepdims=True)
    scale = np.maximum(amax / 127.0, 1e-8)
    q = np.clip(np.round(w_np / scale), -127, 127).astype(np.int8)
    s = np.squeeze(scale, axis=axis).astype(ml_dtypes.bfloat16)
    return jnp.asarray(q), jnp.asarray(s)


def quantize_weight(w) -> QuantizedLinear:
    """Per-output-channel symmetric int8.  ``w`` is [in, out] or stacked
    [L, in, out]; the input (reduction) axis is -2, so scales are [out] /
    [L, out]."""
    q, s = _quantize_symmetric(w, axis=-2)
    return QuantizedLinear(q=q, s=s)


def dequant_weight(w, dtype) -> jnp.ndarray:
    """Compute-dtype view of a maybe-quantized linear weight.  THE one
    definition of the int8/int4->dtype expression — every consumer
    (qmatmul, the MoE expert einsums, dequantize) routes through here so a
    scheme change cannot silently miss a path.  XLA fuses the
    convert+scale into the consuming dot's operand stream on TPU; no bf16
    copy is materialized for the common shapes."""
    if isinstance(w, QuantizedLinear):
        return w.q.astype(dtype) * w.s.astype(dtype)[..., None, :]
    if isinstance(w, QuantizedLinear4):
        lead, out = w.q.shape[:-2], w.q.shape[-1]
        n_g = w.s.shape[-2]
        in_half = w.q.shape[-2]  # in/2 packed byte rows
        half_g = in_half // n_g  # gsz/2 byte rows per group
        pg = w.q.reshape(*lead, n_g, half_g, out)
        lo = (pg & jnp.uint8(0xF)).astype(dtype)
        hi = (pg >> jnp.uint8(4)).astype(dtype)
        grouped = jnp.concatenate([lo, hi], axis=-2)  # [.., n_g, gsz, out]
        wf = (
            grouped * w.s[..., :, None, :].astype(dtype)
            - w.zs[..., :, None, :].astype(dtype)
        )
        return wf.reshape(*lead, 2 * in_half, out)
    return w


def dequantize(t, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Full-precision reconstruction (f32 math, then cast) for tests."""
    if isinstance(t, QuantizedLinear4):
        return dequant_weight(
            QuantizedLinear4(
                q=t.q, s=t.s.astype(jnp.float32), zs=t.zs.astype(jnp.float32)
            ),
            jnp.float32,
        ).astype(dtype)
    return dequant_weight(
        QuantizedLinear(q=t.q, s=t.s.astype(jnp.float32)), jnp.float32
    ).astype(dtype)


def quantize_weight4(w, group_size: int = 64) -> QuantizedLinear4:
    """Group-wise asymmetric uint4 (AWQ-class).  ``w`` is [in, out] or
    stacked [.., in, out]; groups of ``group_size`` run along the input
    axis.  64 (not AWQ's usual 128) is the default because every Qwen2
    in-dimension splits into 64-token groups that stay whole under tp<=8
    row-parallel sharding.  Host-side numpy like _quantize_symmetric (a 7B
    tree must never materialize in f32 on the device being quantized for)."""
    import ml_dtypes
    import numpy as np

    w_np = np.asarray(w, dtype=np.float32)
    in_dim, out = w_np.shape[-2], w_np.shape[-1]
    if group_size % 2 or in_dim % group_size:
        raise ValueError(
            f"input dim {in_dim} must be divisible by the (even) group_size "
            f"{group_size} for in-group nibble plane packing"
        )
    lead = w_np.shape[:-2]
    n_g, half = in_dim // group_size, group_size // 2
    grouped = w_np.reshape(*lead, n_g, group_size, out)
    mx = grouped.max(axis=-2, keepdims=True)
    mn = grouped.min(axis=-2, keepdims=True)
    scale = np.maximum((mx - mn) / 15.0, 1e-8)
    # w ≈ q*scale + mn, i.e. zs = -mn.  Unlike AWQ's nibble-stored zeros,
    # zs is bf16, so no [0,15] clamp: one-sided groups (all-positive mn>0)
    # keep their full range instead of saturating at nibble 15.
    q = np.clip(np.round((grouped - mn) / scale), 0, 15).astype(np.uint8)
    packed = (q[..., :half, :] | (q[..., half:, :] << 4)).reshape(
        *lead, in_dim // 2, out
    )
    s = np.squeeze(scale, axis=-2).astype(ml_dtypes.bfloat16)
    zs = np.squeeze(-mn, axis=-2).astype(ml_dtypes.bfloat16)
    return QuantizedLinear4(q=jnp.asarray(packed), s=jnp.asarray(s), zs=jnp.asarray(zs))


def q4_matmul(x: jnp.ndarray, w: "QuantizedLinear4", preferred=None) -> jnp.ndarray:
    """``x @ dequant(w)`` as TWO dots plus a zero-point correction, never
    materializing the unpacked weight:

        y = x_lo @ (lo(q)*s) + x_hi @ (hi(q)*s) - (Σ_j x)[g] @ zs[g]

    where lo/hi are the in-group nibble planes and x splits the same way.
    The nibble mask/shift and the group-scale multiply are ELEMENTWISE on
    a dot operand — XLA fuses them into the operand stream exactly like
    the int8 convert+scale.  The concat form (dequant_weight) does not
    reliably fuse: measured 0.21 ms vs 0.06 ms per [32,3584]x[3584,18944]
    matmul on v5e (int8: 0.49 ms) — this formulation is what makes int4
    HALVE the decode weight-read time instead of tripling it.

    ``w`` leaves must be unstacked ([in/2, out]); stacked layers arrive
    here sliced by the layer scan.  ``preferred``: accumulation dtype for
    the dots (float32 for logits)."""
    lead = x.shape[:-1]
    in_dim = x.shape[-1]
    n_g = w.s.shape[-2]
    out = w.q.shape[-1]
    gsz = in_dim // n_g
    half = gsz // 2
    dt = x.dtype
    pg = w.q.reshape(n_g, half, out)
    s = w.s[:, None, :].astype(dt)
    lo = (pg & jnp.uint8(0xF)).astype(dt) * s
    hi = (pg >> jnp.uint8(4)).astype(dt) * s
    xg = x.reshape(*lead, n_g, gsz)
    x_lo, x_hi = xg[..., :half], xg[..., half:]
    kw = {} if preferred is None else {"preferred_element_type": preferred}
    y = (
        jnp.einsum("...gj,gjo->...o", x_lo, lo, **kw)
        + jnp.einsum("...gj,gjo->...o", x_hi, hi, **kw)
        - jnp.einsum("...g,go->...o", xg.sum(axis=-1), w.zs.astype(dt), **kw)
    )
    return y


class Layered4(NamedTuple):
    """A per-layer VIEW into stacked int4 weights: the full [L, in/2, out]
    arrays plus the current layer index.  The layer loops of the decode
    burst and the paged forward build these instead of letting the scan
    slice quantized leaves — the Pallas GEMM then indexes (layer, tile)
    directly and no per-layer weight copy is ever materialized (the same
    discipline as the rank-5 KV pools)."""

    q: jnp.ndarray  # [L, in/2, out] uint8
    s: jnp.ndarray  # [L, n_g, out] bf16
    zs: jnp.ndarray  # [L, n_g, out] bf16
    layer: jnp.ndarray  # scalar int32
    # W4A8 routing hint: None = auto (decode-sized batches take the MXU
    # int8 path), False = force exact bf16-dequant — the prefill/verify
    # paths pin False so an engine whose prefill_chunk is decode-sized
    # never silently relaxes the prompt-processing accuracy contract
    w4a8: bool | None = None


class Layered4XLA(NamedTuple):
    """Layered4's XLA-route twin: same fields, but ``qmatmul`` lowers it
    through the two-dot einsum formulation instead of the Pallas kernel.
    Used when the weights are GSPMD-sharded (TP meshes): a pallas_call is
    an opaque custom call with no partitioning rule, so GSPMD would have
    to all-gather the sharded weight stacks to feed it — the einsum path
    partitions normally."""

    q: jnp.ndarray
    s: jnp.ndarray
    zs: jnp.ndarray
    layer: jnp.ndarray


def _use_pallas_int4() -> bool:
    return on_tpu()


def q4_dispatch(x, q, s, zs, layer=None, out_dtype=None, kernel: bool = True,
                w4a8: bool | None = None):
    """THE int4 matmul router (every consumer — qmatmul, _logits — goes
    through here): Pallas GEMM on TPU when ``kernel`` (W4A8 MXU-int8 route
    for decode-sized batches, exact bf16-dequant otherwise — see
    ``int4_matmul``), else the two-dot XLA formulation."""
    if kernel and _use_pallas_int4():
        from githubrepostorag_tpu.ops.pallas_int4 import int4_matmul

        return int4_matmul(x, q, s, zs, layer=layer, out_dtype=out_dtype,
                           w4a8=w4a8)
    if layer is not None:
        sl = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
        q, s, zs = sl(q), sl(s), sl(zs)
    preferred = out_dtype if out_dtype is not None and out_dtype != x.dtype else None
    y = q4_matmul(x, QuantizedLinear4(q, s, zs), preferred=preferred)
    return y if out_dtype is None else y.astype(out_dtype)


def _split_q4(layers: dict) -> tuple[dict, dict]:
    """Partition a layer-param dict into (scan-sliceable leaves, stacked
    int4 stacks).  Layer loops scan the first and view the second through
    ``Layered4`` at each index — see ``qmatmul``."""
    q4 = {k: v for k, v in layers.items() if isinstance(v, QuantizedLinear4)}
    rest = {k: v for k, v in layers.items() if k not in q4}
    return rest, q4


def _with_layered_q4(p: dict, q4_stacks: dict, layer, kernel: bool = True,
                     w4a8: bool | None = None) -> dict:
    """Per-layer param dict = sliced leaves + Layered4 views at ``layer``.
    ``kernel=False`` (TP-sharded weights) builds the XLA-route twin —
    see Layered4XLA.  ``w4a8`` is the routing hint carried into each view
    (decode burst: auto; prefill/verify: False)."""
    if not q4_stacks:
        return p
    out = dict(p)
    for k, v in q4_stacks.items():
        if kernel:
            out[k] = Layered4(q=v.q, s=v.s, zs=v.zs, layer=layer, w4a8=w4a8)
        else:
            out[k] = Layered4XLA(q=v.q, s=v.s, zs=v.zs, layer=layer)
    return out


def qmatmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` where ``w`` is a plain array, QuantizedLinear (int8),
    QuantizedLinear4 (int4), or Layered4 (stacked int4 + layer index).

    int8 dequant fuses into the dot's operand read under XLA.  int4 does
    NOT (the unpack chain materializes — see ops/pallas_int4.py), so on
    TPU int4 routes to the Pallas in-VMEM-dequant GEMM; elsewhere to the
    two-dot XLA formulation (q4_matmul), which is also the kernel's
    correctness oracle."""
    if isinstance(w, Layered4):
        return q4_dispatch(x, w.q, w.s, w.zs, layer=w.layer, w4a8=w.w4a8)
    if isinstance(w, Layered4XLA):
        return q4_dispatch(x, w.q, w.s, w.zs, layer=w.layer, kernel=False)
    if isinstance(w, QuantizedLinear4):
        return q4_dispatch(x, w.q, w.s, w.zs)
    return x @ dequant_weight(w, x.dtype)


def quantize_embedding(w) -> QuantizedEmbedding:
    """Per-ROW symmetric int8 for the embedding table [V, d]: each vocab row
    is one channel, so the tied-weight logits contraction over d dequantizes
    per output logit, and the token-lookup path is ``q[ids] * s[ids]``."""
    q, s = _quantize_symmetric(w, axis=-1)
    return QuantizedEmbedding(q=q, s=s)


def embedding_lookup(embed, ids: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Token embedding gather for plain or int8 tables."""
    if isinstance(embed, QuantizedEmbedding):
        rows = jnp.take(embed.q, ids, axis=0).astype(dtype)
        return rows * jnp.take(embed.s, ids, axis=0)[..., None].astype(dtype)
    return jnp.take(embed, ids, axis=0)


def _concat_linears(ws, biases=None):
    """Concatenate same-input linear leaves along the OUTPUT axis — valid
    for plain arrays and both quantized schemes, because every per-output
    quantity (int8 q columns + per-channel s; int4 packed columns +
    per-group s/zs) concatenates on its last axis while the input-axis
    structure (rows, nibble plane packing, group boundaries) is untouched."""
    w0 = ws[0]
    if isinstance(w0, QuantizedLinear):
        w = QuantizedLinear(
            q=jnp.concatenate([x.q for x in ws], axis=-1),
            s=jnp.concatenate([x.s for x in ws], axis=-1),
        )
    elif isinstance(w0, QuantizedLinear4):
        w = QuantizedLinear4(
            q=jnp.concatenate([x.q for x in ws], axis=-1),
            s=jnp.concatenate([x.s for x in ws], axis=-1),
            zs=jnp.concatenate([x.zs for x in ws], axis=-1),
        )
    else:
        w = jnp.concatenate(ws, axis=-1)
    if biases is None:
        return w
    return w, jnp.concatenate(biases, axis=-1)


def fuse_projections(params: dict, in_place: bool = False) -> dict:
    """Single-chip serving layout transform: fuse wq|wk|wv -> wqkv and
    wg|wu -> wgu so each decode step runs 4 projection matmuls per layer
    instead of 7.  Device profiling (round 4) showed ~60 us of fixed
    per-matmul cost at 7B decode shapes — the three sub-10 MB projections
    (wk/wv at 0.9 MB int4) were pure overhead; fusing also widens the
    quantized-GEMM tiles.  The model block detects the fused keys
    (qwen2._block) and splits activations after the matmul, which is a
    free lane slice.  NOT applied under a TP mesh: a column-sharded fused
    weight would put the q|k|v split boundaries inside shards and force a
    resharding gather after every matmul — the Megatron answer is a
    per-shard interleaved layout, deliberately not replicated here; the
    mesh path keeps per-projection leaves and GSPMD specs.

    ``in_place=True`` mutates ``params["layers"]``, popping each
    per-projection leaf before its replacement concat materializes — on a
    SOLELY-OWNED device-resident 7B tree the transient is one fused stack
    (<= 4 GB), not a full second tree (load_qwen2 uses this).  The default
    copies the dicts so a caller-shared tree is never altered (the Engine
    wraps trees it does not own); a big tree fused this way transiently
    holds both layouts — prefer building big trees fused from the start
    (init_params_quantized(fuse=True) / load_qwen2(fuse=True), after
    which this is a no-op).  MoE layers pass through untouched."""
    if not in_place:
        params = dict(params, layers=dict(params["layers"]))
    layers = params["layers"]
    if "wq" in layers:
        layers["wqkv"], layers["bqkv"] = _concat_linears(
            [layers.pop("wq"), layers.pop("wk"), layers.pop("wv")],
            [layers.pop("bq"), layers.pop("bk"), layers.pop("bv")],
        )
    if "wg" in layers:
        layers["wgu"] = _concat_linears([layers.pop("wg"), layers.pop("wu")])
    return params


def quantize_qwen2_params(
    params: dict, embeddings: bool = True, bits: int = 8, group_size: int = 64
) -> dict:
    """Quantize every linear projection of a Qwen2(-MoE) param tree
    (attention wq/wk/wv/wo, the dense MLP or the expert+shared-expert
    stacks, lm_head when present, and — by default — the embedding table,
    which a tied-weight model reads IN FULL every decode step for logits);
    norms, biases, the MoE router, and the shared-expert gate stay bf16.

    ``bits=4`` switches projections to the AWQ-class group-wise uint4
    scheme (quantize_weight4); the embedding table stays per-row int8
    either way — AWQ itself keeps embeddings full precision, and a 4-bit
    table would put its larger error on every token AND every logit."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    qw = (
        quantize_weight
        if bits == 8
        else lambda w: quantize_weight4(w, group_size=group_size)
    )
    out = dict(params)
    layers = dict(params["layers"])
    # Quantize every projection leaf PRESENT, covering all four layouts:
    # dense/MoE x unfused/fused (fuse_projections renames wq|wk|wv -> wqkv
    # and wg|wu -> wgu; a fused-at-init tree must quantize without being
    # un-fused first).  MoE experts + shared expert quantize with stacked
    # per-expert scales (the leading dims pass through both schemes); the
    # router and the [d, 1] shared gate stay full precision — they are
    # tiny and routing decisions are the precision-sensitive part of a
    # sparse model.  Norms and biases are never in this list.
    matched = 0
    for name in ("wq", "wk", "wv", "wqkv", "wo", "wg", "wu", "wgu", "wd",
                 "e_wg", "e_wu", "e_wd", "s_wg", "s_wu", "s_wd"):
        if name in layers:
            layers[name] = qw(layers[name])
            matched += 1
    if matched == 0:
        # A renamed/foreign tree must fail loudly: every known layout has
        # at least one projection leaf, and returning the tree untouched
        # would silently serve FULL-PRECISION weights under
        # quantizeWeights:"int8" (no error, just 2x the HBM and none of
        # the speedup — the failure only shows up in a memory profile)
        raise ValueError(
            "quantize_qwen2_params: no known projection leaf found in "
            f"params['layers'] (keys: {sorted(layers)}); the tree would "
            "pass through at full precision"
        )
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"] = qw(params["lm_head"])
    if embeddings:
        out["embed"] = quantize_embedding(params["embed"])
    return out


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _devrand(shape: tuple, salt: jnp.ndarray, kind: str) -> jnp.ndarray:
    """Uniform-ish random leaf ON DEVICE via a Knuth-hashed iota — a pure
    elementwise chain XLA fuses straight into the FINAL dtype, so a
    multi-GB random leaf costs one device-side write (in the narrow output
    type — the u32 intermediate must stay inside this jit or a 7B-scale
    leaf transiently materializes 4x its bytes and OOMs the chip) and ZERO
    host->device transfer.  The host-numpy path this replaces cost
    minutes of single-thread RNG plus a multi-GB host->device copy for the
    7B int8 tree; bench throughput is weight-value-independent, so hash
    quality only needs to defeat trivial value patterns.

    kinds: "u8" uniform uint8; "i8" uniform int8 (bitcast); "bf16"
    centered floats with std ~ 0.02."""
    n = 1
    for s_ in shape:
        n *= s_
    i = jax.lax.iota(jnp.uint32, n)
    h = i * jnp.uint32(2654435761) + salt
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = (h ^ (h >> 13)).reshape(shape)
    if kind == "u8":
        return (h & jnp.uint32(0xFF)).astype(jnp.uint8)
    if kind == "i8":
        # clamp -128 -> -127: real checkpoints clip symmetric int8 to
        # +-127, and the documented "uniform int8 std ~73" scale
        # derivation assumes that range (ADVICE r04)
        return jnp.maximum(
            jax.lax.bitcast_convert_type(
                (h & jnp.uint32(0xFF)).astype(jnp.uint8), jnp.int8
            ),
            jnp.int8(-127),
        )
    assert kind == "bf16", kind
    # uniform [0, 2^32) -> centered, std ~ 0.02 (uniform std = range/sqrt(12))
    return ((h.astype(jnp.float32) - 2147483648.0) * (0.02 / 1.24e9)).astype(
        jnp.bfloat16
    )


@startup.records("startup.weights", settle=True)
def init_params_quantized(cfg, seed: int = 0, bits: int = 8,
                          group_size: int = 64, fuse: bool = False) -> dict:
    """Random quantized Qwen2 params (int8 or AWQ-class int4), generated
    leaf by leaf ON DEVICE (_devrand): a 7B bf16 tree cannot be
    materialized on a 16 GB chip just to quantize it, and building the
    quantized tree host-side costs minutes of RNG plus a multi-GB
    host->device copy.  Real checkpoints stream through quantize_weight /
    quantize_weight4 shard by shard in hf_loader.  Bench/test use:
    throughput is weight-value-independent."""
    if getattr(cfg, "num_experts", 0):
        raise NotImplementedError(
            "random quantized MoE init is not implemented (this helper exists "
            "for dense-geometry benches); real MoE checkpoints quantize "
            "through load_qwen2(..., quantize=True)"
        )
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    salt_box = [jnp.uint32(seed * 40503 + 12345)]

    def noise(shape, kind):
        salt_box[0] = salt_box[0] * jnp.uint32(747796405) + jnp.uint32(1)
        return _devrand(tuple(shape), salt_box[0], kind)

    d, nq, nkv, hd, inter, L, v = (
        cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.intermediate_size, cfg.num_layers, cfg.vocab_size,
    )

    def bf16(*shape):
        return noise(shape, "bf16")

    def qlin8(*shape):
        q = noise(shape, "i8")
        # scale so dequantized std ~ 0.02 (uniform int8 std ~ 73)
        s = jnp.full(shape[:-2] + shape[-1:], 0.02 / 73.0, dtype=jnp.bfloat16)
        return QuantizedLinear(q=q, s=s)

    def qlin4(*shape):
        in_dim, out = shape[-2], shape[-1]
        if group_size % 2 or in_dim % group_size:
            raise ValueError(
                f"input dim {in_dim} must be divisible by the (even) "
                f"group_size {group_size} (same contract as quantize_weight4)"
            )
        packed = noise(shape[:-2] + (in_dim // 2, out), "u8")
        sshape = shape[:-2] + (in_dim // group_size, out)
        # uniform uint4 std ~ 4.6; center with zs = 7.5*s
        s = jnp.full(sshape, 0.02 / 4.6, dtype=jnp.bfloat16)
        zs = jnp.full(sshape, 7.5 * 0.02 / 4.6, dtype=jnp.bfloat16)
        return QuantizedLinear4(q=packed, s=s, zs=zs)

    qlin = qlin8 if bits == 8 else qlin4

    layers = {
        "ln1": jnp.ones((L, d), dtype=jnp.bfloat16),
        "ln2": jnp.ones((L, d), dtype=jnp.bfloat16),
        "wo": qlin(L, nq * hd, d),
        "wd": qlin(L, inter, d),
    }
    if fuse:
        # generate the fused single-chip serving layout DIRECTLY (random
        # weights): fusing a resident 7B device tree with jnp.concatenate
        # would transiently double weight HBM — see fuse_projections
        layers.update({
            "wqkv": qlin(L, d, (nq + 2 * nkv) * hd),
            "bqkv": jnp.zeros((L, (nq + 2 * nkv) * hd), dtype=jnp.bfloat16),
            "wgu": qlin(L, d, 2 * inter),
        })
    else:
        layers.update({
            "wq": qlin(L, d, nq * hd),
            "bq": jnp.zeros((L, nq * hd), dtype=jnp.bfloat16),
            "wk": qlin(L, d, nkv * hd),
            "bk": jnp.zeros((L, nkv * hd), dtype=jnp.bfloat16),
            "wv": qlin(L, d, nkv * hd),
            "bv": jnp.zeros((L, nkv * hd), dtype=jnp.bfloat16),
            "wg": qlin(L, d, inter),
            "wu": qlin(L, d, inter),
        })
    embed_q = noise((v, d), "i8")
    embed_s = jnp.full((v,), 0.02 / 73.0, dtype=jnp.bfloat16)
    params = {"embed": QuantizedEmbedding(q=embed_q, s=embed_s), "layers": layers,
              "norm": jnp.ones((d,), dtype=jnp.bfloat16)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = qlin(d, v)
    return params


def params_nbytes(params) -> int:
    return sum(
        leaf.nbytes for leaf in jax.tree.leaves(params) if hasattr(leaf, "nbytes")
    )
