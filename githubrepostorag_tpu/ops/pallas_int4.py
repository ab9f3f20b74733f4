"""Pallas TPU int4 weight-only GEMM: in-VMEM dequant fused into the dot.

Why a kernel: XLA does not fuse the int4 unpack chain (nibble shift ->
group reshape -> scale multiply -> concat) into a dot operand the way it
fuses int8's convert+scale — device traces of the 7B int4 decode burst
show it materializing reshaped/scaled copies at ~37 ms/step of reshapes
plus ~26 ms/step of copies, making int4 3-6x SLOWER than int8.  Here the
packed tile is DMA'd to VMEM (half the int8 bytes off HBM — the entire
point of int4), unpacked and dequantized in VMEM, and fed straight to the
MXU.

Weights are the in-group plane-packed ``QuantizedLinear4`` layout
(models/quant.py): byte row j of group g holds original rows (g*gsz + j)
in the low nibble and (g*gsz + j + gsz/2) in the high nibble, so an
input-tile that is a whole number of groups unpacks with one in-VMEM
concat and its scale rows align exactly.

Stacked [L, in/2, out] weights ride in WHOLE with the layer index as a
prefetched scalar (same discipline as the rank-5 KV pools in
pallas_paged.py): the burst's layer loop never dynamic-slices a weight
into a materialized copy.

Oracle: models/quant.py::q4_matmul (the two-dot XLA formulation) — exact
same math, used on CPU and in interpret-mode tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_tile(total: int, unit: int, target: int) -> int:
    """Largest multiple of ``unit`` that divides ``total``, is <= target,
    AND keeps the TPU lane constraint (multiple of 128, unless it is the
    whole dimension — Pallas requires block minor dims be 128-aligned or
    full).  Falls back to ``total``."""
    import math

    step = math.lcm(unit, 128)
    t = (target // step) * step
    while t >= step:
        if total % t == 0:
            return t
        t -= step
    return total


def _int4_kernel(*refs, half: int, n_gt: int, layered: bool, sliced: bool):
    ii = pl.program_id(2)
    n_ii = pl.num_programs(2)
    # the scale blocks carry the FULL group axis (their shape must be
    # 8/128-aligned or full); this in-tile's rows slice out at the REF.
    # ``sliced`` is static: with one in-tile the whole block is the tile
    # (and Mosaic needs no provably-8-aligned dynamic sublane offset —
    # the wrapper guarantees n_gt % 8 == 0 whenever sliced)
    if layered:
        (_li_ref, xa_ref, xb_ref, q_ref, s_ref, zs_ref, out_ref, acc_ref) = refs
        pq = q_ref[0]  # [IT/2, OT]
        s = s_ref[0, pl.ds(ii * n_gt, n_gt)] if sliced else s_ref[0]
        zs = zs_ref[0, pl.ds(ii * n_gt, n_gt)] if sliced else zs_ref[0]
    else:
        (xa_ref, xb_ref, q_ref, s_ref, zs_ref, out_ref, acc_ref) = refs
        pq = q_ref[...]
        s = s_ref[pl.ds(ii * n_gt, n_gt)] if sliced else s_ref[...]
        zs = zs_ref[pl.ds(ii * n_gt, n_gt)] if sliced else zs_ref[...]

    @pl.when(ii == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ot = pq.shape[-1]
    dt = xa_ref.dtype  # bf16 serving; f32 in CPU-geometry tests
    # The unpack is VPU-bound (every weight element pays mask+cast+scale
    # while the MXU waits), so shave VPU work: no shift — the high nibble
    # stays in place (pq & 0xF0 = 16*nib) with 1/16 folded into its scale
    # — and no concat — the two nibble planes go to the MXU as TWO dots
    # against the matching halves of x (in-group plane packing makes both
    # planes contiguous row ranges).  Widening runs through int32: Mosaic
    # legalizes neither uint8 shifts nor uint8->bf16 casts.
    sdt = s.astype(dt)[:, None, :]
    zdt = zs.astype(dt)[:, None, :]
    # Unpack via int32 widening (Mosaic legalizes neither uint8 shifts nor
    # uint8->bf16 casts; an int8-domain bitcast variant measured ~12%
    # SLOWER — the convert path widens internally regardless).  No shift:
    # the high nibble stays in place (pq & 0xF0 = 16*nib) with 1/16 folded
    # into its scale.  Each plane's rows are distinct original rows, every
    # one dequantizing as nib*s - zs — both planes subtract the FULL zs.
    # The remaining cost is fundamental per-element convert throughput on
    # the VPU (the kernel is compute-bound, not HBM-bound, at 7B: ~2.9 GB
    # of int4 reads vs ~19 ms/step measured); the next step beyond this is
    # W4A8 — int8 activations on the MXU's native int8 path with per-group
    # int32 partial sums — which changes the accuracy contract.
    pq32 = pq.astype(jnp.int32)
    lo = (pq32 & 0x0F).astype(dt).reshape(n_gt, half, ot) * sdt - zdt
    hi = (pq32 & 0xF0).astype(dt).reshape(n_gt, half, ot) * (sdt / 16) - zdt
    # x arrives PRE-SPLIT into the two plane halves (wrapper-side — the
    # [MT, n_gt, gsz] lane slicing is an unsupported shape cast in Mosaic,
    # and activations are tiny for XLA to split)
    x_a = xa_ref[...]  # [MT, IT/2]
    x_b = xb_ref[...]
    dn = (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        x_a, lo.reshape(n_gt * half, ot), dn, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        x_b, hi.reshape(n_gt * half, ot), dn, preferred_element_type=jnp.float32
    )

    @pl.when(ii == n_ii - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _w4a8_kernel(*refs, half: int, n_gt: int, layered: bool, sliced: bool):
    """W4A8 tile: nibbles->int8 on the VPU's cheap integer path, the dots
    on the MXU's NATIVE int8 path (int8 x int8 -> int32), one dot per
    weight GROUP so each int32 partial picks up its own group scale at
    f32 accumulation.  Versus the bf16-dequant kernel (_int4_kernel) the
    per-weight-element VPU work drops from mask+cast+scale+subtract in
    bf16 to mask/shift+int8-cast — the group-scale multiply runs on the
    [MT, OT] partial (1/gsz of the weight elements per group) and the
    zero-point term leaves the kernel entirely (wrapper-side XLA dot).

    Both nibble planes stack into ONE [gsz, OT] int8 operand per group
    (a VMEM scratch written with two static half-slices), so the group
    dot runs at the full K=gsz MXU depth: the in-group plane packing puts
    plane rows at original positions [g*gsz, g*gsz+half) and
    [g*gsz+half, (g+1)*gsz), i.e. stacked [lo; hi] IS group g's rows in
    natural order, matching the wrapper's group-major activations.  The
    earlier two-dots-per-group form (one per plane) halved MXU weight
    throughput: a K=half dot occupies the same systolic passes as K=gsz.

    Accuracy contract: activations are quantized per token row to
    symmetric int8 (the wrapper's x/amax*127), so results differ from the
    bf16-dequant math by the activation-quant error (~1e-2 relative) —
    gated by parity tests mirroring int8's (tests/test_quant4.py)."""
    ii = pl.program_id(2)
    n_ii = pl.num_programs(2)
    if layered:
        (_li_ref, x_ref, q_ref, s_ref, out_ref, acc_ref, w_ref) = refs
        pq = q_ref[0]  # [IT/2, OT] uint8
        s = s_ref[0, pl.ds(ii * n_gt, n_gt)] if sliced else s_ref[0]
    else:
        (x_ref, q_ref, s_ref, out_ref, acc_ref, w_ref) = refs
        pq = q_ref[...]
        s = s_ref[pl.ds(ii * n_gt, n_gt)] if sliced else s_ref[...]

    @pl.when(ii == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s_f = s.astype(jnp.float32)  # [n_gt, OT]
    dn = (((1,), (0,)), ((), ()))
    for g in range(n_gt):  # static unroll: n_gt <= 16 by tile choice
        # unpack PER GROUP ([half, OT] at a time, static 32-row slices):
        # a whole-tile int32 widen materializes it/2 x OT x 4B of VMEM and
        # capped OT at ~1k for the big projections (259 grid steps for
        # wgu); group-at-a-time intermediates are ~100x smaller, so OT can
        # cover 4-9k columns and the grid shrinks ~10x.  int32 widen
        # because Mosaic legalizes neither uint8 shifts nor narrow casts.
        # (An explicitly double-buffered unpack/dot pipeline measured
        # NEUTRAL on-chip — Mosaic already schedules around the single
        # buffer's write-after-read hazard, so keep the simple form.)
        pq32 = pq[g * half : (g + 1) * half].astype(jnp.int32)
        w_ref[:half] = (pq32 & 0x0F).astype(jnp.int8)
        w_ref[half:] = (pq32 >> 4).astype(jnp.int8)
        p = jax.lax.dot_general(
            x_ref[g], w_ref[...], dn, preferred_element_type=jnp.int32
        )
        acc_ref[...] += p.astype(jnp.float32) * s_f[g][None, :]

    @pl.when(ii == n_ii - 1)
    def _():
        out_ref[...] = acc_ref[...]


def _tiles_and_maps(in_dim: int, out: int, gsz: int, n_g: int,
                    layered: bool, layer, wide_ot: bool = False):
    """Tile sizes + (q, s) block specs shared by both int4 routes: the
    in-tile is a multiple of 8 GROUPS (scale slice offsets must be provable
    sublane multiples; single in-tile when it falls back to the whole input
    dim), and stacked weights address (layer, tile) through the prefetched
    scalar so the layer loop never materializes a per-layer copy.
    ``wide_ot``: the W4A8 route unpacks per group (no whole-tile int32
    materialization), so its OT budget is ~4x the bf16-dequant route's —
    which matters: a wider OT shrinks the grid (fewer per-step fixed
    costs) ~10x for the 19k/38k-column projections."""
    it = _pick_tile(in_dim, gsz * 8, 1024)
    ot_budget = (6 * 2**20) // it if wide_ot else (3 * 2**20) // (2 * it)
    ot = _pick_tile(out, 1, max(512, ot_budget))
    n_gt = it // gsz

    def out_map(mi, oi, ii, *refs):
        return (mi, oi)

    if layered:
        def q_map(mi, oi, ii, li):
            return (li[0], ii, oi)

        def s_map(mi, oi, ii, li):
            return (li[0], 0, oi)

        q_block = (1, it // 2, ot)
        s_block = (1, n_g, ot)
        scalars = [jnp.reshape(layer, (1,)).astype(jnp.int32)]
    else:
        def q_map(mi, oi, ii, *refs):
            return (ii, oi)

        def s_map(mi, oi, ii, *refs):
            return (0, oi)

        q_block = (it // 2, ot)
        s_block = (n_g, ot)
        scalars = []
    return it, ot, n_gt, out_map, q_map, s_map, q_block, s_block, scalars


def _w4a8_matmul(x, q, s, zs, layer, out_dtype, interpret: bool):
    """The W4A8 route of ``int4_matmul`` (decode-sized batches).  The
    wrapper quantizes activations to per-row int8, lays them out
    group-major ([n_g, M, gsz] — static leading-axis indexing; in-kernel
    lane slicing at sub-128 offsets is not Mosaic-legal), and folds the
    zero-point term into one small XLA dot:

        y[m,o] = sxn[m] * (Sum_g s[g,o]*P[g,m,o] - Sum_g R[m,g]*zs[g,o])

    with P the kernel's int32 group partials, R the per-group sums of the
    quantized activations, sxn = rowmax|x|/127."""
    layered = q.ndim == 3
    if layered:
        assert layer is not None, "stacked int4 weights need the layer index"
    lead = x.shape[:-1]
    in_dim = x.shape[-1]
    out = q.shape[-1]
    n_g = s.shape[-2]
    gsz = in_dim // n_g
    half = gsz // 2
    out_dtype = out_dtype or x.dtype

    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, in_dim)
    # per-row symmetric int8 activation quant (f32 math: bf16 rounding
    # would double-quantize)
    xf = x2.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)  # [m, 1]
    sxn = amax / 127.0
    xq = jnp.where(
        amax > 0, jnp.round(xf * (127.0 / jnp.maximum(amax, 1e-30))), 0.0
    ).astype(jnp.int8)

    # zero-point term in XLA: R[m, g] = sum of xq over the group
    r = xq.reshape(m, n_g, gsz).sum(axis=-1, dtype=jnp.int32)
    zsl = zs
    if layered:
        zsl = jax.lax.dynamic_index_in_dim(zs, layer, 0, keepdims=False)
    zs_term = jax.lax.dot_general(
        r.astype(jnp.float32), zsl.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
    )  # [m, out]

    # group-major activation layout for the kernel: [n_g, m, gsz] — group
    # g's rows in natural order, matching the stacked [lo; hi] weight
    # operand the kernel assembles per group
    xg = jnp.transpose(xq.reshape(m, n_g, gsz), (1, 0, 2))
    m_padded = -(-m // 8) * 8
    mt = m_padded
    if m_padded != m:
        xg = jnp.pad(xg, ((0, 0), (0, m_padded - m), (0, 0)))

    it, ot, n_gt, out_map, q_map, s_map, q_block, s_block, scalars = \
        _tiles_and_maps(in_dim, out, gsz, n_g, layered, layer, wide_ot=True)
    grid = (m_padded // mt, out // ot, in_dim // it)

    def x_map(mi, oi, ii, *refs):
        return (ii, mi, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_gt, mt, gsz), x_map),
            pl.BlockSpec(q_block, q_map),
            pl.BlockSpec(s_block, s_map),
        ],
        out_specs=pl.BlockSpec((mt, ot), out_map),
        scratch_shapes=[
            pltpu.VMEM((mt, ot), jnp.float32),
            pltpu.VMEM((gsz, ot), jnp.int8),  # per-group stacked [lo; hi]
        ],
    )
    kernel = functools.partial(
        _w4a8_kernel, half=half, n_gt=n_gt, layered=layered,
        sliced=in_dim // it > 1,
    )
    acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_padded, out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, xg, q, s)
    y = sxn * (acc[:m] - zs_term)
    return y.astype(out_dtype).reshape(*lead, out)


def _w4a8_enabled() -> bool:
    import os

    return os.environ.get("INT4_W4A8", "1") != "0"


def int4_matmul(
    x: jnp.ndarray,  # [..., IN]
    q: jnp.ndarray,  # [IN/2, OUT] or [L, IN/2, OUT] uint8 (in-group packed)
    s: jnp.ndarray,  # [(L,) n_g, OUT] bf16 group scales
    zs: jnp.ndarray,  # [(L,) n_g, OUT] bf16 (zero * scale)
    layer: jnp.ndarray | None = None,  # scalar int32, REQUIRED when stacked
    out_dtype=None,  # default x.dtype; jnp.float32 for logits
    interpret: bool = False,
    w4a8: bool | None = None,  # None: W4A8 for decode-sized batches unless
    # INT4_W4A8=0 — the MXU-int8 route is what makes 4-bit FASTER than
    # int8 instead of VPU-dequant-bound (accuracy contract: + per-row
    # int8 activation quant, ~1e-2 relative)
) -> jnp.ndarray:
    """``x @ dequant(q, s, zs)`` with the dequant in VMEM.  Returns
    [..., OUT] in ``out_dtype``."""
    m = 1
    for d in x.shape[:-1]:
        m *= d
    if w4a8 is None:
        # decode-sized rows only: prefill stays on exact bf16-dequant (it
        # is MXU-compute-bound there, and the f32 [m, out] partial would
        # be large), so prompt processing keeps the stricter contract
        w4a8 = m <= 256 and _w4a8_enabled()
    if w4a8 and not interpret:
        # the kernel's stacked [lo; hi] scratch stores slice the int8
        # sublane axis at offset gsz/2, which Mosaic only legalizes at
        # 32-row multiples — serving group sizes (64 default, AWQ 128)
        # qualify; anything smaller routes to the exact bf16-dequant
        # kernel instead of failing to compile (interpret mode has no
        # such constraint, so CPU tests still exercise the W4A8 math at
        # tiny group sizes)
        n_g_chk = s.shape[-2]
        if (x.shape[-1] // n_g_chk) // 2 % 32:
            w4a8 = False
    if w4a8:
        return _w4a8_matmul(x, q, s, zs, layer, out_dtype, interpret)
    layered = q.ndim == 3
    if layered:
        assert layer is not None, "stacked int4 weights need the layer index"
    lead = x.shape[:-1]
    in_dim = x.shape[-1]
    out = q.shape[-1]
    n_g = s.shape[-2]
    gsz = in_dim // n_g
    half = gsz // 2
    out_dtype = out_dtype or x.dtype

    m = 1
    for d in lead:
        m *= d
    # pre-split x into the two in-group nibble plane halves, group-major
    # ([m, n_g*half] each): tile ii's columns are then exactly groups
    # [ii*n_gt, (ii+1)*n_gt)'s half-rows for both planes (the in-kernel
    # lane slicing this replaces is an unsupported Mosaic shape cast)
    xg = x.reshape(m, n_g, gsz)
    xa = xg[:, :, :half].reshape(m, n_g * half)
    xb = xg[:, :, half:].reshape(m, n_g * half)

    # row tiling: whole batch in one tile up to 256 rows (decode), 256-row
    # tiles beyond (prefill); padded rows compute garbage that is sliced off
    if m <= 256:
        m_padded = -(-m // 8) * 8
        mt = m_padded
    else:
        m_padded = -(-m // 256) * 256
        mt = 256
    if m_padded != m:
        xa = jnp.pad(xa, ((0, m_padded - m), (0, 0)))
        xb = jnp.pad(xb, ((0, m_padded - m), (0, 0)))

    it, ot, n_gt, out_map, q_map, s_map, q_block, s_block, scalars = \
        _tiles_and_maps(in_dim, out, gsz, n_g, layered, layer)
    grid = (m_padded // mt, out // ot, in_dim // it)

    def x_map(mi, oi, ii, *refs):
        return (mi, ii)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[
            pl.BlockSpec((mt, it // 2), x_map),
            pl.BlockSpec((mt, it // 2), x_map),
            pl.BlockSpec(q_block, q_map),
            pl.BlockSpec(s_block, s_map),
            pl.BlockSpec(s_block, s_map),
        ],
        out_specs=pl.BlockSpec((mt, ot), out_map),
        scratch_shapes=[pltpu.VMEM((mt, ot), jnp.float32)],
    )
    kernel = functools.partial(
        _int4_kernel, half=half, n_gt=n_gt, layered=layered,
        sliced=in_dim // it > 1,
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_padded, out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, xa, xb, q, s, zs)
    return y[:m].reshape(*lead, out)
