"""Multi-step decode burst: N decode iterations fused into ONE device
program (lax.scan over [forward -> sample -> staged-KV commit]).

Why bursts at all: each host->device dispatch and each device->host
token sync has a fixed cost while a small model's decode step computes in
a few ms.  Bursting N steps amortises dispatch, transfers, and the token
sync across N tokens; this is vLLM's multi-step scheduling
(``--num-scheduler-steps``) rebuilt as a single XLA program.  The default
burst length was sized for a slow host link; not re-measured on an
attached chip.

Why the staged buffer: scattering each step's K/V straight into the page
pools would drag the full pools through the scan carry — XLA then moves the
whole pool (hundreds of MB) every iteration, which measured ~3 ms/step of
pure copy at P=1024.  Instead the pools stay **loop-invariant** inside the
burst: new K/V go to a tiny [L, B, n_kv, N, hd] staging buffer (~MBs),
attention per step covers (frozen pool prefix) + (staged tail so far), and
the staged tokens are scattered into the pools ONCE at burst end.

That one commit (kv_cache.commit_paged, told that a row's ``n_steps`` slots
are one run) writes each row's steps as the one or two 16-slot windows they
fall in, over all layers and heads at once: 64 update-slices of 448 KB a
pool, in place in the layout the pools arrive in, 0.5 ms a burst at
Qwen2-7B widths where a scatter of one [hd] row per (layer, head, slot)
index took 3.9-4.2 ms for its 28,672 indices, dead row slots included
(PERF.md, Findings, PR 38; quantized pools keep that scatter).  As ONE
scatter with a window over all layers and heads per slot, the v5e compiler
transposes each whole pool into the layout that makes the window contiguous
and back out again — four 1.4 GB copies, 17 ms of a 120 ms burst, donation
notwithstanding (PERF.md, Findings, PR 25); the update-slices do not tempt
it because the attention kernel reads the same buffers as they arrive
(tests/test_tpu_compile.py holds the compiled program to "no copy of a
pool" and "no row scatter into a full-precision pool").

Attention inside the burst has two implementations (``use_pallas``):
  - the Pallas flash-decode kernel with a staged-tail operand
    (ops/pallas_paged.py::paged_attention_decode_staged) — nothing
    materialized in HBM.  The TPU path.  It walks only the pages live rows
    hold: a row's own ceil(start_len / page_size) pages, copied from the
    pools (which stay whole in HBM) several pages a wave — as many as a
    fixed VMEM budget holds for the pool's heads, page and dtype, 4 at
    Qwen2-7B widths — with the next wave in flight while this one is folded
    in; a row slot with nothing in the pool costs one small product over
    the staged tail.  ``block_tables`` and ``start_lens`` do not change
    inside a burst, so every one of its n_steps x num_layers calls walks the
    same pages.
  - gather_kv + dense attention over the materialized copy — the CPU test
    path and the kernel's correctness oracle.

Inside the burst everything stays on device: sampled tokens feed the next
step's embedding lookup directly and the repetition-penalty presence mask
updates in place.  The host sees only the final [B, n_steps] token block,
then applies stop/length bookkeeping (tokens past a stop are discarded —
the pools may keep a few orphan K/V writes past the stop, harmless because
pages belong to the row until release and the next occupant overwrites).

Rows self-deactivate when they hit ``row_limits`` (their allocated page
capacity), so a long burst can never scatter beyond a row's pages.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, _block, _embed_dtype, _logits
from githubrepostorag_tpu.models.quant import _split_q4, _with_layered_q4, embedding_lookup
from githubrepostorag_tpu.ops.attention import dense_attention
from githubrepostorag_tpu.ops.paged_attention import gather_kv
from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged
from githubrepostorag_tpu.ops.rope import rope_cos_sin
from githubrepostorag_tpu.ops.sampling import (
    sample_tokens_capped,
    sample_tokens_nofilter,
)
from githubrepostorag_tpu.runtime import on_tpu


def overlay_fresh(last_tokens, seq_lens, rng, first_tokens, fresh, fresh_lens, key_step):
    """The head of a burst program (both families'): rows that a prefill wave
    completed since the last burst take their token from the engine's
    first-token array and their cache length from the host, and the engine's
    dispatch counter is folded into its base key."""
    last_tokens = jnp.where(fresh, first_tokens, last_tokens)
    seq_lens = jnp.where(fresh, fresh_lens, seq_lens)
    return last_tokens, seq_lens, jax.random.fold_in(rng, key_step)


def _staged_attend_tp(mesh, interpret, quant: bool = False):
    """The Pallas staged kernel wrapped in a shard_map island for tensor
    parallelism: attention is embarrassingly parallel over kv heads, so each
    tp shard runs the kernel on its local heads (q [B,1,nq/tp,hd], pools
    [n_kv/tp,...]) with zero collectives — GSPMD handles the dense program
    around it and inserts the row-parallel psums after wo/wd.  ``quant``
    adds the int8 pools' per-page scale operands (sharded with their
    pages' kv-head axis)."""
    def call(q, kp, vp, bt, pool_lens, sk, sv, staged_len, layer, *scales):
        return paged_attention_decode_staged(
            q, kp, vp, bt, pool_lens, sk, sv, staged_len, layer, *scales,
            interpret=interpret,
        )

    in_specs = [
        P(None, None, "tp", None),        # q over heads
        P(None, "tp", None, None, None),  # [L, n_kv, P, ps, hd] pools
        P(None, "tp", None, None, None),  # over kv heads
        P(None, None),                    # block tables replicated
        P(None),                          # pool lens replicated
        P(None, "tp", None, None),        # staged k over kv heads
        P(None, "tp", None, None),        # staged v
        P(None),                          # staged_len replicated
        P(None),                          # layer index replicated
    ]
    if quant:
        in_specs += [P(None, "tp", None)] * 2  # [L, n_kv, P] page scales

    return jax.shard_map(
        call,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, None, "tp", None),
        check_vma=False,
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "n_steps", "use_pallas", "mesh", "layer_unroll",
        "filter_sampling",
    ),
    donate_argnums=(4, 5, 6),
)
def decode_burst(
    params: dict,
    cfg: Qwen2Config,
    last_tokens: jnp.ndarray,  # [B] int32 — last committed token per row
    seq_lens: jnp.ndarray,  # [B] int32 — tokens already cached per row
    k_pages: jnp.ndarray,  # [L, n_kv, P, ps, hd] donated
    v_pages: jnp.ndarray,  # donated
    presence: jnp.ndarray,  # [B, V] bool, donated
    active: jnp.ndarray,  # [B] bool
    row_limits: jnp.ndarray,  # [B] int32 — max cacheable tokens per row
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32
    repetition_penalty: jnp.ndarray,  # [B]
    n_steps: int,
    use_pallas: bool = False,
    mesh=None,  # jax.sharding.Mesh with a tp axis -> TP-sharded attention
    layer_unroll: int = 1,  # lax.scan unroll factor for the layer loop —
    # at small batch the decode step is weight-stream-bound and the scan's
    # per-iteration bookkeeping is a fixed ~tens-of-us tax x num_layers;
    # unrolling lets XLA overlap layer i+1's weight prefetch with layer
    # i's compute and drops the loop overhead
    filter_sampling: bool = True,  # False = every running row has
    # top_p >= 1 and top_k <= 0, so sampling takes the sort-free
    # Gumbel-argmax path (ops/sampling.sample_tokens_nofilter); the
    # engine decides per burst from its host-side sampling mirrors
    k_scales: jnp.ndarray | None = None,  # [L, n_kv, P] f32: int8 (kv_quant)
    v_scales: jnp.ndarray | None = None,  # pools' per-PAGE dequant scales
    *,
    first_tokens: jnp.ndarray,  # [B] int32: the engine's first-token array
    # (models/qwen2.forward_paged_wave scatters into it)
    fresh: jnp.ndarray,  # [B] bool: rows joining fresh from a prefill wave;
    # their token is first_tokens' and their length fresh_lens'
    fresh_lens: jnp.ndarray,  # [B] int32, host-known
    key_step: jnp.ndarray,  # scalar folded into ``rng`` here
):
    """Run ``n_steps`` decode iterations for every active row.

    Returns (tokens [B, n_steps] int32, valid [B, n_steps] bool, k_pages,
    v_pages, presence, seq_lens, last_tokens).  ``tokens`` is PACKED:
    positions where the row was inactive hold -1, so the host learns tokens
    and validity from a single [B, n_steps] transfer (one device->host round
    trip per burst).  ``valid`` (= tokens >= 0) stays a device output for
    in-program consumers and tests.  ``seq_lens`` and ``last_tokens`` [B] are
    what the next burst takes in their place, on the device.

    ``fresh`` overlays the rows a prefill wave just completed on the chained
    state, here and not on the host: one shape whatever the number of waves
    or of rows joining.
    """
    last_tokens, seq_lens, rng = overlay_fresh(
        last_tokens, seq_lens, rng, first_tokens, fresh, fresh_lens, key_step)
    b = last_tokens.shape[0]
    L = cfg.num_layers
    n_kv, hd = cfg.num_kv_heads, cfg.head_dim
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    rows = jnp.arange(b)
    start_lens = seq_lens  # pool validity is frozen for the whole burst
    # what the kernel walks: a row that sits the whole burst out (mid-prefill,
    # finished and still in the chained lens, at its limit) has its result
    # thrown away, so it is handed over as holding nothing
    walk_lens = jnp.where(active & (seq_lens < row_limits), start_lens, 0)
    quant = k_scales is not None
    # int4 pools (uint8, kv_cache.pack_int4): the staged kernel reads int8
    # pages natively but has no nibble path — bursts over int4 pages take
    # the gather fallback, whose gather_kv unpacks and dequantizes: no
    # program of the engine reads int4 pages in a kernel (ROADMAP D14).
    use_pallas = use_pallas and k_pages.dtype != jnp.uint8
    # staged tail stays full precision even over int8 pools — it is tiny
    # (MBs) and fresh tokens re-read every step; only the committed pages
    # carry the int8 + per-token-scale representation.  Full precision
    # means the ACTIVATION dtype (an f32 engine must not silently truncate
    # its staged K/V to bf16)
    kv_dtype = _embed_dtype(params) if quant else k_pages.dtype

    staged_shape = (L, b, n_kv, n_steps, hd)
    staged_k0 = jnp.zeros(staged_shape, dtype=kv_dtype)
    staged_v0 = jnp.zeros(staged_shape, dtype=kv_dtype)
    staged_idx = jnp.arange(n_steps)

    def one_step(carry, step_xs):
        last, lens, staged_k, staged_v, pres, act = carry
        step, step_rng = step_xs
        act = act & (lens < row_limits)

        # last may carry the -1 inactive sentinel (packed tokens chained
        # across bursts); clamp so inactive rows look up a real embedding
        h = embedding_lookup(
            params["embed"], jnp.maximum(last, 0)[:, None], dtype=_embed_dtype(params)
        )  # [B, 1, d]
        cos, sin = rope_cos_sin(lens[:, None], hd, cfg.rope_theta)

        # The FULL [L, ...] staged buffers ride the layer scan as CARRY;
        # each layer writes its [B, n_kv, 1, hd] slab at (li, :, :, step).
        # Making them scan xs/ys instead (the r02 layout) restacks the
        # whole ~2x50 MB at every step — slicing each layer in and
        # collecting each layer out — pure HBM traffic the carry+indexed
        # write avoids.
        def stage_at(sk_all, sv_all, li, k_new, v_new):
            """k_new/v_new: [B, 1, n_kv, hd] -> write at [li, :, :, step]."""
            with jax.named_scope("kv_write"):
                k_t = k_new.swapaxes(1, 2).astype(kv_dtype)[None, :, :, :]
                v_t = v_new.swapaxes(1, 2).astype(kv_dtype)[None, :, :, :]
                sk_all = jax.lax.dynamic_update_slice(sk_all, k_t, (li, 0, 0, step, 0))
                sv_all = jax.lax.dynamic_update_slice(sv_all, v_t, (li, 0, 0, step, 0))
            return sk_all, sv_all

        if use_pallas:
            interpret = not on_tpu()
            if mesh is not None and mesh.shape.get("tp", 1) > 1:
                kernel = _staged_attend_tp(mesh, interpret, quant=quant)
            else:
                kernel = partial(paged_attention_decode_staged, interpret=interpret)

            # full rank-5 pools go straight into the kernel with the layer
            # index as a prefetched scalar — pools are NOT layer-scan xs,
            # so no [n_kv, P, ps, hd] slice is ever materialized (profiled
            # at ~0.5 ms/step of copy traffic in the sliced form)
            def make_attend(kp, vp, li, sk_all, sv_all):
                def attend(q, k_new, v_new):
                    sk2, sv2 = stage_at(sk_all, sv_all, li, k_new, v_new)
                    # NOT under named_scope("paged_attention") like the gather
                    # path below: XLA names a custom call after its innermost
                    # scope, so the kernel's instruction `closed_call.N`
                    # would become `paged_attention.N` in the device trace,
                    # and BENCHMARK.json's paged_attn_hbm_frac finds it by
                    # the old name (PERF.md, Findings, PR 24)
                    out = kernel(
                        q, kp, vp, block_tables, walk_lens,
                        jax.lax.dynamic_index_in_dim(sk2, li, 0, keepdims=False),
                        jax.lax.dynamic_index_in_dim(sv2, li, 0, keepdims=False),
                        jnp.reshape(step + 1, (1,)),
                        jnp.reshape(li, (1,)),
                        *((k_scales, v_scales) if quant else ()),
                    )
                    return out, (sk2, sv2)

                return attend
        else:
            # staged positions are valid up to and including this step (the
            # new token attends itself)
            staged_valid = (staged_idx <= step)[None, :]  # [1, n_steps]

            def make_attend(kp, vp, li, sk_all, sv_all, ks=None, vs=None):
                pool_k, pool_v = gather_kv(
                    kp, vp, block_tables, ks, vs, dtype=kv_dtype
                )  # [B, mp*ps, n_kv, hd]
                pool_valid = (
                    jnp.arange(pool_k.shape[1])[None, :] < start_lens[:, None]
                )

                def attend(q, k_new, v_new):
                    sk2, sv2 = stage_at(sk_all, sv_all, li, k_new, v_new)
                    with jax.named_scope("paged_attention"):
                        sk = jax.lax.dynamic_index_in_dim(sk2, li, 0, keepdims=False)
                        sv = jax.lax.dynamic_index_in_dim(sv2, li, 0, keepdims=False)
                        k_all = jnp.concatenate([pool_k, sk.swapaxes(1, 2)], axis=1)
                        v_all = jnp.concatenate([pool_v, sv.swapaxes(1, 2)], axis=1)
                        valid = jnp.concatenate(
                            [pool_valid, jnp.broadcast_to(staged_valid, (b, n_steps))],
                            axis=1,
                        )
                        out = dense_attention(q, k_all, v_all, causal=False,
                                              kv_valid=valid)
                    return out, (sk2, sv2)

                return attend

        # int4 projection stacks stay OUT of the scan xs: a Layered4 view
        # (full arrays + layer index) feeds the Pallas int4 GEMM directly,
        # so no per-layer weight slice materializes (models/quant.py).
        # Under TP the weights are GSPMD-sharded and the kernel (an opaque
        # custom call) would force an all-gather — the XLA-route view
        # partitions instead (quant.Layered4XLA)
        int4_kernel = mesh is None or mesh.shape.get("tp", 1) == 1
        scan_layers, q4_stacks = _split_q4(params["layers"])
        if use_pallas:
            # pools captured whole (rank-5 into the kernel), NOT sliced xs
            layer_xs = (scan_layers,)
        elif quant:
            layer_xs = (scan_layers, k_pages, v_pages, k_scales, v_scales)
        else:
            layer_xs = (scan_layers, k_pages, v_pages)

        def layer_body(lcarry, xs):
            h, sk_all, sv_all, li = lcarry
            # pallas: loop-invariant full pools; fallback: per-layer slices
            if len(xs) == 1:
                attend = make_attend(k_pages, v_pages, li, sk_all, sv_all)
                p = xs[0]
            elif len(xs) == 5:
                p, kp, vp, ks, vs = xs
                attend = make_attend(kp, vp, li, sk_all, sv_all, ks, vs)
            else:
                p, kp, vp = xs
                attend = make_attend(kp, vp, li, sk_all, sv_all)
            p = _with_layered_q4(p, q4_stacks, li, kernel=int4_kernel)
            h, (sk_all, sv_all) = _block(cfg, h, p, cos, sin, attend)
            return (h, sk_all, sv_all, li + 1), None

        (h, staged_k, staged_v, _), _ = jax.lax.scan(
            layer_body, (h, staged_k, staged_v, 0), layer_xs,
            unroll=min(max(1, layer_unroll), L),
        )
        with jax.named_scope("sample"):
            logits = _logits(params, h, int4_kernel=int4_kernel)
            if filter_sampling:
                toks = sample_tokens_capped(
                    logits[:, 0], step_rng, temperature, top_p, top_k,
                    repetition_penalty, pres,
                )
            else:
                # no running row filters: Gumbel-argmax over the full vocab,
                # skipping the candidate sort (ops/sampling.py)
                toks = sample_tokens_nofilter(
                    logits[:, 0], step_rng, temperature, repetition_penalty, pres,
                )
        toks = jnp.where(act, toks, last)
        pres = pres.at[rows, toks].max(act)
        lens = lens + act.astype(jnp.int32)
        return (toks, lens, staged_k, staged_v, pres, act), (toks, act)

    keys = jax.random.split(rng, n_steps)
    carry0 = (last_tokens, seq_lens, staged_k0, staged_v0, presence, active)
    (last, out_lens, staged_k, staged_v, presence, _), (toks, valid) = jax.lax.scan(
        one_step, carry0, (jnp.arange(n_steps), keys)
    )
    toks, valid = toks.T, valid.T  # [B, n_steps]
    packed = jnp.where(valid, toks, -1)

    # one commit writes the whole burst's staged K/V into the pools: a row's
    # n_steps slots are consecutive positions (a run, for commit_paged)
    total_slots = num_pages * page_size
    pos = start_lens[:, None] + staged_idx[None, :]  # [B, n_steps]
    page_idx = jnp.clip(pos // page_size, 0, block_tables.shape[1] - 1)
    slots = jnp.take_along_axis(block_tables, page_idx, axis=1) * page_size + pos % page_size
    slots = jnp.where(valid, slots, total_slots)  # sentinel -> mode="drop"
    flat_slots = slots.reshape(-1)  # [B*n_steps]

    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    def commit(pools, staged, scales=None):
        # [L, B, n_kv, n, hd] -> [L, n_kv, B*n, hd] matching flat_slots
        # order; commit_paged is THE shared pool-commit rule (per-page
        # first-write scales when quantized)
        vals = staged.swapaxes(1, 2).reshape(L, n_kv, b * n_steps, hd)
        return commit_paged(pools, vals, flat_slots, scales, page_size, run=n_steps)

    with jax.named_scope("kv_write"):
        k_pages, k_scales = commit(k_pages, staged_k, k_scales)
        v_pages, v_scales = commit(v_pages, staged_v, v_scales)
    if quant:
        return packed, valid, k_pages, v_pages, presence, out_lens, last, k_scales, v_scales
    return packed, valid, k_pages, v_pages, presence, out_lens, last
