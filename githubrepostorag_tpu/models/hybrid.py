"""The step skeleton of a hybrid of recurrent-state layers and paged
full-attention layers: what models/qwen3_next.py, models/olmo_hybrid.py
(Gated DeltaNet), models/nemotron_h.py and models/falcon_h1.py (Mamba-2)
share, and the two recurrent mixers themselves.

A model is a LAYER PATTERN, ``cfg.layer_segments``: a tuple of ``(kinds,
repeats)``, ``kinds`` a string of one letter a layer: ``STATE`` ("R": a layer
that keeps a slot of recurrent state a sequence), ``ATTN`` ("A": a layer that
pages keys and values), ``PLAIN`` ("F": a layer that keeps nothing, a
feed-forward or expert block of its own), ``BOTH`` ("B": a layer whose two
mixers, one of each cache, run side by side on ONE ``mixer_input`` and are
summed: it counts among the state layers and among the page layers, and its
slot of state and its pages belong to the same layer).  Three Gated DeltaNet layers then
one attention layer, twice, is ``(("RRRA", 2),)``; ``segments()`` below cuts
any string into such runs.  The prefill wave scans a segment's repeats (one
traced copy of ``kinds`` however often it repeats: the compile is the
pattern's, not the depth's), the burst unrolls them.  Two kinds of
per-sequence memory ride them as carries, never sliced:

* K/V page pools ``[attention layers, n_kv, P, page_size, head_dim]``
  (``cfg.kv_layers``), committed by ``kv_cache.commit_paged`` (the wave at a
  traced layer index, the burst all layers at once after its scan; both say
  their slots are RUNS, a row's columns and a row's steps being consecutive
  positions, so a full-precision pool is written a few aligned windows of
  slots a run, in place, and not a row an index), read by the paged kernels
  qwen2 uses;
* a STATE pool for the recurrent layers (``cfg.state_shapes()``,
  ``kv_cache.make_state_pools``): ``s`` ``[state layers, slots, ...]`` float32
  (the recurrence's matrix: ``[Hv, dk, dv]`` a Gated DeltaNet layer, ``[H, P,
  N]`` a Mamba-2 one) and ``conv`` ``[state layers, slots, (taps - 1) *
  channels]`` bfloat16 (the convolution's history).  A slot is one
  sequence's state in every layer.  The engine's rows own the first
  ``max_num_seqs`` slots (row r = slot r), snapshot slots follow, and the
  last slot takes the writes of rows that have nothing to write.

``wave`` and ``burst`` keep qwen2's contracts (``forward_paged`` /
``forward_paged_wave`` / ``decode_burst``) and add the state beside the
pools: a wave is told, a row, which slot its state comes from (``-1``: a
fresh sequence, zeros; its own slot: the next chunk of a prompt; a snapshot
slot: a prefix hit resumes there), which slot takes the state after the
chunk, and which slot takes a SNAPSHOT of the state after ``snap_col`` of
the chunk's tokens (a page boundary; the chunked rules catch it between two
of their blocks).  All of it is device copies inside the program.  The burst
steps rows 0 .. B-1 in place; a row that sits a step out keeps its state and
history bit for bit.

What a model brings (``m``, an object of functions; the skeleton never asks
which model it runs):

* ``weights(params) -> w``: whatever its own functions index, opaque here;
  ``state_weights(w, n)`` / ``attn_weights(w, n)``: the mixer weights of the
  n-th layer of that kind;
* ``embed(cfg, params, ids)`` (the residual stream, float32), ``position_cols(cfg,
  positions)`` (what runs along the chunk beside it: rotary tables, or
  nothing), ``final(cfg, params, h)`` and ``head(cfg, params, h)``;
* the block's wiring: ``mixer_input(cfg, w, li, h)`` (a pre-norm, or a cast)
  and ``after_mixer(cfg, w, li, h, y, live) -> (h, counts or None)`` (the
  residual add and, where a block has one, the feed-forward or expert layer
  and its add; ``y`` of a ``BOTH`` layer is the pair ``(the state mixer's
  output, the attention mixer's)``, summed there); for a ``PLAIN`` layer, ``plain_layer(cfg, w, n, li, h, live) ->
  (h, counts or None)``, the whole block;
* the recurrent mixer, both forms: ``state_chunk(cfg, p, x, s0, taps0, live,
  new_lens, snap_col, page_size) -> (y, s, s_snap, taps, taps_snap)`` and
  ``state_step(cfg, p, x, s_old, taps_old) -> (y, s, taps)``, ``s`` at the
  width the pool stores (``gdn_chunk`` / ``gdn_step`` below are the Gated
  DeltaNet's, over the model's ``gdn_inputs`` / ``gdn_out``); ``cfg.state_cols``
  is the width the chunked rule works at (``lane_padded``).  A layer type
  whose one-token rule has a kernel that works on the pool itself also brings
  ``state_step_in_pool(cfg, p, x, s_pool, n, taps_old, act, interpret) -> (y,
  s_pool, taps)``: the burst then hands it the whole pool, the layer's index
  and the step's live mask, and neither slices the pool nor selects the old
  rows itself (``use_pallas``; ``state_step`` stays the array form;
  ``ssm_step_in_pool`` and ``gdn_step_in_pool`` below are the two there are);
* ``attn_project(cfg, p, x, *position_cols) -> (q, k, v, more)`` /
  ``attn_out(p, attn, *more)`` around the shared pages and kernels;
* ``attn_window``: columns of a prefill chunk one call of the K/V attention
  kernel takes (``KVPages`` reads it; a model with pages of its own needs none);
* optionally ``pages``: the page layer's two halves, what rows a layer commits
  and the attention over the pools and over the burst's staged tail.  Absent,
  ``KVPages`` below: keys and values in two pools, what ``attn_project`` hands
  over as ``(q, k, v, more)``.  A model whose page layers keep something else
  (models/bailing_hybrid.py: ONE latent row a token, no V pool) brings an
  object of the same four functions and says how many arrays a layer commits
  (``rows``); ``attn_project`` then returns ``(q, *those, more)``;
* optionally ``counts``: how many numbers its layers' counts are (2: experts
  hit and pairs routed to held experts; 3 adds the fullest held expert's pairs).
"""

from __future__ import annotations

import math
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.ops.gated_delta import (
    BLOCK,
    causal_conv,
    causal_conv_step,
    gated_delta_chunked,
    gated_delta_step,
    l2norm,
    mask_padding,
)
from githubrepostorag_tpu.ops import ssd
from githubrepostorag_tpu.ops.latent_attention import einsum_f32
from githubrepostorag_tpu.ops.norms import rms_norm_gate_first, rms_norm_gated
from githubrepostorag_tpu.ops.pallas_state import gated_delta_step_in_place, ssd_step_in_place
from githubrepostorag_tpu.ops.prefill_width import at_wave_width
from githubrepostorag_tpu.ops.sampling import sample_tokens_capped, sample_tokens_nofilter
from githubrepostorag_tpu.runtime import _pinned_to_cpu, on_tpu


STATE, ATTN, PLAIN, BOTH = "R", "A", "F", "B"  # the letters of ``cfg.layer_segments``
# the kinds a layer counts among: a ``BOTH`` layer is the next state layer AND the next page layer
_COUNTS_AS = {STATE: (STATE,), ATTN: (ATTN,), PLAIN: (PLAIN,), BOTH: (STATE, ATTN)}


def segments(kinds: str) -> tuple:
    """``kinds`` (one letter a layer) as runs of ``(body, repeats)``: at each
    position the repeating body that covers the most layers, else the layers up
    to the next one that repeats, once.  ``MEMEM*EMEMEM*EMEME`` is ``MEMEM*E``
    twice, then ``ME`` twice."""
    out, i, n = [], 0, len(kinds)

    def best(i):
        found = (0, 0, 0)
        for p in range(1, (n - i) // 2 + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r > 1 and p * r > found[0]:
                found = (p * r, p, r)
        return found

    while i < n:
        covered, p, r = best(i)
        if covered:
            out.append((kinds[i:i + p], r))
            i += covered
            continue
        j = i + 1
        while j < n and not best(j)[0]:
            j += 1
        out.append((kinds[i:j], 1))
        i = j
    return tuple(out)


def _walk(layer_segments):
    """(kinds, repeats, layers of each kind and of any before the segment)."""
    before = {STATE: 0, ATTN: 0, PLAIN: 0, "all": 0}
    for kinds, reps in layer_segments:
        yield kinds, reps, dict(before)
        for k in (STATE, ATTN, PLAIN):
            before[k] += _of_kind(kinds, k) * reps
        before["all"] += len(kinds) * reps


def _of_kind(kinds: str, k: str) -> int:
    """Layers of ``kinds`` that count among the layers of kind ``k``."""
    return sum(k in _COUNTS_AS[c] for c in kinds)


def _index(base: int, rep, per: int, off: int):
    """Layer ``off`` of its kind in repeat ``rep`` (traced in the wave) of a
    segment that holds ``per`` of them a repeat and follows ``base``."""
    i = rep if per == 1 else rep * per
    return i + (base + off) if base + off else i


def _layers(kinds: str, before: dict, rep):
    """(kind, index among the layers of its kind, index among all layers) of
    each layer of repeat ``rep`` of a segment ``_walk`` gave; for a ``BOTH``
    layer the pair (index among the state layers, among the page layers)."""
    seen = dict.fromkeys(before, 0)
    for j, kind in enumerate(kinds):
        n = tuple(_index(before[k], rep, _of_kind(kinds, k), seen[k]) for k in _COUNTS_AS[kind])
        yield kind, (n if kind == BOTH else n[0]), _index(before["all"], rep, len(kinds), j)
        for k in _COUNTS_AS[kind]:
            seen[k] += 1


def tpu_compiler_options(options: dict) -> dict | None:
    """``options`` for ``jax.jit(compiler_options=...)`` of a step program: the
    TPU compiler's own keys, which the CPU compiler refuses ("No such compile
    option"), so a process pinned to the CPU backend (tests, rehearsals) gets
    None.  Read when the module is imported; reading the pin starts no backend.
    A test that compiles for a described chip passes the options to
    ``.compile()`` itself."""
    return None if _pinned_to_cpu() else dict(options)


def at(tree, index):
    """Layer ``index`` of every stacked leaf of ``tree``: static in the burst
    (a view), traced in the wave (a slice the product reads through)."""
    if isinstance(index, int):
        return jax.tree.map(lambda x: x[index], tree)
    return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, index, keepdims=False), tree)


def state_read(pool, layer, slots):
    """Rows' slots of one layer of a state pool, read where they lie; zeros
    where the slot is negative (a fresh sequence)."""
    rows = [jax.lax.dynamic_slice(pool, (layer, jnp.maximum(slots[r], 0)) + (0,) * (pool.ndim - 2),
                                  (1, 1, *pool.shape[2:]))[0, 0]
            for r in range(slots.shape[0])]
    keep = (slots >= 0).reshape(-1, *(1,) * (pool.ndim - 2))
    return jnp.where(keep, jnp.stack(rows), 0)


def state_write(pool, layer, slots, vals):
    """Rows' values into their slots of one layer, in place, a row at a time."""
    for r in range(slots.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, vals[r][None, None].astype(pool.dtype),
            (layer, slots[r]) + (0,) * (pool.ndim - 2))
    return pool


def lane_padded(width: int) -> int:
    """``width`` up to a multiple of the 128-lane tile: what a state pool's
    last axis is stored at.  A float32 pool ``[.., 96, 192]`` is given, on a
    v5e, a layout with the SLOTS on the lanes (192 would pad to 256, 97 slots
    to 128: the compiler takes the smaller), and every program then either
    copies the 1.7 GB pool into row-major order and back or reads all 97
    slots to step 32; stored ``[.., 96, 256]`` it stays row-major.  The
    padding lanes are zero in every slot, always.  The WAVES cut the padding
    off what they read and put zeros back (``_cut`` / ``_fill``); the BURST
    steps its rows at the stored width (``gated_delta_step`` with a state
    wider than ``v``), where a padding lane comes out as ``0 * decay + k * 0``:
    zero again, with nobody writing it."""
    return -(-width // 128) * 128


def _cut(x, width: int):
    return x if x.shape[-1] == width else x[..., :width]


def _fill(x, width: int):
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def swiglu(x, wgu, wd):
    """SwiGLU (gate | up side by side in ``wgu``) with the down-projection
    accumulated and returned in float32."""
    g, u = jnp.split(x @ wgu, 2, axis=-1)
    return einsum_f32("...f,fd->...d", jax.nn.silu(g) * u, wd)


# ------------------------------------------------- the Gated DeltaNet mixer --

def gdn_inputs(cfg, p, x, act, beta_max: float = 1.0):
    """x [B, S, d] -> (the convolution's input [B, S, C]: q | k | v of the
    linear heads, in ``act`` as the history keeps it; z [B, S, Hv, dv]; beta,
    g [B, S, Hv]).  The projections' columns lie as they are read: ``q | k |
    v | z`` and ``b | a``.  ``beta = beta_max * sigmoid(b)``: 2 where the
    published model allows ``I - beta k k^T`` a negative eigenvalue.

    The burst (one token a row) runs ``w_qkvz`` as one product and cuts its
    columns after it; a chunk runs two products, each on its own columns of
    the leaf.  Either program, compiled for a v5e, then reads the stack where
    it lies, and neither form serves the other: cut before the product, the
    burst transposes the whole stack once a burst; cut after it, a wave of
    eight rows never came back from the chip (PERF.md, Findings, PR 35)."""
    b, s, _ = x.shape
    hv, dv, c = cfg.linear_num_value_heads, cfg.linear_value_head_dim, cfg.conv_channels
    with jax.named_scope("gdn_proj"):
        if s == 1:
            qkvz = einsum_f32("bsd,de->bse", x, p["w_qkvz"])
            mixed, z = qkvz[..., :c], qkvz[..., c:]
        else:
            mixed, z = (einsum_f32("bsd,de->bse", x, w)
                        for w in (p["w_qkvz"][:, :c], p["w_qkvz"][:, c:]))
        ba = einsum_f32("bsd,de->bse", x, p["w_ba"])
        beta = jax.nn.sigmoid(ba[..., :hv])
        if beta_max != 1.0:
            beta = beta_max * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    return mixed.astype(act), z.reshape(b, s, hv, dv), beta, g


def gdn_heads(cfg, y):
    """The convolution's output [B, S, C] float32 -> (q, k [B, S, Hv, dk]
    L2-normalised, q scaled; v [B, S, Hv, dv]); a key head serves r value heads."""
    b, s, _ = y.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    q = l2norm(y[..., :hk * dk].reshape(b, s, hk, dk)) * dk ** -0.5
    k = l2norm(y[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk))
    v = y[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    if hv == hk:
        return q, k, v
    return jnp.repeat(q, hv // hk, axis=2), jnp.repeat(k, hv // hk, axis=2), v


def gdn_out(cfg, p, o, z, act):
    with jax.named_scope("gdn_gate_norm"):
        y = rms_norm_gated(o, z, p["o_norm"], cfg.rms_norm_eps)
    return einsum_f32("bse,ed->bsd", y.reshape(*y.shape[:2], -1).astype(act), p["w_out"])


def gdn_chunk(m, cfg, p, x, s0, taps0, live, new_lens, snap_col, page_size):
    """A Gated DeltaNet mixer over a chunk, ``m.state_chunk`` of the models
    that have one: ``m.gdn_inputs``, the convolution with its history, the
    chunked rule (a snapshot's column lies between two of its blocks),
    ``m.gdn_out``."""
    mixed, z, beta, gate = m.gdn_inputs(cfg, p, x)
    with jax.named_scope("gdn_conv"):
        y, taps, taps_snap = causal_conv(
            mixed, taps0.reshape(x.shape[0], -1, mixed.shape[-1]), p["conv_w"], new_lens, snap_col)
        taps, taps_snap = (t.reshape(t.shape[0], -1) for t in (taps, taps_snap))
    q, k, v = gdn_heads(cfg, y)
    k, gate, beta = mask_padding(live, k, gate, beta)
    with jax.named_scope("gdn_chunked"):
        o, s_new, s_snap = gated_delta_chunked(
            s0, q, k, v, gate, beta, snap_col, block=math.gcd(BLOCK, page_size),
            beta_max=getattr(cfg, "beta_max", 1.0))
    return m.gdn_out(cfg, p, o, z), s_new, s_snap, taps, taps_snap


def _gdn_token(m, cfg, p, x, taps_old):
    """One token a row up to the rule: (q, k [B, Hv, dk]; v [B, Hv, dv]; g, beta
    [B, Hv]; the gate z; the history after the token)."""
    b = x.shape[0]
    mixed, z, beta, gate = m.gdn_inputs(cfg, p, x)
    with jax.named_scope("gdn_conv"):
        y, taps = causal_conv_step(mixed[:, 0], taps_old.reshape(b, -1, mixed.shape[-1]),
                                   p["conv_w"])
        taps = taps.reshape(b, -1)
    q, k, v = gdn_heads(cfg, y[:, None])
    return q[:, 0], k[:, 0], v[:, 0], gate[:, 0], beta[:, 0], z, taps


def gdn_step(m, cfg, p, x, s_old, taps_old):
    """A Gated DeltaNet mixer over one token a row, ``m.state_step``: the
    state at the width the pool stores it (``gated_delta_step``)."""
    q, k, v, gate, beta, z, taps = _gdn_token(m, cfg, p, x, taps_old)
    with jax.named_scope("gdn_recurrent"):
        o, s_new = gated_delta_step(s_old.astype(jnp.float32), q, k, v, gate, beta)
    return m.gdn_out(cfg, p, o[:, None], z), s_new, taps


@partial(jax.jit, static_argnames=("interpret",))
def _gdn_rule_in_pool(s_pool, n, act, q, k, v, g, beta, interpret):
    """ops/pallas_state.gated_delta_step_in_place under the rule's scope, as
    ``_rule_in_pool`` is Mamba-2's: named for the scope and for its first
    result (``o``) in a device trace, and its body (every head unrolled) traced
    once a burst and not once a layer."""
    with jax.named_scope("gdn_recurrent"):
        return gated_delta_step_in_place(s_pool, n, act, q, k, v, g, beta, interpret=interpret)


def gdn_step_in_pool(m, cfg, p, x, s_pool, n, taps_old, act, interpret):
    """The same mixer with the rule as a kernel on the state pool itself
    (``m.state_step_in_pool``): layer ``n``'s rows that are ``act`` are read
    once and written once where they lie, the others are not touched."""
    q, k, v, gate, beta, z, taps = _gdn_token(m, cfg, p, x, taps_old)
    o, s_pool = _gdn_rule_in_pool(s_pool, jnp.int32(n), act, q, k, v, gate, beta,
                                  interpret=interpret)
    return m.gdn_out(cfg, p, o[:, None], z), s_pool, taps


# ------------------------------------------------------- the Mamba-2 mixer --
# What models/nemotron_h.py and models/falcon_h1.py share (ops/ssd.py is the
# rule, ops/pallas_state.py its kernel on the pool).  ``cfg`` states the
# shapes: ``mamba_num_heads`` heads of ``mamba_head_dim``, ``ssm_state_size``,
# ``n_groups`` groups that share ``B`` and ``C``, ``d_inner``, ``rms_norm_eps``.
# A layer's weights ``p``: ``w_z`` | ``w_xbc`` | ``w_dt`` (in_proj as the three
# runs of columns that are read apart, each its own product in either program),
# ``conv_w``, ``conv_b``, ``A_log``, ``dt_bias``, ``D``, ``o_norm``, ``w_out``.

def ssm_inputs(cfg, p, x, act, scales=None):
    """x [B, S, d] normed -> (the convolution's input [B, S, C]: x | B | C, in
    ``act`` as the history keeps it; z [B, S, d_inner]; dt [B, S, H] softplus'd).
    ``scales``: what the float32 results of the three products are multiplied
    by, ``(z, (x, B, C), dt)``, where a family scales runs of in_proj's columns
    (muP multipliers); None: the products as they are."""
    with jax.named_scope("ssm_proj"):
        z, xbc, dt = (einsum_f32("bsd,de->bse", x, p[k]) for k in ("w_z", "w_xbc", "w_dt"))
        if scales is not None:
            gn = cfg.n_groups * cfg.ssm_state_size
            runs = jnp.concatenate([jnp.full((n,), v, jnp.float32)
                                    for n, v in zip((cfg.d_inner, gn, gn), scales[1])])
            z, xbc, dt = z * scales[0], xbc * runs, dt * scales[2]
        dt = jax.nn.softplus(dt + p["dt_bias"])
    return xbc.astype(act), z, dt


def ssm_heads(cfg, y):
    """The convolution's output [B, S, C] float32 -> (x [B, S, H, P]; B, C
    [B, S, G, N])."""
    b, s, _ = y.shape
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    return (y[..., :di].reshape(b, s, cfg.mamba_num_heads, cfg.mamba_head_dim),
            y[..., di:di + gn].reshape(b, s, cfg.n_groups, cfg.ssm_state_size),
            y[..., di + gn:].reshape(b, s, cfg.n_groups, cfg.ssm_state_size))


def ssm_out(cfg, p, y, z, act):
    """y [B, S, H, P] float32 and the gate -> the mixer's output [B, S, d]:
    the norm GATE FIRST and BY GROUP, then ``out_proj``."""
    with jax.named_scope("ssm_gate_norm"):
        y = rms_norm_gate_first(y.reshape(*y.shape[:2], -1), z, p["o_norm"], cfg.n_groups,
                                cfg.rms_norm_eps)
    return einsum_f32("bse,ed->bsd", y.astype(act), p["w_out"])


def ssm_chunk(m, cfg, p, x, s0, taps0, live, new_lens, snap_col, page_size):
    """A Mamba-2 mixer over a chunk, ``m.state_chunk`` of the models that have
    one: ``m.ssm_inputs``, the convolution (with its bias) and its history, the
    chunked rule, ``m.ssm_out``."""
    mixed, z, dt = m.ssm_inputs(cfg, p, x)
    with jax.named_scope("ssm_conv"):
        y, taps, taps_snap = causal_conv(
            mixed, taps0.reshape(x.shape[0], -1, mixed.shape[-1]), p["conv_w"], new_lens,
            snap_col, bias=p["conv_b"])
        taps, taps_snap = (t.reshape(t.shape[0], -1) for t in (taps, taps_snap))
    xs, b, c = ssm_heads(cfg, y)
    with jax.named_scope("ssm_chunked"):
        o, s_new, s_snap = ssd.ssd_chunked(
            s0, xs, ssd.mask_padding(live, dt), -jnp.exp(p["A_log"]), b, c, p["D"], snap_col,
            block=math.gcd(ssd.BLOCK, page_size))
    return m.ssm_out(cfg, p, o, z), s_new, s_snap, taps, taps_snap


def _ssm_token(m, cfg, p, x, taps_old):
    """One token a row up to the rule: (x [B, H, P]; dt [B, H]; B, C [B, G, N];
    the gate z; the history after the token)."""
    bsz = x.shape[0]
    mixed, z, dt = m.ssm_inputs(cfg, p, x)
    with jax.named_scope("ssm_conv"):
        y, taps = causal_conv_step(mixed[:, 0], taps_old.reshape(bsz, -1, mixed.shape[-1]),
                                   p["conv_w"], bias=p["conv_b"])
        taps = taps.reshape(bsz, -1)
    xs, b, c = ssm_heads(cfg, y[:, None])
    return xs[:, 0], dt[:, 0], b[:, 0], c[:, 0], z, taps


def ssm_step(m, cfg, p, x, s_old, taps_old):
    """A Mamba-2 mixer over one token a row (``m.state_step``), as array code:
    the CPU's path, and what the kernel below is held to."""
    xs, dt, b, c, z, taps = _ssm_token(m, cfg, p, x, taps_old)
    with jax.named_scope("ssm_recurrent"):
        o, s_new = ssd.ssd_step(s_old.astype(jnp.float32), xs, dt, -jnp.exp(p["A_log"]), b, c,
                                p["D"])
    return m.ssm_out(cfg, p, o[:, None], z), s_new, taps


@partial(jax.jit, static_argnames=("interpret",))
def _rule_in_pool(s_pool, n, act, xs, dt, a, b, c, d, interpret):
    """ops/pallas_state.ssd_step_in_place under the rule's scope: the call is
    named for it in a device trace, where the rule's roofline looks.  Jitted so
    that the burst traces the kernel's body (every head unrolled: 1.6 s at 64)
    once and not once a layer: the layer's index is an operand."""
    with jax.named_scope("ssm_recurrent"):
        return ssd_step_in_place(s_pool, n, act, xs, dt, a, b, c, d, interpret=interpret)


def ssm_step_in_pool(m, cfg, p, x, s_pool, n, taps_old, act, interpret):
    """The same mixer with the rule as a kernel on the state pool itself
    (``m.state_step_in_pool``): layer ``n``'s rows that are ``act`` are read
    once and written once where they lie, the others are not touched."""
    xs, dt, b, c, z, taps = _ssm_token(m, cfg, p, x, taps_old)
    o, s_pool = _rule_in_pool(s_pool, jnp.int32(n), act, xs, dt, -jnp.exp(p["A_log"]), b, c,
                              p["D"], interpret=interpret)
    return m.ssm_out(cfg, p, o[:, None], z), s_pool, taps


U_MAX = 2147483648.0 * (0.02 / 1.24e9)  # a draw is uniform in +-U_MAX (models/quant._devrand)


def draw_leaves(order: list, seed: int) -> dict:
    """The leaves of ``order`` ((path, shape, gain), a family's ``leaf_order``)
    made on the device from the seed, each a bfloat16 draw of std ~0.02
    (models/quant._devrand) times its gain (a power of two: exact); the salt
    advances once a leaf, in ``order``'s order."""
    from githubrepostorag_tpu.models.quant import _devrand

    salt = jnp.uint32(seed * 40503 + 12345)
    draw = jax.jit(_devrand, static_argnums=(0, 2))
    params: dict = {}
    for path, shape, gain in order:
        salt = salt * jnp.uint32(747796405) + jnp.uint32(1)
        leaf = draw(tuple(shape), salt, "bf16")
        if gain != 1.0:
            leaf = (leaf.astype(jnp.float32) * gain).astype(jnp.bfloat16)
        node = params
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    return params


def ssm_scalars(cfg, a_u, dt_u):
    """(``A_log``, ``dt_bias``) [layers, heads] float32 from two uniform draws
    in +-``U_MAX``, as the Mamba-2 families' initialiser makes them: ``A`` from
    U(1, 16); ``dt_bias`` the inverse softplus of a step drawn log-uniformly in
    [``time_step_min``, ``time_step_max``], floored at ``time_step_floor``.  A
    token's decay ``exp(-A softplus(dt + dt_bias))`` then runs from ~0.2 to
    0.999 over the heads: some remember thousands of tokens, none forgets
    within one (the Gated DeltaNets' U(0, 16) did, and got a ladder)."""
    u = lambda x: x.astype(jnp.float32) / (2.0 * U_MAX) + 0.5  # noqa: E731 - in [0, 1]
    a = 1.0 + 15.0 * u(a_u)
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
    dt = jnp.maximum(jnp.exp(lo + u(dt_u) * (hi - lo)), cfg.time_step_floor)
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


# ------------------------------------------------------------ the page layer --

class KVPages:
    """The page layer's two halves as the K/V families keep them (``m.pages``
    where a model brings none): a layer commits its keys and its values into
    two pools ``[attention layers, n_kv, P, page, head_dim]``.  ``c`` is what the
    step program knows of the call (slots, tables, lengths, the step)."""

    rows = 2  # arrays a layer commits: k, v

    @staticmethod
    def wave_attend(m, cfg, weights, pi, q, rows, kv_pools, c):
        """A layer's keys and values into their pages and the chunk's attention
        over them, outside every switch.  Returns (the pools, attn)."""
        from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref
        from githubrepostorag_tpu.serving.kv_cache import commit_paged

        (k, v), (kp, vp) = rows, kv_pools
        nkv, hd = cfg.num_kv_heads, cfg.head_dim
        with jax.named_scope("kv_write"):
            flat, run = c.slots.reshape(-1), c.slots.shape[1]  # a row's columns: consecutive positions
            kp, _ = commit_paged(kp, k.reshape(-1, nkv, hd).swapaxes(0, 1), flat, None,
                                 c.page_size, layer=pi, run=run)
            vp, _ = commit_paged(vp, v.reshape(-1, nkv, hd).swapaxes(0, 1), flat, None,
                                 c.page_size, layer=pi, run=run)
        with jax.named_scope("paged_attention"):
            if c.use_pallas:
                from githubrepostorag_tpu.ops.fused_decode import fused_paged_attention

                # the kernel keeps a window's queries, accumulator and softmax
                # state for a kv head's whole group in VMEM: 8 heads of 256 over
                # 512 columns are 16.8 MB, past what a v5e kernel may hold, so a
                # chunk goes through in windows of ``m.attn_window`` columns (the
                # keys of the whole chunk are committed: a later window attends the
                # earlier ones as cache); a window past the wave's width is skipped
                span = m.attn_window

                def window(col):
                    qw = q[:, col:col + span]
                    run = lambda: fused_paged_attention(  # noqa: E731
                        qw, kp, vp, c.block_tables, c.cached_lens + jnp.minimum(c.new_lens, col),
                        jnp.clip(c.new_lens - col, 0, span), layer=pi)
                    if col == 0 or c.width is None:
                        return run()
                    return jax.lax.cond(c.width > col, run, lambda: jnp.zeros_like(qw))

                attn = jnp.concatenate([window(col) for col in range(0, c.chunk, span)], axis=1)
            else:
                attn = paged_attention_ref(q, kp[pi], vp[pi], c.block_tables, c.cached_lens,
                                           c.new_lens)
        return (kp, vp), attn

    @staticmethod
    def staged(cfg, kv_pools, b, n_steps):
        """The burst's staged rows, empty: what its layers write and its attention
        reads as a tail until one commit lays them into their pages."""
        return tuple(jnp.zeros((cfg.kv_layers, b, cfg.num_kv_heads, n_steps, cfg.head_dim),
                               kv_pools[0].dtype) for _ in range(2))

    @staticmethod
    def burst_attend(m, cfg, p, pi, q, rows, staged, kv_pools, c):
        """A layer's token into the staged rows and its attention over the pools
        and the tail.  Returns (attn, staged)."""
        from githubrepostorag_tpu.ops.attention import dense_attention
        from githubrepostorag_tpu.ops.paged_attention import gather_kv
        from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged

        (k, v), (sk, sv), (k_pages, v_pages) = rows, staged, kv_pools
        with jax.named_scope("kv_write"):
            sk = jax.lax.dynamic_update_slice(
                sk, k.swapaxes(1, 2).astype(sk.dtype)[None], (pi, 0, 0, c.step, 0))
            sv = jax.lax.dynamic_update_slice(
                sv, v.swapaxes(1, 2).astype(sv.dtype)[None], (pi, 0, 0, c.step, 0))
        sk_l = jax.lax.dynamic_index_in_dim(sk, pi, 0, keepdims=False)
        sv_l = jax.lax.dynamic_index_in_dim(sv, pi, 0, keepdims=False)
        # under the scope: the kernel's instruction is named for it in the
        # device trace, where the accepted metric looks for it
        with jax.named_scope("paged_attention"):
            if c.use_pallas:
                attn = paged_attention_decode_staged(
                    q, k_pages, v_pages, c.block_tables, c.walk_lens, sk_l, sv_l,
                    jnp.reshape(c.step + 1, (1,)), jnp.reshape(pi, (1,)), interpret=c.interpret)
            else:
                pool_k, pool_v = gather_kv(k_pages[pi], v_pages[pi], c.block_tables)
                valid = jnp.concatenate(
                    [jnp.arange(pool_k.shape[1])[None, :] < c.start_lens[:, None],
                     jnp.broadcast_to((jnp.arange(c.n_steps) <= c.step)[None, :],
                                      (c.b, c.n_steps))],
                    axis=1)
                attn = dense_attention(
                    q, jnp.concatenate([pool_k, sk_l.swapaxes(1, 2)], axis=1),
                    jnp.concatenate([pool_v, sv_l.swapaxes(1, 2)], axis=1),
                    causal=False, kv_valid=valid)
        return attn, (sk, sv)

    @staticmethod
    def burst_commit(cfg, kv_pools, staged, slots, b, n_steps, page_size):
        """The burst's staged rows into their pages, one commit a pool."""
        from githubrepostorag_tpu.serving.kv_cache import commit_paged

        nkv, hd = cfg.num_kv_heads, cfg.head_dim
        with jax.named_scope("kv_write"):
            commit = lambda pool, st: commit_paged(  # noqa: E731
                pool, st.swapaxes(1, 2).reshape(cfg.kv_layers, nkv, b * n_steps, hd), slots,
                None, page_size, run=n_steps)[0]  # a row's steps are consecutive positions
            return commit(kv_pools[0], staged[0]), commit(kv_pools[1], staged[1])


# ----------------------------------------------------------- step programs --

def wave(m, params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
         cached_lens, new_lens, state, state_src, state_dst, state_snap, snap_col,
         use_pallas=False, logits_at=None, width=None):
    """A prefill chunk, qwen2.forward_paged's contract with the state beside
    the pools (module docstring), traced into the wave program too
    (``width``: the wave's, see ops/prefill_width.at_wave_width).  Returns
    (logits, k_pages, v_pages, the layers' counts [2], state)."""
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    pages = getattr(m, "pages", KVPages)
    h = m.embed(cfg, params, input_ids)
    along = m.position_cols(cfg, positions)
    slots = jnp.where(slot_mapping < 0, num_pages * page_size, slot_mapping)  # dropped
    live = jnp.arange(input_ids.shape[1])[None, :] < new_lens[:, None]
    w = m.weights(params)
    cut = cfg.state_cols

    chunk = input_ids.shape[1]
    cols = (h, *along, live)

    def padded(x):  # a rung's columns back to the chunk's: every rung returns the same shapes
        return jnp.pad(x, ((0, 0), (0, chunk - x.shape[1])) + ((0, 0),) * (x.ndim - 2))

    def add(stats, counts):
        return stats if counts is None else stats + counts

    # THE POOLS NEVER ENTER A SWITCH (at_wave_width).  A branch that writes a
    # pool it was handed, or only hands it on, makes the v5e compiler copy the
    # pool into the branch and out of it (1.2 GB of state, 0.5 GB of keys, a
    # layer: this program, unlike qwen2's, has four switches a scan body).  A
    # layer's rows of state are read before its switch and written after it;
    # the attention layer's switch ends at q, k, v, its pages are committed
    # and attended outside, and a second switch takes the rest of the layer.
    def read_state(st_pools, g):
        s_pool, c_pool = st_pools
        with jax.named_scope("state_read"):
            s0 = _cut(state_read(s_pool, g, state_src), cut).astype(jnp.float32)
            taps0 = state_read(c_pool, g, state_src)
        return s0, taps0

    def write_state(st_pools, g, s_new, s_snap, taps, taps_snap):
        s_pool, c_pool = st_pools
        with jax.named_scope("state_write"):
            s_new, s_snap = (_fill(x, s_pool.shape[-1]) for x in (s_new, s_snap))
            s_pool = state_write(state_write(s_pool, g, state_dst, s_new), g, state_snap, s_snap)
            c_pool = state_write(state_write(c_pool, g, state_dst, taps), g, state_snap,
                                 taps_snap)
        return s_pool, c_pool

    def state_layer(g, li, h, st_pools, stats):
        def layer(cols, came_in):
            h, live = cols[0], cols[len(along) + 1]
            s0, taps0 = came_in
            x, pg = m.mixer_input(cfg, w, li, h), m.state_weights(w, g)
            y, s_new, s_snap, taps, taps_snap = m.state_chunk(
                cfg, pg, x, s0, taps0, live, new_lens, snap_col, page_size)
            h, st = m.after_mixer(cfg, w, li, h, y, live)
            return h, (s_new, s_snap, taps, taps_snap, st)

        h, (*new, st) = at_wave_width(
            layer, width, page_size, (h, *cols[1:]), read_state(st_pools, g))
        return h, write_state(st_pools, g, *new), add(stats, st)

    call = SimpleNamespace(slots=slots, block_tables=block_tables, cached_lens=cached_lens,
                           new_lens=new_lens, width=width, chunk=chunk, page_size=page_size,
                           use_pallas=use_pallas)

    def attend(pi, q, rows, kv_pools):
        """What a page layer commits into its pages and the chunk's attention over
        them (``pages``), outside every switch.  Returns (the pools, attn)."""
        return pages.wave_attend(m, cfg, lambda: m.attn_weights(w, pi), pi, q, rows, kv_pools,
                                 call)

    def projected(cols, p, x):
        """``m.attn_project`` as (q, what rides to ``attn_out``, the rows to commit)."""
        q, *rows, more = m.attn_project(cfg, p, x, *cols[1:len(along) + 1])
        return tuple(padded(t) for t in (q, *more, *rows))

    def unpacked(out):
        q, *rest = out
        return q, rest[:len(rest) - pages.rows], rest[len(rest) - pages.rows:]

    def attn_layer(pi, li, h, kv_pools, stats):
        def project(cols, _):
            h = cols[0]
            return h, projected(cols, m.attn_weights(w, pi), m.mixer_input(cfg, w, li, h))

        q, more, rows = unpacked(at_wave_width(project, width, page_size, (h, *cols[1:]), ())[1])
        kv_pools, attn = attend(pi, q, rows, kv_pools)

        def rest(cols, came_in):
            h, live, attn, *more = cols[0], *cols[len(along) + 1:]
            return m.after_mixer(cfg, w, li, h, m.attn_out(m.attn_weights(w, pi), attn, *more),
                                 live)

        h, st = at_wave_width(rest, width, page_size, (h, *cols[1:], attn, *more), ())
        return h, kv_pools, add(stats, st)

    def both_layer(n, li, h, st_pools, kv_pools, stats):
        """Both mixers on one ``mixer_input``: the first switch holds the state
        mixer whole and the attention mixer up to q, k, v (they share nothing
        but ``x``: the compiler orders them as it likes); the second the
        attention's output projection, the sum and the rest of the layer."""
        g, pi = n

        def mixers(cols, came_in):
            h, live = cols[0], cols[len(along) + 1]
            s0, taps0 = came_in
            x = m.mixer_input(cfg, w, li, h)
            y, s_new, s_snap, taps, taps_snap = m.state_chunk(
                cfg, m.state_weights(w, g), x, s0, taps0, live, new_lens, snap_col, page_size)
            return h, (s_new, s_snap, taps, taps_snap, (padded(y), *projected(cols, m.attn_weights(w, pi), x)))

        _, (*new, (y, *out)) = at_wave_width(
            mixers, width, page_size, (h, *cols[1:]), read_state(st_pools, g))
        q, more, rows = unpacked(out)
        st_pools = write_state(st_pools, g, *new)
        kv_pools, attn = attend(pi, q, rows, kv_pools)

        def rest(cols, came_in):
            h, live, y, attn, *more = cols[0], *cols[len(along) + 1:]
            return m.after_mixer(
                cfg, w, li, h, (y, m.attn_out(m.attn_weights(w, pi), attn, *more)), live)

        h, st = at_wave_width(rest, width, page_size, (h, *cols[1:], y, attn, *more), ())
        return h, st_pools, kv_pools, add(stats, st)

    def plain_layer(n, li, h, stats):
        def layer(cols, _):
            return m.plain_layer(cfg, w, n, li, cols[0], cols[len(along) + 1])

        h, st = at_wave_width(layer, width, page_size, (h, *cols[1:]), ())
        return h, add(stats, st)

    kv_pools, st_pools = (k_pages, v_pages), (state["s"], state["conv"])
    stats = jnp.zeros((getattr(m, "counts", 2),), jnp.int32)
    for kinds, reps, before in _walk(cfg.layer_segments):
        def body(carry, _, kinds=kinds, before=before):
            h, rep, kv_pools, st_pools, stats = carry
            for kind, n, li in _layers(kinds, before, rep):
                if kind == STATE:
                    h, st_pools, stats = state_layer(n, li, h, st_pools, stats)
                elif kind == ATTN:
                    h, kv_pools, stats = attn_layer(n, li, h, kv_pools, stats)
                elif kind == BOTH:
                    h, st_pools, kv_pools, stats = both_layer(n, li, h, st_pools, kv_pools,
                                                              stats)
                else:
                    h, stats = plain_layer(n, li, h, stats)
            return (h, rep + 1, kv_pools, st_pools, stats), None

        (h, _, kv_pools, st_pools, stats), _ = jax.lax.scan(
            body, (h, jnp.int32(0), kv_pools, st_pools, stats), None, length=reps)
    k_pages, v_pages = kv_pools
    with jax.named_scope("sample"):
        h = m.final(cfg, params, h)
        if logits_at is not None:
            h = jnp.take_along_axis(h, logits_at[:, None, None], axis=1)
        logits = m.head(cfg, params, h)
    return logits, k_pages, v_pages, stats, {"s": st_pools[0], "conv": st_pools[1]}


def burst(m, params, cfg, last_tokens, seq_lens, k_pages, v_pages, presence, active, row_limits,
          block_tables, rng, temperature, top_p, top_k, repetition_penalty, n_steps,
          use_pallas, filter_sampling, first_tokens, fresh, fresh_lens, key_step, state):
    """``n_steps`` decode iterations in one program, serving/decode_burst.py's
    contract and structure: the K/V pools are loop-invariant inside the burst
    (new keys and values go to a staged buffer the kernel reads as a tail, one
    commit a pool lays them into their pages at the end, a row's steps as one
    run); the state pool is stepped in place,
    rows 0 .. B-1, a row that sits a step out keeping what it has.  A layer's
    rows of state cross HBM twice a step: read out of the pool once, at the
    width the pool stores them (``lane_padded``), and written into it once, by
    the update itself; nothing pads, cuts or copies them in between.  Returns
    (packed tokens [B, n_steps], valid, k_pages, v_pages, presence, seq_lens,
    last_tokens, the layers' counts [2], state)."""
    from githubrepostorag_tpu.serving.decode_burst import overlay_fresh

    last_tokens, seq_lens, rng = overlay_fresh(
        last_tokens, seq_lens, rng, first_tokens, fresh, fresh_lens, key_step)
    b = last_tokens.shape[0]
    pages = getattr(m, "pages", KVPages)
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    rows = jnp.arange(b)
    start_lens = seq_lens
    walk_lens = jnp.where(active & (seq_lens < row_limits), start_lens, 0)
    interpret = not on_tpu()
    w = m.weights(params)
    # a layer type whose rule steps the pool where it lies (a kernel over the live rows,
    # ops/pallas_state.py) brings it; the others' rows are read out, stepped and put back
    step_in_pool = getattr(m, "state_step_in_pool", None) if use_pallas else None

    def rows_of(pool, g):  # the engine's rows are the pool's first slots
        return jax.lax.dynamic_slice(pool, (g,) + (0,) * (pool.ndim - 1),
                                     (1, b, *pool.shape[2:]))[0]

    def put_rows(pool, g, vals):
        return jax.lax.dynamic_update_slice(pool, vals[None].astype(pool.dtype),
                                            (g,) + (0,) * (pool.ndim - 1))

    def one_step(carry, step_xs):
        last, lens, staged, st_pools, pres, act, stats = carry
        step, step_rng = step_xs
        act = act & (lens < row_limits)
        h = m.embed(cfg, params, jnp.maximum(last, 0)[:, None])
        along = m.position_cols(cfg, lens[:, None])

        def state_mixer(p, g, x, st_pools):
            s_pool, c_pool = st_pools
            taps_old = rows_of(c_pool, g)
            if step_in_pool is not None:
                y, s_pool, taps = step_in_pool(cfg, p, x, s_pool, g, taps_old, act, interpret)
                return y, (s_pool, put_rows(c_pool, g, jnp.where(act[:, None], taps, taps_old)))
            s_old = rows_of(s_pool, g)
            if s_old.shape[-1] != cfg.state_cols:
                # a pool stored wider than its values (lane_padded).  Behind the barrier the
                # compiler keeps ONE copy of the rows (on a v5e: in VMEM) for the reductions
                # and for the update; without it the update slices the pool a second time,
                # 94 MB more a layer and step at Olmo-Hybrid's shapes (PR 40).  On the chip
                # both Gated DeltaNet families take the kernel above since PR 56: this branch
                # is the CPU's path and the kernel's oracle (ROADMAP D26 asks what may go)
                s_old = jax.lax.optimization_barrier(s_old)
            y, s_new, taps = m.state_step(cfg, p, x, s_old, taps_old)
            with jax.named_scope(m.step_scope):  # the update, into the pool, under the rule's name
                s_new = jnp.where(act[:, None, None, None], s_new.astype(s_pool.dtype), s_old)
                s_pool = put_rows(s_pool, g, s_new)
            c_pool = put_rows(c_pool, g, jnp.where(act[:, None], taps, taps_old))
            return y, (s_pool, c_pool)

        def attn_mixer(p, pi, x, staged):
            q, *rows, more = m.attn_project(cfg, p, x, *along)
            attn, staged = pages.burst_attend(
                m, cfg, p, pi, q, rows, staged, (k_pages, v_pages),
                SimpleNamespace(block_tables=block_tables, walk_lens=walk_lens,
                                start_lens=start_lens, step=step, n_steps=n_steps, b=b,
                                use_pallas=use_pallas, interpret=interpret))
            return m.attn_out(p, attn, *more), staged

        # the layers are unrolled, not scanned: with a layer's index static its
        # weights are views of the stacks, and a burst of 8 layers still
        # compiles in seconds
        live = act[:, None]
        for kinds, reps, before in _walk(cfg.layer_segments):
            for rep in range(reps):
                for kind, n, li in _layers(kinds, before, rep):
                    if kind == PLAIN:
                        h, st = m.plain_layer(cfg, w, n, li, h, live)
                    else:
                        x = m.mixer_input(cfg, w, li, h)
                        if kind == STATE:
                            y, st_pools = state_mixer(m.state_weights(w, n), n, x, st_pools)
                        elif kind == ATTN:
                            y, staged = attn_mixer(m.attn_weights(w, n), n, x, staged)
                        else:  # BOTH: one input, two mixers that meet in ``after_mixer``
                            g, pi = n
                            ys, st_pools = state_mixer(m.state_weights(w, g), g, x, st_pools)
                            ya, staged = attn_mixer(m.attn_weights(w, pi), pi, x, staged)
                            y = (ys, ya)
                        h, st = m.after_mixer(cfg, w, li, h, y, live)
                    if st is not None:
                        stats = stats + st
        with jax.named_scope("sample"):
            logits = m.head(cfg, params, m.final(cfg, params, h))
            if filter_sampling:
                toks = sample_tokens_capped(logits[:, 0], step_rng, temperature, top_p, top_k,
                                            repetition_penalty, pres)
            else:
                toks = sample_tokens_nofilter(logits[:, 0], step_rng, temperature,
                                              repetition_penalty, pres)
        toks = jnp.where(act, toks, last)
        pres = pres.at[rows, toks].max(act)
        lens = lens + act.astype(jnp.int32)
        return (toks, lens, staged, st_pools, pres, act, stats), (toks, act)

    staged0 = pages.staged(cfg, (k_pages, v_pages), b, n_steps)
    carry0 = (last_tokens, seq_lens, staged0, (state["s"], state["conv"]), presence, active,
              jnp.zeros((getattr(m, "counts", 2),), jnp.int32))
    (last, out_lens, staged, st_pools, presence, _, stats), (toks, valid) = jax.lax.scan(
        one_step, carry0, (jnp.arange(n_steps), jax.random.split(rng, n_steps)))
    toks, valid = toks.T, valid.T
    packed = jnp.where(valid, toks, -1)

    pos = start_lens[:, None] + jnp.arange(n_steps)[None, :]
    page_idx = jnp.clip(pos // page_size, 0, block_tables.shape[1] - 1)
    slots = jnp.take_along_axis(block_tables, page_idx, axis=1) * page_size + pos % page_size
    slots = jnp.where(valid, slots, num_pages * page_size).reshape(-1)  # sentinel: dropped
    k_pages, v_pages = pages.burst_commit(cfg, (k_pages, v_pages), staged, slots, b, n_steps,
                                          page_size)
    return (packed, valid, k_pages, v_pages, presence, out_lens, last, stats,
            {"s": st_pools[0], "conv": st_pools[1]})
