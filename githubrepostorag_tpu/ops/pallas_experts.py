"""The hit experts of one layer as ONE walk over the expert stacks.

A decode burst's 32 row slots (or a one-row wave's 128 columns) fit one
dispatch tile, so an expert that was hit runs on all of them and its result
is weighted by that expert's column of the dense ``[t, held]`` weights, zero
where a row did not choose it (models/moe.dropless_experts).  As array code
that was a ``fori_loop`` over the hit experts whose body sliced one expert
out of the ``[L, held, ...]`` stacks and ran two or three XLA products on it:
each product starts its own weight stream when it is dispatched and the next
cannot start before it ends, so every 2-4 MB read paid its own ramp and a
6 MB expert read at two thirds of the HBM's speed (PERF.md, PR 56 and PR 58).

``walk_experts`` is the same sum as one Pallas call.  The stacks stay whole
in HBM and are only ever addressed by the kernel's own DMAs (the walk of
ops/pallas_state.py, ops/pallas_paged.py's burst kernel and
ops/latent_attention.py's decode kernel).  A BLOCK is a slice of one expert's
hidden width: the columns of the up-projection and the rows of the
down-projection that belong to it come into VMEM together, the next block in
flight while this one's products run, and ``act(x W_up) W_down`` of the slice
is added, weighted, into the result, which stays in VMEM over the whole walk
and is written once.  An expert that was not hit starts no DMA and the loop
runs over the hit ones only; with none hit the result is zeros.

The families' expert forms are a ``body`` each on that walk:

* ``SWIGLU``: gate | up side by side in ``e_wgu`` [L, held, d, 2 ff] and
  ``e_wd`` [L, held, ff, d] (models/hybrid.swiglu; Qwen3-Next, Ling, Mellum2);
* ``RELU2``: ``W_down relu(W_up x)^2`` with ``e_wu`` [L, held, ff, d], the
  hidden width FIRST as it is stored (models/nemotron_h.relu2_ffn says why),
  and ``e_wd`` [L, held, ff, d].

Arithmetic: the products take their operands as they are stored (bfloat16 in
every cell) and sum in float32; the hidden activations stay float32 until
the down-projection reads them, where the array code rounded them once more.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from githubrepostorag_tpu.runtime import on_tpu

# a block's bytes in VMEM (its slice of every stack), of which the walk holds two.  On a v5e the
# walk runs at its DMAs' speed whatever the block (the products hide: with none, the same time),
# and the DMAs at 92-93% of 819 GB/s where they read whole runs of HBM (Nemotron-H's rows of 5 KB;
# Ling's columns read 93%, Mellum2's 91%) but 85% in blocks of Qwen3-Next's gate | up columns
# (4 KB pieces 32 KB apart): its 6 MB expert as ONE block, gate | up the one run it is stored as,
# reads 92% (8.31 us an expert against 8.95 in blocks of 1.5 MB and the loop's 13.8; the other
# three read the same at 1.5, 4 and 8 MB): PERF.md, PR 58
BLOCK_BYTES = 8 << 20
SCOPED_VMEM = 16 << 20  # what a kernel may use without asking; the walk asks for two blocks more
LANES = 128  # a block's hidden width is a whole number of lane tiles


class Body(NamedTuple):
    """One expert form.  ``hidden(stacks) -> ff``; ``buffers(stacks, fb)``: the
    VMEM shapes that hold a block of ``fb`` hidden columns; ``pieces(ff, lo, w)``:
    the block ``lo .. lo + w`` as ``(stack, its index inside one expert, buffer,
    the index inside it)`` a DMA; ``apply(x, *views)``: the block's float32
    ``[t, d]`` from ``views(bufs, w)`` of the buffers' filled part."""
    hidden: Callable
    buffers: Callable
    pieces: Callable
    views: Callable
    apply: Callable


def _swiglu_block(x, gu, wd):
    h = jnp.dot(x, gu, preferred_element_type=jnp.float32)
    w = h.shape[1] // 2
    a = jax.nn.silu(h[:, :w]) * h[:, w:]
    return jnp.dot(a.astype(wd.dtype), wd, preferred_element_type=jnp.float32)


def _swiglu_pieces(ff, lo, w):
    down = (1, (pl.ds(lo, w), slice(None)), 1, (pl.ds(0, w), slice(None)))
    if w == ff:  # the whole width: gate | up is the one run of HBM it is stored as
        return ((0, (slice(None), slice(None)), 0, (slice(None), slice(None))), down)
    return ((0, (slice(None), pl.ds(lo, w)), 0, (slice(None), pl.ds(0, w))),
            (0, (slice(None), pl.ds(ff + lo, w)), 0, (slice(None), pl.ds(w, w))), down)


SWIGLU = Body(
    hidden=lambda stacks: stacks[1].shape[2],
    buffers=lambda stacks, fb: ((stacks[0].shape[2], 2 * fb), (fb, stacks[1].shape[3])),
    pieces=_swiglu_pieces,
    views=lambda bufs, w: (bufs[0][:, :2 * w], bufs[1][:w]),
    apply=_swiglu_block,
)


def _relu2_block(x, wu, wd):
    u = jax.nn.relu(jax.lax.dot_general(x, wu, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    return jnp.dot((u * u).astype(wd.dtype), wd, preferred_element_type=jnp.float32)


RELU2 = Body(
    hidden=lambda stacks: stacks[0].shape[2],
    buffers=lambda stacks, fb: ((fb, stacks[0].shape[3]), (fb, stacks[1].shape[3])),
    pieces=lambda ff, lo, w: tuple(
        (s, (pl.ds(lo, w), slice(None)), s, (pl.ds(0, w), slice(None))) for s in (0, 1)),
    views=lambda bufs, w: (bufs[0][:w], bufs[1][:w]),
    apply=_relu2_block,
)


def _walk_kernel(hit_ref, n_ref, layer_ref, x_ref, w_ref, *refs, body: Body, n_stacks: int,
                 blocks: tuple, ff: int):
    """Refs after the three prefetched scalars (``hit`` [held] int32: the hit
    experts first; ``n`` [1]: how many; ``layer`` [1]): ``x`` [t, d] and the
    dense weights ``w`` [t, held] float32 in VMEM, the stacks in HBM, the
    result ``y`` [t, d] float32 in VMEM, then scratch: a block's buffers
    ``(2, ...)`` each and their DMA semaphores ``(2, pieces)``.  ``blocks``:
    the static ``(lo, width)`` slices of the hidden width that make an expert."""
    stacks, y_ref = refs[:n_stacks], refs[n_stacks]
    bufs, sem = refs[n_stacks + 1:-1], refs[-1]
    layer, n_hit, nb = layer_ref[0], n_ref[0], len(blocks)

    def copies(i, j, slot):  # block ``j`` of the ``i``-th hit expert into ``slot``
        e = hit_ref[i]
        return [pltpu.make_async_copy(stacks[s].at[(layer, e, *src)], bufs[b].at[(slot, *dst)],
                                      sem.at[slot, k])
                for k, (s, src, b, dst) in enumerate(body.pieces(ff, *blocks[j]))]

    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_hit > 0)
    def _():
        for c in copies(0, 0, 0):
            c.start()

    def one_expert(i, carry):
        e = hit_ref[i]
        wt = w_ref[...]  # this expert's column: a row's weight, zero where it did not choose it
        col = jnp.sum(jnp.where(jax.lax.broadcasted_iota(jnp.int32, wt.shape, 1) == e, wt, 0.0),
                      axis=1, keepdims=True)
        for j, (_, width) in enumerate(blocks):
            slot = (i * nb + j) % 2
            if j + 1 < nb:
                for c in copies(i, j + 1, 1 - slot):
                    c.start()
            else:
                @pl.when(i + 1 < n_hit)
                def _():
                    for c in copies(i + 1, 0, 1 - slot):
                        c.start()
            for c in copies(i, j, slot):
                c.wait()
            y_ref[...] += col * body.apply(x_ref[...], *body.views([b[slot] for b in bufs], width))
        return carry

    jax.lax.fori_loop(0, n_hit, one_expert, 0)


def _blocks(ff: int, bytes_a_column: int, block_bytes: int) -> tuple:
    """``(lo, width)`` slices of a hidden width ``ff``: as many whole lane
    tiles a block as ``block_bytes`` hold, the last one what is left."""
    fb = max(LANES, block_bytes // (bytes_a_column * LANES) * LANES)
    return tuple((lo, min(fb, ff - lo)) for lo in range(0, ff, fb))


@functools.partial(jax.jit, static_argnames=("body", "name", "block_bytes", "interpret"))
def walk_experts(x, w_dense, first_hit, n_hit, layer, stacks, *, body: Body, name: str,
                 block_bytes: int, interpret: bool = False):
    """Sum over the hit experts ``e`` of ``w_dense[:, e, None] * E_e(x)``.

    ``x`` [t, d], t a multiple of 8; ``w_dense`` [t, held] float32;
    ``first_hit`` [held] int32 with the ``n_hit`` experts that were hit first;
    ``layer`` an index into ``stacks``, the ``[L, held, ...]`` arrays of
    ``body``.  Returns y [t, d] float32.  ``name`` is the innermost scope, which
    a device trace names the call for (``moe_experts.N_f32_32_2048_``);
    ``block_bytes`` what a block may hold of an expert (``BLOCK_BYTES``)."""
    rows, d = x.shape
    if rows % 8:  # whole sublane tiles; a padding row's weights are zero
        x, w_dense = (jnp.pad(a, ((0, -rows % 8), (0, 0))) for a in (x, w_dense))
    t = x.shape[0]
    ff = body.hidden(stacks)
    expert_bytes = sum(s.dtype.itemsize * s.size // (s.shape[0] * s.shape[1]) for s in stacks)
    blocks = _blocks(ff, expert_bytes // ff, block_bytes)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    buffers = body.buffers(stacks, blocks[0][1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[vmem, vmem] + [in_hbm] * len(stacks),
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((2, *shape), stacks[0].dtype) for shape in buffers]
        + [pltpu.SemaphoreType.DMA((2, len(body.pieces(ff, *blocks[0]))))],
    )
    kernel = functools.partial(_walk_kernel, body=body, n_stacks=len(stacks), blocks=blocks, ff=ff)
    with jax.named_scope(name):  # XLA names the custom call after the innermost scope
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                                 vmem_limit_bytes=SCOPED_VMEM + 2 * block_bytes),
            interpret=interpret,
        )(first_hit.astype(jnp.int32), jnp.reshape(n_hit, (1,)).astype(jnp.int32),
          jnp.reshape(layer, (1,)).astype(jnp.int32), x, w_dense, *stacks)[:rows]


def experts_walk(body: Body, stacks, layer, burst: bool):
    """``walk_experts`` over one layer's experts as models/moe.dropless_experts
    takes it (``walk``).  ``burst``: the call is a decode burst's and is named
    ``moe_experts``, which the benchmark's ``moe_experts_hbm_frac`` reads
    against the bursts' count of hit experts; a prefill wave's is named
    ``wave_experts``, which that metric's pattern does not match (the engine
    counts no wave's experts, so its seconds there would read the share low).
    None off the chip: the dispatch then runs its loop of XLA products over the
    hit experts, the CPU's path and this kernel's oracle (interpreted, the walk
    made the benchmark's five-second rehearsals on a loaded CPU serve too few
    requests to judge: CHANGES.md, PR 58)."""
    if not on_tpu():
        return None
    return functools.partial(walk_experts, layer=jnp.asarray(layer, jnp.int32), stacks=stacks,
                             body=body, name="moe_experts" if burst else "wave_experts",
                             block_bytes=BLOCK_BYTES)
