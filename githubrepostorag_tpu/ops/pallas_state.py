"""One-token rules that step a recurrent state pool WHERE IT LIES.

A state pool is ``[layers, slots, heads, a, b]`` (serving/kv_cache.StateSlots:
the engine's rows are a layer's first slots, snapshots and a spare follow).  A
decode burst steps the LIVE rows of one layer, one token each, and every other
slot keeps its bits.  Done as array code (models/hybrid.burst's ``rows_of`` ->
rule -> ``where`` -> ``put_rows``) XLA passes every row slot, live or not, and
at Nemotron-H's shapes three times: ``S C`` read off the pool, the update
reading it again, and the write (201 MB a layer and step where the ~17 live
rows need 71: PERF.md, PR 42).

``step_rows_in_place`` is the walk: the pool stays whole in HBM, aliased from
operand to result, and is only ever addressed by the kernel's own DMAs.  A
live row's state comes into VMEM in blocks of ``block_heads`` heads (one
contiguous run of the pool), the next block in flight while one is computed;
the rule's ``body`` makes the block's new value and whatever else it reads off
the ONE copy; the new block goes back to the slot it came from.  A dead row
starts no DMA in either direction and a slot past the rows is never addressed:
``where(act, new, old)`` as control, not as data.  A block is read once, before
it is written, and no two blocks overlap, so a read can never meet a write.

Three rules take that walk, each as a ``body``:

* ``ssd_step_in_place``: Mamba-2's (ops/ssd.ssd_step is the same rule as array
  code: the CPU path, and the oracle of tests/test_ssd.py).
* ``gated_delta_step_in_place``: the Gated DeltaNet's (ops/gated_delta
  .gated_delta_step likewise: the CPU path and the oracle of
  tests/test_gated_delta.py; both families' bursts take the kernel on the
  chip, Olmo-Hybrid's since PR 49 and Qwen3-Next's since PR 56: PERF.md).
* ``kda_step_in_place``: the same rule with the decay a key channel (Kimi
  Delta Attention: a column ``[dk]`` a head beside ``k`` and ``q`` in VMEM where
  the Gated DeltaNet's is a scalar in SMEM; the array form is
  ``gated_delta_step`` with ``g`` [B, H, dk]).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a block of a row's state in VMEM, of which the walk holds four (two coming in, two going out).
# On a v5e, 16 live rows of 2.1 MB a layer: 0.150 ms in blocks of 256 KB, 0.130 at 512 KB, 0.123
# at 1 MB, where the rule's arithmetic hides behind the DMAs (0.120 with none; a block sent as 2
# or 4 DMAs at once: the same): PERF.md, PR 42
BLOCK_BYTES = 1 << 20


def _walk_kernel(act_ref, layer_ref, *refs, body, n_in: int, n_out: int, block_heads: int):
    """Refs after the two prefetched scalars (``act`` [B] int32, ``layer`` [1]):
    ``n_in`` operands of ``body``, the pool in HBM, ``n_out`` results of
    ``body``, the pool again (the same buffer: the result it is aliased to),
    then scratch: blocks in and out ``(2, block_heads, a, b)``, their DMA
    semaphores ``(2,)`` each, and the list of live rows ``(B,)`` in SMEM.

    ``body(row, block, state [block_heads, a, b], *operand refs, *result refs)``
    returns the block's new value; ``block`` is a Python int, ``row`` traced."""
    ins, pool_in = refs[:n_in], refs[n_in]
    outs, pool_out = refs[n_in + 1:n_in + 1 + n_out], refs[n_in + 1 + n_out]
    buf_in, buf_out, sem_in, sem_out, live_ref = refs[n_in + n_out + 2:]
    layer = layer_ref[0]
    blocks = pool_in.shape[2] // block_heads

    def compact(r, n):  # the live rows, in order; what is past the last is never read
        live_ref[n] = r
        return n + act_ref[r]

    n_live = jax.lax.fori_loop(0, act_ref.shape[0], compact, 0)
    total = n_live * blocks

    def heads_of(pool, t):  # item ``t``: block ``t % blocks`` of the ``t // blocks``-th live row
        return pool.at[layer, live_ref[t // blocks], pl.ds((t % blocks) * block_heads, block_heads)]

    def read(t):
        return pltpu.make_async_copy(heads_of(pool_in, t), buf_in.at[t % 2], sem_in.at[t % 2])

    def write(t):
        return pltpu.make_async_copy(buf_out.at[t % 2], heads_of(pool_out, t), sem_out.at[t % 2])

    @pl.when(total > 0)
    def _():
        read(0).start()

    def one_row(i, carry):
        row = live_ref[i]
        for j in range(blocks):
            t = i * blocks + j

            @pl.when(t + 1 < total)
            def _():
                read(t + 1).start()

            read(t).wait()

            @pl.when(t >= 2)
            def _():
                write(t - 2).wait()  # the block that left this slot last

            buf_out[t % 2] = body(row, j, buf_in[t % 2], *ins, *outs)
            write(t).start()
        return carry

    jax.lax.fori_loop(0, n_live, one_row, 0)
    for back in (2, 1):
        @pl.when(total >= back)
        def _():
            write(total - back).wait()


def step_rows_in_place(body, pool, layer, act, operands, results, block_heads: int,
                       smem_operands=(), interpret=False):
    """Step the live rows of ``pool[layer]`` in place.  ``pool`` [L, slots, H,
    a, b]; ``act`` [B] bool, B <= slots: the rows to step; ``operands``: arrays
    ``body`` reads whole in VMEM (``smem_operands``: in SMEM, handed to it
    first); ``results``: ``jax.ShapeDtypeStruct`` of what it writes whole in
    VMEM.  Returns (*results, the pool).  What ``body`` does not write of its
    results (a dead row's part) is whatever the buffer held: the caller's to
    mask."""
    _, _, heads, a, b = pool.shape
    assert heads % block_heads == 0, (heads, block_heads)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    n_in = len(smem_operands) + len(operands)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[smem] * len(smem_operands) + [vmem] * len(operands) + [in_hbm],
        out_specs=[vmem] * len(results) + [in_hbm],
        scratch_shapes=[
            pltpu.VMEM((2, block_heads, a, b), pool.dtype),
            pltpu.VMEM((2, block_heads, a, b), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((act.shape[0],), jnp.int32),
        ],
    )
    kernel = functools.partial(_walk_kernel, body=body, n_in=n_in, n_out=len(results),
                               block_heads=block_heads)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # the small results first: an instruction is named for its first result's shape in a
        # trace (benchmarks/trace.short_name), and this one computes; it does not move the pool
        out_shape=[*results, jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={2 + n_in: len(results)},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(act.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      *smem_operands, *operands, pool)


def _heads_a_block(heads: int, a: int, b: int, itemsize: int) -> int:
    """As many of a row's ``heads`` states ``[a, b]`` as ``BLOCK_BYTES`` hold,
    and a divisor of ``heads``."""
    fit = max(1, BLOCK_BYTES // (a * b * itemsize))
    return max(k for k in range(1, heads + 1) if heads % k == 0 and k <= fit)


def _ssd_body(row, block, s, decay_ref, dt_ref, x_ref, b_ref, c_ref, heads_ref, y_ref, *,
              heads_per_group: int):
    """Mamba-2's rule on one block of heads of one row, in ops/ssd.ssd_step's
    own operations: ``S C`` of the state that came in (a lane reduction) and
    ``S exp(dt A) + (dt x) (x) B``, both from the one copy ``s`` [K, P, N];
    after a row's last block, its ``y = exp(dt A) S C + (B . C) dt x + D x``.
    ``decay_ref``, ``dt_ref`` [B, H] SMEM: a head's scalars; ``x_ref``, ``y_ref``
    [B, P, H]: a head's column lies down the sublanes as the state's rows do
    (``y_ref`` holds ``S C`` until the row is whole); ``b_ref``, ``c_ref``
    [B, G, N]; ``heads_ref`` [B, 4, H]: ``exp(dt A)``, ``dt``, ``B . C`` and
    ``D`` along the lanes, for the row's ``y``."""
    new = []
    for k in range(s.shape[0]):
        h = block * s.shape[0] + k
        g = h // heads_per_group
        y_ref[row, :, h:h + 1] = jnp.sum(s[k] * c_ref[row, g:g + 1, :], axis=-1, keepdims=True)
        dtx = dt_ref[row, h] * x_ref[row, :, h:h + 1]
        new.append(s[k] * decay_ref[row, h] + dtx * b_ref[row, g:g + 1, :])
    if (block + 1) * s.shape[0] == x_ref.shape[-1]:  # the row's last block: S C is whole
        decay, dt, bc, d = (heads_ref[row, i:i + 1, :] for i in range(4))
        y_ref[row] = decay * y_ref[row] + bc * (dt * x_ref[row]) + d * x_ref[row]
    return jnp.stack(new)


def ssd_step_in_place(pool, layer, act, x, dt, a, b, c, d, interpret=False):
    """ops/ssd.ssd_step on the live rows of ``pool[layer]``, in place.  ``pool``
    [L, slots, H, P, N'] float32, N' >= N a whole number of lane tiles;
    ``layer`` an index; ``act`` [B] bool; ``x`` [B, H, P]; ``dt`` [B, H];
    ``a``, ``d`` [H]; ``b``, ``c`` [B, G, N].  Returns (y [B, H, P], the pool):
    a row that is not ``act`` keeps its state (nobody touches its slot) and its
    ``y`` is zero.  A block is as many heads as ``BLOCK_BYTES`` hold."""
    bsz, h, p = x.shape
    g, n = b.shape[1], pool.shape[-1]
    if b.shape[-1] != n:  # the padding lanes read as nothing and stay zero (ssd_step)
        b, c = (jnp.pad(v, ((0, 0), (0, 0), (0, n - v.shape[-1]))) for v in (b, c))
    decay = jnp.exp(dt * a)
    bc = jnp.repeat(jnp.sum(b * c, axis=-1), h // g, axis=1)
    heads = jnp.stack([decay, dt, bc, jnp.broadcast_to(d, dt.shape)], axis=1)
    y_t, pool = step_rows_in_place(
        functools.partial(_ssd_body, heads_per_group=h // g), pool, layer, act,
        (x.swapaxes(1, 2), b, c, heads), (jax.ShapeDtypeStruct((bsz, p, h), jnp.float32),),
        _heads_a_block(h, p, n, pool.dtype.itemsize), smem_operands=(decay, dt),
        interpret=interpret)
    return jnp.where(act[:, None, None], y_t.swapaxes(1, 2), 0.0), pool


def _gdn_body(row, block, s, decay_ref, beta_ref, kq_ref, k_ref, q_ref, v_ref, o_ref):
    """The Gated DeltaNet rule on one block of heads of one row, in
    ops/gated_delta.gated_delta_step's own operations: ``exp(g) S^T k`` and
    ``exp(g) S^T q`` of the state that came in (reductions down the sublanes),
    ``delta = beta (v - exp(g) S^T k)``, the head's ``o = exp(g) S^T q + (k . q)
    delta`` and ``S exp(g) + k (x) delta``, all from the one copy ``s`` [K, dk,
    dv'].  ``decay_ref`` (``exp(g)``), ``beta_ref``, ``kq_ref`` (``k . q``)
    [B, H] SMEM: a head's scalars; ``k_ref``, ``q_ref`` [B, dk, H]: a head's
    column lies down the sublanes as the state's rows do; ``v_ref`` [B, H, dv']
    with lanes ``dv:`` zero, where the state's are: ``delta`` is zero there and
    a padding lane comes out ``0 * decay + k * 0``; ``o_ref`` [B, H, dv]."""
    new = []
    for i in range(s.shape[0]):
        h = block * s.shape[0] + i
        decay, k, q = decay_ref[row, h], k_ref[row, :, h:h + 1], q_ref[row, :, h:h + 1]
        kv = decay * jnp.sum(s[i] * k, axis=0, keepdims=True)
        qv = decay * jnp.sum(s[i] * q, axis=0, keepdims=True)
        delta = beta_ref[row, h] * (v_ref[row, h:h + 1, :] - kv)
        o_ref[row, h:h + 1, :] = (qv + kq_ref[row, h] * delta)[:, :o_ref.shape[-1]]
        new.append(s[i] * decay + k * delta)
    return jnp.stack(new)


def gated_delta_step_in_place(pool, layer, act, q, k, v, g, beta, interpret=False):
    """ops/gated_delta.gated_delta_step on the live rows of ``pool[layer]``, in
    place.  ``pool`` [L, slots, H, dk, dv'] float32, dv' >= dv a whole number of
    lane tiles, lanes ``dv:`` zero; ``layer`` an index; ``act`` [B] bool; ``q``,
    ``k`` [B, H, dk]; ``v`` [B, H, dv]; ``g``, ``beta`` [B, H].  Returns (o
    [B, H, dv], the pool), ``o`` the call's FIRST result (a trace names an
    instruction for it): a row that is not ``act`` keeps its state (nobody
    touches its slot) and its ``o`` is zero.  A block is as many heads as
    ``BLOCK_BYTES`` hold."""
    bsz, h, dk = k.shape
    dv, n = v.shape[-1], pool.shape[-1]
    if dv != n:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, n - dv)))
    o, pool = step_rows_in_place(
        _gdn_body, pool, layer, act, (k.swapaxes(1, 2), q.swapaxes(1, 2), v),
        (jax.ShapeDtypeStruct((bsz, h, dv), jnp.float32),),
        _heads_a_block(h, dk, n, pool.dtype.itemsize),
        smem_operands=(jnp.exp(g), beta, jnp.sum(k * q, axis=-1)), interpret=interpret)
    return jnp.where(act[:, None, None], o, 0.0), pool


def _kda_body(row, block, s, beta_ref, kq_ref, a_ref, k_ref, ak_ref, aq_ref, v_ref, o_ref):
    """``_gdn_body`` with the decay a key channel: ``a_ref`` (``exp(g)``),
    ``k_ref``, ``ak_ref`` (``a k``) and ``aq_ref`` (``a q``) [B, dk, H], a head's
    column down the sublanes as the state's rows lie: ``S^T (a k)`` and ``S^T (a
    q)`` of the state that came in are those of the decayed one, and the update
    scales the state's ROWS: ``a[:, None] S + k (x) delta``.  ``beta_ref``,
    ``kq_ref`` (``k . q``) [B, H] SMEM; ``v_ref`` [B, H, dv'], ``o_ref`` [B, H, dv]."""
    new = []
    for i in range(s.shape[0]):
        h = block * s.shape[0] + i
        kv = jnp.sum(s[i] * ak_ref[row, :, h:h + 1], axis=0, keepdims=True)
        qv = jnp.sum(s[i] * aq_ref[row, :, h:h + 1], axis=0, keepdims=True)
        delta = beta_ref[row, h] * (v_ref[row, h:h + 1, :] - kv)
        o_ref[row, h:h + 1, :] = (qv + kq_ref[row, h] * delta)[:, :o_ref.shape[-1]]
        new.append(s[i] * a_ref[row, :, h:h + 1] + k_ref[row, :, h:h + 1] * delta)
    return jnp.stack(new)


def kda_step_in_place(pool, layer, act, q, k, v, g, beta, interpret=False):
    """``gated_delta_step_in_place`` with ``g`` [B, H, dk], a decay a key
    channel: the same walk over the live rows of ``pool[layer]``, the same
    results (o [B, H, dv] first, the pool)."""
    bsz, h, dk = k.shape
    dv, n = v.shape[-1], pool.shape[-1]
    if dv != n:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, n - dv)))
    a = jnp.exp(g)
    o, pool = step_rows_in_place(
        _kda_body, pool, layer, act,
        (*(x.swapaxes(1, 2) for x in (a, k, a * k, a * q)), v),
        (jax.ShapeDtypeStruct((bsz, h, dv), jnp.float32),),
        _heads_a_block(h, dk, n, pool.dtype.itemsize),
        smem_operands=(beta, jnp.sum(k * q, axis=-1)), interpret=interpret)
    return jnp.where(act[:, None, None], o, 0.0), pool
