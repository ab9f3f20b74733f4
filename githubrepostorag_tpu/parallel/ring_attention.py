"""Ring attention: causal GQA attention with the sequence axis sharded over
the ``sp`` mesh axis.

Long context the TPU way: each device keeps its contiguous sequence shard of
Q resident and streams the K/V shards around the ring — step ``s`` folds the
block owned by device ``(i - s) mod n`` into an online (streaming) softmax
while ``lax.ppermute`` rotates the K/V blocks one hop over ICI.  Peak memory
per device is O(S/n) for activations and one K/V block in flight; no device
ever materialises the full [S, S] score matrix or the full K/V.

The reference *avoids* long context instead of scaling it (max-model-len
11712 + truncation cascade — SURVEY.md §5.7); this module is what makes
long-context a capability rather than a cap.

``ring_attention`` is the shard_map-local body (pure jnp + ppermute);
``make_ring_attend`` wraps it for global [B, S, H, D] arrays on a mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,  # [B, S_loc, n_q, hd]  this device's query shard
    k: jnp.ndarray,  # [B, S_loc, n_kv, hd] this device's K shard
    v: jnp.ndarray,  # [B, S_loc, n_kv, hd]
    seg: jnp.ndarray | None = None,  # [B, S_loc] per-token segment ids
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = True,
) -> jnp.ndarray:
    """shard_map-local ring attention body.  Sequence shards are contiguous:
    device ``i`` owns global positions [i*S_loc, (i+1)*S_loc).  Returns the
    local attention output [B, S_loc, n_q, hd] in q.dtype; softmax runs in
    float32 (MXU-friendly bf16 inputs, f32 accumulation).

    ``seg`` packs many sequences into one ring pass: tokens attend only
    within their own segment id (and causally, when ``causal``).  The kv-side
    segment shard rotates around the ring with its K/V block, so every step
    masks the held block against the resident queries' ids.  Padding tokens
    carry a sentinel id out of the live range; their rows are garbage and the
    caller never samples them.
    """
    b, sq, n_q, hd = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    scale = 1.0 / (hd**0.5)

    my = lax.axis_index(axis_name)
    q_pos = my * sq + jnp.arange(sq)  # [Sq] global positions of local queries
    qg = q.reshape(b, sq, n_kv, group, hd).astype(jnp.float32)

    # online-softmax state, laid out [B, n_kv, g, Sq(, hd)] like ops.attention
    m = jnp.full((b, n_kv, group, sq), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((b, n_kv, group, sq), dtype=jnp.float32)
    acc = jnp.zeros((b, n_kv, group, sq, hd), dtype=jnp.float32)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    k_blk, v_blk = k, v
    kv_seg_blk = seg  # rotates with its K/V block
    for step in range(axis_size):  # static unroll; axis_size is mesh-known
        owner = (my - step) % axis_size  # whose block we hold this step
        kv_pos = owner * sq + jnp.arange(sq)  # [Sk] global positions

        scores = (
            jnp.einsum("bsngh,btnh->bngst", qg, k_blk.astype(jnp.float32)) * scale
        )  # [B, n_kv, g, Sq, Sk]
        invalid = None  # [B or 1, Sq, Sk]
        if causal:
            invalid = (kv_pos[None, :] > q_pos[:, None])[None]
        if seg is not None:
            cross = seg[:, :, None] != kv_seg_blk[:, None, :]  # [B, Sq, Sk]
            invalid = cross if invalid is None else invalid | cross
        if invalid is not None:
            scores = jnp.where(invalid[:, None, None], NEG_INF, scores)

        new_m = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - new_m)  # rescale of previous accumulation
        p = jnp.exp(scores - new_m[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bngst,btnh->bngsh", p, v_blk.astype(jnp.float32)
        )
        m = new_m

        if step < axis_size - 1:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            if kv_seg_blk is not None:
                kv_seg_blk = lax.ppermute(kv_seg_blk, axis_name, perm)

    # with causal masking alone every query sees at least itself (step 0
    # covers the local diagonal) so l > 0; under segment masking a row can be
    # fully masked (no kv token shares its id), so guard the divide — the
    # where is bit-identical to the plain divide wherever l > 0
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]  # [B, n_kv, g, Sq, hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, n_q, hd)
    return out.astype(q.dtype)


def make_ring_attend(
    mesh: Mesh,
    *,
    num_heads: int,
    num_kv_heads: int,
    axis_name: str = "sp",
    batch_axis: str = "dp",
    head_axis: str = "tp",
    causal: bool = True,
    segmented: bool = False,
):
    """Build ``attend(q, k, v)`` over *global* [B, S, H, hd] arrays: sequence
    sharded over ``sp``, batch over ``dp``, and heads over ``tp`` when tp
    divides both the Q- and KV-head counts (GQA: otherwise heads stay
    replicated inside the ring so local grouping matches global grouping).

    ``segmented=True`` returns ``attend(q, k, v, seg)`` instead, where ``seg``
    is [B, S] per-token segment ids sharded like the sequence: many packed
    sequences share one ring pass, masked to their own segments.
    """
    n = mesh.shape[axis_name]
    tp = mesh.shape.get(head_axis, 1)
    shard_heads = tp > 1 and num_heads % tp == 0 and num_kv_heads % tp == 0
    h_ax = head_axis if shard_heads else None
    b_ax = batch_axis if mesh.shape.get(batch_axis, 1) > 1 else None

    spec = P(b_ax, axis_name, h_ax, None)
    body = partial(ring_attention, axis_name=axis_name, axis_size=n, causal=causal)

    if n == 1 and not segmented:
        # degenerate ring: still honour the head/batch layout, skip ppermute
        from githubrepostorag_tpu.ops.attention import dense_attention

        return lambda q, k, v: dense_attention(q, k, v, causal=causal, q_offset=0)

    if segmented:
        seg_spec = P(b_ax, axis_name)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec, spec, spec, seg_spec),
            out_specs=spec,
            check_vma=False,
        )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
