"""Pipeline-parallel training over the ``pp`` mesh axis.

Completes the parallel fabric: dp/tp/sp are annotation-driven
(training/step.py), while pipelining needs an explicit schedule — this is
the idiomatic JAX form of it.  The decoder's scanned layer stack
[L, ...] splits into ``pp`` contiguous stages ([pp, L/pp, ...], leading
axis sharded over the mesh); a GPipe schedule runs inside ONE
``shard_map``-ped, jit-compiled, *differentiable* program:

  - the batch splits into M microbatches; the schedule runs M + pp - 1
    ticks of ``lax.scan``;
  - every tick, each stage runs its local layers on the activation it
    holds, then ``lax.ppermute`` hands the result one hop down the ring —
    stage transfers ride ICI exactly like ring attention's K/V blocks;
  - stage 0 ingests microbatch ``t`` at tick ``t``; the last stage
    projects logits and accumulates the masked cross-entropy of microbatch
    ``t - (pp-1)`` (a ``lax.cond`` skips the vocab projection on every
    other stage/tick, so fill/drain bubbles cost layer-compute only);
  - backward is plain ``jax.grad`` through the scan: ``ppermute``
    transposes to the reverse rotation, giving the reverse-schedule
    automatically; ``jax.checkpoint`` around each stage keeps one stage's
    activations per in-flight microbatch.

The reference has nothing to mirror (single GPU — SURVEY.md §2.3 lists
PP as "No"); SURVEY required the mesh to be designed so PP can slot in,
and this is the slot filled.  Pipeline-parallelism composes with dp for
the batch dim AND tp inside each stage (Megatron column/row weight shards
with explicit ``lax.psum`` after the row-parallel products — annotations
don't propagate into shard_map bodies, so the tp collectives are written
out; see ``pp_layer_specs``).  sp-in-stage is future work.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from githubrepostorag_tpu.models.qwen2 import (
    Qwen2Config,
    _block,
    _logits,
)
from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.ops.attention import dense_attention
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.rope import rope_cos_sin


def pp_layer_specs(tp: int):
    """PartitionSpecs for the [pp, L/pp, ...]-staged layer dict.  tp==1:
    one prefix spec (stage axis only).  tp>1: Megatron column/row shards —
    wq/wk/wv/wg/wu (+ qkv biases) on their output axis, wo/wd on their
    input axis — the shard_map-side mirror of
    parallel/sharding.py::qwen2_param_specs."""
    if tp <= 1:
        return P("pp")
    col_lin = P("pp", None, None, "tp")
    col_bias = P("pp", None, "tp")
    row_lin = P("pp", None, "tp", None)
    return {
        "ln1": P("pp"), "ln2": P("pp"),
        "wq": col_lin, "bq": col_bias,
        "wk": col_lin, "bk": col_bias,
        "wv": col_lin, "bv": col_bias,
        "wo": row_lin,
        "wg": col_lin, "wu": col_lin,
        "wd": row_lin,
    }


def split_layers_for_pp(params: dict, pp: int) -> dict:
    """[L, ...]-stacked layer params -> [pp, L/pp, ...] stages (leading axis
    is the one shard_map shards over pp).  Non-layer params pass through."""
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    if L % pp:
        raise ValueError(f"num_layers={L} must divide by pp={pp}")
    staged = jax.tree.map(
        lambda x: x.reshape(pp, L // pp, *x.shape[1:]), params["layers"]
    )
    return {**params, "layers": staged}


def merge_layers_from_pp(params: dict) -> dict:
    """Inverse of split_layers_for_pp (for checkpointing / eval reuse)."""
    merged = jax.tree.map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
        params["layers"],
    )
    return {**params, "layers": merged}


def make_pp_train_step(
    cfg: Qwen2Config,
    mesh: Mesh,
    optimizer: optax.GradientTransformation | None = None,
    *,
    num_microbatches: int = 2,
    remat: bool = True,
) -> tuple[Callable, optax.GradientTransformation]:
    """Build ``step(params, opt_state, batch) -> (params, opt_state, loss)``
    with the layer stack pipelined over the mesh's ``pp`` axis.

    ``params`` carry pp-SPLIT layers (see split_layers_for_pp).  ``batch``
    is the usual dict of int32 [B, S] ``input_ids``/``targets``/``mask``
    with B divisible by num_microbatches (and by mesh dp).
    """
    optimizer = optimizer or optax.adamw(1e-4)
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    tp = mesh.shape.get("tp", 1)
    M = num_microbatches
    if pp < 2:
        raise ValueError("make_pp_train_step needs a pp>=2 mesh axis")
    if mesh.shape.get("sp", 1) != 1:
        raise ValueError("pp step composes with dp and tp (got sp>1)")
    if tp > 1:
        if cfg.num_experts > 0:
            raise ValueError("tp-in-stage does not cover MoE layers")
        if cfg.num_heads % tp or cfg.num_kv_heads % tp or cfg.intermediate_size % tp:
            raise ValueError(
                f"tp={tp} must divide num_heads={cfg.num_heads}, "
                f"num_kv_heads={cfg.num_kv_heads}, and "
                f"intermediate_size={cfg.intermediate_size}"
            )
    import dataclasses

    # inside the shard_map body each tp member holds 1/tp of the heads and
    # the MLP width; _block reshapes by these LOCAL counts
    cfg_local = dataclasses.replace(
        cfg, num_heads=cfg.num_heads // tp, num_kv_heads=cfg.num_kv_heads // tp
    ) if tp > 1 else cfg

    n_ticks = M + pp - 1
    mb_spec = P(None, "dp") if dp > 1 else P()  # [M, B/M, S]: batch over dp

    def pp_loss(layers_local, embed, norm, lm_head, ids, targets, mask):
        """shard_map body.  layers_local: [1, L/pp, ...] this stage's slice
        (weights additionally 1/tp-sharded column/row-wise when tp>1);
        ids/targets/mask: [M, mb, S] microbatches (replicated over pp/tp)."""
        layers_local = jax.tree.map(lambda x: x[0], layers_local)  # [L/pp,...]
        p_idx = lax.axis_index("pp")
        last = pp - 1
        mb, S = ids.shape[1], ids.shape[2]
        head = {"embed": embed, "norm": norm}
        if lm_head is not None:
            head["lm_head"] = lm_head

        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        attend = lambda q, k, v: (
            dense_attention(q, k, v, causal=True, q_offset=0), None
        )
        # Megatron TP inside the stage: column shards compute local heads /
        # MLP width, the row-parallel products psum back to replicated
        reduce = (lambda x: lax.psum(x, "tp")) if tp > 1 else None

        def run_stage(x):
            def layer_body(h, xs):
                (pl,) = xs
                h, _ = _block(cfg_local, h, pl, cos, sin, attend, reduce=reduce)
                return h, None

            if remat:
                layer_body = jax.checkpoint(layer_body)
            h, _ = lax.scan(layer_body, x, (layers_local,))
            return h

        def tick(carry, t):
            buf, loss_sum, tok_sum = carry
            # stage 0 ingests microbatch t (clamped; post-M garbage drains
            # past the loss window and is never scored)
            ids_t = ids[jnp.clip(t, 0, M - 1)]
            x0 = embedding_lookup(embed, ids_t, dtype=buf.dtype)
            x_in = jnp.where(p_idx == 0, x0, buf)
            y = run_stage(x_in)

            # the last stage just finished microbatch t-(pp-1)
            done = t - last
            is_done = (p_idx == last) & (done >= 0) & (done < M)
            d_idx = jnp.clip(done, 0, M - 1)

            def score(y):
                h = rms_norm(y, norm, cfg.rms_norm_eps)
                logits = _logits(head, h)  # [mb, S, V] f32
                losses = optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets[d_idx]
                )
                msk = mask[d_idx].astype(jnp.float32)
                return (losses * msk).sum(), msk.sum()

            l, n = lax.cond(is_done, score, lambda y: (0.0, 0.0), y)

            buf_next = lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            return (buf_next, loss_sum + l, tok_sum + n), None

        buf0 = jnp.zeros((mb, S, cfg.hidden_size), dtype=embed.dtype)
        (_, loss_sum, tok_sum), _ = lax.scan(
            tick, (buf0, 0.0, 0.0), jnp.arange(n_ticks)
        )
        loss_sum = lax.psum(loss_sum, "pp")
        tok_sum = lax.psum(tok_sum, "pp")
        if dp > 1:
            loss_sum = lax.psum(loss_sum, "dp")
            tok_sum = lax.psum(tok_sum, "dp")
        return loss_sum / jnp.maximum(tok_sum, 1.0)

    # layers: leading (stage) axis over pp, plus Megatron column/row tp
    # shards when tp>1; head params replicated; microbatches replicated
    # over pp/tp, batch-dim over dp
    shard_body = jax.shard_map(
        pp_loss,
        mesh=mesh,
        in_specs=(pp_layer_specs(tp), P(), P(), P(), mb_spec, mb_spec, mb_spec),
        out_specs=P(),
        check_vma=False,
    )

    def loss_fn(params, batch):
        b, S = batch["input_ids"].shape
        if b % M:
            raise ValueError(f"batch {b} must divide by num_microbatches {M}")
        if (b // M) % dp:
            raise ValueError(
                f"microbatch size {b // M} (batch {b} / {M} microbatches) "
                f"must divide by mesh dp={dp}"
            )
        to_mb = lambda x: x.reshape(M, b // M, S)
        return shard_body(
            params["layers"],
            params["embed"],
            params["norm"],
            params.get("lm_head"),
            to_mb(batch["input_ids"]),
            to_mb(batch["targets"]),
            to_mb(batch["mask"]),
        )

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step, optimizer


def init_pp_train_state(
    cfg: Qwen2Config,
    mesh: Mesh,
    key: jax.Array,
    optimizer: optax.GradientTransformation,
    dtype=jnp.float32,
):
    """Random-init params pp-split onto the mesh (stage axis over pp, head
    replicated) with an opt state inheriting the shardings."""
    from jax.sharding import NamedSharding

    from githubrepostorag_tpu.models.qwen2 import init_params
    from githubrepostorag_tpu.training.step import TrainState

    pp = mesh.shape["pp"]
    tp = mesh.shape.get("tp", 1)
    params = split_layers_for_pp(init_params(cfg, key, dtype=dtype), pp)
    specs = pp_layer_specs(tp)
    replicated = NamedSharding(mesh, P())

    def place_layers(layers: dict) -> dict:
        if isinstance(specs, P):  # tp==1: one prefix spec for every leaf
            return jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(mesh, specs)), layers
            )
        return {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in layers.items()
        }

    params = {
        k: place_layers(v) if k == "layers"
        else jax.tree.map(lambda x: jax.device_put(x, replicated), v)
        for k, v in params.items()
    }
    opt_state = jax.jit(optimizer.init)(params)
    return TrainState(params=params, opt_state=opt_state)
