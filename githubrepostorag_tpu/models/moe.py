"""Sparse Mixture-of-Experts layers: routers, and one dropless dispatch
that is told which experts it holds.

Dispatch (``dropless_experts``): the (token, expert) pairs routed to an
expert held here are sorted by expert and cut into tiles of at most ``tile``
rows, each tile of one expert; a scan visits the tiles that exist and a
``lax.cond`` skips the rest, so an expert that received no token streams no
weights, no token is ever dropped, and nothing of shape [T, E, C] is built.
Pairs routed to experts that are not held (``lo <= id < lo + n`` fails) add
nothing: they are another chip's share, and the exchange that would bring
their result here is not built (one chip runs without it).  The scan has a
static length (the most tiles the shapes allow), so the layer differentiates
and shards like any other; with the expert axis sharded P("ep", ...) GSPMD
resolves the per-tile expert index itself.

Which form serves which regime (``listed`` and ``walk``; values of the caller's
shapes, not switches a deployment sets):

* FEW LARGE experts, some hit (16 held of 100 MB each, ~6 hit: Command A+;
  16 of 88 MB: DeepSeek-V3): the scan.  A tile's bookkeeping is nothing beside
  88-100 MB of weights, and the scan differentiates.
* MANY SMALL experts, a third hit (128 held of 6 MB each, ~30 hit:
  Qwen3-Next; 128 of 12 MB: Ling; 32 of 20 MB: Nemotron-H), or EVERY expert
  held and nearly all hit (64 of 64 at 12 MB each: Mellum2, PR 54), the rows
  fitting ONE tile (a decode burst's 32 row slots, a one-row wave of 128
  columns): an expert that was hit runs on all the rows and is weighted by
  its column of the dense [T, held] weights.  On the chip the hit experts'
  weights come through one Pallas call whose own DMAs bring the next block in
  while this one's products run (``walk``, ops/pallas_experts.py: 8.3 us a
  6 MB expert on a v5e where a loop of XLA products took 13.8, PERF.md,
  PR 58); the family hands its expert form to it as a ``body``.  Off the chip
  ``walk`` is None and the loop of XLA products over the hit experts runs: the
  CPU's path and the kernel's oracle (ROADMAP D26).
* The same families' waves of more than one tile of rows (256-1,024 columns):
  the listed form, whose loop runs the tiles that exist and no others, each
  tile's expert, rows and weights listed once before it (PERF.md, PR 34 and
  PR 54; a kernel for it needs rows gathered by expert: ROADMAP S21 (a)).

Routers: ``moe_mlp`` is HF ``Qwen2MoeSparseMoeBlock`` (float32 softmax,
top-k, optional renormalisation, a shared expert behind a sigmoid gate);
``route_noaux_tc`` is DeepSeek-V3's (sigmoid scores, a selection-only bias,
group-limited top-k, weights from the unbiased scores).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.quant import qmatmul


def route_noaux_tc(scores: jnp.ndarray, bias: jnp.ndarray, top_k: int, n_group: int,
                   topk_group: int, norm_topk_prob: bool = True,
                   routed_scaling_factor: float = 1.0):
    """DeepSeek-V3's ``noaux_tc`` selection.  ``scores`` [T, E] float32 are
    the sigmoid affinities, ``bias`` [E] the ``e_score_correction_bias`` that
    enters the choice and not the weight.  A group's score is the sum of its
    two largest biased scores; the best ``topk_group`` groups stay, and the
    ``top_k`` largest biased scores inside them are chosen; with every group
    kept (``n_group`` 1, Nemotron-H's) there is no limit to build and the
    choice is the plain biased top k.  Returns (ids [T, K] int32, weights
    [T, K] float32: the unbiased scores of the chosen, normalised to sum 1 and
    scaled)."""
    t, e = scores.shape
    masked = scores + bias[None, :].astype(scores.dtype)
    if topk_group < n_group:
        grouped = masked.reshape(t, n_group, e // n_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)  # [T, G]
        _, best = jax.lax.top_k(group_score, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[jnp.arange(t)[:, None], best].set(True)
        masked = jnp.where(jnp.repeat(keep, e // n_group, axis=1), masked, -jnp.inf)
    _, ids = jax.lax.top_k(masked, top_k)
    w = jnp.take_along_axis(scores, ids, axis=1)
    if norm_topk_prob:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * routed_scaling_factor


EXPERT_TILE = 128  # rows of one expert a dispatch tile holds, at most


def dropless_experts(x: jnp.ndarray, top_i: jnp.ndarray, top_w: jnp.ndarray, expert_ffn,
                     n_held: int, lo=0, listed: bool = False, walk=None):
    """Sum over the held experts of w * E(x) for the pairs routed to them.

    ``x`` [T, d]; ``top_i`` [T, K] expert ids over ALL experts; ``top_w``
    [T, K] float32; ``expert_ffn(e, rows [M, d]) -> [M, d]`` runs held expert
    ``e`` (0-based among the held, a traced int32); the held are the ids
    ``lo .. lo + n_held - 1``.  Returns (y [T, d] float32, counts [n_held]
    int32: pairs each held expert received).

    ``listed``: the form for MANY SMALL experts (128 held of 6 MB each:
    models/qwen3_next.py).  There the scan's own bookkeeping, a dozen scalar
    ops a tile and a skipped ``cond`` for each of the ~90 tiles that do not
    exist, costs more than the experts' bytes (PERF.md, Findings, PR 34).
    Every tile's expert, rows and weights are listed once, vectorised, before
    the loop; the loop runs the tiles that exist and no others (a dynamic trip
    count: serving only, it does not differentiate in reverse).

    ``walk(x, w_dense [T, n_held] float32, first_hit [n_held] int32, n_hit) ->
    y [T, d] float32`` (ops/pallas_experts.experts_walk): where the rows fit
    one tile, the hit experts (``first_hit[:n_hit]``) as one kernel over the
    family's expert stacks, and ``expert_ffn`` is not called.  None (off the
    chip): the ``listed`` form's loop over the hit experts calls it."""
    t, d = x.shape
    k = top_i.shape[1]
    m = min(EXPERT_TILE, -(-t // 8) * 8)  # rows a tile holds
    held = (top_i >= lo) & (top_i < lo + n_held)
    e = jnp.where(held, top_i - lo, n_held).reshape(-1)  # [T*K]; n_held = not here
    counts = (e[:, None] == jnp.arange(n_held)[None, :]).sum(axis=0).astype(jnp.int32)
    if (listed or walk is not None) and t <= m:
        # the rows fit one tile: an expert that was hit runs on ALL of them (a
        # tile is m rows whatever it holds) and its result is weighted by that
        # expert's column of the dense [T, held] weights, zero where a row did
        # not choose it: no sort, no gather of rows, no scatter-add a tile; on
        # the chip the hit experts' weights as one stream (ops/pallas_experts.py)
        w_dense = jnp.zeros((t, n_held + 1), jnp.float32).at[
            jnp.arange(t)[:, None], e.reshape(t, k)].add(top_w.astype(jnp.float32))
        first_hit = jnp.argsort(counts == 0, stable=True).astype(jnp.int32)  # the hit ones first
        n_hit = (counts > 0).sum()
        if walk is not None:
            return walk(x, w_dense[:, :n_held], first_hit, n_hit), counts
        w_cols = w_dense.T[first_hit]  # [n_held, T].  The loop: the CPU's path and the walk's oracle

        def one(i, y):
            yt = expert_ffn(first_hit[i], x).astype(jnp.float32)
            return y + yt * jax.lax.dynamic_index_in_dim(w_cols, i, keepdims=False)[:, None]

        return jax.lax.fori_loop(0, n_hit, one, jnp.zeros((t, d), jnp.float32)), counts
    order = jnp.argsort(e, stable=True)  # pairs by expert, the absent last
    tiles_of = (counts + m - 1) // m
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    group_start = jnp.cumsum(counts) - counts  # an expert's first pair in sorted order
    # ceil(c_e / m) summed is at most floor(pairs / m) + n_held, and an expert
    # receives a token at most once
    n_tiles = min(t * k // m + n_held, n_held * -(-t // m))
    flat_w = top_w.reshape(-1).astype(jnp.float32)

    if listed:
        tiles = jnp.arange(n_tiles)
        ex = jnp.minimum(jnp.searchsorted(tile_end, tiles, side="right"), n_held - 1)
        off = (tiles - tile_start[ex]) * m
        idx = (group_start[ex] + off)[:, None] + jnp.arange(m)[None, :]
        valid = jnp.arange(m)[None, :] < (counts[ex] - off)[:, None]
        pair = order[jnp.clip(idx, 0, t * k - 1)]
        tok, w = pair // k, jnp.where(valid, flat_w[pair], 0.0)  # [n_tiles, m]
        ex = ex.astype(jnp.int32)

        def tile(i, y):
            rows = jax.lax.dynamic_index_in_dim(tok, i, keepdims=False)
            yt = expert_ffn(ex[i], x[rows]).astype(jnp.float32)
            return y.at[rows].add(yt * jax.lax.dynamic_index_in_dim(w, i, keepdims=False)[:, None])

        y = jax.lax.fori_loop(0, tile_end[-1], tile, jnp.zeros((t, d), jnp.float32))
        return y, counts

    def run(i, y):
        ex = jnp.minimum(jnp.searchsorted(tile_end, i, side="right"), n_held - 1)
        off = (i - tile_start[ex]) * m
        idx = group_start[ex] + off + jnp.arange(m)
        valid = jnp.arange(m) < counts[ex] - off
        pair = order[jnp.clip(idx, 0, t * k - 1)]
        tok = pair // k
        w = jnp.where(valid, flat_w[pair], 0.0)
        yt = expert_ffn(ex.astype(jnp.int32), x[tok]).astype(jnp.float32)
        return y.at[tok].add(yt * w[:, None])  # rows past the group add zero

    def body(y, i):
        return jax.lax.cond(i < tile_end[-1], lambda y: run(i, y), lambda y: y, y), None

    y, _ = jax.lax.scan(body, jnp.zeros((t, d), jnp.float32), jnp.arange(n_tiles))
    return y, counts


def _expert(w, e):
    """Expert ``e`` of a stacked [E, ...] weight, plain or quantized."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, keepdims=False), w)


def moe_mlp(cfg, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Sparse MoE MLP over normed hidden states ``x`` [B, S, d].

    ``p`` keys: ``router`` [d, E]; ``e_wg``/``e_wu`` [held, d, ff_e],
    ``e_wd`` [held, ff_e, d] (the experts held here: all E unless
    ``cfg.experts_held`` names a range); ``s_wg``/``s_wu`` [d, ff_s], ``s_wd`` [ff_s, d];
    ``s_gate`` [d, 1].
    """
    b, s, d = x.shape
    xf = x.reshape(b * s, d)

    with jax.named_scope("moe_route"):  # float32 softmax over experts, top-k (HF parity)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            top_p = top_p / jnp.maximum(top_p.sum(axis=-1, keepdims=True), 1e-20)

    def expert_ffn(e, rows):
        h = jax.nn.silu(qmatmul(rows, _expert(p["e_wg"], e))) * qmatmul(rows, _expert(p["e_wu"], e))
        return qmatmul(h, _expert(p["e_wd"], e))

    # the layer is told which experts it holds (``cfg.experts_held``: a
    # contiguous range of ``num_experts``, all of them where the configuration
    # does not say); the router above scored every one
    lo, hi = getattr(cfg, "experts_held", None) or (0, cfg.num_experts)
    with jax.named_scope("moe_experts"):
        y, _ = dropless_experts(xf, top_i, top_p, expert_ffn, hi - lo, lo=lo)

    with jax.named_scope("moe_shared"):  # always on, behind a sigmoid gate
        sh = jax.nn.silu(qmatmul(xf, p["s_wg"])) * qmatmul(xf, p["s_wu"])
        sh = qmatmul(sh, p["s_wd"]) * jax.nn.sigmoid(qmatmul(xf, p["s_gate"]))
    return (y.astype(x.dtype) + sh).reshape(b, s, d)


def init_moe_layer_params(cfg, key: jax.Array, dtype=jnp.float32) -> dict:
    """Random init of ONE stack of MoE-MLP layer params ([L, ...] leaves),
    merged into the attention params by qwen2.init_params."""
    L, d = cfg.num_layers, cfg.hidden_size
    E, ffe, ffs = cfg.num_experts, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    ks = jax.random.split(key, 8)
    norm = lambda k, *shape: (
        jax.random.normal(k, shape, dtype=jnp.float32) * 0.02
    ).astype(dtype)
    return {
        "router": norm(ks[0], L, d, E),
        "e_wg": norm(ks[1], L, E, d, ffe),
        "e_wu": norm(ks[2], L, E, d, ffe),
        "e_wd": norm(ks[3], L, E, ffe, d),
        "s_wg": norm(ks[4], L, d, ffs),
        "s_wu": norm(ks[5], L, d, ffs),
        "s_wd": norm(ks[6], L, ffs, d),
        "s_gate": norm(ks[7], L, d, 1),
    }


# EP sharding lives with every other layout decision in
# parallel/sharding.py::qwen2_param_specs (expert axes P(None, "ep", ...)),
# so Engine(mesh=...) and init_train_state shard MoE trees the same way
# they shard dense ones.
