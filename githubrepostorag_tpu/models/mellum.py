"""Mellum decoder (``model_type`` ``mellum``: Mellum2-12B-A2.5B): periods of
sliding-window layers closed by one global layer, BOTH kinds rotary from
tables of their own, every layer a sequential pre-norm block that ends in a
mixture of softmax-routed experts with no shared one.

A layer, on the residual stream ``h`` (float32):

    x = RMSNorm(h)
    q, k, v = W_q x, W_k x, W_v x          32 query / 4 kv heads of 128, no bias
    q, k = RMSNorm over each head's dims, learned gain
    q, k rotated (rotate-half, the whole head) by the layer kind's cos and sin
    h = h + W_o attn(q, k, v)
    y = RMSNorm(h)
    p = softmax_f32(W_r y);  top 8;  w = p_top / sum p_top
    h = h + sum_{e in top 8} w_e E_e(y)    SwiGLU of width 896, no shared expert

A SLIDING layer's table is the plain ``rope_theta`` ladder and its query at
position i sees the keys ``0 <= i - j < sliding_window``; a GLOBAL layer's is
YaRN's frequencies (ops/rope.yarn_inv_freq) with cos and sin times the
source's ``attention_factor``, plain causal: the keys its pool stores carry
the factor.  Both tables are made once a program (a wave) or once a step (a
burst) and a layer takes its kind's (``_KINDS`` below).  The head is a matrix
of its own (``lm_head``), after a last RMSNorm.

Two kinds of page (``page_kinds``), pools, tables and step-program contracts
as models/cohere2_moe.py has them (serving/kv_cache.SlidingPages; the paged
kernels told a window, ops/fused_decode.py, or a first key a row,
ops/pallas_paged.py, under ``sliding_prefill_attention`` / ``sliding_attention``;
the global layers' calls under ``paged_attention``).

The expert layer is told which experts it holds (``experts_held``, a range of
``num_experts``; ALL of them in the benchmark's cell): the router scores every
one, the layer computes its own (models/moe.dropless_experts, the listed form:
with every expert held nearly every one is hit, and what is left to save is the
bookkeeping) and adds nothing for the others.  Beside [experts hit, pairs] the
programs count the fullest held expert's pairs, summed over layers and steps:
with nothing skipped it is the stragglers that set a wave's tiles.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.hybrid import at as _at, draw_leaves, swiglu as _swiglu
from githubrepostorag_tpu.models.moe import dropless_experts
from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.ops.latent_attention import einsum_f32
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.pallas_experts import SWIGLU, experts_walk
from githubrepostorag_tpu.ops.prefill_width import at_wave_width
from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate, yarn_inv_freq
from githubrepostorag_tpu.ops.sampling import (
    first_token_tail,
    sample_tokens_capped,
    sample_tokens_nofilter,
)
from githubrepostorag_tpu.runtime import on_tpu

ACT = jnp.bfloat16  # products take bfloat16 operands; the residual stream is float32
SLIDING, GLOBAL = "sliding", "global"
# what a layer of each kind is given: (the scope of its burst kernel, of its
# wave kernel).  Window and rotary table are the configuration's.
_KINDS = {SLIDING: ("sliding_attention", "sliding_prefill_attention"),
          GLOBAL: ("paged_attention", "paged_attention")}
# columns of a prefill chunk one call of the wave's kernel takes: 8 query heads
# of 128 a kv head, so 256 columns are the 2,048 query rows a call that
# models/cohere2_moe.py's 128 columns of 16 heads are (8.4 MB of VMEM beside
# tables of 208 pages).  512 columns compile for a v5e too (PR 54), but most
# waves of a session's tail are 256 columns or fewer and skip the second call
ATTN_SPAN = 256


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896  # one expert's width
    num_layers: int = 28
    period: tuple = (SLIDING, SLIDING, SLIDING, GLOBAL)  # ``layer_types``, one period of it
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    num_experts: int = 64  # the router's width: every expert it scores
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0  # both kinds' base
    # the global layers' YaRN (``rope_parameters.full_attention``)
    yarn_factor: float = 16.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782
    max_position_embeddings: int = 131072
    experts_held: tuple = (0, 64)  # [first, past the last) of num_experts

    # what the serving engine asks of a model: the module whose step programs
    # serve it, expert counters from them, the most rows one prefill wave
    # carries, and (``page_kinds`` below) its kinds of page
    step_programs = "githubrepostorag_tpu.models.mellum"
    expert_counters = True
    prefill_rows_cap = 8

    def __post_init__(self):
        if self.num_layers % len(self.period) or set(self.period) - {SLIDING, GLOBAL}:
            raise ValueError("num_layers must hold whole periods of sliding / global layers")

    @property
    def periods(self) -> int:
        return self.num_layers // len(self.period)

    @property
    def kv_layers(self) -> int:
        """Layers of the GLOBAL kind: what ``kv_cache.make_page_pools`` sizes
        the engine's own pools by."""
        return self.periods * self.period.count(GLOBAL)

    @property
    def sliding_layers(self) -> int:
        return self.num_layers - self.kv_layers

    @property
    def page_kinds(self) -> tuple:
        """(name, layers, window or None): serving/kv_cache.page_kinds."""
        return ((GLOBAL, self.kv_layers, None),
                (SLIDING, self.sliding_layers, self.sliding_window))

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def expert_layers(self) -> int:
        return self.num_layers

    @classmethod
    def tiny(cls, **kw) -> "MellumConfig":
        """Test widths that keep what is new: two periods, 4 query heads a kv
        head, a window of a few pages, YaRN past a short original context."""
        base = dict(
            vocab_size=512, hidden_size=64, moe_intermediate_size=32, num_layers=8,
            num_heads=8, num_kv_heads=2, head_dim=16, sliding_window=48, num_experts=16,
            num_experts_per_tok=4, yarn_factor=8.0, yarn_original_max=64,
            max_position_embeddings=512, experts_held=(0, 16))
        return cls(**{**base, **kw})


# ------------------------------------------------------------------ weights --

ROUTER_GAIN = 2.0  # the router's draw, times this: logits of std ~1.9 on a normed input
QK_NORM_GAIN = 16.0  # the per-head norms' gains are 1 + a draw times this: 0.45 .. 1.55


def leaf_order(cfg: MellumConfig) -> list:
    """(path, shape, gain) of every leaf the initialiser draws, in draw order
    (models/hybrid.draw_leaves: a bfloat16 draw of std ~0.02 times ``gain``,
    the salt advancing once a leaf).  The benchmark's reference re-states
    this list."""
    d, L, h, nkv, hd = (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                        cfg.head_dim)
    ff, n = cfg.moe_intermediate_size, cfg.n_held
    return [
        (("embed",), (cfg.vocab_size, d), 1.0),
        (("lm_head",), (d, cfg.vocab_size), 1.0),
        (("layers", "wq"), (L, d, h * hd), 1.0),
        (("layers", "wk"), (L, d, nkv * hd), 1.0),
        (("layers", "wv"), (L, d, nkv * hd), 1.0),
        (("layers", "wo"), (L, h * hd, d), 1.0),
        (("layers", "q_norm"), (L, hd), QK_NORM_GAIN),
        (("layers", "k_norm"), (L, hd), QK_NORM_GAIN),
        (("layers", "router"), (L, d, cfg.num_experts), ROUTER_GAIN),
        (("layers", "e_wgu"), (L, n, d, 2 * ff), 1.0),
        (("layers", "e_wd"), (L, n, ff, d), 1.0),
    ]


@startup.records("startup.weights", settle=True)
def init_params(cfg: MellumConfig, seed: int = 0) -> dict:
    """Weights made on the device from the seed, leaf by leaf, in bfloat16
    (models/quant._devrand), the block norms at one.  The per-head norms'
    gains are ``1 + draw`` (a trained checkpoint's are not all one, and at one
    the norm would hardly move a head whose draw already has unit size).  The
    expert stacks hold the ``experts_held`` range only.  ``wq | wk | wv`` are
    laid side by side as the one product the attention branch runs."""
    params = draw_leaves(leaf_order(cfg), seed)
    layers = params["layers"]
    layers["wqkv"] = jnp.concatenate([layers.pop("wq"), layers.pop("wk"), layers.pop("wv")],
                                     axis=-1)
    for name in ("q_norm", "k_norm"):
        layers[name] = (1.0 + layers[name].astype(jnp.float32)).astype(jnp.bfloat16)
    ones = lambda *shape: jnp.ones(shape, jnp.bfloat16)  # noqa: E731
    layers["ln1"], layers["ln2"] = ones(cfg.num_layers, cfg.hidden_size), ones(
        cfg.num_layers, cfg.hidden_size)
    params["norm"] = ones(cfg.hidden_size)
    return params


# ------------------------------------------------------------------- layers --

def _norm(cfg, x, w):
    return rms_norm(x, w.astype(jnp.float32), cfg.rms_norm_eps).astype(ACT)


def rope_tables(cfg: MellumConfig, positions: jnp.ndarray) -> dict:
    """kind -> (cos, sin) [B, S, hd] float32 at ``positions`` [B, S]: the
    plain ladder for the sliding layers; YaRN's frequencies, both tables times
    ``attention_factor``, for the global ones."""
    hd = cfg.head_dim
    yarn = yarn_inv_freq(hd, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_max,
                         cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    with jax.named_scope("rope"):
        return {SLIDING: rope_cos_sin(positions, hd, cfg.rope_theta),
                GLOBAL: rope_cos_sin(positions, hd, inv_freq=yarn, factor=cfg.attention_factor)}


def _project(cfg, p, x, cos, sin):
    """x [B, S, d] normed -> (q [B, S, H, hd]; k, v [B, S, n_kv, hd]): no bias;
    q and k normed over each head's dims (float32) and rotated by the layer
    kind's ``cos`` / ``sin`` [B, S, hd]."""
    b, s, _ = x.shape
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        qkv = x @ p["wqkv"]
        q = qkv[..., :h * hd].reshape(b, s, h, hd)
        k = qkv[..., h * hd:(h + nkv) * hd].reshape(b, s, nkv, hd)
        v = qkv[..., (h + nkv) * hd:].reshape(b, s, nkv, hd)
    with jax.named_scope("qk_norm"):
        q = rms_norm(q.astype(jnp.float32), p["q_norm"].astype(jnp.float32), cfg.rms_norm_eps)
        k = rms_norm(k.astype(jnp.float32), p["k_norm"].astype(jnp.float32), cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        return rope_rotate(q, cos, sin).astype(ACT), rope_rotate(k, cos, sin).astype(ACT), v


def _moe_ffn(cfg, p, experts: dict, li, x: jnp.ndarray, live):
    """x [B, S, d] normed -> (f [B, S, d] float32, [experts hit, pairs to held
    experts, the fullest held expert's pairs]).  A float32 softmax over all
    the router's logits, top k, renormalised.  ``experts`` holds the whole
    [L, n_held, ...] stacks and ``li`` the layer.  ``live`` [B, S] marks real
    tokens: padding wakes no expert."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe_route"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            top_w = top_w / jnp.maximum(top_w.sum(axis=-1, keepdims=True), 1e-20)
        top_i = jnp.where(live.reshape(-1, 1), top_i, -1)

    def expert_ffn(e, rows):
        at = lambda w: jax.lax.dynamic_slice(  # noqa: E731 - one expert of one layer, in place
            w, (li, e, 0, 0), (1, 1, *w.shape[2:]))[0, 0]
        return _swiglu(rows, at(experts["e_wgu"]), at(experts["e_wd"]))

    with jax.named_scope("moe_experts"):
        y, counts = dropless_experts(
            xf, top_i, top_w, expert_ffn, cfg.n_held, lo=cfg.experts_held[0], listed=True,
            walk=experts_walk(SWIGLU, (experts["e_wgu"], experts["e_wd"]), li, burst=s == 1))
    stats = jnp.stack([(counts > 0).sum(), counts.sum(), counts.max()]).astype(jnp.int32)
    return y.reshape(b, s, d), stats


def _split(params: dict):
    """(the small leaves, as stacked; the routed experts' whole stacks): an
    expert's weights are read where they lie (models/deepseek_v3._split_experts)."""
    experts = {k: params["layers"][k] for k in ("e_wgu", "e_wd")}
    return {k: v for k, v in params["layers"].items() if k not in experts}, experts


def _block_rest(cfg, p, experts, li, h, attn, live):
    """The block after its attention: the output projection onto the stream,
    then the experts on the stream's second norm."""
    with jax.named_scope("attn_proj"):
        h = h + einsum_f32("bse,ed->bsd", attn.reshape(*attn.shape[:2], -1), p["wo"])
    f, st = _moe_ffn(cfg, p, experts, li, _norm(cfg, h, p["ln2"]), live)
    return h + f, st


def _layer_ids(cfg, rep):
    """(kind, the layer among all, among its kind) of period ``rep``'s layers
    (``rep`` static in the burst, traced in the wave)."""
    n = len(cfg.period)
    seen = {SLIDING: 0, GLOBAL: 0}
    for j, kind in enumerate(cfg.period):
        yield kind, rep * n + j, rep * cfg.period.count(kind) + seen[kind]
        seen[kind] += 1


def _head(cfg, params, h):
    return einsum_f32("bsd,dv->bsv", _norm(cfg, h, params["norm"]), params["lm_head"])


@partial(jax.jit, static_argnames=("cfg",))
def forward(params: dict, cfg: MellumConfig, input_ids: jnp.ndarray) -> jnp.ndarray:
    """The un-paged forward: input_ids [B, S] whole sequences from position 0
    -> logits [B, S, V] float32.  No cache and no kernel; what the engine's
    tests hold the paged programs' greedy tokens to."""
    from githubrepostorag_tpu.ops.attention import dense_attention

    b, s = input_ids.shape
    h = embedding_lookup(params["embed"], input_ids).astype(jnp.float32)
    rope = rope_tables(cfg, jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s)))
    small, experts = _split(params)
    live = jnp.ones((b, s), bool)
    for rep in range(cfg.periods):
        for kind, li, _ in _layer_ids(cfg, rep):
            p = _at(small, li)
            q, k, v = _project(cfg, p, _norm(cfg, h, p["ln1"]), *rope[kind])
            attn = dense_attention(q, k, v, sliding=cfg.sliding_window if kind == SLIDING else None)
            h, _ = _block_rest(cfg, p, experts, li, h, attn, live)
    return _head(cfg, params, h)


# ----------------------------------------------------------- step programs --

N_COUNTS = 3  # [experts hit, pairs to held experts, the fullest held expert's pairs]


def forward_paged_impl(params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping,
                       block_tables, cached_lens, new_lens, sliding_k, sliding_v, sliding_slots,
                       sliding_tables, use_pallas=False, logits_at=None, width=None):
    """A prefill chunk over both kinds of page, traced into the wave program
    too (``width``: the wave's, ops/prefill_width.at_wave_width).  A layer's
    keys and values go into its kind's pool at its index among that kind, then
    the chunk attends them: a sliding layer through ITS table, inside its
    window.  The pools never enter a switch (models/hybrid.wave): a layer's
    first switch ends at q, k, v, its second takes the rest of the block.
    Returns (logits, k_pages, v_pages, counts [3], sliding_k, sliding_v)."""
    from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref
    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    page_size = k_pages.shape[3]
    nkv, hd, chunk = cfg.num_kv_heads, cfg.head_dim, input_ids.shape[1]
    h = embedding_lookup(params["embed"], input_ids).astype(jnp.float32)
    live = jnp.arange(chunk)[None, :] < new_lens[:, None]
    small, experts = _split(params)
    rope = rope_tables(cfg, positions)
    dropped = lambda slots, pool: jnp.where(slots < 0, pool.shape[2] * page_size, slots)  # noqa: E731
    tables = {GLOBAL: (dropped(slot_mapping, k_pages), block_tables, None),
              SLIDING: (dropped(sliding_slots, sliding_k), sliding_tables, cfg.sliding_window)}

    def padded(x):  # a rung's columns back to the chunk's: every rung returns the same shapes
        return jnp.pad(x, ((0, 0), (0, chunk - x.shape[1])) + ((0, 0),) * (x.ndim - 2))

    def attend(kind, n, q, k, v, pools):
        kp, vp = pools
        slots, table, window = tables[kind]
        span = min(ATTN_SPAN, chunk)
        with jax.named_scope("kv_write"):
            flat, run = slots.reshape(-1), slots.shape[1]  # a row's columns: consecutive positions
            kp, _ = commit_paged(kp, k.reshape(-1, nkv, hd).swapaxes(0, 1), flat, None,
                                 page_size, layer=n, run=run)
            vp, _ = commit_paged(vp, v.reshape(-1, nkv, hd).swapaxes(0, 1), flat, None,
                                 page_size, layer=n, run=run)
        with jax.named_scope(_KINDS[kind][1]):
            if use_pallas:
                from githubrepostorag_tpu.ops.fused_decode import fused_paged_attention

                # a chunk goes through ``span`` columns a call (the keys of the whole
                # chunk are committed: a later call attends the earlier columns as
                # cache); a call past the wave's width is skipped
                def window_of(c):
                    qw = q[:, c:c + span]
                    run = lambda: fused_paged_attention(  # noqa: E731
                        qw, kp, vp, table, cached_lens + jnp.minimum(new_lens, c),
                        jnp.clip(new_lens - c, 0, span), layer=n, sliding=window,
                        bf16_products=True)
                    if c == 0 or width is None:
                        return run()
                    return jax.lax.cond(width > c, run, lambda: jnp.zeros_like(qw))

                attn = jnp.concatenate([window_of(c) for c in range(0, chunk, span)], axis=1)
            else:
                attn = paged_attention_ref(q, kp[n], vp[n], table, cached_lens, new_lens,
                                           sliding=window)
        return (kp, vp), attn

    def layer(kind, li, n, h, pools, stats):
        def project(cols, _):
            h, cos, sin = cols
            p = _at(small, li)
            q, k, v = _project(cfg, p, _norm(cfg, h, p["ln1"]), cos, sin)
            return h, tuple(padded(t) for t in (q, k, v))

        _, (q, k, v) = at_wave_width(project, width, page_size, (h, *rope[kind]), ())
        pools, attn = attend(kind, n, q, k, v, pools)

        def rest(cols, _):
            h, live, attn = cols
            return _block_rest(cfg, _at(small, li), experts, li, h, attn, live)

        h, st = at_wave_width(rest, width, page_size, (h, live, attn), ())
        return h, pools, stats + st

    def body(carry, _):
        h, rep, pools, stats = carry
        pools = dict(pools)
        for kind, li, n in _layer_ids(cfg, rep):
            h, pools[kind], stats = layer(kind, li, n, h, pools[kind], stats)
        return (h, rep + 1, pools, stats), None

    pools = {GLOBAL: (k_pages, v_pages), SLIDING: (sliding_k, sliding_v)}
    (h, _, pools, stats), _ = jax.lax.scan(
        body, (h, jnp.int32(0), pools, jnp.zeros((N_COUNTS,), jnp.int32)), None,
        length=cfg.periods)
    with jax.named_scope("sample"):
        if logits_at is not None:
            h = jnp.take_along_axis(h, logits_at[:, None, None], axis=1)
        logits = _head(cfg, params, h)
    return (logits, *pools[GLOBAL], stats, *pools[SLIDING])


@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5), donate_argnames=("sliding_k", "sliding_v"))
def forward_paged(
    params: dict,
    cfg: MellumConfig,
    input_ids: jnp.ndarray,  # [B, S] int32, right-padded per row
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    k_pages: jnp.ndarray,  # [global layers, n_kv, P, page_size, hd] (donated)
    v_pages: jnp.ndarray,  # (donated)
    slot_mapping: jnp.ndarray,  # [B, S] int32 flat slots of the global pool, -1 for padding
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    cached_lens: jnp.ndarray,  # [B]
    new_lens: jnp.ndarray,  # [B]
    use_pallas: bool = False,
    logits_at: jnp.ndarray | None = None,
    k_scales=None, v_scales=None, int4_kernel: bool = True, mesh=None,
    *, sliding_k: jnp.ndarray, sliding_v: jnp.ndarray,  # [sliding layers, n_kv, P_s, ...] (donated)
    sliding_slots: jnp.ndarray, sliding_tables: jnp.ndarray,
):
    """A prefill chunk, qwen2.forward_paged's contract with the sliding kind's
    pools, slots and tables beside the global kind's.  Returns (logits,
    k_pages, v_pages, the expert layers' counts [3], sliding_k, sliding_v)."""
    return forward_paged_impl(
        params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
        cached_lens, new_lens, sliding_k, sliding_v, sliding_slots, sliding_tables, use_pallas,
        logits_at)


@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5, 6), donate_argnames=("sliding_k", "sliding_v"))
def forward_paged_wave(
    params: dict,
    cfg: MellumConfig,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,  # (donated)
    v_pages: jnp.ndarray,  # (donated)
    presence: jnp.ndarray,  # [rows, V] bool (donated)
    first_tokens: jnp.ndarray,  # [rows] int32
    slot_mapping: jnp.ndarray,
    block_tables: jnp.ndarray,
    cached_lens: jnp.ndarray,
    new_lens: jnp.ndarray,
    logits_at: jnp.ndarray,
    row_idx: jnp.ndarray,
    done_mask: jnp.ndarray,
    width: jnp.ndarray,
    rng: jax.Array,
    key_step: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
    use_pallas: bool = False,
    k_scales=None, v_scales=None, int4_kernel: bool = True, mesh=None,
    *, sliding_k: jnp.ndarray, sliding_v: jnp.ndarray,
    sliding_slots: jnp.ndarray, sliding_tables: jnp.ndarray,
):
    """The engine's prefill wave as one program, qwen2.forward_paged_wave's
    contract: the chunk, every layer at the narrowest width that holds
    ``width`` columns, then the first-token tail every family shares.
    Returns (first_tokens, presence, k_pages, v_pages, counts [3], sliding_k,
    sliding_v)."""
    logits, *cache = forward_paged_impl(
        params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
        cached_lens, new_lens, sliding_k, sliding_v, sliding_slots, sliding_tables, use_pallas,
        logits_at, width)
    with jax.named_scope("sample"):
        first_tokens, presence = first_token_tail(
            logits[:, 0], presence, first_tokens, input_ids, new_lens, row_idx, done_mask,
            jax.random.fold_in(rng, key_step), temperature, top_p, top_k, repetition_penalty)
    return (first_tokens, presence, *cache)


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "use_pallas", "mesh", "layer_unroll",
                          "filter_sampling"),
         donate_argnums=(4, 5, 6), donate_argnames=("sliding_k", "sliding_v"))
def decode_burst(
    params: dict,
    cfg: MellumConfig,
    last_tokens: jnp.ndarray,  # [B]
    seq_lens: jnp.ndarray,  # [B] rows already cached
    k_pages: jnp.ndarray,  # (donated)
    v_pages: jnp.ndarray,  # (donated)
    presence: jnp.ndarray,  # [B, V] bool (donated)
    active: jnp.ndarray,
    row_limits: jnp.ndarray,
    block_tables: jnp.ndarray,
    rng: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
    n_steps: int,
    use_pallas: bool = False,
    mesh=None,
    layer_unroll: int = 1,
    filter_sampling: bool = True,
    k_scales=None, v_scales=None,
    *, first_tokens, fresh, fresh_lens, key_step,
    sliding_k: jnp.ndarray, sliding_v: jnp.ndarray, sliding_tables: jnp.ndarray,
):
    """``n_steps`` decode iterations in one program, serving/decode_burst.py's
    contract and structure: both kinds' pools are loop-invariant inside the
    burst (new keys and values go to staged buffers the kernel reads as a
    tail, one commit a pool lays them into their pages at the end, a row's
    steps as one run).  A sliding layer's walk begins at the first key of the
    row's window, which moves a key a step.  Returns (packed tokens [B,
    n_steps], valid, k_pages, v_pages, presence, seq_lens, last_tokens, counts
    [3], sliding_k, sliding_v)."""
    from githubrepostorag_tpu.ops.attention import dense_attention
    from githubrepostorag_tpu.ops.paged_attention import gather_kv
    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged
    from githubrepostorag_tpu.serving.decode_burst import overlay_fresh
    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    last_tokens, seq_lens, rng = overlay_fresh(
        last_tokens, seq_lens, rng, first_tokens, fresh, fresh_lens, key_step)
    b, nkv, hd = last_tokens.shape[0], cfg.num_kv_heads, cfg.head_dim
    page_size = k_pages.shape[3]
    rows = jnp.arange(b)
    start_lens = seq_lens  # pool validity is frozen for the whole burst
    walk_lens = jnp.where(active & (seq_lens < row_limits), start_lens, 0)
    interpret = not on_tpu()
    small, experts = _split(params)
    pools = {GLOBAL: (k_pages, v_pages, block_tables), SLIDING: (sliding_k, sliding_v,
                                                                 sliding_tables)}

    def one_step(carry, step_xs):
        last, lens, staged, pres, act, stats = carry
        staged = dict(staged)
        step, step_rng = step_xs
        act = act & (lens < row_limits)
        h = embedding_lookup(params["embed"], jnp.maximum(last, 0)[:, None]).astype(jnp.float32)
        rope = rope_tables(cfg, lens[:, None])
        # the first key a sliding layer's query at position ``lens`` sees
        first_key = jnp.maximum(lens - (cfg.sliding_window - 1), 0)

        def attend(kind, n, q, k, v, staged):
            sk, sv = staged
            kp, vp, table = pools[kind]
            with jax.named_scope("kv_write"):
                sk = jax.lax.dynamic_update_slice(
                    sk, k.swapaxes(1, 2).astype(sk.dtype)[None], (n, 0, 0, step, 0))
                sv = jax.lax.dynamic_update_slice(
                    sv, v.swapaxes(1, 2).astype(sv.dtype)[None], (n, 0, 0, step, 0))
            sk_l, sv_l = sk[n], sv[n]
            # under the scope: the kernel's instruction is named for it in the
            # device trace, where the metrics look for it
            with jax.named_scope(_KINDS[kind][0]):
                if use_pallas:
                    attn = paged_attention_decode_staged(
                        q, kp, vp, table, walk_lens, sk_l, sv_l, jnp.reshape(step + 1, (1,)),
                        jnp.reshape(jnp.int32(n), (1,)), interpret=interpret,
                        pool_starts=first_key if kind == SLIDING else None, bf16_products=True)
                else:
                    pool_k, pool_v = gather_kv(kp[n], vp[n], table)
                    at = jnp.arange(pool_k.shape[1])[None, :]
                    in_pool = at < start_lens[:, None]
                    if kind == SLIDING:
                        in_pool = in_pool & (at >= first_key[:, None])
                    valid = jnp.concatenate(
                        [in_pool, jnp.broadcast_to((jnp.arange(n_steps) <= step)[None, :],
                                                   (b, n_steps))], axis=1)
                    attn = dense_attention(
                        q, jnp.concatenate([pool_k, sk_l.swapaxes(1, 2)], axis=1),
                        jnp.concatenate([pool_v, sv_l.swapaxes(1, 2)], axis=1),
                        causal=False, kv_valid=valid)
            return attn, (sk, sv)

        # the layers are unrolled, not scanned: with a layer's index static its
        # weights are views of the stacks
        live = act[:, None]
        for rep in range(cfg.periods):
            for kind, li, n in _layer_ids(cfg, rep):
                p = _at(small, li)
                q, k, v = _project(cfg, p, _norm(cfg, h, p["ln1"]), *rope[kind])
                attn, staged[kind] = attend(kind, n, q, k, v, staged[kind])
                h, st = _block_rest(cfg, p, experts, li, h, attn, live)
                stats = stats + st
        with jax.named_scope("sample"):
            logits = _head(cfg, params, h)
            if filter_sampling:
                toks = sample_tokens_capped(logits[:, 0], step_rng, temperature, top_p, top_k,
                                            repetition_penalty, pres)
            else:
                toks = sample_tokens_nofilter(logits[:, 0], step_rng, temperature,
                                              repetition_penalty, pres)
        toks = jnp.where(act, toks, last)
        pres = pres.at[rows, toks].max(act)
        lens = lens + act.astype(jnp.int32)
        return (toks, lens, staged, pres, act, stats), (toks, act)

    staged0 = {kind: tuple(jnp.zeros((pool[0].shape[0], b, nkv, n_steps, hd), pool[0].dtype)
                           for _ in range(2)) for kind, pool in pools.items()}
    carry0 = (last_tokens, seq_lens, staged0, presence, active,
              jnp.zeros((N_COUNTS,), jnp.int32))
    (last, out_lens, staged, presence, _, stats), (toks, valid) = jax.lax.scan(
        one_step, carry0, (jnp.arange(n_steps), jax.random.split(rng, n_steps)))
    toks, valid = toks.T, valid.T
    packed = jnp.where(valid, toks, -1)

    pos = start_lens[:, None] + jnp.arange(n_steps)[None, :]
    out = {}
    for kind, (kp, vp, table) in pools.items():
        page_idx = jnp.clip(pos // page_size, 0, table.shape[1] - 1)
        slots = jnp.take_along_axis(table, page_idx, axis=1) * page_size + pos % page_size
        slots = jnp.where(valid, slots, kp.shape[2] * page_size).reshape(-1)  # sentinel: dropped
        with jax.named_scope("kv_write"):
            commit = lambda pool, st: commit_paged(  # noqa: E731
                pool, st.swapaxes(1, 2).reshape(pool.shape[0], nkv, b * n_steps, hd), slots, None,
                page_size, run=n_steps)[0]  # a row's steps are consecutive positions
            out[kind] = (commit(kp, staged[kind][0]), commit(vp, staged[kind][1]))
    return (packed, valid, *out[GLOBAL], presence, out_lens, last, stats, *out[SLIDING])
