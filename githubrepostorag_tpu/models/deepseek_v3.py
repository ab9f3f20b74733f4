"""DeepSeek-V3 decoder (arXiv:2412.19437): multi-head latent attention over
a latent page pool, a run of dense SwiGLU layers, then mixture-of-experts
layers with a shared expert, as a share of a larger deployment.

Not a scan of one block: ``first_k_dense`` leading layers are scanned as one
stack (``params["dense"]``), the expert layers as a second
(``params["moe"]``).  Both step programs here keep qwen2's contracts, so the
serving engine drives either model through the same calls:
``forward_paged`` (a prefill chunk against the paged cache) and
``decode_burst`` (N decode steps in one program).  The cache is ONE pool
``[L, 1, P, page_size, 640]``: a row ``[c_kv (512) | k_rope (64) | 64 zeros]``
a token and layer (576 columns used, padded to whole lane tiles) and no V
pool, committed by ``kv_cache.commit_paged`` at a traced layer index and
never sliced.

Attention has two paths over that pool (ops/latent_attention.py): prefill
materialises K and V from the cached latents tile by tile; decode absorbs
``W_uk`` into the query and ``W_uv`` into the output and reads latent rows
only.  Rotary position is YaRN's, on the 64 rope columns alone, rotate-half
(half-split) pairs.

The expert layer is told which experts it holds (``experts_held``, a
contiguous range of ``n_routed_experts``): the router scores all of them,
the layer computes its own (models/moe.dropless_experts) and adds nothing
for the others; the shared expert and attention are whole on every chip.
``vocab_size`` may be a slice of the published vocabulary.  The
multi-token-prediction module is not built: the main model's logits do not
depend on it.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.moe import dropless_experts, route_noaux_tc
from githubrepostorag_tpu.models.quant import _devrand, embedding_lookup
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.ops.latent_attention import (
    einsum_f32,
    latent_decode_attention,
    latent_prefill_attention,
    prefill_tile_counts,
)
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.prefill_width import at_wave_width, layer_weights
from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate, yarn_inv_freq, yarn_mscale
from githubrepostorag_tpu.ops.sampling import (
    first_token_tail,
    sample_tokens_capped,
    sample_tokens_nofilter,
)
from githubrepostorag_tpu.runtime import on_tpu

ACT = jnp.bfloat16  # products take bfloat16 operands; the residual stream is float32


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 163840
    experts_held: tuple = (0, 256)  # [first, past the last) of n_routed_experts

    # what the serving engine and kv_cache.make_page_pools ask of a model: the
    # module whose step programs serve it, one "kv head" whose row is the
    # latent and no V pool, and expert counters from its programs
    step_programs = "githubrepostorag_tpu.models.deepseek_v3"
    latent_kv = True
    expert_counters = True
    # and the most rows one prefill wave carries (the rest ride the next
    # step's wave).  The prefill program is compiled once a row bucket (1, 2,
    # 4, 8, ...), whole, kernel included; a server warms the buckets up to
    # this one, so a ninth prompt arriving in one step waits a wave instead
    # of compiling a program under traffic
    prefill_rows_cap = 8

    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        """Columns of a pool row: the 576 of ``[c_kv | k_rope]`` padded with
        zeros to whole lane tiles (640).  Row-major tiles pad a 576-wide row to
        640 in HBM whatever its logical width; declared 576 wide, the v5e
        compiler instead lays the pool out with the page's slots along the
        lanes, and re-lays all of it out three times a burst for the kernel and
        the commit (PERF.md, Findings, PR 27)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def expert_layers(self) -> int:
        """Layers with an expert layer (what the engine's expert counters span)."""
        return self.num_layers - self.first_k_dense

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV3Config":
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_layers=3, first_k_dense=1, num_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
            num_experts_per_tok=4, n_group=4, topk_group=2, max_position_embeddings=1024,
            rope_original_max=64, experts_held=(0, 16))
        return cls(**{**base, **kw})


# ------------------------------------------------------------------ weights --

def leaf_order(cfg: DeepseekV3Config) -> list:
    """(path, shape, kind) of every leaf the initialiser draws, in draw order:
    each draw advances the salt once.  ``kind`` "w" is a bfloat16 matrix of
    std ~0.02, "bias" the router's selection bias (the same draw in
    float32: at std 0.02 it reorders near neighbours and gives no expert a
    following of its own; at 0.08 the held experts' share of the pairs, and
    with it a run's ``tpot``, swung with the seed: PERF.md, Findings, PR 27).  The benchmark's reference re-states this list."""
    d, h = cfg.hidden_size, cfg.num_heads
    nope, rope, vd, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    ld, lm, n = cfg.first_k_dense, cfg.num_layers - cfg.first_k_dense, cfg.n_held
    ff, ffe = cfg.intermediate_size, cfg.moe_intermediate_size
    ffs = ffe * cfg.n_shared_experts

    def attn(stack, layers):
        return [((stack, "wdq"), (layers, d, cfg.q_lora_rank), "w"),
                ((stack, "wuq"), (layers, cfg.q_lora_rank, h * (nope + rope)), "w"),
                ((stack, "wdkv"), (layers, d, rank + rope), "w"),
                ((stack, "wuk"), (layers, h, nope, rank), "w"),
                ((stack, "wuv"), (layers, h, rank, vd), "w"),
                ((stack, "wo"), (layers, h * vd, d), "w")]

    leaves = [(("embed",), (cfg.vocab_size, d), "w"), (("lm_head",), (d, cfg.vocab_size), "w")]
    if ld:
        leaves += attn("dense", ld) + [(("dense", "wgu"), (ld, d, 2 * ff), "w"),
                                       (("dense", "wd"), (ld, ff, d), "w")]
    if lm:
        leaves += attn("moe", lm) + [
            (("moe", "router"), (lm, d, cfg.n_routed_experts), "w"),
            (("moe", "e_bias"), (lm, cfg.n_routed_experts), "bias"),
            (("moe", "e_wgu"), (lm, n, d, 2 * ffe), "w"),
            (("moe", "e_wd"), (lm, n, ffe, d), "w"),
            (("moe", "s_wgu"), (lm, d, 2 * ffs), "w"),
            (("moe", "s_wd"), (lm, ffs, d), "w")]
    return leaves


@startup.records("startup.weights", settle=True)
def init_params(cfg: DeepseekV3Config, seed: int = 0) -> dict:
    """Weights made on the device from the seed, leaf by leaf, in bfloat16
    (models/quant._devrand: a Knuth-hashed iota, std ~0.02), norms at one.
    The expert stacks hold the ``experts_held`` range only: flat index
    ``(layer * n_held + e) * ...`` of THIS chip's leaf, so another range is
    another draw (the share test remakes slices of one uncut draw itself)."""
    salt = jnp.uint32(seed * 40503 + 12345)
    params: dict = {"norm": jnp.ones((cfg.hidden_size,), jnp.bfloat16)}
    draw = jax.jit(_devrand, static_argnums=(0, 2))
    for path, shape, kind in leaf_order(cfg):
        salt = salt * jnp.uint32(747796405) + jnp.uint32(1)
        leaf = draw(tuple(shape), salt, "bf16")
        if kind == "bias":
            leaf = leaf.astype(jnp.float32)
        node = params
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    for stack, layers in (("dense", cfg.first_k_dense),
                          ("moe", cfg.num_layers - cfg.first_k_dense)):
        if layers:
            params[stack].update(
                ln1=jnp.ones((layers, cfg.hidden_size), jnp.bfloat16),
                ln2=jnp.ones((layers, cfg.hidden_size), jnp.bfloat16),
                q_norm=jnp.ones((layers, cfg.q_lora_rank), jnp.bfloat16),
                kv_norm=jnp.ones((layers, cfg.kv_lora_rank), jnp.bfloat16))
    return params


# ------------------------------------------------------------------- layers --

def _rope_tables(cfg: DeepseekV3Config, positions: jnp.ndarray):
    """cos, sin [B, S, rope] (mscale == mscale_all_dim: scaled by 1)."""
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow)
    cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim, inv_freq=inv)
    scale = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cos * scale, sin * scale


def _mla_project(cfg: DeepseekV3Config, p: dict, x: jnp.ndarray, cos, sin):
    """x [B, S, d] normed -> (q_nope [B,S,H,nope], q_rope [B,S,H,rope] rotated,
    latent [B,S,rank+rope]: the normed c_kv beside the rotated shared k_rope)."""
    b, s, _ = x.shape
    h, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla_q_proj"):
        c_q = rms_norm(x @ p["wdq"], p["q_norm"], cfg.rms_norm_eps)
        q = (c_q @ p["wuq"]).reshape(b, s, h, nope + rope)
        q_nope = q[..., :nope]
        q_rope = rope_rotate(q[..., nope:], cos[:, :, None, :], sin[:, :, None, :])
    with jax.named_scope("mla_kv_proj"):
        ckv = x @ p["wdkv"]
        c_kv = rms_norm(ckv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
        k_rope = rope_rotate(ckv[..., cfg.kv_lora_rank:], cos, sin)
        pad = jnp.zeros((*c_kv.shape[:-1], cfg.head_dim - cfg.kv_lora_rank - rope), c_kv.dtype)
        latent = jnp.concatenate([c_kv, k_rope, pad], axis=-1)
    return q_nope, q_rope, latent


def _swiglu(x, wgu, wd):
    """SwiGLU with the down-projection accumulated and returned in float32:
    it is added to the residual stream unrounded."""
    g, u = jnp.split(x @ wgu, 2, axis=-1)
    return einsum_f32("...f,fd->...d", jax.nn.silu(g) * u, wd)


def _moe_ffn(cfg: DeepseekV3Config, p: dict, experts: dict, li, x: jnp.ndarray, live):
    """x [B, S, d] normed -> (y [B, S, d], [experts hit, pairs to held experts]).
    ``experts`` holds the whole [Lm, n_held, ...] stacks and ``li`` the layer
    among the expert layers: an expert's weights are read where they lie.
    ``live`` [B, S] marks real tokens: padding routes nowhere, so it wakes no
    expert."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.einsum("td,de->te", xf.astype(jnp.float32),
                                           p["router"].astype(jnp.float32)))
        top_i, top_w = route_noaux_tc(scores, p["e_bias"], cfg.num_experts_per_tok, cfg.n_group,
                                      cfg.topk_group, cfg.norm_topk_prob,
                                      cfg.routed_scaling_factor)
        top_i = jnp.where(live.reshape(-1, 1), top_i, -1)

    def expert_ffn(e, rows):
        at = lambda w: jax.lax.dynamic_slice(  # noqa: E731 - one expert of one layer, in place
            w, (li, e, 0, 0), (1, 1, *w.shape[2:]))[0, 0]
        return _swiglu(rows, at(experts["e_wgu"]), at(experts["e_wd"]))

    with jax.named_scope("moe_experts"):
        y, counts = dropless_experts(xf, top_i, top_w, expert_ffn, cfg.n_held,
                                     lo=cfg.experts_held[0])
    with jax.named_scope("moe_shared"):  # no gate on DeepSeek's shared expert
        y = y.reshape(b, s, d) + _swiglu(x, p["s_wgu"], p["s_wd"])
    stats = jnp.stack([(counts > 0).sum(), counts.sum()]).astype(jnp.int32)
    return y, stats


def _split_experts(moe: dict):
    """(the expert layers' scan xs, the routed experts' whole stacks).  As scan
    xs a layer's [n_held, d, 2f] slab would be sliced out whole before the
    dispatch indexes one expert of it."""
    experts = {k: moe[k] for k in ("e_wgu", "e_wd")}
    return {k: v for k, v in moe.items() if k not in experts}, experts


def _layer(cfg, p, h, cos, sin, attend, ffn):
    """Pre-norm block: h + attention, then h + feed-forward.  ``attend(q_nope,
    q_rope, latent) -> ([B, S, H*v], carry)``; ``ffn(x) -> (y float32, stats)``.
    ``h`` is the residual stream, float32: a rounding of it to bfloat16 in
    every layer is noise at the router's input, where a score that crosses
    its neighbour swaps a whole expert (at test widths the logits' error
    against the reference halves; at published widths it is 0.016 either way,
    PERF.md section 4)."""
    x = rms_norm(h, p["ln1"], cfg.rms_norm_eps).astype(ACT)
    attn, carry = attend(*_mla_project(cfg, p, x, cos, sin))
    h = h + einsum_f32("bse,ed->bsd", attn, p["wo"])
    y, stats = ffn(rms_norm(h, p["ln2"], cfg.rms_norm_eps).astype(ACT))
    return h + y, carry, stats


def _run_layers(cfg, params, cols, make_attend, carry, width=None, page_size=0):
    """Both stacks in order.  ``cols`` = (h, cos, sin, live, ...): what runs
    along the chunk, ``live`` [B, S] marking the real tokens, anything after
    it handed on to ``make_attend(p, layer index, carry, ...)``, which builds
    a layer's ``attend``; ``carry`` (the pool, or the staged rows) threads
    through every layer.  ``width`` (the prefill wave's) runs every layer at
    the narrowest rung that holds it (ops/prefill_width.at_wave_width).
    Returns (h, carry, [experts hit, expert tokens])."""
    zero = jnp.zeros((2,), jnp.int32)
    h, *cols = cols

    def run(stack, ffn_of, c, first):
        """One stack's scan; its layers index their own weights where a
        ``width`` puts them in a branch (layer_weights): no xs then."""
        def body(c, p_xs):
            h, li, carry, stats = c

            def layer(cols, carry):
                h, cos, sin, live, *rest = cols
                p = layer_weights(p_xs, stack, li - first)
                h, carry, st = _layer(cfg, p, h, cos, sin, make_attend(p, li, carry, *rest),
                                      ffn_of(p, li - first, live))
                return h, (carry, st)

            h, (carry, st) = at_wave_width(layer, width, page_size, (h, *cols), carry)
            return (h, li + 1, carry, stats + st), None

        n = jax.tree.leaves(stack)[0].shape[0]
        xs, n = (stack, None) if width is None else (None, n)
        return jax.lax.scan(body, c, xs, length=n)[0]

    def dense_ffn(p, li, live):
        return lambda x: (_swiglu(x, p["wgu"], p["wd"]), zero)

    c = (h, jnp.int32(0), carry, zero)
    if cfg.first_k_dense:
        c = run(params["dense"], dense_ffn, c, 0)
    if cfg.num_layers > cfg.first_k_dense:
        scan_moe, experts = _split_experts(params["moe"])

        def moe_ffn(p, li, live):
            return lambda x: _moe_ffn(cfg, p, experts, li, x, live)

        c = run(scan_moe, moe_ffn, c, cfg.first_k_dense)
    h, _, carry, stats = c
    return h, carry, stats


def _head(params, h):
    return einsum_f32("bsd,dv->bsv", h, params["lm_head"])


# ----------------------------------------------------------- step programs --

@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4,))
def forward_paged(
    params: dict,
    cfg: DeepseekV3Config,
    input_ids: jnp.ndarray,  # [B, S] int32, right-padded per row
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    k_pages: jnp.ndarray,  # the latent pool [L, 1, P, page_size, 640] (donated)
    v_pages,  # None: there is no V pool
    slot_mapping: jnp.ndarray,  # [B, S] int32 flat pool slots, -1 for padding
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    cached_lens: jnp.ndarray,  # [B]
    new_lens: jnp.ndarray,  # [B]
    use_pallas: bool = False,
    logits_at: jnp.ndarray | None = None,
    k_scales=None, v_scales=None, int4_kernel: bool = True, mesh=None,
):
    """A prefill chunk over the latent cache, qwen2.forward_paged's contract:
    the chunk's latent rows are committed to the pool, then each row attends
    its cached prefix and itself.  Returns (logits, pool, None, stats [2])."""
    return forward_paged_impl(params, cfg, input_ids, positions, k_pages, slot_mapping,
                              block_tables, cached_lens, new_lens, use_pallas, logits_at)


# the engine's hook for a wave's annotation and counters (cached lens, new lens, width, pages a
# row, page size): the (query tile, key step) pairs the wave's attention kernel runs and skips
wave_attention_tiles = prefill_tile_counts


@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 6))
def forward_paged_wave(
    params: dict,
    cfg: DeepseekV3Config,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,  # the latent pool (donated)
    v_pages,  # None
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
):
    """The engine's prefill wave as one program, qwen2.forward_paged_wave's
    contract: the chunk, every layer at the narrowest width that holds
    ``width`` columns, then the first-token tail every family shares.
    Returns (first_tokens, presence, pool, None, stats [2])."""
    logits, *cache = forward_paged_impl(params, cfg, input_ids, positions, k_pages,
                                        slot_mapping, block_tables, cached_lens, new_lens,
                                        use_pallas, logits_at, width)
    with jax.named_scope("sample"):
        first_tokens, presence = first_token_tail(
            logits[:, 0], presence, first_tokens, input_ids, new_lens, row_idx, done_mask,
            jax.random.fold_in(rng, key_step), temperature, top_p, top_k, repetition_penalty)
    return (first_tokens, presence, *cache)


def forward_paged_impl(params, cfg, input_ids, positions, k_pages, slot_mapping, block_tables,
                       cached_lens, new_lens, use_pallas=False, logits_at=None, width=None):
    """Unjitted body of ``forward_paged``, traced into the wave program too
    (``width``: the wave's, see ops/prefill_width.at_wave_width)."""
    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    h = embedding_lookup(params["embed"], input_ids).astype(jnp.float32)
    cos, sin = _rope_tables(cfg, positions)
    slots = jnp.where(slot_mapping < 0, num_pages * page_size, slot_mapping)  # dropped

    def make_attend(p, li, pool, slots):
        def attend(q_nope, q_rope, latent):
            with jax.named_scope("latent_write"):
                pool2, _ = commit_paged(pool, latent.reshape(1, -1, latent.shape[-1]),
                                        slots.reshape(-1), None, page_size, layer=li)
            with jax.named_scope("latent_prefill_attention"):
                out = latent_prefill_attention(
                    q_nope, q_rope, pool2, li, block_tables, cached_lens, new_lens,
                    p["wuk"], p["wuv"], cfg.softmax_scale, use_pallas=use_pallas,
                    interpret=not on_tpu())
            return out.reshape(*out.shape[:2], -1), pool2
        return attend

    live = jnp.arange(input_ids.shape[1])[None, :] < new_lens[:, None]
    h, k_pages, stats = _run_layers(cfg, params, (h, cos, sin, live, slots), make_attend,
                                    k_pages, width, page_size)
    with jax.named_scope("sample"):
        h = rms_norm(h, params["norm"], cfg.rms_norm_eps).astype(ACT)
        if logits_at is not None:
            h = jnp.take_along_axis(h, logits_at[:, None, None], axis=1)
        logits = _head(params, h)
    return logits, k_pages, None, stats


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "use_pallas", "mesh", "layer_unroll",
                          "filter_sampling"),
         donate_argnums=(4, 6))
def decode_burst(
    params: dict,
    cfg: DeepseekV3Config,
    last_tokens: jnp.ndarray,  # [B]
    seq_lens: jnp.ndarray,  # [B] rows already cached
    k_pages: jnp.ndarray,  # the latent pool (donated)
    v_pages,  # None
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
):
    """``n_steps`` decode iterations in one program, serving/decode_burst.py's
    contract and structure: the pool is loop-invariant inside the burst, new
    latent rows go to a staged buffer [L, B, n_steps, 640] that attention
    reads as a tail, and one scatter commits them at the end.  Returns
    (packed tokens [B, n_steps], valid, pool, None, presence, seq_lens,
    last_tokens, stats [2]: experts hit and pairs routed to held experts,
    summed over layers and steps)."""
    from githubrepostorag_tpu.serving.decode_burst import overlay_fresh
    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    last_tokens, seq_lens, rng = overlay_fresh(
        last_tokens, seq_lens, rng, first_tokens, fresh, fresh_lens, key_step)
    b, L = last_tokens.shape[0], cfg.num_layers
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    rows = jnp.arange(b)
    start_lens = seq_lens  # pool validity is frozen for the whole burst
    # what attention walks: a row that sits the whole burst out (mid-prefill,
    # finished and still in the chained lens, at its limit) has its result
    # thrown away, so it is handed over as holding nothing
    walk_lens = jnp.where(active & (seq_lens < row_limits), start_lens, 0)
    interpret = not on_tpu()
    scale = cfg.softmax_scale

    def one_step(carry, step_xs):
        last, lens, staged, pres, act, stats = carry
        step, step_rng = step_xs
        act = act & (lens < row_limits)
        h = embedding_lookup(params["embed"], jnp.maximum(last, 0)[:, None]).astype(jnp.float32)
        cos, sin = _rope_tables(cfg, lens[:, None])

        def make_attend(p, li, staged):
            def attend(q_nope, q_rope, latent):
                with jax.named_scope("latent_write"):
                    staged2 = jax.lax.dynamic_update_slice(
                        staged, latent[None].astype(staged.dtype), (li, 0, step, 0))
                with jax.named_scope("latent_attention"):
                    # absorbed: W_uk folded into the query, W_uv applied after
                    q_lat = einsum_f32("bhn,hnc->bhc", q_nope[:, 0], p["wuk"])
                    out = latent_decode_attention(
                        (q_lat * scale).astype(latent.dtype),
                        (q_rope[:, 0].astype(jnp.float32) * scale).astype(latent.dtype),
                        k_pages, li, block_tables, walk_lens,
                        jax.lax.dynamic_index_in_dim(staged2, li, 0, keepdims=False),
                        step + 1, use_pallas=use_pallas, interpret=interpret)
                    o = jnp.einsum("bhc,hcv->bhv", out, p["wuv"])
                return o.reshape(b, 1, -1), staged2
            return attend

        h, staged, st = _run_layers(cfg, params, (h, cos, sin, act[:, None]), make_attend,
                                    staged)
        with jax.named_scope("sample"):
            logits = _head(params, rms_norm(h, params["norm"], cfg.rms_norm_eps).astype(ACT))
            if filter_sampling:
                toks = sample_tokens_capped(logits[:, 0], step_rng, temperature, top_p, top_k,
                                            repetition_penalty, pres)
            else:
                toks = sample_tokens_nofilter(logits[:, 0], step_rng, temperature,
                                              repetition_penalty, pres)
        toks = jnp.where(act, toks, last)
        pres = pres.at[rows, toks].max(act)
        lens = lens + act.astype(jnp.int32)
        return (toks, lens, staged, pres, act, stats + st), (toks, act)

    staged0 = jnp.zeros((L, b, n_steps, k_pages.shape[-1]), k_pages.dtype)
    carry0 = (last_tokens, seq_lens, staged0, presence, active, jnp.zeros((2,), jnp.int32))
    (last, out_lens, staged, presence, _, stats), (toks, valid) = jax.lax.scan(
        one_step, carry0, (jnp.arange(n_steps), jax.random.split(rng, n_steps)))
    toks, valid = toks.T, valid.T
    packed = jnp.where(valid, toks, -1)

    pos = start_lens[:, None] + jnp.arange(n_steps)[None, :]
    page_idx = jnp.clip(pos // page_size, 0, block_tables.shape[1] - 1)
    slots = jnp.take_along_axis(block_tables, page_idx, axis=1) * page_size + pos % page_size
    slots = jnp.where(valid, slots, num_pages * page_size).reshape(-1)  # sentinel: dropped
    with jax.named_scope("latent_write"):
        k_pages, _ = commit_paged(k_pages, staged.reshape(L, 1, b * n_steps, -1), slots, None,
                                  page_size)
    return packed, valid, k_pages, None, presence, out_lens, last, stats
