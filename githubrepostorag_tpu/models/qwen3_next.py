"""Qwen3-Next decoder (Qwen3NextForCausalLM, Qwen/Qwen3-Next-80B-A3B): a
hybrid of Gated DeltaNet (linear-attention) layers and gated full-attention
layers, every layer followed by a sparse mixture of many small experts with
a shared expert behind a sigmoid gate, as a share of a larger deployment.

One PERIOD is ``full_attention_interval - 1`` Gated DeltaNet layers, then one
full-attention layer; the prefill wave is a scan over periods, the burst
unrolls them.  Two kinds of
per-sequence memory ride that scan as carries, never sliced:

* K/V page pools ``[periods, n_kv, P, page_size, head_dim]`` for the
  full-attention layers alone (``kv_layers``), committed by
  ``kv_cache.commit_paged`` at a traced layer index, read by the paged
  kernels qwen2 uses;
* a STATE pool for the Gated DeltaNet layers: ``s`` ``[gdn layers, slots,
  Hv, dk, dv]`` float32 (the recurrence's matrix) and ``conv`` ``[gdn layers,
  slots, (taps - 1) * channels]`` bfloat16 (the convolution's history).  A
  slot is one sequence's state in every layer.  The engine's rows own the
  first ``max_num_seqs`` slots (row r = slot r), snapshot slots follow, and
  the last slot takes the writes of rows that have nothing to write.

Both step programs keep qwen2's contracts (``forward_paged`` /
``forward_paged_wave`` / ``decode_burst``) and add the state beside the
pools: a wave is told, a row, which slot its state comes from (``-1``: a
fresh sequence, zeros; its own slot: the next chunk of a prompt; a snapshot
slot: a prefix hit resumes there), which slot takes the state after the
chunk, and which slot takes a SNAPSHOT of the state after ``snap_col`` of
the chunk's tokens (a page boundary; ops/gated_delta.py catches it between
two blocks of the chunked form).  All of it is device copies inside the
program.  The burst steps rows 0 .. B-1 in place; a row that sits a step
out keeps its state and history bit for bit.

The expert layer is told which experts it holds (``experts_held``, a
contiguous range of ``num_experts``): the router scores all of them, the
layer computes its own (models/moe.dropless_experts) and adds nothing for
the others.  ``vocab_size`` may be a slice of the published vocabulary.  The
multi-token-prediction module is not built: the main model's logits do not
depend on it.  The residual stream is float32, as DeepSeek-V3's, for the
router's sake.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.moe import dropless_experts
from githubrepostorag_tpu.models.quant import _devrand, embedding_lookup
from githubrepostorag_tpu.ops.gated_delta import (
    BLOCK,
    causal_conv,
    causal_conv_step,
    gated_delta_chunked,
    gated_delta_step,
    l2norm,
    mask_padding,
)
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.ops.latent_attention import einsum_f32
from githubrepostorag_tpu.ops.norms import rms_norm_gated, rms_norm_zero_centered
from githubrepostorag_tpu.ops.prefill_width import at_wave_width
from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate_leading
from githubrepostorag_tpu.ops.sampling import (
    first_token_tail,
    sample_tokens_capped,
    sample_tokens_nofilter,
)
from githubrepostorag_tpu.runtime import on_tpu

ACT = jnp.bfloat16  # products take bfloat16 operands; the residual stream is float32
ATTN_WINDOW = 256  # columns of a prefill chunk one call of the attention kernel takes


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512  # the router's width: every expert it scores
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    experts_held: tuple = (0, 512)  # [first, past the last) of num_experts
    state_dtype: str = "float32"  # the recurrence's matrix as the pool keeps it

    # what the serving engine asks of a model: per-sequence state beside the
    # pages (serving/kv_cache.StateSlots), no dense lead-in for the expert
    # counters, and the most rows one prefill wave carries (see
    # DeepseekV3Config.prefill_rows_cap)
    recurrent_state = True
    first_k_dense = 0
    prefill_rows_cap = 8

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def gdn_per_period(self) -> int:
        return self.full_attention_interval - 1

    @property
    def kv_layers(self) -> int:
        """Layers that page keys and values: one a period."""
        return self.periods

    @property
    def gdn_layers(self) -> int:
        return self.periods * self.gdn_per_period

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        """The convolution runs over [q | k | v] of the linear heads."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def state_shapes(self) -> dict:
        """One slot of one Gated DeltaNet layer: (shape, dtype) by name."""
        return {
            "s": ((self.linear_num_value_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim), jnp.dtype(self.state_dtype)),
            # the history's taps side by side in one row: [taps - 1, channels] would
            # put 3 on a tiled axis, and the v5e compiler then copies the pool
            # into another layout and back around every burst
            "conv": (((self.linear_conv_kernel_dim - 1) * self.conv_channels,), jnp.dtype(ACT)),
        }

    @classmethod
    def tiny(cls, **kw) -> "Qwen3NextConfig":
        base = dict(
            vocab_size=512, hidden_size=64, num_layers=8, num_heads=4, num_kv_heads=2,
            head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, max_position_embeddings=1024,
            experts_held=(0, 16))
        return cls(**{**base, **kw})


def make_state_pools(cfg: Qwen3NextConfig, slots: int) -> dict:
    """Zeroed state pools of ``slots`` slots (the engine adds the slot that
    takes dropped writes itself)."""
    return {name: jnp.zeros((cfg.gdn_layers, slots, *shape), dtype)
            for name, (shape, dtype) in cfg.state_shapes().items()}


# ------------------------------------------------------------------ weights --

ROUTER_GAIN = 4.0  # the router's draw, times this: logits of std ~3.6
CONV_GAIN = 16.0  # the convolution's taps, times this: std ~0.32


def leaf_order(cfg: Qwen3NextConfig) -> list:
    """(path, shape, gain) of every leaf the initialiser draws, in draw order:
    each draw advances the salt once.  A draw is a bfloat16 leaf of std ~0.02
    times ``gain`` (a power of two: exact).  The router's gain spreads its
    logits as a trained router's are spread: at std 0.9 the 10th and 11th of
    512 near-flat scores lie 0.04 apart and swap between bfloat16 and
    float32 in a token of every few; at 3.6 they lie 0.15 apart.  The
    benchmark's reference re-states this list."""
    d, L, P, G = cfg.hidden_size, cfg.num_layers, cfg.periods, cfg.gdn_layers
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ffe, ffs, n = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size, cfg.n_held
    return [
        (("embed",), (cfg.vocab_size, d), 1.0),
        (("lm_head",), (d, cfg.vocab_size), 1.0),
        (("gdn", "w_qkvz"), (G, d, 2 * hk * dk + 2 * hv * dv), 1.0),
        (("gdn", "w_ba"), (G, d, 2 * hv), 1.0),
        (("gdn", "conv_w"), (G, cfg.conv_channels, cfg.linear_conv_kernel_dim), CONV_GAIN),
        (("gdn", "w_out"), (G, hv * dv, d), 1.0),
        (("attn", "wq"), (P, d, h * 2 * hd), 1.0),
        (("attn", "wk"), (P, d, nkv * hd), 1.0),
        (("attn", "wv"), (P, d, nkv * hd), 1.0),
        (("attn", "wo"), (P, h * hd, d), 1.0),
        (("moe", "router"), (L, d, cfg.num_experts), ROUTER_GAIN),
        (("moe", "e_wgu"), (L, n, d, 2 * ffe), 1.0),
        (("moe", "e_wd"), (L, n, ffe, d), 1.0),
        (("moe", "s_wgu"), (L, d, 2 * ffs), 1.0),
        (("moe", "s_wd"), (L, ffs, d), 1.0),
        (("moe", "s_gate"), (L, d, 1), 1.0),
    ]


def decay_ladder(cfg: Qwen3NextConfig) -> jnp.ndarray:
    """``A_log`` [Hv]: ``A`` from 0.001 to 1 in equal ratios over the heads, so
    a token's decay ``exp(-A softplus(a + 1))`` runs from 0.999 (a head that
    remembers thousands of tokens) to 0.27.  The published initialiser draws
    ``A`` from U(0, 16): nearly every head would forget within a token, and
    the state, the snapshots and their precision would decide nothing."""
    hv = cfg.linear_num_value_heads
    return jnp.linspace(math.log(1e-3), 0.0, hv, dtype=jnp.float32)


def _by_kind(cfg, w_qkvz, w_ba):
    """The Gated DeltaNet projections' columns from the published order,
    grouped by key head (``q | k | v (r value heads) | z (r)`` and ``b (r) |
    a (r)``, a key head after another), into the order ``_gdn_inputs`` reads:
    ``q | k | v | z`` and ``b | a``, each of every head, the value heads in
    ``_gdn_heads``' order (a key head's r side by side).  Read as published,
    the product's columns are regrouped after it, and the v5e compiler folds
    that into the weight: it transposed the 302 MB stack once a burst and a
    wave and wrote all six layers of it out again every step (PERF.md,
    Findings, PR 35)."""
    hk, r = cfg.linear_num_key_heads, cfg.linear_num_value_heads // cfg.linear_num_key_heads
    dk, rdv = cfg.linear_key_head_dim, r * cfg.linear_value_head_dim

    def regroup(w, widths):
        heads = w.reshape(*w.shape[:-1], hk, sum(widths))
        kinds = jnp.split(heads, [sum(widths[:i]) for i in range(1, len(widths))], axis=-1)
        return jnp.concatenate([k.reshape(*w.shape[:-1], -1) for k in kinds], axis=-1)

    return regroup(w_qkvz, (dk, dk, rdv, rdv)), regroup(w_ba, (r, r))


@startup.records("startup.weights", settle=True)
def init_params(cfg: Qwen3NextConfig, seed: int = 0) -> dict:
    """Weights made on the device from the seed, leaf by leaf, in bfloat16
    (models/quant._devrand), as DeepSeek-V3's are.  Zero-centred norms at
    zero (a scale of one), the Gated DeltaNet output norm at one, ``dt_bias``
    at one, ``A_log`` the ``decay_ladder``.  The expert stacks hold the
    ``experts_held`` range only.  ``wq | wk | wv`` are then laid side by side
    as the one product the attention layer runs, and the Gated DeltaNet
    projections' columns, drawn in the published order, are put once into
    the order their products are read in (``_by_kind``)."""
    salt = jnp.uint32(seed * 40503 + 12345)
    params: dict = {"norm": jnp.zeros((cfg.hidden_size,), jnp.bfloat16)}
    draw = jax.jit(_devrand, static_argnums=(0, 2))
    for path, shape, gain in leaf_order(cfg):
        salt = salt * jnp.uint32(747796405) + jnp.uint32(1)
        leaf = draw(tuple(shape), salt, "bf16")
        if gain != 1.0:
            leaf = (leaf.astype(jnp.float32) * gain).astype(jnp.bfloat16)
        node = params
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    attn = params["attn"]
    attn["wqkv"] = jnp.concatenate([attn.pop("wq"), attn.pop("wk"), attn.pop("wv")], axis=-1)
    gdn = params["gdn"]
    gdn["w_qkvz"], gdn["w_ba"] = jax.jit(partial(_by_kind, cfg))(gdn["w_qkvz"], gdn["w_ba"])
    L, P, G = cfg.num_layers, cfg.periods, cfg.gdn_layers
    attn.update(q_norm=jnp.zeros((P, cfg.head_dim), jnp.bfloat16),
                k_norm=jnp.zeros((P, cfg.head_dim), jnp.bfloat16))
    gdn.update(
        A_log=jnp.tile(decay_ladder(cfg)[None], (G, 1)),
        dt_bias=jnp.ones((G, cfg.linear_num_value_heads), jnp.float32),
        o_norm=jnp.ones((G, cfg.linear_value_head_dim), jnp.bfloat16))
    params["moe"].update(ln1=jnp.zeros((L, cfg.hidden_size), jnp.bfloat16),
                         ln2=jnp.zeros((L, cfg.hidden_size), jnp.bfloat16))
    return params


# ------------------------------------------------------------------- layers --

def _norm(cfg, x, w):
    return rms_norm_zero_centered(x, w, cfg.rms_norm_eps).astype(ACT)


def _swiglu(x, wgu, wd):
    """SwiGLU with the down-projection accumulated and returned in float32."""
    g, u = jnp.split(x @ wgu, 2, axis=-1)
    return einsum_f32("...f,fd->...d", jax.nn.silu(g) * u, wd)


def _gdn_inputs(cfg, p, x):
    """x [B, S, d] normed -> (the convolution's input [B, S, C]: q | k | v of
    the linear heads, in bfloat16 as the history keeps it; z [B, S, Hv, dv];
    beta, g [B, S, Hv]).  The projections' columns lie as they are read
    (``_by_kind``): ``q | k | v | z`` and ``b | a``.

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
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    return mixed.astype(ACT), z.reshape(b, s, hv, dv), beta, g


def _gdn_heads(cfg, y):
    """The convolution's output [B, S, C] float32 -> (q, k [B, S, Hv, dk]
    L2-normalised, q scaled; v [B, S, Hv, dv]); a key head serves r value heads."""
    b, s, _ = y.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    q = l2norm(y[..., :hk * dk].reshape(b, s, hk, dk)) * dk ** -0.5
    k = l2norm(y[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk))
    v = y[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    return jnp.repeat(q, hv // hk, axis=2), jnp.repeat(k, hv // hk, axis=2), v


def _gdn_out(cfg, p, o, z):
    with jax.named_scope("gdn_gate_norm"):
        y = rms_norm_gated(o, z, p["o_norm"], cfg.rms_norm_eps)
    return einsum_f32("bse,ed->bsd", y.reshape(*y.shape[:2], -1).astype(ACT), p["w_out"])


def _attn_project(cfg, p, x, cos, sin):
    """x [B, S, d] normed -> (q [B, S, H, hd] normed and rotated, its gate
    [B, S, H * hd], k and v [B, S, n_kv, hd])."""
    b, s, _ = x.shape
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = x @ p["wqkv"]
    qg = qkv[..., :h * 2 * hd].reshape(b, s, h, 2 * hd)
    k = qkv[..., h * 2 * hd:h * 2 * hd + nkv * hd].reshape(b, s, nkv, hd)
    v = qkv[..., h * 2 * hd + nkv * hd:].reshape(b, s, nkv, hd)
    q = rms_norm_zero_centered(qg[..., :hd], p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm_zero_centered(k, p["k_norm"], cfg.rms_norm_eps)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return (rope_rotate_leading(q, cos, sin), qg[..., hd:].reshape(b, s, h * hd),
            rope_rotate_leading(k, cos, sin), v)


def _attn_out(p, attn, gate):
    with jax.named_scope("attn_gate"):
        attn = attn.reshape(*attn.shape[:2], -1) * jax.nn.sigmoid(
            gate.astype(jnp.float32)).astype(attn.dtype)
    return einsum_f32("bse,ed->bsd", attn, p["wo"])


def _moe_ffn(cfg, p, experts: dict, li, x: jnp.ndarray, live):
    """x [B, S, d] normed -> (y [B, S, d] float32, [experts hit, pairs to held
    experts]).  HF ``Qwen3NextSparseMoeBlock``: float32 softmax over all the
    router's logits, top k, renormalised; the held experts' part through
    ``dropless_experts`` (``experts`` holds the whole [L, n_held, ...] stacks:
    an expert's weights are read where they lie); the shared expert behind
    its sigmoid gate.  ``live`` [B, S] marks real tokens: padding wakes no
    expert."""
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
        y, counts = dropless_experts(xf, top_i, top_w, expert_ffn, cfg.n_held,
                                     lo=cfg.experts_held[0], listed=True)
    with jax.named_scope("moe_shared"):  # on x [B, S, d]: its products keep three axes
        gate = jax.nn.sigmoid(einsum_f32("bsd,de->bse", x, p["s_gate"]))
        y = y.reshape(b, s, d) + gate * _swiglu(x, p["s_wgu"], p["s_wd"])
    stats = jnp.stack([(counts > 0).sum(), counts.sum()]).astype(jnp.int32)
    return y, stats


def _split(params: dict):
    """(the mixers' and the expert layers' small leaves, as stacked; the routed
    experts' whole stacks).  Nothing is a scan's xs and nothing is reshaped by
    period: a layer takes its own weights out of the flat stacks with ONE index
    (``_at``), static in the burst (a view) and traced in the wave (a slice the
    product reads through).  Sliced by a scan, or taken as ``[period][j]``, the
    v5e compiler copied a period's Gated DeltaNet projections whole, 150 MB,
    every step (PERF.md, Findings, PR 34)."""
    experts = {k: params["moe"][k] for k in ("e_wgu", "e_wd")}
    moe = {k: v for k, v in params["moe"].items() if k not in experts}
    return {"gdn": params["gdn"], "attn": params["attn"], "moe": moe}, experts


def _at(tree, index):
    """Layer ``index`` of every stacked leaf of ``tree``."""
    if isinstance(index, int):
        return jax.tree.map(lambda x: x[index], tree)
    return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, index, keepdims=False), tree)


def _state_read(pool, layer, slots):
    """Rows' slots of one layer of a state pool, read where they lie; zeros
    where the slot is negative (a fresh sequence)."""
    rows = [jax.lax.dynamic_slice(pool, (layer, jnp.maximum(slots[r], 0)) + (0,) * (pool.ndim - 2),
                                  (1, 1, *pool.shape[2:]))[0, 0]
            for r in range(slots.shape[0])]
    keep = (slots >= 0).reshape(-1, *(1,) * (pool.ndim - 2))
    return jnp.where(keep, jnp.stack(rows), 0)


def _state_write(pool, layer, slots, vals):
    """Rows' values into their slots of one layer, in place, a row at a time."""
    for r in range(slots.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, vals[r][None, None].astype(pool.dtype),
            (layer, slots[r]) + (0,) * (pool.ndim - 2))
    return pool


def _head(params, h):
    return einsum_f32("bsd,dv->bsv", h, params["lm_head"])


# ----------------------------------------------------------- step programs --

@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5), donate_argnames=("state",))
def forward_paged(
    params: dict,
    cfg: Qwen3NextConfig,
    input_ids: jnp.ndarray,  # [B, S] int32, right-padded per row
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    k_pages: jnp.ndarray,  # [periods, n_kv, P, page_size, hd] (donated)
    v_pages: jnp.ndarray,  # (donated)
    slot_mapping: jnp.ndarray,  # [B, S] int32 flat pool slots, -1 for padding
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    cached_lens: jnp.ndarray,  # [B]
    new_lens: jnp.ndarray,  # [B]
    use_pallas: bool = False,
    logits_at: jnp.ndarray | None = None,
    k_scales=None, v_scales=None, int4_kernel: bool = True, mesh=None,
    *, state: dict, state_src: jnp.ndarray, state_dst: jnp.ndarray,
    state_snap: jnp.ndarray, snap_col: jnp.ndarray,
):
    """A prefill chunk, qwen2.forward_paged's contract with the state beside
    the pools (module docstring).  Returns (logits, k_pages, v_pages, stats
    [2], state)."""
    return forward_paged_impl(params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping,
                              block_tables, cached_lens, new_lens, state, state_src, state_dst,
                              state_snap, snap_col, use_pallas, logits_at)


@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5, 6), donate_argnames=("state",))
def forward_paged_wave(
    params: dict,
    cfg: Qwen3NextConfig,
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
    *, state: dict, state_src: jnp.ndarray, state_dst: jnp.ndarray,
    state_snap: jnp.ndarray, snap_col: jnp.ndarray,
):
    """The engine's prefill wave as one program, qwen2.forward_paged_wave's
    contract: the chunk, every layer at the narrowest width that holds
    ``width`` columns, then the first-token tail every family shares.
    Returns (first_tokens, presence, k_pages, v_pages, stats [2], state)."""
    logits, *cache = forward_paged_impl(
        params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
        cached_lens, new_lens, state, state_src, state_dst, state_snap, snap_col, use_pallas,
        logits_at, width)
    with jax.named_scope("sample"):
        first_tokens, presence = first_token_tail(
            logits[:, 0], presence, first_tokens, input_ids, new_lens, row_idx, done_mask,
            jax.random.fold_in(rng, key_step), temperature, top_p, top_k, repetition_penalty)
    return (first_tokens, presence, *cache)


def forward_paged_impl(params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping,
                       block_tables, cached_lens, new_lens, state, state_src, state_dst,
                       state_snap, snap_col, use_pallas=False, logits_at=None, width=None):
    """Unjitted body of ``forward_paged``, traced into the wave program too
    (``width``: the wave's, see ops/prefill_width.at_wave_width)."""
    from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref
    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    block = math.gcd(BLOCK, page_size)  # a snapshot's column lies between two blocks
    h = embedding_lookup(params["embed"], input_ids).astype(jnp.float32)
    cos, sin = rope_cos_sin(positions, cfg.rotary_dim, cfg.rope_theta)
    slots = jnp.where(slot_mapping < 0, num_pages * page_size, slot_mapping)  # dropped
    live = jnp.arange(input_ids.shape[1])[None, :] < new_lens[:, None]
    scan_p, experts = _split(params)
    gpp = cfg.gdn_per_period

    chunk = input_ids.shape[1]
    cols = (h, cos, sin, live)

    def padded(x):  # a rung's columns back to the chunk's: every rung returns the same shapes
        return jnp.pad(x, ((0, 0), (0, chunk - x.shape[1])) + ((0, 0),) * (x.ndim - 2))

    # THE POOLS NEVER ENTER A SWITCH (at_wave_width).  A branch that writes a
    # pool it was handed, or only hands it on, makes the v5e compiler copy the
    # pool into the branch and out of it (1.2 GB of state, 0.5 GB of keys, a
    # layer: this program, unlike qwen2's, has four switches a scan body).  A
    # layer's rows of state are read before its switch and written after it;
    # the attention layer's switch ends at q, k, v, its pages are committed
    # and attended outside, and a second switch takes the rest of the layer.
    def gdn_layer(pi, j, h, st_pools, stats):
        s_pool, c_pool = st_pools
        g = pi * gpp + j
        with jax.named_scope("state_read"):
            s0 = _state_read(s_pool, g, state_src).astype(jnp.float32)
            taps0 = _state_read(c_pool, g, state_src)

        def layer(cols, came_in):
            h, _, _, live = cols
            s0, taps0 = came_in
            pm, pg = _at(scan_p["moe"], pi * (gpp + 1) + j), _at(scan_p["gdn"], g)
            mixed, z, beta, gate = _gdn_inputs(cfg, pg, _norm(cfg, h, pm["ln1"]))
            with jax.named_scope("gdn_conv"):
                y, taps, taps_snap = causal_conv(
                    mixed, taps0.reshape(h.shape[0], -1, mixed.shape[-1]), pg["conv_w"],
                    new_lens, snap_col)
                taps, taps_snap = (t.reshape(t.shape[0], -1) for t in (taps, taps_snap))
            q, k, v = _gdn_heads(cfg, y)
            k, gate, beta = mask_padding(live, k, gate, beta)
            with jax.named_scope("gdn_chunked"):
                o, s_new, s_snap = gated_delta_chunked(s0, q, k, v, gate, beta, snap_col,
                                                       block=block)
            h = h + _gdn_out(cfg, pg, o, z)
            y, st = _moe_ffn(cfg, pm, experts, pi * (gpp + 1) + j, _norm(cfg, h, pm["ln2"]), live)
            return h + y, (s_new, s_snap, taps, taps_snap, st)

        h, (s_new, s_snap, taps, taps_snap, st) = at_wave_width(
            layer, width, page_size, (h, *cols[1:]), (s0, taps0))
        with jax.named_scope("state_write"):
            s_pool = _state_write(_state_write(s_pool, g, state_dst, s_new), g, state_snap, s_snap)
            c_pool = _state_write(_state_write(c_pool, g, state_dst, taps), g, state_snap,
                                  taps_snap)
        return h, (s_pool, c_pool), stats + st

    def attn_layer(pi, h, kv_pools, stats):
        kp, vp = kv_pools
        j = gpp

        def project(cols, _):
            h, cos, sin, _ = cols
            ln1 = _at(scan_p["moe"]["ln1"], pi * (gpp + 1) + j)
            q, gate, k, v = _attn_project(cfg, _at(scan_p["attn"], pi), _norm(cfg, h, ln1),
                                          cos, sin)
            return h, tuple(padded(t) for t in (q, gate, k, v))

        _, (q, gate, k, v) = at_wave_width(project, width, page_size, (h, *cols[1:]), ())
        with jax.named_scope("kv_write"):
            flat = slots.reshape(-1)
            kp, _ = commit_paged(kp, k.reshape(-1, nkv, hd).swapaxes(0, 1), flat, None,
                                 page_size, layer=pi)
            vp, _ = commit_paged(vp, v.reshape(-1, nkv, hd).swapaxes(0, 1), flat, None,
                                 page_size, layer=pi)
        with jax.named_scope("paged_attention"):
            if use_pallas:
                from githubrepostorag_tpu.ops.fused_decode import fused_paged_attention

                # the kernel keeps a window's queries, accumulator and softmax
                # state for a kv head's whole group in VMEM: 8 heads of 256 over
                # 512 columns are 16.8 MB, past what a v5e kernel may hold, so a
                # chunk goes through in windows of ATTN_WINDOW columns (the keys
                # of the whole chunk are committed: a later window attends the
                # earlier ones as cache); a window past the wave's width is skipped
                def window(c):
                    qw = q[:, c:c + ATTN_WINDOW]
                    run = lambda: fused_paged_attention(  # noqa: E731
                        qw, kp, vp, block_tables, cached_lens + jnp.minimum(new_lens, c),
                        jnp.clip(new_lens - c, 0, ATTN_WINDOW), layer=pi)
                    if c == 0 or width is None:
                        return run()
                    return jax.lax.cond(width > c, run, lambda: jnp.zeros_like(qw))

                attn = jnp.concatenate([window(c) for c in range(0, chunk, ATTN_WINDOW)], axis=1)
            else:
                attn = paged_attention_ref(q, kp[pi], vp[pi], block_tables, cached_lens,
                                           new_lens)

        def rest(cols, came_in):
            h, _, _, live, attn, gate = cols
            pm = _at(scan_p["moe"], pi * (gpp + 1) + j)
            h = h + _attn_out(_at(scan_p["attn"], pi), attn, gate)
            y, st = _moe_ffn(cfg, pm, experts, pi * (gpp + 1) + j, _norm(cfg, h, pm["ln2"]), live)
            return h + y, st

        h, st = at_wave_width(rest, width, page_size, (h, *cols[1:], attn, gate), ())
        return h, (kp, vp), stats + st

    def body(carry, _):
        h, pi, kv_pools, st_pools, stats = carry
        for j in range(gpp):
            h, st_pools, stats = gdn_layer(pi, j, h, st_pools, stats)
        h, kv_pools, stats = attn_layer(pi, h, kv_pools, stats)
        return (h, pi + 1, kv_pools, st_pools, stats), None

    (h, _, (k_pages, v_pages), st_pools, stats), _ = jax.lax.scan(
        body, (h, jnp.int32(0), (k_pages, v_pages), (state["s"], state["conv"]),
               jnp.zeros((2,), jnp.int32)), None, length=cfg.periods)
    with jax.named_scope("sample"):
        h = _norm(cfg, h, params["norm"])
        if logits_at is not None:
            h = jnp.take_along_axis(h, logits_at[:, None, None], axis=1)
        logits = _head(params, h)
    return logits, k_pages, v_pages, stats, {"s": st_pools[0], "conv": st_pools[1]}


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "use_pallas", "mesh", "layer_unroll",
                          "filter_sampling"),
         donate_argnums=(4, 5, 6), donate_argnames=("state",))
def decode_burst(
    params: dict,
    cfg: Qwen3NextConfig,
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
    *, first_tokens, fresh, fresh_lens, key_step, state: dict,
):
    """``n_steps`` decode iterations in one program, serving/decode_burst.py's
    contract and structure: the K/V pools are loop-invariant inside the burst
    (new keys and values go to a staged buffer the kernel reads as a tail, one
    scatter commits them at the end); the state pool is stepped in place,
    rows 0 .. B-1, a row that sits a step out keeping what it has.  Returns
    (packed tokens [B, n_steps], valid, k_pages, v_pages, presence, seq_lens,
    last_tokens, stats [2], state)."""
    from githubrepostorag_tpu.ops.attention import dense_attention
    from githubrepostorag_tpu.ops.paged_attention import gather_kv
    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged
    from githubrepostorag_tpu.serving.decode_burst import overlay_fresh
    from githubrepostorag_tpu.serving.kv_cache import commit_paged

    last_tokens, seq_lens, rng = overlay_fresh(
        last_tokens, seq_lens, rng, first_tokens, fresh, fresh_lens, key_step)
    b, P, gpp = last_tokens.shape[0], cfg.periods, cfg.gdn_per_period
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    rows = jnp.arange(b)
    start_lens = seq_lens
    walk_lens = jnp.where(active & (seq_lens < row_limits), start_lens, 0)
    interpret = not on_tpu()
    scan_p, experts = _split(params)

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
        h = embedding_lookup(params["embed"], jnp.maximum(last, 0)[:, None]).astype(jnp.float32)
        cos, sin = rope_cos_sin(lens[:, None], cfg.rotary_dim, cfg.rope_theta)

        def gdn_mixer(p, g, x, st_pools):
            s_pool, c_pool = st_pools
            mixed, z, beta, gate = _gdn_inputs(cfg, p, x)
            taps_old, s_old = rows_of(c_pool, g), rows_of(s_pool, g)
            with jax.named_scope("gdn_conv"):
                y, taps = causal_conv_step(mixed[:, 0], taps_old.reshape(b, -1, mixed.shape[-1]),
                                           p["conv_w"])
                taps = taps.reshape(b, -1)
            q, k, v = _gdn_heads(cfg, y[:, None])
            with jax.named_scope("gdn_recurrent"):
                o, s_new = gated_delta_step(s_old.astype(jnp.float32), q[:, 0], k[:, 0], v[:, 0],
                                            gate[:, 0], beta[:, 0])
                s_new = jnp.where(act[:, None, None, None], s_new.astype(s_pool.dtype), s_old)
                s_pool = put_rows(s_pool, g, s_new)
            c_pool = put_rows(c_pool, g, jnp.where(act[:, None], taps, taps_old))
            return _gdn_out(cfg, p, o[:, None], z), (s_pool, c_pool)

        def attn_mixer(p, pi, x, staged):
            sk, sv = staged
            q, gate, k, v = _attn_project(cfg, p, x, cos, sin)
            with jax.named_scope("kv_write"):
                sk = jax.lax.dynamic_update_slice(
                    sk, k.swapaxes(1, 2).astype(sk.dtype)[None], (pi, 0, 0, step, 0))
                sv = jax.lax.dynamic_update_slice(
                    sv, v.swapaxes(1, 2).astype(sv.dtype)[None], (pi, 0, 0, step, 0))
            sk_l = jax.lax.dynamic_index_in_dim(sk, pi, 0, keepdims=False)
            sv_l = jax.lax.dynamic_index_in_dim(sv, pi, 0, keepdims=False)
            # under the scope: the kernel's instruction is named for it in the
            # device trace, where the accepted metric looks for it
            with jax.named_scope("paged_attention"):
                if use_pallas:
                    attn = paged_attention_decode_staged(
                        q, k_pages, v_pages, block_tables, walk_lens, sk_l, sv_l,
                        jnp.reshape(step + 1, (1,)), jnp.reshape(pi, (1,)), interpret=interpret)
                else:
                    pool_k, pool_v = gather_kv(k_pages[pi], v_pages[pi], block_tables)
                    valid = jnp.concatenate(
                        [jnp.arange(pool_k.shape[1])[None, :] < start_lens[:, None],
                         jnp.broadcast_to((jnp.arange(n_steps) <= step)[None, :], (b, n_steps))],
                        axis=1)
                    attn = dense_attention(
                        q, jnp.concatenate([pool_k, sk_l.swapaxes(1, 2)], axis=1),
                        jnp.concatenate([pool_v, sv_l.swapaxes(1, 2)], axis=1),
                        causal=False, kv_valid=valid)
            return _attn_out(p, attn, gate), (sk, sv)

        def body(c):
            h, pi, staged, st_pools, stats = c
            for j in range(gpp + 1):
                pm = _at(scan_p["moe"], pi * (gpp + 1) + j)
                x = _norm(cfg, h, pm["ln1"])
                if j < gpp:
                    y, st_pools = gdn_mixer(_at(scan_p["gdn"], pi * gpp + j), pi * gpp + j, x,
                                            st_pools)
                else:
                    y, staged = attn_mixer(_at(scan_p["attn"], pi), pi, x, staged)
                h = h + y
                y, st = _moe_ffn(cfg, pm, experts, pi * (gpp + 1) + j,
                                 _norm(cfg, h, pm["ln2"]), act[:, None])
                h, stats = h + y, stats + st
            return h, pi + 1, staged, st_pools, stats

        # the periods are unrolled, not scanned: with a layer's index static its
        # weights are views of the stacks, and a burst of 8 layers still
        # compiles in seconds
        c = (h, 0, staged, st_pools, stats)
        for _ in range(P):
            c = body(c)
        h, _, staged, st_pools, stats = c
        with jax.named_scope("sample"):
            logits = _head(params, _norm(cfg, h, params["norm"]))
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

    staged0 = tuple(jnp.zeros((P, b, nkv, n_steps, hd), k_pages.dtype) for _ in range(2))
    carry0 = (last_tokens, seq_lens, staged0, (state["s"], state["conv"]), presence, active,
              jnp.zeros((2,), jnp.int32))
    (last, out_lens, staged, st_pools, presence, _, stats), (toks, valid) = jax.lax.scan(
        one_step, carry0, (jnp.arange(n_steps), jax.random.split(rng, n_steps)))
    toks, valid = toks.T, valid.T
    packed = jnp.where(valid, toks, -1)

    pos = start_lens[:, None] + jnp.arange(n_steps)[None, :]
    page_idx = jnp.clip(pos // page_size, 0, block_tables.shape[1] - 1)
    slots = jnp.take_along_axis(block_tables, page_idx, axis=1) * page_size + pos % page_size
    slots = jnp.where(valid, slots, num_pages * page_size).reshape(-1)  # sentinel: dropped
    with jax.named_scope("kv_write"):
        commit = lambda pool, st: commit_paged(  # noqa: E731
            pool, st.swapaxes(1, 2).reshape(P, nkv, b * n_steps, hd), slots, None, page_size)[0]
        k_pages, v_pages = commit(k_pages, staged[0]), commit(v_pages, staged[1])
    return (packed, valid, k_pages, v_pages, presence, out_lens, last, stats,
            {"s": st_pools[0], "conv": st_pools[1]})
