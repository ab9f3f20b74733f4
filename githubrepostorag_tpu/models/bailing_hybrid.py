"""Ling-3.0-flash decoder (inclusionAI, ``model_type`` ``bailing_hybrid``): a
hybrid of Kimi Delta Attention layers (KDA; Kimi Linear, arXiv:2510.26692: the
gated delta rule with a decay a key CHANNEL) and multi-head latent attention
layers (DeepSeek's MLA without a query rank), one latent layer closing every
``layer_group_size`` layers; the first ``first_k_dense`` layers end in a dense
SwiGLU, the rest in DeepSeek-V3's ``noaux_tc`` experts (groups, a sigmoid, a
selection bias) with one ungated shared expert, as a share of a larger
deployment.

The two step programs are models/hybrid.py's.  Two kinds of per-sequence memory
ride them, and this is the first model whose page layers are not K/V: the KDA
layers keep a slot of the STATE pool (``s`` [H, dk, dv] float32 and the
convolution's history), the latent layers ONE row ``[c_kv | k_rope | pad]`` a
token in a latent page pool ``[latent layers, 1, P, page, 640]`` with no V pool
(``_LatentPages``: what the skeleton's page layer commits and attends, through
ops/latent_attention.py's two kernels, as models/deepseek_v3.py does).

This file is the model's own part: its weights, the KDA mixer (``q``, ``k``, ``v``
through a short convolution, L2-normed; the decay ``g = kda_lower_bound *
sigmoid(exp(A_log) (W_f x + dt_bias))`` in (-5, 0) a channel; a head's RMSNorm
and an elementwise sigmoid gate on the way out), the latent mixer (interleaved
rotary on the 64 rope columns, a head-wise sigmoid gate after attention), and
the block (pre-norm, sequential, float32 residual stream).

``experts_held`` is a contiguous range of ``num_experts``: the router scores
all of them, the layer computes its own and adds nothing for the others.
``vocab_size`` may be a slice of the published vocabulary.  The
multi-token-prediction module is not built.  ``layer_kinds`` states a cut's
pattern where it is not the first layers of the published one.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models import hybrid
from githubrepostorag_tpu.models.moe import dropless_experts, route_noaux_tc
from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.ops.gated_delta import (
    BLOCK,
    causal_conv,
    causal_conv_step,
    gated_delta_chunked,
    gated_delta_step,
    l2norm,
    mask_padding,
)
from githubrepostorag_tpu.ops.latent_attention import (
    einsum_f32,
    latent_decode_attention,
    latent_prefill_attention,
)
from githubrepostorag_tpu.ops.norms import rms_norm, rms_norm_gated
from githubrepostorag_tpu.ops.pallas_experts import SWIGLU, experts_walk
from githubrepostorag_tpu.ops.pallas_state import kda_step_in_place
from githubrepostorag_tpu.ops.rope import rope_cos_sin_interleaved, rope_rotate_interleaved
from githubrepostorag_tpu.ops.sampling import first_token_tail
from githubrepostorag_tpu.runtime import on_tpu

ACT = jnp.bfloat16  # products take bfloat16 operands; the residual stream is float32


@dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    shared_expert_intermediate_size: int = 768
    num_layers: int = 42
    layer_group_size: int = 6
    first_k_dense: int = 2
    num_heads: int = 32
    kda_head_dim: int = 128  # dk = dv of a KDA head
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    num_experts: int = 512  # the router's width: every expert it scores
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    experts_held: tuple = (0, 512)  # [first, past the last) of num_experts
    layer_kinds: str | None = None  # one letter a layer where a cut states its own pattern

    # what the serving engine asks of a model (see Qwen3NextConfig and
    # DeepseekV3Config): both kinds of per-sequence memory at once
    step_programs = "githubrepostorag_tpu.models.bailing_hybrid"
    latent_kv = True
    recurrent_state = True
    expert_counters = True
    prefill_rows_cap = 8

    @property
    def kinds(self) -> str:
        """One letter a layer: the LAST layer of every group of
        ``layer_group_size`` is the latent one."""
        if self.layer_kinds is not None:
            return self.layer_kinds
        return "".join(hybrid.ATTN if (i + 1) % self.layer_group_size == 0 else hybrid.STATE
                       for i in range(self.num_layers))

    @property
    def layer_segments(self) -> tuple:
        return hybrid.segments(self.kinds)

    @property
    def kv_layers(self) -> int:
        """Layers that page a latent row a token."""
        return self.kinds.count(hybrid.ATTN)

    @property
    def state_layers(self) -> int:
        """Layers that keep a slot of state a sequence: the KDA ones."""
        return self.kinds.count(hybrid.STATE)

    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        """Columns of a latent pool row: ``[c_kv | k_rope]`` padded with zeros to
        whole lane tiles (640; DeepseekV3Config.head_dim says why)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def conv_channels(self) -> int:
        """The convolution runs over [q | k | v] of the KDA heads."""
        return 3 * self.num_heads * self.kda_head_dim

    @property
    def state_cols(self) -> int:
        return self.kda_head_dim

    def state_shapes(self) -> dict:
        """One slot of one KDA layer: (shape, dtype) by name (the history's taps
        side by side in one row, as Qwen3NextConfig.state_shapes says why)."""
        return {
            "s": ((self.num_heads, self.kda_head_dim, self.kda_head_dim), jnp.dtype("float32")),
            "conv": (((self.short_conv_kernel_size - 1) * self.conv_channels,), jnp.dtype(ACT)),
        }

    @classmethod
    def tiny(cls, **kw) -> "BailingHybridConfig":
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, num_layers=7, layer_kinds="RRRARRA",
            first_k_dense=1, num_heads=4, kda_head_dim=16, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_experts=16, num_experts_per_tok=4, n_group=4,
            topk_group=2, rope_theta=100.0, max_position_embeddings=1024, experts_held=(0, 16))
        return cls(**{**base, **kw})


# ------------------------------------------------------------------ weights --

ROUTER_GAIN = 2.0  # the router's draw, times this: logits of std ~2 (DeepSeek-V3's are ~1.7)
CONV_GAIN = 16.0  # the convolution's taps, times this: std ~0.32
# W_q of the latent layers, times this: at a plain draw a score has std ~0.7 and the softmax over
# 25k keys is flat, so the layer's output is the mean of 25k random values (1/160 of one) and
# neither its rotary pairing nor its head gate decides anything (the knock-outs read 0.002 and
# 0.07 beside the program's own 0.10: my chip run, PR 57); at 4 a score has std ~2.8 and a query
# attends some tens of keys, as a trained layer's do
ATTN_Q_GAIN = 4.0
# and their W_o times this: ONE latent layer of seven at a plain draw adds 0.2 a column to a
# residual stream of ~3 (a KDA layer adds 0.6), so all it decides moves the logits by 3-6%, under
# the program's own bfloat16 error over 25k tokens (0.07-0.125); at 8 it is a layer among equals
# and the two knock-outs that touch it alone (its rotary pairing, its head gate) can fail a limit
ATTN_O_GAIN = 8.0


def leaf_order(cfg: BailingHybridConfig) -> list:
    """(path, shape, gain) of every leaf the initialiser draws, in draw order
    (models/hybrid.draw_leaves).  The benchmark's reference re-states this list."""
    d, L, G, P = cfg.hidden_size, cfg.num_layers, cfg.state_layers, cfg.kv_layers
    h, dk = cfg.num_heads, cfg.kda_head_dim
    nope, rope, vd, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    D, M, n = cfg.first_k_dense, L - cfg.first_k_dense, cfg.n_held
    ff, ffe, ffs = (cfg.intermediate_size, cfg.moe_intermediate_size,
                    cfg.shared_expert_intermediate_size)
    leaves = [
        (("embed",), (cfg.vocab_size, d), 1.0),
        (("lm_head",), (d, cfg.vocab_size), 1.0),
        (("kda", "w_qkv"), (G, d, 3 * h * dk), 1.0),
        (("kda", "w_f"), (G, d, h * dk), 1.0),
        (("kda", "w_g"), (G, d, h * dk), 1.0),
        (("kda", "w_beta"), (G, d, h), 1.0),
        (("kda", "conv_w"), (G, cfg.conv_channels, cfg.short_conv_kernel_size), CONV_GAIN),
        (("kda", "w_out"), (G, h * dk, d), 1.0),
        (("attn", "wq"), (P, d, h * (nope + rope)), ATTN_Q_GAIN),
        (("attn", "wkva"), (P, d, rank + rope), 1.0),
        (("attn", "wuk"), (P, h, nope, rank), 1.0),
        (("attn", "wuv"), (P, h, rank, vd), 1.0),
        (("attn", "w_gate"), (P, d, h), 1.0),
        (("attn", "wo"), (P, h * vd, d), ATTN_O_GAIN),
    ]
    if D:
        leaves += [(("dense", "wgu"), (D, d, 2 * ff), 1.0), (("dense", "wd"), (D, ff, d), 1.0)]
    if M:
        leaves += [
            (("moe", "router"), (M, d, cfg.num_experts), ROUTER_GAIN),
            (("moe", "e_bias"), (M, cfg.num_experts), 1.0),  # kept in float32 (init_params)
            (("moe", "e_wgu"), (M, n, d, 2 * ffe), 1.0),
            (("moe", "e_wd"), (M, n, ffe, d), 1.0),
            (("moe", "s_wgu"), (M, d, 2 * ffs), 1.0),
            (("moe", "s_wd"), (M, ffs, d), 1.0)]
    return leaves


def gate_ladder(cfg: BailingHybridConfig):
    """(``A_log`` [H], ``dt_bias`` [H * dk]) float32: ``A`` from 0.5 to 2 in equal
    ratios over the heads, and ``dt_bias`` a head's constant chosen so that the
    gate's argument at ``W_f x = 0``, ``A dt_bias``, runs from -8.5 to 2.5 in equal
    steps: a token's decay ``exp(-5 sigmoid(.))`` from 0.999 (a head that
    remembers thousands of tokens) to 0.01, the channels of a head spread about
    it by ``W_f x`` (std ~1).  As Qwen3-Next's ladder, and for its reason: under
    a drawn initialiser nearly every channel would sit mid-range and forget
    within a token, and the state and its precision would decide nothing."""
    h, dk = cfg.num_heads, cfg.kda_head_dim
    a_log = jnp.linspace(math.log(0.5), math.log(2.0), h, dtype=jnp.float32)
    z0 = jnp.linspace(-8.5, 2.5, h, dtype=jnp.float32)
    return a_log, jnp.repeat(z0 / jnp.exp(a_log), dk)


@startup.records("startup.weights", settle=True)
def init_params(cfg: BailingHybridConfig, seed: int = 0) -> dict:
    """Weights made on the device from the seed, leaf by leaf, in bfloat16
    (models/hybrid.draw_leaves), norms at one, the selection bias the same
    draw in float32 (DeepSeek-V3's), ``A_log`` and ``dt_bias`` the
    ``gate_ladder``.  The expert stacks hold the ``experts_held`` range only."""
    params = hybrid.draw_leaves(leaf_order(cfg), seed)
    d, L, G, P = cfg.hidden_size, cfg.num_layers, cfg.state_layers, cfg.kv_layers
    if "moe" in params:
        params["moe"]["e_bias"] = params["moe"]["e_bias"].astype(jnp.float32)
    a_log, dt_bias = gate_ladder(cfg)
    params["kda"].update(A_log=jnp.tile(a_log[None], (G, 1)),
                         dt_bias=jnp.tile(dt_bias[None], (G, 1)),
                         o_norm=jnp.ones((G, cfg.kda_head_dim), jnp.bfloat16))
    params["attn"]["kv_norm"] = jnp.ones((P, cfg.kv_lora_rank), jnp.bfloat16)
    params["norms"] = {"ln1": jnp.ones((L, d), jnp.bfloat16), "ln2": jnp.ones((L, d), jnp.bfloat16)}
    params["norm"] = jnp.ones((d,), jnp.bfloat16)
    return params


# ---------------------------------------------------------------- KDA mixer --

def _kda_inputs(cfg, p, x):
    """x [B, S, d] normed -> (the convolution's input [B, S, 3 H dk]: q | k | v,
    in ``ACT`` as the history keeps it; the output gate z [B, S, H, dk]; beta
    [B, S, H]; the log decay g [B, S, H, dk] in (``kda_lower_bound``, 0))."""
    b, s, _ = x.shape
    h, dk = cfg.num_heads, cfg.kda_head_dim
    with jax.named_scope("kda_proj"):
        mixed, a, z, beta = (einsum_f32("bsd,de->bse", x, p[k])
                             for k in ("w_qkv", "w_f", "w_g", "w_beta"))
    with jax.named_scope("kda_gate"):
        arg = jnp.exp(p["A_log"])[:, None] * (a.reshape(b, s, h, dk) + p["dt_bias"].reshape(h, dk))
        g = cfg.kda_lower_bound * jax.nn.sigmoid(arg)
    return mixed.astype(ACT), z.reshape(b, s, h, dk), jax.nn.sigmoid(beta), g


def _kda_heads(cfg, y):
    """The convolution's output [B, S, 3 H dk] float32 -> (q, k L2-normalised, q
    scaled; v), each [B, S, H, dk]."""
    b, s, _ = y.shape
    h, dk = cfg.num_heads, cfg.kda_head_dim
    q, k, v = (y[..., i * h * dk:(i + 1) * h * dk].reshape(b, s, h, dk) for i in range(3))
    return l2norm(q) * dk ** -0.5, l2norm(k), v


def _kda_out(cfg, p, o, z):
    """A head's RMSNorm of the rule's output times the sigmoid of its gate,
    elementwise, then the output projection."""
    with jax.named_scope("kda_gate_norm"):
        y = rms_norm_gated(o, z, p["o_norm"], cfg.rms_norm_eps, gate_fn=jax.nn.sigmoid)
    return einsum_f32("bse,ed->bsd", y.reshape(*y.shape[:2], -1).astype(ACT), p["w_out"])


def _kda_chunk(cfg, p, x, s0, taps0, live, new_lens, snap_col, page_size):
    """The KDA mixer over a chunk (``state_chunk`` of models/hybrid.py)."""
    mixed, z, beta, gate = _kda_inputs(cfg, p, x)
    with jax.named_scope("kda_conv"):
        y, taps, taps_snap = causal_conv(
            mixed, taps0.reshape(x.shape[0], -1, mixed.shape[-1]), p["conv_w"], new_lens, snap_col)
        taps, taps_snap = (t.reshape(t.shape[0], -1) for t in (taps, taps_snap))
    q, k, v = _kda_heads(cfg, y)
    k, gate, beta = mask_padding(live, k, gate, beta)
    with jax.named_scope("kda_chunked"):
        o, s_new, s_snap = gated_delta_chunked(
            s0, q, k, v, gate, beta, snap_col, block=math.gcd(BLOCK, page_size),
            g_min=cfg.kda_lower_bound)
    return _kda_out(cfg, p, o, z), s_new, s_snap, taps, taps_snap


def _kda_token(cfg, p, x, taps_old):
    """One token a row up to the rule."""
    b = x.shape[0]
    mixed, z, beta, gate = _kda_inputs(cfg, p, x)
    with jax.named_scope("kda_conv"):
        y, taps = causal_conv_step(mixed[:, 0], taps_old.reshape(b, -1, mixed.shape[-1]),
                                   p["conv_w"])
        taps = taps.reshape(b, -1)
    q, k, v = _kda_heads(cfg, y[:, None])
    return q[:, 0], k[:, 0], v[:, 0], gate[:, 0], beta[:, 0], z, taps


def _kda_step(cfg, p, x, s_old, taps_old):
    """The KDA mixer over one token a row, as array code (``state_step``): the
    CPU's path, and what the kernel below is held to."""
    q, k, v, gate, beta, z, taps = _kda_token(cfg, p, x, taps_old)
    with jax.named_scope("kda_recurrent"):
        o, s_new = gated_delta_step(s_old.astype(jnp.float32), q, k, v, gate, beta)
    return _kda_out(cfg, p, o[:, None], z), s_new, taps


@partial(jax.jit, static_argnames=("interpret",))
def _kda_rule_in_pool(s_pool, n, act, q, k, v, g, beta, interpret):
    """ops/pallas_state.kda_step_in_place under the rule's scope and its own jit,
    as models/hybrid._gdn_rule_in_pool: named for the scope in a device trace, and
    its body (every head unrolled) traced once a burst."""
    with jax.named_scope("kda_recurrent"):
        return kda_step_in_place(s_pool, n, act, q, k, v, g, beta, interpret=interpret)


def _kda_step_in_pool(cfg, p, x, s_pool, n, taps_old, act, interpret):
    """The same mixer with the rule as a kernel on the state pool itself
    (``state_step_in_pool``)."""
    q, k, v, gate, beta, z, taps = _kda_token(cfg, p, x, taps_old)
    o, s_pool = _kda_rule_in_pool(s_pool, jnp.int32(n), act, q, k, v, gate, beta,
                                  interpret=interpret)
    return _kda_out(cfg, p, o[:, None], z), s_pool, taps


# ------------------------------------------------------------- latent mixer --

def _attn_project(cfg, p, x, cos, sin):
    """x [B, S, d] normed -> (q [B, S, H, nope + rope], its rope columns rotated;
    the latent row [B, S, 640]: the normed c_kv beside the rotated shared k_rope
    and zeros; (the heads' gate logits [B, S, H],))."""
    b, s, _ = x.shape
    h, nope, rope, rank = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.kv_lora_rank)
    with jax.named_scope("mla_q_proj"):
        q = (x @ p["wq"]).reshape(b, s, h, nope + rope)
        q = jnp.concatenate([q[..., :nope], rope_rotate_interleaved(
            q[..., nope:], cos[:, :, None, :], sin[:, :, None, :])], axis=-1)
    with jax.named_scope("mla_kv_proj"):
        ckv = x @ p["wkva"]
        c_kv = rms_norm(ckv[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
        k_rope = rope_rotate_interleaved(ckv[..., rank:], cos, sin)
        pad = jnp.zeros((b, s, cfg.head_dim - rank - rope), c_kv.dtype)
        latent = jnp.concatenate([c_kv, k_rope, pad], axis=-1)
    with jax.named_scope("attn_gate"):
        gate = einsum_f32("bsd,dh->bsh", x, p["w_gate"])
    return q, latent, (gate,)


def _attn_out(p, attn, gate):
    """attn [B, S, H, v] times its head's sigmoid gate, then ``W_o``."""
    with jax.named_scope("attn_gate"):
        attn = attn * jax.nn.sigmoid(gate)[..., None].astype(attn.dtype)
    return einsum_f32("bse,ed->bsd", attn.reshape(*attn.shape[:2], -1), p["wo"])


class _LatentPages:
    """The page layer's two halves over a latent pool (models/hybrid.KVPages is
    the K/V form): a layer commits ONE row a token, a chunk attends through the
    materialising prefill kernel and a burst's token through the absorbed
    decode kernel with the burst's staged rows as its tail, as
    models/deepseek_v3.py's two programs do.  There is no V pool: ``kv_pools``
    is ``(the latent pool, None)``."""

    rows = 1

    @staticmethod
    def wave_attend(m, cfg, weights, pi, q, rows, kv_pools, c):
        from githubrepostorag_tpu.serving.kv_cache import commit_paged

        (latent,), pool, p = rows, kv_pools[0], weights()
        nope = cfg.qk_nope_head_dim
        with jax.named_scope("latent_write"):
            pool, _ = commit_paged(pool, latent.reshape(1, -1, latent.shape[-1]),
                                   c.slots.reshape(-1), None, c.page_size, layer=pi)
        with jax.named_scope("latent_prefill_attention"):
            out = latent_prefill_attention(
                q[..., :nope], q[..., nope:], pool, pi, c.block_tables, c.cached_lens, c.new_lens,
                p["wuk"], p["wuv"], cfg.softmax_scale, use_pallas=c.use_pallas,
                interpret=not on_tpu())
        return (pool, None), out

    @staticmethod
    def staged(cfg, kv_pools, b, n_steps):
        pool = kv_pools[0]
        return jnp.zeros((cfg.kv_layers, b, n_steps, pool.shape[-1]), pool.dtype)

    @staticmethod
    def burst_attend(m, cfg, p, pi, q, rows, staged, kv_pools, c):
        (latent,), nope, scale = rows, cfg.qk_nope_head_dim, cfg.softmax_scale
        with jax.named_scope("latent_write"):
            staged = jax.lax.dynamic_update_slice(
                staged, latent[None].astype(staged.dtype), (pi, 0, c.step, 0))
        with jax.named_scope("latent_attention"):
            # absorbed: W_uk folded into the query, W_uv applied after
            q_lat = einsum_f32("bhn,hnc->bhc", q[:, 0, :, :nope], p["wuk"])
            out = latent_decode_attention(
                (q_lat * scale).astype(latent.dtype),
                (q[:, 0, :, nope:].astype(jnp.float32) * scale).astype(latent.dtype),
                kv_pools[0], pi, c.block_tables, c.walk_lens,
                jax.lax.dynamic_index_in_dim(staged, pi, 0, keepdims=False), c.step + 1,
                use_pallas=c.use_pallas, interpret=c.interpret)
            o = jnp.einsum("bhc,hcv->bhv", out, p["wuv"])
        return o[:, None], staged

    @staticmethod
    def burst_commit(cfg, kv_pools, staged, slots, b, n_steps, page_size):
        from githubrepostorag_tpu.serving.kv_cache import commit_paged

        with jax.named_scope("latent_write"):
            pool, _ = commit_paged(
                kv_pools[0], staged.reshape(cfg.kv_layers, 1, b * n_steps, -1), slots, None,
                page_size)
        return pool, None


# ------------------------------------------------------------- feed-forward --

def _moe_ffn(cfg, p: dict, experts: dict, li, x: jnp.ndarray, live):
    """x [B, S, d] normed -> (y [B, S, d] float32, [experts hit, pairs to held
    experts, the fullest held expert's pairs]).  ``noaux_tc``: sigmoid scores in
    float32, the choice on score + bias limited to the best ``topk_group`` of
    ``n_group`` groups, the weights the chosen scores renormalised and scaled
    (models/moe.route_noaux_tc); the held experts' part through
    ``dropless_experts`` (``experts`` holds the whole [M, n_held, ...] stacks and
    ``li`` the layer among the expert layers); the shared expert ungated.
    ``live`` [B, S] marks real tokens: padding wakes no expert."""
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
        return hybrid.swiglu(rows, at(experts["e_wgu"]), at(experts["e_wd"]))

    with jax.named_scope("moe_experts"):
        y, counts = dropless_experts(
            xf, top_i, top_w, expert_ffn, cfg.n_held, lo=cfg.experts_held[0], listed=True,
            walk=experts_walk(SWIGLU, (experts["e_wgu"], experts["e_wd"]), li, burst=s == 1))
    with jax.named_scope("moe_shared"):  # on x [B, S, d]: its products keep three axes
        y = y.reshape(b, s, d) + hybrid.swiglu(x, p["s_wgu"], p["s_wd"])
    stats = jnp.stack([(counts > 0).sum(), counts.sum(), counts.max()]).astype(jnp.int32)
    return y, stats


def _feed_forward(cfg, w, li, x, live):
    """Layer ``li``'s feed-forward on x [B, S, d] normed: the dense SwiGLU in the
    first ``first_k_dense`` layers, the experts after them.  ``li`` is static in
    the burst; the wave scans its layers, so there it is traced and the two kinds
    are the branches of a ``cond`` (a layer's weights are read where they lie in
    either: nothing but ``x`` enters it by value)."""
    scan_p, experts = w
    zero = jnp.zeros((3,), jnp.int32)
    D = cfg.first_k_dense

    def dense(x):
        p = hybrid.at(scan_p["dense"], li if isinstance(li, int) else jnp.minimum(li, D - 1))
        return hybrid.swiglu(x, p["wgu"], p["wd"]), zero

    def moe(x):
        mi = li - D if isinstance(li, int) else jnp.maximum(li - D, 0)
        return _moe_ffn(cfg, hybrid.at(scan_p["moe"], mi), experts, mi, x, live)

    if D == 0 or D == cfg.num_layers:
        return (moe if D == 0 else dense)(x)
    if isinstance(li, int):
        return (dense if li < D else moe)(x)
    return jax.lax.cond(li < D, dense, moe, x)


def _split(params: dict):
    """(the small leaves as stacked; the routed experts' whole stacks), as
    models/qwen3_next._split: a layer takes its own weights out of the flat
    stacks with one index."""
    moe = params.get("moe", {})
    experts = {k: moe[k] for k in ("e_wgu", "e_wd") if k in moe}
    rest = {k: v for k, v in params.items() if k not in ("moe", "embed", "lm_head", "norm")}
    rest["moe"] = {k: v for k, v in moe.items() if k not in experts}
    return rest, experts


class _Layers:
    """This model's layers, as models/hybrid.py's skeleton asks for them."""

    counts = 3  # experts hit, pairs to held experts, the fullest held expert's pairs
    pages = _LatentPages
    step_scope = "kda_recurrent"
    weights = staticmethod(_split)
    state_weights = staticmethod(lambda w, g: hybrid.at(w[0]["kda"], g))
    attn_weights = staticmethod(lambda w, pi: hybrid.at(w[0]["attn"], pi))
    state_chunk = staticmethod(lambda *a: _kda_chunk(*a))
    state_step = staticmethod(lambda *a: _kda_step(*a))
    state_step_in_pool = staticmethod(lambda *a: _kda_step_in_pool(*a))
    attn_project = staticmethod(lambda *a: _attn_project(*a))
    attn_out = staticmethod(lambda *a: _attn_out(*a))

    @staticmethod
    def embed(cfg, params, ids):
        return embedding_lookup(params["embed"], ids).astype(jnp.float32)

    @staticmethod
    def position_cols(cfg, positions):
        return rope_cos_sin_interleaved(positions, cfg.qk_rope_head_dim, cfg.rope_theta)

    @staticmethod
    def mixer_input(cfg, w, li, h):
        return rms_norm(h, hybrid.at(w[0]["norms"]["ln1"], li), cfg.rms_norm_eps).astype(ACT)

    @staticmethod
    def after_mixer(cfg, w, li, h, y, live):
        """The mixer's residual add, then the feed-forward and its own."""
        h = h + y
        x = rms_norm(h, hybrid.at(w[0]["norms"]["ln2"], li), cfg.rms_norm_eps).astype(ACT)
        y, st = _feed_forward(cfg, w, li, x, live)
        return h + y, st

    @staticmethod
    def final(cfg, params, h):
        return rms_norm(h, params["norm"], cfg.rms_norm_eps).astype(ACT)

    @staticmethod
    def head(cfg, params, h):
        return einsum_f32("bsd,dv->bsv", h, params["lm_head"])


# ----------------------------------------------------------- step programs --

@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4,), donate_argnames=("state",))
def forward_paged(
    params: dict,
    cfg: BailingHybridConfig,
    input_ids: jnp.ndarray,  # [B, S] int32, right-padded per row
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    k_pages: jnp.ndarray,  # the latent pool [latent layers, 1, P, page_size, 640] (donated)
    v_pages,  # None: there is no V pool
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
    """A prefill chunk, qwen2.forward_paged's contract with the state beside the
    latent pool (models/hybrid.wave).  Returns (logits, pool, None, counts [3],
    state)."""
    return forward_paged_impl(params, cfg, input_ids, positions, k_pages, slot_mapping,
                              block_tables, cached_lens, new_lens, state, state_src, state_dst,
                              state_snap, snap_col, use_pallas, logits_at)


@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 6), donate_argnames=("state",))
def forward_paged_wave(
    params: dict,
    cfg: BailingHybridConfig,
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
    *, state: dict, state_src: jnp.ndarray, state_dst: jnp.ndarray,
    state_snap: jnp.ndarray, snap_col: jnp.ndarray,
):
    """The engine's prefill wave as one program, qwen2.forward_paged_wave's
    contract.  Returns (first_tokens, presence, pool, None, counts [3], state)."""
    logits, *cache = forward_paged_impl(
        params, cfg, input_ids, positions, k_pages, slot_mapping, block_tables, cached_lens,
        new_lens, state, state_src, state_dst, state_snap, snap_col, use_pallas, logits_at, width)
    with jax.named_scope("sample"):
        first_tokens, presence = first_token_tail(
            logits[:, 0], presence, first_tokens, input_ids, new_lens, row_idx, done_mask,
            jax.random.fold_in(rng, key_step), temperature, top_p, top_k, repetition_penalty)
    return (first_tokens, presence, *cache)


def forward_paged_impl(params, cfg, input_ids, positions, k_pages, slot_mapping, block_tables,
                       cached_lens, new_lens, state, state_src, state_dst, state_snap, snap_col,
                       use_pallas=False, logits_at=None, width=None):
    """Unjitted body of ``forward_paged``, traced into the wave program too: the
    shared skeleton over this model's layers."""
    return hybrid.wave(_Layers, params, cfg, input_ids, positions, k_pages, None, slot_mapping,
                       block_tables, cached_lens, new_lens, state, state_src, state_dst,
                       state_snap, snap_col, use_pallas, logits_at, width)


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "use_pallas", "mesh", "layer_unroll",
                          "filter_sampling"),
         donate_argnums=(4, 6), donate_argnames=("state",))
def decode_burst(
    params: dict,
    cfg: BailingHybridConfig,
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
    *, first_tokens, fresh, fresh_lens, key_step, state: dict,
):
    """``n_steps`` decode iterations in one program, serving/decode_burst.py's
    contract: the shared skeleton (models/hybrid.burst) over this model's
    layers.  Returns (packed tokens [B, n_steps], valid, pool, None, presence,
    seq_lens, last_tokens, counts [3], state)."""
    return hybrid.burst(_Layers, params, cfg, last_tokens, seq_lens, k_pages, None, presence,
                        active, row_limits, block_tables, rng, temperature, top_p, top_k,
                        repetition_penalty, n_steps, use_pallas, filter_sampling, first_tokens,
                        fresh, fresh_lens, key_step, state)
