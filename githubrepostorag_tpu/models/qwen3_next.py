"""Qwen3-Next decoder (Qwen3NextForCausalLM, Qwen/Qwen3-Next-80B-A3B): a
hybrid of Gated DeltaNet (linear-attention) layers and gated full-attention
layers, every layer followed by a sparse mixture of many small experts with
a shared expert behind a sigmoid gate, as a share of a larger deployment.

One PERIOD is ``full_attention_interval - 1`` Gated DeltaNet layers, then one
full-attention layer.  The two step programs, the K/V pools of the attention
layers and the state pool of the Gated DeltaNet layers are models/hybrid.py's
(the skeleton this model shares with models/olmo_hybrid.py); this file is the
model's own part: its weights, its pre-norm block with an expert layer after
either mixer, its gated attention with rotary on a slice, ``beta`` in (0, 1).

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

from githubrepostorag_tpu.models import hybrid
from githubrepostorag_tpu.models.moe import dropless_experts
from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.ops.latent_attention import einsum_f32
from githubrepostorag_tpu.ops.norms import rms_norm_zero_centered
from githubrepostorag_tpu.ops.pallas_experts import SWIGLU, experts_walk
from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate_leading
from githubrepostorag_tpu.ops.sampling import first_token_tail

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

    # what the serving engine asks of a model: the module whose step programs
    # serve it, per-sequence state beside the pages (serving/kv_cache.StateSlots),
    # expert counters over ``expert_layers``, and the most rows one prefill wave
    # carries (see DeepseekV3Config.prefill_rows_cap)
    step_programs = "githubrepostorag_tpu.models.qwen3_next"
    recurrent_state = True
    expert_counters = True
    prefill_rows_cap = 8

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def gdn_per_period(self) -> int:
        return self.full_attention_interval - 1

    @property
    def layer_segments(self) -> tuple:
        """The layer pattern models/hybrid.py walks: a period, ``periods`` times."""
        return ((hybrid.STATE * self.gdn_per_period + hybrid.ATTN, self.periods),)

    @property
    def kv_layers(self) -> int:
        """Layers that page keys and values: one a period."""
        return self.periods

    @property
    def gdn_layers(self) -> int:
        return self.periods * self.gdn_per_period

    @property
    def state_layers(self) -> int:
        """Layers that keep a slot of state a sequence: the Gated DeltaNet ones."""
        return self.gdn_layers

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def expert_layers(self) -> int:
        """Every layer ends in an expert layer."""
        return self.num_layers

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        """The convolution runs over [q | k | v] of the linear heads."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def state_cols(self) -> int:
        """Columns of a slot's matrix the chunked rule works at (the pool may store more)."""
        return self.linear_value_head_dim

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


# The state pools are made by serving/kv_cache.make_state_pools from what
# ``state_shapes()`` and ``state_layers`` say above.  The engine, the tests
# and the benchmark all go through that one function: a hybrid family brings
# the shapes of its slot (and where an axis has to be padded or flattened for
# the chip's tiling), and no pool constructor of its own.


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
    ``hybrid.gdn_heads``' order (a key head's r side by side).  Read as published,
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
    params = hybrid.draw_leaves(leaf_order(cfg), seed)
    params["norm"] = jnp.zeros((cfg.hidden_size,), jnp.bfloat16)
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


def _gdn_inputs(cfg, p, x):
    """The shared mixer's projections (models/hybrid.gdn_inputs) in this
    model's activation type, ``beta`` in (0, 1)."""
    return hybrid.gdn_inputs(cfg, p, x, ACT)


def _attn_project(cfg, p, x, cos, sin):
    """x [B, S, d] normed -> (q [B, S, H, hd] normed and rotated, k and v
    [B, S, n_kv, hd], (q's gate [B, S, H * hd],))."""
    b, s, _ = x.shape
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = x @ p["wqkv"]
    qg = qkv[..., :h * 2 * hd].reshape(b, s, h, 2 * hd)
    k = qkv[..., h * 2 * hd:h * 2 * hd + nkv * hd].reshape(b, s, nkv, hd)
    v = qkv[..., h * 2 * hd + nkv * hd:].reshape(b, s, nkv, hd)
    q = rms_norm_zero_centered(qg[..., :hd], p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm_zero_centered(k, p["k_norm"], cfg.rms_norm_eps)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return (rope_rotate_leading(q, cos, sin), rope_rotate_leading(k, cos, sin), v,
            (qg[..., hd:].reshape(b, s, h * hd),))


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
        return hybrid.swiglu(rows, at(experts["e_wgu"]), at(experts["e_wd"]))

    with jax.named_scope("moe_experts"):
        y, counts = dropless_experts(
            xf, top_i, top_w, expert_ffn, cfg.n_held, lo=cfg.experts_held[0], listed=True,
            walk=experts_walk(SWIGLU, (experts["e_wgu"], experts["e_wd"]), li, burst=s == 1))
    with jax.named_scope("moe_shared"):  # on x [B, S, d]: its products keep three axes
        gate = jax.nn.sigmoid(einsum_f32("bsd,de->bse", x, p["s_gate"]))
        y = y.reshape(b, s, d) + gate * hybrid.swiglu(x, p["s_wgu"], p["s_wd"])
    stats = jnp.stack([(counts > 0).sum(), counts.sum()]).astype(jnp.int32)
    return y, stats


def _split(params: dict):
    """(the mixers' and the expert layers' small leaves, as stacked; the routed
    experts' whole stacks).  Nothing is a scan's xs and nothing is reshaped by
    period: a layer takes its own weights out of the flat stacks with ONE index
    (``hybrid.at``), static in the burst (a view) and traced in the wave (a slice the
    product reads through).  Sliced by a scan, or taken as ``[period][j]``, the
    v5e compiler copied a period's Gated DeltaNet projections whole, 150 MB,
    every step (PERF.md, Findings, PR 34)."""
    experts = {k: params["moe"][k] for k in ("e_wgu", "e_wd")}
    moe = {k: v for k, v in params["moe"].items() if k not in experts}
    return {"gdn": params["gdn"], "attn": params["attn"], "moe": moe}, experts


def _head(params, h):
    return einsum_f32("bsd,dv->bsv", h, params["lm_head"])


class _Layers:
    """This model's layers, as models/hybrid.py's skeleton asks for them.  The
    functions are looked up in this module when they are called (tests patch
    ``_gdn_inputs`` and ``ACT``)."""

    attn_window = ATTN_WINDOW
    weights = staticmethod(lambda params: _split(params))
    step_scope = "gdn_recurrent"
    state_weights = staticmethod(lambda w, g: hybrid.at(w[0]["gdn"], g))
    state_chunk = staticmethod(lambda *a: hybrid.gdn_chunk(_Layers, *a))
    state_step = staticmethod(lambda *a: hybrid.gdn_step(_Layers, *a))
    state_step_in_pool = staticmethod(lambda *a: hybrid.gdn_step_in_pool(_Layers, *a))
    attn_weights = staticmethod(lambda w, pi: hybrid.at(w[0]["attn"], pi))
    gdn_inputs = staticmethod(lambda cfg, p, x: _gdn_inputs(cfg, p, x))
    gdn_out = staticmethod(lambda cfg, p, o, z: hybrid.gdn_out(cfg, p, o, z, ACT))
    attn_project = staticmethod(lambda cfg, p, x, cos, sin: _attn_project(cfg, p, x, cos, sin))
    attn_out = staticmethod(lambda p, attn, gate: _attn_out(p, attn, gate))
    head = staticmethod(lambda cfg, params, h: _head(params, h))

    @staticmethod
    def embed(cfg, params, ids):
        return embedding_lookup(params["embed"], ids).astype(jnp.float32)

    @staticmethod
    def position_cols(cfg, positions):
        return rope_cos_sin(positions, cfg.rotary_dim, cfg.rope_theta)

    @staticmethod
    def mixer_input(cfg, w, li, h):
        return _norm(cfg, h, hybrid.at(w[0]["moe"]["ln1"], li))

    @staticmethod
    def after_mixer(cfg, w, li, h, y, live):
        """The mixer's residual add, then the expert layer and its own."""
        scan_p, experts = w
        pm = hybrid.at(scan_p["moe"], li)
        h = h + y
        y, st = _moe_ffn(cfg, pm, experts, li, _norm(cfg, h, pm["ln2"]), live)
        return h + y, st

    @staticmethod
    def final(cfg, params, h):
        return _norm(cfg, h, params["norm"])


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
    """Unjitted body of ``forward_paged``, traced into the wave program too:
    the shared skeleton (models/hybrid.wave) over this model's layers."""
    return hybrid.wave(_Layers, params, cfg, input_ids, positions, k_pages, v_pages,
                       slot_mapping, block_tables, cached_lens, new_lens, state, state_src,
                       state_dst, state_snap, snap_col, use_pallas, logits_at, width)


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
    contract: the shared skeleton (models/hybrid.burst) over this model's
    layers.  Returns (packed tokens [B, n_steps], valid, k_pages, v_pages,
    presence, seq_lens, last_tokens, stats [2], state)."""
    return hybrid.burst(_Layers, params, cfg, last_tokens, seq_lens, k_pages, v_pages, presence,
                        active, row_limits, block_tables, rng, temperature, top_p, top_k,
                        repetition_penalty, n_steps, use_pallas, filter_sampling, first_tokens,
                        fresh, fresh_lens, key_step, state)
