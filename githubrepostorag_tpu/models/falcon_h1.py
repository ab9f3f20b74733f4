"""Falcon-H1 decoder (``falcon_h1``, tiiuae/Falcon-H1-34B-Instruct): a hybrid
whose EVERY layer runs two mixers side by side on one normed input and adds
both to the residual stream, then a dense SwiGLU:

    x = RMSNorm(h; input_layernorm)
    m = ssm_out_multiplier       * Mamba2(x * ssm_in_multiplier)
    a = attention_out_multiplier * Attention(x * attention_in_multiplier)
    h = h + m + a
    h = h + mlp_multipliers[1] * W_down(silu(mlp_multipliers[0] * W_gate x2) * W_up x2),
        x2 = RMSNorm(h; pre_ff_layernorm)

* the Mamba-2 mixer is models/hybrid.py's (``ssm_*``, shared with
  models/nemotron_h.py): ``in_proj`` as ``z | x B C | dt``, the five runs of its
  columns scaled by ``ssm_multipliers`` (the published ``mup_vector``), a causal
  depthwise convolution WITH a bias, SiLU, ``dt = softplus(dt + dt_bias)``, the
  state ``[heads, head width, state size]`` float32 with ``B`` and ``C`` shared
  by a group of heads, the skip ``D``, the output norm GATE FIRST and BY GROUP
  (``mamba_rms_norm``, ``mamba_norm_before_gate`` false), ``out_proj``;
* attention is grouped-query, no bias, rotary over the WHOLE head at
  ``rope_theta`` (1e11: the tables are float32, ops/rope.py), keys times
  ``key_multiplier`` before the rotation and the pages, scale ``head_dim^-1/2``;
* the embedding's rows times ``embedding_multiplier``, the logits times
  ``lm_head_multiplier``, an untied head.

So every layer keeps pages AND a slot of state: ``kv_layers == state_layers ==
num_layers``, the layer kind models/hybrid.py calls ``BOTH``.  The two step
programs and both pools are that skeleton's; this file is the model's own part.

The multipliers are applied where the published module applies them, on the
float32 RESULT of a product, with two exceptions that are the same numbers:
``ssm_in_multiplier`` and ``attention_in_multiplier`` scale a product's INPUT
there (``in_proj(x * 0.25)``); here they are folded into the scale of the
product's result (``in_proj(x) * (0.25 * mup)``), one multiply of a float32
where the published bfloat16 module rounds ``x * c`` first: the product is
linear, so the values agree to that rounding (none for 0.25 and 1, powers of
two).  No multiplier is folded into a leaf.

The residual stream is float32 (a departure this repo makes for every family),
the state float32, the convolution's history and the pages bfloat16, products
take bfloat16 operands.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from githubrepostorag_tpu.models import hybrid
from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.ops.latent_attention import einsum_f32
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate
from githubrepostorag_tpu.ops.sampling import first_token_tail

ACT = jnp.bfloat16  # products take bfloat16 operands; the residual stream is float32
# columns of a prefill chunk one call of the attention kernel takes: 5 query
# heads of 128 a kv head, so a whole chunk's queries, accumulator and softmax
# state are 2.7 MB of VMEM
ATTN_WINDOW = 512


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    mamba_num_heads: int = 32
    mamba_head_dim: int = 128  # heads x width = ``mamba_d_ssm`` (4,096); ``mamba_expand`` sizes nothing
    ssm_state_size: int = 256
    n_groups: int = 2
    conv_kernel: int = 4
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    # the muP multipliers, as published
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                              0.3535533905932738)  # z | x | B | C | dt
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)  # gate, down
    # the initialiser's step sizes (the published module's constants; no config key)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    # what the serving engine asks of a model: the module whose step programs
    # serve it, per-sequence state beside the pages (serving/kv_cache.StateSlots),
    # no expert counters, and the most rows one prefill wave carries
    step_programs = "githubrepostorag_tpu.models.falcon_h1"
    recurrent_state = True
    expert_counters = False
    prefill_rows_cap = 8

    @property
    def layer_segments(self) -> tuple:
        """The layer pattern models/hybrid.py walks: one layer of both caches, every time."""
        return ((hybrid.BOTH, self.num_layers),)

    @property
    def kv_layers(self) -> int:
        """Layers that page keys and values: all of them."""
        return self.num_layers

    @property
    def state_layers(self) -> int:
        """Layers that keep a slot of state a sequence: all of them."""
        return self.num_layers

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """The convolution runs over [x | B | C]."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def state_cols(self) -> int:
        return self.ssm_state_size

    @property
    def ssm_scales(self) -> tuple:
        """What in_proj's float32 results are multiplied by, ``(z, (x, B, C),
        dt)``: the published ``mup_vector``'s five runs, each times
        ``ssm_in_multiplier`` (module docstring)."""
        z, x, b, c, dt = (self.ssm_in_multiplier * v for v in self.ssm_multipliers)
        return z, (x, b, c), dt

    def state_shapes(self) -> dict:
        """One slot of one layer: (shape, dtype) by name.  The state's last
        axis is the state size, 256: two whole lane tiles, so the pool lies
        row-major on a v5e as it is; the history's taps lie side by side in one
        row (models/qwen3_next.py)."""
        return {
            "s": ((self.mamba_num_heads, self.mamba_head_dim,
                   hybrid.lane_padded(self.ssm_state_size)), jnp.dtype(jnp.float32)),
            "conv": (((self.conv_kernel - 1) * self.conv_channels,), jnp.dtype(ACT)),
        }

    @classmethod
    def tiny(cls, **kw) -> "FalconH1Config":
        """Test widths that keep what is new: both mixers in every layer, 2
        groups, 5 query heads a kv head, a state wider than its head, 4 taps
        with a bias, every multiplier away from one."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=3, num_heads=10,
            num_kv_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, max_position_embeddings=1024, attention_in_multiplier=0.75,
            attention_out_multiplier=0.6, key_multiplier=0.4, ssm_out_multiplier=0.7,
            mlp_multipliers=(0.8, 0.3), embedding_multiplier=3.0, lm_head_multiplier=0.25,
            # 64 columns give in_proj an eighth of what 5,120 give it: x, B and C back to the
            # order of one, so that the state's part of the mixer stands beside its skip
            ssm_in_multiplier=0.5, ssm_multipliers=(0.8, 2.0, 1.5, 2.5, 0.6))
        return cls(**{**base, **kw})


# ------------------------------------------------------------------ weights --

# A draw has std ~0.02.  Under the published multipliers (0.0375, 0.088 and
# 0.011 on the branches' outputs, 0.011 on the keys, 5.66 on the embedding) such
# leaves leave every branch a few thousandths of the embedding, the attention
# uniform and the state's part of the mixer four hundred times smaller than its
# skip ``D x``: a wrong branch or a wrong state would pass any comparison.  The
# gains below (powers of two: exact) stand where a trained checkpoint's larger
# weights stand, and make each branch about the embedding's size
# (benchmarks/configs/falcon-h1-34b-bf16.json gives the arithmetic and the
# three knock-outs that hold it).
GAINS = {
    "w_z": 8.0,  # the gate's argument to std ~1: SiLU is not its tangent
    "w_xbc": 16.0,  # x, B, C to the order of one after the convolution: S C stands beside D x
    "w_dt": 8.0,  # dt + dt_bias moves a step by e^+-1: the decay depends on the token
    "conv_w": 16.0,  # the taps: std ~0.32 (models/qwen3_next.py)
    "conv_b": 4.0,  # the convolution's bias: std ~0.08
    "wk": 128.0,  # against key_multiplier 0.011: scores of std ~2.9, a softmax that chooses
    "wo": 4.0,  # against attention_out_multiplier 0.0375
    "w_gate": 4.0,  # against mlp_multipliers[0] 0.177: the gate's argument to std ~1
    "wd": 4.0,  # against mlp_multipliers[1] 0.011
}


def leaf_order(cfg: FalconH1Config) -> list:
    """(path, shape, gain) of every leaf the initialiser draws, in draw order
    (models/hybrid.draw_leaves).  ``a_u`` and ``dt_u`` are the uniform draws
    ``A_log`` and ``dt_bias`` are made from (``hybrid.ssm_scalars``).  in_proj is
    drawn as the three runs of columns the mixer reads apart.  The benchmark's
    reference re-states this list."""
    d, L, ff = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    di, c, mh = cfg.d_inner, cfg.conv_channels, cfg.mamba_num_heads
    g = GAINS
    return [
        (("embed",), (cfg.vocab_size, d), 1.0),
        (("lm_head",), (d, cfg.vocab_size), 1.0),
        (("ssm", "w_z"), (L, d, di), g["w_z"]),
        (("ssm", "w_xbc"), (L, d, c), g["w_xbc"]),
        (("ssm", "w_dt"), (L, d, mh), g["w_dt"]),
        (("ssm", "conv_w"), (L, c, cfg.conv_kernel), g["conv_w"]),
        (("ssm", "conv_b"), (L, c), g["conv_b"]),
        (("ssm", "a_u"), (L, mh), 1.0),
        (("ssm", "dt_u"), (L, mh), 1.0),
        (("ssm", "w_out"), (L, di, d), 1.0),
        (("attn", "wq"), (L, d, h * hd), 1.0),
        (("attn", "wk"), (L, d, nkv * hd), g["wk"]),
        (("attn", "wv"), (L, d, nkv * hd), 1.0),
        (("attn", "wo"), (L, h * hd, d), g["wo"]),
        (("mlp", "w_gate"), (L, d, ff), g["w_gate"]),
        (("mlp", "w_up"), (L, d, ff), 1.0),
        (("mlp", "wd"), (L, ff, d), g["wd"]),
    ]


@startup.records("startup.weights", settle=True)
def init_params(cfg: FalconH1Config, seed: int = 0) -> dict:
    """Weights made on the device from the seed, leaf by leaf, in bfloat16, as
    the other hybrids' are.  Every norm at one, the skip ``D`` at one (the
    published initialiser's), ``A_log`` and ``dt_bias`` as
    ``hybrid.ssm_scalars`` says.  ``wq | wk | wv`` are laid side by side as the
    one product the attention branch runs; gate and up stay two leaves (a layer's
    pair is 440 MB: side by side they would be copied to be made so)."""
    params = hybrid.draw_leaves(leaf_order(cfg), seed)
    ssm, attn = params["ssm"], params["attn"]
    attn["wqkv"] = jnp.concatenate([attn.pop("wq"), attn.pop("wk"), attn.pop("wv")], axis=-1)
    ssm["A_log"], ssm["dt_bias"] = hybrid.ssm_scalars(cfg, ssm.pop("a_u"), ssm.pop("dt_u"))
    L, d = cfg.num_layers, cfg.hidden_size
    ssm.update(D=jnp.ones((L, cfg.mamba_num_heads), jnp.float32),
               o_norm=jnp.ones((L, cfg.d_inner), jnp.bfloat16))
    params.update(norm=jnp.ones((d,), jnp.bfloat16), ln=jnp.ones((L, d), jnp.bfloat16),
                  ln_ff=jnp.ones((L, d), jnp.bfloat16))
    return params


# ------------------------------------------------------------------- layers --

def _norm(cfg, x, w):
    return rms_norm(x, w, cfg.rms_norm_eps).astype(ACT)


def _attn_project(cfg, p, x, cos, sin):
    """x [B, S, d] normed -> (q [B, S, H, hd] and k [B, S, n_kv, hd] scaled and
    rotated over the whole head, v [B, S, n_kv, hd], ()).  The multipliers
    meet the product's float32 result; the rotation is float32 too."""
    b, s, _ = x.shape
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        qkv = einsum_f32("bsd,de->bse", x, p["wqkv"])
        a_in = cfg.attention_in_multiplier
        q = qkv[..., :h * hd].reshape(b, s, h, hd)
        k = qkv[..., h * hd:(h + nkv) * hd].reshape(b, s, nkv, hd) * (a_in * cfg.key_multiplier)
        v = qkv[..., (h + nkv) * hd:].reshape(b, s, nkv, hd)
        if a_in != 1.0:
            q, v = q * a_in, v * a_in
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        return (rope_rotate(q, cos, sin).astype(ACT), rope_rotate(k, cos, sin).astype(ACT),
                v.astype(ACT), ())


def _attn_out(p, attn):
    with jax.named_scope("attn_proj"):
        return einsum_f32("bse,ed->bsd", attn.reshape(*attn.shape[:2], -1), p["wo"])


def _after_mixer(cfg, w, li, h, y, live):
    """The two branches' outputs, each times its multiplier, in ONE residual
    add; then the dense SwiGLU and its add.  A layer's weights are indexed
    here, inside the wave's branch (ops/prefill_width.py)."""
    ssm_y, attn_y = y
    with jax.named_scope("branch_sum"):
        h = h + (ssm_y * cfg.ssm_out_multiplier + attn_y * cfg.attention_out_multiplier)
    pm = hybrid.at(w["mlp"], li)
    with jax.named_scope("dense_mlp"):
        x = _norm(cfg, h, hybrid.at(w["ln_ff"], li))
        gate = einsum_f32("...d,df->...f", x, pm["w_gate"]) * cfg.mlp_multipliers[0]
        mid = (jax.nn.silu(gate) * einsum_f32("...d,df->...f", x, pm["w_up"])).astype(ACT)
        h = h + einsum_f32("...f,fd->...d", mid, pm["wd"]) * cfg.mlp_multipliers[1]
    return h, None


class _Layers:
    """This model's layers, as models/hybrid.py's skeleton asks for them.  The
    functions are looked up in this module when they are called (tests patch
    ``ACT``)."""

    attn_window = ATTN_WINDOW
    step_scope = "ssm_recurrent"
    weights = staticmethod(lambda params: params)
    state_weights = staticmethod(lambda w, n: hybrid.at(w["ssm"], n))
    attn_weights = staticmethod(lambda w, n: hybrid.at(w["attn"], n))
    # the Mamba-2 mixer is models/hybrid.py's, shared with models/nemotron_h.py
    state_chunk = staticmethod(lambda *a: hybrid.ssm_chunk(_Layers, *a))
    state_step = staticmethod(lambda *a: hybrid.ssm_step(_Layers, *a))
    state_step_in_pool = staticmethod(lambda *a: hybrid.ssm_step_in_pool(_Layers, *a))
    ssm_inputs = staticmethod(lambda cfg, p, x: hybrid.ssm_inputs(cfg, p, x, ACT, cfg.ssm_scales))
    ssm_out = staticmethod(lambda cfg, p, y, z: hybrid.ssm_out(cfg, p, y, z, ACT))
    attn_project = staticmethod(lambda cfg, p, x, cos, sin: _attn_project(cfg, p, x, cos, sin))
    attn_out = staticmethod(lambda p, attn: _attn_out(p, attn))
    after_mixer = staticmethod(lambda *a: _after_mixer(*a))

    @staticmethod
    def embed(cfg, params, ids):
        return embedding_lookup(params["embed"], ids).astype(jnp.float32) * cfg.embedding_multiplier

    @staticmethod
    def position_cols(cfg, positions):
        """The rotary tables, float32.  The inverse frequencies (down to 1.5e-11 at
        theta 1e11) are made in float64 on the host and rounded once: a float32
        power on the device is good to ~1e-6 of an angle that reaches 1e5 rad."""
        hd = cfg.head_dim
        inv_freq = 1.0 / cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
        return rope_cos_sin(positions, hd, inv_freq=jnp.asarray(inv_freq, jnp.float32))

    @staticmethod
    def mixer_input(cfg, w, li, h):
        return _norm(cfg, h, hybrid.at(w["ln"], li))

    @staticmethod
    def final(cfg, params, h):
        return _norm(cfg, h, params["norm"])

    @staticmethod
    def head(cfg, params, h):
        return einsum_f32("bsd,dv->bsv", h, params["lm_head"]) * cfg.lm_head_multiplier


# ----------------------------------------------------------- step programs --

@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5), donate_argnames=("state",))
def forward_paged(
    params: dict,
    cfg: FalconH1Config,
    input_ids: jnp.ndarray,  # [B, S] int32, right-padded per row
    positions: jnp.ndarray,  # [B, S] int32 absolute positions (the rotary tables)
    k_pages: jnp.ndarray,  # [layers, n_kv, P, page_size, hd] (donated)
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
    the pools (models/hybrid.wave).  Returns (logits, k_pages, v_pages,
    state)."""
    logits, k_pages, v_pages, _, state = hybrid.wave(
        _Layers, params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
        cached_lens, new_lens, state, state_src, state_dst, state_snap, snap_col, use_pallas,
        logits_at)
    return logits, k_pages, v_pages, state


@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5, 6), donate_argnames=("state",))
def forward_paged_wave(
    params: dict,
    cfg: FalconH1Config,
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
    Returns (first_tokens, presence, k_pages, v_pages, state)."""
    logits, k_pages, v_pages, _, state = hybrid.wave(
        _Layers, params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
        cached_lens, new_lens, state, state_src, state_dst, state_snap, snap_col, use_pallas,
        logits_at, width)
    with jax.named_scope("sample"):
        first_tokens, presence = first_token_tail(
            logits[:, 0], presence, first_tokens, input_ids, new_lens, row_idx, done_mask,
            jax.random.fold_in(rng, key_step), temperature, top_p, top_k, repetition_penalty)
    return first_tokens, presence, k_pages, v_pages, state


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "use_pallas", "mesh", "layer_unroll",
                          "filter_sampling"),
         donate_argnums=(4, 5, 6), donate_argnames=("state",))
def decode_burst(
    params: dict,
    cfg: FalconH1Config,
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
    contract (models/hybrid.burst).  Returns (packed tokens [B, n_steps],
    valid, k_pages, v_pages, presence, seq_lens, last_tokens, state)."""
    *out, _, state = hybrid.burst(
        _Layers, params, cfg, last_tokens, seq_lens, k_pages, v_pages, presence, active,
        row_limits, block_tables, rng, temperature, top_p, top_k, repetition_penalty, n_steps,
        use_pallas, filter_sampling, first_tokens, fresh, fresh_lens, key_step, state)
    return (*out, state)
