"""Nemotron-H decoder (``nemotron_h``, nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B):
a hybrid whose block is ONE sublayer, ``h = h + mixer(RMSNorm(h))``, of three
kinds in the order a pattern string gives (``hybrid_override_pattern``):

* ``M``, Mamba-2: a state-space layer (ops/ssd.py; the mixer itself is
  models/hybrid.py's ``ssm_*``, shared with models/falcon_h1.py).  ``in_proj`` gives the gate
  ``z``, the convolution's input ``x | B | C`` and a step size a head; a causal
  depthwise convolution WITH a bias, then SiLU; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the state ``S [heads, head width, state size]`` float32,
  ``B`` and ``C`` shared by a GROUP of heads, the skip ``D``; then the output
  norm GATE FIRST and BY GROUP (ops/norms.rms_norm_gate_first) and ``out_proj``.
  ``d_inner = heads x head width``: the config's ``expand`` sizes nothing;
* ``E``, experts: sigmoid scores over all the experts in float32, a selection
  bias that enters the choice and not the weight (models/moe.route_noaux_tc at
  one group), top k, normalised, scaled; an expert is NOT gated, ``W_down
  relu(W_up x)^2``, two products; one shared expert of the same form, added
  unweighted.  The layer is told which experts it holds (``experts_held``):
  the router scores all of them, the layer computes its own
  (models/moe.dropless_experts) and adds nothing for the others;
* ``*``, attention: grouped-query, no bias, NO rotary (positions do not
  enter), through the paged pools and kernels.

The two step programs, the K/V pools of the ``*`` layers and the state pool of
the ``M`` layers are models/hybrid.py's; this file is the model's own part.
``vocab_size`` may be a slice of the published vocabulary.  The residual
stream is float32 (the config's ``residual_in_fp32`` is false: a departure
this repo makes for every family, for the router's sake), products take
bfloat16 operands.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models import hybrid
from githubrepostorag_tpu.models.moe import dropless_experts, route_noaux_tc
from githubrepostorag_tpu.models.quant import embedding_lookup
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.ops.latent_attention import einsum_f32
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.pallas_experts import RELU2, experts_walk
from githubrepostorag_tpu.ops.sampling import first_token_tail

ACT = jnp.bfloat16  # products take bfloat16 operands; the residual stream is float32
# columns of a prefill chunk one call of the attention kernel takes: 16 query
# heads of 128 a kv head, so a window's queries, accumulator and softmax state
# are 8.4 MB of VMEM at 256 columns (models/hybrid.wave)
ATTN_WINDOW = 256
KINDS = {"M": hybrid.STATE, "E": hybrid.PLAIN, "*": hybrid.ATTN}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # a letter a block
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    num_experts: int = 128  # the router's width: every expert it scores
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    shared_expert_intermediate_size: int = 3712
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    experts_held: tuple = (0, 128)  # [first, past the last) of num_experts

    # what the serving engine asks of a model: the module whose step programs
    # serve it, per-sequence state beside the pages (serving/kv_cache.StateSlots),
    # expert counters, and the most rows one prefill wave carries
    step_programs = "githubrepostorag_tpu.models.nemotron_h"
    recurrent_state = True
    expert_counters = True
    prefill_rows_cap = 8

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def layer_segments(self) -> tuple:
        """The layer pattern models/hybrid.py walks, in its letters, cut where it repeats."""
        return hybrid.segments("".join(KINDS[k] for k in self.pattern))

    @property
    def kv_layers(self) -> int:
        """Layers that page keys and values: the ``*`` ones."""
        return self.pattern.count("*")

    @property
    def state_layers(self) -> int:
        """Layers that keep a slot of state a sequence: the ``M`` ones."""
        return self.pattern.count("M")

    @property
    def expert_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

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

    def state_shapes(self) -> dict:
        """One slot of one Mamba-2 layer: (shape, dtype) by name.  The state's
        last axis is the state size, 128: a whole lane tile, so the pool lies
        row-major on a v5e as it is (models/hybrid.lane_padded pads one that is
        not); the history's taps lie side by side in one row
        (models/qwen3_next.py)."""
        return {
            "s": ((self.mamba_num_heads, self.mamba_head_dim,
                   hybrid.lane_padded(self.ssm_state_size)), jnp.dtype(jnp.float32)),
            "conv": (((self.conv_kernel - 1) * self.conv_channels,), jnp.dtype(ACT)),
        }

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """Test widths that keep what is new: the three kinds in a pattern that
        repeats and then does not, groups of heads, 16 query heads a kv head is
        cut to 4, an expert width that is no multiple of the hidden size."""
        base = dict(
            vocab_size=512, hidden_size=64, pattern="MEM*EMEM*EME", num_heads=8, num_kv_heads=2,
            head_dim=16, mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=24,
            shared_expert_intermediate_size=48, max_position_embeddings=1024,
            experts_held=(0, 16))
        return cls(**{**base, **kw})


# ------------------------------------------------------------------ weights --

ROUTER_GAIN = 2.0  # the router's draw, times this: logits of std ~2.1 (sigmoid scores unsaturated)
CONV_GAIN = 16.0  # the convolution's taps, times this: std ~0.32 (models/qwen3_next.py)
BIAS_GAIN = 4.0  # the convolution's bias, times this: std ~0.08


def leaf_order(cfg: NemotronHConfig) -> list:
    """(path, shape, gain) of every leaf the initialiser draws, in draw order:
    each draw advances the salt once.  A draw is a bfloat16 leaf of std ~0.02
    times ``gain`` (a power of two: exact).  ``a_u`` and ``dt_u`` are the
    uniform draws ``A_log`` and ``dt_bias`` are made from (``hybrid.ssm_scalars``),
    ``e_bias`` the router's selection bias (float32, std 0.02: it reorders near
    neighbours and gives no expert a following of its own,
    models/deepseek_v3.py).  The benchmark's reference re-states this list."""
    d, M, A, E = cfg.hidden_size, cfg.state_layers, cfg.kv_layers, cfg.expert_layers
    h, nkv, hd, n = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.n_held
    di, c, mh = cfg.d_inner, cfg.conv_channels, cfg.mamba_num_heads
    ffe, ffs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    return [
        (("embed",), (cfg.vocab_size, d), 1.0),
        (("lm_head",), (d, cfg.vocab_size), 1.0),
        (("ssm", "w_z"), (M, d, di), 1.0),
        (("ssm", "w_xbc"), (M, d, c), 1.0),
        (("ssm", "w_dt"), (M, d, mh), 1.0),
        (("ssm", "conv_w"), (M, c, cfg.conv_kernel), CONV_GAIN),
        (("ssm", "conv_b"), (M, c), BIAS_GAIN),
        (("ssm", "a_u"), (M, mh), 1.0),
        (("ssm", "dt_u"), (M, mh), 1.0),
        (("ssm", "w_out"), (M, di, d), 1.0),
        (("attn", "wq"), (A, d, h * hd), 1.0),
        (("attn", "wk"), (A, d, nkv * hd), 1.0),
        (("attn", "wv"), (A, d, nkv * hd), 1.0),
        (("attn", "wo"), (A, h * hd, d), 1.0),
        (("moe", "router"), (E, d, cfg.num_experts), ROUTER_GAIN),
        (("moe", "e_bias"), (E, cfg.num_experts), 1.0),
        (("moe", "e_wu"), (E, n, ffe, d), 1.0),  # W_up as published, [out, in]: see relu2_ffn
        (("moe", "e_wd"), (E, n, ffe, d), 1.0),
        (("moe", "s_wu"), (E, ffs, d), 1.0),
        (("moe", "s_wd"), (E, ffs, d), 1.0),
    ]


@startup.records("startup.weights", settle=True)
def init_params(cfg: NemotronHConfig, seed: int = 0) -> dict:
    """Weights made on the device from the seed, leaf by leaf, in bfloat16
    (models/quant._devrand), as the other hybrids' are.  Every norm at one,
    the skip ``D`` at one (the published initialiser's), ``A_log`` and
    ``dt_bias`` as ``hybrid.ssm_scalars`` says.  The expert stacks hold the
    ``experts_held`` range only.  ``wq | wk | wv`` are laid side by side as the
    one product the attention layer runs; the Mamba-2 projections stay three
    leaves, each read whole by its own product in either program."""
    params = hybrid.draw_leaves(leaf_order(cfg), seed)
    params["norm"] = jnp.ones((cfg.hidden_size,), jnp.bfloat16)
    ssm, attn, moe = params["ssm"], params["attn"], params["moe"]
    attn["wqkv"] = jnp.concatenate([attn.pop("wq"), attn.pop("wk"), attn.pop("wv")], axis=-1)
    ssm["A_log"], ssm["dt_bias"] = hybrid.ssm_scalars(cfg, ssm.pop("a_u"), ssm.pop("dt_u"))
    ssm.update(D=jnp.ones((cfg.state_layers, cfg.mamba_num_heads), jnp.float32),
               o_norm=jnp.ones((cfg.state_layers, cfg.d_inner), jnp.bfloat16))
    moe["e_bias"] = moe["e_bias"].astype(jnp.float32)
    params["ln"] = jnp.ones((cfg.num_layers, cfg.hidden_size), jnp.bfloat16)
    return params


# ------------------------------------------------------------------- layers --

def _norm(cfg, x, w):
    return rms_norm(x, w, cfg.rms_norm_eps).astype(ACT)


def _attn_project(cfg, p, x):
    """x [B, S, d] normed -> (q [B, S, H, hd], k and v [B, S, n_kv, hd], ()):
    no bias, no rotary."""
    b, s, _ = x.shape
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = x @ p["wqkv"]
    return (qkv[..., :h * hd].reshape(b, s, h, hd),
            qkv[..., h * hd:(h + nkv) * hd].reshape(b, s, nkv, hd),
            qkv[..., (h + nkv) * hd:].reshape(b, s, nkv, hd), ())


def _attn_out(p, attn):
    return einsum_f32("bse,ed->bsd", attn.reshape(*attn.shape[:2], -1), p["wo"])


def relu2_ffn(x, wu, wd):
    """The ungated feed-forward of this family: ``W_down relu(W_up x)^2``, the
    down-projection accumulated and returned in float32.  ``wu`` is [f, d],
    the hidden width last, as ``wd`` [f, d] is: an expert width of 1,856 is no
    whole number of lane tiles, and a v5e stores a [.., 2688, 1856] stack with
    the 2,688 on the lanes whatever order its axes are given in, so a product
    that reads it as [d, f] first copies the whole 2.5 GB stack, every burst
    (tests/test_nemotron_h_compile.py)."""
    u = jax.nn.relu(jnp.einsum("...d,fd->...f", x, wu))
    return einsum_f32("...f,fd->...d", u * u, wd)


def _moe_ffn(cfg, p, experts: dict, n, x: jnp.ndarray, live):
    """x [B, S, d] normed -> (y [B, S, d] float32, [experts hit, pairs to held
    experts]).  ``experts`` holds the whole [E layers, n_held, ...] stacks and
    ``n`` the layer among the expert layers: an expert's weights are read where
    they lie.  ``live`` [B, S] marks real tokens: padding wakes no expert."""
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
            w, (n, e, 0, 0), (1, 1, *w.shape[2:]))[0, 0]
        return relu2_ffn(rows, at(experts["e_wu"]), at(experts["e_wd"]))

    # 32 held experts of 20 MB lie between DeepSeek-V3's 16 of 88 MB (the plain scan) and
    # Qwen3-Next's 128 of 6 MB.  The burst's 32 rows and a one-row wave of 128 columns take, on the
    # chip, the walk over the hit experts (``RELU2``: both stacks read by rows, as stored); a wider
    # wave the listed tile loop, whose eight-row wave took 114.6 ms on a v5e against the scan's
    # 131.6 (PERF.md, PR 41 and PR 58)
    with jax.named_scope("moe_experts"):
        y, counts = dropless_experts(
            xf, top_i, top_w, expert_ffn, cfg.n_held, lo=cfg.experts_held[0], listed=True,
            walk=experts_walk(RELU2, (experts["e_wu"], experts["e_wd"]), n, burst=s == 1))
    with jax.named_scope("moe_shared"):  # always on, no gate
        y = y.reshape(b, s, d) + relu2_ffn(x, p["s_wu"], p["s_wd"])
    stats = jnp.stack([(counts > 0).sum(), counts.sum()]).astype(jnp.int32)
    return y, stats


def _split(params: dict):
    """(the small leaves, as stacked; the routed experts' whole stacks): a
    layer takes its own weights out of the flat stacks with ONE index
    (``hybrid.at``), static in the burst and traced in the wave
    (models/qwen3_next._split says what the compiler does otherwise)."""
    experts = {k: params["moe"][k] for k in ("e_wu", "e_wd")}
    moe = {k: v for k, v in params["moe"].items() if k not in experts}
    return {"ssm": params["ssm"], "attn": params["attn"], "moe": moe, "ln": params["ln"]}, experts


class _Layers:
    """This model's layers, as models/hybrid.py's skeleton asks for them.  The
    functions are looked up in this module when they are called (tests patch
    ``ACT``)."""

    attn_window = ATTN_WINDOW
    step_scope = "ssm_recurrent"
    weights = staticmethod(lambda params: _split(params))
    state_weights = staticmethod(lambda w, n: hybrid.at(w[0]["ssm"], n))
    attn_weights = staticmethod(lambda w, n: hybrid.at(w[0]["attn"], n))
    # the Mamba-2 mixer is models/hybrid.py's, shared with models/falcon_h1.py
    state_chunk = staticmethod(lambda *a: hybrid.ssm_chunk(_Layers, *a))
    state_step = staticmethod(lambda *a: hybrid.ssm_step(_Layers, *a))
    state_step_in_pool = staticmethod(lambda *a: hybrid.ssm_step_in_pool(_Layers, *a))
    ssm_inputs = staticmethod(lambda cfg, p, x: hybrid.ssm_inputs(cfg, p, x, ACT))
    ssm_out = staticmethod(lambda cfg, p, y, z: hybrid.ssm_out(cfg, p, y, z, ACT))
    attn_project = staticmethod(lambda cfg, p, x: _attn_project(cfg, p, x))
    attn_out = staticmethod(lambda p, attn: _attn_out(p, attn))
    position_cols = staticmethod(lambda cfg, positions: ())  # positions do not enter
    after_mixer = staticmethod(lambda cfg, w, li, h, y, live: (h + y, None))

    @staticmethod
    def embed(cfg, params, ids):
        return embedding_lookup(params["embed"], ids).astype(jnp.float32)

    @staticmethod
    def mixer_input(cfg, w, li, h):
        return _norm(cfg, h, hybrid.at(w[0]["ln"], li))

    @staticmethod
    def plain_layer(cfg, w, n, li, h, live):
        """An ``E`` block whole: the norm, the expert layer, the residual add."""
        small, experts = w
        y, st = _moe_ffn(cfg, hybrid.at(small["moe"], n), experts, n,
                         _norm(cfg, h, hybrid.at(small["ln"], li)), live)
        return h + y, st

    @staticmethod
    def final(cfg, params, h):
        return _norm(cfg, h, params["norm"])

    @staticmethod
    def head(cfg, params, h):
        return einsum_f32("bsd,dv->bsv", h, params["lm_head"])


# ----------------------------------------------------------- step programs --

@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5), donate_argnames=("state",))
def forward_paged(
    params: dict,
    cfg: NemotronHConfig,
    input_ids: jnp.ndarray,  # [B, S] int32, right-padded per row
    positions: jnp.ndarray,  # [B, S] int32 absolute positions (unused: no rotary)
    k_pages: jnp.ndarray,  # [* layers, n_kv, P, page_size, hd] (donated)
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
    the pools (models/hybrid.wave).  Returns (logits, k_pages, v_pages, the
    expert layers' counts [2], state)."""
    return hybrid.wave(
        _Layers, params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
        cached_lens, new_lens, state, state_src, state_dst, state_snap, snap_col, use_pallas,
        logits_at)


@partial(jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
         donate_argnums=(4, 5, 6), donate_argnames=("state",))
def forward_paged_wave(
    params: dict,
    cfg: NemotronHConfig,
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
    Returns (first_tokens, presence, k_pages, v_pages, counts [2], state)."""
    logits, k_pages, v_pages, stats, state = hybrid.wave(
        _Layers, params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping, block_tables,
        cached_lens, new_lens, state, state_src, state_dst, state_snap, snap_col, use_pallas,
        logits_at, width)
    with jax.named_scope("sample"):
        first_tokens, presence = first_token_tail(
            logits[:, 0], presence, first_tokens, input_ids, new_lens, row_idx, done_mask,
            jax.random.fold_in(rng, key_step), temperature, top_p, top_k, repetition_penalty)
    return first_tokens, presence, k_pages, v_pages, stats, state


@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "use_pallas", "mesh", "layer_unroll",
                          "filter_sampling"),
         donate_argnums=(4, 5, 6), donate_argnames=("state",))
def decode_burst(
    params: dict,
    cfg: NemotronHConfig,
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
    valid, k_pages, v_pages, presence, seq_lens, last_tokens, counts [2],
    state)."""
    return hybrid.burst(
        _Layers, params, cfg, last_tokens, seq_lens, k_pages, v_pages, presence, active,
        row_limits, block_tables, rng, temperature, top_p, top_k, repetition_penalty, n_steps,
        use_pallas, filter_sampling, first_tokens, fresh, fresh_lens, key_step, state)
