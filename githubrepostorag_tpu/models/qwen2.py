"""Qwen2-family decoder in pure functional JAX.

Replaces the reference's out-of-tree vLLM serving model
(helm/templates/qwen-deployment.yaml:21-33 — image vllm/vllm-openai serving
Qwen2.5-Coder-7B-Instruct-AWQ) with an in-tree implementation designed for
TPU: bfloat16 activations on the MXU, stacked-layer params scanned with
``lax.scan``, grouped-query attention without materialized KV repetition,
and a cache interface the paged serving engine plugs into.

Architecture (matches HF ``Qwen2ForCausalLM``): token embedding, N blocks of
[RMSNorm -> GQA attention with QKV bias + RoPE -> residual, RMSNorm ->
SwiGLU MLP -> residual], final RMSNorm, (optionally tied) LM head.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.quant import (
    QuantizedEmbedding,
    QuantizedLinear,
    QuantizedLinear4,
    _split_q4,
    _with_layered_q4,
    dequant_weight,
    embedding_lookup,
    qmatmul,
)
from githubrepostorag_tpu.ops.attention import dense_attention
from githubrepostorag_tpu.ops.norms import rms_norm
from githubrepostorag_tpu.ops.prefill_width import at_wave_width, layer_weights
from githubrepostorag_tpu.ops.rope import apply_rope, rope_cos_sin
from githubrepostorag_tpu.ops.sampling import first_token_tail


@dataclass(frozen=True)
class Qwen2Config:
    vocab_size: int = 151936
    hidden_size: int = 896
    intermediate_size: int = 4864
    num_layers: int = 24
    num_heads: int = 14
    num_kv_heads: int = 2
    head_dim: int = 64
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 32768
    # ---- MoE (Qwen2-MoE family: Qwen1.5-MoE-A2.7B / Qwen2-57B-A14B) ------
    # num_experts 0 = dense; >0 switches every layer's MLP to the sparse
    # block (router top-k experts + always-on shared expert), models/moe.py
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = False

    # ---- presets (HF config.json values for the eval-config model family) --

    @classmethod
    def tiny(cls) -> "Qwen2Config":
        """Test-scale config (CI / parity tests)."""
        return cls(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=10_000.0,
            tie_word_embeddings=True,
            max_position_embeddings=512,
        )

    @classmethod
    def tiny_moe(cls) -> "Qwen2Config":
        """Test-scale MoE: 4 experts top-2 + shared expert."""
        return cls(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=10_000.0, tie_word_embeddings=True,
            max_position_embeddings=512,
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=48,
            shared_expert_intermediate_size=96, norm_topk_prob=True,
        )

    @classmethod
    def qwen1_5_moe_a2_7b(cls) -> "Qwen2Config":
        """Qwen/Qwen1.5-MoE-A2.7B geometry (60 experts top-4 + shared)."""
        return cls(
            vocab_size=151936, hidden_size=2048, intermediate_size=5632,
            num_layers=24, num_heads=16, num_kv_heads=16, head_dim=128,
            tie_word_embeddings=False,
            num_experts=60, num_experts_per_tok=4, moe_intermediate_size=1408,
            shared_expert_intermediate_size=5632, norm_topk_prob=False,
        )

    @classmethod
    def qwen2_0_5b(cls) -> "Qwen2Config":
        return cls(
            hidden_size=896, intermediate_size=4864, num_layers=24,
            num_heads=14, num_kv_heads=2, head_dim=64, tie_word_embeddings=True,
        )

    @classmethod
    def qwen2_1_5b(cls) -> "Qwen2Config":
        return cls(
            hidden_size=1536, intermediate_size=8960, num_layers=28,
            num_heads=12, num_kv_heads=2, head_dim=128, tie_word_embeddings=True,
        )

    @classmethod
    def qwen2_7b(cls) -> "Qwen2Config":
        return cls(
            hidden_size=3584, intermediate_size=18944, num_layers=28,
            num_heads=28, num_kv_heads=4, head_dim=128, tie_word_embeddings=False,
            vocab_size=152064,
        )


def init_params(cfg: Qwen2Config, key: jax.Array, dtype=jnp.float32) -> dict:
    """Random init (normal 0.02, the HF default) with stacked layer leaves."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, nq, nkv, hd, inter, L = (
        cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.intermediate_size, cfg.num_layers,
    )

    def norm(key, *shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * 0.02).astype(dtype)

    keys = jax.random.split(k_layers, 10)
    layers = {
        "ln1": jnp.ones((L, d), dtype=dtype),
        "ln2": jnp.ones((L, d), dtype=dtype),
        "wq": norm(keys[0], L, d, nq * hd),
        "bq": jnp.zeros((L, nq * hd), dtype=dtype),
        "wk": norm(keys[1], L, d, nkv * hd),
        "bk": jnp.zeros((L, nkv * hd), dtype=dtype),
        "wv": norm(keys[2], L, d, nkv * hd),
        "bv": jnp.zeros((L, nkv * hd), dtype=dtype),
        "wo": norm(keys[3], L, nq * hd, d),
    }
    if cfg.num_experts > 0:
        from githubrepostorag_tpu.models.moe import init_moe_layer_params

        layers.update(init_moe_layer_params(cfg, keys[9], dtype=dtype))
    else:
        layers.update({
            "wg": norm(keys[4], L, d, inter),
            "wu": norm(keys[5], L, d, inter),
            "wd": norm(keys[6], L, inter, d),
        })
    params = {
        "embed": norm(k_embed, cfg.vocab_size, d),
        "layers": layers,
        "norm": jnp.ones((d,), dtype=dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(k_head, d, cfg.vocab_size)
    return params


def _block(cfg: Qwen2Config, h, p, cos, sin, attend, reduce=None):
    """One transformer block.  ``attend(q, k, v) -> (attn_out, cache_info)``
    commits this step's K/V into whatever cache representation the caller
    uses (dense slab, page pool, or nothing) and returns the attention
    output.  Both the dense and paged forward paths share this body, so
    projection/RoPE/MLP changes cannot drift between them.

    ``reduce``: applied to the two row-parallel products (wo and wd) before
    the residual add.  Callers running this body INSIDE a shard_map with
    tensor-parallel weight shards (training/pipeline.py's tp-in-stage)
    pass ``lambda x: lax.psum(x, "tp")`` and a cfg whose head counts are
    the LOCAL per-shard counts; annotation-driven (GSPMD) callers leave it
    None — the compiler inserts the same psums from the param shardings."""
    b, s, d = h.shape
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if reduce is None:
        reduce = lambda x: x

    # named scopes (attn_proj, mlp; the callers' attend adds kv_write and
    # paged_attention, the head adds sample): the names a device trace's
    # ops carry in every step program, whatever a refactor renames
    with jax.named_scope("attn_proj"):
        hn = rms_norm(h, p["ln1"], cfg.rms_norm_eps)
        if "wqkv" in p:  # fused single-chip serving layout (quant.fuse_projections)
            qkv = qmatmul(hn, p["wqkv"]) + p["bqkv"]
            q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
            q = q.reshape(b, s, nq, hd)
            k = k.reshape(b, s, nkv, hd)
            v = v.reshape(b, s, nkv, hd)
        else:
            q = (qmatmul(hn, p["wq"]) + p["bq"]).reshape(b, s, nq, hd)
            k = (qmatmul(hn, p["wk"]) + p["bk"]).reshape(b, s, nkv, hd)
            v = (qmatmul(hn, p["wv"]) + p["bv"]).reshape(b, s, nkv, hd)
        q, k = apply_rope(q, k, cos, sin)

    attn, cache_info = attend(q, k, v)
    with jax.named_scope("attn_proj"):
        h = h + reduce(qmatmul(attn.reshape(b, s, nq * hd), p["wo"]))

    with jax.named_scope("mlp"):
        hn = rms_norm(h, p["ln2"], cfg.rms_norm_eps)
        if "router" in p:  # sparse MoE MLP (Qwen2-MoE family, models/moe.py)
            from githubrepostorag_tpu.models.moe import moe_mlp

            h = h + moe_mlp(cfg, p, hn)
        elif "wgu" in p:  # fused gate|up (quant.fuse_projections)
            g, u = jnp.split(qmatmul(hn, p["wgu"]), 2, axis=-1)
            h = h + reduce(qmatmul(jax.nn.silu(g) * u, p["wd"]))
        else:
            h = h + reduce(
                qmatmul(jax.nn.silu(qmatmul(hn, p["wg"])) * qmatmul(hn, p["wu"]), p["wd"])
            )
    return h, cache_info


@partial(jax.jit, static_argnames=("cfg",))
def forward(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32
    cache_k: jnp.ndarray | None = None,  # [L, B, S_cache, n_kv, hd]
    cache_v: jnp.ndarray | None = None,
    kv_lengths: jnp.ndarray | None = None,  # [B] tokens already cached
):
    """Full forward pass -> (logits [B, S, V] float32, (cache_k, cache_v)).

    Without a cache: plain causal attention over the input (training /
    scoring / parity tests).  With a cache: incremental prefill or decode —
    new K/V are written at each row's ``kv_lengths`` offset and attention
    covers the whole cache.

    Caller contract: ``kv_lengths + S`` must not exceed the cache's length
    axis.  ``dynamic_update_slice`` clamps out-of-range starts, which would
    silently corrupt the newest cache entries — the serving engine
    (serving/engine.py) enforces the bound before dispatch.
    """
    h = embedding_lookup(params["embed"], input_ids, dtype=_embed_dtype(params))
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    s = input_ids.shape[1]

    use_cache = cache_k is not None
    if use_cache:
        xs = (params["layers"], cache_k, cache_v)
    else:
        xs = (params["layers"],)

    def body(h, layer_xs):
        if use_cache:
            p, ck, cv = layer_xs

            def attend(q, k, v):
                # Commit new k/v at each row's current length, then attend
                # over the full cache with per-row validity masking.
                def write(cache, new, start):
                    return jax.lax.dynamic_update_slice(
                        cache, new.astype(cache.dtype), (start, 0, 0)
                    )

                new_ck = jax.vmap(write)(ck, k, kv_lengths)
                new_cv = jax.vmap(write)(cv, v, kv_lengths)
                attn = dense_attention(
                    q, new_ck, new_cv,
                    causal=True,
                    q_offset=kv_lengths,
                    kv_lengths=kv_lengths + s,
                )
                return attn, (new_ck, new_cv)

            h, cache_info = _block(cfg, h, p, cos, sin, attend)
            return h, cache_info

        (p,) = layer_xs
        h, _ = _block(
            cfg, h, p, cos, sin,
            lambda q, k, v: (dense_attention(q, k, v, causal=True, q_offset=0), None),
        )
        return h, None

    h, cache_out = jax.lax.scan(body, h, xs)
    h = rms_norm(h, params["norm"], cfg.rms_norm_eps)
    logits = _logits(params, h)

    if use_cache:
        new_k, new_v = cache_out
        return logits, (new_k, new_v)
    return logits, None


def forward_with_attend(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32
    attend_fn=None,
    remat: bool = False,
) -> jnp.ndarray:
    """Cache-free forward with a pluggable attention implementation.

    ``attend_fn(q, k, v) -> out`` defaults to causal dense attention; the
    training path passes ``parallel.make_ring_attend(...)`` so the sequence
    axis can live sharded over the ``sp`` mesh axis.  ``remat`` checkpoints
    each scanned layer, so backward holds one layer's activations at a time
    (peak HBM O(S) instead of O(S·L)).  Not jitted — callers jit (the train
    step jits the whole loss+grad program).  Returns logits [B, S, V] f32.
    """
    if attend_fn is None:
        attend_fn = lambda q, k, v: dense_attention(q, k, v, causal=True, q_offset=0)

    h = embedding_lookup(params["embed"], input_ids, dtype=_embed_dtype(params))
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    def body(h, layer_xs):
        (p,) = layer_xs
        h, _ = _block(cfg, h, p, cos, sin, lambda q, k, v: (attend_fn(q, k, v), None))
        return h, None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, (params["layers"],))
    h = rms_norm(h, params["norm"], cfg.rms_norm_eps)
    return _logits(params, h)


def _embed_dtype(params: dict):
    """Activation dtype for the param tree — taken from the final norm
    vector, which is always a plain array (embed may be int8, and its bf16
    scales must not force bf16 activations on an f32 test tree)."""
    return params["norm"].dtype


def _logits(params: dict, h: jnp.ndarray, int4_kernel: bool = True,
            w4a8: bool | None = None) -> jnp.ndarray:
    """Final projection -> float32 logits (tied embedding or separate
    lm_head).  Operands stay in their stored dtype (bf16 on the MXU) with
    float32 accumulation via preferred_element_type — an explicit astype
    would materialize a second full-vocab matrix every decode step."""
    lm_head = params.get("lm_head")
    if lm_head is None:
        embed = params["embed"]
        if isinstance(embed, QuantizedEmbedding):
            # int8 tied embedding: dequant fuses into the contraction; the
            # per-row scales apply to the OUTPUT logits
            logits = jnp.einsum(
                "bsd,vd->bsv", h, embed.q.astype(h.dtype),
                preferred_element_type=jnp.float32,
            )
            return logits * embed.s.astype(jnp.float32)[None, None, :]
        return jnp.einsum(
            "bsd,vd->bsv", h, embed, preferred_element_type=jnp.float32
        )
    if isinstance(lm_head, QuantizedLinear4):
        # XLA materializes the int4 unpack (~1 GB bf16 head per step) —
        # q4_dispatch routes to the Pallas in-VMEM-dequant GEMM on TPU
        # (two-dot XLA formulation elsewhere / under TP sharding)
        from githubrepostorag_tpu.models.quant import q4_dispatch

        return q4_dispatch(h, lm_head.q, lm_head.s, lm_head.zs,
                           out_dtype=jnp.float32, kernel=int4_kernel,
                           w4a8=w4a8)
    if isinstance(lm_head, QuantizedLinear):
        # dequantized per use; the convert+scale fuses into the dot
        wd = dequant_weight(lm_head, h.dtype)
        return jnp.einsum("bsd,dv->bsv", h, wd, preferred_element_type=jnp.float32)
    return jnp.einsum(
        "bsd,dv->bsv", h, lm_head, preferred_element_type=jnp.float32
    )


def make_dense_cache(cfg: Qwen2Config, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Allocate a contiguous per-layer KV cache [L, B, max_len, n_kv, hd]."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)


def _attend_per_tp_shard(attn_fn, mesh, quant: bool):
    """Run the Pallas paged-attention dispatcher as a shard_map island over
    the mesh: a Mosaic kernel has no GSPMD partitioning rule (the TPU
    compiler refuses one in a multi-device program), and attention needs no
    collective across heads, so each tp shard runs the kernel on its own
    q/kv heads.  Same island as the decode burst's staged kernel."""
    from jax.sharding import PartitionSpec as P

    heads = P(None, None, "tp", None)  # q / out [B, S, n_q, hd]
    pool = P(None, "tp", None, None, None)  # whole pools [L, n_kv, P, ps, hd]
    in_specs = [heads, pool, pool, P(None, None), P(None), P(None), P()]
    if quant:
        in_specs += [P(None, "tp", None)] * 2  # [L, n_kv, P] page scales
    return jax.shard_map(
        attn_fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=heads,
        check_vma=False,
    )


@partial(
    jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
    donate_argnums=(4, 5),
)
def forward_paged(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,  # [B, S] int32, right-padded per row
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    k_pages: jnp.ndarray,  # [L, n_kv, P, page_size, hd] (donated)
    v_pages: jnp.ndarray,  # (donated)
    slot_mapping: jnp.ndarray,  # [B, S] int32 flat pool slots, -1 for padding
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    cached_lens: jnp.ndarray,  # [B] tokens already in cache before this step
    new_lens: jnp.ndarray,  # [B] valid new tokens this step
    use_pallas: bool = False,
    logits_at: jnp.ndarray | None = None,  # [B] per-row position, see below
    k_scales: jnp.ndarray | None = None,  # [L, n_kv, P] f32 (per-page) —
    v_scales: jnp.ndarray | None = None,  # int8 (kv_quant) pool scales
    int4_kernel: bool = True,  # False under TP-sharded int4 weights
    # (pallas_call has no GSPMD partitioning rule — see quant.Layered4XLA)
    mesh=None,  # the engine's jax.sharding.Mesh: with use_pallas the
    # attention kernel then runs per tp shard (_attend_per_tp_shard)
):
    """Prefill-chunk or decode step over the paged KV cache.

    New K/V are committed to the page pools at ``slot_mapping`` (padding
    slots are -1 and dropped; a row's columns are consecutive positions, so
    kv_cache.commit_paged writes them a page-sized window at a time where
    the pools are full precision, a row per slot where they are quantized),
    then attention runs over each row's block table.  Returns (logits,
    k_pages, v_pages[, k_scales, v_scales]) — the pools are donated so XLA
    updates them in place (scale pools are small enough that their copy is
    noise).

    ``k_scales``/``v_scales`` mark int8 kv_quant pools: new K/V quantize
    per PAGE at the scatter (kv_cache.quantize_kv_paged: the first write
    to a page fixes its scale, appends reuse it and clip) and attention
    runs the gather path with dequant — prefill/verify chunks are
    compute-dominated, so the materialized gather costs little here; the
    decode hot path (decode_burst) reads int8 pages directly in its
    Pallas kernel.

    ``logits_at``: per-row chunk index at which to project logits, returning
    [B, 1, V].  Without it logits cover every position ([B, S, V] float32 —
    at prefill width x batch x vocab that is GBs of HBM; the serving engine
    only ever needs each prompt's last position, vLLM's
    "last-token-only logits" optimization).
    """
    return forward_paged_impl(
        params, cfg, input_ids, positions, k_pages, v_pages,
        slot_mapping, block_tables, cached_lens, new_lens, use_pallas,
        logits_at=logits_at, k_scales=k_scales, v_scales=v_scales,
        int4_kernel=int4_kernel, mesh=mesh,
    )


@partial(
    jax.jit, static_argnames=("cfg", "use_pallas", "int4_kernel", "mesh"),
    donate_argnums=(4, 5, 6),
)
def forward_paged_wave(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,  # [R, S] the wave's chunk per row (forward_paged's)
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,  # donated
    v_pages: jnp.ndarray,  # donated
    presence: jnp.ndarray,  # [rows, V] bool, the engine's whole mask (donated)
    first_tokens: jnp.ndarray,  # [rows] int32, the engine's first-token array
    slot_mapping: jnp.ndarray,
    block_tables: jnp.ndarray,
    cached_lens: jnp.ndarray,
    new_lens: jnp.ndarray,
    logits_at: jnp.ndarray,  # [R] each row's last valid position
    row_idx: jnp.ndarray,  # [R] engine row of each wave row
    done_mask: jnp.ndarray,  # [R] bool: the chunk completes the row's prompt
    width: jnp.ndarray,  # scalar: columns the wave's longest chunk needs
    rng: jax.Array,  # the engine's base key; ``key_step`` is folded in here
    key_step: jnp.ndarray,  # scalar: the engine's dispatch counter
    temperature: jnp.ndarray,  # [rows] per engine row, like the burst's
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
    use_pallas: bool = False,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    int4_kernel: bool = True,
    mesh=None,
):
    """The engine's prefill wave as ONE program: ``forward_paged``'s chunk,
    every layer at the narrowest width that holds ``width`` columns
    (``ops/prefill_width.at_wave_width``), then
    ``ops/sampling.first_token_tail`` on its logits (prompt tokens into
    ``presence``, the first token of every completed row drawn, marked and
    scattered into ``first_tokens``).  Every input but the pools, ``presence``,
    ``first_tokens`` and ``rng`` is a host array, so the host dispatches it
    and goes on: no value of the wave is touched before the commit fetches
    ``first_tokens``.  Returns (first_tokens, presence, k_pages, v_pages[,
    k_scales, v_scales])."""
    logits, *cache = forward_paged_impl(
        params, cfg, input_ids, positions, k_pages, v_pages,
        slot_mapping, block_tables, cached_lens, new_lens, use_pallas,
        logits_at=logits_at, k_scales=k_scales, v_scales=v_scales,
        int4_kernel=int4_kernel, mesh=mesh, width=width,
    )
    with jax.named_scope("sample"):
        first_tokens, presence = first_token_tail(
            logits[:, 0], presence, first_tokens, input_ids, new_lens, row_idx,
            done_mask, jax.random.fold_in(rng, key_step),
            temperature, top_p, top_k, repetition_penalty)
    return (first_tokens, presence, *cache)


def forward_paged_impl(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    slot_mapping: jnp.ndarray,
    block_tables: jnp.ndarray,
    cached_lens: jnp.ndarray,
    new_lens: jnp.ndarray,
    use_pallas: bool = False,
    logits_at: jnp.ndarray | None = None,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    int4_kernel: bool = True,
    mesh=None,
    width: jnp.ndarray | None = None,  # the wave program's: see at_wave_width
):
    """Unjitted body of ``forward_paged`` so larger fused programs (the
    multi-step decode burst in serving/decode_burst.py) can inline it inside
    their own scan without nested-jit donation clashes."""
    from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref

    quant = k_scales is not None
    if use_pallas:
        # ONE kernel for every window shape and pool precision: spec
        # verify (S = k+1), plain decode (S = 1), fp/int8/int4 pages all
        # run ops/fused_decode's flash window kernel — the old dispatcher
        # routed S > 1 and quantized pools to the materialized gather_kv
        # fallback, a full [B, mp*ps, n_kv, hd] HBM copy per layer.
        from githubrepostorag_tpu.ops.fused_decode import fused_paged_attention

        def attn_fn(q, kp, vp, bt, cached, new, layer, *scales):
            return fused_paged_attention(q, kp, vp, bt, cached, new, *scales,
                                         layer=layer)

        if mesh is not None:
            attn_fn = _attend_per_tp_shard(attn_fn, mesh, quant)
    else:
        attn_fn = paged_attention_ref

    b, s = input_ids.shape
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    total_slots = num_pages * page_size

    h = embedding_lookup(params["embed"], input_ids, dtype=_embed_dtype(params))
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    # Padding slots arrive as -1; JAX scatter *wraps* negative indices (it
    # only drops indices >= size), so map them to an out-of-range positive
    # sentinel that mode="drop" actually drops.
    slots = jnp.where(slot_mapping < 0, total_slots, slot_mapping)  # [B, S]

    scan_layers, q4_stacks = _split_q4(params["layers"])

    # The pools (and a quantized pool's page scales) ride the layer scan as
    # CARRY, whole: layer li is committed by index (kv_cache.commit_paged's
    # carried form) and read through the kernel's index map.  As scan xs/ys
    # every layer's slab is sliced out, re-laid-out around its scatter and
    # written into a second stacked pool that two whole-pool copies then
    # reconcile with the donated one: 44% of a 512-token chunk's device
    # time at Qwen2-7B widths (PERF.md, Findings, PR 25).
    def body(carry, p_xs):
        h, li, *pools = carry

        def layer(cols, pools):
            h, cos, sin, slots = cols
            kp, vp, ks, vs = pools
            flat_slots = slots.reshape(-1)  # [B*S]
            # prefill / spec-verify chunks pin w4a8=False: prompt processing
            # keeps the exact bf16-dequant contract even when the chunk is
            # decode-sized (the auto gate must never catch a prefill batch)
            p = _with_layered_q4(layer_weights(p_xs, scan_layers, li), q4_stacks, li,
                                 kernel=int4_kernel, w4a8=False)

            def attend(q, k, v):
                from githubrepostorag_tpu.serving.kv_cache import commit_paged

                k_t = k.reshape(-1, nkv, hd).swapaxes(0, 1)  # [n_kv, B*S, hd]
                v_t = v.reshape(-1, nkv, hd).swapaxes(0, 1)
                # commit_paged is THE shared pool-commit rule (cast for bf16
                # pools; per-page first-write scales for int8 — same semantics
                # as the burst and ring-prefill commits)
                with jax.named_scope("kv_write"):
                    run = slots.shape[1]  # a row's columns are consecutive positions
                    new_kp, new_ks = commit_paged(kp, k_t, flat_slots, ks, page_size,
                                                  layer=li, run=run)
                    new_vp, new_vs = commit_paged(vp, v_t, flat_slots, vs, page_size,
                                                  layer=li, run=run)
                with jax.named_scope("paged_attention"):
                    scales = (new_ks, new_vs) if quant else ()
                    if use_pallas:
                        attn = attn_fn(q, new_kp, new_vp, block_tables, cached_lens,
                                       new_lens, li, *scales)
                    else:
                        # the CPU/oracle path reads one layer's slab
                        attn = attn_fn(
                            q, new_kp[li], new_vp[li], block_tables, cached_lens,
                            new_lens, *(sc[li] for sc in scales),
                        )
                return attn, (new_kp, new_vp, new_ks, new_vs)

            return _block(cfg, h, p, cos, sin, attend)

        h, pools = at_wave_width(layer, width, page_size, (h, cos, sin, slots), tuple(pools))
        return (h, li + 1, *pools), None

    # the wave's layers index their own weights (layer_weights): no xs
    xs, n = (scan_layers, None) if width is None else (None, cfg.num_layers)
    (h, _, k_pages, v_pages, k_scales, v_scales), _ = jax.lax.scan(
        body, (h, 0, k_pages, v_pages, k_scales, v_scales), xs, length=n
    )
    with jax.named_scope("sample"):  # the head; the first token's draw is the engine's
        h = rms_norm(h, params["norm"], cfg.rms_norm_eps)
        if logits_at is not None:
            h = jnp.take_along_axis(h, logits_at[:, None, None], axis=1)  # [B, 1, d]
        # w4a8=False: prefill/spec-verify logits keep the exact bf16-dequant
        # contract, like the projections above (the prompt's first sampled
        # token and every verify accept/reject come from these)
        logits = _logits(params, h, int4_kernel=int4_kernel, w4a8=False)
    if quant:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages


@partial(
    jax.jit, static_argnames=("cfg", "tq", "use_pallas", "int4_kernel"),
    donate_argnums=(4, 5),
)
def forward_paged_packed(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,  # [1, T] int32 packed token buffer (T = budget)
    positions: jnp.ndarray,  # [1, T] int32 absolute positions per token
    k_pages: jnp.ndarray,  # [L, n_kv, P, page_size, hd] (donated)
    v_pages: jnp.ndarray,  # (donated)
    slot_mapping: jnp.ndarray,  # [T] int32 flat pool slots, -1 for padding
    block_tables: jnp.ndarray,  # [R, max_pages] int32 per SEGMENT
    cached_lens: jnp.ndarray,  # [R] tokens in cache before this chunk
    new_lens: jnp.ndarray,  # [R] valid new tokens this chunk
    seg_ids: jnp.ndarray,  # [T] int32 owning segment; >= R marks padding
    logits_at: jnp.ndarray,  # [R] packed-buffer index of each segment's
    # last token (the generalized per-segment logits_at)
    tq: int,  # static per-segment chunk cap — min(prefill_chunk, budget)
    use_pallas: bool = False,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    int4_kernel: bool = True,
):
    """Token-budget packed prefill step over the paged KV cache.

    The padded ``forward_paged`` prefill runs [row_bucket, width] with every
    row padded to the widest pending chunk; this variant runs ONE flat
    [1, budget] buffer holding every prefilling row's next chunk back to
    back, so embedding/projection/MLP FLOPs — the bulk of prefill compute —
    scale with real tokens.  Attention runs the segment-masked path
    (ops/packed_prefill.py): per-token ``seg_ids`` map tokens to block
    tables / cached lengths, causal structure is per segment.

    New K/V are scattered into the page pools at ``slot_mapping`` exactly
    like forward_paged (padding slots -1 drop).  Returns
    (logits [R, 1, V], k_pages, v_pages[, k_scales, v_scales]) — logits
    are per SEGMENT at each segment's last packed position, so the engine's
    [row-bucket] sampling program is unchanged."""
    return forward_paged_packed_impl(
        params, cfg, input_ids, positions, k_pages, v_pages, slot_mapping,
        block_tables, cached_lens, new_lens, seg_ids, logits_at, tq,
        use_pallas, k_scales=k_scales, v_scales=v_scales,
        int4_kernel=int4_kernel,
    )


def forward_paged_packed_impl(
    params: dict,
    cfg: Qwen2Config,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    slot_mapping: jnp.ndarray,
    block_tables: jnp.ndarray,
    cached_lens: jnp.ndarray,
    new_lens: jnp.ndarray,
    seg_ids: jnp.ndarray,
    logits_at: jnp.ndarray,
    tq: int,
    use_pallas: bool = False,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    int4_kernel: bool = True,
):
    """Unjitted body of ``forward_paged_packed`` so larger fused programs
    (serving/fused_step.py's one-dispatch prefill+decode step) can inline
    the packed phase without nested-jit donation clashes — the same split
    as forward_paged/forward_paged_impl."""
    from githubrepostorag_tpu.ops.packed_prefill import packed_prefill_attention

    quant = k_scales is not None
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    num_pages, page_size = k_pages.shape[2], k_pages.shape[3]
    total_slots = num_pages * page_size

    h = embedding_lookup(params["embed"], input_ids, dtype=_embed_dtype(params))
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    flat_slots = slot_mapping.reshape(-1)  # [T]
    flat_slots = jnp.where(flat_slots < 0, total_slots, flat_slots)
    pos_flat = positions.reshape(-1)

    scan_layers, q4_stacks = _split_q4(params["layers"])

    def body(carry, layer_xs):
        h, li = carry
        if quant:
            p, kp, vp, ks, vs = layer_xs
        else:
            p, kp, vp = layer_xs
            ks = vs = None
        # same w4a8=False pin as forward_paged: prompt processing keeps the
        # exact bf16-dequant contract regardless of the packed buffer size
        p = _with_layered_q4(p, q4_stacks, li, kernel=int4_kernel, w4a8=False)

        def attend(q, k, v):
            from githubrepostorag_tpu.serving.kv_cache import commit_paged

            k_t = k.reshape(-1, nkv, hd).swapaxes(0, 1)  # [n_kv, T, hd]
            v_t = v.reshape(-1, nkv, hd).swapaxes(0, 1)
            new_kp, new_ks = commit_paged(
                kp, k_t, flat_slots, ks if quant else None, page_size
            )
            new_vp, new_vs = commit_paged(
                vp, v_t, flat_slots, vs if quant else None, page_size
            )
            attn = packed_prefill_attention(
                q[0], new_kp, new_vp, block_tables, cached_lens, new_lens,
                seg_ids, pos_flat, tq=tq, use_pallas=use_pallas,
                k_scales=new_ks if quant else None,
                v_scales=new_vs if quant else None,
            )[None]  # [1, T, n_q, hd]
            if quant:
                return attn, (new_kp, new_vp, new_ks, new_vs)
            return attn, (new_kp, new_vp)

        h, cache = _block(cfg, h, p, cos, sin, attend)
        return (h, li + 1), cache

    if quant:
        xs = (scan_layers, k_pages, v_pages, k_scales, v_scales)
        (h, _), (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
            body, (h, 0), xs
        )
    else:
        (h, _), (k_pages, v_pages) = jax.lax.scan(
            body, (h, 0), (scan_layers, k_pages, v_pages)
        )
    h = rms_norm(h, params["norm"], cfg.rms_norm_eps)
    # per-segment last-token hidden states: [1, T, d] -> [R, 1, d]
    h = h[0, logits_at][:, None, :]
    logits = _logits(params, h, int4_kernel=int4_kernel, w4a8=False)
    if quant:
        return logits, k_pages, v_pages, k_scales, v_scales
    return logits, k_pages, v_pages
