"""Load HuggingFace Qwen2 checkpoints into the stacked-params pytree.

Two entry points:
  - ``params_from_state_dict`` — from an in-memory state dict (numpy/torch
    tensors); used by parity tests against ``transformers`` models.
  - ``load_qwen2`` — from a local checkpoint directory (config.json +
    safetensors shards).  No network access: weights must already be on
    disk (MODEL_WEIGHTS_PATH).

HF stores linear weights [out, in]; this framework stores [in, out] so the
forward pass is ``x @ w``.  Per-layer tensors are stacked on a leading L
axis for the lax.scan layer loop.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from githubrepostorag_tpu.models.qwen2 import Qwen2Config
from githubrepostorag_tpu.obs import startup


def _np(t) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    # torch tensor (parity tests) without importing torch here
    return t.detach().to("cpu").float().numpy()


def config_from_hf(hf_cfg: dict) -> Qwen2Config:
    """Pure parser: HF config dict -> Qwen2Config (parsing the same
    config.json never depends on process env)."""
    num_heads = hf_cfg["num_attention_heads"]
    moe: dict = {}
    if hf_cfg.get("num_experts", 0):  # Qwen2MoeConfig (model_type qwen2_moe)
        if hf_cfg.get("decoder_sparse_step", 1) != 1 or hf_cfg.get("mlp_only_layers"):
            # lax.scan over stacked layers needs a uniform block structure
            raise ValueError(
                "only uniformly-sparse Qwen2-MoE checkpoints are supported "
                "(decoder_sparse_step=1, no mlp_only_layers)"
            )
        moe = dict(
            num_experts=hf_cfg["num_experts"],
            num_experts_per_tok=hf_cfg["num_experts_per_tok"],
            moe_intermediate_size=hf_cfg["moe_intermediate_size"],
            shared_expert_intermediate_size=hf_cfg["shared_expert_intermediate_size"],
            norm_topk_prob=hf_cfg.get("norm_topk_prob", False),
        )
    return Qwen2Config(
        vocab_size=hf_cfg["vocab_size"],
        hidden_size=hf_cfg["hidden_size"],
        intermediate_size=hf_cfg["intermediate_size"],
        num_layers=hf_cfg["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=hf_cfg.get("num_key_value_heads", num_heads),
        head_dim=hf_cfg.get("head_dim") or hf_cfg["hidden_size"] // num_heads,
        rope_theta=hf_cfg.get("rope_theta", 1_000_000.0),  # HF Qwen2Config default
        rms_norm_eps=hf_cfg.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=hf_cfg.get("tie_word_embeddings", False),
        max_position_embeddings=hf_cfg.get("max_position_embeddings", 32768),
        **moe,
    )


def params_from_state_dict(state_dict: dict, cfg: Qwen2Config, dtype=np.float32) -> dict:
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    L = cfg.num_layers

    def get(name: str) -> np.ndarray:
        return _np(sd[name])

    def stack_linear(fmt: str) -> np.ndarray:
        # HF [out, in] -> ours [in, out], stacked [L, in, out]
        return np.stack([get(fmt.format(i)).T for i in range(L)]).astype(dtype)

    def stack_vec(fmt: str) -> np.ndarray:
        return np.stack([get(fmt.format(i)) for i in range(L)]).astype(dtype)

    layers = {
        "ln1": stack_vec("layers.{}.input_layernorm.weight"),
        "ln2": stack_vec("layers.{}.post_attention_layernorm.weight"),
        "wq": stack_linear("layers.{}.self_attn.q_proj.weight"),
        "bq": stack_vec("layers.{}.self_attn.q_proj.bias"),
        "wk": stack_linear("layers.{}.self_attn.k_proj.weight"),
        "bk": stack_vec("layers.{}.self_attn.k_proj.bias"),
        "wv": stack_linear("layers.{}.self_attn.v_proj.weight"),
        "bv": stack_vec("layers.{}.self_attn.v_proj.bias"),
        "wo": stack_linear("layers.{}.self_attn.o_proj.weight"),
    }
    if cfg.num_experts > 0:  # Qwen2-MoE sparse MLP (models/moe.py keys)
        E = cfg.num_experts

        def stack_experts(fmt: str) -> np.ndarray:
            # [L, E, in, out] from HF's per-expert [out, in] linears
            return np.stack([
                np.stack([get(fmt.format(i, e)).T for e in range(E)])
                for i in range(L)
            ]).astype(dtype)

        layers.update({
            "router": stack_linear("layers.{}.mlp.gate.weight"),
            "e_wg": stack_experts("layers.{}.mlp.experts.{}.gate_proj.weight"),
            "e_wu": stack_experts("layers.{}.mlp.experts.{}.up_proj.weight"),
            "e_wd": stack_experts("layers.{}.mlp.experts.{}.down_proj.weight"),
            "s_wg": stack_linear("layers.{}.mlp.shared_expert.gate_proj.weight"),
            "s_wu": stack_linear("layers.{}.mlp.shared_expert.up_proj.weight"),
            "s_wd": stack_linear("layers.{}.mlp.shared_expert.down_proj.weight"),
            "s_gate": stack_linear("layers.{}.mlp.shared_expert_gate.weight"),
        })
    else:
        layers.update({
            "wg": stack_linear("layers.{}.mlp.gate_proj.weight"),
            "wu": stack_linear("layers.{}.mlp.up_proj.weight"),
            "wd": stack_linear("layers.{}.mlp.down_proj.weight"),
        })
    params = {
        "embed": get("embed_tokens.weight").astype(dtype),
        "layers": layers,
        "norm": get("norm.weight").astype(dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T.astype(dtype)
    return params


@startup.records("startup.weights", settle=True)
def load_qwen2(
    checkpoint_dir: str,
    dtype=np.float32,
    quantize: bool | int = False,
    fuse: bool = False,
) -> tuple[dict, Qwen2Config]:
    """Load config.json + *.safetensors from a local directory.

    ``quantize`` converts every linear projection AND the embedding table
    to weight-only quantized form (models/quant.py) host-side before
    device placement: ``True``/``8`` = per-channel int8, ``4`` = AWQ-class
    group-wise uint4 — the path that fits Qwen2-7B on a single 16 GB chip
    (matching the reference's Qwen2.5-Coder-7B-Instruct-AWQ deployment,
    values.yaml:67).  Checkpoints that are ALREADY AWQ-quantized
    (quant_config.quant_method == "awq" in config.json, qweight/qzeros/
    scales tensors) are detected and repacked via
    ``awq_params_from_state_dict`` — the uint4 codes transfer exactly (no
    dequant/requant round trip); scales round fp16->bf16.
    """
    from safetensors import safe_open  # ships with transformers' deps

    root = Path(checkpoint_dir)
    hf_cfg = json.loads((root / "config.json").read_text())
    cfg = config_from_hf(hf_cfg)

    state: dict[str, np.ndarray] = {}
    for shard in sorted(root.glob("*.safetensors")):
        with safe_open(str(shard), framework="np") as f:
            for key in f.keys():
                state[key] = f.get_tensor(key)
    if quantize not in (False, True, 4, 8):
        raise ValueError(f"quantize must be False/True/8/4, got {quantize!r}")
    if (hf_cfg.get("quantization_config") or {}).get("quant_method") == "awq":
        if quantize in (True, 8):
            import logging

            logging.getLogger(__name__).warning(
                "checkpoint %s is natively 4-bit AWQ; ignoring the int8 "
                "quantize request and repacking the AWQ codes", checkpoint_dir
            )
        params = awq_params_from_state_dict(state, cfg, hf_cfg, dtype=dtype)
    else:
        params = params_from_state_dict(state, cfg, dtype=dtype)
        if quantize:
            from githubrepostorag_tpu.models.quant import quantize_qwen2_params

            params = quantize_qwen2_params(params, bits=4 if quantize == 4 else 8)
    if fuse:
        # single-chip serving layout (quant.fuse_projections): fuse at load
        # time, while the tree is the only thing on the device, rather than
        # at Engine construction next to freshly allocated KV pools
        from githubrepostorag_tpu.models.quant import fuse_projections

        params = fuse_projections(params, in_place=True)  # solely owned here
    return params, cfg


# ---- AWQ checkpoint repacking -------------------------------------------

AWQ_NIBBLE_ORDER = (0, 2, 4, 6, 1, 3, 5, 7)  # AutoAWQ's GEMM packing order


def _awq_unpack(packed: np.ndarray) -> np.ndarray:
    """Unpack AutoAWQ int32 nibble-packed tensors along the LAST axis:
    [r, c/8] int32 -> [r, c] uint8 (values 0..15).  AWQ packs 8 columns
    per int32 in the interleaved order ``AWQ_NIBBLE_ORDER`` (see
    AutoAWQ awq/utils/packing_utils.py — behavioral contract only)."""
    r, c8 = packed.shape
    out = np.empty((r, c8 * 8), dtype=np.uint8)
    u = packed.view(np.uint32) if packed.dtype == np.int32 else packed.astype(np.uint32)
    for pos, col in enumerate(AWQ_NIBBLE_ORDER):
        out[:, col::8] = ((u >> np.uint32(4 * pos)) & np.uint32(0xF)).astype(np.uint8)
    return out


def awq_linear_to_quantized4(
    qweight: np.ndarray,  # int32 [in, out/8]
    qzeros: np.ndarray,  # int32 [in/group, out/8]
    scales: np.ndarray,  # f16/f32 [in/group, out]
):
    """Repack one AutoAWQ GEMM-format linear into the in-tree
    ``QuantizedLinear4`` layout.  AWQ dequant is ``(q - z) * s``; ours is
    ``q * s - zs`` with ``zs = z * s``.  The uint4 codes transfer exactly;
    s and zs are stored bf16 (AWQ ships fp16 scales), so repacked dequant
    matches the AWQ reference to bf16 rounding of the scales (~2^-8
    relative) — not bit-exact."""
    import jax.numpy as jnp
    import ml_dtypes

    from githubrepostorag_tpu.models.quant import QuantizedLinear4

    q = _awq_unpack(qweight)  # [in, out] uint8
    z = _awq_unpack(qzeros).astype(np.float32)  # [in/group, out]
    s = scales.astype(np.float32)
    in_dim, out = q.shape
    n_g = s.shape[0]
    group = in_dim // n_g
    if group % 2 or in_dim % group:
        raise ValueError(f"AWQ group size {group} not even over in dim {in_dim}")
    # in-group plane packing (see QuantizedLinear4): low nibble = first
    # half of each group's rows, high nibble = second half
    qg = q.reshape(n_g, group, out)
    packed = (qg[:, : group // 2, :] | (qg[:, group // 2 :, :] << 4)).reshape(
        in_dim // 2, out
    )
    return QuantizedLinear4(
        q=jnp.asarray(packed),
        s=jnp.asarray(s.astype(ml_dtypes.bfloat16)),
        zs=jnp.asarray((z * s).astype(ml_dtypes.bfloat16)),
    )


def awq_params_from_state_dict(
    state_dict: dict, cfg: Qwen2Config, hf_cfg: dict, dtype=np.float32
) -> dict:
    """Build the stacked-params pytree from an AWQ checkpoint's
    qweight/qzeros/scales tensors (projections) + full-precision
    embedding/norm tensors.  The embedding table re-quantizes to the
    in-tree per-row int8 (AWQ keeps it fp16; int8 per-row is this
    framework's standard table format and adds <0.4% RMS error).
    ``dtype`` sets the unquantized leaves (norms/biases) and thereby the
    activation dtype (qwen2._embed_dtype) — pass bf16 for serving."""
    from githubrepostorag_tpu.models.quant import quantize_embedding

    if cfg.num_experts > 0:
        raise NotImplementedError(
            "AWQ repacking covers dense Qwen2 checkpoints; AWQ MoE exports "
            "are not supported (quantize a bf16 MoE checkpoint instead)"
        )
    qc = hf_cfg.get("quantization_config") or {}
    if qc.get("bits", 4) != 4 or qc.get("version", "gemm").lower() != "gemm":
        raise ValueError(
            f"only 4-bit GEMM-format AWQ checkpoints are supported, got "
            f"bits={qc.get('bits')} version={qc.get('version')}"
        )
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    L = cfg.num_layers

    def stack_awq(prefix_fmt: str):
        import jax
        import jax.numpy as jnp

        per_layer = [
            awq_linear_to_quantized4(
                _np_int(sd[prefix_fmt.format(i) + ".qweight"]),
                _np_int(sd[prefix_fmt.format(i) + ".qzeros"]),
                _np(sd[prefix_fmt.format(i) + ".scales"]),
            )
            for i in range(L)
        ]
        # stack each field (q/s/zs) on a new leading L axis
        return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer)

    def stack_vec(fmt: str) -> np.ndarray:
        return np.stack([_np(sd[fmt.format(i)]) for i in range(L)]).astype(dtype)

    layers = {
        "ln1": stack_vec("layers.{}.input_layernorm.weight"),
        "ln2": stack_vec("layers.{}.post_attention_layernorm.weight"),
        "wq": stack_awq("layers.{}.self_attn.q_proj"),
        "bq": stack_vec("layers.{}.self_attn.q_proj.bias"),
        "wk": stack_awq("layers.{}.self_attn.k_proj"),
        "bk": stack_vec("layers.{}.self_attn.k_proj.bias"),
        "wv": stack_awq("layers.{}.self_attn.v_proj"),
        "bv": stack_vec("layers.{}.self_attn.v_proj.bias"),
        "wo": stack_awq("layers.{}.self_attn.o_proj"),
        "wg": stack_awq("layers.{}.mlp.gate_proj"),
        "wu": stack_awq("layers.{}.mlp.up_proj"),
        "wd": stack_awq("layers.{}.mlp.down_proj"),
    }
    params = {
        "embed": quantize_embedding(_np(sd["embed_tokens.weight"])),
        "layers": layers,
        "norm": _np(sd["norm.weight"]).astype(dtype),
    }
    if not cfg.tie_word_embeddings:
        lm = sd.get("lm_head.weight")
        if lm is not None:  # AWQ keeps lm_head fp16; re-quantize to int8
            from githubrepostorag_tpu.models.quant import quantize_weight

            params["lm_head"] = quantize_weight(_np(lm).T)
        else:  # some AWQ exports quantize lm_head too
            params["lm_head"] = awq_linear_to_quantized4(
                _np_int(sd["lm_head.qweight"]),
                _np_int(sd["lm_head.qzeros"]),
                _np(sd["lm_head.scales"]),
            )
    return params


def _np_int(t) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    return t.detach().to("cpu").numpy()
