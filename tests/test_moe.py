"""Qwen2-MoE model family (models/moe.py): HF logits/generation parity,
expert-parallel sharding parity on the CPU mesh, the dropless dispatch,
and the full serving engine over a MoE checkpoint.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import (
    Qwen2Config,
    forward_with_attend,
    init_params,
)
from githubrepostorag_tpu.parallel import MeshPlan, make_mesh
from githubrepostorag_tpu.serving import Engine, SamplingParams

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402


@pytest.fixture(scope="module")
def tiny_moe():
    from githubrepostorag_tpu.models.hf_loader import config_from_hf, params_from_state_dict

    hf_cfg = transformers.Qwen2MoeConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=True, attention_dropout=0.0,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=48,
        shared_expert_intermediate_size=96, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[],
        output_router_logits=False,
    )
    torch.manual_seed(0)
    model = transformers.Qwen2MoeForCausalLM(hf_cfg).eval()
    cfg = config_from_hf(hf_cfg.to_dict())  # the served dispatch is the exact one
    params = params_from_state_dict(model.state_dict(), cfg)
    return model, params, cfg


def test_config_from_hf_maps_moe_fields(tiny_moe):
    from githubrepostorag_tpu.models.hf_loader import config_from_hf

    _, _, cfg = tiny_moe
    assert cfg.num_experts == 4
    assert cfg.num_experts_per_tok == 2
    assert cfg.moe_intermediate_size == 48
    assert cfg.shared_expert_intermediate_size == 96
    assert cfg.norm_topk_prob is True
    # nothing bounds an expert's capacity any more: what is loaded is what parity ran
    loaded = config_from_hf(transformers.Qwen2MoeConfig(num_experts=4).to_dict())
    assert not hasattr(loaded, "capacity_factor") and loaded.num_experts == 4


def test_nonuniform_sparsity_rejected():
    from githubrepostorag_tpu.models.hf_loader import config_from_hf

    hf = transformers.Qwen2MoeConfig(num_experts=4, mlp_only_layers=[0]).to_dict()
    with pytest.raises(ValueError, match="uniform"):
        config_from_hf(hf)


def test_forward_logits_match_hf(tiny_moe):
    model, params, cfg = tiny_moe
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 17), dtype=np.int32)
    with torch.no_grad():
        ref = model(torch.tensor(ids)).logits.numpy()
    pos = np.broadcast_to(np.arange(17, dtype=np.int32), (2, 17))
    got = np.asarray(
        forward_with_attend(params, cfg, jnp.asarray(ids), jnp.asarray(pos))
    )
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_engine_greedy_matches_hf_generate(tiny_moe):
    """The MoE family serves through the same paged engine: greedy decode
    must equal HF generate token-for-token."""
    model, params, cfg = tiny_moe
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 21).tolist()
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=64, page_size=8,
                 max_seq_len=128, prefill_chunk=32, kv_dtype=jnp.float32,
                 decode_burst=4)
    got = eng.generate(
        [prompt], SamplingParams(max_tokens=12, temperature=0.0, stop_token_ids=())
    )[0].output_tokens
    with torch.no_grad():
        hf = model.generate(torch.tensor([prompt]), max_new_tokens=12,
                            do_sample=False, pad_token_id=0, eos_token_id=None,
                            use_cache=True)
    assert got == hf[0, len(prompt):].tolist()


def test_ep_sharded_forward_matches_single_device(tiny_moe):
    """Expert weights sharded over ep=4 via the standard param specs: same
    logits as replicated."""
    from githubrepostorag_tpu.parallel.sharding import qwen2_param_specs, shard_params

    _, params, cfg = tiny_moe
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16), dtype=np.int32))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    ref = np.asarray(forward_with_attend(params, cfg, ids, pos))

    mesh = make_mesh(MeshPlan(ep=4))
    sharded = shard_params(params, mesh, qwen2_param_specs(cfg, mesh, params))
    for name in ("e_wg", "e_wu", "e_wd"):
        assert "ep" in str(sharded["layers"][name].sharding.spec)
    got = np.asarray(forward_with_attend(sharded, cfg, ids, pos))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_ep_sharded_engine_token_identical(tiny_moe):
    """The paged engine with an ep=4 mesh (expert weights sharded through
    Engine's own shard_params path) decodes the same greedy tokens as the
    unsharded engine.  Two prompt seeds guard against a reordered-psum
    near-tie argmax flip (a numerics artifact, not a sharding bug)."""
    _, params, cfg = tiny_moe
    sp = SamplingParams(max_tokens=10, temperature=0.0, stop_token_ids=())

    def run(mesh, prompt):
        eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=8,
                     max_seq_len=64, prefill_chunk=32, kv_dtype=jnp.float32,
                     decode_burst=4, mesh=mesh)
        return eng.generate([prompt], sp)[0].output_tokens

    for seed in (6, 11):
        prompt = np.random.default_rng(seed).integers(0, cfg.vocab_size, 19).tolist()
        if run(make_mesh(MeshPlan(ep=4)), prompt) == run(None, prompt):
            break
    else:
        raise AssertionError("ep-sharded engine decode diverged on 2 seeds")


def test_uneven_routing_equals_a_loop_over_experts(monkeypatch):
    """Where a capacity of 1.5 used to drop: routing skewed hard towards one
    expert still gives, token for token, what a dense loop over the experts
    gives (tiles of 8 rows, so the busy expert spans several)."""
    from githubrepostorag_tpu.models import moe
    from githubrepostorag_tpu.models.moe import dropless_experts

    monkeypatch.setattr(moe, "EXPERT_TILE", 8)
    t, d, e, k = 64, 16, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (t, d))
    w = jax.random.normal(keys[1], (e, d, d))
    logits = jax.random.normal(keys[2], (t, e)).at[:, 1].add(4.0)  # expert 1 takes nearly all
    top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits), k)
    y, counts = dropless_experts(x, top_i, top_w, lambda ex, rows: rows @ w[ex], e)
    want = sum(jnp.where((top_i == ex).any(axis=1, keepdims=True),
                         (x @ w[ex]) * jnp.where(top_i == ex, top_w, 0).sum(axis=1, keepdims=True), 0)
               for ex in range(e))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert int(counts.sum()) == t * k and int(counts[1]) > t * 0.9


def test_noaux_tc_at_one_group_is_the_plain_biased_top_k():
    """Nemotron-H's ``n_group`` 1 / ``topk_group`` 1: a group of all the
    experts limits nothing, so the choice is the top k of score + bias and the
    weights are the unbiased scores of the chosen, normalised and scaled."""
    from githubrepostorag_tpu.models.moe import route_noaux_tc

    rng = np.random.default_rng(5)
    t, e, k = 50, 128, 6
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(t, e)) * 2.0, jnp.float32))
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.05, jnp.float32)
    ids, w = route_noaux_tc(scores, bias, k, 1, 1, True, 2.5)
    want_ids = np.argsort(-np.asarray(scores + bias[None]), axis=1)[:, :k]
    assert (np.sort(np.asarray(ids), axis=1) == np.sort(want_ids, axis=1)).all()
    assert (np.asarray(ids) != np.argsort(-np.asarray(scores), axis=1)[:, :k]).any()  # the bias chose
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), axis=1)
    np.testing.assert_allclose(w, 2.5 * picked / picked.sum(axis=1, keepdims=True), rtol=1e-6)
    # a limit that limits (8 groups, 4 kept) chooses otherwise: the short cut is for one group alone
    limited, _ = route_noaux_tc(scores, bias, k, 8, 4, True, 2.5)
    assert (np.sort(np.asarray(limited), axis=1) != np.sort(want_ids, axis=1)).any()


@pytest.mark.parametrize("listed", [False, True], ids=["scan", "listed"])
@pytest.mark.parametrize("tokens", [24, 200], ids=["one-tile", "many-tiles"])
def test_two_product_relu2_experts_through_the_dropless_dispatch(listed, tokens):
    """An expert that is NOT gated, ``W_down relu(W_up x)^2`` (Nemotron-H's):
    the dispatch takes any ``expert_ffn``; both forms, rows inside one tile and
    over several, a held range in the middle of the router's width."""
    from githubrepostorag_tpu.models.moe import dropless_experts
    from githubrepostorag_tpu.models.nemotron_h import relu2_ffn

    rng = np.random.default_rng(6)
    d, f, e, k, lo, held = 16, 24, 16, 4, 4, 8
    x = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(held, f, d)) * 0.3, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(held, f, d)) * 0.3, jnp.float32)
    top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(jnp.asarray(rng.normal(size=(tokens, e)),
                                                            jnp.float32)), k)
    y, counts = dropless_experts(x, top_i, top_w, lambda ex, rows: relu2_ffn(rows, wu[ex], wd[ex]),
                                 held, lo=lo, listed=listed)
    want = sum(jnp.where(top_i == lo + ex, top_w, 0).sum(axis=1, keepdims=True)
               * (jnp.square(jax.nn.relu(x @ wu[ex].T)) @ wd[ex]) for ex in range(held))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert int(counts.sum()) == int(((top_i >= lo) & (top_i < lo + held)).sum())


def _walk_case(form, t, routing, seed=8):
    """(x, top_i, top_w, the stacks [2 layers, held, ...], a plain float32
    loop's sum for layer 1) at tiny widths: 16 experts of which 4 .. 11 are
    held, a hidden width of 320 (two and a half lane tiles)."""
    rng = np.random.default_rng(seed)
    d, ff, e, k, lo, held = 16, 320, 16, 4, 4, 8
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    draw = lambda *shape: jnp.asarray(rng.normal(size=(2, held, *shape)) * 0.3, jnp.float32)  # noqa: E731
    if form == "swiglu":
        stacks = (draw(d, 2 * ff), draw(ff, d))
        ffn = lambda ex: (jax.nn.silu(x @ stacks[0][1, ex][:, :ff])  # noqa: E731
                          * (x @ stacks[0][1, ex][:, ff:])) @ stacks[1][1, ex]
    else:
        stacks = (draw(ff, d), draw(ff, d))
        ffn = lambda ex: jnp.square(jax.nn.relu(x @ stacks[0][1, ex].T)) @ stacks[1][1, ex]  # noqa: E731
    scores = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    if routing == "none":  # every pair goes to an expert that is not held
        scores = scores.at[:, lo:lo + held].add(-20.0)
    top_w, top_i = jax.lax.top_k(jax.nn.sigmoid(scores), k)
    if routing == "all":  # row r also chooses held expert r % held
        top_i = top_i.at[:, 0].set(lo + jnp.arange(t) % held)
    elif routing == "mixed":  # padding rows, and three held experts that nobody chooses
        top_i = jnp.where(jnp.isin(top_i, jnp.asarray([lo, lo + 3, lo + 7])), e - 1, top_i)
        top_i = top_i.at[t // 2:t // 2 + 5].set(-1)
    want = sum(jnp.where(top_i == lo + ex, top_w, 0).sum(axis=1, keepdims=True) * ffn(ex)
               for ex in range(held))
    return x, top_i, top_w, stacks, want, lo, held


WALKS = [(form, t, "mixed", "ragged") for form in ("swiglu", "relu2") for t in (24, 32, 128)] + [
    (form, 32, routing, blocks) for form in ("swiglu", "relu2")
    for routing, blocks in (("none", "ragged"), ("all", "ragged"), ("mixed", "whole"))]


@pytest.mark.parametrize("form,tokens,routing,blocks", WALKS, ids=["-".join(map(str, w)) for w in WALKS])
def test_the_walk_over_the_hit_experts_equals_a_plain_loop(monkeypatch, form, tokens, routing,
                                                           blocks):
    """ops/pallas_experts.walk_experts (interpreted) through the dispatch,
    as the chip's programs take it in the one-tile loop's place: both expert forms, rows that fill a tile or
    not, rows routed to ``-1`` (padding) and to experts not held, no expert
    hit, every expert hit, a hidden width that is two blocks and a half
    (``ragged``: 128 + 128 + 64 columns) or one block (``whole``: SwiGLU's gate
    | up as one run), the second layer of the stacks."""
    from githubrepostorag_tpu.models.moe import dropless_experts
    from githubrepostorag_tpu.ops import pallas_experts

    x, top_i, top_w, stacks, want, lo, held = _walk_case(form, tokens, routing)
    if blocks == "ragged":
        monkeypatch.setattr(pallas_experts, "BLOCK_BYTES", 1)  # a lane tile a block
    body = pallas_experts.SWIGLU if form == "swiglu" else pallas_experts.RELU2

    def refuse(ex, rows):
        raise AssertionError("rows that fit one tile take the walk")

    assert pallas_experts.experts_walk(body, stacks, 1, burst=True) is None  # off the chip: the loop
    walk = functools.partial(pallas_experts.walk_experts, layer=jnp.int32(1), stacks=stacks,
                             body=body, name="moe_experts",
                             block_bytes=pallas_experts.BLOCK_BYTES, interpret=True)
    y, counts = dropless_experts(x, top_i, top_w, refuse, held, lo=lo, listed=True, walk=walk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    hit = ((top_i >= lo) & (top_i < lo + held))
    assert int(counts.sum()) == int(hit.sum())
    assert {"none": int((counts > 0).sum()) == 0, "all": int((counts > 0).sum()) == held,
            "mixed": 0 < int((counts > 0).sum()) <= held - 3}[routing]


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_the_walk_waits_for_every_block_it_reads(form):
    """The same walk under the TPU interpreter, which runs a DMA when it is
    waited for and watches for races: a block read before its DMA was waited
    for, or a slot filled again while it is read, shows here."""
    from jax.experimental.pallas import tpu as pltpu
    from githubrepostorag_tpu.ops import pallas_experts

    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this jax has no TPU interpreter")
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter

    x, top_i, top_w, stacks, want, lo, held = _walk_case(form, 16, "all", seed=9)
    w_dense = sum(jnp.where(top_i[:, j:j + 1] == lo + jnp.arange(held)[None], top_w[:, j:j + 1], 0)
                  for j in range(top_i.shape[1]))
    y = pallas_experts.walk_experts(
        x, w_dense, jnp.arange(held, dtype=jnp.int32)[::-1], jnp.int32(held), jnp.int32(1), stacks,
        body=pallas_experts.SWIGLU if form == "swiglu" else pallas_experts.RELU2,
        name="moe_experts", block_bytes=1,  # a lane tile a block: two blocks and a half
        interpret=pltpu.InterpretParams(detect_races=True, dma_execution_mode="on_wait"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert not tpu_interpreter.races.races_found


def test_moe_int8_quantization(tiny_moe):
    """Weight-only int8 MoE: experts/shared-expert carry stacked per-expert
    scales, router and gate stay full precision, and logits track the bf16
    model within quantization tolerance (greedy engine output included)."""
    from githubrepostorag_tpu.models.quant import (
        QuantizedLinear,
        quantize_qwen2_params,
    )

    _, params, cfg = tiny_moe
    qp = quantize_qwen2_params(params)
    layers = qp["layers"]
    assert isinstance(layers["e_wg"], QuantizedLinear)
    assert layers["e_wg"].q.dtype == jnp.int8
    # scales: [L, E, ff] — per expert, per output channel
    assert layers["e_wg"].s.shape == layers["e_wg"].q.shape[:2] + (
        layers["e_wg"].q.shape[-1],
    )
    assert not isinstance(layers["router"], QuantizedLinear)
    assert not isinstance(layers["s_gate"], QuantizedLinear)

    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16), dtype=np.int32))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (1, 16))
    full = np.asarray(forward_with_attend(params, cfg, ids, pos))
    quant = np.asarray(forward_with_attend(qp, cfg, ids, pos))
    # int8 error bound, not exactness — relative to the logit scale
    assert np.abs(quant - full).max() / (np.abs(full).max() + 1e-6) < 0.15

    prompt = rng.integers(0, cfg.vocab_size, 15).tolist()
    eng = Engine(qp, cfg, max_num_seqs=2, num_pages=32, page_size=8,
                 max_seq_len=64, prefill_chunk=32, kv_dtype=jnp.float32,
                 decode_burst=4)
    res = eng.generate(
        [prompt], SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
    )[0]
    assert len(res.output_tokens) == 8


def test_moe_random_int8_init_still_guarded(tiny_moe):
    from githubrepostorag_tpu.models.quant import init_params_quantized

    _, _, cfg = tiny_moe
    with pytest.raises(NotImplementedError, match="load_qwen2"):
        init_params_quantized(cfg)


def test_moe_sharded_train_step(tiny_moe):
    """The REAL sharded train step (training/step.py) accepts MoE params on
    an ep mesh: loss finite, expert weights actually update."""
    import optax

    from githubrepostorag_tpu.training import init_train_state, make_train_step

    _, _, cfg = tiny_moe
    mesh = make_mesh(MeshPlan(ep=4))
    opt = optax.sgd(1e-2)
    step, _ = make_train_step(cfg, mesh, opt, remat=False)
    state = init_train_state(cfg, mesh, jax.random.PRNGKey(1), opt)
    before = np.asarray(state.params["layers"]["e_wg"])
    rng = np.random.default_rng(4)
    ids = rng.integers(0, cfg.vocab_size, (2, 16), dtype=np.int32)
    batch = {
        "input_ids": jnp.asarray(ids),
        "targets": jnp.asarray(np.roll(ids, -1, 1)),
        "mask": jnp.ones((2, 16), dtype=jnp.int32),
    }
    params, _, loss = step(state.params, state.opt_state, batch)
    assert np.isfinite(float(loss))
    after = np.asarray(params["layers"]["e_wg"])
    assert np.abs(after - before).sum() > 0, "expert weights did not update"


def test_every_token_to_one_expert_loses_nothing(tiny_moe):
    """The case the drop counter used to report: a router forced to send
    every token to expert 0.  The layer's output is the dense formula's, for
    every token, and no [T, E, C] tensor is built on the way."""
    from githubrepostorag_tpu.models import moe

    _, params, cfg = tiny_moe
    lay = dict(params["layers"])
    router = np.asarray(lay["router"]).copy()
    router[:, :, 0] += 100.0
    lay["router"] = jnp.asarray(router)
    x = jnp.asarray(np.abs(np.random.default_rng(0).normal(size=(2, 8, cfg.hidden_size))),
                    dtype=jnp.float32)  # positive, so the biased column wins for every token
    p0 = jax.tree.map(lambda l: l[0], lay)
    got = moe.moe_mlp(cfg, p0, x)
    xf = x.reshape(-1, cfg.hidden_size)
    probs = jax.nn.softmax(xf @ p0["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    assert bool((top_i[:, 0] == 0).all())
    top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    want = jax.nn.silu(xf @ p0["s_wg"]) * (xf @ p0["s_wu"]) @ p0["s_wd"] \
        * jax.nn.sigmoid(xf @ p0["s_gate"])
    for j in range(cfg.num_experts_per_tok):
        for ex in range(cfg.num_experts):
            h = jax.nn.silu(xf @ p0["e_wg"][ex]) * (xf @ p0["e_wu"][ex]) @ p0["e_wd"][ex]
            want = want + jnp.where((top_i[:, j] == ex)[:, None], h * top_p[:, j:j + 1], 0)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, cfg.hidden_size), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    shapes = {tuple(v.aval.shape) for eqn in jax.make_jaxpr(
        lambda x: moe.moe_mlp(cfg, p0, x))(x).jaxpr.eqns for v in eqn.outvars}
    assert not any(len(sh) == 3 and sh[0] == 16 and sh[1] == cfg.num_experts for sh in shapes)
