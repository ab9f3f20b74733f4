"""Falcon-H1 through its step programs and through the engine against the
benchmark's plain reference (benchmarks/reference_falcon_h1.py, which imports
nothing of the program and steps its recurrence one token at a time), at a
small size on the CPU that keeps what is new: every layer runs a Mamba-2 mixer
AND rotary grouped-query attention on one normed input (2 groups, 5 query
heads a kv head, a state of 16 wider than its head of 8, 4 taps with a bias)
and every muP multiplier is away from one.  Prefill chunk by chunk through the
pages and the state pool (150 tokens in chunks of 64 over pages of 16: two
chunk edges, nine page edges), then decode bursts (the engine's own path, with
pages and a snapshot OF THE SAME LAYER restored, is tests/test_falcon_h1_engine.py's).
Logits, not tokens: a
decoded token is held to the reference's logits by how far below the
reference's best it lies, in units of the row's spread.  One test holds the
reference itself to ``transformers``' ``falcon_h1`` module.

Tolerances.  In float32 the program and the reference differ by the order of
their sums alone (the chunked form against the token-by-token recurrence, the
paged softmax against the dense one, the feed-forward whole against in blocks):
2e-5 of the logits' root mean square (it reads 1.4e-6), where a key multiplier
left out or the two branches' multipliers swapped read 1e-3 and more, and a state pool kept in bfloat16 1.4e-4 (rounded
where a chunk hands its state to the next, twice in this prompt; the test below
tries each).  In bfloat16 (weights and products as served, float32 residual
stream and state) the prefill reads 0.0060 at this size; 0.025 leaves four
times that room and is a quarter of what float8 weights read (0.109, the
control)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_falcon_h1 as ref
from githubrepostorag_tpu.models import falcon_h1 as model
from githubrepostorag_tpu.models import hybrid

CFG = model.FalconH1Config.tiny()
MODEL = dict(
    hidden_size=64, intermediate_size=96, num_hidden_layers=3, num_attention_heads=10,
    num_key_value_heads=2, head_dim=16, vocab_size=512, mamba_d_ssm=64, mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, rms_norm_eps=1e-5,
    rope_theta=1e11, embedding_multiplier=CFG.embedding_multiplier,
    lm_head_multiplier=CFG.lm_head_multiplier,
    attention_in_multiplier=CFG.attention_in_multiplier,
    attention_out_multiplier=CFG.attention_out_multiplier, key_multiplier=CFG.key_multiplier,
    ssm_in_multiplier=CFG.ssm_in_multiplier, ssm_out_multiplier=CFG.ssm_out_multiplier,
    ssm_multipliers=list(CFG.ssm_multipliers), mlp_multipliers=list(CFG.mlp_multipliers))
SEED, PAGE, CHUNK, PAGES, ROWS, STEPS = 7, 16, 64, 32, 2, 4
PROMPT = [int(t) for t in np.random.default_rng(0).integers(1, 500, size=150)]
BF16_LIMIT, F32_LIMIT = 0.025, 2e-5


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


def cast(params, act):
    return jax.tree.map(lambda x: x.astype(act) if x.dtype == jnp.bfloat16 else x, params)


def run_program(act, cfg=CFG):
    """(prefill logits at every prompt position, the greedy tokens of one
    burst after it, whether an idle row kept its state and history) from the
    program's own step programs on pools built here."""
    from githubrepostorag_tpu.serving.kv_cache import make_state_pools

    params = cast(model.init_params(cfg, seed=SEED), act)
    kp = jnp.zeros((cfg.kv_layers, cfg.num_kv_heads, PAGES, PAGE, cfg.head_dim), act)
    vp = jnp.zeros_like(kp)
    state = make_state_pools(cfg, ROWS + 3)
    trash = ROWS + 2
    bt = np.zeros((1, 16), np.int32)
    bt[0, :12] = np.arange(12)
    rows, start = [], 0
    while start < len(PROMPT):
        valid = min(CHUNK, len(PROMPT) - start)
        ids = np.zeros((1, CHUNK), np.int32)
        ids[0, :valid] = PROMPT[start:start + valid]
        pos = np.arange(start, start + CHUNK)[None].astype(np.int32)
        slots = np.full((1, CHUNK), -1, np.int32)
        at = start + np.arange(valid)
        slots[0, :valid] = bt[0, at // PAGE] * PAGE + at % PAGE
        logits, kp, vp, state = model.forward_paged(
            params, cfg, jnp.asarray(ids), jnp.asarray(pos), kp, vp, jnp.asarray(slots),
            jnp.asarray(bt), jnp.asarray([start]), jnp.asarray([valid]), state=state,
            state_src=jnp.asarray([0 if start else -1]), state_dst=jnp.asarray([0]),
            state_snap=jnp.asarray([trash]), snap_col=jnp.asarray([0]))
        rows.append(np.asarray(logits[0, :valid], np.float32))
        start += valid
    prefill = np.concatenate(rows)
    first = int(np.argmax(prefill[-1]))
    bt2 = np.zeros((ROWS, 16), np.int32)
    bt2[0] = bt[0]
    before = jax.tree.map(lambda x: np.asarray(x[:, 1]), state)  # row 1 sits the burst out
    out = model.decode_burst(
        params, cfg, jnp.asarray([first, 0]), jnp.asarray([len(PROMPT), 0]), kp, vp,
        jnp.zeros((ROWS, cfg.vocab_size), bool), jnp.asarray([True, False]),
        jnp.asarray([190, 0]), jnp.asarray(bt2), jax.random.PRNGKey(0), jnp.zeros((ROWS,)),
        jnp.ones((ROWS,)), jnp.zeros((ROWS,), jnp.int32), jnp.ones((ROWS,)), n_steps=STEPS,
        filter_sampling=False, first_tokens=jnp.zeros((ROWS,), jnp.int32),
        fresh=jnp.zeros((ROWS,), bool), fresh_lens=jnp.zeros((ROWS,), jnp.int32),
        key_step=jnp.uint32(1), state=state)
    assert len(out) == 8  # no expert counts: the state rides last
    after = jax.tree.map(lambda x: np.asarray(x[:, 1]), out[-1])
    idle_kept = all(bool((before[k] == after[k]).all()) for k in before)
    return prefill, [first] + [int(t) for t in np.asarray(out[0])[0]], idle_kept


@pytest.fixture(scope="module")
def reference():
    return ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))])[0]


@pytest.fixture()
def in_float32(monkeypatch):
    monkeypatch.setattr(model, "ACT", jnp.float32)
    jax.clear_caches()
    yield
    jax.clear_caches()


def decode_gaps(prompt, tokens, control=None):
    """How far below the reference's best logit each decoded token lies, in
    units of the row's spread (benchmarks/correctness.token_gap)."""
    full = prompt + tokens[:-1]
    rows = ref.logits_at(MODEL, SEED, [full], [list(range(len(prompt) - 1, len(full)))],
                         control=control)[0]
    return [float((r.max() - r[t]) / r.std()) for r, t in zip(rows, tokens)]


def test_a_layer_of_both_kinds_counts_among_the_state_layers_and_the_page_layers():
    """models/hybrid.py's walk: a ``B`` layer advances the state index AND the
    page index; the older letters count as they did."""
    walked = [(kinds, before) for kinds, _, before in hybrid._walk((("RB", 2), ("BAF", 1)))]
    assert walked[1] == ("BAF", {"R": 4, "A": 2, "F": 0, "all": 4})
    assert list(hybrid._layers("RB", walked[0][1], 1)) == [("R", 2, 2), ("B", (3, 1), 3)]
    assert list(hybrid._layers("BAF", walked[1][1], 0)) == [
        ("B", (4, 2), 4), ("A", 3, 5), ("F", 0, 6)]
    assert CFG.layer_segments == (("B", 3),)
    assert CFG.kv_layers == CFG.state_layers == CFG.num_layers == 3
    assert [n for _, n, _ in hybrid._layers("B", {"R": 0, "A": 0, "F": 0, "all": 0}, 2)] == [(2, 2)]


def test_the_programs_leaves_are_the_references_leaves():
    """``leaf_order`` twice: the program's and the reference's re-statement of
    it agree on names, shapes and gains, and a drawn leaf is the same numbers."""
    mine = [(".".join(p), tuple(s), g) for p, s, g in model.leaf_order(CFG)]
    assert mine == [(n, tuple(s), g) for n, s, g in ref.leaf_order(MODEL)]
    params = model.init_params(CFG, seed=SEED)
    w = ref.Weights(MODEL, SEED)
    np.testing.assert_array_equal(np.asarray(params["mlp"]["wd"][2], np.float32),
                                  np.asarray(w.at("mlp.wd", 2)))
    np.testing.assert_array_equal(np.asarray(params["mlp"]["w_gate"][1, :, 32:64], np.float32),
                                  np.asarray(w.cols("mlp.w_gate", 1, 32, 32)))
    np.testing.assert_array_equal(np.asarray(params["mlp"]["wd"][1, 32:64], np.float32),
                                  np.asarray(w.rows("mlp.wd", 1, 32, 32)))
    a_log, dt_bias = ref.ssm_scalars(w.at("ssm.a_u", 1), w.at("ssm.dt_u", 1))
    np.testing.assert_allclose(np.asarray(params["ssm"]["A_log"][1]), np.asarray(a_log), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(params["ssm"]["dt_bias"][1]), np.asarray(dt_bias),
                               rtol=1e-5)


def test_float32_program_is_the_reference_to_rounding(in_float32, reference):
    prefill, tokens, idle_kept = run_program(jnp.float32)
    assert rel_rms(prefill, reference) < F32_LIMIT
    assert max(decode_gaps(PROMPT, tokens)) < 1e-4  # the burst's tokens are the reference's best
    assert idle_kept  # a row that sits the burst out keeps state and history bit for bit


def test_bfloat16_program_is_inside_its_tolerance_and_the_fp8_control_is_not(reference):
    prefill, tokens, idle_kept = run_program(jnp.bfloat16)
    err = rel_rms(prefill, reference)
    control = ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))], control="fp8")[0]
    low = rel_rms(control, reference)
    print(f"prefill_logits_rel_rms: bfloat16 program {err:.3g}, fp8 control {low:.3g}")
    assert 4 * err < BF16_LIMIT < low / 4  # 0.0060 and 0.109
    assert np.mean(decode_gaps(PROMPT, tokens)) < 0.05 and idle_kept


@pytest.mark.parametrize("departure,factor", [
    ("bfloat16_state", 5), ("no_key_multiplier", 20), ("branch_multipliers_swapped", 20)])
def test_each_stated_precision_and_multiplier_shows_in_float32(in_float32, monkeypatch,
                                                               reference, departure, factor):
    """The tight limit sees bfloat16 where float32 is stated and every piece
    of the block's wiring: the program with one of them changed is not the
    reference by 20x the limit (5x for the state pool, which is rounded twice
    in this prompt and not once a token)."""
    import dataclasses

    cfg = CFG
    if departure == "bfloat16_state":  # the pool's matrix in bfloat16: rounded once a token
        shapes = CFG.state_shapes()
        monkeypatch.setattr(model.FalconH1Config, "state_shapes", lambda self: {
            **shapes, "s": (shapes["s"][0], jnp.dtype(jnp.bfloat16))})
    elif departure == "no_key_multiplier":
        cfg = dataclasses.replace(CFG, key_multiplier=1.0)
    else:
        cfg = dataclasses.replace(CFG, ssm_out_multiplier=CFG.attention_out_multiplier,
                                  attention_out_multiplier=CFG.ssm_out_multiplier)
    prefill, _, _ = run_program(jnp.float32, cfg)
    assert rel_rms(prefill, reference) > factor * F32_LIMIT


def test_zeroing_each_branch_of_the_reference_moves_the_logits(reference):
    """What the configuration's gains are for, at the tiny size: the Mamba-2
    branch, the attention branch, the feed-forward and the state's own part of
    the mixer each move the logits by more than the bfloat16 limit, so a
    program that computed one of them wrongly could not pass."""
    for knock in ref.KNOCK_OUTS:
        got = ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))], control=knock)[0]
        assert rel_rms(got, reference) > 3 * BF16_LIMIT, knock


def test_rotary_tables_at_theta_1e11_keep_the_slow_columns_in_float32():
    """ops/rope.py at the published theta: inverse frequencies run down to
    1.5e-11, and at position 262,143 the slowest columns' sines (4e-6) are
    still good to float32 rounding, from the function's own float32 power and
    from the host-made frequencies this family hands it."""
    from githubrepostorag_tpu.ops.rope import rope_cos_sin

    hd, theta = 128, 1e11
    pos = np.array([[1, 8191, 262143]], np.int32)
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    want = np.sin(pos[0, :, None].astype(np.float64) * inv)
    for kw in (dict(theta=theta), dict(inv_freq=jnp.asarray(inv, jnp.float32))):
        cos, sin = rope_cos_sin(jnp.asarray(pos), hd, **kw)
        assert cos.dtype == sin.dtype == jnp.float32
        slow = np.asarray(sin[0, :, 32:64], np.float64)  # angles under 1 rad at 262,143
        np.testing.assert_allclose(slow, want[:, 32:], rtol=2e-5)
        assert slow[2, -1] > 3e-6 and float(cos[0, 2, 63]) == 1.0
    made = model._Layers.position_cols(model.FalconH1Config(), jnp.asarray(pos))
    # the fast columns, to the float32 angle's own rounding (0.016 at 2.6e5 rad)
    np.testing.assert_allclose(np.asarray(made[1][0, :, :64], np.float64), want, atol=0.02)


def test_the_reference_is_the_published_module():
    """The anchor that no multiplier is guessed: ``transformers``' own
    ``FalconH1ForCausalLM`` at a tiny ``FalconH1Config``, given the reference's
    weights, computes the reference's logits (float32 on both sides)."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.falcon_h1")

    m = dict(MODEL, num_hidden_layers=2)
    cfg = hf.FalconH1Config(
        **{k: v for k, v in m.items()}, mamba_expand=2, mamba_chunk_size=16, mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_norm_before_gate=False, mamba_rms_norm=True,
        attention_bias=False, mlp_bias=False, projectors_bias=False, hidden_act="silu",
        tie_word_embeddings=False, max_position_embeddings=1024, attn_implementation="eager",
        pad_token_id=0)
    net = hf.FalconH1ForCausalLM(cfg).float().eval()
    w = ref.Weights(m, SEED)
    t = lambda x: torch.tensor(np.asarray(x, np.float32))  # noqa: E731
    with torch.no_grad():
        net.model.embed_tokens.weight.copy_(t(w.embed(np.arange(m["vocab_size"]))))
        net.lm_head.weight.copy_(t(w.head_cols(0, m["vocab_size"])).T)
        net.model.final_layernorm.weight.fill_(1.0)
        for i, layer in enumerate(net.model.layers):
            mx, at, ff = layer.mamba, layer.self_attn, layer.feed_forward
            mx.in_proj.weight.copy_(torch.cat(
                [t(w.at(f"ssm.{n}", i)) for n in ("w_z", "w_xbc", "w_dt")], dim=1).T)
            mx.conv1d.weight.copy_(t(w.at("ssm.conv_w", i))[:, None, :])
            mx.conv1d.bias.copy_(t(w.at("ssm.conv_b", i)))
            a_log, dt_bias = ref.ssm_scalars(w.at("ssm.a_u", i), w.at("ssm.dt_u", i))
            mx.A_log.copy_(t(a_log))
            mx.dt_bias.copy_(t(dt_bias))
            mx.D.fill_(1.0)
            mx.norm.weight.fill_(1.0)
            mx.out_proj.weight.copy_(t(w.at("ssm.w_out", i)).T)
            for name, leaf in (("q_proj", "wq"), ("k_proj", "wk"), ("v_proj", "wv"),
                               ("o_proj", "wo")):
                getattr(at, name).weight.copy_(t(w.at(f"attn.{leaf}", i)).T)
            for name, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_up"), ("down_proj", "wd")):
                getattr(ff, name).weight.copy_(t(w.at(f"mlp.{leaf}", i)).T)
            layer.input_layernorm.weight.fill_(1.0)
            layer.pre_ff_layernorm.weight.fill_(1.0)
        ids = PROMPT[:70]  # not a whole number of the module's chunks of 16
        got = net(torch.tensor([ids])).logits[0].numpy()
    want = ref.logits_at(m, SEED, [ids], [list(range(len(ids)))])[0]
    assert rel_rms(got, want) < 2e-5  # float32 on both sides: the order of the sums
