"""Ling-3.0-flash (models/bailing_hybrid.py) through its step programs against
the benchmark's plain reference (benchmarks/reference_bailing_hybrid.py, which
imports nothing of the program, steps the KDA recurrence one token at a time
and materialises the latent layer's keys and values), at a small size on the
CPU that keeps what is new: a decay a key channel bounded at -5, a latent page
pool BESIDE a state pool, interleaved rotary, a head-wise gate, a dense layer
then group-limited sigmoid experts of which a share is held.  Prefill chunk by
chunk through latent pages and the state pool (150 tokens in chunks of 64 over
pages of 16), then a decode burst; the engine's own path is
tests/test_bailing_hybrid_engine.py's.  Logits, not tokens.

Tolerances.  In float32 the program and the reference differ by the order of
their sums alone (the chunked form against the token scan, the absorbed
latent form against the materialised one): 2e-5 of the logits' root mean
square, where every knock-out of the reference reads 6e-4 and more.  In
bfloat16 (as served; float32 residual stream and state) the prefill reads
~0.01 at this size; 0.04 is a third of what float8 weights read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_bailing_hybrid as ref
from githubrepostorag_tpu.models import bailing_hybrid as model

CFG = model.BailingHybridConfig.tiny()


def model_of(cfg):
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, moe_intermediate_size=cfg.moe_intermediate_size,
        moe_shared_expert_intermediate_size=cfg.shared_expert_intermediate_size,
        num_hidden_layers=cfg.num_layers, layer_group_size=cfg.layer_group_size,
        layer_kinds=cfg.kinds, first_k_dense_replace=cfg.first_k_dense,
        num_attention_heads=cfg.num_heads, head_dim=cfg.kda_head_dim,
        short_conv_kernel_size=cfg.short_conv_kernel_size, kda_lower_bound=cfg.kda_lower_bound,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok, n_group=cfg.n_group,
        topk_group=cfg.topk_group, routed_scaling_factor=cfg.routed_scaling_factor,
        rms_norm_eps=cfg.rms_norm_eps, experts_held=list(cfg.experts_held))


MODEL = model_of(CFG)
SEED, PAGE, CHUNK, PAGES, ROWS, STEPS = 7, 16, 64, 32, 2, 4
PROMPT = [int(t) for t in np.random.default_rng(0).integers(1, 500, size=150)]
BF16_LIMIT, F32_LIMIT = 0.04, 2e-5


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


def cast(params, act):
    return jax.tree.map(lambda x: x.astype(act) if x.dtype == jnp.bfloat16 else x, params)


def run_program(act, cfg=CFG, use_pallas=False):
    """(prefill logits at every prompt position, the greedy tokens of one burst
    after it, whether an idle row kept its state and history, the counts) from
    the program's own step programs on pools built here."""
    from githubrepostorag_tpu.serving.kv_cache import make_page_pools, make_state_pools

    params = cast(model.init_params(cfg, seed=SEED), act)
    pools = make_page_pools(cfg, PAGES, PAGE, dtype=act)
    assert pools.v is None and pools.k.shape == (cfg.kv_layers, 1, PAGES, PAGE, cfg.head_dim)
    kp = pools.k
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
        logits, kp, none, counts, state = model.forward_paged(
            params, cfg, jnp.asarray(ids), jnp.asarray(pos), kp, None, jnp.asarray(slots),
            jnp.asarray(bt), jnp.asarray([start]), jnp.asarray([valid]), use_pallas=use_pallas,
            state=state, state_src=jnp.asarray([0 if start else -1]), state_dst=jnp.asarray([0]),
            state_snap=jnp.asarray([trash]), snap_col=jnp.asarray([0]))
        assert none is None and counts.shape == (3,)
        rows.append(np.asarray(logits[0, :valid], np.float32))
        start += valid
    prefill = np.concatenate(rows)
    first = int(np.argmax(prefill[-1]))
    bt2 = np.zeros((ROWS, 16), np.int32)
    bt2[0] = bt[0]
    before = jax.tree.map(lambda x: np.asarray(x[:, 1]), state)  # row 1 sits the burst out
    out = model.decode_burst(
        params, cfg, jnp.asarray([first, 0]), jnp.asarray([len(PROMPT), 0]), kp, None,
        jnp.zeros((ROWS, cfg.vocab_size), bool), jnp.asarray([True, False]),
        jnp.asarray([190, 0]), jnp.asarray(bt2), jax.random.PRNGKey(0), jnp.zeros((ROWS,)),
        jnp.ones((ROWS,)), jnp.zeros((ROWS,), jnp.int32), jnp.ones((ROWS,)), n_steps=STEPS,
        use_pallas=use_pallas, filter_sampling=False, first_tokens=jnp.zeros((ROWS,), jnp.int32),
        fresh=jnp.zeros((ROWS,), bool), fresh_lens=jnp.zeros((ROWS,), jnp.int32),
        key_step=jnp.uint32(1), state=state)
    assert len(out) == 9 and out[3] is None  # no V pool; the counts, then the state, ride last
    after = jax.tree.map(lambda x: np.asarray(x[:, 1]), out[-1])
    idle_kept = all(bool((before[k] == after[k]).all()) for k in before)
    return (prefill, [first] + [int(t) for t in np.asarray(out[0])[0]], idle_kept,
            np.asarray(out[-2]))


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


def test_the_pattern_puts_the_latent_layer_last_in_its_group_and_cuts_state_their_own():
    full = model.BailingHybridConfig()
    assert full.kinds == ("RRRRRA" * 7) and (full.kv_layers, full.state_layers) == (7, 35)
    assert full.expert_layers == 40 and full.head_dim == 640
    assert full.state_shapes()["s"][0] == (32, 128, 128)
    assert full.state_shapes()["conv"][0] == (3 * 12288,)
    cut = model.BailingHybridConfig(num_layers=7, first_k_dense=1, layer_kinds="RRRRRRA")
    assert cut.layer_segments == (("R", 6), ("A", 1)) and (cut.kv_layers, cut.state_layers) == (1, 6)
    assert CFG.layer_segments == (("R", 3), ("A", 1), ("R", 2), ("A", 1))
    assert (CFG.latent_kv, CFG.recurrent_state, CFG.expert_counters) == (True, True, True)


def test_prefill_and_decode_in_float32_are_the_references(in_float32, reference):
    """Chunked KDA against the token scan, absorbed and materialised latent
    attention against the plain one, through latent pages and the state pool:
    the order of the sums alone apart.  The Pallas kernels (interpreted) give
    the array forms' logits."""
    prefill, tokens, idle_kept, counts = run_program(jnp.float32)
    assert rel_rms(prefill, reference) < F32_LIMIT
    assert idle_kept and max(decode_gaps(PROMPT, tokens)) < 1e-3
    # experts hit, pairs to held experts (every expert is held: 4 a token and expert layer)
    assert counts[1] == STEPS * CFG.num_experts_per_tok * CFG.expert_layers
    kernels = run_program(jnp.float32, use_pallas=True)
    assert rel_rms(kernels[0], reference) < F32_LIMIT and kernels[1] == tokens and kernels[2]


@pytest.mark.parametrize("control", ref.KNOCK_OUTS)
def test_every_knock_out_of_the_reference_is_seen(in_float32, reference, control):
    """Each knock-out breaks one thing the program must get right, and the
    comparison sees it: 10 times the float32 tolerance at the least (the
    rotary pairing reads 6e-4 at this size, where random weights leave the
    softmax nearly flat; the others 1e-3 and more)."""
    broken = ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))], control=control)[0]
    assert rel_rms(broken, reference) > 10 * F32_LIMIT


def test_prefill_in_bfloat16_stays_inside_its_tolerance_and_fp8_does_not(reference):
    prefill, tokens, idle_kept, _ = run_program(jnp.bfloat16)
    assert rel_rms(prefill, reference) < BF16_LIMIT and idle_kept
    assert np.mean(decode_gaps(PROMPT, tokens)) < 0.05
    fp8 = ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))], control="fp8")[0]
    assert rel_rms(fp8, reference) > 3 * BF16_LIMIT


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Ids 0-3, 4-7, 8-11, 12-15 of one uncut draw, the shared expert counted
    once, add up to the reference's layer over all 16; the program's layer
    holding one share computes that share (``lo`` offsets the ids)."""
    s = ref.dims(MODEL)
    w = ref.Weights(MODEL, SEED)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, s["d"]), jnp.float32)
    router, bias = w.at("moe.router", 0), w.at("moe.e_bias", 0)
    expert = lambda e: (w.at("moe.e_wgu", 0, e), w.at("moe.e_wd", 0, e))  # noqa: E731
    shared = (w.at("moe.s_wgu", 0), w.at("moe.s_wd", 0))
    whole = ref.moe_layer(MODEL, x, router, bias, expert, shared)
    parts = 0.0
    for lo in range(0, 16, 4):
        share = {**MODEL, "experts_held": [lo, lo + 4]}
        parts = parts + ref.moe_layer(share, x, router, bias,
                                      lambda e, lo=lo: expert(lo + e), None)
    parts = parts + ref._swiglu(x, *shared)
    assert rel_rms(np.asarray(parts), np.asarray(whole)) < 1e-6
    # the program's expert layer on the second share, against the reference's same share
    cfg = model.BailingHybridConfig.tiny(experts_held=(4, 8))
    p = {"router": router, "e_bias": bias, "s_wgu": shared[0], "s_wd": shared[1]}
    experts = {"e_wgu": jnp.stack([expert(e)[0] for e in range(4, 8)])[None],
               "e_wd": jnp.stack([expert(e)[1] for e in range(4, 8)])[None]}
    with jax.default_matmul_precision("highest"):
        got, counts = model._moe_ffn(cfg, p, experts, 0, x[None], jnp.ones((1, 24), bool))
    want = ref.moe_layer({**MODEL, "experts_held": [4, 8]}, x, router, bias,
                         lambda e: expert(4 + e), shared)
    assert rel_rms(np.asarray(got[0]), np.asarray(want)) < 1e-5
    assert counts[1] <= 24 * cfg.num_experts_per_tok and counts[2] <= counts[1]
