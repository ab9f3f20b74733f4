"""Nemotron-H through its two step programs and through the engine against the
benchmark's plain reference (benchmarks/reference_nemotron_h.py, which imports
nothing of the program and steps its recurrence one token at a time), at a
small size on the CPU: a pattern of twelve one-sublayer blocks (``MEM*EMEM*EME``:
a run that repeats, then a tail that repeats otherwise), prefill chunk by
chunk through the paged cache and the state pool, then decode bursts; then the
engine's own path with a snapshot restored.  Logits, not tokens: a decoded
token is held to the reference's logits by how far below the reference's best
it lies, in units of the row's spread.

Tolerances.  In float32 the program and the reference differ by the order of
their sums alone (the chunked form against the token-by-token recurrence, the
sorted dispatch against a dense loop over experts): 5e-5 of the logits' root
mean square, where a norm before the gate, a norm over the whole width, a
missing convolution bias, a missing skip, a weight that keeps its selection
bias or a rotary that should not be there read 1e-3 and more (the test below
tries each).  In bfloat16 (weights and products as served, float32 residual
stream and state) the prefill reads 0.0071 at this size (a pre-norm block
with a float32 stream: Qwen3-Next's order, a tenth of Olmo-Hybrid's
output-normed 0.099); 0.03 leaves four times that room and is a quarter of
what float8 weights read (0.125, the control)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_nemotron_h as ref
from githubrepostorag_tpu.models import nemotron_h as model
from githubrepostorag_tpu.serving import Engine, SamplingParams

MODEL = dict(hidden_size=64, hybrid_override_pattern="MEM*EMEM*EME", num_hidden_layers=12,
             num_attention_heads=8, num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
             mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
             n_routed_experts=16, num_experts_per_tok=4, moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=48, n_group=1, topk_group=1,
             norm_topk_prob=True, routed_scaling_factor=2.5, time_step_min=0.001,
             time_step_max=0.1, time_step_floor=1e-4, layer_norm_epsilon=1e-5, vocab_size=512,
             experts_held=[0, 16])
SEED, PAGE, CHUNK, PAGES, ROWS, STEPS = 7, 16, 64, 32, 2, 4
PROMPT = [int(t) for t in np.random.default_rng(0).integers(1, 500, size=150)]
BF16_LIMIT, F32_LIMIT = 0.03, 5e-5


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


def tiny(**kw):
    return model.NemotronHConfig.tiny(**kw)


def cast(params, act):
    return jax.tree.map(lambda x: x.astype(act) if x.dtype == jnp.bfloat16 else x, params)


def run_program(act, cfg=None):
    """(prefill logits at every prompt position, the greedy tokens of one
    burst after it, whether an idle row kept its state, the bursts' expert
    counts) from the program's own step programs on pools built here."""
    cfg = cfg or tiny()
    params = cast(model.init_params(cfg, seed=SEED), act)
    kp = jnp.zeros((cfg.kv_layers, cfg.num_kv_heads, PAGES, PAGE, cfg.head_dim), act)
    vp = jnp.zeros_like(kp)
    from githubrepostorag_tpu.serving.kv_cache import make_state_pools

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
        logits, kp, vp, stats, state = model.forward_paged(
            params, cfg, jnp.asarray(ids), jnp.asarray(pos), kp, vp, jnp.asarray(slots),
            jnp.asarray(bt), jnp.asarray([start]), jnp.asarray([valid]), state=state,
            state_src=jnp.asarray([0 if start else -1]), state_dst=jnp.asarray([0]),
            state_snap=jnp.asarray([trash]), snap_col=jnp.asarray([0]))
        # padding wakes no expert: every real token sends k pairs to each expert layer
        pairs = valid * cfg.num_experts_per_tok * cfg.expert_layers
        assert int(stats[1]) == pairs if cfg.n_held == cfg.num_experts else int(stats[1]) < pairs
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
    assert len(out) == 9  # the expert layers' counts ride beside the state
    after = jax.tree.map(lambda x: np.asarray(x[:, 1]), out[-1])
    idle_kept = all(bool((before[k] == after[k]).all()) for k in before)
    return (prefill, [first] + [int(t) for t in np.asarray(out[0])[0]], idle_kept,
            [int(x) for x in np.asarray(out[-2])])


@pytest.fixture(scope="module")
def reference():
    return ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))])[0]


@pytest.fixture()
def in_float32(monkeypatch):
    monkeypatch.setattr(model, "ACT", jnp.float32)
    jax.clear_caches()
    yield
    jax.clear_caches()


def decode_gaps(prompt, tokens, control=None, share=MODEL):
    """How far below the reference's best logit each decoded token lies, in
    units of the row's spread (benchmarks/correctness.token_gap)."""
    full = prompt + tokens[:-1]
    rows = ref.logits_at(share, SEED, [full], [list(range(len(prompt) - 1, len(full)))],
                         control=control)[0]
    return [float((r.max() - r[t]) / r.std()) for r, t in zip(rows, tokens)]


def test_the_pattern_is_walked_in_runs_that_repeat():
    from githubrepostorag_tpu.models import hybrid

    assert hybrid.segments("MEMEM*EMEMEM*EMEME") == (("MEMEM*E", 2), ("ME", 2))
    assert hybrid.segments("RRRA") == (("R", 3), ("A", 1))
    full = model.NemotronHConfig().pattern
    assert "".join(k * r for k, r in hybrid.segments(full)) == full
    cfg = tiny()
    assert cfg.layer_segments == (("RFRAF", 2), ("RF", 1))
    assert (cfg.state_layers, cfg.expert_layers, cfg.kv_layers, cfg.num_layers) == (5, 5, 2, 12)


def test_float32_program_is_the_reference_to_rounding(in_float32, reference):
    prefill, tokens, idle_kept, counts = run_program(jnp.float32)
    assert rel_rms(prefill, reference) < F32_LIMIT
    assert max(decode_gaps(PROMPT, tokens)) < 1e-4  # the burst's tokens are the reference's best
    assert idle_kept  # a row that sits the burst out keeps state and history bit for bit
    assert counts[1] == STEPS * 4 * 5  # one live row: k pairs a step and expert layer, all held


def test_float32_share_of_the_experts_is_the_references_share(in_float32):
    """Experts 4..11 of 16 held: the router scores all 16, the layer adds its
    own experts' part and nothing for the others, as the reference given the
    same share does."""
    share = dict(MODEL, experts_held=[4, 12])
    want = ref.logits_at(share, SEED, [PROMPT], [list(range(len(PROMPT)))])[0]
    prefill, tokens, _, counts = run_program(jnp.float32, tiny(experts_held=(4, 12)))
    assert rel_rms(prefill, want) < F32_LIMIT
    assert max(decode_gaps(PROMPT, tokens, share=share)) < 1e-4
    assert 0 < counts[1] < STEPS * 4 * 5


def test_bfloat16_program_is_inside_its_tolerance_and_the_fp8_control_is_not(reference):
    prefill, tokens, _, _ = run_program(jnp.bfloat16)
    err = rel_rms(prefill, reference)
    control = ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))], control="fp8")[0]
    low = rel_rms(control, reference)
    print(f"prefill_logits_rel_rms: bfloat16 program {err:.3g}, fp8 control {low:.3g}")
    assert 3 * err < BF16_LIMIT < low / 3
    assert np.mean(decode_gaps(PROMPT, tokens)) < 0.05


@pytest.mark.parametrize("departure", ["norm_before_gate", "norm_over_the_whole_width",
                                       "no_conv_bias", "no_skip", "biased_weights", "rotary"])
def test_each_published_convention_shows_in_float32(in_float32, monkeypatch, reference,
                                                    departure):
    """The tight limit sees every piece of the block's wiring: the program
    with one of them changed is not the reference by 20x the limit."""
    from githubrepostorag_tpu.models import hybrid, moe
    from githubrepostorag_tpu.ops import gated_delta, norms, ssd
    from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate_leading

    if departure == "norm_before_gate":  # ops/norms.rms_norm_gated's order, by group
        def norm_first(x, gate, weight, groups, eps):
            xg = x.reshape(*x.shape[:-1], groups, -1)
            xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
            return xg.reshape(x.shape) * weight * jax.nn.silu(gate)
        monkeypatch.setattr(hybrid, "rms_norm_gate_first", norm_first)  # the shared mixer's
    elif departure == "norm_over_the_whole_width":
        monkeypatch.setattr(hybrid, "rms_norm_gate_first",
                            lambda x, g, w, groups, eps: norms.rms_norm_gate_first(x, g, w, 1, eps))
    elif departure == "no_conv_bias":
        monkeypatch.setattr(hybrid, "causal_conv",
                            lambda *a, bias=None: gated_delta.causal_conv(*a))
    elif departure == "no_skip":
        chunked = ssd.ssd_chunked
        monkeypatch.setattr(ssd, "ssd_chunked", lambda s, x, dt, a, b, c, d, *r, **k:
                            chunked(s, x, dt, a, b, c, jnp.zeros_like(d), *r, **k))
    elif departure == "biased_weights":  # the selection bias left in the weights
        def biased(scores, bias, *a):
            return moe.route_noaux_tc(scores + bias[None], jnp.zeros_like(bias), *a)
        monkeypatch.setattr(model, "route_noaux_tc", biased)
    else:  # rotary on the whole head
        plain = model._attn_project

        def rotated(cfg, p, x):
            q, k, v, more = plain(cfg, p, x)
            pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
            cos, sin = rope_cos_sin(pos, cfg.head_dim, 1e4)
            cos, sin = cos[:, :, None, :], sin[:, :, None, :]
            return rope_rotate_leading(q, cos, sin), rope_rotate_leading(k, cos, sin), v, more
        monkeypatch.setattr(model, "_attn_project", rotated)
    prefill, _, _, _ = run_program(jnp.float32)
    assert rel_rms(prefill[:CHUNK], reference[:CHUNK]) > 20 * F32_LIMIT


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """models/moe.dropless_experts is told which experts it holds; the parts
    that the four shares of one expert layer give through the program's own
    ``_moe_ffn`` (sigmoid router over all the experts, two-product relu^2
    experts), with the shared expert counted once, add up to what the
    reference gives for the whole layer (every expert held).  Float32, one
    draw of the uncut stacks sliced here."""
    rng = np.random.default_rng(3)
    d, e, f, fs, k, t = 32, 16, 24, 40, 4, 40
    x = jnp.asarray(rng.normal(size=(1, t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.05, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(e, f, d)) * 0.2, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)) * 0.2, jnp.float32)
    s_wu = jnp.asarray(rng.normal(size=(fs, d)) * 0.2, jnp.float32)
    s_wd = jnp.asarray(rng.normal(size=(fs, d)) * 0.2, jnp.float32)
    whole = dict(MODEL, hidden_size=d, n_routed_experts=e, num_experts_per_tok=k,
                 moe_intermediate_size=f, moe_shared_expert_intermediate_size=fs,
                 experts_held=[0, e])
    want = ref.moe_layer(whole, x[0], router, bias, lambda i: (wu[i], wd[i]), (s_wu, s_wd))
    live = jnp.ones((1, t), bool)
    total, pairs = jnp.zeros_like(x), 0
    shared = model.relu2_ffn(x, s_wu, s_wd)
    for lo in range(0, e, e // 4):
        cfg = tiny(hidden_size=d, num_experts=e, num_experts_per_tok=k,
                   moe_intermediate_size=f, shared_expert_intermediate_size=fs,
                   experts_held=(lo, lo + e // 4))
        p = {"router": router, "e_bias": bias, "s_wu": s_wu, "s_wd": s_wd}
        stacks = {"e_wu": wu[None, lo:lo + e // 4], "e_wd": wd[None, lo:lo + e // 4]}
        part, stats = model._moe_ffn(cfg, p, stacks, 0, x, live)
        total, pairs = total + (part - shared), pairs + int(stats[1])
    assert pairs == t * k  # every pair lands in exactly one share
    np.testing.assert_allclose((total + shared)[0], want, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ the engine --

RNG = np.random.default_rng(1)
HEAD = [int(t) for t in RNG.integers(1, 500, size=100)]
A = HEAD + [int(t) for t in RNG.integers(1, 500, size=50)]   # 150 tokens: last boundary 144
B = HEAD + [int(t) for t in RNG.integers(1, 500, size=20)]   # shares 6 pages (96) with A
SP = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())


def build_engine(act, **kw):
    cfg = tiny()
    params = cast(model.init_params(cfg, seed=SEED), act)
    return Engine(params, cfg, **{**dict(
        max_num_seqs=4, num_pages=64, page_size=PAGE, max_seq_len=256, prefill_chunk=64,
        decode_burst=4, kv_dtype=act, state_snapshots=4), **kw})


def run(eng, prompt):
    res = eng.generate([prompt], SP)[0]
    return res.cached_tokens, list(res.output_tokens)


def test_engine_prefill_decode_and_a_restored_snapshot_are_the_references(in_float32):
    """The engine's own path in float32: a cold prompt through waves (three
    chunks) and bursts, a second prompt of the same head that leaves the
    branch-point snapshot, and both again from their snapshots.  Every token
    (the first is the prefill's, the rest the bursts', through pages and
    state) lies within 1e-3 of a row's spread of the reference's best logit;
    the fp8 control's own best tokens do not."""
    eng = build_engine(jnp.float32)
    cached, cold = run(eng, A)
    assert cached == 0 and max(decode_gaps(A, cold)) < 1e-3
    assert eng._state.written == 1
    cached, out_b = run(eng, B)
    assert cached == 0 and eng.page_hit_tokens == 96 and max(decode_gaps(B, out_b)) < 1e-3
    cached, again = run(eng, A)  # from the snapshot at its last page boundary
    assert cached == 144 and again == cold and eng.state_restored == 1
    cached, again_b = run(eng, B)  # from the branch-point snapshot
    assert cached == 96 and again_b == out_b and max(decode_gaps(B, again_b)) < 1e-3
    assert (eng.page_hit_tokens, eng.state_hit_tokens) == (96 + 144 + 112, 144 + 96)
    assert max(decode_gaps(B, again_b, control="fp8")) > 1e-3  # held to the control, it fails
    # the expert counters came with ``expert_counters``: pairs to held experts, slots offered
    burst = eng.moe_stats["burst"]
    assert burst[1] > 0 and burst[2] % (eng.cfg.n_held * eng.cfg.expert_layers) == 0


def test_engine_with_the_kernel_on_the_pool_gives_the_array_forms_tokens(in_float32):
    """The burst's one-token rule as ops/pallas_state.py's kernel (``use_pallas``,
    interpreted here) against ``ssd_step`` (the engine's path on the CPU): the
    same prompts through waves, bursts, snapshots and restores give the same
    tokens; a snapshot's slot keeps the bits it was written with through every
    burst that follows (the kernel addresses live rows' slots alone), so what a
    restore reads is what was saved."""
    plain, kernel = build_engine(jnp.float32), build_engine(jnp.float32, use_pallas=True)
    saved = {}
    for prompt in (A, B, A, B, A + B[100:]):
        want, got = run(plain, prompt), run(kernel, prompt)
        assert got == want and max(decode_gaps(prompt, got[1])) < 1e-3
        pool = np.asarray(kernel.state_pools["s"]).view(np.uint32)
        for h, slot in kernel._state._by_hash.items():
            bits = saved.setdefault(h, pool[:, slot])  # first seen: as the wave wrote it
            assert (pool[:, slot] == bits).all()
    assert kernel.state_restored == plain.state_restored == 3 and len(saved) >= 3
    # the rows that sat every burst out (one request at a time: rows 1 .. 3) hold what they held
    assert not pool[:, 1:4].any()


def test_engine_in_bfloat16_stays_inside_the_decode_tolerance():
    """As served (bfloat16 weights, products and pages, float32 state): the
    tokens of a cold and of a resumed prompt lie 0.05 of a row's spread below
    the reference's best on average at the most (rounding flips near-ties; a
    wrong state or a stale page reads 1 and more)."""
    eng = build_engine(jnp.bfloat16)
    _, cold = run(eng, A)
    run(eng, B)
    cached, again_b = run(eng, B)
    assert cached == 96
    assert np.mean(decode_gaps(A, cold)) < 0.05 and np.mean(decode_gaps(B, again_b)) < 0.05


def test_the_configuration_object_brings_the_programs_pools_and_counters():
    """The engine reads which step programs serve the model, its state's
    shapes and whether it counts experts from the configuration object: no
    model's name in it."""
    import inspect

    from githubrepostorag_tpu.serving import engine as engine_mod

    eng = build_engine(jnp.bfloat16)
    assert eng._wave_fn is model.forward_paged_wave and eng._decode_burst_fn is model.decode_burst
    assert eng._recurrent and eng._expert_counters
    assert eng.moe_stats == {"burst": [0, 0, 0], "prefill": [0, 0, 0]}
    assert eng.state_pools["s"].shape == (5, 4 + 4 + 1, 8, 8, 128)  # 16, lane-padded
    assert eng.state_pools["s"].dtype == jnp.float32
    assert eng.state_pools["conv"].shape == (5, 9, 3 * (64 + 2 * 2 * 16))
    assert eng.page_pool.shape == (2, 2, 64, PAGE, 16)
    from githubrepostorag_tpu.obs.startup import startup_record

    held = startup_record().snapshot()["notes"]["pool_bytes"]
    assert held["pages"] == 2 * eng.page_pool.nbytes
    assert held["state"] == sum(x.nbytes for x in eng.state_pools.values()) > 0
    # the compile ledger is told this family's two programs
    assert {model.forward_paged_wave, model.decode_burst} <= set(eng.step_programs())
    imports = [ln for ln in inspect.getsource(engine_mod).splitlines()
               if ln.lstrip().startswith(("import ", "from "))]
    assert not [ln for ln in imports if "nemotron" in ln]
    assert "nemotron" not in inspect.getsource(engine_mod).lower()


def test_what_a_state_pool_refuses_at_construction_stands_for_this_family_too():
    for kw, named in ((dict(kv_quant=8), "kv_quant"),
                      (dict(prefill_token_budget=64), "prefill_token_budget"),
                      (dict(prefill_chunk=40), "prefill_chunk"),
                      (dict(kv_tier="on"), "kv_tier")):
        with pytest.raises(ValueError, match="recurrent state pool: .*" + named):
            build_engine(jnp.bfloat16, **kw)
