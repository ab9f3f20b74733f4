"""Olmo-Hybrid through its two step programs and through the engine against
the benchmark's plain reference (benchmarks/reference_olmo_hybrid.py, which
imports nothing of the program), at two periods and a small size on the CPU:
prefill chunk by chunk through the paged cache and the state pool, then
decode bursts; then the engine's own path with a snapshot restored.  Logits,
not tokens: a decoded token is held to the reference's logits by how far
below the reference's best it lies, in units of the row's spread.

Tolerances.  In float32 the program and the reference differ by the order of
their sums alone: 2e-5 of the logits' root mean square (a norm on the wrong
side of a mixer, a QK-norm a head instead of over the projection, ``beta`` in
(0, 1) or a rotary that should not be there read 1e-2 and more: the test
below tries each).  In bfloat16 (weights and products as served, float32
residual stream and state) the prefill reads 0.099 at this size, seven times
what Qwen3-Next's pre-norm blocks read: a block that norms each sublayer's
OUTPUT hands the sublayer's relative error on at full scale, sixteen times
over (no one rounding carries it: the MLP's middle, the q|k|v product or the
stream's cast kept in float32 each move it by a tenth).  0.3 leaves three
times that room and is a third of what float8 weights read (0.97, the
control: the same amplification leaves it no better than unrelated logits)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmo_hybrid as ref
from githubrepostorag_tpu.models import olmo_hybrid as model
from githubrepostorag_tpu.serving import Engine, SamplingParams

MODEL = dict(hidden_size=96, intermediate_size=160, num_hidden_layers=8, num_attention_heads=6,
             num_key_value_heads=6, linear_num_key_heads=6, linear_num_value_heads=6,
             linear_key_head_dim=12, linear_value_head_dim=24, linear_conv_kernel_dim=4,
             linear_allow_neg_eigval=True, rms_norm_eps=1e-6, vocab_size=512,
             layer_types=["linear_attention"] * 3 + ["full_attention"]
             + ["linear_attention"] * 3 + ["full_attention"])
SEED, PAGE, CHUNK, PAGES, ROWS, STEPS = 7, 16, 64, 32, 2, 4
PROMPT = [int(t) for t in np.random.default_rng(0).integers(1, 500, size=150)]
BF16_LIMIT, F32_LIMIT = 0.3, 2e-5


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


def run_program(act):
    """(prefill logits at every prompt position, the greedy tokens of one
    burst after it, whether an idle row kept its state) from the program's own
    step programs on pools built here."""
    cfg = model.OlmoHybridConfig.tiny()
    params = jax.tree.map(lambda x: x.astype(act) if x.dtype == jnp.bfloat16 else x,
                          model.init_params(cfg, seed=SEED))
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
    assert len(out) == 8  # no expert counts among what a dense model's burst returns
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


def test_float32_program_is_the_reference_to_rounding(in_float32, reference):
    prefill, tokens, idle_kept = run_program(jnp.float32)
    assert rel_rms(prefill, reference) < F32_LIMIT
    assert max(decode_gaps(PROMPT, tokens)) < 1e-4  # the burst's tokens are the reference's best
    assert idle_kept  # a row that sits the burst out keeps state and history bit for bit


def test_bfloat16_program_is_inside_its_tolerance_and_the_fp8_control_is_not(reference):
    prefill, tokens, _ = run_program(jnp.bfloat16)
    err = rel_rms(prefill, reference)
    control = ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))], control="fp8")[0]
    low = rel_rms(control, reference)
    print(f"prefill_logits_rel_rms: bfloat16 program {err:.3g}, fp8 control {low:.3g}")
    assert 3 * err < BF16_LIMIT < low / 3
    assert np.mean(decode_gaps(PROMPT, tokens)) < 0.05


@pytest.mark.parametrize("departure", ["pre_norm", "head_qk_norm", "beta_below_one", "rotary"])
def test_each_assumed_convention_and_the_write_strength_show_in_float32(
        in_float32, monkeypatch, reference, departure):
    """The tight limit sees every piece of the block's wiring: the program
    with one of them changed is not the reference by 500x the limit."""
    from githubrepostorag_tpu.ops.norms import rms_norm
    from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate_leading

    if departure == "pre_norm":  # norm the mixer's input instead of its output
        one = jnp.ones((MODEL["hidden_size"],), jnp.float32)
        monkeypatch.setattr(model._Layers, "mixer_input", staticmethod(
            lambda cfg, w, li, h: rms_norm(h, one, cfg.rms_norm_eps)))
    elif departure == "head_qk_norm":  # a norm a head instead of one over the projection
        whole = model._attn_project

        def by_head(cfg, p, x):
            b, s, _ = x.shape
            h, hd = cfg.num_heads, cfg.head_dim
            qkv = x @ p["wqkv"]
            q = rms_norm(qkv[..., :h * hd].reshape(b, s, h, hd), jnp.ones((hd,)), 1e-6)
            _, k, v, more = whole(cfg, p, x)
            return q, k, v, more
        monkeypatch.setattr(model, "_attn_project", by_head)
    elif departure == "beta_below_one":
        monkeypatch.setattr(model.OlmoHybridConfig, "beta_max", property(lambda self: 1.0))
    else:  # rotary on the whole head
        plain = model._attn_project

        def rotated(cfg, p, x):
            q, k, v, more = plain(cfg, p, x)
            pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
            cos, sin = rope_cos_sin(pos, cfg.head_dim, 1e4)
            cos, sin = cos[:, :, None, :], sin[:, :, None, :]
            return rope_rotate_leading(q, cos, sin), rope_rotate_leading(k, cos, sin), v, more
        monkeypatch.setattr(model, "_attn_project", rotated)
    prefill, _, _ = run_program(jnp.float32)
    assert rel_rms(prefill[:CHUNK], reference[:CHUNK]) > 500 * F32_LIMIT


# ------------------------------------------------------------ the engine --

RNG = np.random.default_rng(1)
HEAD = [int(t) for t in RNG.integers(1, 500, size=100)]
A = HEAD + [int(t) for t in RNG.integers(1, 500, size=50)]   # 150 tokens: last boundary 144
B = HEAD + [int(t) for t in RNG.integers(1, 500, size=20)]   # shares 6 pages (96) with A
SP = SamplingParams(max_tokens=6, temperature=0.0, stop_token_ids=())


def build_engine(act, **kw):
    cfg = model.OlmoHybridConfig.tiny()
    params = jax.tree.map(lambda x: x.astype(act) if x.dtype == jnp.bfloat16 else x,
                          model.init_params(cfg, seed=SEED))
    return Engine(params, cfg, **{**dict(
        max_num_seqs=4, num_pages=64, page_size=PAGE, max_seq_len=256, prefill_chunk=64,
        decode_burst=4, kv_dtype=act, state_snapshots=4), **kw})


def run(eng, prompt):
    res = eng.generate([prompt], SP)[0]
    return res.cached_tokens, list(res.output_tokens)


def padding_lanes(eng):
    """(bits set in the state pool's lanes past the value width: the padding
    that ``hybrid.lane_padded`` stores; whether a lane of values is non-zero)."""
    dv, s = eng.cfg.linear_value_head_dim, np.asarray(eng.state_pools["s"])
    assert s.shape[-1] > dv and s.dtype == np.float32
    return int(np.count_nonzero(s[..., dv:].view(np.uint32))), bool(s[..., :dv].any())


def test_engine_prefill_decode_and_a_restored_snapshot_are_the_references(in_float32):
    """The engine's own path in float32: a cold prompt through waves and bursts,
    a second prompt of the same head that leaves the branch-point snapshot, and
    both again from their snapshots.  Every token (the first is the prefill's,
    the rest the bursts', through pages and state) lies within 1e-3 of a row's
    spread of the reference's best logit; the fp8 control's own best tokens do
    not (its gap is the control failing the same limit)."""
    eng = build_engine(jnp.float32)
    cached, cold = run(eng, A)
    assert cached == 0 and max(decode_gaps(A, cold)) < 1e-3
    assert eng._state.written == 1 and not hasattr(eng, "moe_stats")
    cached, out_b = run(eng, B)
    assert cached == 0 and eng.page_hit_tokens == 96 and max(decode_gaps(B, out_b)) < 1e-3
    cached, again = run(eng, A)  # from the snapshot at its last page boundary
    assert cached == 144 and again == cold and eng.state_restored == 1
    cached, again_b = run(eng, B)  # from the branch-point snapshot
    assert cached == 96 and again_b == out_b and max(decode_gaps(B, again_b)) < 1e-3
    assert (eng.page_hit_tokens, eng.state_hit_tokens) == (96 + 144 + 112, 144 + 96)
    # the bursts update the pool at its stored width and nobody writes the padding: after waves,
    # bursts, snapshots and restores every padding lane of every slot is still +0.0
    assert padding_lanes(eng) == (0, True)
    assert max(decode_gaps(B, again_b, control="fp8")) > 1e-3  # held to the control, it fails


def test_engine_with_the_kernel_on_the_pool_gives_the_array_forms_tokens_and_state(in_float32):
    """The burst's one-token rule as ops/pallas_state.py's kernel (``use_pallas``,
    interpreted here) against ``gated_delta_step`` (the engine's path on the
    CPU): three prompts at once whose answers end at 3, 6 and 9 tokens, so in
    bursts of 4 a row turns dead BETWEEN two steps of a burst (``act & (lens <
    row_limits)``) while its neighbours step on, then the first prompt again
    from its snapshot.  The same tokens, and the same state pool to float32
    rounding (the kernel sums down ``dk`` sublanes in its own order): every
    slot, so a row that finished, a row that never ran and the snapshots hold
    what the array form left there; the padding lanes all zero."""
    plain, kernel = build_engine(jnp.float32), build_engine(jnp.float32, use_pallas=True)
    prompts = [A, B, A[:120]]
    sps = [SamplingParams(max_tokens=n, temperature=0.0, stop_token_ids=()) for n in (3, 6, 9)]
    for batch, sp in ((prompts, sps), ([A], [SP])):
        want, got = (
            [(r.cached_tokens, list(r.output_tokens)) for r in eng.generate(batch, sp)]
            for eng in (plain, kernel))
        assert got == want and [len(t) for _, t in got] == [p.max_tokens for p in sp]
        for prompt, (_, tokens) in zip(batch, got):
            assert max(decode_gaps(prompt, tokens)) < 1e-3
        np.testing.assert_allclose(np.asarray(kernel.state_pools["s"]),
                                   np.asarray(plain.state_pools["s"]), atol=2e-5, rtol=0)
        assert padding_lanes(kernel) == (0, True)
    assert kernel.state_restored == plain.state_restored == 1


def test_engine_in_bfloat16_stays_inside_the_decode_tolerance():
    """As served (bfloat16 weights, products and pages, float32 state): the
    tokens of a cold and of a resumed prompt lie 0.05 of a row's spread below
    the reference's best on average at the most (rounding flips near-ties; a
    wrong state or a stale page reads 1 and more)."""
    eng = build_engine(jnp.bfloat16)
    _, cold = run(eng, A)
    run(eng, B)
    cached, again_b = run(eng, B)
    assert cached == 96 and padding_lanes(eng) == (0, True)
    assert np.mean(decode_gaps(A, cold)) < 0.05 and np.mean(decode_gaps(B, again_b)) < 0.05


def test_each_configuration_object_brings_its_own_programs_and_counters():
    """The engine reads which step programs serve a model, and whether they
    count experts, from the configuration object: no model's name in it."""
    import inspect

    from githubrepostorag_tpu.models import qwen3_next
    from githubrepostorag_tpu.serving import engine as engine_mod

    olmo = build_engine(jnp.bfloat16)
    assert olmo._wave_fn is model.forward_paged_wave and olmo._decode_burst_fn is model.decode_burst
    assert olmo._recurrent and not olmo._expert_counters and not hasattr(olmo, "moe_stats")
    assert set(olmo.state_pools) == {"s", "conv"}
    assert olmo.state_pools["s"].shape == (6, 4 + 4 + 1, 6, 12, 128)  # 24, lane-padded
    assert olmo.page_pool.shape == (2, 6, 64, PAGE, 16)
    # the bytes each of the two caches holds stand in the start-up record
    from githubrepostorag_tpu.obs.startup import startup_record

    held = startup_record().snapshot()["notes"]["pool_bytes"]
    assert held["pages"] == 2 * olmo.page_pool.nbytes
    assert held["state"] == sum(x.nbytes for x in olmo.state_pools.values()) > 0
    cfg = qwen3_next.Qwen3NextConfig.tiny(experts_held=(4, 12))
    other = Engine(qwen3_next.init_params(cfg, seed=SEED), cfg, max_num_seqs=4, num_pages=64,
                   page_size=PAGE, max_seq_len=256, prefill_chunk=64, state_snapshots=4)
    assert other._wave_fn is qwen3_next.forward_paged_wave
    assert other._decode_burst_fn is qwen3_next.decode_burst
    assert other._expert_counters and other.moe_stats == {"burst": [0, 0, 0], "prefill": [0, 0, 0]}
    imports = [ln for ln in inspect.getsource(engine_mod).splitlines()
               if ln.lstrip().startswith(("import ", "from "))]
    for name in ("qwen3_next", "olmo_hybrid", "deepseek_v3"):  # qwen2's are the default programs
        assert not [ln for ln in imports if name in ln], name


def test_what_a_state_pool_refuses_at_construction_stands_for_this_family_too():
    for kw, named in ((dict(kv_quant=8), "kv_quant"),
                      (dict(prefill_token_budget=64), "prefill_token_budget"),
                      (dict(prefill_chunk=40), "prefill_chunk")):
        with pytest.raises(ValueError, match="recurrent state pool: .*" + named):
            build_engine(jnp.bfloat16, **kw)
