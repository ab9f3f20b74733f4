"""Qwen3-Next through its two step programs against the benchmark's plain
reference (benchmarks/reference_qwen3_next.py, which imports nothing of the
program), at two periods and a small size on the CPU: prefill chunk by chunk
through the paged cache and the state pool, then decode bursts, logits and
not tokens.

Tolerances.  In float32 the program and the reference differ by the order of
their sums alone: 2e-5 of the logits' root mean square (a dropped gate, a
plain norm for a zero-centred one or a swapped head reads 1e-1 and more).
In bfloat16 (weights and products as served, float32 residual stream and
state) the prefill reads 0.013-0.017 at this size; 0.04 leaves that room and
is a third of what float8 weights read (0.12: tests/benchmarks/
test_bench_qwen3_next.py).  The state kept in bfloat16 is reported against
the float32 program and must fail the tight limit: that is why the pool is
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_qwen3_next as ref
from githubrepostorag_tpu.models import qwen3_next as model
from githubrepostorag_tpu.serving.kv_cache import make_state_pools

MODEL = dict(hidden_size=64, num_hidden_layers=8, full_attention_interval=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             partial_rotary_factor=0.25, rope_theta=1e7, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
             linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=4,
             moe_intermediate_size=32, shared_expert_intermediate_size=32, norm_topk_prob=True,
             rms_norm_eps=1e-6, vocab_size=512, experts_held=[4, 12])
SEED, PAGE, CHUNK, PAGES, ROWS, STEPS = 7, 16, 64, 32, 2, 4
PROMPT = [int(t) for t in np.random.default_rng(0).integers(1, 500, size=150)]


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


def run_program(act, state_dtype="float32"):
    """(prefill logits at every prompt position, the greedy tokens of one
    burst after it, the experts' counts) from the program's own step programs
    on pools built here."""
    cfg = model.Qwen3NextConfig.tiny(experts_held=(4, 12), state_dtype=state_dtype)
    params = jax.tree.map(lambda x: x.astype(act), model.init_params(cfg, seed=SEED))
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
        logits, kp, vp, _, state = model.forward_paged(
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
    after = jax.tree.map(lambda x: np.asarray(x[:, 1]), out[-1])
    idle_kept = all(bool((before[k] == after[k]).all()) for k in before)
    return prefill, [first] + [int(t) for t in np.asarray(out[0])[0]], np.asarray(out[7]), idle_kept


@pytest.fixture(scope="module")
def reference():
    return ref.logits_at(MODEL, SEED, [PROMPT], [list(range(len(PROMPT)))])[0]


@pytest.fixture(scope="module")
def float32_program():
    saved = model.ACT
    model.ACT = jnp.float32
    try:
        return run_program(jnp.float32)
    finally:
        model.ACT = saved


def decode_gaps(tokens):
    """How far below the reference's best logit each decoded token lies, in
    units of the row's spread (benchmarks/correctness.token_gap)."""
    full = PROMPT + tokens[:-1]
    rows = ref.logits_at(MODEL, SEED, [full], [list(range(len(PROMPT) - 1, len(full)))])[0]
    return [float((r.max() - r[t]) / r.std()) for r, t in zip(rows, tokens)]


def test_float32_program_is_the_reference_to_rounding(float32_program, reference):
    prefill, tokens, stats, idle_kept = float32_program
    assert rel_rms(prefill, reference) < 2e-5
    assert max(decode_gaps(tokens)) < 1e-4  # the burst's tokens are the reference's best
    assert stats[0] > 0 and stats[1] >= stats[0]  # experts hit, pairs to held experts
    assert idle_kept  # a row that sits the burst out keeps state and history bit for bit


def test_bfloat16_program_is_inside_its_tolerance_and_bfloat16_state_is_not_tight(
        float32_program, reference):
    prefill, tokens, _, _ = run_program(jnp.bfloat16)
    assert rel_rms(prefill, reference) < 0.04
    assert np.mean(decode_gaps(tokens)) < 0.05
    # the same program with the recurrence's matrix kept in bfloat16, against
    # the float32 program (everything else equal): not rounding
    saved = model.ACT
    model.ACT = jnp.float32
    try:
        low_state, _, _, _ = run_program(jnp.float32, state_dtype="bfloat16")
    finally:
        model.ACT = saved
    err = rel_rms(low_state, float32_program[0])
    print(f"state in bfloat16, all else float32: prefill_logits_rel_rms {err:.3g}")
    assert err > 2e-5


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """models/moe.dropless_experts is told which experts it holds; the parts
    that the four shares of one expert layer give, with the shared expert
    counted once, add up to what the reference gives for the whole layer
    (every expert held).  Float32, one draw of the uncut stacks sliced here."""
    from githubrepostorag_tpu.models.moe import dropless_experts

    rng = np.random.default_rng(3)
    d, e, f, k, t = 32, 16, 24, 4, 40
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.2, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)) * 0.2, jnp.float32)
    shared = (jnp.asarray(rng.normal(size=(d, 2 * f)) * 0.2, jnp.float32),
              jnp.asarray(rng.normal(size=(f, d)) * 0.2, jnp.float32),
              jnp.asarray(rng.normal(size=(d, 1)), jnp.float32))
    whole = dict(MODEL, hidden_size=d, num_experts=e, num_experts_per_tok=k,
                 moe_intermediate_size=f, shared_expert_intermediate_size=f,
                 experts_held=[0, e])
    want = ref.moe_layer(whole, x, router, lambda i: (wgu[i], wd[i]), shared)
    top_w, top_i = jax.lax.top_k(jax.nn.softmax(x @ router, axis=-1), k)
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    total = jnp.zeros_like(x)
    for lo in range(0, e, e // 4):
        def expert_ffn(i, rows, lo=lo):
            g, u = jnp.split(rows @ wgu[lo + i], 2, axis=-1)
            return (jax.nn.silu(g) * u) @ wd[lo + i]
        part, counts = dropless_experts(x, top_i, top_w, expert_ffn, e // 4, lo=lo)
        assert int(counts.sum()) == int(((top_i >= lo) & (top_i < lo + e // 4)).sum())
        total = total + part
    g, u = jnp.split(x @ shared[0], 2, axis=-1)
    total = total + jax.nn.sigmoid(x @ shared[2]) * ((jax.nn.silu(g) * u) @ shared[1])
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


def test_zero_centred_and_gated_norms_and_the_partial_rotary_slice():
    from githubrepostorag_tpu.ops.norms import rms_norm_gated, rms_norm_zero_centered
    from githubrepostorag_tpu.ops.rope import rope_cos_sin, rope_rotate_leading

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8,)) * 0.1, jnp.float32)
    unit = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(rms_norm_zero_centered(x, w), unit * (1 + w), rtol=1e-5)
    gate = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    np.testing.assert_allclose(rms_norm_gated(x, gate, w), unit * w * jax.nn.silu(gate),
                               rtol=1e-5)
    pos = jnp.asarray([[5]])
    cos, sin = rope_cos_sin(pos, 4, 1e7)
    y = rope_rotate_leading(x[None, :1], cos, sin)
    np.testing.assert_allclose(y[..., 4:], x[None, :1, 4:])  # the rest passes through
    np.testing.assert_allclose(y[0, 0, :4], ref._rope(x[:1, None, :], pos[0], 4, 1e7)[0, 0, :4],
                               rtol=1e-5)


# ---- the Gated DeltaNet projections as stored (PR 35): drawn as published,
# their columns put once into the order the product is read in

def published_split(cfg, p, x):
    """``_gdn_inputs`` as the published model writes it, on the projections as
    DRAWN: the product's columns grouped by key head, ``q | k | v (r value
    heads) | z (r)`` and ``b (r) | a (r)``, split and laid side by side."""
    from githubrepostorag_tpu.ops.latent_attention import einsum_f32

    b, s, _ = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, r = cfg.linear_key_head_dim, cfg.linear_value_head_dim, hv // hk
    f32 = lambda w: einsum_f32("bsd,de->bse", x, w)  # noqa: E731 - the program's own product
    qkvz = f32(p["w_qkvz"]).reshape(b, s, hk, 2 * dk + 2 * r * dv)
    parts = (qkvz[..., :dk], qkvz[..., dk:2 * dk], qkvz[..., 2 * dk:2 * dk + r * dv])
    mixed = jnp.concatenate([t.reshape(b, s, -1) for t in parts], axis=-1).astype(model.ACT)
    z = qkvz[..., 2 * dk + r * dv:].reshape(b, s, hv, dv)
    ba = f32(p["w_ba"]).reshape(b, s, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, hv))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., r:].reshape(b, s, hv) + p["dt_bias"])
    return mixed, z, beta, g


def drawn(cfg, name):
    """A leaf as ``leaf_order`` names it: the reference's own draw of it
    (benchmarks/reference_qwen3_next.py), which knows nothing of the stored order."""
    spec = dict(MODEL, linear_num_key_heads=cfg.linear_num_key_heads,
                linear_num_value_heads=cfg.linear_num_value_heads)
    w = ref.Weights(spec, SEED)
    return np.stack([np.asarray(w.at(name, g)) for g in range(cfg.gdn_layers)])


def published_columns(cfg, widths):
    """For each stored column, the published column it holds: kind after kind
    (``widths`` a key head), and inside a kind key head after key head."""
    hk, group = cfg.linear_num_key_heads, sum(widths)
    starts = np.cumsum([0, *widths[:-1]])
    return np.concatenate([head * group + start + np.arange(width)
                           for start, width in zip(starts, widths) for head in range(hk)])


@pytest.mark.parametrize("value_heads", [2, 4], ids=["one-value-head-a-key-head", "two"])
def test_stored_projections_are_the_published_draw_with_its_columns_permuted(value_heads):
    cfg = model.Qwen3NextConfig.tiny(experts_held=(4, 12), linear_num_value_heads=value_heads)
    gdn = model.init_params(cfg, seed=SEED)["gdn"]
    dk, dv, r = cfg.linear_key_head_dim, cfg.linear_value_head_dim, value_heads // 2
    for name, widths in (("w_qkvz", (dk, dk, r * dv, r * dv)), ("w_ba", (r, r))):
        want, stored = drawn(cfg, f"gdn.{name}"), np.asarray(gdn[name], np.float32)
        cols = published_columns(cfg, widths)
        assert sorted(cols) == list(range(want.shape[-1]))  # a permutation, nothing else
        assert stored.shape == want.shape and (stored == want[..., cols]).all(), name
    if r > 1:  # grouped by key head is another order than kind after kind
        assert not (np.asarray(gdn["w_qkvz"], np.float32) == drawn(cfg, "gdn.w_qkvz")).all()


@pytest.mark.parametrize("value_heads", [2, 4], ids=["one-value-head-a-key-head", "two"])
def test_gdn_inputs_on_the_stored_leaf_is_the_published_split_on_the_drawn_leaf(value_heads):
    cfg = model.Qwen3NextConfig.tiny(experts_held=(4, 12), linear_num_value_heads=value_heads)
    stored = jax.tree.map(lambda w: w[1], model.init_params(cfg, seed=SEED)["gdn"])
    as_drawn = dict(stored, **{name: jnp.asarray(drawn(cfg, f"gdn.{name}")[1], jnp.bfloat16)
                               for name in ("w_qkvz", "w_ba")})
    for shape in ((2, 7), (3, 1)):  # a chunk's two products, the burst's one (one token a row)
        x = jnp.asarray(np.random.default_rng(5).normal(size=(*shape, cfg.hidden_size)), model.ACT)
        got, want = model._gdn_inputs(cfg, stored, x), published_split(cfg, as_drawn, x)
        for name, a, b in zip(("mixed", "z", "beta", "g"), got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert a.shape == b.shape and (a == b).all(), (shape, name)  # bit for bit
    # and the value heads lie where ``hybrid.gdn_heads``' repeat of q and k expects them:
    # value head h of the convolution's input belongs to key head h // r
    q, k, v = model.hybrid.gdn_heads(cfg, got[0].astype(jnp.float32))
    q_pub, k_pub, v_pub = model.hybrid.gdn_heads(cfg, want[0].astype(jnp.float32))
    assert all((np.asarray(a) == np.asarray(b)).all()
               for a, b in ((q, q_pub), (k, k_pub), (v, v_pub)))


def run_wave(act):
    """(first token, the state and the keys after it) of ONE wave of one row:
    the first 40 prompt tokens in a chunk of 64 columns, at the rung of 64 /
    32 / 16 that holds them, a snapshot after 32."""
    cfg = model.Qwen3NextConfig.tiny(experts_held=(4, 12))
    params = jax.tree.map(lambda x: x.astype(act), model.init_params(cfg, seed=SEED))
    kp = jnp.zeros((cfg.kv_layers, cfg.num_kv_heads, PAGES, PAGE, cfg.head_dim), act)
    valid, row = 40, lambda v, t=jnp.int32: jnp.asarray([v], t)  # noqa: E731
    ids = np.zeros((1, CHUNK), np.int32)
    ids[0, :valid] = PROMPT[:valid]
    slots = np.full((1, CHUNK), -1, np.int32)
    slots[0, :valid] = np.arange(valid)
    per_row = (jnp.zeros((ROWS,)), jnp.ones((ROWS,)), jnp.zeros((ROWS,), jnp.int32),
               jnp.ones((ROWS,)))  # temperature 0: the first token is the best logit
    first, _, kp, _, _, state = model.forward_paged_wave(
        params, cfg, jnp.asarray(ids), jnp.arange(CHUNK, dtype=jnp.int32)[None], kp,
        jnp.zeros_like(kp), jnp.zeros((ROWS, cfg.vocab_size), bool),
        jnp.zeros((ROWS,), jnp.int32), jnp.asarray(slots),
        jnp.arange(16, dtype=jnp.int32)[None], row(0), row(valid), row(valid - 1), row(0),
        row(True, bool), jnp.int32(valid), jax.random.PRNGKey(0), jnp.uint32(1), *per_row,
        state=make_state_pools(cfg, ROWS + 3), state_src=row(-1), state_dst=row(0),
        state_snap=row(ROWS), snap_col=row(32))
    return int(first[0]), {k: np.asarray(v, np.float32) for k, v in state.items()}, \
        np.asarray(kp, np.float32)


@pytest.fixture()
def as_published(monkeypatch):
    """The program as it was before PR 35: the projections stored as drawn and
    split after the product.  The jitted step programs are traced anew on
    both sides of it (they would otherwise be found in jit's cache)."""
    traced = []
    monkeypatch.setattr(model, "_by_kind", lambda cfg, w_qkvz, w_ba: (w_qkvz, w_ba))
    monkeypatch.setattr(model, "_gdn_inputs",
                        lambda *a: traced.append(1) or published_split(*a))
    jax.clear_caches()
    yield traced
    jax.clear_caches()


@pytest.mark.parametrize("program", ["wave", "burst"])
def test_a_wave_and_a_burst_give_what_they_gave_with_the_projections_as_published(
        program, request):
    """Bit for bit in bfloat16 as served: a column of the product is the same
    contraction wherever it lies."""
    run = {"wave": lambda: run_wave(jnp.bfloat16), "burst": lambda: run_program(jnp.bfloat16)}
    stored = run[program]()
    traced = request.getfixturevalue("as_published")
    published = run[program]()
    assert traced  # the published split is what ran
    if program == "wave":
        assert stored[0] == published[0]
        assert stored[1]["s"][:, ROWS].any() and stored[1]["conv"][:, 0].any()  # snapshot, state
        for name in ("s", "conv"):
            assert (stored[1][name] == published[1][name]).all(), name
        assert (stored[2] == published[2]).all()
    else:  # chunk by chunk through forward_paged, then a burst of four steps
        assert (stored[0] == published[0]).all() and stored[1] == published[1]
        assert (stored[2] == published[2]).all()


# ------------------------------------------------------------ the engine --
def test_engine_with_the_kernel_on_the_pool_gives_the_array_forms_tokens_and_state(monkeypatch):
    """The burst's one-token rule as ops/pallas_state.py's kernel (``use_pallas``,
    interpreted here: what the chip runs since PR 56) against ``gated_delta_step``
    (the engine's path on the CPU), in float32 at one period: three prompts at
    once whose answers end at 3, 6 and 9 tokens, so in bursts of 4 a row turns
    dead BETWEEN two steps of a burst (``act & (lens < row_limits)``) while its
    neighbours step on, then all three again, each from its snapshot.  The same
    tokens, and the same state pool to float32 rounding (the kernel sums down
    ``dk`` sublanes in its own order): every slot, so a row that finished, a
    row that never ran and the snapshots hold what the array form left there.
    (Every prompt is three chunks and every resumed one is one, so an engine
    compiles one wave and one burst: ~20 s.)"""
    from githubrepostorag_tpu.serving import Engine, SamplingParams

    in_pool = []
    rule = model.hybrid.gdn_step_in_pool
    monkeypatch.setattr(model.hybrid, "gdn_step_in_pool",
                        lambda *a: in_pool.append(a[-1]) or rule(*a))
    monkeypatch.setattr(model, "ACT", jnp.float32)
    cfg = model.Qwen3NextConfig.tiny(experts_held=(4, 12), num_layers=4)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), model.init_params(cfg, seed=SEED))
    rng = np.random.default_rng(1)
    head, tail_a, tail_b = ([int(t) for t in rng.integers(1, 500, size=n)] for n in (100, 50, 40))
    a = head + tail_a  # 150 tokens: its last page boundary 144; the others share 6 and 8 pages
    prompts = [a, head + tail_b, a[:140]]
    sps = [SamplingParams(max_tokens=n, temperature=0.0, stop_token_ids=()) for n in (3, 6, 9)]
    pools, told = [], []
    for use_pallas in (False, True):
        eng = Engine(params, cfg, max_num_seqs=4, num_pages=64, page_size=PAGE, max_seq_len=256,
                     prefill_chunk=64, decode_burst=4, kv_dtype=jnp.float32, state_snapshots=4,
                     use_pallas=use_pallas)
        told.append([[(r.cached_tokens, list(r.output_tokens)) for r in eng.generate(prompts, sps)]
                     for _ in range(2)])
        pools.append([np.asarray(eng.state_pools[k]) for k in ("s", "conv")])
        assert eng.state_restored == 3
        # the array form never asks for the kernel; the other traces it once a layer, interpreted
        assert in_pool == [True] * (3 * use_pallas)
    assert told[1] == told[0]
    cold, resumed = told[1]
    assert [(c, len(t)) for c, t in cold] == [(0, 3), (0, 6), (0, 9)]
    assert resumed == [(144, cold[0][1]), (128, cold[1][1]), (128, cold[2][1])]
    for got, want in zip(pools[1], pools[0]):  # the states, then the convolutions' histories
        assert got.any()
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
