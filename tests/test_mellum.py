"""Mellum on the CPU at test widths: the seeded weights leaf for leaf, YaRN's
table against closed-form values, the program's prefill through BOTH kinds of
page against the plain reference's full forward (chunks shorter and longer
than the window, sequences several windows long), prefill then the decode
burst through both pools, the module's un-paged forward, every control, and
the shares of the experts against the uncut layer.

Tolerances: the reference is float32 at ``Precision.HIGHEST``; the program
multiplies in bfloat16 on a float32 residual stream, which at these widths
leaves the logits 0.4-0.5% apart (measured here; the limit is 2%, four times
the largest; the weakest control reads 6.6%).  A control has to read at least twice that limit.  The
per-head norm makes a score's spread the same at every width, so attention
needs no help at test widths; the experts do (``Wide``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference_mellum as ref
from benchmarks.correctness import rel_rms, token_gap
from githubrepostorag_tpu.models import mellum as program
from githubrepostorag_tpu.models.mellum import MellumConfig, init_params

LIMIT = 0.02
GAP = 0.05  # standard deviations of a row of logits: a near-tie of the best
TYPES = {"sliding": "sliding_attention", "global": "full_attention"}


def model_of(cfg: MellumConfig) -> dict:
    """The reference's view of a program configuration: the source's keys."""
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            ref.KV_HEADS: cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "moe_intermediate_size": cfg.moe_intermediate_size, "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok, "experts_held": cfg.experts_held,
            "num_hidden_layers": cfg.num_layers,
            "layer_types": [TYPES[k] for k in cfg.period] * cfg.periods,
            "sliding_window": cfg.sliding_window, "vocab_size": cfg.vocab_size,
            "rms_norm_eps": cfg.rms_norm_eps, "norm_topk_prob": cfg.norm_topk_prob,
            "rope_parameters": {
                "sliding_attention": {"rope_type": "default", "rope_theta": cfg.rope_theta},
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": cfg.rope_theta, "factor": cfg.yarn_factor,
                    "original_max_position_embeddings": cfg.yarn_original_max,
                    "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
                    "attention_factor": cfg.attention_factor}}}


CFG = MellumConfig.tiny(experts_held=(0, 8))
SEED = 11
EXPERT_GAIN = 2.0  # a power of two: exact in bfloat16


class Wide:
    """The seeded weights with the experts' gate | up projections times 2 (an
    expert's output times 4): at test widths a draw of std 0.02 leaves the
    experts' branch a fourteenth of the embedding's size, and un-normalised
    router weights move the logits by 1.5%; at the published widths (2,304 in,
    896 wide) the branch is five times the embedding's as drawn.  Not more:
    at times 4 a router whose logits lie 0.3 apart at these widths chooses
    another expert on bfloat16 inputs than on float32 ones often enough to read
    3-7% (measured here)."""

    def __init__(self, control=None, cfg=None, seed=SEED):
        self.w = ref.Weights(model_of(cfg or CFG), seed, control)

    def mat(self, name, *index, **kw):
        return self.w.mat(name, *index, **kw) * (EXPERT_GAIN if name == "e_wgu" else 1.0)

    def embed(self, ids):
        return self.w.embed(ids)

    def head_gain(self, name, li):
        return self.w.head_gain(name, li)


def wide_params(cfg, seed):
    """``init_params`` with ``Wide``'s gain."""
    params = init_params(cfg, seed=seed)
    params["layers"]["e_wgu"] = params["layers"]["e_wgu"] * jnp.bfloat16(EXPERT_GAIN)
    return params


# ------------------------------------------------------------ the pieces --

def test_the_seeded_weights_are_the_programs_leaf_for_leaf():
    params = init_params(CFG, seed=SEED)
    w, lay = ref.Weights(model_of(CFG), SEED), params["layers"]
    h, nkv, hd = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    assert [n for n, _, _ in ref.leaf_order(model_of(CFG))] == [
        p[-1] for p, _, _ in program.leaf_order(CFG)]
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    np.testing.assert_array_equal(f32(w.embed(np.arange(7))), f32(params["embed"][:7]))
    np.testing.assert_array_equal(f32(w.mat("lm_head", cols=(9, 30))), f32(params["lm_head"][:, 9:39]))
    for li in (0, CFG.num_layers - 1):
        wqkv = f32(lay["wqkv"][li])
        np.testing.assert_array_equal(f32(w.mat("wq", li)), wqkv[:, :h * hd])
        np.testing.assert_array_equal(f32(w.mat("wk", li)), wqkv[:, h * hd:(h + nkv) * hd])
        np.testing.assert_array_equal(f32(w.mat("wv", li)), wqkv[:, (h + nkv) * hd:])
        np.testing.assert_array_equal(f32(w.mat("wo", li, rows=(8, 24))), f32(lay["wo"][li, 8:32]))
        np.testing.assert_array_equal(f32(w.mat("router", li)), f32(lay["router"][li]))
        np.testing.assert_array_equal(f32(w.mat("e_wd", li, 3, rows=(4, 9))), f32(lay["e_wd"][li, 3, 4:13]))
        np.testing.assert_array_equal(f32(w.mat("e_wgu", li, 7, cols=(5, 40))),
                                      f32(lay["e_wgu"][li, 7, :, 5:45]))
        for name in ("q_norm", "k_norm"):
            np.testing.assert_array_equal(f32(w.head_gain(name, li)), f32(lay[name][li]))
    gains = f32(lay["q_norm"])
    assert 0.4 < gains.min() < 0.7 and 1.3 < gains.max() < 1.6  # 1 + a draw of +-0.55
    assert float(np.abs(f32(lay["router"])).max()) > 0.05  # the gain of 2
    assert f32(lay["ln1"]).min() == f32(lay["ln2"]).max() == f32(params["norm"]).max() == 1.0


def test_yarns_table_is_the_closed_form_at_two_positions_and_carries_the_factor():
    """The published widths: 64 pairs at theta 500,000, factor 16 over an
    original 8,192.  Pair 0 turns 1,304 times inside the original context (more
    than beta_fast 32): it keeps its frequency, 1.  Pair 63 turns 0.002 times:
    interpolated, theta^(-126/128) / 16.  The ramp runs over pairs 18 .. 35;
    pair 26 is 8/17 of the way.  Cos and sin carry ``attention_factor``, which
    is YaRN's 0.1 ln(16) + 1."""
    from githubrepostorag_tpu.ops.rope import rope_cos_sin, yarn_inv_freq, yarn_mscale

    cfg = MellumConfig()
    a = cfg.attention_factor
    assert abs(a - yarn_mscale(16.0)) < 1e-12 and abs(a - (0.1 * np.log(16.0) + 1.0)) < 1e-12
    pos = np.array([[8191, 100000]], np.int32)
    tables = program.rope_tables(cfg, jnp.asarray(pos))
    cos_g, sin_g = (np.asarray(t, np.float64) for t in tables["global"])
    cos_s, sin_s = (np.asarray(t, np.float64) for t in tables["sliding"])
    assert cos_g.shape == (1, 2, 128)
    plain = lambda i: 500000.0 ** (-2.0 * i / 128)  # noqa: E731
    for j, p in enumerate(pos[0].astype(np.float64)):
        want = {0: 1.0, 63: plain(63) / 16, 17: plain(17), 36: plain(36) / 16,
                26: plain(26) * (1 - 8 / 17) + plain(26) / 16 * (8 / 17)}
        for i, f in want.items():
            # float32 angles: 1e5 rad is exact to ~4e-3
            tol = 2e-2 if i < 20 else 1e-4
            assert abs(cos_g[0, j, i] - a * np.cos(p * f)) < tol, (p, i)
            assert abs(sin_g[0, j, i + 64] - a * np.sin(p * f)) < tol, (p, i)
        assert abs(cos_s[0, j, 63] - np.cos(p * plain(63))) < 1e-4  # the sliding table: plain, no factor
        assert abs(sin_s[0, j, 36] - np.sin(p * plain(36))) < 1e-4
    # the reference restates the frequencies; both agree with ops/rope's to rounding
    mine = np.asarray(ref.yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0))
    np.testing.assert_allclose(mine, np.asarray(yarn_inv_freq(128, 500000.0, 16.0, 8192)), rtol=1e-6)
    # ``factor`` is the only thing the argument adds
    c1, s1 = rope_cos_sin(jnp.asarray(pos), 128, 500000.0)
    c2, s2 = rope_cos_sin(jnp.asarray(pos), 128, 500000.0, factor=2.0)
    np.testing.assert_array_equal(np.asarray(c2), 2 * np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(s2), 2 * np.asarray(s1))


# ------------------------------------------------- the program vs the reference --

def _pools(cfg, pages, ps, sliding_pages):
    from githubrepostorag_tpu.serving.kv_cache import make_page_pools

    g = make_page_pools(cfg, pages, ps)
    s = make_page_pools(cfg, sliding_pages, ps, layers=cfg.sliding_layers)
    return [g.k, g.v, s.k, s.v]


def _prefill(cfg, params, seqs, chunk=32, ps=16, pools=None):
    """(next-token logits of the program's prefill, chunk by chunk through both
    pools with absolute tables and nothing released; the pools; the table)."""
    rb, per = len(seqs), -(-(max(map(len, seqs)) + 8) // ps)
    pools = pools or _pools(cfg, rb * per, ps, rb * per)
    bt = np.arange(rb * per, dtype=np.int32).reshape(rb, per)
    out = np.zeros((rb, cfg.vocab_size), np.float32)
    for start in range(0, max(map(len, seqs)), chunk):
        ids = np.zeros((rb, chunk), np.int32)
        slots = np.full((rb, chunk), -1, np.int32)
        cached, lens = np.zeros((rb,), np.int32), np.zeros((rb,), np.int32)
        for i, seq in enumerate(seqs):
            valid = max(0, min(len(seq) - start, chunk))
            ids[i, :valid] = seq[start:start + valid]
            pos = start + np.arange(valid)
            slots[i, :valid] = bt[i, pos // ps] * ps + pos % ps
            cached[i], lens[i] = (start, valid) if valid else (0, 0)
        pos2 = np.broadcast_to(start + np.arange(chunk, dtype=np.int32), (rb, chunk))
        logits, pools[0], pools[1], counts, pools[2], pools[3] = program.forward_paged(
            params, cfg, jnp.asarray(ids), jnp.asarray(pos2), pools[0], pools[1],
            jnp.asarray(slots), jnp.asarray(bt), jnp.asarray(cached), jnp.asarray(lens),
            logits_at=jnp.asarray(np.maximum(lens - 1, 0)), sliding_k=pools[2], sliding_v=pools[3],
            sliding_slots=jnp.asarray(slots), sliding_tables=jnp.asarray(bt))
        assert counts.shape == (3,) and int(counts[2]) <= int(counts[1])
        for i, seq in enumerate(seqs):
            if start < len(seq) <= start + chunk:
                out[i] = np.asarray(logits[i, 0], np.float32)
    return out, pools, bt


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(3)
    return [rng.integers(2, CFG.vocab_size, n).tolist() for n in (150, 70)]  # both past the window


@pytest.fixture(scope="module")
def params():
    return wide_params(CFG, SEED)


@pytest.fixture(scope="module")
def reference_logits(sequences):
    model = model_of(CFG)
    return {c: np.stack([r[0] for r in ref.logits_at(
        model, SEED, sequences, [[len(s) - 1] for s in sequences], control=c, q_block=32,
        weights=Wide(c))]) for c in (None, "fp8", *ref.KNOCK_OUTS)}


@pytest.mark.parametrize("chunk", [32, 64], ids=["chunk-under-the-window", "chunk-over-the-window"])
def test_prefill_through_both_pools_gives_the_references_logits(
        chunk, params, sequences, reference_logits):
    """Sequences of three and one and a half windows of 48, in chunks of 32
    (every chunk after the second attends keys behind its window's start) and
    of 64 (longer than the window: a chunk's last queries do not see its first
    keys)."""
    got, _, _ = _prefill(CFG, params, sequences, chunk=chunk)
    err = rel_rms(got, reference_logits[None])
    print("prefill_logits_rel_rms", chunk, err)
    assert err < LIMIT


def test_the_unpaged_forward_gives_the_references_logits(params, sequences, reference_logits):
    for seq, want in zip(sequences, reference_logits[None]):
        logits = program.forward(params, CFG, jnp.asarray([seq], jnp.int32))
        assert rel_rms(np.asarray(logits[0, -1], np.float32), want) < LIMIT


def test_prefill_then_the_decode_burst_through_both_pools(params, sequences):
    """The burst program itself, greedy, after the prefill program: 6 steps of
    both rows from staged keys over both kinds' pools, the sliding walk starting
    inside a page.  Each token is the reference's best for its history or a
    near-tie of it."""
    steps, ps = 6, 16
    logits, pools, bt = _prefill(CFG, params, sequences)
    b = len(sequences)
    first = np.argmax(logits, axis=1).astype(np.int32)
    lens = np.array([len(s) for s in sequences], np.int32)
    zeros = jnp.zeros((b,), jnp.float32)
    out = program.decode_burst(
        params, CFG, jnp.asarray(first), jnp.asarray(lens), pools[0], pools[1],
        jnp.zeros((b, CFG.vocab_size), bool), jnp.ones((b,), bool), jnp.asarray(lens + steps),
        jnp.asarray(bt), jax.random.PRNGKey(0), zeros, zeros + 1.0, jnp.zeros((b,), jnp.int32),
        zeros + 1.0, n_steps=steps, filter_sampling=False,
        first_tokens=jnp.asarray(first), fresh=jnp.zeros((b,), bool),
        fresh_lens=jnp.asarray(lens), key_step=jnp.uint32(0),
        sliding_k=pools[2], sliding_v=pools[3], sliding_tables=jnp.asarray(bt))
    packed, valid, *_, out_lens, _, counts, _, _ = out
    assert bool(valid.all()) and list(np.asarray(out_lens)) == list(lens + steps)
    hit, pairs, fullest = (int(c) for c in counts)
    layer_steps = CFG.num_layers * steps
    assert pairs <= b * CFG.num_experts_per_tok * layer_steps and hit <= pairs
    assert layer_steps <= fullest <= b * layer_steps  # an expert holds a row at most once a step
    toks = np.asarray(packed)
    for i, seq in enumerate(sequences):
        full = list(seq) + [int(first[i])] + toks[i, :-1].tolist()
        at = list(range(len(seq), len(full)))
        rows = ref.logits_at(model_of(CFG), SEED, [full], [at], q_block=32, weights=Wide())[0]
        assert token_gap(rows, toks[i].tolist()) < GAP


@pytest.mark.parametrize("control", ["fp8", *ref.KNOCK_OUTS])
def test_each_control_reads_not_correct(control, reference_logits):
    """The reference at the precision below, and with one piece of the
    mathematics knocked out, against the plain one: each is far past the limit
    the program passes."""
    err = rel_rms(reference_logits[control], reference_logits[None])
    print(control, err)
    assert err > 2 * LIMIT


# --------------------------------------------------------------- the shares --

@pytest.mark.parametrize("shares", [[(0, 16)], [(0, 8), (8, 16)],
                                    [(0, 4), (4, 8), (8, 12), (12, 16)]],
                         ids=["all-held", "two-halves", "four-quarters"])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(shares):
    """The guide's share test (nothing is computed alike on every chip: the
    layer has no shared expert): the routed parts of all the shares add up to
    the uncut reference's layer, and the program's expert layer, told it holds
    a share, computes that share's part and counts its own pairs."""
    cfg = MellumConfig.tiny(num_layers=4)
    whole = model_of(cfg)
    w = ref.Weights(whole, 5)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((24, cfg.hidden_size)), jnp.float32)
    uncut = ref.routed_part(w, whole, 2, x)

    class Share:
        """Experts [lo, hi) of the uncut draw, as a share's weights."""

        def __init__(self, lo):
            self.lo = lo

        def mat(self, name, *index, **kw):
            if name.startswith("e_"):
                index = (index[0], index[1] + self.lo)
            return w.mat(name, *index, **kw)

    parts = [ref.routed_part(Share(lo), {**whole, "experts_held": (lo, hi)}, 2, x)
             for lo, hi in shares]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=1e-5)
    params = init_params(cfg, seed=5)  # the uncut draw
    lay = params["layers"]
    xb = x.astype(jnp.bfloat16)[None]
    total = 0
    for (lo, hi), want in zip(shares, parts):
        share_cfg = MellumConfig.tiny(num_layers=4, experts_held=(lo, hi))
        experts = {k: lay[k][:, lo:hi] for k in ("e_wgu", "e_wd")}
        p = jax.tree.map(lambda a: a[2], {k: v for k, v in lay.items() if k not in experts})
        got, stats = program._moe_ffn(share_cfg, p, experts, 2, xb, jnp.ones((1, 24), bool))
        want = ref.routed_part(Share(lo), {**whole, "experts_held": (lo, hi)}, 2,
                               xb[0].astype(jnp.float32))
        assert rel_rms(np.asarray(got[0]), np.asarray(want)) < LIMIT
        hit, pairs, fullest = (int(s) for s in stats)
        assert hit <= hi - lo and fullest <= 24 and fullest * hit >= pairs
        total += pairs
    assert total == 24 * cfg.num_experts_per_tok  # every pair is some share's
