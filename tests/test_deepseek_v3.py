"""DeepSeek-V3 at a small size on the CPU, seeded weights, against the plain
reference (benchmarks/reference_deepseek_v3.py: float32, not absorbed, a loop
over experts, no cache): logits, not tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_deepseek_v3 as ref
from githubrepostorag_tpu.models import deepseek_v3 as ds
from githubrepostorag_tpu.models import moe as moe_ops
from githubrepostorag_tpu.models.moe import dropless_experts, route_noaux_tc
from githubrepostorag_tpu.ops import latent_attention as latent_ops
from githubrepostorag_tpu.ops.latent_attention import (
    latent_decode_attention,
    latent_prefill_attention,
)
from githubrepostorag_tpu.serving import Engine, SamplingParams

PAGE, PAGES = 8, 48
YARN = dict(factor=40, original_max_position_embeddings=64, beta_fast=32, beta_slow=1,
            mscale=1.0, mscale_all_dim=1.0, type="yarn")


def model_of(cfg: ds.DeepseekV3Config) -> dict:
    """The reference's description of ``cfg`` (HF config.json keys)."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size, n_shared_experts=cfg.n_shared_experts,
        n_routed_experts=cfg.n_routed_experts, experts_held=list(cfg.experts_held),
        first_k_dense_replace=cfg.first_k_dense, num_hidden_layers=cfg.num_layers,
        vocab_size=cfg.vocab_size, num_experts_per_tok=cfg.num_experts_per_tok,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        routed_scaling_factor=cfg.routed_scaling_factor, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, rope_scaling=YARN)


@pytest.fixture(scope="module")
def tiny():
    cfg = ds.DeepseekV3Config.tiny(experts_held=(4, 12))
    return cfg, ds.init_params(cfg, seed=11)


def prefill(cfg, params, pool, ids, start, bt, all_positions=True):
    """One chunk ``ids`` [B, S] at position ``start`` through forward_paged."""
    b, s = ids.shape
    pos = np.broadcast_to(start + np.arange(s, dtype=np.int32), (b, s))
    slots = np.stack([bt[i, pos[i] // PAGE] * PAGE + pos[i] % PAGE for i in range(b)])
    logits, pool, _, stats = ds.forward_paged(
        params, cfg, jnp.asarray(ids), jnp.asarray(pos), pool, None, jnp.asarray(slots),
        jnp.asarray(bt), jnp.full((b,), start, jnp.int32), jnp.full((b,), s, jnp.int32),
        logits_at=None if all_positions else jnp.full((b,), s - 1, jnp.int32))
    return np.asarray(logits, np.float32), pool, stats


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


def test_yarn_frequencies_match_the_reference():
    from githubrepostorag_tpu.ops.rope import yarn_inv_freq

    for dim, orig in ((8, 64), (64, 4096)):
        got = np.asarray(yarn_inv_freq(dim, 10000.0, 40.0, orig, 32.0, 1.0))
        np.testing.assert_allclose(got, ref.yarn_inv_freq(dim, 10000.0, {**YARN,
                                   "original_max_position_embeddings": orig}), rtol=1e-6)
    cfg = ds.DeepseekV3Config()
    assert cfg.head_dim == 640 and cfg.num_kv_heads == 1  # 576 used, whole lane tiles
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.3689 ** 2) < 1e-4
    assert abs(ref.softmax_scale(model_of(cfg) | {"rope_scaling": {**YARN}}) - cfg.softmax_scale) < 1e-9


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(tiny):
    """Two prompts prefilled in chunks of 16 into the paged latent pool, then
    eight greedy tokens through decode bursts: the prefill logits at every
    position, and the logits the reference gives the engine's tokens."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    ids = rng.integers(2, cfg.vocab_size, size=(2, 32)).astype(np.int32)
    bt = np.arange(2 * 6, dtype=np.int32).reshape(2, 6)
    pool = jnp.zeros((cfg.num_layers, 1, PAGES, PAGE, cfg.head_dim), jnp.bfloat16)
    got = []
    for start in (0, 16):
        logits, pool, _ = prefill(cfg, params, pool, ids[:, start:start + 16], start, bt)
        got.append(logits)
    got = np.concatenate(got, axis=1)  # [2, 32, V]
    want = ref.logits_at(model_of(cfg), 11, [list(r) for r in ids], [list(range(32))] * 2)
    assert rel_rms(got, np.stack(want)) < 0.02  # bfloat16 activations against float32

    eng = Engine(params, cfg, max_num_seqs=4, num_pages=PAGES, page_size=PAGE, max_seq_len=64,
                 prefill_chunk=16, decode_burst=4, rng_seed=0)
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=())
    outs = [list(r.output_tokens) for r in eng.generate([list(map(int, r)) for r in ids], sp)]
    full = [list(map(int, r)) + o[:-1] for r, o in zip(ids, outs)]
    rows = ref.logits_at(model_of(cfg), 11, full, [list(range(31, 39))] * 2)
    for row, toks in zip(rows, outs):
        gaps = [(r.max() - r[t]) / r.std() for r, t in zip(row, toks)]
        assert np.mean(gaps) < 0.02  # the engine picks the reference's best, or a near-tie
    assert eng.moe_stats["burst"][2] == 2 * 2 * 4 * cfg.n_held  # 2 bursts x 2 layers x 4 steps


def test_a_prefill_wave_stays_inside_the_warmed_row_buckets(tiny):
    """Eleven prompts admitted in one step: a wave carries at most
    ``prefill_rows_cap`` rows (the largest bucket a server warms), the rest
    ride the next step's wave, and every prompt gets the tokens it gets when
    it is served alone."""
    cfg, params = tiny
    assert cfg.prefill_rows_cap == 8
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(2, cfg.vocab_size, size=20))) for _ in range(11)]
    sp = SamplingParams(max_tokens=3, temperature=0.0, stop_token_ids=())

    def engine():
        return Engine(params, cfg, max_num_seqs=16, num_pages=PAGES, page_size=PAGE,
                      max_seq_len=64, prefill_chunk=32, decode_burst=4, rng_seed=0)

    eng = engine()
    waves, inner = [], eng._prefill_batch
    eng._prefill_batch = lambda reqs, finished: (waves.append(len(reqs)), inner(reqs, finished))[1]
    together = [list(r.output_tokens) for r in eng.generate(prompts, sp)]
    assert waves == [8, 3]
    alone = engine()
    assert together == [list(alone.generate([p], sp)[0].output_tokens) for p in prompts]


def test_a_latent_step_is_a_wave_and_a_burst_and_no_eager_op(tiny):
    """The latent engine through the same two programs a step: once warm, a
    row joining a running row, then eleven at once (a wave of eight, the rest
    in the next step's wave), apply no primitive eagerly and dispatch at most
    the wave and the burst; greedy tokens are a request-at-a-time run's."""
    from tests.helpers.step_programs import assert_two_programs_a_step, run_recorded

    cfg, params = tiny
    rng = np.random.default_rng(30)
    prompts = [list(map(int, rng.integers(2, cfg.vocab_size, size=n)))
               for n in [9, 7] + [20] * 10 + [40]]
    sp = SamplingParams(max_tokens=14, temperature=0.0, stop_token_ids=())

    def engine():
        return Engine(params, cfg, max_num_seqs=16, num_pages=2 * PAGES, page_size=PAGE,
                      max_seq_len=64, prefill_chunk=32, decode_burst=4, rng_seed=0)

    def script(eng, ids):
        ids.append(eng.add_request(prompts[0], sp))
        yield
        yield
        yield
        ids.append(eng.add_request(prompts[1], sp))
        yield
        yield
        ids.extend(eng.add_request(p, sp) for p in prompts[2:])
        yield
        yield

    rehearsal = engine()
    run_recorded(rehearsal, script(rehearsal, []), warm=False)
    eng, ids = engine(), []
    done, steps = run_recorded(eng, script(eng, ids))
    assert_two_programs_a_step(steps)
    alone = engine()
    assert [done[rid].output_tokens for rid in ids] == [
        alone.generate([p], sp)[0].output_tokens for p in prompts]


def test_a_chunk_against_a_cached_prefix_equals_a_cold_prefill(tiny):
    """The prefix-cache hit path: 16 new tokens after 32 cached give the
    logits a cold 48-token prefill gives at the same positions."""
    cfg, params = tiny
    ids = np.random.default_rng(5).integers(2, cfg.vocab_size, size=(1, 48)).astype(np.int32)
    bt = np.arange(6, dtype=np.int32).reshape(1, 6)
    zeros = lambda: jnp.zeros((cfg.num_layers, 1, PAGES, PAGE, cfg.head_dim), jnp.bfloat16)  # noqa: E731
    cold, _, _ = prefill(cfg, params, zeros(), ids, 0, bt)
    _, pool, _ = prefill(cfg, params, zeros(), ids[:, :32], 0, bt)
    warm, pool, _ = prefill(cfg, params, pool, ids[:, 32:], 32, bt)
    assert rel_rms(warm, cold[:, 32:]) < 5e-3
    want = ref.logits_at(model_of(cfg), 11, [list(ids[0])], [list(range(32, 48))])[0]
    assert rel_rms(warm[0], want) < 0.02


@pytest.mark.parametrize("use_pallas", [False, True], ids=["gather", "kernel-interpreted"])
def test_absorbed_attention_equals_materialised_attention(use_pallas, monkeypatch):
    """One new token over a paged latent prefix, float32: the decode path
    (W_uk folded into the query, W_uv applied after, rows read as stored)
    against the prefill path (K and V rebuilt from the latents tile by tile)."""
    monkeypatch.setattr(latent_ops, "TILE_PAGES", 2)  # two tiles over the four pages
    h, nope, rope, rank, vd, ps, width = 4, 16, 8, 16, 16, 8, 128
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    lens = jnp.asarray([13, 30], jnp.int32)
    bt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    pool = jnp.zeros((2, 1, 8, ps, width)).at[..., :rank + rope].set(
        jax.random.normal(k[0], (2, 1, 8, ps, rank + rope)))
    new = jnp.zeros((2, 1, width)).at[..., :rank + rope].set(
        jax.random.normal(k[1], (2, 1, rank + rope)))
    q_nope, q_rope = jax.random.normal(k[2], (2, 1, h, nope)), jax.random.normal(k[3], (2, 1, h, rope))
    w_uk, w_uv = jax.random.normal(k[4], (h, nope, rank)), jax.random.normal(k[5], (h, rank, vd))
    layer, scale = jnp.int32(1), 0.2
    staged = jnp.zeros((2, 4, width)).at[:, :1].set(new)
    q_lat = jnp.einsum("bhn,hnc->bhc", q_nope[:, 0], w_uk)
    out = latent_decode_attention(q_lat * scale, q_rope[:, 0] * scale, pool, layer, bt, lens,
                                  staged, jnp.int32(1), use_pallas=use_pallas, interpret=True)
    absorbed = jnp.einsum("bhc,hcv->bhv", out, w_uv)
    # the materialised path reads the new row from the pool, where the engine commits it
    slot = bt[jnp.arange(2), lens // ps] * ps + lens % ps
    flat = pool.reshape(2, 1, -1, width).at[layer, 0, slot].set(new[:, 0]).reshape(pool.shape)
    mat = latent_prefill_attention(q_nope, q_rope, flat, layer, bt, lens, jnp.ones((2,), jnp.int32),
                                   w_uk, w_uv, scale)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(mat[:, 0]), rtol=2e-4, atol=2e-4)


def walk_case(lens, staged_len=3, wave=2, ps=16, max_pages=8, order="shuffled"):
    """A case of the decode kernel's walk: rows of ``lens`` cached tokens over
    pages of ``ps`` rows and tables of ``max_pages``, waves of ``wave`` pages."""
    return dict(lens=lens, staged_len=staged_len, wave=wave, ps=ps, max_pages=max_pages,
                order=order)


def walk_args(lens, staged_len, ps, max_pages, order, seed=0, h=4, rank=16, rope=8, width=128,
              n_steps=8, layers=3):
    """Arguments of ``latent_decode_attention`` (float32, layer 1 of 3): every
    live row's pages its own, a dead row's table zeroed, page 0 no row's."""
    rng = np.random.default_rng(seed)
    need = [-(-n // ps) for n in lens]
    pages = sum(need) + 3
    pool = np.zeros((layers, 1, pages, ps, width), np.float32)
    pool[..., :rank + rope] = rng.normal(size=(layers, 1, pages, ps, rank + rope))
    ids = {"shuffled": rng.permutation(pages - 1) + 1, "descending": np.arange(pages - 1, 0, -1)}
    bt = np.zeros((len(lens), max_pages), np.int32)
    taken = 0
    for row, n in enumerate(need):
        bt[row, :n] = ids[order][taken:taken + n]
        taken += n
    staged = np.zeros((len(lens), n_steps, width), np.float32)
    staged[..., :rank + rope] = rng.normal(size=(len(lens), n_steps, rank + rope))
    q_lat, q_rope = rng.normal(size=(len(lens), h, rank)), rng.normal(size=(len(lens), h, rope))
    return (jnp.asarray(q_lat, jnp.float32), jnp.asarray(q_rope, jnp.float32), jnp.asarray(pool),
            jnp.int32(1), jnp.asarray(bt), jnp.asarray(lens, jnp.int32), jnp.asarray(staged),
            jnp.int32(staged_len))


def three_live_of_32():
    lens = [0] * 32
    lens[5], lens[17], lens[30] = 70, 33, 128  # the last slot is a dead row
    return lens


WALK_CASES = [
    pytest.param(walk_case(three_live_of_32()), id="32-slots-3-live"),
    pytest.param(walk_case([0, 1, 127, 128, 129, 256], ps=128, max_pages=2, wave=1),
                 id="around-a-page-of-128-one-wave-plus-1"),
    pytest.param(walk_case([0, 1, 127, 128, 129, 256], ps=128, max_pages=2, wave=2),
                 id="around-a-page-of-128-one-wave-exactly"),
    pytest.param(walk_case([1, 15, 16, 17]), id="one-token-and-around-a-page"),
    pytest.param(walk_case([31, 32, 33, 0]), id="around-a-wave"),
    pytest.param(walk_case([64, 65, 127, 128], 4), id="waves-plus-one-to-the-whole-table"),
    pytest.param(walk_case([128] * 4, 7), id="every-row-the-whole-table"),
    pytest.param(walk_case([128] * 4, 7, wave=8), id="whole-table-one-wave"),
    pytest.param(walk_case([16 * n for n in (8, 1, 7, 2, 6, 3, 5, 4)], 2, wave=1),
                 id="one-to-eight-waves-folded-in-pairs"),
    pytest.param(walk_case([0, 128, 0, 0, 113, 0, 128, 0, 0, 97, 0], 5, wave=1),
                 id="the-stream-runs-ahead-over-dead-rows"),
    pytest.param(walk_case([0, 77, 0, 0], 6), id="live-between-dead-and-a-dead-row-last"),
    pytest.param(walk_case([0, 0, 0, 0, 0, 77, 0, 0], 6), id="one-live-row-of-many"),
    pytest.param(walk_case([0, 0, 0, 0], 1), id="no-live-row-first-step"),
    pytest.param(walk_case([40, 0, 128, 9], 1), id="staged-len-1"),
    pytest.param(walk_case([40, 0, 128, 9], 8), id="staged-len-n-steps"),
    pytest.param(walk_case([0, 33, 128, 16], 3, wave=1), id="waves-of-one-page"),
    pytest.param(walk_case([1, 63, 64, 65, 127, 128, 0, 33], 3, wave=4), id="around-waves-of-4"),
    pytest.param(walk_case([50, 0, 97, 16], 3, order="descending"), id="pages-out-of-order"),
    pytest.param(walk_case([0, 97, 0, 16, 17, 128], 8, wave=16), id="wave-wider-than-the-table"),
]


@pytest.mark.parametrize("case", WALK_CASES)
def test_decode_kernel_walks_what_live_rows_hold(monkeypatch, case):
    """The decode kernel, interpreted, against the gather path over ragged
    tables: what a walk over the rows' own pages can get wrong."""
    case = dict(case)
    monkeypatch.setattr(latent_ops, "DECODE_WAVE_PAGES", case.pop("wave"))
    args = walk_args(**case)
    want = latent_decode_attention(*args)
    got = latent_decode_attention(*args, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_decode_kernel_dmas_land_before_they_are_read(monkeypatch):
    """Plain interpret mode copies at ``start()``; the TPU interpreter runs a
    DMA only when it is waited for and watches every buffer for races: a wave
    folded before its wait, a slot refilled while it is still read (the stream
    is up to five waves ahead, over rows' ends) or a wait that matches no
    start shows here (the last as a hang, not a failure)."""
    from jax.experimental.pallas import tpu as pltpu

    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this jax has no TPU interpreter")
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter

    monkeypatch.setattr(latent_ops, "DECODE_WAVE_PAGES", 1)
    args = walk_args([0, 100, 0, 33, 128, 0, 17, 64, 0], 3, 16, 8, "shuffled", seed=5)
    want = latent_decode_attention(*args)
    got = latent_decode_attention(
        *args, use_pallas=True,
        interpret=pltpu.InterpretParams(detect_races=True, dma_execution_mode="on_wait"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert not tpu_interpreter.races.races_found


def test_a_row_that_sits_the_burst_out_is_handed_over_as_holding_nothing(tiny, monkeypatch):
    """A finished row still in the chained lens (inactive) and a row at its
    ``row_limits``, both with a stale ``seq_lens`` and a zeroed block table:
    attention is told they hold nothing, and the live row's tokens are what
    they are with those rows empty."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.normal(size=(cfg.num_layers, 1, PAGES, PAGE, cfg.head_dim)) * 0.1,
                       jnp.bfloat16).at[..., cfg.kv_lora_rank + cfg.qk_rope_head_dim:].set(0)
    bt = np.zeros((4, 6), np.int32)
    bt[1] = np.arange(7, 13)  # row 1 is live: 20 rows cached on pages 7, 8, 9
    handed = []
    inner = ds.latent_decode_attention

    def recording(q_lat, q_rope, pool, layer, block_tables, pool_lens, *rest, **kw):
        jax.debug.callback(lambda lens: handed.append(np.asarray(lens)), pool_lens)
        return inner(q_lat, q_rope, pool, layer, block_tables, pool_lens, *rest, **kw)

    monkeypatch.setattr(ds, "latent_decode_attention", recording)

    def burst(seq_lens, active, limits):
        b = len(seq_lens)
        out = ds.decode_burst.__wrapped__(
            params, cfg, jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.asarray(seq_lens, jnp.int32),
            pool, None, jnp.zeros((b, cfg.vocab_size), bool), jnp.asarray(active),
            jnp.asarray(limits, jnp.int32), jnp.asarray(bt), jax.random.PRNGKey(0),
            jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
            jnp.ones((b,), jnp.float32), n_steps=3, first_tokens=jnp.zeros((b,), jnp.int32),
            fresh=jnp.zeros((b,), bool), fresh_lens=jnp.zeros((b,), jnp.int32),
            key_step=jnp.uint32(0))
        jax.effects_barrier()
        return np.asarray(out[0]), np.asarray(out[5])

    # row 0 finished (inactive, 37 in the chained lens), row 2 at its limit, row 3 free
    stale, stale_lens = burst([37, 20, 40, 0], [False, True, True, False], [0, 48, 40, 0])
    assert handed and all(list(lens) == [0, 20, 0, 0] for lens in handed)
    handed.clear()
    clean, clean_lens = burst([0, 20, 0, 0], [False, True, False, False], [0, 48, 0, 0])
    assert all(list(lens) == [0, 20, 0, 0] for lens in handed)
    assert (stale[1] >= 0).all() and list(stale[1]) == list(clean[1])
    assert (stale[[0, 2, 3]] == -1).all()  # no token from a row that sat out
    assert list(stale_lens) == [37, 23, 40, 0] and list(clean_lens) == [0, 23, 0, 0]


def chunk_case(cached, new, s=16, table=6, h=4, head_columns=32):
    """A chunk of ``s`` columns a row over rows of ``cached`` + ``new`` keys
    (pages of 8, query tiles of 8, a table of ``table`` pages a row)."""
    return dict(cached=cached, new=new, s=s, table=table, h=h, head_columns=head_columns)


def chunk_args(cached, new, s, table, h):
    nope, rope, rank, vd, ps, width = 16, 8, 16, 16, 8, 128
    rows = len(cached)
    k = jax.random.split(jax.random.PRNGKey(9), 5)
    # a row's pages lie apart in the pool and out of order; a padding row names page 0
    bt = np.arange(rows * table, dtype=np.int32).reshape(table, rows).T[:, ::-1].copy()
    bt[np.asarray(cached) + np.asarray(new) == 0] = 0
    pool = jnp.zeros((2, 1, rows * table, ps, width)).at[..., :rank + rope].set(
        jax.random.normal(k[0], (2, 1, rows * table, ps, rank + rope)))
    q_nope = jax.random.normal(k[1], (rows, s, h, nope))
    q_rope = jax.random.normal(k[2], (rows, s, h, rope))
    w_uk, w_uv = jax.random.normal(k[3], (h, nope, rank)), jax.random.normal(k[4], (h, rank, vd))
    return (q_nope, q_rope, pool, jnp.int32(1), jnp.asarray(bt), jnp.asarray(cached, jnp.int32),
            jnp.asarray(new, jnp.int32), w_uk, w_uv, 0.2)


# pages of 8 keys, query tiles of 8 columns; a step is the largest of 8 / 4 / 2 / 1 pages that
# divides the table: 6 pages -> 2 a step (16 keys), 8 -> 8, 4 -> 4, 5 -> 1
CHUNK_CASES = [
    # row 1: step 0 inside the cached prefix (every key seen), step 1 crosses ``cached``, step 2 has
    # the row's last key; its 9 real tokens end inside the second query tile
    pytest.param(chunk_case([13, 30, 0], [16, 9, 0]), id="inside-the-prefix-and-across-it"),
    pytest.param(chunk_case([32, 16, 0, 34], [5, 17, 0, 8], s=32),
                 id="last-tiles-all-padding"),
    pytest.param(chunk_case([0, 0], [16, 7]), id="cold-prompts"),
    pytest.param(chunk_case([32, 0, 8], [16, 0, 3]), id="a-padding-row-between-two-lengths"),
    # heads a step: 16 columns in ``head_columns`` of 64 ask for 4, of 32 (the default) for 2
    pytest.param(chunk_case([16, 30], [16, 9], h=6, head_columns=64), id="heads-3-a-step-of-6"),
    pytest.param(chunk_case([16, 30], [16, 9], h=3), id="heads-no-multiple-of-the-rule"),
    pytest.param(chunk_case([16, 30], [16, 9], h=8, head_columns=64), id="heads-4-a-step-of-8"),
    pytest.param(chunk_case([13, 9], [16, 12], table=4), id="four-pages-a-step"),
    pytest.param(chunk_case([13, 24], [16, 9], table=5), id="one-page-a-step"),
    pytest.param(chunk_case([40, 17], [16, 24], s=24, table=8), id="the-table-one-step"),
]


def patched_for_chunks(monkeypatch, case):
    monkeypatch.setattr(latent_ops, "TILE_PAGES", 2)
    monkeypatch.setattr(latent_ops, "QUERY_TILE", 8)
    monkeypatch.setattr(latent_ops, "HEAD_COLUMNS", case["head_columns"])
    return {k: v for k, v in case.items() if k != "head_columns"}


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_prefill_kernel_equals_the_tiled_oracle(monkeypatch, case):
    """The Pallas kernel, interpreted, against the XLA path that materialises K
    and V a tile at a time, at real rows only, over what the kernel's body
    tells apart: steps inside the cached prefix, across its end and past the
    row's last key, query tiles run and left out, heads a step, pages a step.
    A column in a tile that holds no real token and a padding row (which
    walks no page) come back zero."""
    case = patched_for_chunks(monkeypatch, case)
    args = chunk_args(**case)
    want = latent_prefill_attention(*args)
    got = np.asarray(latent_prefill_attention(*args, use_pallas=True, interpret=True))
    assert np.isfinite(got).all()
    for row, n in enumerate(case["new"]):  # a padded query attends what its position allows: unused
        np.testing.assert_allclose(got[row, :n], np.asarray(want[row, :n]), rtol=2e-4, atol=2e-4)
        assert not got[row, -(-n // 8) * 8:].any()


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_the_hosts_tile_counts_are_what_the_kernel_selects(monkeypatch, case):
    """``prefill_tile_counts`` (the wave annotation's ``attn_tiles_*``) against
    the kernel itself, interpreted: every fold of a (head, step) reports the
    columns it took, and the tiles nobody reported are the skipped ones."""
    case = patched_for_chunks(monkeypatch, case)
    folded, head_fold = [], latent_ops._head_fold

    def watched_fold(sc, *args):
        jax.debug.callback(lambda _: folded.append(sc.shape[0] // 8), sc[0, 0])
        return head_fold(sc, *args)

    monkeypatch.setattr(latent_ops, "_head_fold", watched_fold)
    # the jitted wrapper keeps traces of the unwatched kernel
    monkeypatch.setattr(latent_ops, "_prefill_pallas", latent_ops._prefill_pallas.__wrapped__)
    latent_prefill_attention(*chunk_args(**case), use_pallas=True, interpret=True)
    jax.effects_barrier()
    h, s, table = case["h"], case["s"], case["table"]
    span = latent_ops._pages_per_step(table) * 8
    counts = latent_ops.prefill_tile_counts(case["cached"], case["new"], s, table, 8)
    assert sum(counts.values()) == len(case["new"]) * (s // 8) * (table * 8 // span)
    assert sum(folded) == h * counts["run"]
    assert counts["run"] == sum(  # by hand: the steps that hold a key of the row, live tiles each
        -(-(c + n) // span) * -(-n // 8) for c, n in zip(case["cached"], case["new"]))


async def test_the_wave_tile_counts_reach_the_annotation_and_prometheus(tiny, monkeypatch):
    """The latent family's hook: a wave's annotation carries the tiles its
    attention kernel runs and skips, the engine adds them up, and /metrics
    exports them by kind."""
    from githubrepostorag_tpu.metrics import render
    from tests.helpers.step_programs import recorded_waves

    cfg, params = tiny
    # a table of 12 pages is walked 4 pages (32 keys) a step, a chunk of 32 columns whole
    eng = Engine(params, cfg, max_num_seqs=4, num_pages=PAGES, page_size=PAGE, max_seq_len=96,
                 prefill_chunk=32, decode_burst=4, rng_seed=0)
    waves = recorded_waves(monkeypatch)

    def exported():
        return {line.split('kind="')[1].split('"')[0]: float(line.split()[-1])
                for line in render().decode().splitlines()
                if line.startswith("rag_engine_prefill_attn_tiles_total{")}

    before = exported()
    prompt = list(range(3, 43))  # 40 tokens: a chunk of 32, then 8 over a prefix of 32
    eng.generate([prompt], SamplingParams(max_tokens=2, temperature=0.0, stop_token_ids=()))
    # one row, one tile a row, three steps: the first chunk's keys lie in one, the second's in two
    assert [(w["attn_tiles"], w["attn_tiles_run"], w["attn_tiles_skipped"]) for w in waves] == [
        (3, 1, 2), (3, 2, 1)]
    assert eng.prefill_attn_tiles == {"run": 3, "skipped": 3}
    assert {k: exported()[k] - before.get(k, 0.0) for k in ("run", "skipped")} == {
        "run": 3, "skipped": 3}


def test_router_is_the_group_limited_top_k_and_the_bias_only_selects():
    """Against the reference's hand-written selection, on seeded scores with
    no ties; the bias changes which experts are chosen and never a weight."""
    t, e, k, groups, keep = 64, 32, 4, 8, 3
    scores = jax.nn.sigmoid(2 * jax.random.normal(jax.random.PRNGKey(1), (t, e)))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (e,))
    ids, w = route_noaux_tc(scores, bias, k, groups, keep, True, 2.5)
    dense = np.zeros((t, e), np.float32)
    np.put_along_axis(dense, np.asarray(ids), np.asarray(w), axis=1)
    np.testing.assert_allclose(dense, np.asarray(ref.route(scores, bias, k, groups, keep, 2.5)),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-5)
    plain, _ = route_noaux_tc(scores, jnp.zeros((e,)), k, groups, keep, True, 2.5)
    differ = [set(map(int, a)) != set(map(int, b)) for a, b in zip(np.asarray(ids), np.asarray(plain))]
    assert sum(differ) > t // 4  # s + b and s pick differently
    # hand check of one token: groups by the sum of their two best biased scores
    biased = np.asarray(scores + bias)[0].reshape(groups, -1)
    best = np.argsort(-np.sort(biased, axis=1)[:, -2:].sum(axis=1))[:keep]
    allowed = {g * (e // groups) + j for g in best for j in range(e // groups)}
    order = sorted(allowed, key=lambda j: -np.asarray(scores + bias)[0, j])[:k]
    assert set(order) == set(map(int, np.asarray(ids)[0]))


def test_every_share_of_the_experts_adds_up_to_the_uncut_layer():
    """The share test (model-configs guide, section 4): over all 16 ranges of
    the experts, the parts the program's expert layer computes for the
    experts it holds, with the shared expert counted once, sum to what the
    uncut reference gives for the whole layer."""
    cfg = ds.DeepseekV3Config.tiny(n_routed_experts=32, n_group=4, topk_group=2,
                                   num_experts_per_tok=6, experts_held=(0, 32))
    model = model_of(cfg)
    d, ffe, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
    k = jax.random.split(jax.random.PRNGKey(4), 7)
    x = jax.random.normal(k[0], (2, 9, d))
    p = {"router": 0.4 * jax.random.normal(k[1], (d, e)),
         "e_bias": 0.2 * jax.random.normal(k[2], (e,)),
         "s_wgu": 0.1 * jax.random.normal(k[5], (d, 2 * ffe)),
         "s_wd": 0.1 * jax.random.normal(k[6], (ffe, d))}
    e_wgu = 0.1 * jax.random.normal(k[3], (1, e, d, 2 * ffe))
    e_wd = 0.1 * jax.random.normal(k[4], (1, e, ffe, d))
    live = jnp.ones((2, 9), bool)
    shared = ds._swiglu(x, p["s_wgu"], p["s_wd"])
    total, pairs = shared, 0
    for lo in range(0, e, 2):  # 16 chips, 2 experts each
        share = dataclasses.replace(cfg, experts_held=(lo, lo + 2))
        y, stats = ds._moe_ffn(share, p, {"e_wgu": e_wgu[:, lo:lo + 2], "e_wd": e_wd[:, lo:lo + 2]},
                               jnp.int32(0), x, live)
        total = total + (y - shared)  # every chip computes the shared expert alike: once
        pairs += int(stats[1])
    assert pairs == 2 * 9 * cfg.num_experts_per_tok  # every pair computed by exactly one share
    want = ref.moe_layer(model, x.reshape(-1, d), p["router"], p["e_bias"],
                         lambda i: (e_wgu[0, i], e_wd[0, i]), (p["s_wgu"], p["s_wd"]))
    np.testing.assert_allclose(np.asarray(total).reshape(-1, d), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("tokens,tile", [(40, 8), (5, 128), (64, 16)])
def test_dropless_dispatch_loses_nothing_when_every_token_picks_one_expert(tokens, tile,
                                                                           monkeypatch):
    """The case a capacity would drop from: all tokens route to one held
    expert (and to one that is not held, which adds nothing)."""
    d, n = 16, 4
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(k[0], (tokens, d))
    w = jax.random.normal(k[1], (n, d, d))
    top_i = jnp.stack([jnp.full((tokens,), 10 + 2), jnp.full((tokens,), 3)], axis=1)
    top_w = jax.random.uniform(k[2], (tokens, 2))
    monkeypatch.setattr(moe_ops, "EXPERT_TILE", tile)
    y, counts = dropless_experts(x, top_i, top_w, lambda e, rows: rows @ w[e], n, lo=10)
    np.testing.assert_allclose(np.asarray(y), np.asarray((x @ w[2]) * top_w[:, :1]),
                               rtol=1e-5, atol=1e-5)
    assert list(map(int, counts)) == [0, 0, tokens, 0]


def test_engine_refuses_what_is_not_built_for_a_latent_pool(tiny):
    cfg, params = tiny
    for kw in ({"kv_quant": 8}, {"prefill_token_budget": 64}, {"kv_tier": "on"}):
        with pytest.raises(ValueError, match="latent page pool"):
            Engine(params, cfg, max_num_seqs=2, num_pages=16, page_size=PAGE, max_seq_len=64, **kw)
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=16, page_size=PAGE, max_seq_len=64)
    assert eng._v_pages is None and eng.page_pool.shape == (cfg.num_layers, 1, 16, PAGE, 128)
