"""Pallas paged-attention decode kernel vs the gather+dense oracle
(interpret mode on CPU; the same kernel runs compiled on TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.ops.paged_attention import paged_attention_ref
from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode


def _case(seed, b, n_q, n_kv, hd, ps, num_pages, max_pages, lens):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, 1, n_q, hd)).astype(np.float32)
    k_pages = rng.normal(size=(n_kv, num_pages, ps, hd)).astype(np.float32)
    v_pages = rng.normal(size=(n_kv, num_pages, ps, hd)).astype(np.float32)
    # distinct random pages per row
    perm = rng.permutation(num_pages)
    block_tables = np.zeros((b, max_pages), dtype=np.int32)
    taken = 0
    for row in range(b):
        need = -(-int(lens[row]) // ps) if lens[row] else 0
        block_tables[row, :need] = perm[taken : taken + need]
        taken += need
    cached = np.asarray([max(l - 1, 0) for l in lens], dtype=np.int32)
    new = np.asarray([1 if l else 0 for l in lens], dtype=np.int32)
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(block_tables), jnp.asarray(cached), jnp.asarray(new))


@pytest.mark.parametrize("lens", [[13], [16], [1]])
def test_single_row_matches_ref(lens):
    args = _case(0, 1, 4, 2, 32, 8, 16, 4, lens)
    ref = paged_attention_ref(*args)
    out = paged_attention_decode(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ragged_batch_with_padding_rows():
    # rows with different lengths, including an inactive row (len 0)
    args = _case(1, 4, 8, 2, 64, 16, 32, 4, [50, 7, 0, 33])
    ref = paged_attention_ref(*args)
    out = paged_attention_decode(*args, interpret=True)
    active = np.asarray([0, 1, 3])
    np.testing.assert_allclose(
        np.asarray(out)[active], np.asarray(ref)[active], atol=1e-5, rtol=1e-5
    )
    assert bool(jnp.isfinite(out).all())  # padding row must not NaN


def test_gqa_group_of_seven():
    # Qwen2-7B geometry: 28 q heads over 4 kv heads (group 7)
    args = _case(2, 2, 28, 4, 64, 16, 24, 6, [80, 42])
    ref = paged_attention_ref(*args)
    out = paged_attention_decode(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_engine_with_pallas_path_matches_hf():
    transformers = pytest.importorskip("transformers")
    import torch
    from githubrepostorag_tpu.models.hf_loader import config_from_hf, params_from_state_dict
    from githubrepostorag_tpu.serving import Engine, SamplingParams

    hf_cfg = transformers.Qwen2Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=True, attention_dropout=0.0,
    )
    torch.manual_seed(0)
    model = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    cfg = config_from_hf(hf_cfg.to_dict())
    params = params_from_state_dict(model.state_dict(), cfg)

    prompt = np.random.default_rng(3).integers(0, 512, size=21).tolist()
    eng = Engine(params, cfg, max_num_seqs=2, num_pages=32, page_size=8,
                 max_seq_len=64, prefill_chunk=32, kv_dtype=jnp.float32,
                 use_pallas=True)
    res = eng.generate([prompt], SamplingParams(temperature=0.0, max_tokens=6))[0]
    with torch.no_grad():
        ref = model.generate(torch.tensor([prompt]), max_new_tokens=6, do_sample=False,
                             pad_token_id=0, eos_token_id=None)
    assert res.output_tokens == ref[0, len(prompt):].tolist()


# ------------------------------------------------- staged burst kernel ----


def _staged_case(seed, b, n_q, n_kv, hd, ps, num_pages, max_pages, pool_lens,
                 n_steps, staged_len, layers=0):
    """``layers`` > 0: rank-5 pools [layers, n_kv, P, ps, hd], every layer
    drawn apart, so that a kernel reading another layer than it was told
    cannot match."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, 1, n_q, hd)).astype(np.float32)
    lead = (layers,) if layers else ()
    k_pages = rng.normal(size=(*lead, n_kv, num_pages, ps, hd)).astype(np.float32)
    v_pages = rng.normal(size=(*lead, n_kv, num_pages, ps, hd)).astype(np.float32)
    staged_k = rng.normal(size=(b, n_kv, n_steps, hd)).astype(np.float32)
    staged_v = rng.normal(size=(b, n_kv, n_steps, hd)).astype(np.float32)
    perm = rng.permutation(num_pages)
    block_tables = np.zeros((b, max_pages), dtype=np.int32)
    taken = 0
    for row in range(b):
        need = -(-int(pool_lens[row]) // ps) if pool_lens[row] else 0
        block_tables[row, :need] = perm[taken : taken + need]
        taken += need
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(block_tables), jnp.asarray(pool_lens, dtype=jnp.int32),
            jnp.asarray(staged_k), jnp.asarray(staged_v),
            jnp.asarray([staged_len], dtype=jnp.int32))


def _staged_oracle(q, k_pages, v_pages, block_tables, pool_lens, staged_k,
                   staged_v, staged_len):
    """gather pool + concat staged tail + masked dense attention — the same
    math the decode burst's CPU path runs."""
    from githubrepostorag_tpu.ops.attention import dense_attention
    from githubrepostorag_tpu.ops.paged_attention import gather_kv

    b = q.shape[0]
    n_steps = staged_k.shape[2]
    pool_k, pool_v = gather_kv(k_pages, v_pages, block_tables)
    pool_valid = jnp.arange(pool_k.shape[1])[None, :] < pool_lens[:, None]
    staged_valid = jnp.broadcast_to(
        (jnp.arange(n_steps) < staged_len[0])[None, :], (b, n_steps)
    )
    k_all = jnp.concatenate([pool_k, staged_k.swapaxes(1, 2)], axis=1)
    v_all = jnp.concatenate([pool_v, staged_v.swapaxes(1, 2)], axis=1)
    valid = jnp.concatenate([pool_valid, staged_valid], axis=1)
    return dense_attention(q, k_all, v_all, causal=False, kv_valid=valid)


def set_wave(monkeypatch, pages, n_kv, ps, hd, itemsize=4):
    """Make the burst kernel's waves ``pages`` pages wide at these sizes: the
    width comes from a VMEM budget (ops/pallas_paged.py::_wave_pages), which
    at test sizes would hold a whole table."""
    from githubrepostorag_tpu.ops import pallas_paged

    per_page = n_kv * ps * hd * (2 * 2 * itemsize + 2 * 4)
    monkeypatch.setattr(pallas_paged, "WAVE_VMEM_BYTES", pages * per_page)
    assert pallas_paged._wave_pages(n_kv, ps, hd, itemsize, 1 << 20) == pages


def _walk(pool_lens, staged_len, wave=2, max_pages=8, num_pages=40, **kw):
    """A case of the page walk: pages of 16, tables of ``max_pages``, waves
    of ``wave`` pages (None: the budget's own, a whole table here)."""
    return dict(pool_lens=pool_lens, staged_len=staged_len, wave=wave,
                max_pages=max_pages, num_pages=num_pages, **kw)


STAGED_CASES = [
    # the dense-grid kernel's cases: tables of 4 pages, one wave holds them
    pytest.param(_walk([50, 7, 0, 33], 3, None, 4, 32), id="ragged-incl-empty-mid-burst"),
    pytest.param(_walk([0, 0, 0, 0], 1, None, 4, 32), id="no-pool-first-step"),
    pytest.param(_walk([64, 64, 64, 64], 8, None, 4, 32), id="full-pools-full-tail"),
    # what a walk over the rows' own pages can get wrong
    pytest.param(_walk([0, 300, 0, 0, 17, 0], 3, 4, 20), id="dead-rows-between-live"),
    pytest.param(_walk([1, 15, 16, 17], 2), id="one-token-and-around-a-page"),
    pytest.param(_walk([31, 32, 33, 0], 5), id="around-a-wave"),
    pytest.param(_walk([64, 65, 127, 128], 4), id="waves-plus-one-to-the-full-table"),
    pytest.param(_walk([0, 0, 0, 0, 0, 77, 0, 0], 6), id="one-live-row-of-many"),
    pytest.param(_walk([128] * 4, 7), id="every-row-full"),
    pytest.param(_walk([128] * 4, 7, 8), id="every-row-full-one-wave"),
    pytest.param(_walk([40, 0, 128, 9], 1), id="staged-len-1"),
    pytest.param(_walk([40, 0, 128, 9], 8), id="staged-len-n-steps"),
    pytest.param(_walk([0, 33, 128, 16], 3, 1), id="waves-of-one-page"),
    pytest.param(_walk([1, 63, 64, 65, 127, 128, 0, 33], 3, 4), id="around-waves-of-4"),
    pytest.param(_walk([0, 97, 0, 16, 17, 128], 8, 8), id="dead-rows-one-wave-a-table"),
    pytest.param(_walk([50, 0, 97, 16], 3, n_q=7, n_kv=1), id="one-kv-head-tp-shard"),
    pytest.param(_walk([0, 33, 128, 16], 3, 4, n_q=7, n_kv=1), id="one-kv-head-waves-of-4"),
    pytest.param(_walk([50, 7, 0, 33], 3, None, 4, 32, layers=3), id="rank5-one-wave"),
    pytest.param(_walk([0, 300, 0, 0, 17, 0], 2, 4, 20, layers=2), id="rank5-dead-rows-between-live"),
    pytest.param(_walk([31, 32, 33, 128], 8, layers=3), id="rank5-around-a-wave"),
]


@pytest.mark.parametrize("case", STAGED_CASES)
def test_staged_kernel_matches_oracle(monkeypatch, case):
    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged

    case = dict(case)
    n_q, n_kv, layers = case.pop("n_q", 8), case.pop("n_kv", 2), case.pop("layers", 0)
    hd, ps, wave = 64, 16, case.pop("wave")
    if wave:
        set_wave(monkeypatch, wave, n_kv, ps, hd)
    args = _staged_case(0, len(case["pool_lens"]), n_q, n_kv, hd, ps, n_steps=8,
                        layers=layers, **case)
    q, k_pages, v_pages, *rest = args
    layer = layers - 2 if layers else None  # neither the first nor the last
    one = (lambda pool: pool[layer]) if layers else (lambda pool: pool)
    ref = _staged_oracle(q, one(k_pages), one(v_pages), *rest)
    out = paged_attention_decode_staged(
        *args, layer=None if layer is None else jnp.asarray(layer), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
def test_staged_kernel_dmas_land_before_they_are_read(monkeypatch, kv_quant):
    """Plain interpret mode copies at ``start()``; the TPU interpreter runs a
    DMA only when it is waited for and watches every buffer for races, so a
    wave folded before its wait, a slot refilled while it is still read (the
    next row's first wave goes out during this row's last) or a wait that
    matches no start shows here (the last as a hang, not a failure)."""
    from jax.experimental.pallas import tpu as pltpu
    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged

    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this jax has no TPU interpreter")
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter

    n_kv, hd, ps = 2, 64, 16
    set_wave(monkeypatch, 2, n_kv, ps, hd, itemsize=1 if kv_quant else 4)
    args = _staged_case(5, 6, 8, n_kv, hd, ps, 40, 8, [0, 100, 0, 33, 128, 0], 8, 3)
    ref = _staged_oracle(*args)
    q, k_pages, v_pages, *rest = args
    scales = ()
    if kv_quant:  # whole numbers under one scale a page: int8 holds them exactly
        k_pages, v_pages = (jnp.round(x * 20).astype(jnp.int8) for x in (k_pages, v_pages))
        scales = (jnp.full(k_pages.shape[:2], 0.05, jnp.float32),) * 2
        ref = _staged_oracle(q, k_pages.astype(jnp.float32) * 0.05,
                             v_pages.astype(jnp.float32) * 0.05, *rest)
    out = paged_attention_decode_staged(
        q, k_pages, v_pages, *rest, None, *scales,
        interpret=pltpu.InterpretParams(detect_races=True, dma_execution_mode="on_wait"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
    assert not tpu_interpreter.races.races_found


def test_staged_kernel_gqa_group_seven():
    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged

    args = _staged_case(3, 2, 28, 4, 64, 16, 24, 6, [80, 42], 16, 11)
    ref = _staged_oracle(*args)
    out = paged_attention_decode_staged(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def set_slices(monkeypatch, heads, pages, ps, hd, itemsize=4):
    """Make the burst kernel's waves ``heads`` kv heads by ``pages`` pages at
    these sizes (ops/pallas_paged.py::_wave_heads): a budget that holds that
    many head-pages and not a page of every head."""
    from githubrepostorag_tpu.ops import pallas_paged

    monkeypatch.setattr(pallas_paged, "WAVE_VMEM_BYTES",
                        heads * pages * pallas_paged._head_page_bytes(ps, hd, itemsize))


HEAD_SLICE_CASES = [
    # multi-head attention, one query row a kv head: 30 / 30 as 5 slices of 6 heads, 2 pages
    pytest.param(dict(n_q=30, n_kv=30, heads=6, pages=2, pool_lens=[0, 100, 0, 33, 128, 17]),
                 id="mha-30-heads-5-slices"),
    pytest.param(dict(n_q=30, n_kv=30, heads=6, pages=2, pool_lens=[0, 0, 0, 0]),
                 id="mha-30-heads-no-pool"),
    pytest.param(dict(n_q=30, n_kv=30, heads=10, pages=2, pool_lens=[64, 0, 1, 127], layers=3),
                 id="mha-30-heads-3-slices-rank5"),
    # Qwen2-7B's 28 / 4 whole (what it runs as) and a head at a time
    pytest.param(dict(n_q=28, n_kv=4, heads=4, pages=2, pool_lens=[80, 42, 0, 128]),
                 id="gqa-28-4-whole"),
    pytest.param(dict(n_q=28, n_kv=4, heads=1, pages=2, pool_lens=[80, 42, 0, 128]),
                 id="gqa-28-4-4-slices"),
]


@pytest.mark.parametrize("case", HEAD_SLICE_CASES)
def test_staged_kernel_takes_the_kv_heads_a_slice_at_a_time(monkeypatch, case):
    """Where not even two pages of every kv head fit the VMEM budget the kernel
    walks the rows once a slice of the kv heads; group size 1 (kv heads =
    query heads) is the multi-head case that needs it."""
    from githubrepostorag_tpu.ops import pallas_paged

    hd, ps, layers = 64, 16, case.get("layers", 0)
    set_slices(monkeypatch, case["heads"], case["pages"], ps, hd)
    assert pallas_paged._wave_heads(case["n_kv"], ps, hd, 4) == case["heads"]
    assert pallas_paged._wave_pages(case["n_kv"], ps, hd, 4, 8) == case["pages"]
    args = _staged_case(11, len(case["pool_lens"]), case["n_q"], case["n_kv"], hd, ps, 40, 8,
                        case["pool_lens"], 8, 3, layers=layers)
    q, k_pages, v_pages, *rest = args
    one = (lambda pool: pool[1]) if layers else (lambda pool: pool)
    ref = _staged_oracle(q, one(k_pages), one(v_pages), *rest)
    out = pallas_paged.paged_attention_decode_staged(
        *args, layer=jnp.asarray(1) if layers else None, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
def test_head_slices_dmas_land_before_they_are_read(monkeypatch, kv_quant):
    """The race detector over a walk of three slices of two heads: the first
    wave of a slice's first live row goes out during the last row of the
    slice before."""
    from jax.experimental.pallas import tpu as pltpu
    from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged

    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this jax has no TPU interpreter")
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter

    n_kv, hd, ps = 6, 64, 16
    set_slices(monkeypatch, 2, 2, ps, hd, itemsize=1 if kv_quant else 4)
    args = _staged_case(5, 6, 6, n_kv, hd, ps, 40, 8, [0, 100, 0, 33, 128, 0], 8, 3)
    ref = _staged_oracle(*args)
    q, k_pages, v_pages, *rest = args
    scales = ()
    if kv_quant:  # a scale a head and page, so a slice reading another's scales cannot match
        k_pages, v_pages = (jnp.round(x * 20).astype(jnp.int8) for x in (k_pages, v_pages))
        by_head = 0.05 * (1.0 + jnp.arange(n_kv, dtype=jnp.float32))[:, None]
        scales = (jnp.broadcast_to(by_head, k_pages.shape[:2]),) * 2
        ref = _staged_oracle(q, k_pages.astype(jnp.float32) * by_head[..., None, None],
                             v_pages.astype(jnp.float32) * by_head[..., None, None], *rest)
    out = paged_attention_decode_staged(
        q, k_pages, v_pages, *rest, None, *scales,
        interpret=pltpu.InterpretParams(detect_races=True, dma_execution_mode="on_wait"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
    assert not tpu_interpreter.races.races_found


def test_wave_tiles_of_the_served_models():
    """(kv heads, pages) of a wave at the pools the benchmark's models serve:
    the three the header lists, unchanged, and 30 kv heads of 128."""
    from githubrepostorag_tpu.ops.pallas_paged import _wave_heads, _wave_pages

    tile = lambda n_kv, hd, size: (_wave_heads(n_kv, 128, hd, size),  # noqa: E731
                                   _wave_pages(n_kv, 128, hd, size, 80))
    assert tile(4, 128, 2) == (4, 4) and tile(2, 128, 2) == (2, 8) and tile(1, 128, 2) == (1, 16)
    assert tile(2, 256, 2) == (2, 4) and tile(4, 128, 1) == (4, 4)
    assert tile(30, 128, 2) == (6, 2)  # 12 head-pages of 262,144 B: 3.1 MB of the 4 MB budget


def test_burst_pallas_matches_gather_path():
    """decode_burst(use_pallas=True) must be token-identical to the gather
    oracle path on the same inputs (greedy, so no sampling noise)."""
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
    from githubrepostorag_tpu.serving.decode_burst import decode_burst
    from githubrepostorag_tpu.serving.kv_cache import make_page_pools

    cfg = Qwen2Config.tiny()
    params = init_params(cfg, jax.random.PRNGKey(7))
    b, num_pages, page_size, n_steps = 2, 16, 4, 6
    max_pages = 8

    rng = np.random.default_rng(0)
    seq_lens = np.asarray([5, 3], dtype=np.int32)
    bt = np.zeros((b, max_pages), dtype=np.int32)
    bt[0] = np.arange(8); bt[1] = np.arange(8, 16)
    last = np.asarray([4, 7], dtype=np.int32)

    outs = {}
    for use_pallas in (False, True):
        pools = make_page_pools(cfg, num_pages, page_size, dtype=jnp.float32)
        # identical pool contents for both paths
        rng2 = np.random.default_rng(42)
        k_init = jnp.asarray(rng2.standard_normal(pools.k.shape), dtype=jnp.float32)
        v_init = jnp.asarray(rng2.standard_normal(pools.v.shape), dtype=jnp.float32)
        toks, valid, k_out, v_out, _, out_lens, _ = decode_burst(
            params, cfg,
            jnp.asarray(last), jnp.asarray(seq_lens),
            k_init, v_init,
            jnp.zeros((b, cfg.vocab_size), dtype=bool),
            jnp.ones((b,), dtype=bool),
            jnp.full((b,), 30, dtype=jnp.int32),
            jnp.asarray(bt), jax.random.PRNGKey(5),
            jnp.zeros((b,)), jnp.ones((b,)), jnp.zeros((b,), jnp.int32),
            jnp.ones((b,)),
            n_steps=n_steps, use_pallas=use_pallas,
            first_tokens=jnp.zeros((b,), jnp.int32), fresh=np.zeros((b,), bool),
            fresh_lens=np.zeros((b,), np.int32), key_step=np.uint32(0),
        )
        outs[use_pallas] = (np.asarray(toks), np.asarray(valid),
                            np.asarray(k_out), np.asarray(v_out),
                            np.asarray(out_lens))

    np.testing.assert_array_equal(outs[False][0], outs[True][0])  # tokens
    np.testing.assert_array_equal(outs[False][1], outs[True][1])  # valid
    np.testing.assert_allclose(outs[False][2], outs[True][2], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(outs[False][3], outs[True][3], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(outs[False][4], outs[True][4])
