"""DeepSeek-V3's two step programs compiled whole for a TPU v5e that is
described, not attached, at the shapes of the benchmark's cell
(``deepseek-v3-ep16-bf16``: published widths, 1 dense + 4 expert layers, 16
experts held, 2,048 pages of 128 latent rows): the decode kernel (its own
DMAs out of the pool whole in HBM) passes the chip's compiler, as does the
prefill kernel, and nothing in the optimized HLO copies, transposes or slices
the latent pool or a layer of it, nor an expert stack (PERF.md, Findings,
PR 25 and PR 27); and the ops that the benchmark's
``moe_experts_hbm_frac`` picks out of a trace by their shapes are the
products under the ``moe_experts`` scope, all of them and no others.  Nothing
executes; a pass here is not a chip run.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from tests.test_tpu_compile import (  # noqa: F401 - fixtures
    assert_wave_holds_every_rung,
    assert_wave_keeps_in_place,
    chip,
    pool_movers,
    topo,
)

PAGES, PAGE, ROWS, ROW_PAGES = 2048, 128, 32, 80


def cell_config():
    from githubrepostorag_tpu.models.deepseek_v3 import DeepseekV3Config

    return DeepseekV3Config(vocab_size=16160, num_layers=5, first_k_dense=1,
                            experts_held=(0, 16))


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.deepseek_v3 as model
    import githubrepostorag_tpu.ops.latent_attention as latent

    for mod in (model, latent):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def lowered_program(where, program: str, variant):
    from githubrepostorag_tpu.models.deepseek_v3 import (
        decode_burst,
        forward_paged,
        forward_paged_wave,
        init_params,
    )

    cfg = cell_config()
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where),
                          jax.eval_shape(lambda: init_params(cfg, 0)))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    pool_shape = (cfg.num_layers, 1, PAGES, PAGE, cfg.head_dim)
    pool = sds(pool_shape, jnp.bfloat16)
    b, i32, f32 = ROWS, jnp.int32, jnp.float32
    if program == "burst":
        lowered = decode_burst.lower(
            params, cfg, sds((b,), i32), sds((b,), i32), pool, None,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), jnp.bool_), sds((b,), i32),
            sds((b, ROW_PAGES), i32), sds((2,), jnp.uint32), sds((b,), f32), sds((b,), f32),
            sds((b,), i32), sds((b,), f32), n_steps=8, use_pallas=True, filter_sampling=variant,
            # as the engine calls it: rows fresh from a wave are overlaid inside
            first_tokens=sds((b,), i32), fresh=sds((b,), jnp.bool_), fresh_lens=sds((b,), i32),
            key_step=sds((), jnp.uint32))
    elif program == "wave":  # the engine's prefill wave: the chunk and its first-token tail
        chunk, rows = (variant, 512), (variant,)
        lowered = forward_paged_wave.lower(
            params, cfg, sds(chunk, i32), sds(chunk, i32), pool, None,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), i32), sds(chunk, i32),
            sds((variant, ROW_PAGES), i32), sds(rows, i32), sds(rows, i32), sds(rows, i32),
            sds(rows, i32), sds(rows, jnp.bool_), sds((), i32), sds((2,), jnp.uint32),
            sds((), jnp.uint32), sds((b,), f32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
            use_pallas=True)
    else:
        chunk = (variant, 512)
        lowered = forward_paged.lower(
            params, cfg, sds(chunk, i32), sds(chunk, i32), pool, None, sds(chunk, i32),
            sds((variant, ROW_PAGES), i32), sds((variant,), i32), sds((variant,), i32),
            use_pallas=True, logits_at=sds((variant,), i32))
    return lowered, pool_shape, params


def prefill_kernels(hlo: str) -> int:
    """The prefill kernel's custom calls in a compiled program, each named as
    ``latent_prefill_attn_flops_frac`` finds it (``^latent_prefill_attention``)
    and over the operands the kernel takes since PR 53: block tables, cached
    lens, kv lens, layer, q = [q_nope | q_rope], the pool once a page of a step
    (8 at the cell's table of 80 pages), W_uk^T, W_uv."""
    calls = re.findall(r"%latent_prefill_attention[.\d]* = [^\n]*custom-call\(([^\n]*?)\), "
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(calls) == hlo.count('"tpu_custom_call"')  # and no other kernel in the program
    for args in calls:
        operands = re.findall(r"%[\w.\-]+", args)
        assert len(operands) == 15 and len(set(operands[5:13])) == 1, operands
    return len(calls)


@pytest.mark.parametrize("program,variant", [
    pytest.param("burst", False, id="burst-nofilter"),
    pytest.param("prefill", 1, id="prefill-1x512"),
    pytest.param("prefill", 2, id="prefill-2x512"),
    pytest.param("wave", 1, id="wave-1x512"),
    pytest.param("wave", 8, id="wave-8x512"),
])
def test_step_program_leaves_latent_pool_and_experts_in_place(chip, as_on_chip, program,
                                                              variant):
    lowered, pool_shape, params = lowered_program(chip, program, variant)
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo  # the decode kernel, or the prefill kernel, is in the program
    if program == "burst":  # one decode kernel a stack (dense, scanned), named as its metric reads it
        kernels = re.findall(r"%(latent_attention[.\d]*) = [^\n]*\"tpu_custom_call\"", hlo)
        assert len(kernels) == 2 and hlo.count('"tpu_custom_call"') == 2, kernels
    else:  # one a layer kind, and a rung (a wave of one or two rows has three, of more one)
        assert prefill_kernels(hlo) == (6 if (program, variant) == ("wave", 1) else 2)
    assert pool_movers(hlo, pool_shape) == []
    for name in ("e_wgu", "e_wd"):  # [Lm, n_held, in, out]: no copy of a stack or a layer's slab
        assert pool_movers(hlo, params["moe"][name].shape) == []


def test_the_wave_is_one_program_that_donates_the_pool_and_presence(chip, as_on_chip):
    """The benchmark's readers find the wave by ``forward_paged`` in its
    module's name; the latent pool and the presence mask come back in place."""
    lowered, pool_shape, _ = lowered_program(chip, "wave", 2)
    hlo = lowered.compile().as_text()
    assert_wave_keeps_in_place(hlo, rf"bf16\[5,1,{PAGES},{PAGE},640\]|pred\[{ROWS},16160\]", 2)
    # the dense stack's scan and the expert stack's: 512, 256, 128 columns in each
    assert_wave_holds_every_rung(hlo, 3, pool_shape)
    assert prefill_kernels(hlo) == 6  # one a layer kind and rung


def test_the_expert_metric_selects_the_products_under_the_moe_experts_scope(chip, as_on_chip):
    """A trace's device plane names instructions, not scopes, so the metric
    finds the burst's expert products by output shape; the compiled program's
    own metadata says which scope each instruction came from."""
    from benchmarks import manifest
    from benchmarks.families import deepseek_v3 as family
    from benchmarks.trace import short_name

    cell = manifest.load_cell("deepseek-v3-ep16-bf16.repo-sessions")
    sizes = family.expert_op_sizes(family.model_of(cell.config, rehearse=False), cell.config)
    assert sizes == {"tile_rows": ROWS, "gate_up": 4096, "hidden": 7168}
    pattern = re.compile(manifest.metric_spec("moe_experts_hbm_frac")["args"]["op"].format(**sizes))
    hlo = lowered_program(chip, "burst", False)[0].compile().as_text()
    picked, under_scope = set(), set()
    for line in hlo.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if " fusion(" not in line:  # what a trace times: fusions, not the instructions fused
            continue
        name, _ = short_name(line)
        scope = re.search(r'op_name="([^"]*)"', line)
        scope = scope.group(1) if scope else ""
        if pattern.search(name):
            picked.add(name)
            assert "/moe_experts/" in scope, (name, scope)
        if "/moe_experts/" in scope and re.search(r"(dot_general|scatter-add)$", scope):
            under_scope.add(name)
    assert len(picked) == 3  # gate|up, down, the combine's scatter-add
    assert picked == under_scope


def test_the_latent_burst_has_one_shape_whatever_joins_it():
    """On the CPU, at a tiny size: bursts that no row, one row and nine rows
    (two waves: a wave carries eight) join call one program with one set of
    shapes."""
    from githubrepostorag_tpu.models import deepseek_v3 as ds
    from githubrepostorag_tpu.serving import Engine, SamplingParams
    from tests.helpers.step_programs import burst_call_shapes

    cfg = ds.DeepseekV3Config.tiny(experts_held=(4, 12))
    eng = Engine(ds.init_params(cfg, seed=11), cfg, max_num_seqs=16, num_pages=96, page_size=8,
                 max_seq_len=64, prefill_chunk=32, decode_burst=4, rng_seed=0)
    sp = SamplingParams(max_tokens=20, temperature=0.0, stop_token_ids=())
    before = ds.decode_burst._cache_size()
    shapes = burst_call_shapes(eng)
    joined = []
    for wave in ([[3, 4, 5]], [[6, 7]], [[8 + i, 9, 10] for i in range(9)]):
        for prompt in wave:
            eng.add_request(prompt, sp)
        for _ in range(3):
            eng.step()
            joined.append(sum(len(w) for _, w in eng._chain["first"]) if eng._chain else 0)
    while eng.has_work():
        eng.step()
    assert {0, 1, 8} <= set(joined)
    assert len(shapes) >= 9 and len(set(shapes)) == 1
    assert ds.decode_burst._cache_size() - before <= 1
