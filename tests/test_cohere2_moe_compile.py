"""Cohere2-MoE's decode burst and its prefill waves compiled whole for a TPU
v5e that is described, not attached, at the shapes of the benchmark's cell
(``command-a-plus-ep8-bf16.repo-longctx``: published widths, one period of
three sliding layers and a global one, 2,560 global pages and 1,024 sliding
pages of 128 tokens, tables of 208 pages a row).  Both paged kernels pass the
chip's compiler told a first key (the burst's) and a window (the wave's), at
128 query and 8 kv heads of 128 with a 26 KB table in SMEM; nothing in the
optimized HLO copies, transposes or slices a pool of EITHER kind, an expert
stack or the embedding that is also the head; both kinds' commits are aligned
windows of slots written in place; and the ops this cell's metrics pick out of
a trace by their names are the ops under the scopes they are meant to read.
Nothing executes; a pass here is not a chip run.

What this file found: at tables of 208 pages the wave's kernel wants 17.3 MB of
VMEM for the eight-row wave at 256 columns a call (16 MB is the limit; the
one-row wave's passes): a chunk goes through 128 columns a call
(models/cohere2_moe.ATTN_SPAN).
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from tests.test_qwen3_next_compile import timed_lines
from tests.test_tpu_compile import (  # noqa: F401 - fixtures
    assert_commits_windows_in_place,
    chip,
    pool_movers,
    topo,
)

PAGE, ROWS, ROW_PAGES = 128, 32, 208
SCOPES = ("attn_proj", "rope", "kv_write", "sliding_attention", "sliding_prefill_attention",
          "paged_attention", "moe_route", "moe_experts", "moe_shared", "block_sum", "sample")
CELL = "command-a-plus-ep8-bf16.repo-longctx"
PROGRAMS = [pytest.param("burst", 0, id="burst")] + [
    pytest.param("wave", rows, id=f"wave-{rows}x512") for rows in (1, 2, 4, 8)]


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.cohere2_moe as family
    import githubrepostorag_tpu.ops.fused_decode as fused_decode

    for mod in (family, fused_decode):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def cell_config():
    from benchmarks import manifest
    from benchmarks.families import cohere2_moe as family

    cell = manifest.load_cell(CELL)
    return cell, family, family.model_config(family.model_of(cell.config, rehearse=False))


LOWERED_ONLY = (2, 8)  # wave buckets held on their lowered text: the one- and four-row forms again


@functools.lru_cache(maxsize=None)
def compiled(where, program: str, rows: int):
    """(optimized HLO, the shapes of what must stay in place) of the burst or
    of the wave at a row bucket, compiled once a module."""
    lowered, held = lowered_program(where, program, rows)
    return lowered.compile().as_text(), held


@functools.lru_cache(maxsize=None)
def lowered_program(where, program: str, rows: int):
    """(the lowered program, the shapes of what must stay in place): traced and
    lowered for the described chip, not yet through its back end."""
    from githubrepostorag_tpu.models.cohere2_moe import decode_burst, forward_paged_wave, init_params

    cell, _, cfg = cell_config()
    eng = cell.config["engine"]
    assert -(-eng["max_seq_len"] // PAGE) == ROW_PAGES and eng["max_num_seqs"] == ROWS
    assert (cfg.kv_layers, cfg.sliding_layers) == (1, 3)
    shaped = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where), t)
    params = shaped(jax.eval_shape(lambda: init_params(cfg, 0)))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    g_shape = (cfg.kv_layers, cfg.num_kv_heads, eng["num_pages"], PAGE, cfg.head_dim)
    s_shape = (cfg.sliding_layers, cfg.num_kv_heads, eng["sliding_pages"], PAGE, cfg.head_dim)
    kp, vp = sds(g_shape, jnp.bfloat16), sds(g_shape, jnp.bfloat16)
    sk, sv = sds(s_shape, jnp.bfloat16), sds(s_shape, jnp.bfloat16)
    b, i32, f32 = ROWS, jnp.int32, jnp.float32
    if program == "burst":
        lowered = decode_burst.lower(
            params, cfg, sds((b,), i32), sds((b,), i32), kp, vp,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), jnp.bool_), sds((b,), i32),
            sds((b, ROW_PAGES), i32), sds((2,), jnp.uint32), sds((b,), f32), sds((b,), f32),
            sds((b,), i32), sds((b,), f32), n_steps=eng["decode_burst"], use_pallas=True,
            filter_sampling=False,
            first_tokens=sds((b,), i32), fresh=sds((b,), jnp.bool_), fresh_lens=sds((b,), i32),
            key_step=sds((), jnp.uint32), sliding_k=sk, sliding_v=sv,
            sliding_tables=sds((b, ROW_PAGES), i32))
    else:
        chunk, row = (rows, 512), (rows,)
        lowered = forward_paged_wave.lower(
            params, cfg, sds(chunk, i32), sds(chunk, i32), kp, vp,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), i32), sds(chunk, i32),
            sds((rows, ROW_PAGES), i32), sds(row, i32), sds(row, i32), sds(row, i32),
            sds(row, i32), sds(row, jnp.bool_), sds((), i32), sds((2,), jnp.uint32),
            sds((), jnp.uint32), sds((b,), f32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
            use_pallas=True, sliding_k=sk, sliding_v=sv, sliding_slots=sds(chunk, i32),
            sliding_tables=sds((rows, ROW_PAGES), i32))
    held = {"global": g_shape, "sliding": s_shape, "embed": params["embed"].shape,
            **{k: params["layers"][k].shape for k in ("e_wgu", "e_wd", "s_wgu", "s_wd", "wqkv")}}
    return lowered, held


def timed_ops(hlo: str):
    """(name as a trace shows it, the scope it was traced under or '') of what
    a trace times."""
    from benchmarks.trace import short_name

    for line in timed_lines(hlo):
        path = re.search(r'op_name="([^"]*)"', line)
        scope = next((s for s in SCOPES if path and f"/{s}/" in path.group(1) + "/"), "")
        yield short_name(line)[0], scope


def _picked(hlo, pattern):
    by_scope = {}
    for name, scope in timed_ops(hlo):
        if pattern.search(name):
            by_scope.setdefault(scope, set()).add(name)
    return by_scope


def _dims(shape):
    return ",".join(map(str, shape))


@pytest.mark.parametrize("program,rows", PROGRAMS)
def test_step_program_leaves_both_kinds_of_pool_and_the_weights_in_place(
        chip, as_on_chip, program, rows):
    if rows in LOWERED_ONLY:
        # the two- and eight-row waves are the one- and four-row FORMS at another bucket (with
        # rungs and inline windows; one loop over the runs), which the back end is shown below
        # and in the commit guard.  What could differ by bucket shows before the back end
        # (ROADMAP D23: 65 s of compile a bucket): both kernels are called, and no pool is the
        # result of a scatter, a gather, a transpose or a concatenation, only of update-slices
        # of windows and of the kernels' own aliased results
        lowered, held = lowered_program(chip, program, rows)
        text = lowered.as_text()
        assert "tpu_custom_call" in text
        for kind in ("global", "sliding"):
            pool = "x".join(map(str, held[kind])) + "xbf16>"
            made = {m.group(1) for m in re.finditer(
                r"= \"?(stablehlo\.[a-z_]+|func\.call)\"?.*-> tensor<" + pool + "$", text, re.M)}
            assert made and made <= {"stablehlo.dynamic_update_slice", "stablehlo.custom_call",
                                     "stablehlo.while", "stablehlo.case", "func.call",
                                     "stablehlo.optimization_barrier"}, (kind, made)
            # a scatter states its operands' types where its region closes, the others in one line
            moved = [ln[:200] for ln in text.splitlines() if pool in ln and re.search(
                r"scatter_dimension_numbers|stablehlo\.(gather|transpose|concatenate|copy)\b", ln)]
            assert moved == [], (kind, moved)
        return
    hlo, held = compiled(chip, program, rows)
    assert "tpu_custom_call" in hlo  # the paged kernels: 128 / 8 x 128, a table of 208 pages
    for kind in ("global", "sliding"):
        assert pool_movers(hlo, held[kind], windows=False) == [], kind
        assert pool_movers(hlo, held[kind], ops=("scatter",)) == [], kind
    # every pool and every large leaf lies as the program is handed it: row-major
    layout = hlo.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
    for name, shape in held.items():
        order = ",".join(str(i) for i in reversed(range(len(shape))))
        assert f"bf16[{_dims(shape)}]{{{order}:" in layout, (name, layout[:2000])
    # and nothing the size of a pool, an expert stack, the shared experts or the head is copied
    big = [ln for ln in timed_lines(hlo, ("copy", "transpose"))
           if any(f"[{_dims(shape)}]" in ln.split("(")[0] or f"[{_dims(shape[1:])}]" in ln.split("(")[0]
                  for shape in held.values())]
    assert big == [], [ln[:200] for ln in big]


@pytest.mark.parametrize("program,rows", [PROGRAMS[0], PROGRAMS[1], PROGRAMS[3]])
def test_step_program_commits_both_kinds_as_windows_in_place(chip, as_on_chip, program, rows):
    """The global kind's commit is the guard every K/V family passes.  The
    sliding kind's pools are written by the same rule, three layers of them a
    period: in the burst one commit of all layers after the scan, in the wave a
    commit a layer inside the one traced period."""
    hlo, held = compiled(chip, program, rows)
    assert_commits_windows_in_place(hlo, held["global"], program, rows)
    if program == "burst":
        assert_commits_windows_in_place(hlo, held["sliding"], program, rows)
    else:
        once = len(pool_movers(hlo, held["global"], ("dynamic-update-slice",)))
        assert len(pool_movers(hlo, held["sliding"], ("dynamic-update-slice",))) == 3 * once


def test_this_cells_metrics_select_the_ops_under_their_scopes(chip, as_on_chip):
    """A trace's device plane names instructions, not scopes, so the metrics
    find their ops by name and output shape; the compiled programs' own
    metadata says which scope each came from."""
    from benchmarks import manifest

    cell, family, _ = cell_config()
    model = family.model_of(cell.config, rehearse=False)
    burst, _ = compiled(chip, "burst", 0)
    wave, _ = compiled(chip, "wave", 1)
    spec = lambda name: manifest.metric_spec(name)["args"]  # noqa: E731

    # the burst's kernel in a sliding layer: one call a layer and step, named for its scope;
    # the global layer's call keeps the name the accepted metric reads
    sliding = re.compile(spec("sliding_attn_roofline_frac")["op"])
    got = _picked(burst, sliding)
    assert set(got) == {"sliding_attention"}
    names = [n for n, s in timed_ops(burst) if s == "sliding_attention"]
    assert len(names) == 3 and all(n.startswith("sliding_attention.") for n in names)
    assert _picked(wave, sliding) == {}
    paged = re.compile(spec("paged_attn_hbm_frac")["op"])
    assert set(_picked(burst, paged)) == {"paged_attention"}
    assert len([n for n, _ in timed_ops(burst) if paged.search(n)]) == 1
    assert _picked(wave, paged) == {}  # the wave's global kernel is ``fused_window_attention``

    # the wave's kernel in a sliding layer: four calls of 128 columns a layer under its name,
    # the first in the period's body, the others in the branches that a narrow wave skips
    prefill = re.compile(spec("sliding_prefill_attn_roofline_frac")["op"])
    got = _picked(wave, prefill)
    assert set(got) == {"sliding_prefill_attention"} and _picked(burst, prefill) == {}
    calls = [n for n, _ in timed_ops(wave) if prefill.search(n)]
    assert len(calls) == 3 * 4, calls
    # the global layer's four (the compiler numbers the later ones; the first may go bare)
    assert len(re.findall(r"%fused_window_attention(\.\d+)? = ", wave)) == 4

    # the guard on the pools of BOTH kinds: nothing either program times moves one whole
    moves = re.compile(spec("sliding_pool_move_share")["pattern"])
    assert _picked(burst, moves) == {} and _picked(wave, moves) == {}
    for name in ("copy.3_bf16_3_8_1024_128_128_", "dynamic-slice.7_bf16_1_8_1024_128_128_",
                 "copy_dynamic-update-slice_fusion.2_bf16_3_8_131072_128_",
                 "copy.9_bf16_1_8_2560_128_128_", "dynamic-update-slice.4_bf16_8_327680_128_"):
        assert moves.search(name), name
    assert not moves.search("fusion.12_bf16_3_8_8192_16_128_")  # a commit's windows, in place

    # the expert products of the burst, by the accepted pattern filled with this family's sizes
    experts = re.compile(spec("moe_experts_hbm_frac")["op"].format(
        **family.expert_op_sizes(model, cell.config)))
    got = _picked(burst, experts)
    assert "moe_experts" in got
    # the same name and shape ([32, 4096] float32) also ends attention's output projection and
    # the shared experts' down-projection: the share's seconds hold them too (PERF.md section 3)
    assert set(got) <= {"moe_experts", "attn_proj", "moe_shared", "block_sum", ""}
    # the scopes this model adds or shares name ops of both programs
    assert {"attn_proj", "rope", "kv_write", "sliding_attention", "paged_attention", "moe_route",
            "moe_experts", "moe_shared", "sample"} <= {s for _, s in timed_ops(burst)}
    assert {"attn_proj", "rope", "kv_write", "sliding_prefill_attention", "paged_attention",
            "moe_route", "moe_experts", "moe_shared", "sample"} <= {s for _, s in timed_ops(wave)}
