"""Mellum's decode burst and its prefill waves compiled whole for a TPU v5e
that is described, not attached, at the shapes of the benchmark's cell
(``mellum2-12b-a2.5b-bf16.repo-longctx``: published widths, two periods of
three sliding layers and a global one, every one of a layer's 64 experts,
2,560 global pages and 1,024 sliding pages of 128 tokens, tables of 208 pages a
row).  Both paged kernels pass the chip's compiler at 8 query heads a kv head
(32 / 4 x 128) told a first key (the burst's) and a window of 8 pages (the
wave's); nothing in the optimized HLO copies, transposes or slices a pool of
EITHER kind, an expert stack, the embedding or the head; both kinds' commits
are aligned windows of slots written in place; the program fits the chip's
memory beside nothing else; and the ops this cell's metrics pick out of a
trace by their names are the ops under the scopes they are meant to read.
Nothing executes; a pass here is not a chip run.

The burst, the one-row wave (three rungs a layer) and the eight-row wave (the
largest) are compiled here; the two- and four-row waves were compiled once by
hand for PR 54 (they are the same program at other row counts).
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
    assert_hit_experts_are_one_walk,
    chip,
    pool_movers,
    topo,
)

PAGE, ROWS, ROW_PAGES = 128, 32, 208
SCOPES = ("attn_proj", "qk_norm", "rope", "kv_write", "sliding_attention",
          "sliding_prefill_attention", "paged_attention", "moe_route", "moe_experts", "sample")
CELL = "mellum2-12b-a2.5b-bf16.repo-longctx"
PROGRAMS = [pytest.param("burst", 0, id="burst")] + [
    pytest.param("wave", rows, id=f"wave-{rows}x512") for rows in (1, 8)]
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.mellum as family
    import githubrepostorag_tpu.ops.fused_decode as fused_decode
    import githubrepostorag_tpu.ops.pallas_experts as experts

    for mod in (family, fused_decode, experts):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def cell_config():
    from benchmarks import manifest
    from benchmarks.families import mellum as family

    cell = manifest.load_cell(CELL)
    return cell, family, family.model_config(family.model_of(cell.config, rehearse=False))


@functools.lru_cache(maxsize=None)
def compiled(where, program: str, rows: int):
    """(optimized HLO, the shapes of what must stay in place, the compiler's
    memory analysis) of the burst or of the wave at a row bucket, compiled once
    a module."""
    from githubrepostorag_tpu.models.mellum import decode_burst, forward_paged_wave, init_params

    cell, _, cfg = cell_config()
    eng = cell.config["engine"]
    assert -(-eng["max_seq_len"] // PAGE) == ROW_PAGES and eng["max_num_seqs"] == ROWS
    assert (cfg.kv_layers, cfg.sliding_layers, cfg.n_held) == (2, 6, 64)
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
            "lm_head": params["lm_head"].shape,
            **{k: params["layers"][k].shape for k in ("e_wgu", "e_wd", "wqkv", "wo")}}
    exe = lowered.compile()
    return exe.as_text(), held, exe.memory_analysis()


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
    hlo, held, memory = compiled(chip, program, rows)
    assert "tpu_custom_call" in hlo  # the paged kernels: 32 / 4 x 128, a table of 208 pages
    for kind in ("global", "sliding"):
        assert pool_movers(hlo, held[kind], windows=False) == [], kind
        assert pool_movers(hlo, held[kind], ops=("scatter",)) == [], kind
    # every pool and every large leaf lies as the program is handed it: row-major
    layout = hlo.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
    for name, shape in held.items():
        order = ",".join(str(i) for i in reversed(range(len(shape))))
        assert f"bf16[{_dims(shape)}]{{{order}:" in layout, (name, layout[:2000])
    # and nothing the size of a pool, an expert stack, the embedding or the head is copied
    big = [ln for ln in timed_lines(hlo, ("copy", "transpose"))
           if any(f"[{_dims(shape)}]" in ln.split("(")[0] or f"[{_dims(shape[1:])}]" in ln.split("(")[0]
                  for shape in held.values())]
    assert big == [], [ln[:200] for ln in big]
    # the weights and both pools (10.54 GB) and the program's own temporaries fit the chip:
    # the pools are donated, so arguments count them once
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(program, rows, "arguments", memory.argument_size_in_bytes, "temporaries",
          memory.temp_size_in_bytes)
    assert 10.4e9 < memory.argument_size_in_bytes < 10.7e9
    assert total < 0.75 * HBM_BYTES, total


@pytest.mark.parametrize("program,rows", PROGRAMS[:2])
def test_step_program_commits_both_kinds_as_windows_in_place(chip, as_on_chip, program, rows):
    """The global kind's commit is the guard every K/V family passes.  The
    sliding kind's pools are written by the same rule, three layers of them a
    period: in the burst one commit of all layers after the scan, in the wave a
    commit a layer inside the one traced period."""
    hlo, held, _ = compiled(chip, program, rows)
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

    cell, family, cfg = cell_config()
    model = family.model_of(cell.config, rehearse=False)
    burst, _, _ = compiled(chip, "burst", 0)
    wave, _, _ = compiled(chip, "wave", 1)
    spec = lambda name: manifest.metric_spec(name)["args"]  # noqa: E731

    # the burst's kernel in a sliding layer: one call a layer and step, named for its scope;
    # the global layers' calls keep the name the accepted metric reads
    sliding = re.compile(spec("mellum_sliding_attn_roofline_frac")["op"])
    assert set(_picked(burst, sliding)) == {"sliding_attention"}
    names = [n for n, s in timed_ops(burst) if s == "sliding_attention"]
    assert len(names) == cfg.sliding_layers and all(n.startswith("sliding_attention.") for n in names)
    assert _picked(wave, sliding) == {}
    paged = re.compile(spec("paged_attn_hbm_frac")["op"])
    assert set(_picked(burst, paged)) == {"paged_attention"}
    assert len([n for n, _ in timed_ops(burst) if paged.search(n)]) == cfg.kv_layers
    assert _picked(wave, paged) == {}  # the wave's global kernel is ``fused_window_attention``

    # the wave's kernel in a sliding layer: two calls of 256 columns a layer under its name (the
    # scan traces one period: three sliding layers), the first in the period's body, the second
    # in the branch that a narrow wave skips; the global layer's two
    prefill = re.compile(spec("mellum_sliding_prefill_attn_roofline_frac")["op"])
    assert set(_picked(wave, prefill)) == {"sliding_prefill_attention"}
    assert _picked(burst, prefill) == {}
    calls = [n for n, _ in timed_ops(wave) if prefill.search(n)]
    assert len(calls) == 3 * 2, calls
    assert len(re.findall(r"%fused_window_attention(\.\d+)? = ", wave)) == 2

    # the guard on the pools of BOTH kinds reads Command A+'s shapes, not these: it is not in
    # this cell's list; what it would guard is held by the test above
    assert CELL not in next(m for m in manifest.load_manifest()["per_layer"]
                            if m["name"] == "sliding_pool_move_share")["workloads"]

    # the expert products of the burst, by the accepted pattern filled with this family's sizes
    experts = re.compile(spec("moe_experts_hbm_frac")["op"].format(
        **family.expert_op_sizes(model, cell.config)))
    got = _picked(burst, experts)
    print("moe_experts_hbm_frac picks", {k: sorted(v)[:4] for k, v in got.items()})
    assert len(got["moe_experts"]) == cfg.num_layers  # the walk over the hit experts, a call a layer
    assert_hit_experts_are_one_walk(
        list(timed_ops(burst)), timed_lines(wave, ("custom-call",)), cfg.num_layers, 32, 2304, 1792,
        ((2304, 1792), (896, 2304)), experts)
    assert "moe_experts" not in _picked(wave, experts)
    # the same name and shape ([32, 2304] float32) also ends attention's output projection
    # (2.5% of the experts' bytes a layer): the share's seconds hold it too (PERF.md section 3)
    assert set(got) <= {"moe_experts", "attn_proj", ""}
    # the scopes this model adds or shares name ops of both programs
    assert {"attn_proj", "kv_write", "sliding_attention", "paged_attention", "moe_route",
            "moe_experts", "sample"} <= {s for _, s in timed_ops(burst)}
    assert {"attn_proj", "kv_write", "sliding_prefill_attention", "paged_attention",
            "moe_route", "moe_experts", "sample"} <= {s for _, s in timed_ops(wave)}
