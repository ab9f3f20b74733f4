"""The main-path Pallas kernels, compiled at published widths for a TPU v5e
that is described, not attached (on-chip-measurement guide, section 2.3).

Interpret mode — what every other kernel test runs — accepts things the
chip's compiler refuses: slices not aligned to the tiling, more VMEM than a
kernel may use.  These cases hand each kernel the shapes the serving engine
gives it for Qwen2-1.5B and Qwen2-7B (head 128, page size 128) and ask the
real TPU compiler.  Nothing executes, so they say nothing about results or
speed; a pass here is not a chip run.

The second half compiles the two step programs of the benchmark's cells
whole (decode burst, paged prefill; Qwen2-7B int8 weights, 384 pages) and
reads the optimized HLO: nothing in it may copy, transpose or slice a K/V
page pool, or a layer of one (PERF.md, Findings, PR 25), nor scatter single
rows into a full-precision one (PR 38: windows of slots, written in place).
"""

import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from githubrepostorag_tpu.ops.fused_decode import (
    _fold_pages,
    fused_window_attention,
    sliding_prefill_attention,
)
from githubrepostorag_tpu.ops.packed_prefill import packed_prefill_attention_seg
from githubrepostorag_tpu.ops.pallas_int4 import int4_matmul
from githubrepostorag_tpu.ops.pallas_paged import paged_attention_decode_staged

PAGE, PAGES, MAX_PAGES, LAYERS = 128, 256, 32, 28
# name -> (n_q heads, n_kv heads, head_dim, hidden, intermediate, batch)
MODELS = {
    "qwen2-1.5b": (12, 2, 128, 1536, 8960, 64),
    "qwen2-7b": (28, 4, 128, 3584, 18944, 32),
}
KV = {"fp": (jnp.bfloat16, 1), "int8": (jnp.int8, 1), "int4": (jnp.uint8, 2)}


@pytest.fixture(scope="module")
def topo():
    """A described v5e host (2x2); the persistent compile cache is off
    around these compiles (an entry written for a described chip cannot be
    read back without one, and the retry warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """Sharding on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    """The four described chips as the engine's MeshPlan(tp=4) mesh."""
    from githubrepostorag_tpu.parallel.mesh import MeshPlan, make_mesh

    return make_mesh(MeshPlan(tp=4), devices=topo.devices)


def _staged(model: str, kv: str, max_pages: int = MAX_PAGES):
    n_q, n_kv, hd, _, _, b = MODELS[model]
    dtype, _ = KV[kv]
    pool = ((LAYERS, n_kv, PAGES, PAGE, hd), dtype)
    staged = ((b, n_kv, 8, hd), jnp.bfloat16)
    args = [((b, 1, n_q, hd), jnp.bfloat16), pool, pool, ((b, max_pages), jnp.int32),
            ((b,), jnp.int32), staged, staged, ((1,), jnp.int32), ((1,), jnp.int32)]
    if kv != "fp":
        args += [((LAYERS, n_kv, PAGES), jnp.float32)] * 2
    return jax.jit(functools.partial(paged_attention_decode_staged, interpret=False)), args


def _window(model: str, kv: str, window: int):
    n_q, n_kv, hd, _, _, b = MODELS[model]
    dtype, pack = KV[kv]
    pool = ((n_kv, PAGES, PAGE, hd // pack), dtype)
    args = [((b, window, n_q, hd), jnp.bfloat16), pool, pool, ((b, MAX_PAGES), jnp.int32),
            ((b,), jnp.int32), ((b,), jnp.int32)]
    if kv != "fp":
        args += [((n_kv, PAGES), jnp.float32)] * 2
    return jax.jit(functools.partial(fused_window_attention, interpret=False)), args


def _packed(model: str):
    n_q, n_kv, hd, _, _, _ = MODELS[model]
    r = 8
    pool = ((n_kv, PAGES, PAGE, hd), jnp.bfloat16)
    args = [((r, 512, n_q, hd), jnp.bfloat16), pool, pool, ((r, MAX_PAGES), jnp.int32),
            ((r,), jnp.int32), ((r,), jnp.int32)]
    return jax.jit(functools.partial(packed_prefill_attention_seg, interpret=False)), args


def _int4(model: str):
    _, _, _, d, inter, _ = MODELS[model]
    group = 64
    args = [((32, d), jnp.bfloat16), ((LAYERS, d // 2, 2 * inter), jnp.uint8),
            ((LAYERS, d // group, 2 * inter), jnp.bfloat16),
            ((LAYERS, d // group, 2 * inter), jnp.bfloat16), ((), jnp.int32)]
    return jax.jit(functools.partial(int4_matmul, interpret=False)), args


CASES = [
    pytest.param(build, id=name)
    for model in MODELS
    for name, build in (
        [(f"staged-{kv}-{model}", functools.partial(_staged, model, kv)) for kv in ("fp", "int8")]
        # windows: 1 = plain decode, 5 = a spec-verify window, 512 = the
        # prefill chunk the default engine sends through this kernel
        + [(f"window{w}-{kv}-{model}", functools.partial(_window, model, kv, w))
           for kv in KV for w in (1, 5)]
        + [(f"window512-fp-{model}", functools.partial(_window, model, "fp", 512)),
           (f"packed-tq512-{model}", functools.partial(_packed, model)),
           (f"int4-matmul-m32-{model}", functools.partial(_int4, model))]
    )
] + [
    # the burst kernel at the benchmark cells' own table width (CELL_ROW_PAGES below)
    pytest.param(functools.partial(_staged, "qwen2-7b", kv, 16), id=f"staged-{kv}-qwen2-7b-table16")
    for kv in ("fp", "int8")
]


@pytest.mark.parametrize("build", CASES)
def test_kernel_compiles_for_v5e(chip, build):
    fn, args = build()
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in args]
    compiled = fn.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the wave's attention call as the cells' programs make it (PR 51): name -> (rows, kv heads,
# query heads a kv head, columns, head, pages a row's table holds, pages of the pool, a sliding
# layer's window, bfloat16 products, the pages a grid step folds)
WAVE_CALLS = {
    # Command A+: 128 columns a call (ATTN_SPAN), tables of 208 pages; the eight-row call is
    # the tightest fit of any cell (models/cohere2_moe.py)
    **{f"command-a-plus-{rows}row": (rows, 8, 16, 128, 128, 208, 2560, None, True, 8)
       for rows in (1, 2, 4, 8)},
    "command-a-plus-8row-sliding": (8, 8, 16, 128, 128, 208, 1024, 4096, True, 7),
    # the hybrids' 512-column calls, contexts to 10,240
    "olmo-hybrid-2row": (2, 30, 1, 512, 128, 80, 1280, None, False, 8),
    "falcon-h1-2row": (2, 4, 5, 512, 128, 80, 1280, None, False, 8),
    # Mellum2: 8 query heads a kv head, 256 columns a call (models/mellum.ATTN_SPAN), tables of
    # 208 pages; a sliding layer's window is 8 pages where a call's columns are 2: a walk of 11
    # pages in 2 steps of 6
    "mellum2-1row": (1, 4, 8, 256, 128, 208, 2560, None, True, 8),
    "mellum2-8row": (8, 4, 8, 256, 128, 208, 2560, None, True, 8),
    "mellum2-8row-sliding": (8, 4, 8, 256, 128, 208, 1024, 1024, True, 6),
}


@pytest.mark.parametrize("name", WAVE_CALLS)
def test_wave_kernel_folds_pages_and_fits_v5e(chip, name):
    """The rule picks the pages a step folds from the call's shapes alone, and
    the call it shapes fits the 16 MB a v5e kernel may hold: as many K and as
    many V operands as pages folded, each the whole pool."""
    rows, n_kv, group, cols, hd, table, pages, window, narrow, fold = WAVE_CALLS[name]
    walk = table if window is None else min(table, (window + cols - 2) // PAGE + 2)
    assert _fold_pages(walk, group, cols, hd, PAGE, 2, 0) == fold
    pool = ((1, n_kv, pages, PAGE, hd), jnp.bfloat16)
    args = [((rows, cols, n_kv * group, hd), jnp.bfloat16), pool, pool, ((rows, table), jnp.int32),
            ((rows,), jnp.int32), ((rows,), jnp.int32), None, None, ((), jnp.int32)]
    shapes = [a and jax.ShapeDtypeStruct(*a, sharding=chip) for a in args]
    call = fused_window_attention if window is None else sliding_prefill_attention
    hlo = call.lower(*shapes, interpret=False, sliding=window, bf16_products=narrow).compile().as_text()
    custom = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(custom) == 1
    assert custom[0].count(f"bf16[1,{n_kv},{pages},{PAGE},{hd}]") == 2 * fold  # the pool itself, no copy
    # the name and the shape a trace's op names carry (benchmarks/readers, tests/benchmarks)
    assert re.match(rf"\s*%{call.__name__}\.\d+ = bf16\[{rows},{n_kv},{group},{cols},{hd}\]", custom[0])


# ---- the step programs keep the K/V page pools where they are -------------

CELL_PAGES, CELL_ROWS, CELL_ROW_PAGES = 384, 32, 16  # benchmarks/configs/qwen2-7b-int8.json
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\[([\d,]+)\]\S* ([\w\-]+)\(")
MOVERS = ("copy", "copy-start", "transpose", "dynamic-slice", "dynamic-update-slice",
          "all-gather", "all-gather-start", "all-to-all", "collective-permute")


_UPDATE = re.compile(r" dynamic-update-slice\(%?[\w.\-]+, %?([\w.\-]+)[,)]")


def assert_hit_experts_are_one_walk(burst_ops, wave_lines, layers: int, rows: int, hidden: int,
                                    gate_up: int, expert_shapes: tuple, pattern) -> None:
    """A layer's hit experts are ONE call of ops/pallas_experts.walk_experts
    (PR 58).  ``burst_ops``: (name, scope) of a burst's timed ops: the call is
    named for the ``moe_experts`` scope and its result, where the accepted
    ``moe_experts_hbm_frac`` (``pattern``, filled) finds it; XLA's product of
    one expert (``fusion_bf16_<rows>_<gate_up>_``) is gone with the loop, and
    nothing under the scope copies or slices an expert or a layer of them out
    of the stacks.  ``wave_lines``: a one-row wave's timed custom calls: its 128
    columns take the same walk under a name the pattern does NOT match (the
    engine counts hit experts for bursts only: a wave's seconds there would
    read the share low)."""
    under = [re.sub(r"\.\d+", "", n) for n, scope in burst_ops if scope == "moe_experts"]
    assert under.count(f"moe_experts_f32_{rows}_{hidden}_") == layers, under
    assert pattern.search(f"moe_experts.80_f32_{rows}_{hidden}_")
    shapes = "|".join("_".join(map(str, s)) for s in expert_shapes)
    assert not [n for n in under if re.search(rf"_bf16_{rows}_{gate_up}_$|_({shapes})_$", n)], under
    calls = {m.group(1) for ln in wave_lines if "/moe_experts/" in ln and "tpu_custom_call" in ln
             for m in [re.match(rf"%?([\w\-]+?)(\.\d+)? = f32\[128,{hidden}\]", ln)] if m}
    assert calls == {"wave_experts"}, calls
    assert not pattern.search(f"wave_experts.4_f32_128_{hidden}_")


def pool_movers(hlo: str, pool_shape: tuple, ops: tuple = MOVERS, windows: bool = True) -> list:
    """Instructions of the optimized HLO, fused computations included (so a
    fusion of a copy counts), that copy, transpose, slice, update-slice or
    gather across chips into a result holding a whole pool (as one device
    holds it, or all of it) or a whole layer of one, however its
    leading axes are merged: [28,4,384,128,128], [1,4,384,128,128],
    [4,49152,128], [112,49152,128], [5505024,128], [28,4,3072,16,128], ...

    ``windows=False`` leaves out the update-slices that write in place: an
    update-slice writes its update (its second operand) into the buffer of
    its first, and where the compiler cannot reuse that buffer it says so
    with a ``copy`` before it, which is found under its own name.  So an
    update-slice moves a pool only when the update itself holds a layer of
    one or more (a scan stacking its ys: PR 25), not when it is a window of
    slots (``commit_paged``'s run form, PR 38; a state pool's rows, PR 34,
    whose test counts them).  ``ops`` names other instructions to look for
    instead: ("scatter",) finds the row form's scatters into a pool."""
    sizes = (math.prod(pool_shape[:-1]), math.prod(pool_shape[1:-1]))
    elements, found = {}, []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            elements[m.group(1)] = math.prod(map(int, m.group(2).split(",")))
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and m.group(3) in ops:
            *lead, width = map(int, m.group(2).split(","))
            if width != pool_shape[-1] or math.prod(lead) not in sizes:
                continue
            update = _UPDATE.search(line)
            if (not windows and update
                    and elements.get(update.group(1), math.inf) < math.prod(pool_shape[1:])):
                continue
            found.append(f"{m.group(1)} {m.group(3)} [{m.group(2)}]")
    return found


def assert_calls_step_pool_in_place(calls: list, pool: str) -> None:
    """Each of ``calls`` (custom-call lines of the optimized HLO: the kernels
    of ops/pallas_state.py under their scope) takes the pool (its type as the
    HLO prints it, ``f32[8,97,64,64,128]``) whole, once, and gives it back as
    its SECOND result in the same buffer."""
    for call in calls:
        result, operands = call.split(" custom-call(", 1)
        layouts = operands.split("operand_layout_constraints={", 1)[1].split("}, output_to", 1)[0]
        assert result.count(pool) == 1 and layouts.count(pool) == 1, call[:400]
        at = len(re.findall(r"[a-z0-9]+\[[0-9,]*\]\{", layouts.split(pool)[0]))  # its operand
        assert f"output_to_operand_aliasing={{{{1}}: ({at}, {{}})}}" in call, call[:1200]


def assert_wave_keeps_in_place(hlo: str, held: str, count: int,
                               module: str = "jit_forward_paged_wave") -> None:
    """The compiled module is ``module`` and each of its ``count`` entry
    parameters whose type matches ``held`` (the pools, the presence mask) is
    aliased to an output: donated, and updated in place."""
    head = hlo.split("\n", 1)[0]
    assert re.match(rf"HloModule {module}\b", head), head
    aliases = head.split("input_output_alias=")[1].split("entry_computation_layout")[0]
    aliased = {int(p) for p in re.findall(r"\(\s*(\d+), \{\}", aliases)}
    params = {int(re.search(r"parameter\((\d+)\)", line).group(1))
              for line in hlo.split("ENTRY", 1)[1].splitlines()
              if " parameter(" in line and re.search(held, line)}
    assert len(params) == count and params <= aliased, (params, aliased)


# the programs a hybrid family's commit guard compiles: (program, rows of its 512-column wave).
# The FORMS, not every bucket (ROADMAP D23): the burst, a wave with rungs (its windows inline a
# row: one row; two rows are the same form twice over) and the wave whose commit loops over its
# runs at both its buckets (four rows and eight; eight is the bucket the families' other guards
# compile already, so it costs Qwen3-Next's and Nemotron-H's files nothing, where the two-row
# wave cost them 117 and 95 s of compile for one more copy of the one-row form)
COMMIT_CASES = [pytest.param("burst", 0, id="burst")] + [
    pytest.param("wave", rows, id=f"wave-{rows}x512") for rows in (1, 4, 8)]


def assert_commits_windows_in_place(hlo: str, pool_shape: tuple, program: str, rows: int) -> None:
    """A step program of a cell (``program``: "burst", 8 steps, or "wave" at
    ``rows`` rows of 512 columns) that tells ``commit_paged`` its slots are
    runs writes its two full-precision K/V pools as update-slices of aligned
    windows of slots and as nothing else: no scatter of single rows into a
    pool, nothing that copies, transposes or re-lays a pool or a layer of
    one, both pools aliased operand to result, and exactly the windows a run
    can fall in, each written once: inline for a wave with rungs, once in the
    body of a loop over the runs otherwise (``kv_cache._commit_runs``)."""
    from githubrepostorag_tpu.ops.prefill_width import MAX_RUNG_ROWS
    from githubrepostorag_tpu.serving.kv_cache import _run_window

    run = 8 if program == "burst" else 512  # the cells' ``decode_burst`` and ``prefill_chunk``
    assert pool_movers(hlo, pool_shape, ops=("scatter",)) == []
    assert pool_movers(hlo, pool_shape, windows=False) == []
    module = "jit_decode_burst" if program == "burst" else "jit_forward_paged_wave"
    assert_wave_keeps_in_place(hlo, r"bf16\[" + ",".join(map(str, pool_shape)) + r"\]", 2, module)
    win = _run_window(jax.ShapeDtypeStruct(pool_shape, jnp.bfloat16), run)
    per_run = (run + win - 2) // win + 1
    inline = rows if program == "wave" and rows <= MAX_RUNG_ROWS else 1
    windows = pool_movers(hlo, pool_shape, ("dynamic-update-slice",))
    assert len(windows) == 2 * per_run * inline, windows


def assert_wave_holds_every_rung(hlo: str, rungs: int, pool_shape: tuple) -> None:
    """Each layer scan of the compiled wave switches among ``rungs`` branches
    that take the pool and hand it back (ops/prefill_width.at_wave_width),
    and no branch, like nothing else in the module, copies it."""
    pool = r"bf16\[" + ",".join(map(str, pool_shape)) + r"\]"
    switches = [ln for ln in hlo.splitlines()
                if re.search(r"= \([^=]*" + pool + r"[^=]* conditional\(", ln)]
    assert switches, "no switch hands the pool on"
    for ln in switches:
        assert "while/body" in ln, ln  # inside a layer scan, not around one
        branches = re.search(r"branch_computations=\{([^}]*)\}", ln).group(1).split(",")
        assert len(branches) == rungs, ln
    assert pool_movers(hlo, pool_shape, windows=False) == []


@pytest.fixture()
def as_on_chip(monkeypatch):
    """The program asks runtime.on_tpu() whether its kernels run compiled or
    interpreted; a compile for a described chip answers for it here."""
    import githubrepostorag_tpu.models.quant as quant
    import githubrepostorag_tpu.ops.fused_decode as fused_decode
    import githubrepostorag_tpu.serving.decode_burst as decode_burst

    for mod in (quant, fused_decode, decode_burst):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def _cell_program(where, program: str, kv: str, variant):
    """decode_burst (variant: filter_sampling; with the overlay of fresh rows,
    as the engine calls it), forward_paged or the engine's forward_paged_wave
    (variant: rows of 512 new tokens) lowered at the cell's shapes ->
    (lowered, shape of the pool a device holds).  ``where`` is one described chip's sharding
    (the cell's own int8 weights) or a tp mesh (bf16 weights sharded as the
    engine shards them, the pools over kv heads, everything else replicated)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from githubrepostorag_tpu.models.quant import init_params_quantized
    from githubrepostorag_tpu.models.qwen2 import (
        Qwen2Config,
        forward_paged,
        forward_paged_wave,
        init_params,
    )
    from githubrepostorag_tpu.parallel.sharding import qwen2_param_specs
    from githubrepostorag_tpu.serving.decode_burst import decode_burst

    cfg = Qwen2Config.qwen2_7b()
    dtype, pack = KV[kv]
    lead = (cfg.num_layers, cfg.num_kv_heads, CELL_PAGES)
    pool_shape = (*lead, PAGE, cfg.head_dim // pack)
    mesh = where if isinstance(where, Mesh) else None
    if mesh is None:
        params = jax.eval_shape(lambda: init_params_quantized(cfg, 0, bits=8, fuse=True))
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where), params)
        everywhere = over_heads = where
        held = pool_shape
    else:
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
        params = jax.tree.map(
            lambda x, spec: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
            params, qwen2_param_specs(cfg, mesh, params))
        everywhere = NamedSharding(mesh, P())
        over_heads = NamedSharding(mesh, P(None, "tp"))
        held = (lead[0], lead[1] // mesh.shape["tp"], *pool_shape[2:])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=everywhere)

    pool = jax.ShapeDtypeStruct(pool_shape, dtype, sharding=over_heads)
    scale = jax.ShapeDtypeStruct(lead, jnp.float32, sharding=over_heads)
    scales = {} if kv == "fp" else {"k_scales": scale, "v_scales": scale}
    b, i32, f32 = CELL_ROWS, jnp.int32, jnp.float32
    if program == "burst":
        lowered = decode_burst.lower(
            params, cfg, sds((b,), i32), sds((b,), i32), pool, pool,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), jnp.bool_), sds((b,), i32),
            sds((b, CELL_ROW_PAGES), i32), sds((2,), jnp.uint32), sds((b,), f32),
            sds((b,), f32), sds((b,), i32), sds((b,), f32),
            n_steps=8, use_pallas=True, filter_sampling=variant, mesh=mesh, **scales,
            first_tokens=sds((b,), i32), fresh=sds((b,), jnp.bool_),
            fresh_lens=sds((b,), i32), key_step=sds((), jnp.uint32),
        )
    elif program == "wave":
        chunk, rows = (variant, 512), (variant,)
        lowered = forward_paged_wave.lower(
            params, cfg, sds(chunk, i32), sds(chunk, i32), pool, pool,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), i32), sds(chunk, i32),
            sds((variant, CELL_ROW_PAGES), i32), sds(rows, i32), sds(rows, i32),
            sds(rows, i32), sds(rows, i32), sds(rows, jnp.bool_), sds((), i32),
            sds((2,), jnp.uint32), sds((), jnp.uint32), sds((b,), f32), sds((b,), f32),
            sds((b,), i32), sds((b,), f32), use_pallas=True, mesh=mesh, **scales,
        )
    else:
        chunk = (variant, 512)
        lowered = forward_paged.lower(
            params, cfg, sds(chunk, i32), sds(chunk, i32), pool, pool, sds(chunk, i32),
            sds((variant, CELL_ROW_PAGES), i32), sds((variant,), i32), sds((variant,), i32),
            use_pallas=True, logits_at=sds((variant,), i32), mesh=mesh, **scales,
        )
    return lowered, held


# int4 pages are 64 bytes wide, half a lane tile: the compiler keeps them
# in layouts of its own, and the burst reads them through the gather path
_INT4 = pytest.mark.xfail(strict=True, reason=(
    "int4 pools: forward_paged's scatter wants u8[5505024,64]{0,1} and the "
    "compiler copies each pool in and out of it (copy u8[28,4,384,128,64]); "
    "the burst has no nibble kernel and dynamic-slices each layer for gather_kv"))
POOL_CASES = [
    pytest.param("burst", "fp", True, id="burst-filter-fp"),
    pytest.param("burst", "fp", False, id="burst-nofilter-fp"),
    pytest.param("prefill", "fp", 1, id="prefill-1x512-fp"),
    pytest.param("prefill", "fp", 2, id="prefill-2x512-fp"),
    pytest.param("wave", "fp", 1, id="wave-1x512-fp"),
    pytest.param("wave", "fp", 4, id="wave-4x512-fp"),
    pytest.param("wave", "int8", 1, id="wave-1x512-int8"),
    pytest.param("burst", "int8", False, id="burst-nofilter-int8"),
    pytest.param("prefill", "int8", 1, id="prefill-1x512-int8"),
    pytest.param("burst", "int4", False, id="burst-nofilter-int4", marks=_INT4),
    pytest.param("prefill", "int4", 1, id="prefill-1x512-int4", marks=_INT4),
]


@functools.lru_cache(maxsize=None)
def _cell_hlo(where, program: str, kv: str, variant):
    """(optimized HLO, the pool's shape) of a cell's step program, compiled once
    for every case that inspects it (ROADMAP D23)."""
    lowered, pool_shape = _cell_program(where, program, kv, variant)
    return lowered.compile().as_text(), pool_shape


@pytest.mark.parametrize("program,kv,variant", POOL_CASES)
def test_step_program_leaves_the_pools_in_place(chip, as_on_chip, program, kv, variant):
    hlo, pool_shape = _cell_hlo(chip, program, kv, variant)
    assert "tpu_custom_call" in hlo  # the attention kernel is in the program
    assert pool_movers(hlo, pool_shape, windows=False) == []
    # full-precision pools are committed a window of slots at a time, in every
    # rung of the wave: no scatter of single rows into a pool is left
    rows = pool_movers(hlo, pool_shape, ("scatter",))
    windows = pool_movers(hlo, pool_shape, ("dynamic-update-slice",))
    assert (rows == [] and windows) if kv == "fp" else rows, (rows, windows)


def test_the_wave_is_one_program_that_donates_pools_and_presence(chip, as_on_chip):
    """The engine's prefill wave, first-token tail included: the readers of
    the benchmark find it by ``forward_paged`` in its module's name, and the
    three buffers it is handed to keep (K pool, V pool, the presence mask)
    come back in place.  (That nothing in it moves a pool is a case of
    test_step_program_leaves_the_pools_in_place, whose one-row wave this reads:
    one row and two are the same form, three rungs, and the two-row wave was
    55 s of compile for this test alone.)"""
    hlo, pool_shape = _cell_hlo(chip, "wave", "fp", 1)
    assert_wave_keeps_in_place(hlo, r"bf16\[28,4,384,128,128\]|pred\[32,152064\]", 3)
    assert_wave_holds_every_rung(hlo, 3, pool_shape)  # 512, 256, 128 columns


@pytest.mark.parametrize("program,variant", [("burst", False), ("prefill", 1)],
                         ids=["burst-nofilter-fp", "prefill-1x512-fp"])
def test_step_program_leaves_sharded_pools_in_place(tp4, as_on_chip, program, variant):
    """MeshPlan(tp=4): each chip commits into its own kv head's pages.  The
    window commit made GSPMD all-gather both pools in every burst (PR 22 read
    18% of device time there); the row commit indexes the head axis, which
    stays sharded."""
    lowered, held = _cell_program(tp4, program, "fp", variant)
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    whole = (held[0], held[1] * tp4.shape["tp"], *held[2:])
    assert pool_movers(hlo, held, windows=False) + pool_movers(hlo, whole, windows=False) == []


def test_the_burst_has_one_shape_whatever_joins_it():
    """On the CPU, at a tiny size: no row, one row, three rows of one wave and
    rows of two waves joining a burst call the same program with the same
    shapes (the masks of fresh rows are [max_num_seqs] wide), so nothing but
    the wave and the burst is ever compiled for a step."""
    from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
    from githubrepostorag_tpu.serving import Engine, SamplingParams
    from githubrepostorag_tpu.serving.decode_burst import decode_burst
    from tests.helpers.step_programs import burst_call_shapes

    cfg = Qwen2Config.tiny()
    eng = Engine(init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32), cfg, max_num_seqs=8,
                 num_pages=128, page_size=4, max_seq_len=96, prefill_chunk=16,
                 kv_dtype=jnp.float32, decode_burst=4, prefill_priority=True)
    sp = SamplingParams(max_tokens=30, temperature=0.0, stop_token_ids=())
    shapes = burst_call_shapes(eng)
    joined = []
    for wave in ([[1, 2, 3]], [[4, 5]], [[6, 7, 8], [9, 10], [11]], [[12, 13], list(range(2, 40))]):
        for prompt in wave:
            eng.add_request(prompt, sp)
        for _ in range(3):
            eng.step()
            joined.append(len(eng._chain["first"]) if eng._chain else 0)
    while eng.has_work():
        eng.step()
    assert {0, 1, 2} <= set(joined)  # bursts with no wave, one wave and two waves of rows
    assert len(shapes) >= 12 and len(set(shapes)) == 1
    assert decode_burst._cache_size() == 1
