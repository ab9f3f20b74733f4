"""Qwen3-Next's two step programs compiled whole for a TPU v5e that is
described, not attached, at the shapes of the benchmark's cell
(``qwen3-next-80b-a3b-ep4-bf16``: published widths, two periods, 128 experts
held, 2,048 pages of 128 tokens for the 2 attention layers, 32 live + 64
snapshot + 1 slots of state for the 6 Gated DeltaNet layers): both paged
kernels pass the chip's compiler at head size 256 with a group of 8; nothing
in the optimized HLO copies, transposes or slices a K/V pool, the state pool,
an expert stack or a Gated DeltaNet weight stack (a wave WRITES the state pool
in place, a row's slot at a time, and the burst hands it whole to one kernel a
Gated DeltaNet layer, ops/pallas_state.py, aliased in and out, PR 56; the
burst slices a layer of a weight stack inside the product that reads it, and
nowhere else);
and the ops that the benchmark's metrics pick out of a trace by their shapes
are the ops under the scopes they are meant to read.  Nothing executes; a
pass here is not a chip run.
"""

import collections
import functools
import json
import math
import os
import pathlib
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from tests.test_tpu_compile import (  # noqa: F401 - fixtures
    COMMIT_CASES,
    assert_calls_step_pool_in_place,
    assert_commits_windows_in_place,
    assert_hit_experts_are_one_walk,
    assert_wave_keeps_in_place,
    chip,
    pool_movers,
    topo,
)

PAGES, PAGE, ROWS, ROW_PAGES, SLOTS = 2048, 128, 32, 80, 97
SCOPES = ("gdn_proj", "gdn_conv", "gdn_chunked", "gdn_recurrent", "gdn_gate_norm", "attn_gate",
          "state_read", "state_write", "paged_attention", "kv_write", "moe_route", "moe_experts",
          "moe_shared", "sample")


def cell_config():
    from githubrepostorag_tpu.models.qwen3_next import Qwen3NextConfig

    return Qwen3NextConfig(vocab_size=37984, num_layers=8, experts_held=(0, 128))


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.hybrid as hybrid
    import githubrepostorag_tpu.ops.fused_decode as fused_decode
    import githubrepostorag_tpu.ops.latent_attention as latent
    import githubrepostorag_tpu.ops.pallas_experts as experts

    for mod in (hybrid, fused_decode, latent, experts):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


@functools.lru_cache(maxsize=None)
def compiled(where, program: str, rows: int):
    """(optimized HLO, the shapes of what must stay in place) of the burst or
    of the wave at a row bucket.  Compiled once a module: a wave of one row
    holds three rungs of eight layers and takes a minute."""
    from githubrepostorag_tpu.models.qwen3_next import (
        decode_burst,
        forward_paged_wave,
        init_params,
    )
    from githubrepostorag_tpu.serving.kv_cache import make_state_pools

    cfg = cell_config()
    shaped = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where), t)
    params = shaped(jax.eval_shape(lambda: init_params(cfg, 0)))
    state = shaped(jax.eval_shape(lambda: make_state_pools(cfg, SLOTS)))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    kv_shape = (cfg.kv_layers, cfg.num_kv_heads, PAGES, PAGE, cfg.head_dim)
    kp, vp = sds(kv_shape, jnp.bfloat16), sds(kv_shape, jnp.bfloat16)
    b, i32, f32 = ROWS, jnp.int32, jnp.float32
    if program == "burst":
        lowered = decode_burst.lower(
            params, cfg, sds((b,), i32), sds((b,), i32), kp, vp,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), jnp.bool_), sds((b,), i32),
            sds((b, ROW_PAGES), i32), sds((2,), jnp.uint32), sds((b,), f32), sds((b,), f32),
            sds((b,), i32), sds((b,), f32), n_steps=8, use_pallas=True, filter_sampling=False,
            first_tokens=sds((b,), i32), fresh=sds((b,), jnp.bool_), fresh_lens=sds((b,), i32),
            key_step=sds((), jnp.uint32), state=state)
    else:
        chunk, row = (rows, 512), (rows,)
        lowered = forward_paged_wave.lower(
            params, cfg, sds(chunk, i32), sds(chunk, i32), kp, vp,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), i32), sds(chunk, i32),
            sds((rows, ROW_PAGES), i32), sds(row, i32), sds(row, i32), sds(row, i32),
            sds(row, i32), sds(row, jnp.bool_), sds((), i32), sds((2,), jnp.uint32),
            sds((), jnp.uint32), sds((b,), f32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
            use_pallas=True, state=state, state_src=sds(row, i32), state_dst=sds(row, i32),
            state_snap=sds(row, i32), snap_col=sds(row, i32))
    pools = {"kv": kv_shape, "s": state["s"].shape, "conv": state["conv"].shape,
             "e_wgu": params["moe"]["e_wgu"].shape, "e_wd": params["moe"]["e_wd"].shape}
    return lowered.compile().as_text(), pools


TIMED = ("fusion", "copy", "custom-call", "dynamic-slice", "dynamic-update-slice")
_HEAD = re.compile(r"^(ENTRY )?%?(\S+) \(.*\) -> .* \{$")


def timed_lines(hlo: str, opcodes=TIMED):
    """The instructions a trace times: fusions, copies, slices and custom
    calls of the entry and loop computations, not the instructions fused
    into them."""
    fused = False
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            fused = head.group(2).startswith("fused_")
        if not fused and re.search(rf" ({'|'.join(opcodes)})\(", line):
            yield line.strip().removeprefix("ROOT ")


def timed_ops(hlo: str):
    """(name as a trace shows it, the scope it was traced under or '') of what
    a trace times."""
    from benchmarks.trace import short_name

    for line in timed_lines(hlo):
        path = re.search(r'op_name="([^"]*)"', line)
        scope = next((s for s in SCOPES if path and f"/{s}/" in path.group(1) + "/"), "")
        yield short_name(line)[0], scope


def written_out(hlo: str) -> str:
    """The optimized HLO less the fused computations that another fusion calls:
    what is left writes its result out.  A layer's slice of a weight stack
    inside the fusion of the product that reads it is how every stack is read
    in place; the same slice as a fusion of its own is 50 MB written a layer."""
    name, bodies = None, {}
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(2)
        bodies.setdefault(name, []).append(line)
    inner = {callee for name, lines in bodies.items() if name and name.startswith("fused_")
             for line in lines for callee in re.findall(r"calls=%?([\w.\-]+)", line)}
    return "\n".join(line for name, lines in bodies.items() if name not in inner for line in lines)


def timed_ops_rewriting(hlo: str, leaf) -> list:
    """Timed ops (plain slices too) that take the weight stack ``leaf`` or a
    layer of it and give the stack or a layer of it, as their result or as an
    element of a tuple result.  Not memory-space assignment's prefetches of a
    stack into VMEM (``ConcatBitcast`` over ``slice-done``): they read what
    the product is about to read."""
    dtype = {"bfloat16": "bf16", "float32": "f32"}[str(leaf.dtype)]
    forms = {",".join(map(str, d)) for d in (leaf.shape, (1, *leaf.shape[1:]), leaf.shape[1:])}
    holds = lambda types: any(  # noqa: E731
        d in forms for d in re.findall(rf"\b{dtype}\[([\d,]+)\]", types))
    instr = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) [a-z][a-z\-]*\(")
    typed = {m.group(1): m.group(2) for m in map(instr.match, hlo.splitlines()) if m}
    found, opcodes = [], (*TIMED, "slice")
    for line in timed_lines(hlo, opcodes):
        operands = re.search(rf" (?:{'|'.join(opcodes)})\(([^)]*)\)", line).group(1)
        if "ConcatBitcast" not in line and holds(typed[line.split(" = ", 1)[0]]) and holds(
                " ".join(typed.get(name, "") for name in re.findall(r"%[\w.\-]+", operands))):
            found.append(line[:160])
    return found


@pytest.mark.parametrize("program,rows,writes", [
    # a Gated DeltaNet layer: one write of its rows of history; the STATE is the kernel's alone
    pytest.param("burst", 0, {"s": 0, "conv": 6}, id="burst"),
    # a row: its state after the chunk, its snapshot
    pytest.param("wave", 1, {"s": 6, "conv": 6}, id="wave-1x512"),
    pytest.param("wave", 8, {"s": 48, "conv": 48}, id="wave-8x512"),
])
def test_step_program_leaves_pools_and_experts_in_place(chip, as_on_chip, program, rows, writes):
    hlo, pools = compiled(chip, program, rows)
    assert "tpu_custom_call" in hlo  # the paged kernel of the burst, or of the prefill
    assert pool_movers(hlo, pools["kv"], windows=False) == []  # written a window of slots at a time
    for name in ("e_wgu", "e_wd"):
        assert pool_movers(hlo, pools[name]) == [], name
    for name in ("s", "conv"):  # written in place, a slot (the burst: its rows) at a time
        movers = pool_movers(hlo, pools[name])
        assert all(m.startswith("dynamic_update_slice") for m in movers), (name, movers)
        assert len(movers) == writes[name], (name, movers)
    if program == "burst":
        # a Gated DeltaNet layer's rule is ONE call (PR 56, as Olmo-Hybrid's since PR 49): the state
        # pool goes in whole and comes out as the same buffer (ops/pallas_state.py), and no array
        # of all 32 rows' states exists
        calls = [ln for ln in timed_lines(hlo, ("custom-call",)) if "/gdn_recurrent/" in ln]
        assert_calls_step_pool_in_place(calls, "f32[6,97,32,128,128]")
        assert len(calls) == 6, [c[:120] for c in calls]
        assert "f32[32,32,128,128]" not in hlo


@pytest.mark.parametrize("program,rows", COMMIT_CASES)
def test_step_program_commits_keys_and_values_as_windows_in_place(chip, as_on_chip, program, rows):
    """models/hybrid.py's wave and burst tell ``commit_paged`` that their slots
    are runs (PR 43): a commit here is 2 layers x 2 kv heads of 256 (a burst's
    window 33 KB, a wave's 131 KB a layer)."""
    hlo, pools = compiled(chip, program, rows)
    assert_commits_windows_in_place(hlo, pools["kv"], program, rows)
    if program == "burst":
        # the loop over the row slots' runs (the window plan once, 12 instructions a pool's
        # iteration): 539 timed instructions (511 until the rule became a kernel, PR 56: a layer's
        # two passes are one call, and k | q turned on their side in VMEM, their sum, exp(g) and
        # the dead rows' mask are small ops of their own, 28 more) where the row form's two
        # scatters and their indices made it 485; a commit that unrolls its windows, or plans
        # them twice, shows here
        assert len(list(timed_lines(hlo))) <= 539


@pytest.mark.parametrize("program,rows,layers_written", [
    pytest.param("burst", 0, 0, id="burst"),
    # the chunk's two products share one slice of the leaf: ONE fusion a period writes its three
    # layers out (50 MB each), where PR 34's wave also transposed the whole stack once a wave
    pytest.param("wave", 8, 1, id="wave-8x512"),
    # with rungs (one and two rows) every branch still wants its layer transposed: the stack is
    # copied once a wave and a layer written out a rung, as in PR 34's program.  The form that
    # reads it in place (one product, cut after it) hangs the chip (PERF.md, Findings, PR 35)
    pytest.param("wave", 1, 0, id="wave-1x512", marks=pytest.mark.xfail(
        strict=True, reason="the one-row wave still copies w_qkvz: PERF.md section 7")),
])
def test_step_program_reads_the_gated_deltanet_stacks_where_they_lie(chip, as_on_chip, program,
                                                                     rows, layers_written):
    """``w_qkvz`` (302 MB) and ``w_out`` (101 MB) are stored as their products
    read them.  With ``w_qkvz``'s columns as published (grouped by key head,
    regrouped after the product) the compiler wanted the contraction axis
    minor: it transposed the stack once a burst and a wave in ``main``
    (``copy.547``, ``copy.2207``), and wrote every layer's 50 MB out again
    inside the loops: all six a step of the burst, as one fusion with a tuple of
    six results (``fusion.1225``), and a ``constant_dynamic-slice_fusion`` a
    layer of the wave (PERF.md, Findings, PR 35)."""
    from githubrepostorag_tpu.models.qwen3_next import init_params

    hlo, _ = compiled(chip, program, rows)
    gdn = jax.eval_shape(lambda: init_params(cell_config(), 0))["gdn"]
    # a stack a layer of which is over 1 MB; ``w_ba`` (1.6 MB the stack) is not one
    stacks = {k: v for k, v in gdn.items()
              if math.prod(v.shape[1:]) * v.dtype.itemsize > 2 ** 20}
    assert set(stacks) == {"w_qkvz", "w_out"}
    assert timed_ops_rewriting(hlo, stacks["w_out"]) == []
    rewriting = timed_ops_rewriting(hlo, stacks["w_qkvz"])
    whole = " = bf16[" + ",".join(map(str, stacks["w_qkvz"].shape)) + "]"  # a copy of the stack
    assert [op for op in rewriting if whole in op] == []
    assert len(rewriting) == layers_written, rewriting
    if not layers_written:
        # 12,288 columns are no activation's: every merged form of the stack counts.
        # (``w_out``'s layer is 4,096 x 2,048, which eight rows of 512 columns are too.)
        assert pool_movers(written_out(hlo), stacks["w_qkvz"].shape) == []


def test_the_wave_is_one_program_that_donates_every_pool_and_keeps_them_out_of_its_switches(
        chip, as_on_chip):
    hlo, _ = compiled(chip, "wave", 1)
    held = (rf"bf16\[2,2,{PAGES},{PAGE},256\]|f32\[6,{SLOTS},32,128,128\]|bf16\[6,{SLOTS},24576\]"
            rf"|pred\[{ROWS},37984\]")
    assert_wave_keeps_in_place(hlo, held, 5)
    switches = [ln for ln in hlo.splitlines() if re.search(r" conditional\(", ln)
                and len(re.search(r"branch_computations=\{([^}]*)\}", ln).group(1).split(",")) == 3]
    assert len(switches) >= 5  # a layer at 512 / 256 / 128 columns: 3 + 2 switches a period
    for ln in switches:  # a pool a branch is handed is copied into it and out of it
        assert not re.search(r"\[(2,2,2048,128,256|6,97,32,128,128|6,97,24576)\]", ln), ln[:200]


def _picked(hlo, pattern):
    by_scope = {}
    for name, scope in timed_ops(hlo):
        if pattern.search(name):
            by_scope.setdefault(scope, set()).add(name)
    return by_scope


def test_the_metrics_select_the_ops_under_their_scopes(chip, as_on_chip):
    """A trace's device plane names instructions, not scopes, so the metrics
    find their ops by name and output shape; the compiled programs' own
    metadata says which scope each came from."""
    from benchmarks import manifest
    from benchmarks.families import qwen3_next as family

    cell = manifest.load_cell("qwen3-next-80b-a3b-ep4-bf16.repo-sessions")
    model = family.model_of(cell.config, rehearse=False)
    burst, _ = compiled(chip, "burst", 0)
    wave, _ = compiled(chip, "wave", 1)
    spec = lambda name: manifest.metric_spec(name)["args"]  # noqa: E731

    experts = re.compile(spec("moe_experts_hbm_frac")["op"].format(
        **family.expert_op_sizes(model, cell.config)))
    got = _picked(burst, experts)
    assert set(got) == {"moe_experts"} and len(got["moe_experts"]) == 8  # a call a layer
    assert_hit_experts_are_one_walk(
        list(timed_ops(burst)), timed_lines(wave, ("custom-call",)), 8, 32, 2048, 1024,
        ((2048, 1024), (512, 2048)), experts)
    assert _picked(wave, experts) == {}
    # the shared expert is 512 wide too: its products keep three axes and are not picked
    assert any(re.search(r"_bf16_32_1_1024_$", n) for n, s in timed_ops(burst) if s == "moe_shared")

    # the one-token rule, a layer and step: ONE call of the kernel (PR 56), named for its scope and
    # for its FIRST result (o, [32, 32, 128]: the pool is its second), picked by the accepted
    # pattern's arm for the scope; XLA's two passes over all 32 row slots, which its other arm
    # names, are gone, and the pattern picks nothing outside the scope (the mask of the dead rows'
    # o is ``select_multiply_fusion``, not ``fusion``, and k | q on their side are copies)
    rule = re.compile(spec("gdn_decode_roofline_frac")["op"])
    decode = _picked(burst, rule)
    assert set(decode) == {"gdn_recurrent"} and len(decode["gdn_recurrent"]) == 6  # a layer's call
    names = [re.sub(r"\.\d+", "", n) for n in decode["gdn_recurrent"]]
    assert names == ["gdn_recurrent_f32_32_32_128_"] * 6, names
    # what the scope holds besides is per-head vectors ([32, 32]: exp(g), sum(k q)), a
    # thousandth of the rows' state, and nothing of state size
    rest = {n for n, scope in timed_ops(burst) if scope == "gdn_recurrent"} - decode["gdn_recurrent"]
    assert rest and all(n.endswith("_f32_32_32_") for n in rest), rest
    assert _picked(wave, rule) == {}

    chunked = re.compile(spec("gdn_prefill_roofline_frac")["op"].format(
        **family.state_op_sizes(model, cell.config)))
    got = _picked(wave, chunked)
    assert "gdn_chunked" in got and set(got) <= {"gdn_chunked", "state_read", ""}
    assert _picked(burst, chunked) == {}

    moves = re.compile(spec("state_pool_move_share")["pattern"])
    assert set(_picked(wave, moves)) <= {"state_write"}  # the in-place row writes, nothing else
    # the kernel computes, it does not move the pool: its name ends in o's shape, not the pool's;
    # what is left of the burst is the histories' rows written back
    assert set(_picked(burst, moves)) <= {"gdn_conv", ""}

    # the burst's attention kernel is named for its scope, where the accepted metric looks
    paged = re.compile(manifest.metric_spec("paged_attn_hbm_frac")["args"]["op"])
    names = {n for n, _ in timed_ops(burst) if paged.search(n)}
    assert names and all(n.startswith("paged_attention") for n in names)


def test_the_burst_keeps_the_timed_ops_it_has(chip, as_on_chip):
    """The burst's timed ops by the name a trace shows (less XLA's running
    number), counted: the parent commit's of PR 40, whose burst this model shares
    with Olmo-Hybrid (models/hybrid.py:burst), written again by PR 43 for the ops
    under ``kv_write`` and for no other (the commit as windows of slots: the two
    row scatters and their indices went), and by PR 56 for the one-token rule and
    for no other (ops/pallas_state.gated_delta_step_in_place on the pool: the
    rule's two fusions went, the kernel and its small operands came), and by
    PR 58 for the ops under ``moe_experts`` and two of the compiler's own
    prefetches (ops/pallas_experts.walk_experts in place of the one-tile loop:
    eleven kinds of op a layer went, among them the two products an expert, and
    ``moe_experts_f32_32_2048_`` came).
    The accepted ``gdn_decode_roofline_frac`` and ``moe_experts_hbm_frac`` find
    their ops by these names, so an edit made for the other hybrid that renames
    one here reads null on the chip.  A change that MEANS to move this program
    writes the file again: ``{name: count}`` of ``timed_ops(burst)``, sorted."""
    golden = pathlib.Path(__file__).parent / "golden" / "qwen3_next_burst_timed_ops.json"
    want = collections.Counter(json.loads(golden.read_text()))
    burst, _ = compiled(chip, "burst", 0)
    got = collections.Counter(re.sub(r"\.\d+", "", name) for name, _ in timed_ops(burst))
    assert got == want, {"gone": dict(want - got), "new": dict(got - want)}
    # the recurrence: one kernel a layer on the pool where it lies; XLA's two passes are gone
    assert want["gdn_recurrent_f32_32_32_128_"] == 6
    assert "fusion_f32_32_32_128_" not in want
    assert "select_dynamic-update-slice_fusion_f32_6_97_32_128_128_" not in want
    # the hit experts: one walk a layer; the loop's products of one expert are gone
    assert want["moe_experts_f32_32_2048_"] == 8
    assert "fusion_bf16_32_1024_" not in want and "fusion_f32_32_2048_" not in want
