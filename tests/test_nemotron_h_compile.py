"""Nemotron-H's two step programs compiled whole for a TPU v5e that is
described, not attached, at the shapes of the benchmark's cell
(``nemotron-3-nano-30b-a3b-ep4-bf16``: published widths, the first 18 blocks
``MEMEM*EMEMEM*EMEME``, 32 of 128 experts, 2,048 pages of 128 tokens for the 2
attention layers, 32 live + 63 snapshot + 1 slots of state for the 8 Mamba-2
layers): both paged kernels pass the chip's compiler at 32 query and 2 kv
heads of 128 (a group of 16, a shape neither had compiled at); nothing in the
optimized HLO copies, transposes or slices a K/V pool, the state pool or an
expert stack, and every array the programs are handed lies row-major as the
program reads it; and the ops that this cell's metrics pick out of a trace by
their shapes are the ops under the scopes they are meant to read.  Nothing
executes; a pass here is not a chip run.

Two things this file found, both about how a v5e STORES an array whose axes
do not fill its tiles (PR 39 met the first kind at a 192-wide axis):

* an expert's ``W_up`` as ``[.., 2688, 1856]``: 1,856 is 14.5 lane tiles, so
  the chip keeps the 2,688 on the lanes whatever order the axes are given in,
  and a burst that multiplies by it as ``[d, f]`` first copied the whole 2.5 GB
  stack, every burst.  Stored ``[f, d]`` (as published: [out, in]) it is read
  where it lies;
* the history pool as ``[8 layers, 97 slots, 18432]`` bfloat16: 97 slots pad
  to 112 sublanes and 8 layers to none, so the chip lays the LAYERS on the
  sublanes and every burst copied the pool in and out (two copies of 29 MB).
  At 96 slots (63 snapshots) neither axis pads and it stays row-major.
"""

import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from tests.test_qwen3_next_compile import timed_lines
from tests.test_tpu_compile import (  # noqa: F401 - fixtures
    COMMIT_CASES,
    assert_calls_step_pool_in_place,
    assert_commits_windows_in_place,
    assert_hit_experts_are_one_walk,
    chip,
    pool_movers,
    topo,
)

PAGES, PAGE, ROWS, ROW_PAGES, SLOTS = 2048, 128, 32, 80, 96
SCOPES = ("ssm_proj", "ssm_conv", "ssm_chunked", "ssm_recurrent", "ssm_gate_norm", "state_read",
          "state_write", "paged_attention", "kv_write", "moe_route", "moe_experts", "moe_shared",
          "sample")
CELL = "nemotron-3-nano-30b-a3b-ep4-bf16.repo-sessions"


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.hybrid as hybrid
    import githubrepostorag_tpu.ops.fused_decode as fused_decode
    import githubrepostorag_tpu.ops.latent_attention as latent
    import githubrepostorag_tpu.ops.pallas_experts as experts

    for mod in (hybrid, fused_decode, latent, experts):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def cell_config():
    from benchmarks import manifest
    from benchmarks.families import nemotron_h as family

    cell = manifest.load_cell(CELL)
    return cell, family, family.model_config(family.model_of(cell.config, rehearse=False))


@functools.lru_cache(maxsize=None)
def compiled(where, program: str, rows: int):
    """(optimized HLO, the shapes of what must stay in place) of the burst or
    of the wave at a row bucket, compiled once a module."""
    from githubrepostorag_tpu.models.nemotron_h import decode_burst, forward_paged_wave, init_params
    from githubrepostorag_tpu.serving.kv_cache import make_state_pools

    cell, _, cfg = cell_config()
    eng = cell.config["engine"]
    assert eng["max_num_seqs"] + eng["state_snapshots"] + 1 == SLOTS and eng["num_pages"] == PAGES
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
             "e_wu": params["moe"]["e_wu"].shape, "e_wd": params["moe"]["e_wd"].shape}
    return lowered.compile().as_text(), pools


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


@pytest.mark.parametrize("program,rows,writes", [
    # a Mamba-2 layer: one write of its rows of history; the STATE is the kernel's alone
    pytest.param("burst", 0, {"s": 0, "conv": 8}, id="burst"),
    # a row and traced layer: its state, its snapshot (4 M layers are traced: 3 + 1 of the two scans)
    pytest.param("wave", 1, {"s": 8, "conv": 8}, id="wave-1x512"),
    pytest.param("wave", 8, {"s": 64, "conv": 64}, id="wave-8x512"),
])
def test_step_program_leaves_both_caches_and_the_expert_stacks_in_place(
        chip, as_on_chip, program, rows, writes):
    hlo, pools = compiled(chip, program, rows)
    assert "tpu_custom_call" in hlo  # the paged kernel of the burst, or of the prefill: 32 / 2 x 128
    assert pool_movers(hlo, pools["kv"], windows=False) == []  # written a window of slots at a time
    assert pools["s"] == (8, SLOTS, 64, 64, 128) and pools["conv"] == (8, SLOTS, 3 * 6144)
    for name in ("s", "conv"):  # written in place, a slot (the burst: its rows) at a time
        movers = pool_movers(hlo, pools[name])
        assert all(m.startswith("dynamic_update_slice") for m in movers), (name, movers)
        assert len(movers) == writes[name], (name, movers)
    if program == "burst":
        # a Mamba-2 layer's rule is ONE call: the state pool goes in whole and comes out as the
        # same buffer (ops/pallas_state.py), and no array of all 32 rows' states exists anywhere
        pool = f"f32[{_dims(pools['s'])}]"
        calls = [ln for ln in timed_lines(hlo, ("custom-call",)) if "/ssm_recurrent/" in ln]
        assert_calls_step_pool_in_place(calls, pool)
        assert len(calls) == 8, [c[:120] for c in calls]
        assert f"f32[{ROWS},64,64,128]" not in hlo
    # every array lies as the program is handed it: row-major, the last axis on the lanes
    layout = hlo.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
    assert pools["e_wu"] == pools["e_wd"] == (8, 32, 1856, 2688)  # [f, d]: 2,688 = 21 lane tiles
    for dtype, name in (("f32", "s"), ("bf16", "conv"), ("bf16", "e_wu"), ("bf16", "kv")):
        shape, order = pools[name], ",".join(str(i) for i in reversed(range(len(pools[name]))))
        assert f"{dtype}[{_dims(shape)}]{{{order}:" in layout, (name, layout[:2000])
    # and nothing the size of an expert stack or a pool is copied anywhere in the program
    big = [ln for ln in timed_lines(hlo, ("copy",))
           if any(f"[{_dims(pools[k])}]" in ln.split(" copy(")[0] for k in pools)]
    assert big == [], [ln[:200] for ln in big]


@pytest.mark.parametrize("program,rows", COMMIT_CASES)
def test_step_program_commits_keys_and_values_as_windows_in_place(chip, as_on_chip, program, rows):
    """models/hybrid.py's wave and burst tell ``commit_paged`` that their slots
    are runs (PR 43): a commit here is 2 layers x 2 kv heads of 128 (a burst's
    window 16 KB, a wave's 66 KB a layer), in the one scan of the two that
    holds the attention layers."""
    hlo, pools = compiled(chip, program, rows)
    assert_commits_windows_in_place(hlo, pools["kv"], program, rows)
    if program == "burst":
        # the loop over the row slots' runs (the window plan once, 12 instructions a pool's
        # iteration): 527 timed instructions where the row form's two scatters and their indices
        # made it 501; a commit that unrolls its windows, or plans them twice, shows here
        assert len(list(timed_lines(hlo))) <= 527


def test_the_wave_scans_where_the_pattern_repeats():
    """18 blocks compile as 9: ``MEMEM*E`` twice, then ``ME`` twice (the cold
    compile is the pattern's, not the depth's)."""
    _, _, cfg = cell_config()
    assert cfg.pattern == "MEMEM*EMEMEM*EMEME"
    assert cfg.layer_segments == (("RFRFRAF", 2), ("RF", 2))
    assert (cfg.state_layers, cfg.expert_layers, cfg.kv_layers) == (8, 8, 2)


def test_this_cells_metrics_select_the_ops_under_their_scopes(chip, as_on_chip):
    """A trace's device plane names instructions, not scopes, so the metrics
    find their ops by name and output shape; the compiled programs' own
    metadata says which scope each came from."""
    from benchmarks import manifest

    cell, family, _ = cell_config()
    model = family.model_of(cell.config, rehearse=False)
    burst, _ = compiled(chip, "burst", 0)
    waves = [compiled(chip, "wave", rows)[0] for rows in (1, 8)]
    spec = lambda name: manifest.metric_spec(name)["args"]  # noqa: E731

    # the one-token rule, a layer and step: ONE call of the kernel, named for its scope and for its
    # FIRST result (y, [32, 64, 64]: the pool is its second), and the copies that turn a row's
    # [heads, width] on its side for it and back (x on its way in, y on its way out, and the gate
    # beside y: XLA files the first under no scope and the last under the projection that made
    # it).  All of them, and nothing else of either program
    rule = re.compile(spec("ssm_decode_roofline_frac")["op"])
    decode = _picked(burst, rule)
    assert set(decode) == {"ssm_recurrent", "ssm_proj", ""}
    for scope in ("ssm_proj", ""):
        assert len(decode[scope]) == 8 and all(n.startswith("copy.") for n in decode[scope])
    names = [re.sub(r"\.\d+", "", n) for n in decode["ssm_recurrent"]]
    assert names.count("ssm_recurrent_f32_32_64_64_") == 8, names
    # what XLA stepped every row slot with is gone: S C read off the pool, the update in place
    assert not any(n.startswith(("multiply_reduce_fusion_f32_32_64_64_", "select_dynamic-update-slice"))
                   for n in names), names
    in_scope = {n for n, scope in timed_ops(burst) if scope == "ssm_recurrent"}
    rest = in_scope - decode["ssm_recurrent"]
    # what the scope holds besides is a head's or a group's scalars ([32, 64] and stacks of it,
    # [32, 8], [64]): every op of a row's size ([32, 64, 64]) or more is in the seconds
    assert all(re.search(r"_f32_(32_64|32_[14]_64|32_8|1_64|64)_$", n) for n in rest), rest
    for wave in waves:
        assert _picked(wave, rule) == {}
    # a kernel named after the scope is read (it is a custom call in a trace), an unnamed one is not
    assert rule.search("ssm_recurrent.3") and not rule.search("custom-call.2_f32_32_64_64_128_")

    chunked = re.compile(spec("ssm_prefill_roofline_frac")["op"].format(
        **family.state_op_sizes(model, cell.config)))
    for wave in waves:
        got = _picked(wave, chunked)
        assert "ssm_chunked" in got
        # beside the scope's own: the rows of state on their way in (read, stacked, selected) and
        # the transposes of x into blocks and of y out of them, which XLA files under the
        # projection and the norm that made or take them
        assert set(got) <= {"ssm_chunked", "state_read", "ssm_proj", "ssm_gate_norm", ""}
        for scope in ("ssm_proj", "ssm_gate_norm"):
            assert all(n.startswith("copy") for n in got.get(scope, ())), got[scope]
        # and of the scope itself everything of a block's size or more is in the seconds (a
        # custom call there is the compiler's buffer, not a kernel: no time in a trace)
        missed = {n for n, s in timed_ops(wave)
                  if s == "ssm_chunked" and not n.startswith("custom-call")} - got["ssm_chunked"]
        sized = lambda n: math.prod(map(int, re.findall(r"\d+", n.split("_f32_")[-1])))  # noqa: E731
        assert all("_f32_" not in n or sized(n) <= 2 * 512 * 4096 for n in missed), missed
    assert _picked(burst, chunked) == {}
    assert chunked.search("ssm_chunked.7")

    moves = re.compile(spec("ssm_state_pool_move_share")["pattern"])
    for wave in waves:
        assert set(_picked(wave, moves)) == {"state_write"}  # the in-place row writes, nothing else
    # the kernel computes, it does not move the pool: its name ends in S C's shape, not the pool's
    # (had the pool been its first result, ``ssm_recurrent.N_f32_8_96_64_64_128_`` would be a move)
    assert _picked(burst, moves) == {}
    assert moves.search("ssm_recurrent.80_f32_8_96_64_64_128_")
    assert not any(moves.search(n) for n in in_scope)

    # the accepted experts' metric finds the burst's walk over the hit experts, a call a layer,
    # and only them; both stacks are read by rows, [1856, 2688] as stored, and never copied
    experts = re.compile(manifest.metric_spec("moe_experts_hbm_frac")["args"]["op"].format(
        **family.expert_op_sizes(model, cell.config)))
    got = _picked(burst, experts)
    assert set(got) == {"moe_experts"} and len(got["moe_experts"]) == 8
    assert_hit_experts_are_one_walk(
        list(timed_ops(burst)), timed_lines(waves[0], ("custom-call",)), 8, 32, 2688, 1856,
        ((1856, 2688),), experts)
    for wave in waves:
        assert _picked(wave, experts) == {}
    # the burst's attention kernel is named for its scope, where the accepted metric looks
    paged = re.compile(manifest.metric_spec("paged_attn_hbm_frac")["args"]["op"])
    names = {n for n, _ in timed_ops(burst) if paged.search(n)}
    assert names and all(n.startswith("paged_attention") for n in names)
    # the scopes this model adds name ops of both programs
    assert {"ssm_proj", "ssm_conv", "ssm_recurrent", "ssm_gate_norm", "moe_experts"} <= {
        scope for _, scope in timed_ops(burst)}
    for wave in waves:
        assert {"ssm_proj", "ssm_conv", "ssm_chunked", "ssm_gate_norm", "state_read",
                "state_write", "moe_experts", "moe_shared"} <= {s for _, s in timed_ops(wave)}
