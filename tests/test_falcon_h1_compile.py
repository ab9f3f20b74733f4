"""Falcon-H1's decode burst and its one-row prefill wave compiled whole for a
TPU v5e that is described, not attached, at the shapes of the benchmark's cell
(``falcon-h1-34b-bf16``: published widths, 4 layers that EACH keep pages and a
slot of state, 1,280 pages of 128 tokens, 32 live + 63 snapshot + 1 slots of
4.2 MB a layer): the forms, not every row bucket (ROADMAP D23).  Both paged
kernels pass the chip's compiler at 20 query and 4 kv heads of 128 (a group of
5, a shape neither had compiled at) and the state kernel at heads of 128 x 256
(blocks of 8 heads, 1 MB); nothing in the optimized HLO copies, transposes or
slices a K/V pool or either state pool, every pool lies row-major as the
program reads it, and the ops that this cell's metrics pick out of a trace by
their names are the ops under the scopes they are meant to read.  Nothing
executes; a pass here is not a chip run.

What this file found: with the issue's 64 snapshots the bfloat16 history pool
is ``[4 layers, 97 slots, 15360]``; 97 slots pad to 112 sublanes and 4 layers
to none, so the chip lays the LAYERS on the sublanes and both programs copy
the pool in and out (11.9 MB each way, a burst and a wave).  At 96 slots (63
snapshots, as Nemotron-H's cell needed) neither axis pads.
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
    assert_calls_step_pool_in_place,
    assert_commits_windows_in_place,
    chip,
    pool_movers,
    topo,
)

PAGES, PAGE, ROWS, ROW_PAGES, SLOTS, LAYERS = 1280, 128, 32, 80, 96, 4
STATE_SCOPES = ("ssm_proj", "ssm_conv", "ssm_chunked", "ssm_recurrent", "ssm_gate_norm",
                "state_read", "state_write")
ATTN_SCOPES = ("attn_proj", "paged_attention", "kv_write")
SCOPES = (*STATE_SCOPES, *ATTN_SCOPES, "branch_sum", "dense_mlp", "sample")
CELL = "falcon-h1-34b-bf16.repo-sessions"
PROGRAMS = [pytest.param("burst", 0, id="burst"), pytest.param("wave", 1, id="wave-1x512")]


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.hybrid as hybrid
    import githubrepostorag_tpu.ops.fused_decode as fused_decode
    import githubrepostorag_tpu.ops.latent_attention as latent

    for mod in (hybrid, fused_decode, latent):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def cell_config():
    from benchmarks import manifest
    from benchmarks.families import falcon_h1 as family

    cell = manifest.load_cell(CELL)
    return cell, family, family.model_config(family.model_of(cell.config, rehearse=False))


@functools.lru_cache(maxsize=None)
def compiled(where, program: str, rows: int):
    """(optimized HLO, the shapes of what must stay in place) of the burst or
    of the wave at a row bucket, compiled once a module."""
    from githubrepostorag_tpu.models.falcon_h1 import decode_burst, forward_paged_wave, init_params
    from githubrepostorag_tpu.serving.kv_cache import make_state_pools

    cell, _, cfg = cell_config()
    eng = cell.config["engine"]
    assert eng["max_num_seqs"] + eng["state_snapshots"] + 1 == SLOTS and eng["num_pages"] == PAGES
    assert cfg.kv_layers == cfg.state_layers == cfg.num_layers == LAYERS
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
             "w_gate": params["mlp"]["w_gate"].shape, "lm_head": params["lm_head"].shape}
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


@pytest.mark.parametrize("program,rows", PROGRAMS)
def test_step_program_leaves_pages_and_state_of_every_layer_in_place(
        chip, as_on_chip, program, rows):
    hlo, pools = compiled(chip, program, rows)
    assert "tpu_custom_call" in hlo  # the paged kernel of the burst, or of the prefill: 20 / 4 x 128
    assert pool_movers(hlo, pools["kv"], windows=False) == []  # written a window of slots at a time
    assert pools["s"] == (LAYERS, SLOTS, 32, 128, 256)
    assert pools["conv"] == (LAYERS, SLOTS, 3 * 5120)
    # written in place: the burst its rows of history a layer (the STATE is the kernel's alone);
    # the wave a row's state and its snapshot, history and its snapshot, for the ONE traced layer
    writes = {"s": 0, "conv": LAYERS} if program == "burst" else {"s": 2, "conv": 2}
    for name in ("s", "conv"):
        movers = pool_movers(hlo, pools[name])
        assert all(m.startswith("dynamic_update_slice") for m in movers), (name, movers)
        assert len(movers) == writes[name], (name, movers)
    if program == "burst":
        # a layer's rule is ONE call under its scope: the state pool goes in whole and comes out
        # as the same buffer (ops/pallas_state.py at blocks of 8 heads of 128 KB), and no array
        # of all 32 rows' states exists anywhere
        pool = f"f32[{_dims(pools['s'])}]"
        calls = [ln for ln in timed_lines(hlo, ("custom-call",)) if "/ssm_recurrent/" in ln]
        assert_calls_step_pool_in_place(calls, pool)
        assert len(calls) == LAYERS, [c[:120] for c in calls]
        assert f"f32[{ROWS},32,128,256]" not in hlo
    # every pool lies as the program is handed it: row-major, the last axis on the lanes
    layout = hlo.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
    for dtype, name in (("f32", "s"), ("bf16", "conv"), ("bf16", "kv"), ("bf16", "w_gate"),
                        ("bf16", "lm_head")):
        shape, order = pools[name], ",".join(str(i) for i in reversed(range(len(pools[name]))))
        assert f"{dtype}[{_dims(shape)}]{{{order}:" in layout, (name, layout[:2000])
    # and nothing the size of a pool, of a layer's feed-forward or of the head is copied
    big = [ln for ln in timed_lines(hlo, ("copy",))
           if any(f"[{_dims(pools[k])}]" in ln.split(" copy(")[0] for k in pools)
           or re.search(r"\[(5120,21504|21504,5120)\]", ln.split(" copy(")[0])]
    assert big == [], [ln[:200] for ln in big]


@pytest.mark.parametrize("program,rows", PROGRAMS)
def test_step_program_commits_keys_and_values_as_windows_in_place(chip, as_on_chip, program, rows):
    """The new family's K/V commit through models/hybrid.py's wave and burst:
    4 layers x 4 kv heads of 128, a run's aligned windows of slots, in place
    (the same guard the three older hybrids' commits pass)."""
    hlo, pools = compiled(chip, program, rows)
    assert_commits_windows_in_place(hlo, pools["kv"], program, rows)


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

    # the one-token rule, a layer and step: ONE call of the kernel, named for its scope and its
    # FIRST result (y, [32, 128, 32]: the pool is its second), and the [32, 32, 128] copies that
    # turn a row's [heads, width] on its side for it and back.  Nothing of the wave
    rule = re.compile(spec("falcon_ssm_decode_roofline_frac")["op"])
    decode = _picked(burst, rule)
    assert set(decode) <= {"ssm_recurrent", "ssm_proj", ""} and "ssm_recurrent" in decode
    names = [re.sub(r"\.\d+", "", n) for n in decode["ssm_recurrent"]]
    assert names.count("ssm_recurrent_f32_32_128_32_") == LAYERS, names
    for scope in ("ssm_proj", ""):
        assert all(n.startswith("copy.") for n in decode.get(scope, ())), decode[scope]
    in_scope = {n for n, scope in timed_ops(burst) if scope == "ssm_recurrent"}
    rest = in_scope - decode["ssm_recurrent"]
    # what the scope holds besides is a head's or a group's scalars: every op of a row's size
    # ([32, 32, 128]) or more is in the seconds
    assert all(re.search(r"_f32_(32_[0-9]+_32|32_2|32_32)_$", n) for n in rest), rest
    assert _picked(wave, rule) == {}
    assert rule.search("ssm_recurrent.3") and not rule.search("custom-call.2_f32_32_32_128_256_")

    chunked = re.compile(spec("falcon_ssm_prefill_roofline_frac")["op"].format(
        **family.state_op_sizes(model, cell.config)))
    got = _picked(wave, chunked)
    assert "ssm_chunked" in got
    # beside the scope's own: the rows of state on their way in and out, and the transposes of x
    # into blocks and of y out of them, which XLA files under the scope that made or takes them
    assert set(got) <= {"ssm_chunked", "state_read", "state_write", "ssm_proj", "ssm_gate_norm", ""}
    assert _picked(burst, chunked) == {} and chunked.search("ssm_chunked.7")

    moves = re.compile(spec("falcon_state_pool_move_share")["pattern"])
    assert set(_picked(wave, moves)) == {"state_write"}  # the in-place row writes, nothing else
    # the kernel computes, it does not move the pool: its name ends in y's shape, not the pool's;
    # the burst's shift of a layer's rows of history computes too (``select_``)
    assert _picked(burst, moves) == {}
    assert moves.search("dynamic_update_slice.8_bf16_4_96_15360_")

    # which branch of the parallel block sets the pace: each share's ops lie under its branch's
    # scopes (or under none: a copy XLA files nowhere), in both programs, and the two never meet
    ssm = re.compile(spec("falcon_ssm_branch_share")["pattern"])
    attn = re.compile(spec("falcon_attn_branch_share")["pattern"])
    for hlo in (burst, wave):
        mine, theirs = _picked(hlo, ssm), _picked(hlo, attn)
        assert set(mine) <= {*STATE_SCOPES, ""} and set(theirs) <= {*ATTN_SCOPES, ""}
        assert not set().union(*mine.values()) & set().union(*theirs.values())
        for scope in ("ssm_proj", "ssm_conv", "ssm_gate_norm"):
            assert scope in mine, (scope, sorted(mine))
        for scope in ATTN_SCOPES:
            assert scope in theirs, (scope, sorted(theirs))
    assert "ssm_recurrent" in _picked(burst, ssm) and "ssm_chunked" in _picked(wave, ssm)
    # the accepted paged-attention metric finds the burst's kernel under its scope
    paged = re.compile(manifest.metric_spec("paged_attn_hbm_frac")["args"]["op"])
    names = {n for n, _ in timed_ops(burst) if paged.search(n)}
    assert names and all(n.startswith("paged_attention") for n in names)
    # the scopes this model adds or shares name ops of both programs
    assert {"ssm_proj", "ssm_conv", "ssm_recurrent", "ssm_gate_norm", "attn_proj", "kv_write",
            "paged_attention", "dense_mlp"} <= {scope for _, scope in timed_ops(burst)}
    assert {"ssm_proj", "ssm_conv", "ssm_chunked", "ssm_gate_norm", "state_read", "state_write",
            "attn_proj", "kv_write", "paged_attention", "dense_mlp"} <= {
        s for _, s in timed_ops(wave)}
