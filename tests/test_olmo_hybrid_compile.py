"""Olmo-Hybrid's two step programs compiled whole for a TPU v5e that is
described, not attached, at the shapes of the benchmark's cell
(``olmo-hybrid-7b-bf16``: published widths, two periods, 1,280 pages of 128
tokens for the 2 multi-head attention layers, 32 live + 64 snapshot + 1 slots
of state for the 6 Gated DeltaNet layers): both paged kernels pass the chip's
compiler at 30 kv heads of 128 with a group of one (the burst's takes the
heads 6 at a time); nothing in the optimized HLO copies, transposes or slices
a K/V pool or the state pool (stored with its value axis at 256 lanes: at 192
the compiler lays the SLOTS on the lanes and every program copies 1.7 GB in
and out); and the ops that this cell's three metrics pick out of a trace by
their shapes are the ops under the scopes they are meant to read.  Nothing
executes; a pass here is not a chip run.
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
    COMMIT_CASES,
    assert_calls_step_pool_in_place,
    assert_commits_windows_in_place,
    chip,
    pool_movers,
    topo,
)

PAGES, PAGE, ROWS, ROW_PAGES, SLOTS = 1280, 128, 32, 80, 97
SCOPES = ("gdn_proj", "gdn_conv", "gdn_chunked", "gdn_recurrent", "gdn_gate_norm", "qk_norm",
          "dense_mlp", "state_read", "state_write", "paged_attention", "kv_write", "sample")
CELL = "olmo-hybrid-7b-bf16.repo-sessions"


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.hybrid as hybrid
    import githubrepostorag_tpu.ops.fused_decode as fused_decode
    import githubrepostorag_tpu.ops.latent_attention as latent

    for mod in (hybrid, fused_decode, latent):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


@functools.lru_cache(maxsize=None)
def compiled(where, program: str, rows: int):
    """(optimized HLO, the shapes of what must stay in place) of the burst or
    of the wave at a row bucket, compiled once a module."""
    from githubrepostorag_tpu.models import olmo_hybrid as model
    from githubrepostorag_tpu.models.olmo_hybrid import (
        OlmoHybridConfig,
        decode_burst,
        forward_paged_wave,
        init_params,
    )
    from githubrepostorag_tpu.serving.kv_cache import make_state_pools

    cfg = OlmoHybridConfig(num_layers=8)
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
    pools = {"kv": kv_shape, "s": state["s"].shape, "conv": state["conv"].shape}
    # the options the chip's compiler is given (a process pinned to the CPU passes none itself)
    options = None if program == "burst" else model.WAVE_COMPILER_OPTIONS
    return lowered.compile(compiler_options=options).as_text(), pools


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


@pytest.mark.parametrize("program,rows,writes", [
    # a Gated DeltaNet layer: one write of its rows of history; the STATE is the kernel's alone
    pytest.param("burst", 0, {"s": 0, "conv": 6}, id="burst"),
    # a row: its state after the chunk, its snapshot
    pytest.param("wave", 1, {"s": 6, "conv": 6}, id="wave-1x512"),
    pytest.param("wave", 4, {"s": 24, "conv": 24}, id="wave-4x512"),
])
def test_step_program_leaves_both_caches_in_place(chip, as_on_chip, program, rows, writes):
    hlo, pools = compiled(chip, program, rows)
    assert "tpu_custom_call" in hlo  # the paged kernel of the burst, or of the prefill
    assert pool_movers(hlo, pools["kv"], windows=False) == []  # written a window of slots at a time
    assert pools["s"] == (6, SLOTS, 30, 96, 256)  # the value axis at a whole number of lane tiles
    for name in ("s", "conv"):  # written in place, a slot (the burst: its rows) at a time
        movers = pool_movers(hlo, pools[name])
        assert all(m.startswith("dynamic_update_slice") for m in movers), (name, movers)
        assert len(movers) == writes[name], (name, movers)
    if program == "burst":
        # a Gated DeltaNet layer's rule is ONE call (PR 49): the state pool goes in whole and comes
        # out as the same buffer (ops/pallas_state.py), and no array of all 32 rows' states exists
        calls = [ln for ln in timed_lines(hlo, ("custom-call",)) if "/gdn_recurrent/" in ln]
        assert_calls_step_pool_in_place(calls, "f32[6,97,30,96,256]")
        assert len(calls) == 6, [c[:120] for c in calls]
        assert "f32[32,30,96,256]" not in hlo and "f32[32,30,96,192]" not in hlo
    # a wave is compiled without the memory-space assignment: none of its arrays lives in VMEM
    assert ("S(1)" in hlo) == (program == "burst")


@pytest.mark.parametrize("program,rows", COMMIT_CASES)
def test_step_program_commits_keys_and_values_as_windows_in_place(chip, as_on_chip, program, rows):
    """models/hybrid.py's wave and burst tell ``commit_paged`` that their slots
    are runs (PR 43): the scatter of 15,360 rows a pool is gone from the burst
    (32 runs of 8 steps, each 2 windows of 16 slots over both layers and all
    30 heads: 245 KB) and from every wave (a row: 5 windows of 128 slots, 983
    KB a layer), the four-row wave's through the loop, under
    ``WAVE_COMPILER_OPTIONS`` like the rest of it."""
    hlo, pools = compiled(chip, program, rows)
    assert_commits_windows_in_place(hlo, pools["kv"], program, rows)
    if program == "burst":
        # the loop over the row slots' runs (the window plan once, 12 instructions a pool's
        # iteration): 262 timed instructions (261 until the rule became a kernel, PR 49: a layer's
        # slice, sums and update are one call now, and k | q turned on their side and the dead
        # rows' mask two more) where the row form's two scatters and their indices made it 232; a
        # commit that unrolls its windows, or plans them twice, shows here
        assert len(list(timed_lines(hlo))) <= 262


def test_this_cells_metrics_select_the_ops_under_their_scopes(chip, as_on_chip):
    """A trace's device plane names instructions, not scopes, so the metrics
    find their ops by name and output shape; the compiled programs' own
    metadata says which scope each came from."""
    from benchmarks import manifest
    from benchmarks.families import olmo_hybrid as family

    cell = manifest.load_cell(CELL)
    model = family.model_of(cell.config, rehearse=False)
    burst, _ = compiled(chip, "burst", 0)
    wave, _ = compiled(chip, "wave", 1)
    spec = lambda name: manifest.metric_spec(name)["args"]  # noqa: E731

    # the one-token rule, a layer and step: ONE call of the kernel (PR 49), named for its scope and
    # for its FIRST result (o, [32, 30, 192]: the pool is its second), so the pattern that read
    # XLA's three passes over all 32 row slots (PR 40: the rows sliced out of the pool into VMEM,
    # S^T k | S^T q, one update written in place) reads it under the same name; beside it the
    # mask of the dead rows' o, which XLA fuses into the gate norm's first product: 0.7 MB
    rule = re.compile(spec("olmo_gdn_decode_roofline_frac")["op"])
    decode = _picked(burst, rule)
    assert set(decode) == {"gdn_recurrent", "gdn_gate_norm"}
    names = [re.sub(r"\.\d+", "", n) for n in decode["gdn_recurrent"]]
    assert names == ["gdn_recurrent_f32_32_30_192_"] * 6, names
    assert len(decode["gdn_gate_norm"]) == 6
    assert all(n.startswith("select_multiply_fusion") and n.endswith("_f32_32_30_192_")
               for n in decode["gdn_gate_norm"])
    timed = list(timed_lines(burst))
    # nothing of state size beside the kernels: no slice of the rows, no sums, no update of XLA's
    # own; no timed instruction reads or writes the pool but the six calls
    everything = {n for n, _ in timed_ops(burst)}
    assert not [n for n in everything if n.startswith(
        ("slice_bitcast_fusion_f32_32_30_96_256_", "multiply_reduce_fusion_f32_32_30_192_",
         "bitcast_dynamic-update-slice_fusion_f32_6_97_30_96_256_"))]
    holds_pool = {m.group(1) for m in re.finditer(r"(%[\w.\-]+) = ([^=]*?) [a-z\-]+\(", burst)
                  if "f32[6,97,30,96,256]" in m.group(2)}  # whatever is, or carries, the pool
    at_pool = [ln for ln in timed if holds_pool & set(re.findall(r"%[\w.\-]+", ln.split("), ")[0]))]
    assert len(at_pool) == 6 and all(
        " custom-call(" in ln and "/gdn_recurrent/" in ln for ln in at_pool), at_pool
    # what the scope holds besides is k | q turned on their side for the kernel ([32, 96, 30], 0.7
    # MB a layer) and per-head vectors ([32, 30]): a thousandth of the rows' state
    rest = {n for n, scope in timed_ops(burst) if scope == "gdn_recurrent"} - decode["gdn_recurrent"]
    assert all(n.endswith(("_f32_32_30_", "_f32_32_96_30_")) for n in rest), rest
    # a kernel named after the scope is read (it is a custom call in a trace), an unnamed one is not
    assert rule.search("gdn_recurrent.30_f32_32_30_192_")
    assert not rule.search("custom-call.2_f32_32_30_192_")
    # XLA names a fusion after its ops and its result, so a wave's slot writes into the same pool
    # read like the burst's rows: the metric's seconds hold them too (one slot a row and a snapshot,
    # against 32 rows a layer and step: they can only lower the reading), and nothing else of a wave
    in_wave = _picked(wave, rule)
    assert set(in_wave) == {"state_write"}
    assert all(n.startswith("bitcast_dynamic-update-slice_fusion") for n in in_wave["state_write"])

    chunked = re.compile(spec("olmo_gdn_prefill_roofline_frac")["op"].format(
        **family.state_op_sizes(model, cell.config)))
    got = _picked(wave, chunked)
    assert "gdn_chunked" in got and set(got) <= {"gdn_chunked", "state_read", ""}
    assert _picked(burst, chunked) == {}

    moves = re.compile(spec("olmo_state_pool_move_share")["pattern"])
    assert set(_picked(wave, moves)) <= {"state_write"}  # the in-place row writes, nothing else
    # the kernel computes, it does not move the pool: its name ends in o's shape, not the pool's
    # (had the pool been its first result, ``gdn_recurrent.N_f32_6_97_30_96_256_`` would be a move);
    # what is left of the burst is the histories' rows written back
    assert set(_picked(burst, moves)) <= {"gdn_conv", ""}
    assert moves.search("gdn_recurrent.30_f32_6_97_30_96_256_")

    # the burst's attention kernel is named for its scope, where the accepted metric looks
    paged = re.compile(manifest.metric_spec("paged_attn_hbm_frac")["args"]["op"])
    names = {n for n, _ in timed_ops(burst) if paged.search(n)}
    assert names and all(n.startswith("paged_attention") for n in names)
    # the scopes this model adds name ops of both programs
    for program in (burst, wave):
        assert {"qk_norm", "dense_mlp"} <= {scope for _, scope in timed_ops(program)}
