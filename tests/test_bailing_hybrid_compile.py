"""Ling-3.0-flash's decode burst and its one-row prefill wave compiled whole
for a TPU v5e that is described, not attached, at the shapes of the benchmark's
cell (``ling-3.0-flash-ep4-bf16``: published widths, 6 KDA layers and 1 latent
layer, 2,560 latent pages of 128 tokens, 32 live + 63 snapshot + 1 slots of
2.1 MB a layer): the forms, not every row bucket (ROADMAP D23).  Both latent
kernels pass the chip's compiler at 32 heads over tables of 208 pages (shapes
neither had compiled at) and the KDA kernel at 32 heads of 128 x 128 with its
decay a column a head in VMEM; nothing in the optimized HLO copies, transposes
or slices the latent pool, either state pool, an expert stack or the head;
every pool lies row-major as the program reads it; and the ops that this
cell's metrics pick out of a trace by their names are the ops under the scopes
they are meant to read.  Nothing executes; a pass here is not a chip run.

What this file found and left: both programs copy ``w_f`` and ``w_g`` ([6, 2560,
4096], 126 MB each) and ``wq`` once a dispatch into a transposed layout, outside
the step loop (the compiler folds a later swap of axes into the weight, as
PERF.md's PR 35 found of Qwen3-Next's projections): ~0.6 ms a burst, under
PERF.md's open questions."""

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
    assert_hit_experts_are_one_walk,
    chip,
    pool_movers,
    topo,
)

PAGES, PAGE, ROWS, ROW_PAGES, SLOTS, KDA, LATENT = 2560, 128, 32, 208, 96, 6, 1
SCOPES = ("kda_proj", "kda_conv", "kda_gate", "kda_chunked", "kda_recurrent", "kda_gate_norm",
          "state_read", "state_write", "mla_q_proj", "mla_kv_proj", "latent_write",
          "latent_attention", "latent_prefill_attention", "attn_gate", "moe_route", "moe_experts",
          "moe_shared", "sample")
CELL = "ling-3.0-flash-ep4-bf16.repo-longctx"
PROGRAMS = [pytest.param("burst", 0, id="burst"), pytest.param("wave", 1, id="wave-1x512")]


@pytest.fixture()
def as_on_chip(monkeypatch):
    import githubrepostorag_tpu.models.bailing_hybrid as model
    import githubrepostorag_tpu.models.hybrid as hybrid
    import githubrepostorag_tpu.ops.pallas_experts as experts

    for mod in (hybrid, model, experts):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


def cell_config():
    from benchmarks import manifest
    from benchmarks.families import bailing_hybrid as family

    cell = manifest.load_cell(CELL)
    return cell, family, family.model_config(family.model_of(cell.config, rehearse=False))


@functools.lru_cache(maxsize=None)
def compiled(where, program: str, rows: int):
    """(optimized HLO, the shapes of what must stay in place) of the burst or
    of the wave at a row bucket, compiled once a module."""
    from githubrepostorag_tpu.models.bailing_hybrid import (
        decode_burst,
        forward_paged_wave,
        init_params,
    )
    from githubrepostorag_tpu.serving.kv_cache import make_state_pools

    cell, _, cfg = cell_config()
    eng = cell.config["engine"]
    assert eng["max_num_seqs"] + eng["state_snapshots"] + 1 == SLOTS and eng["num_pages"] == PAGES
    assert (cfg.state_layers, cfg.kv_layers, cfg.num_layers) == (KDA, LATENT, 7)
    assert -(-eng["max_seq_len"] // PAGE) == ROW_PAGES
    shaped = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where), t)
    params = shaped(jax.eval_shape(lambda: init_params(cfg, 0)))
    state = shaped(jax.eval_shape(lambda: make_state_pools(cfg, SLOTS)))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    pool_shape = (cfg.kv_layers, 1, PAGES, PAGE, cfg.head_dim)
    kp = sds(pool_shape, jnp.bfloat16)
    b, i32, f32 = ROWS, jnp.int32, jnp.float32
    if program == "burst":
        lowered = decode_burst.lower(
            params, cfg, sds((b,), i32), sds((b,), i32), kp, None,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), jnp.bool_), sds((b,), i32),
            sds((b, ROW_PAGES), i32), sds((2,), jnp.uint32), sds((b,), f32), sds((b,), f32),
            sds((b,), i32), sds((b,), f32), n_steps=8, use_pallas=True, filter_sampling=False,
            first_tokens=sds((b,), i32), fresh=sds((b,), jnp.bool_), fresh_lens=sds((b,), i32),
            key_step=sds((), jnp.uint32), state=state)
    else:
        chunk, row = (rows, 512), (rows,)
        lowered = forward_paged_wave.lower(
            params, cfg, sds(chunk, i32), sds(chunk, i32), kp, None,
            sds((b, cfg.vocab_size), jnp.bool_), sds((b,), i32), sds(chunk, i32),
            sds((rows, ROW_PAGES), i32), sds(row, i32), sds(row, i32), sds(row, i32),
            sds(row, i32), sds(row, jnp.bool_), sds((), i32), sds((2,), jnp.uint32),
            sds((), jnp.uint32), sds((b,), f32), sds((b,), f32), sds((b,), i32), sds((b,), f32),
            use_pallas=True, state=state, state_src=sds(row, i32), state_dst=sds(row, i32),
            state_snap=sds(row, i32), snap_col=sds(row, i32))
    pools = {"latent": pool_shape, "s": state["s"].shape, "conv": state["conv"].shape,
             "e_wgu": params["moe"]["e_wgu"].shape, "e_wd": params["moe"]["e_wd"].shape,
             "lm_head": params["lm_head"].shape}
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
def test_step_program_leaves_the_latent_pool_the_state_and_the_experts_in_place(
        chip, as_on_chip, program, rows):
    hlo, pools = compiled(chip, program, rows)
    assert "tpu_custom_call" in hlo  # the latent kernel of the burst, or of the prefill, at 32 heads
    assert pool_movers(hlo, pools["latent"]) == []  # rows scattered in place, no window, no copy
    assert pools["s"] == (KDA, SLOTS, 32, 128, 128) and pools["conv"] == (KDA, SLOTS, 3 * 12288)
    # written in place: the burst its rows of history a layer (the STATE is the kernel's alone);
    # the wave a row's state and its snapshot, history and its snapshot, for the ONE traced layer
    writes = {"s": 0, "conv": KDA} if program == "burst" else {"s": 2, "conv": 2}
    for name in ("s", "conv"):
        movers = pool_movers(hlo, pools[name])
        assert all(m.startswith("dynamic_update_slice") for m in movers), (name, movers)
        assert len(movers) == writes[name], (name, movers)
    if program == "burst":
        # a layer's rule is ONE call under its scope: the pool goes in whole and comes out as the
        # same buffer, and no array of all 32 rows' states exists anywhere
        pool = f"f32[{_dims(pools['s'])}]"
        calls = [ln for ln in timed_lines(hlo, ("custom-call",)) if "/kda_recurrent/" in ln]
        assert_calls_step_pool_in_place(calls, pool)
        assert len(calls) == KDA, [c[:120] for c in calls]
        assert f"f32[{ROWS},32,128,128]" not in hlo
    # every pool and stack lies as the program is handed it: row-major, the last axis on the lanes
    layout = hlo.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
    for dtype, name in (("f32", "s"), ("bf16", "conv"), ("bf16", "latent"), ("bf16", "e_wgu"),
                        ("bf16", "e_wd"), ("bf16", "lm_head")):
        shape, order = pools[name], ",".join(str(i) for i in reversed(range(len(pools[name]))))
        assert f"{dtype}[{_dims(shape)}]{{{order}:" in layout, (name, layout[:2000])
    # and nothing the size of a pool, of an expert stack or of the head is copied
    big = [ln for ln in timed_lines(hlo, ("copy",))
           if any(f"[{_dims(pools[k])}]" in ln.split(" copy(")[0] for k in pools)]
    assert big == [], [ln[:200] for ln in big]


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
    # FIRST result (o, [32, 32, 128]: the pool is its second).  Nothing of the wave
    rule = re.compile(spec("kda_decode_roofline_frac")["op"])
    decode = _picked(burst, rule)
    assert set(decode) == {"kda_recurrent"}
    names = [re.sub(r"\.\d+", "", n) for n in decode["kda_recurrent"]]
    assert names.count("kda_recurrent_f32_32_32_128_") == KDA, names
    assert _picked(wave, rule) == {} and rule.search("kda_recurrent.3_f32_32_32_128_")

    chunked = re.compile(spec("kda_prefill_roofline_frac")["op"].format(
        **family.state_op_sizes(model, cell.config)))
    got = _picked(wave, chunked)
    assert "kda_chunked" in got
    # beside the scope's own: the rows of state on their way in and out, and the transposes of
    # q, k, v into blocks and of o out of them, which XLA files under the scope that made them
    assert set(got) <= {"kda_chunked", "state_read", "state_write", "kda_proj", "kda_conv", "kda_gate",
                        "kda_gate_norm", ""}, sorted(got)
    in_scope = {n for n, scope in timed_ops(wave) if scope == "kda_chunked"}
    left = {re.sub(r"\.\d+", "", n) for n in in_scope - got["kda_chunked"]}
    # what the pattern leaves of the scope is masks, indices and a head's columns: nothing of
    # [.., 64, 128], [.., 64, 64] or a state's size
    assert all(not re.search(r"_f32_([0-9]+_)*32_(64_128|64_64|128_128)_$", n) for n in left), left
    assert _picked(burst, chunked) == {} and chunked.search("kda_chunked.7_f32_8_")

    for name, scopes in (("ling_state_pool_move_share", {"state_write"}),
                         ("ling_latent_pool_move_share", set())):
        moves = re.compile(spec(name)["pattern"])
        assert set(_picked(wave, moves)) == scopes, (name, _picked(wave, moves))
        assert _picked(burst, moves) == {}, (name, _picked(burst, moves))
    assert re.compile(spec("ling_state_pool_move_share")["pattern"]).search(
        "dynamic_update_slice.8_bf16_6_96_36864_")
    assert re.compile(spec("ling_latent_pool_move_share")["pattern"]).search(
        "copy.3_bf16_1_1_2560_128_640_")

    # the accepted experts' metric finds the burst's walk over the hit experts, a call an expert
    # layer (the first layer is dense), and nothing of the wave
    experts = re.compile(spec("moe_experts_hbm_frac")["op"].format(
        **family.expert_op_sizes(model, cell.config)))
    got = _picked(burst, experts)
    assert set(got) == {"moe_experts"} and len(got["moe_experts"]) == 6
    assert_hit_experts_are_one_walk(
        list(timed_ops(burst)), timed_lines(wave, ("custom-call",)), 6, 32, 2560, 1536,
        ((2560, 1536), (768, 2560)), experts)
    assert "moe_experts" not in _picked(wave, experts)

    # DeepSeek-V3's two latent metrics find this cell's kernels under their scopes
    for name, hlo, scope in (("latent_attn_roofline_frac", burst, "latent_attention"),
                             ("latent_prefill_attn_flops_frac", wave, "latent_prefill_attention")):
        names = {n for n, _ in timed_ops(hlo) if re.search(spec(name)["op"], n)}
        assert names and all(n.startswith(scope) for n in names), (name, names)
    # the scopes this model adds or shares name ops of both programs
    assert {"kda_proj", "kda_conv", "kda_gate", "kda_recurrent", "kda_gate_norm", "mla_q_proj",
            "mla_kv_proj", "latent_write", "latent_attention", "attn_gate", "moe_route",
            "moe_experts", "moe_shared", "sample"} <= {scope for _, scope in timed_ops(burst)}
    assert {"kda_proj", "kda_conv", "kda_gate", "kda_chunked", "kda_gate_norm", "state_read",
            "state_write", "mla_q_proj", "mla_kv_proj", "latent_write",
            "latent_prefill_attention", "attn_gate", "moe_route", "moe_experts", "moe_shared",
            "sample"} <= {s for _, s in timed_ops(wave)}
