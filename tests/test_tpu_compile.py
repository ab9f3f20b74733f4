"""The main-path Pallas kernels, compiled at published widths for a TPU v5e
that is described, not attached (on-chip-measurement guide, section 2.3).

Interpret mode — what every other kernel test runs — accepts things the
chip's compiler refuses: slices not aligned to the tiling, more VMEM than a
kernel may use.  These cases hand each kernel the shapes the serving engine
gives it for Qwen2-1.5B and Qwen2-7B (head 128, page size 128) and ask the
real TPU compiler.  Nothing executes, so they say nothing about results or
speed; a pass here is not a chip run.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from githubrepostorag_tpu.ops.fused_decode import fused_window_attention
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
def chip():
    """Sharding on one described v5e chip; the persistent compile cache is
    off around these compiles (an entry written for a described chip cannot
    be read back without one, and the retry warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _staged(model: str, kv: str):
    n_q, n_kv, hd, _, _, b = MODELS[model]
    dtype, _ = KV[kv]
    pool = ((LAYERS, n_kv, PAGES, PAGE, hd), dtype)
    staged = ((b, n_kv, 8, hd), jnp.bfloat16)
    args = [((b, 1, n_q, hd), jnp.bfloat16), pool, pool, ((b, MAX_PAGES), jnp.int32),
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
]


@pytest.mark.parametrize("build", CASES)
def test_kernel_compiles_for_v5e(chip, build):
    fn, args = build()
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in args]
    compiled = fn.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
