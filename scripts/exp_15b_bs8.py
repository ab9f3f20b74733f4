"""Attribute the 1.5B bf16 bs8 decode gap (VERDICT r03 next #6).

Measures on the real chip:
  1. the achievable weight-stream ceiling for the fused serving layout
     (a jitted full-tree reduction — the roofline the burst can actually
     reach, vs the 819 GB/s nameplate),
  2. decode tok/s with sampled vs greedy rows (sampling-cost slice),
  3. step time at bs8 vs bs16 (bandwidth-bound check: equal step time
     means the remaining gap is per-step glue, not FLOPs).
"""

import sys
import time

sys.path.insert(0, ".")
from githubrepostorag_tpu.runtime import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.models.quant import fuse_projections, params_nbytes
from githubrepostorag_tpu.serving import Engine, SamplingParams

cfg = Qwen2Config.qwen2_1_5b()
params = fuse_projections(init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16),
                          in_place=True)
jax.block_until_ready(params)
nbytes = params_nbytes(params)
print(f"params: {nbytes / 1e9:.2f} GB", flush=True)


@jax.jit
def stream_all(p):
    # force every weight byte through HBM once; tiny f32 accumulator out
    return sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(p))


v = stream_all(params)
jax.block_until_ready(v)
t0 = time.monotonic()
for _ in range(10):
    v = stream_all(params)
jax.block_until_ready(v)
dt = (time.monotonic() - t0) / 10
print(f"stream_all: {dt * 1e3:.2f} ms -> {nbytes / dt / 1e9:.0f} GB/s achievable ceiling",
      flush=True)

rng = np.random.default_rng(0)
for batch, temp in ((8, 0.7), (8, 0.0), (16, 0.7)):
    eng = Engine(params, cfg, max_num_seqs=batch, num_pages=64, page_size=256,
                 max_seq_len=1024, prefill_chunk=128, use_pallas=True,
                 decode_burst=128)
    prompts = [rng.integers(0, cfg.vocab_size, size=128).tolist() for _ in range(batch)]
    sp = SamplingParams(max_tokens=256, temperature=temp, stop_token_ids=())
    for trial in range(2):
        t0 = time.monotonic()
        results = eng.generate(prompts, sp)
        wall = time.monotonic() - t0
        decode_t = max(max(r.decode_time_s for r in results), 1e-9)
        toks = sum(max(len(r.output_tokens) - 1, 0) for r in results)
        step_ms = decode_t / (toks / batch) * 1e3
        print(f"bs={batch} temp={temp} trial={trial}: {toks / decode_t:.0f} tok/s "
              f"decode | {step_ms:.2f} ms/step | weight-stream share "
              f"{nbytes / 819e9 * 1e3:.2f} ms", flush=True)
    del eng
