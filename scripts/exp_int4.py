"""7B int4 (W4A8) decode throughput check — iterates on the Pallas kernel
without paying the full bench. Generates the int4 tree on device
(quant._devrand — no host build, no host->device copy), then runs the bs32
decode geometry from bench.py's int4 item."""

import sys
import time

sys.path.insert(0, ".")
from githubrepostorag_tpu.runtime import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np

from githubrepostorag_tpu.models.qwen2 import Qwen2Config
from githubrepostorag_tpu.serving import Engine, SamplingParams

t0 = time.monotonic()
cfg = Qwen2Config.qwen2_7b()
from githubrepostorag_tpu.models.quant import init_params_quantized, params_nbytes

params = init_params_quantized(cfg, bits=4, fuse=True)
jax.block_until_ready(params)
nbytes = params_nbytes(params)
print(f"int4 tree {nbytes / 1e9:.2f} GB generated on device in "
      f"{time.monotonic() - t0:.0f}s", flush=True)

rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, size=128).tolist() for _ in range(32)]
sp = SamplingParams(max_tokens=256, temperature=0.7, stop_token_ids=())
eng = Engine(params, cfg, max_num_seqs=32, num_pages=64, page_size=256,
             max_seq_len=1024, prefill_chunk=128, use_pallas=True,
             decode_burst=128)
for trial in range(3):
    t1 = time.monotonic()
    results = eng.generate(prompts, sp)
    decode_t = max(max(r.decode_time_s for r in results), 1e-9)
    toks = sum(max(len(r.output_tokens) - 1, 0) for r in results)
    tps = toks / decode_t
    gbps = tps / 32 * nbytes / 1e9
    print(f"trial={trial}: {tps:.0f} tok/s | {decode_t / (toks / 32) * 1e3:.2f} "
          f"ms/step | {gbps:.0f} GB/s ({gbps / 8.19:.1f}% roofline)", flush=True)
