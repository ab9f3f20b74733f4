"""One-off experiment: conc64 p50 TTFT with vs without width-bucketed
prefill, on random-weight models on the real chip.  Usage:

    python scripts/exp_ttft.py [0.5b|1.5b] [widths...]

Not part of bench.py — this is the iteration harness for the eval
config #5 TTFT work (VERDICT r03 next #3)."""

import sys
import time

sys.path.insert(0, ".")
from githubrepostorag_tpu.runtime import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, init_params
from githubrepostorag_tpu.serving import Engine, SamplingParams

model = sys.argv[1] if len(sys.argv) > 1 else "0.5b"
widths = [int(w) for w in sys.argv[2:]] or [1, 2]
cfg = {"0.5b": Qwen2Config.qwen2_0_5b, "1.5b": Qwen2Config.qwen2_1_5b}[model]()
params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
jax.block_until_ready(params)

rng = np.random.default_rng(1)
sp = SamplingParams(max_tokens=128, temperature=0.7, stop_token_ids=())

for pw in widths:
    eng = Engine(params, cfg, max_num_seqs=64, num_pages=320, page_size=64,
                 max_seq_len=1024, prefill_chunk=256, use_pallas=True,
                 decode_burst=32, prefill_widths=pw)
    t0 = time.monotonic()
    eng.warmup()
    t_warm = time.monotonic() - t0
    for trial in range(2):  # trial 0 warms any residual state; keep trial 1
        # FRESH prompts per trial: reusing trial 0's prompts would hit the
        # prefix cache and measure a half-cached wave, not eval config #5
        prompts = [rng.integers(0, cfg.vocab_size, size=128).tolist()
                   for _ in range(64)]
        t0 = time.monotonic()
        results = eng.generate(prompts, sp)
        wall = time.monotonic() - t0
        toks = sum(len(r.output_tokens) for r in results)
        ttfts = sorted(r.ttft_s for r in results if r.ttft_s is not None)
        print(f"widths={pw} trial={trial}: warmup {t_warm:.1f}s | "
              f"agg {toks / wall:.1f} tok/s | p50 TTFT {ttfts[32]:.3f}s | "
              f"p99 {ttfts[-1]:.3f}s", flush=True)
    del eng
