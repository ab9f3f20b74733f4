"""The comparison that decides ``correct``.

On a seeded sample of the prompts the window served, after the window:

1. ``prefill_logits_rel_rms``: the engine's own prefill program
   (``forward_paged`` on the engine's weights and page pools, chunk by
   chunk as the engine dispatches it) gives next-token logits; their
   root-mean-square difference from the float32 reference's, as a share
   of the reference logits' root mean square.
2. ``decode_token_gap``: the engine itself (admission, prefix cache,
   prefill, decode bursts through the paged cache) generates tokens
   greedily; each token is looked up in the reference's logits for its
   position (given the same history): how far below the row's best logit
   it lies, in units of the row's standard deviation, averaged.  With
   random weights the largest logit changes on rounding, so tokens are not
   compared with tokens: a sound engine picks near-ties of the best, a
   lower precision picks tokens that the reference ranks far down.

Each limit is read from the configuration file, which says where it came
from.  Every number is printed beside its limit in every run.
"""

from __future__ import annotations

import random

import numpy as np


def sample_prompts(prompts: list, seed: int, k: int, max_len: int) -> list:
    """``k`` distinct prompts of the window, the same for the same seed."""
    seen, pool = set(), []
    for ids in prompts:
        key = tuple(ids)
        if 8 <= len(ids) <= max_len and key not in seen:
            seen.add(key)
            pool.append(list(ids))
    rng = random.Random(int(seed) * 31 + 5)
    rng.shuffle(pool)
    return pool[:k]


def engine_prefill_logits(engine, seqs: list) -> np.ndarray:
    """Next-token logits [K, V] from the engine's prefill program, one row
    per sequence, prompts longer than a chunk in several dispatches.  Pages
    are taken from the top of the pool without asking the allocator, so
    this runs last: the prefix cache is no longer valid afterwards."""
    import jax.numpy as jnp

    from githubrepostorag_tpu.models.qwen2 import forward_paged
    from githubrepostorag_tpu.serving.engine import _bucket

    rb = _bucket(len(seqs), engine.max_num_seqs, minimum=1)
    w, ps = engine.prefill_chunk, engine.page_size
    per = -(-max(len(s) for s in seqs) // ps)
    if per > engine.max_pages_per_seq or rb * per > engine._allocator.num_pages:
        raise RuntimeError("correctness sample does not fit the page pool")
    bt = np.zeros((rb, engine.max_pages_per_seq), np.int32)
    for i in range(len(seqs)):
        bt[i, :per] = np.arange(i * per, (i + 1) * per)
    out = np.zeros((len(seqs), engine.cfg.vocab_size), np.float32)
    n_chunks = -(-max(len(s) for s in seqs) // w)
    for c in range(n_chunks):
        start = c * w
        ids = np.zeros((rb, w), np.int32)
        slots = np.full((rb, w), -1, np.int32)
        cached = np.zeros((rb,), np.int32)
        lens = np.zeros((rb,), np.int32)
        for i, s in enumerate(seqs):
            valid = max(0, min(len(s) - start, w))
            if not valid:
                continue
            ids[i, :valid] = s[start:start + valid]
            pos = start + np.arange(valid)
            slots[i, :valid] = bt[i, pos // ps] * ps + pos % ps
            cached[i], lens[i] = start, valid
        pos2 = np.broadcast_to(start + np.arange(w, dtype=np.int32), (rb, w))
        logits, engine._k_pages, engine._v_pages = forward_paged(
            engine.params, engine.cfg, jnp.asarray(ids), jnp.asarray(pos2),
            engine._k_pages, engine._v_pages, jnp.asarray(slots), jnp.asarray(bt),
            jnp.asarray(cached), jnp.asarray(lens), use_pallas=engine.use_pallas,
            logits_at=jnp.asarray(np.maximum(lens - 1, 0)),
            k_scales=engine._k_scales, v_scales=engine._v_scales,
            int4_kernel=engine._int4_kernel, mesh=engine.mesh)
        got = np.asarray(logits[:, 0], np.float32)
        for i, s in enumerate(seqs):
            if start < len(s) <= start + w:
                out[i] = got[i]
    return out


def engine_tokens(engine, seqs: list, m: int) -> list:
    from githubrepostorag_tpu.serving.sampling_params import SamplingParams

    sp = SamplingParams(max_tokens=m, temperature=0.0, stop_token_ids=())
    return [list(r.output_tokens) for r in engine.generate([list(s) for s in seqs], sp)]


def rel_rms(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.sqrt(np.mean((got - ref) ** 2)) / np.sqrt(np.mean(ref ** 2)))


def token_gap(ref_rows: np.ndarray, tokens) -> float:
    """Mean over rows of (best logit - chosen token's logit) / row std."""
    gaps = [(row.max() - row[t]) / row.std() for row, t in zip(ref_rows, tokens)]
    return float(np.mean(gaps))


def compare(prefill_logits: np.ndarray, tokens: list, ref: list) -> dict:
    """``ref[i]`` holds the reference logits of sequence i at the positions
    prompt_len - 1 .. prompt_len + m - 2 (row 0 follows the prompt)."""
    ref0 = np.stack([r[0] for r in ref])
    gaps = [token_gap(r[:len(t)], t) for r, t in zip(ref, tokens)]
    return {"prefill_logits_rel_rms": rel_rms(prefill_logits, ref0),
            "decode_token_gap": float(np.mean(gaps)),
            "finite": bool(np.isfinite(prefill_logits).all())}


def check(engine, model: dict, wseed: int, fuse: bool, prompts: list, seed: int,
          spec: dict, control: str | None = None) -> dict:
    """Run the comparison; ``spec`` is the traffic file's ``correctness``
    (sample size, decode tokens) merged with the configuration's limits.
    With ``control`` the reference at the lower precision stands in the
    program's place (it must come out as not correct)."""
    from benchmarks import reference

    k, m = int(spec["sequences"]), int(spec["decode_tokens"])
    seqs = sample_prompts(prompts, seed, k, int(spec["max_prompt_tokens"]))
    if not seqs:
        return {"numbers": {}, "correct": False, "why": "no prompt to sample"}
    tokens = engine_tokens(engine, seqs, m)
    full = [s + t[:-1] for s, t in zip(seqs, tokens)]
    positions = [list(range(len(s) - 1, len(s) - 1 + len(t))) for s, t in zip(seqs, tokens)]
    ref = reference.logits_at(model, wseed, fuse, full, positions)
    if control is None:
        prefill = engine_prefill_logits(engine, seqs)
        numbers = compare(prefill, tokens, ref)
    else:
        ctl = reference.logits_at(model, wseed, fuse, full, positions, control=control)
        numbers = compare(np.stack([c[0] for c in ctl]),
                          [list(np.argmax(c, axis=1)) for c in ctl], ref)
    limits = spec["limits"]
    ok = numbers.pop("finite")
    lines = []
    for name, value in numbers.items():
        ok = ok and value <= limits[name]
        lines.append(f"correct: {name} {value:.6g} limit {limits[name]:.6g}")
    return {"numbers": numbers, "limits": limits, "correct": bool(ok), "lines": lines,
            "sequences": len(seqs)}
