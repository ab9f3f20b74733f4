"""Token sampling: greedy / temperature / top-k / top-p (nucleus) with
repetition penalty.

Covers the reference's client-side sampling surface (qwen_llm.py:107-114:
temperature 0.4, top_p 0.8, repetition_penalty 1.2, and the ingest client's
0.7/0.9) executed *inside* the engine on TPU — one fused jit per decode step
rather than vLLM's GPU sampler.

All functions are batch-first and jit-safe with static vocab shapes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _segment_logits(logits: jnp.ndarray, seg_pos=None) -> jnp.ndarray:
    """Accept the fused kernel's per-segment [B, S, V] logits layout
    directly: ``seg_pos`` ([B] int32) gathers each row's window position
    on device (None = position 0, the committed token of a verify/decode
    window).  Rank-2 logits pass through untouched — callers used to
    transpose-copy [B, S, V] windows host-side before sampling; the
    on-device take_along_axis fuses into the sampling program instead."""
    if logits.ndim == 2:
        return logits
    if seg_pos is None:
        return logits[:, 0]
    return jnp.take_along_axis(logits, seg_pos[:, None, None], axis=1)[:, 0]


def _exact_topk() -> bool:
    """SAMPLING_EXACT_TOPK=1 -> exact full-vocab candidate selection in
    sample_tokens_capped (read per trace, so flipping the env between
    engine constructions takes effect on the next compile)."""
    from githubrepostorag_tpu.config import _env_bool

    return _env_bool("SAMPLING_EXACT_TOPK", False)


def apply_repetition_penalty(
    logits: jnp.ndarray,  # [B, V] float32
    presence: jnp.ndarray,  # [B, V] bool — token appeared in prompt or output
    penalty: float | jnp.ndarray,
) -> jnp.ndarray:
    """HF/vLLM convention: divide positive logits by the penalty, multiply
    negative ones, for every token already seen."""
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(presence, penalized, logits)


def top_k_mask(logits: jnp.ndarray, k: jnp.ndarray | int) -> jnp.ndarray:
    """Keep the k highest logits per row.  ``k`` is a scalar or [B] array of
    int32; k <= 0 disables filtering for that row."""
    vocab = logits.shape[-1]
    k_arr = jnp.broadcast_to(jnp.asarray(k, jnp.int32), logits.shape[:-1])  # [B]
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    idx = jnp.clip(k_arr - 1, 0, vocab - 1)[..., None]
    threshold = jnp.take_along_axis(sorted_desc, idx, axis=-1)  # [B, 1]
    filtered = jnp.where(logits < threshold, NEG_INF, logits)
    return jnp.where((k_arr <= 0)[..., None], logits, filtered)


def top_p_mask(logits: jnp.ndarray, p: jnp.ndarray | float) -> jnp.ndarray:
    """Nucleus filtering: mask tokens outside the smallest set with cumulative
    probability >= p.  p >= 1 disables."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumprobs = jnp.cumsum(probs, axis=-1)
    # keep tokens while cumulative prob of *previous* tokens < p; the top
    # token always survives (p <= 0 must degrade to near-greedy, not to
    # uniform sampling over a fully masked vocab)
    keep_sorted = (cumprobs - probs) < jnp.asarray(p)[..., None]
    keep_sorted = keep_sorted.at[..., 0].set(True)
    # threshold = smallest kept logit
    threshold = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < threshold, NEG_INF, logits)


def sample_tokens_capped(
    logits: jnp.ndarray,  # [B, V] float32, or [B, S, V] fused-window layout
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B] — 0 means greedy
    top_p: jnp.ndarray,  # [B] — 1.0 disables
    top_k: jnp.ndarray,  # [B] int32 — 0 disables
    repetition_penalty: jnp.ndarray,  # [B]
    presence: jnp.ndarray,  # [B, V] bool
    cap: int = 128,
    seg_pos: jnp.ndarray | None = None,  # [B] window position per row
    # (rank-3 logits only; None = position 0)
) -> jnp.ndarray:
    """Decode-loop sampler: identical semantics to ``sample_tokens`` except
    top-k/top-p operate within the ``cap`` highest logits.  The candidate
    set comes from one ``lax.approx_max_k`` (TPU-native; an exact
    ``lax.top_k`` over the 152k vocab measures ~1.6 ms/step standalone on
    v5e — comparable to the whole 0.5B forward — and costs ~15% of decode
    throughput in-burst) whose default aggregate_to_topk pass already
    returns the cap candidates EXACTLY sorted; recall_target=0.99 sets the
    internal bin oversampling.  A bin-collision miss (~(1-recall) per
    step) costs one step of that token's sampling mass — no correctness
    impact, greedy rows use the separate exact argmax below.
    Exact nucleus whenever it fits the cap, which holds for every sampling
    config in the system (reference clients use top_p 0.8/0.9 at
    temperature <= 0.7 — qwen_llm.py:107-114).

    SAMPLING_EXACT_TOPK=1 swaps the approximate candidate pull for an
    exact ``lax.top_k`` over the full vocab — the escape hatch for
    reproducibility-sensitive evals where the ~(1-recall)-per-step chance
    of a missing tail candidate matters more than the ~15%
    decode-throughput cost."""
    logits = _segment_logits(logits, seg_pos)
    logits = apply_repetition_penalty(logits, presence, repetition_penalty[:, None])
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    vocab = logits.shape[-1]
    cap = min(cap, vocab)
    if _exact_topk():
        vals, idx = jax.lax.top_k(scaled, cap)
        idx = idx.astype(jnp.int32)
    else:
        # approx_max_k's default aggregate_to_topk=True ENDS with an exact
        # sorted top-k over its oversampled candidate bins (the recall
        # knob controls the internal oversampling), so its output is
        # already what a second lax.top_k would produce — device profiling
        # showed that redundant second sort costing ~0.1 ms/decode step.
        # Pull exactly cap candidates: the in-burst aggregate sort scales
        # with the pull size (real-chip scan bench: pool=2*cap costs
        # ~0.17 ms/step more than pool=cap at bs8), and each true top-cap
        # candidate still lands in the pull with >= recall_target
        # probability.  SAMPLING_EXACT_TOPK=1 below remains the exactness
        # escape hatch.
        # recall_target stays 0.99: ADVICE r04 suggested 0.995 on the
        # theory that only pull size (not recall) costs time — MEASURED
        # false on the real chip (r05 A/B, 3-run medians on the 0.5B bs8
        # decode item: 3354 tok/s at 0.99 vs 3215 at 0.995, a 4.3% hit —
        # the recall knob widens approx_max_k's internal bins and that
        # reduction work is visible where sampling is a large step
        # fraction).  SAMPLING_EXACT_TOPK=1 remains the exactness hatch.
        vals, idx = jax.lax.approx_max_k(scaled, cap, recall_target=0.99)
        idx = idx.astype(jnp.int32)
    # top-k within the cap: positions >= k masked (k<=0 disables)
    ranks = jnp.arange(cap)[None, :]
    k_arr = top_k[:, None]
    vals = jnp.where((k_arr > 0) & (ranks >= k_arr), NEG_INF, vals)
    # nucleus within the cap (vals already sorted descending)
    probs = jax.nn.softmax(vals, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]
    keep = keep.at[:, 0].set(True)
    vals = jnp.where(keep, vals, NEG_INF)
    choice = jax.random.categorical(rng, vals, axis=-1)  # [B] index into cap
    sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_tokens_nofilter(
    logits: jnp.ndarray,  # [B, V] float32, or [B, S, V] fused-window layout
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B] — 0 means greedy
    repetition_penalty: jnp.ndarray,  # [B]
    presence: jnp.ndarray,  # [B, V] bool
    seg_pos: jnp.ndarray | None = None,  # [B] window position per row
) -> jnp.ndarray:
    """Sampling fast path for rows with top_p >= 1 and top_k <= 0 (the
    default API sampling config): ``jax.random.categorical`` over the full
    vocab is exactly Gumbel-argmax — one fused reduce, no approx_max_k
    candidate pull and no sort.  The candidate sort costs ~0.23 ms per
    decode step at bs8 on v5e (device trace: ``sort.9``), and grows with
    the row count; the engine selects this variant per burst from its
    host-side sampling mirrors (serving/engine.py _decode_step).

    Distribution contract: the engine's sampling support is "the top-cap
    candidates" (sample_tokens_capped); this variant WIDENS that to the
    exact full vocab when the whole batch qualifies.  A non-filtering row
    batched with a filtering one therefore samples from the top-cap
    support instead — the delta is the tail mass beyond the top 128
    logits, negligible at practical temperatures, and batch composition
    already shifts per-row draws (rows index a shared step key), so no
    cross-composition reproducibility is lost that ever existed."""
    logits = _segment_logits(logits, seg_pos)
    logits = apply_repetition_penalty(logits, presence, repetition_penalty[:, None])
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


@partial(jax.jit, static_argnames=())
def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float32 (last-position logits)
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B] — 0 means greedy
    top_p: jnp.ndarray,  # [B] — 1.0 disables
    top_k: jnp.ndarray,  # [B] int32 — 0 disables
    repetition_penalty: jnp.ndarray,  # [B] — 1.0 disables
    presence: jnp.ndarray,  # [B, V] bool
) -> jnp.ndarray:
    """Per-request sampling params, one fused kernel.  Returns [B] int32."""
    logits = apply_repetition_penalty(logits, presence, repetition_penalty[:, None])
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    filtered = top_p_mask(top_k_mask(scaled, top_k), top_p)
    sampled = jax.random.categorical(rng, filtered, axis=-1).astype(jnp.int32)

    return jnp.where(temperature <= 0.0, greedy, sampled)


def mark_presence_chunks(
    presence: jnp.ndarray,  # [rows, V] bool
    row_idx: jnp.ndarray,  # [R] int32
    ids: jnp.ndarray,  # [R, W] int32 prompt-chunk tokens (right-padded)
    lens: jnp.ndarray,  # [R] valid tokens per row
) -> jnp.ndarray:
    """Batched prompt-token presence marking: padding positions map to an
    out-of-range sentinel that the drop-mode scatter discards."""
    valid = jnp.arange(ids.shape[1])[None, :] < lens[:, None]
    safe_ids = jnp.where(valid, ids, presence.shape[1])
    return presence.at[row_idx[:, None], safe_ids].set(True, mode="drop")


def first_token_tail(
    logits: jnp.ndarray,  # [R, V] float32: each wave row's last-position logits
    presence: jnp.ndarray,  # [rows, V] bool, the engine's whole mask
    first_tokens: jnp.ndarray,  # [rows] int32, the engine's first-token array
    input_ids: jnp.ndarray,  # [R, W] the wave's chunk tokens (right-padded)
    new_lens: jnp.ndarray,  # [R] valid tokens per wave row (0 on padding rows)
    row_idx: jnp.ndarray,  # [R] int32 engine row of each wave row
    done_mask: jnp.ndarray,  # [R] bool: this chunk completes the row's prompt
    rng: jax.Array,
    temperature: jnp.ndarray,  # [rows] per-ENGINE-row sampling parameters,
    top_p: jnp.ndarray,  # taken by row_idx here
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """What follows a prefill wave's forward pass, traced into the wave's own
    program (both model families): mark the chunk's prompt tokens in
    ``presence``, draw the first token of every row whose prompt this chunk
    completes (``sample_tokens`` on the rows' own parameters), mark those
    tokens too and scatter them by engine row into ``first_tokens``.  A wave
    that completes no prompt skips the draw on the device (``lax.cond``: the
    two vocabulary-wide sorts are the cost).  Returns (first_tokens,
    presence)."""
    presence = mark_presence_chunks(presence, row_idx, input_ids, new_lens)

    def draw(presence, first_tokens):
        toks = sample_tokens(
            logits, rng, temperature[row_idx], top_p[row_idx], top_k[row_idx],
            repetition_penalty[row_idx], presence[row_idx])
        # rows that are not done sample too; their scatters are dropped
        rows = jnp.where(done_mask, row_idx, presence.shape[0])
        return (first_tokens.at[rows].set(toks, mode="drop"),
                presence.at[rows, toks].set(True, mode="drop"))

    return jax.lax.cond(jnp.any(done_mask), draw, lambda p, f: (f, p),
                        presence, first_tokens)
