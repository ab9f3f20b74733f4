"""One compiled program per engine step: packed prefill + mixed
spec/plain decode fused into a single dispatch.

What the unfused step loop dispatches, worst case, per step: a packed
prefill program, then EITHER a spec burst (only when every running row is
plain greedy — one sampled row demotes the whole batch) OR a plain decode
burst.  Two model programs per step, and mixed traffic loses speculation
entirely: serving/engine.py's all-greedy gate exists because
spec_decode_burst has no way to sample.

``fused_step_burst`` is one jitted program that

  - phase A: runs the packed-prefill chunk wave inline
    (models/qwen2.forward_paged_packed_impl — the segment-ID grid), when
    the step admitted prompt work (``has_prefill``; a static no-prefill
    variant skips the phase entirely);
  - phase B: scans ``n_iters`` MIXED decode iterations.  Every row gets a
    (k+1)-wide window through ONE forward_paged_impl call — greedy rows
    use it as an n-gram spec-verify window (draft/verify/accept exactly
    as serving/spec_burst.py, token-identical by construction), sampled
    rows use position 0 and draw on-device via ops/sampling's fused-
    window logits layout (no host transpose, no demotion to a separate
    burst program).  With the fused attention seam this is one Pallas
    launch per iteration over fp/int8/int4 pages alike.

So a step that used to cost [prefill program] + [decode-or-spec program]
(+ the gather fallbacks inside each) is ONE dispatch, and a mixed batch
keeps speculation for its greedy rows (no benchmark cell runs it: not
measured on the chip).

Host contract matches spec_burst/decode_burst: stop/max_tokens
bookkeeping stays host-side on the returned packed [B, n_iters, k+1]
token block; prefill first-token sampling stays host-side on the returned
per-segment logits.  Rows finishing prefill in phase A join the NEXT
step's phase B (their first token commits host-side after the dispatch) —
one step of extra latency for their second token, in exchange for the
step staying a single program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import (
    Qwen2Config,
    forward_paged_impl,
    forward_paged_packed_impl,
)
from githubrepostorag_tpu.ops.sampling import (
    sample_tokens_capped,
    sample_tokens_nofilter,
)
from githubrepostorag_tpu.serving.spec_burst import ngram_draft_device


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "n_iters", "k", "tq", "use_pallas", "int4_kernel",
        "filter_sampling", "has_prefill",
    ),
    donate_argnums=(5, 6, 12),
)
def fused_step_burst(
    params: dict,
    cfg: Qwen2Config,
    history: jnp.ndarray,  # [B, H] int32 — prompt + committed output
    hist_lens: jnp.ndarray,  # [B] int32
    lens: jnp.ndarray,  # [B] int32 cached tokens per decode row
    k_pages: jnp.ndarray,  # donated
    v_pages: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,  # [B, max_pages] int32 (decode rows)
    row_limits: jnp.ndarray,  # [B] int32 max cacheable tokens
    active: jnp.ndarray,  # [B] bool
    spec_ok: jnp.ndarray,  # [B] bool — greedy rows (temperature <= 0,
    # repetition_penalty == 1): verify windows; False rows sample 1 token
    row_idx: jnp.ndarray,  # [B] int32 engine row per compacted row — the
    # presence pool stays engine-row indexed across compactions
    presence: jnp.ndarray,  # [max_num_seqs, V] bool, donated
    rng: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32
    repetition_penalty: jnp.ndarray,  # [B]
    # phase-A packed prefill operands (all None when has_prefill=False —
    # the static flag also changes the arg treedef, so the two variants
    # are distinct precompiled programs)
    pf_ids: jnp.ndarray | None = None,  # [1, T]
    pf_pos: jnp.ndarray | None = None,  # [1, T]
    pf_slots: jnp.ndarray | None = None,  # [T]
    pf_block_tables: jnp.ndarray | None = None,  # [R, max_pages]
    pf_cached: jnp.ndarray | None = None,  # [R]
    pf_new: jnp.ndarray | None = None,  # [R]
    pf_seg: jnp.ndarray | None = None,  # [T]
    pf_logits_at: jnp.ndarray | None = None,  # [R]
    *,
    n_iters: int,
    k: int,
    tq: int = 0,
    use_pallas: bool = False,
    int4_kernel: bool = True,
    filter_sampling: bool = True,
    has_prefill: bool = False,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
):
    """Returns (tokens [B, n_iters, k+1] int32 -1-padded, proposed
    [B, n_iters], pf_logits [R, 1, V] | None, k_pages, v_pages, presence
    [, k_scales, v_scales])."""
    b, h = history.shape
    width = k + 1
    rows = jnp.arange(b)
    page_size = k_pages.shape[3]
    quant = k_scales is not None

    pf_logits = None
    if has_prefill:
        out = forward_paged_packed_impl(
            params, cfg, pf_ids, pf_pos, k_pages, v_pages, pf_slots,
            pf_block_tables, pf_cached, pf_new, pf_seg, pf_logits_at, tq,
            use_pallas, k_scales=k_scales, v_scales=v_scales,
            int4_kernel=int4_kernel,
        )
        if quant:
            pf_logits, k_pages, v_pages, k_scales, v_scales = out
        else:
            pf_logits, k_pages, v_pages = out

    def one_iter(carry, step_rng):
        history, hist_lens, lens, active, pres, kp, vp, ks, vs = carry
        act = active & (lens + 1 <= row_limits)

        draft, dlen = ngram_draft_device(history, hist_lens, k)
        # sampled rows take a plain 1-token window; greedy rows leave room
        # for the correction token inside their page budget
        dlen = jnp.where(spec_ok, dlen, 0)
        dlen = jnp.minimum(dlen, jnp.maximum(row_limits - lens - 1, 0))
        last = history[rows, jnp.maximum(hist_lens - 1, 0)]
        ids = jnp.concatenate([last[:, None], draft], axis=1)  # [B, width]
        pos = lens[:, None] + jnp.arange(width)[None, :]
        n_new = jnp.where(act, 1 + dlen, 0).astype(jnp.int32)
        in_window = jnp.arange(width)[None, :] < n_new[:, None]
        page_idx = jnp.clip(pos // page_size, 0, block_tables.shape[1] - 1)
        slots = jnp.take_along_axis(block_tables, page_idx, axis=1) * page_size \
            + pos % page_size
        slots = jnp.where(in_window, slots, -1)  # -1 drops at the scatter

        out = forward_paged_impl(
            params, cfg, ids, pos, kp, vp, slots, block_tables,
            lens, n_new, use_pallas, int4_kernel=int4_kernel,
            k_scales=ks if quant else None, v_scales=vs if quant else None,
        )
        if quant:
            logits, kp, vp, ks, vs = out
        else:
            logits, kp, vp = out
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, width]

        # sampled rows draw from their window's position-0 logits — the
        # fused [B, width, V] layout goes straight into the sampler
        # (ops/sampling._segment_logits), no host transpose
        pres_rows = pres[row_idx]
        if filter_sampling:
            tok_s = sample_tokens_capped(
                logits, step_rng, temperature, top_p, top_k,
                repetition_penalty, pres_rows,
            )
        else:
            tok_s = sample_tokens_nofilter(
                logits, step_rng, temperature, repetition_penalty, pres_rows,
            )
        final0 = jnp.where(spec_ok, greedy[:, 0], tok_s)

        # greedy rows: longest agreed prefix + correction (spec_burst's
        # accept rule, so fused greedy output is token-identical to the
        # spec path); sampled rows: exactly their one drawn token
        agree = (greedy[:, :k] == draft) & (jnp.arange(k)[None, :] < dlen[:, None])
        a = jnp.cumprod(agree.astype(jnp.int32), axis=1).sum(axis=1)
        n_commit = jnp.where(act, jnp.where(spec_ok, a + 1, 1), 0).astype(jnp.int32)
        committed = jnp.arange(width)[None, :] < n_commit[:, None]
        toks_full = greedy.at[:, 0].set(final0)
        toks = jnp.where(committed, toks_full, -1)

        # presence rides the engine-row index through the compaction; -1
        # padding maps to token 0 with a False update (no-op)
        pres = pres.at[
            row_idx[:, None], jnp.where(committed, toks_full, 0)
        ].max(committed & act[:, None])

        hidx = hist_lens[:, None] + jnp.arange(width)[None, :]
        hidx = jnp.where(committed & (hidx < h), hidx, h)
        history = history.at[rows[:, None], hidx].set(toks_full, mode="drop")
        hist_lens = hist_lens + n_commit
        lens = lens + n_commit

        carry = (history, hist_lens, lens, active, pres, kp, vp, ks, vs)
        return carry, (toks, jnp.where(act & spec_ok, dlen, 0))

    ks0 = k_scales if quant else jnp.zeros((), jnp.float32)
    vs0 = v_scales if quant else jnp.zeros((), jnp.float32)
    keys = jax.random.split(rng, n_iters)
    carry0 = (history, hist_lens, lens, active, presence, k_pages, v_pages,
              ks0, vs0)
    (history, hist_lens, lens, active, presence, k_pages, v_pages, ks, vs), \
        (toks, proposed) = jax.lax.scan(one_iter, carry0, keys)
    # scan stacks leading: [n_iters, B, ...] -> [B, n_iters, ...]
    toks = jnp.swapaxes(toks, 0, 1)
    proposed = jnp.swapaxes(proposed, 0, 1)
    if quant:
        return toks, proposed, pf_logits, k_pages, v_pages, presence, ks, vs
    return toks, proposed, pf_logits, k_pages, v_pages, presence
