"""Fused speculative decode bursts: draft + verify entirely on-device.

Speculation dispatched from the host would pay one dispatch+fetch round
trip per verify.  Here ``n_iters`` draft->verify->accept iterations run
inside ONE compiled program (``lax.scan``), so a 128-token generation is
one dispatch with or without speculation and the comparison becomes what
speculative decoding is actually about — ~16 verify forwards (each reading
the weights once for k+1 positions) versus 128 sequential single-token
forwards.  In the
acceptance regime that is a direct weight-HBM-read reduction, the decode
bottleneck.  No benchmark cell runs it yet: not measured on the chip.

Design, per iteration (all [B]-vectorized, no host control flow):
  1. DRAFT on-device: bigram prompt-lookup over a device-resident token
     history [B, H] — match positions j where history[j:j+2] equals the
     row's last two tokens, take the EARLIEST (argmax of the match mask:
     on repetitive text the most recent match sits just before the suffix
     and truncates the draft), and gather the following k tokens as the
     draft.
  2. VERIFY: one ``forward_paged_impl`` call over [last, draft...] (k+1
     positions, causal over the row's pages) — the same body the engine's
     prefill path inlines; rejected positions' K/V are overwritten by the
     next iteration.
  3. ACCEPT: commit the longest model-agreed draft prefix plus the
     model's correction token (cumprod of the agreement mask), append to
     the history, advance lens.

Greedy-only by design: the engine engages this program only when every
running row is plain greedy (temperature 0, no penalties; any other step
decodes plainly, Engine._decode_path), so outputs are token-identical to
the plain burst path.  Stop-token / max_tokens bookkeeping stays host-side on
the returned packed tokens — the same contract as decode_burst.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from githubrepostorag_tpu.models.qwen2 import Qwen2Config, forward_paged_impl


def ngram_draft_device(
    history: jnp.ndarray,  # [B, H] int32 token history (prompt + output)
    hist_lens: jnp.ndarray,  # [B] valid tokens per row
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized bigram prompt-lookup: returns (draft [B, k] int32,
    draft_len [B] int32).  A row drafts 0 tokens when its history has no
    earlier occurrence of its final bigram (or is shorter than 4 tokens —
    a match must end strictly before the suffix and have a follower)."""
    b, h = history.shape
    rows = jnp.arange(b)
    last1 = history[rows, jnp.maximum(hist_lens - 1, 0)]  # [B]
    last0 = history[rows, jnp.maximum(hist_lens - 2, 0)]
    # match[j] = history[j] == last0 & history[j+1] == last1, j in [0, H-2)
    m = (history[:, :-1] == last0[:, None]) & (history[:, 1:] == last1[:, None])
    j = jnp.arange(h - 1)[None, :]
    # strictly before the suffix bigram itself, with >= 1 follower:
    # j + 1 < hist_lens - 2  <=>  j < hist_lens - 3
    m = m & (j < (hist_lens - 3)[:, None]) & (hist_lens[:, None] >= 4)
    has = m.any(axis=1)
    p = jnp.argmax(m, axis=1)  # earliest True (argmax of bool)
    idx = p[:, None] + 2 + jnp.arange(k)[None, :]  # follower positions
    draft = jnp.take_along_axis(history, jnp.clip(idx, 0, h - 1), axis=1)
    n_follow = hist_lens - (p + 2)  # valid tokens after the match
    dlen = jnp.where(has, jnp.minimum(k, n_follow), 0).astype(jnp.int32)
    return draft.astype(jnp.int32), jnp.maximum(dlen, 0)


@partial(
    jax.jit,
    static_argnames=("cfg", "n_iters", "k", "use_pallas", "int4_kernel"),
    donate_argnums=(5, 6),
)
def spec_decode_burst(
    params: dict,
    cfg: Qwen2Config,
    history: jnp.ndarray,  # [B, H] int32 — prompt + committed output
    hist_lens: jnp.ndarray,  # [B] int32
    lens: jnp.ndarray,  # [B] int32 cached tokens (== hist_lens - 1 for
    # running rows: the newest committed token is not yet cached)
    k_pages: jnp.ndarray,  # donated
    v_pages: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    row_limits: jnp.ndarray,  # [B] int32 max cacheable tokens
    active: jnp.ndarray,  # [B] bool
    *,
    n_iters: int,
    k: int,
    use_pallas: bool = False,
    int4_kernel: bool = True,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
):
    """Run ``n_iters`` fused draft/verify/accept iterations.

    Returns (tokens [B, n_iters, k+1] int32 with -1 padding — committed
    tokens in order, the decode_burst packing contract per iteration —
    proposed [B, n_iters] draft lengths, k_pages, v_pages[, k_scales,
    v_scales]).  Token outputs are identical to plain greedy decoding."""
    b, h = history.shape
    width = k + 1
    rows = jnp.arange(b)
    page_size = k_pages.shape[3]
    quant = k_scales is not None

    def one_iter(carry, _):
        history, hist_lens, lens, active, kp, vp, ks, vs = carry
        act = active & (lens + 1 <= row_limits)

        draft, dlen = ngram_draft_device(history, hist_lens, k)
        # leave room for the correction token inside the row's page budget
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

        # longest agreed prefix: a = number of leading draft positions the
        # model reproduces; commit greedy[:, :a+1] (the a agreed tokens ARE
        # greedy's, plus its correction at position a)
        agree = (greedy[:, :k] == draft) & (jnp.arange(k)[None, :] < dlen[:, None])
        a = jnp.cumprod(agree.astype(jnp.int32), axis=1).sum(axis=1)  # [B]
        n_commit = jnp.where(act, a + 1, 0).astype(jnp.int32)
        committed = jnp.arange(width)[None, :] < n_commit[:, None]
        toks = jnp.where(committed, greedy, -1)

        # append committed tokens to the history (out-of-range -> drop)
        hidx = hist_lens[:, None] + jnp.arange(width)[None, :]
        hidx = jnp.where(committed & (hidx < h), hidx, h)
        history = history.at[rows[:, None], hidx].set(greedy, mode="drop")
        hist_lens = hist_lens + n_commit
        lens = lens + n_commit

        carry = (history, hist_lens, lens, active, kp, vp, ks, vs)
        return carry, (toks, jnp.where(act, dlen, 0))

    ks0 = k_scales if quant else jnp.zeros((), jnp.float32)
    vs0 = v_scales if quant else jnp.zeros((), jnp.float32)
    carry0 = (history, hist_lens, lens, active, k_pages, v_pages, ks0, vs0)
    (history, hist_lens, lens, active, k_pages, v_pages, ks, vs), \
        (toks, proposed) = jax.lax.scan(one_iter, carry0, None, length=n_iters)
    # scan stacks leading: [n_iters, B, ...] -> [B, n_iters, ...]
    toks = jnp.swapaxes(toks, 0, 1)
    proposed = jnp.swapaxes(proposed, 0, 1)
    if quant:
        return toks, proposed, k_pages, v_pages, ks, vs
    return toks, proposed, k_pages, v_pages
