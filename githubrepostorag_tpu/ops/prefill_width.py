"""The width of a prefill wave follows its longest pending chunk, inside the
one compiled program a row bucket has.

The engine builds every wave ``[row bucket, prefill_chunk]`` and hands the
wave program the columns its longest row needs as a scalar.  Each layer of
the program holds one branch per rung of ``width_ladder``; a branch cuts the
residual stream and what else runs along the chunk (rotary tables, slots,
the mask of real tokens) to its own width and runs the family's layer on
that, so a wave of short rows multiplies no padding and a warm-up of the
row bucket has compiled and loaded every rung.  The columns a narrow rung
drops are the ones whose slots are -1 and which ``new_lens`` masks: the
mathematics of the columns that stay does not change.

The branches sit inside the layer scan and not around it: around it, the
v5e compiler copies a donated page pool into and out of every layer of the
scan it finds in a branch (two pools, 28 layers, 1.4 GB a copy;
tests/test_tpu_compile.py holds the program to none).  For the same reason
a branch indexes its layer's weights out of the stacks itself: sliced out
by the scan, they would be copied whole to cross into the branch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# rungs below chunk / 4 buy little (at 128 columns a 7B chunk streams its
# weights about as long as it multiplies) and each is a branch to compile
MAX_RUNGS = 3
# a wave of more rows runs whole: the more rows, the likelier one long one
# among them, and every branch is traced anew for every row bucket at every
# start of a server (0.2 s a Qwen2 branch on a v5e's host, 0.6 s a
# DeepSeek-V3 one: seconds to ready; PERF.md section 6, PR 33)
MAX_RUNG_ROWS = 2


def width_ladder(chunk: int, page_size: int, rows: int = 1) -> list[int]:
    """The widths a wave of ``rows`` rows (its row bucket) can run at:
    ``chunk`` halved, largest first, never below the page size (slot
    mappings stay page-aligned), ``MAX_RUNGS`` entries at most: [512, 256,
    128] at 512-token chunks over 128-token pages; ``chunk`` alone beyond
    ``MAX_RUNG_ROWS`` rows.  The engine picks a wave's rung from this list
    and the wave program cuts to it."""
    ladder = [chunk]
    while (rows <= MAX_RUNG_ROWS and len(ladder) < MAX_RUNGS and ladder[-1] % 2 == 0
           and ladder[-1] // 2 >= page_size):
        ladder.append(ladder[-1] // 2)
    return ladder


def layer_weights(p_xs, stack, li):
    """A layer's weights: the scan's own slice ``p_xs`` where the layers are
    scanned as xs, else layer ``li`` of ``stack``, indexed where it is used
    (inside a branch of ``at_wave_width``)."""
    if p_xs is not None:
        return p_xs
    return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, li, keepdims=False), stack)


def at_wave_width(layer_fn, width, page_size: int, cols, carried):
    """``layer_fn(cols, carried) -> (h, out)`` on the first w columns of
    every array of ``cols`` (a tuple of ``[R, chunk, ...]`` arrays, the
    residual stream first), w the narrowest rung that holds ``width`` (a
    traced scalar: the wave's longest pending chunk); ``h`` comes back
    padded to the chunk again, so every rung returns the same shapes.
    ``carried`` (the donated pools) enters and leaves each branch of the
    ``lax.switch`` as the same buffers.  ``width`` None, or a ladder of
    one rung, runs the whole chunk and no switch."""
    rows, chunk = cols[0].shape[:2]
    ladder = width_ladder(chunk, page_size, rows)
    if width is None or len(ladder) == 1:
        return layer_fn(cols, carried)
    rung = sum((width <= w).astype(jnp.int32) for w in ladder[1:])

    def at(w, cols, carried):
        h, out = layer_fn(jax.tree.map(lambda x: x[:, :w], cols), carried)
        return jnp.pad(h, ((0, 0), (0, chunk - w), (0, 0))), out

    return jax.lax.switch(rung, [partial(at, w) for w in ladder], cols, carried)
