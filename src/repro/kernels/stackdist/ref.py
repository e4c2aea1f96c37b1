"""Pure-jnp oracle for the segmented LRU-stack scan kernel.

The stack-distance engine (:mod:`repro.core.stackdist`) reshapes a set-sorted
access stream into ``L`` independent lanes of ``C`` accesses and walks all
lanes in lock-step: one :func:`lru_stack_step` per in-lane position, ``C``
sequential steps total instead of one per trace element.  Each lane carries a
capped LRU stack — the ``W`` most-recently-used distinct tags of the current
set segment, MRU first, ``-1`` = empty — and every access reports its 0-based
depth in the pre-access stack (``-1`` = absent: cold, or distance >= W).

``seg_flag`` marks set-segment starts; the stack resets there, which is what
makes one lane able to host many (short) per-set segments back to back.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def lru_stack_step(
    stack: jnp.ndarray,      # int32 [W, L] MRU-first along axis 0, -1 = empty
    tag: jnp.ndarray,        # int32 [1, L]
    seg_start: jnp.ndarray,  # bool  [1, L]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Advance L capped LRU stacks (one per lane, ways down axis 0) by one
    access each.

    Returns ``(new_stack, depth [1, L])``.  The update is exact for any
    ways <= W: the capped stack always equals the first W entries of the
    uncapped LRU stack (recency only ever deepens, so truncated entries
    never resurface).  Ways run down the sublanes and lanes across the TPU
    lanes, so the Pallas kernel reads each access as one tile-aligned row;
    the first matching slot is ``min(where(eq, iota, W))``, which Mosaic
    lowers where an integer ``argmax`` is refused.
    """
    W = stack.shape[0]
    stack = jnp.where(seg_start, -1, stack)
    way_ix = jax.lax.broadcasted_iota(jnp.int32, stack.shape, 0)
    pos = jnp.min(jnp.where(stack == tag, way_ix, W), axis=0, keepdims=True)
    found = pos < W
    depth = jnp.where(found, pos, -1)
    # Move the tag to the front: rotate slots [0, idx] down by one, where idx
    # is the tag's slot on a hit and the last slot (LRU eviction) on a miss.
    idx = jnp.where(found, pos, W - 1)
    shifted = jnp.where(way_ix == 0, tag, jnp.roll(stack, 1, axis=0))
    new = jnp.where(way_ix <= idx, shifted, stack)
    return new, depth


@jax.jit
def stack_scan_ref(
    tags: jnp.ndarray,        # int32 [L, C]
    seg_flags: jnp.ndarray,   # bool  [L, C]
    init_stack: jnp.ndarray,  # int32 [L, W]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Walk C accesses per lane.  Returns (depths int32 [L, C], final [L, W])."""

    def step(stack, inp):
        t, f = inp
        return lru_stack_step(stack, t[None], f[None])

    final, depths = jax.lax.scan(step, init_stack.T, (tags.T, seg_flags.T))
    return depths[:, 0].T, final.T
