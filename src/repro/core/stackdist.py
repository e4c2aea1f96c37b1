"""Exact sort-based stack-distance engine for set-associative LRU sweeps.

The sequential LRU simulators in :mod:`repro.core.tlbsim` replay a trace one
access at a time — ``N`` scan steps no matter how wide the config batch,
which is the worst possible shape for both XLA and the Pallas TPU path.  For
pure-LRU structures the sequential state is unnecessary: an access hits a
``w``-way set **iff fewer than w distinct tags mapped to that set since the
same tag's previous occurrence** (the classic stack algorithm of
Mattson et al.; the same trace-driven methodology the paper uses in §6.2, and
the standard trick in translation studies that sweep huge design spaces —
Picorel et al. "Near-Memory Address Translation", Kanellopoulos et al.
"Utopia").  So exact per-access hit bits for *every* associativity fall out
of one data-parallel reuse-depth computation per set-mapping.

Pipeline (no O(N)-sequential scan anywhere):

1. **sort by set** (stable numpy argsort — radix, O(N)): the trace becomes
   contiguous per-set segments, trace order preserved inside each segment;
2. **lane-blocked segmented stack scan**: the set-sorted stream is reshaped
   into ``L = N/C`` lanes of ``C`` accesses and all lanes advance capped LRU
   stacks (the ``W`` most-recent distinct tags of the current segment) in
   lock-step — ``C`` sequential steps instead of ``N``.  Cross-lane carry is
   restored by composing per-lane *stack effects* (a short prefix pass over
   lane finals) and re-walking with the true carry-in.  The per-step update
   and the TPU kernel live in :mod:`repro.kernels.stackdist`;
3. **depth -> hits**: an access at stack depth ``d`` hits every ``ways > d``
   geometry sharing the set-mapping, so one pass per (sets, partitions,
   page_shift) bucket serves an entire sweep axis (the grouping layer in
   :mod:`repro.core.sweep` exploits this).

Exactness: a capped stack always equals the first ``W`` entries of the
uncapped LRU stack (recency only deepens, truncated entries never
resurface), and composing capped effects preserves that prefix — so hit bits
are **bit-identical** to :func:`repro.core.tlbsim.simulate_tlb` for every
``ways <= W`` (tests/test_stackdist.py asserts this across the property
grid).  The sequential scans remain the oracle path.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.stackdist import stack_scan

__all__ = [
    "STACKDIST_INF",
    "AUTO_MAX_WAYS",
    "auto_eligible",
    "MAX_CAP",
    "prev_occurrence",
    "stack_depths",
    "stack_depths_batched",
    "reuse_distances",
    "hits_from_depths",
]

# "Infinite" reuse distance: the tag was never seen before in its set.
STACKDIST_INF = np.int32(np.iinfo(np.int32).max)

# `auto` prefers the stackdist backend only when every spec's associativity is
# at most this: the scan state is [lanes, W], so huge fully-associative
# geometries would trade the N-step scan for a W-wide one.
AUTO_MAX_WAYS = 16


def auto_eligible(ways) -> bool:
    """May ``"auto"`` pick this engine for LRU structures of these
    associativities?  A memory-shape limit of the capped stack, not a
    measured preference."""
    return max(ways) <= AUTO_MAX_WAYS

# Hard cap: beyond this the capped-stack state stops being "small" in the
# sense the engine is built around; use the sequential reference instead.
MAX_CAP = 256

_PAD_TAG = -2  # never matches a real tag (>= 0) nor an empty slot (-1)

# Chunk the (groups x padded-trace) workspace so a wide sweep (e.g. fig4's
# 60 specs) doesn't materialise gigabytes of lane-blocked arrays at once.
_CHUNK_ELEMS = 1 << 25


# ---------------------------------------------------------------------------
# Host-side layout preparation (numpy; cheap radix sorts, no scans).
# ---------------------------------------------------------------------------

def prev_occurrence(set_idx: np.ndarray, tag: np.ndarray) -> np.ndarray:
    """Index of the previous access to the same (set, tag), -1 if none.

    One stable lexsort by (set, tag): equal keys become adjacent in trace
    order, so each access's predecessor is its sorted neighbour.
    """
    n = set_idx.shape[0]
    prev = np.full(n, -1, np.int64)
    if n == 0:
        return prev
    order = np.lexsort((tag, set_idx))
    s, t = set_idx[order], tag[order]
    same = (s[1:] == s[:-1]) & (t[1:] == t[:-1])
    prev[order[1:][same]] = order[:-1][same]
    return prev


def _set_layout(set_idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(order, seg_flag): stable set-sort permutation (trace order preserved
    within each set) and segment-start flags in sorted order."""
    n = set_idx.shape[0]
    order = np.argsort(set_idx, kind="stable")  # radix for integer keys
    counts = np.bincount(set_idx)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    seg_flag = np.zeros(n, bool)
    seg_flag[starts[counts > 0]] = True
    return order, seg_flag


# ---------------------------------------------------------------------------
# Stack-effect composition across lanes.
# ---------------------------------------------------------------------------

def _merge_effects(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Stack after running sequence A then sequence B, given each sequence's
    final stack from empty: B's distinct tags (MRU side) followed by A's tags
    not in B, truncated to W.  Safe under capping: dropped entries could only
    ever get deeper."""
    W = a.shape[-1]
    in_b = (a[..., :, None] == b[..., None, :]).any(-1)
    a_kept = jnp.where(in_b | (a < 0), -1, a)
    c = jnp.concatenate([b, a_kept], axis=-1)                  # [..., 2W]
    valid = c >= 0
    pos = jnp.cumsum(valid, axis=-1) - 1
    onehot = (pos[..., None] == jnp.arange(W)) & valid[..., None]
    return jnp.max(jnp.where(onehot, c[..., None], -1), axis=-2)


@jax.jit
def _lane_prefix(finals: jnp.ndarray, has_start: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix of lane effects along the lane-block axis.

    finals [G, NB, W] (per-lane final stacks from empty), has_start [G, NB]
    (lane contains a segment start => earlier lanes cannot influence it).
    Returns the carry-in stack for each lane.  NB sequential steps of [G, W]
    work — negligible next to the lane walks.
    """
    G, NB, W = finals.shape

    def step(carry, inp):
        s, f = inp
        new = jnp.where(f[:, None], s, _merge_effects(carry, s))
        return new, carry

    init = jnp.full((G, W), -1, jnp.int32)
    _, carries = jax.lax.scan(
        step, init, (finals.swapaxes(0, 1), has_start.swapaxes(0, 1))
    )
    return carries.swapaxes(0, 1)


# ---------------------------------------------------------------------------
# Core depth computation.
# ---------------------------------------------------------------------------

def _depths_layout(
    tags_l: np.ndarray,       # int32 [G, NP] set-sorted tags, padded
    seg_l: np.ndarray,        # bool  [G, NP] segment starts, padded
    cap: int,
    kernel_mode: str,
    block: int,
) -> np.ndarray:
    """Capped stack depths for G set-sorted (padded) streams, [G, NP]."""
    G, NP = tags_l.shape
    nb = NP // block
    tags_b = jnp.asarray(tags_l.reshape(G * nb, block))
    seg_b = jnp.asarray(seg_l.reshape(G * nb, block))
    empty = jnp.full((G * nb, cap), -1, jnp.int32)
    # Phase 1: per-lane effects from empty; phase 2: re-walk with true carry.
    _, finals = stack_scan(tags_b, seg_b, empty, kernel_mode=kernel_mode)
    carries = _lane_prefix(
        finals.reshape(G, nb, cap),
        jnp.asarray(seg_l.reshape(G, nb, block).any(axis=2)),
    ).reshape(G * nb, cap)
    depths, _ = stack_scan(tags_b, seg_b, carries, kernel_mode=kernel_mode)
    return np.asarray(depths).reshape(G, NP)


def stack_depths_batched(
    set_b: np.ndarray,        # int  [G, N] set-index streams (one per mapping)
    tag_b: np.ndarray,        # int  [G, N] tag streams
    *,
    cap: int,
    kernel_mode: str = "auto",
    block: int = 1024,
) -> np.ndarray:
    """Per-access LRU stack depth (trace order) for G set-mappings at once.

    Returns int32 [G, N]: 0-based depth of each access's tag in its set's
    pre-access LRU stack, or -1 when the tag is not among the ``cap`` most
    recent distinct tags (cold miss, or true distance >= cap).  An access
    hits a ``w``-way set iff ``0 <= depth < w`` for any ``w <= cap``.
    """
    if cap < 1:
        raise ValueError(f"cap={cap}: must be >= 1")
    if cap > MAX_CAP:
        raise ValueError(
            f"cap={cap} exceeds MAX_CAP={MAX_CAP}; the capped-stack engine is "
            "built for small associativities — use the sequential reference "
            "backend for huge fully-associative geometries"
        )
    G, n = set_b.shape
    if n == 0:
        return np.empty((G, 0), np.int32)
    # Tags are carried as int32 with -1 (empty slot) and -2 (padding) as
    # sentinels; anything outside [0, 2^31) would silently alias on the cast.
    if tag_b.min() < 0 or int(tag_b.max()) >= 2**31:
        raise ValueError("tags must be in [0, 2**31) to fit int32 stack slots")
    block = max(32, min(block, 1 << 14))
    n_pad = -(-n // block) * block

    tags_l = np.full((G, n_pad), _PAD_TAG, np.int32)
    seg_l = np.zeros((G, n_pad), bool)
    orders = []
    for g in range(G):
        order, seg = _set_layout(set_b[g])
        tags_l[g, :n] = tag_b[g][order]
        seg_l[g, :n] = seg
        if n_pad > n:
            seg_l[g, n] = True  # padding forms its own throwaway segment
        orders.append(order)

    out = np.empty((G, n), np.int32)
    g_chunk = max(1, min(G, _CHUNK_ELEMS // n_pad))
    for lo in range(0, G, g_chunk):
        hi = min(lo + g_chunk, G)
        tl, sl = tags_l[lo:hi], seg_l[lo:hi]
        if hi - lo < g_chunk and G > g_chunk:
            # Keep the compiled shape stable across chunks: pad the remainder
            # chunk by repeating its last stream (results discarded).
            reps = g_chunk - (hi - lo)
            tl = np.concatenate([tl, np.repeat(tl[-1:], reps, axis=0)])
            sl = np.concatenate([sl, np.repeat(sl[-1:], reps, axis=0)])
        d = _depths_layout(tl, sl, cap, kernel_mode, block)[: hi - lo]
        for g in range(lo, hi):
            out[g, orders[g]] = d[g - lo, :n]
    return out


def stack_depths(
    set_idx: np.ndarray,
    tag: np.ndarray,
    *,
    cap: int,
    kernel_mode: str = "auto",
    block: int = 1024,
) -> np.ndarray:
    """Single-stream :func:`stack_depths_batched`."""
    return stack_depths_batched(
        set_idx[None], tag[None], cap=cap, kernel_mode=kernel_mode, block=block
    )[0]


def hits_from_depths(depths: np.ndarray, ways: int) -> np.ndarray:
    """Hit bits for a ``ways``-way LRU structure (requires ways <= the cap
    the depths were computed with)."""
    return (depths >= 0) & (depths < ways)


def reuse_distances(
    set_idx: np.ndarray,
    tag: np.ndarray,
    *,
    cap: int = AUTO_MAX_WAYS,
    kernel_mode: str = "auto",
    block: int = 1024,
) -> np.ndarray:
    """Exact set-local LRU stack distances, clipped at ``cap``.

    Returns int32 [N]: the number of distinct other tags that mapped to the
    access's set since the same tag's previous occurrence — exact when
    ``< cap``, ``cap`` when the true (finite) distance is >= cap, and
    :data:`STACKDIST_INF` for cold accesses (no previous occurrence, i.e.
    infinite distance).  ``distance < STACKDIST_INF`` iff the access is a
    reuse; ``distance < w`` iff the access hits a w-way set (w <= cap).
    """
    depth = stack_depths(set_idx, tag, cap=cap, kernel_mode=kernel_mode, block=block)
    cold = prev_occurrence(set_idx, tag) < 0
    return np.where(
        depth >= 0, depth, np.where(cold, STACKDIST_INF, np.int32(cap))
    ).astype(np.int32)
