"""Oracles for the TLB-simulation kernels: the LRU state layout and the
pure-JAX scans.

The scans are the bit-exactness references of :mod:`repro.kernels.tlb_sim`
and of the host-side simulators in :mod:`repro.core.tlbsim`, which import
them from here.  :func:`padded_tlb_state` and the poison values define the
carried state that every LRU backend shares, the Pallas kernels included.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("total_sets", "ways"))
def _scan_tlb(set_idx: jnp.ndarray, tag: jnp.ndarray, total_sets: int, ways: int):
    """Sequential LRU simulation.  Returns per-access hit bits."""
    tags0 = jnp.full((total_sets, ways), -1, dtype=jnp.int32)
    last0 = jnp.zeros((total_sets, ways), dtype=jnp.int32)

    def step(state, inp):
        tags, last = state
        s, t, now = inp
        row_t = tags[s]
        row_l = last[s]
        hit_vec = row_t == t
        hit = jnp.any(hit_vec)
        way = jnp.where(hit, jnp.argmax(hit_vec), jnp.argmin(row_l))
        tags = tags.at[s, way].set(t)
        last = last.at[s, way].set(now)
        return (tags, last), hit

    n = set_idx.shape[0]
    now = jnp.arange(1, n + 1, dtype=jnp.int32)
    (_, _), hits = jax.lax.scan(step, (tags0, last0), (set_idx, tag, now))
    return hits


_POISON_TAG = -2          # never matches a real tag (tags are >= 0, empty = -1)
_POISON_LAST = 2**31 - 1  # argmin never selects a poisoned way (real last <= N)
# (also used by the Pallas kernels, repro.kernels.tlb_sim.kernel and
# repro.kernels.system_sim.kernel)


def padded_tlb_state(
    num_cfgs: int, total_sets: int, ways: int, valid_ways: Tuple[int, ...]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Initial stacked (tags, last) for a batch of configs padded to a common
    ``(total_sets, ways)`` envelope.

    Ways beyond config ``b``'s ``valid_ways[b]`` are *poisoned*: their tag can
    never match (real tags are non-negative, empty ways hold -1) and their
    last-use stamp is so large that LRU replacement never selects them, so the
    padded simulation is bit-identical to each config's unpadded one.  Padded
    *sets* need no poisoning — a config's set indices never reach them.
    """
    vw = jnp.asarray(valid_ways, jnp.int32)[:, None, None]
    way_ix = jax.lax.broadcasted_iota(jnp.int32, (num_cfgs, total_sets, ways), 2)
    pad = way_ix >= vw
    tags0 = jnp.where(pad, _POISON_TAG, -1).astype(jnp.int32)
    last0 = jnp.where(pad, _POISON_LAST, 0).astype(jnp.int32)
    return tags0, last0


@functools.partial(jax.jit, static_argnames=("total_sets", "ways", "valid_ways"))
def _scan_tlb_batched(
    set_idx: jnp.ndarray,   # int32 [B, N]
    tag: jnp.ndarray,       # int32 [B, N]
    total_sets: int,        # padded envelope (max over configs)
    ways: int,              # padded envelope (max over configs)
    valid_ways: Tuple[int, ...],
):
    """Batched sequential LRU simulation: B configs advance in lock-step
    through ONE scan over the trace.  Returns hit bits [B, N].

    Per-config semantics are bit-identical to :func:`_scan_tlb` on that
    config's own geometry (see :func:`padded_tlb_state` for why padding is
    invisible)."""
    tags0, last0 = padded_tlb_state(set_idx.shape[0], total_sets, ways, valid_ways)

    def probe(tags_b, last_b, s, t, now):
        row_t = tags_b[s]
        row_l = last_b[s]
        hit_vec = row_t == t
        hit = jnp.any(hit_vec)
        way = jnp.where(hit, jnp.argmax(hit_vec), jnp.argmin(row_l))
        tags_b = tags_b.at[s, way].set(t)
        last_b = last_b.at[s, way].set(now)
        return tags_b, last_b, hit

    def step(state, inp):
        tags, last = state
        s, t, now = inp
        tags, last, hit = jax.vmap(probe, in_axes=(0, 0, 0, 0, None))(
            tags, last, s, t, now
        )
        return (tags, last), hit

    n = set_idx.shape[1]
    now = jnp.arange(1, n + 1, dtype=jnp.int32)
    (_, _), hits = jax.lax.scan(step, (tags0, last0), (set_idx.T, tag.T, now))
    return hits.T


@jax.jit
def _scan_tlb_batched_carry(
    set_idx: jnp.ndarray,   # int32 [B, L] one trace chunk
    tag: jnp.ndarray,       # int32 [B, L]
    tags0: jnp.ndarray,     # int32 [B, TS, W] carried state in
    last0: jnp.ndarray,     # int32 [B, TS, W]
    now0: jnp.ndarray,      # int32 scalar: accesses consumed before this chunk
):
    """Chunk-resumable :func:`_scan_tlb_batched`: explicit carried state.

    The caller owns the initial state (:func:`padded_tlb_state`) and the
    global access counter; feeding a trace in chunks through this scan is
    bit-identical to one monolithic ``_scan_tlb_batched`` pass because the
    carried (tags, last) and the ``now0``-offset timestamps are exactly the
    mid-scan state of the single pass.  Returns ``(hits [B, L], tags, last)``.
    """

    def probe(tags_b, last_b, s, t, now):
        row_t = tags_b[s]
        row_l = last_b[s]
        hit_vec = row_t == t
        hit = jnp.any(hit_vec)
        way = jnp.where(hit, jnp.argmax(hit_vec), jnp.argmin(row_l))
        tags_b = tags_b.at[s, way].set(t)
        last_b = last_b.at[s, way].set(now)
        return tags_b, last_b, hit

    def step(state, inp):
        tags, last = state
        s, t, now = inp
        tags, last, hit = jax.vmap(probe, in_axes=(0, 0, 0, 0, None))(
            tags, last, s, t, now
        )
        return (tags, last), hit

    n = set_idx.shape[1]
    now = now0.astype(jnp.int32) + jnp.arange(1, n + 1, dtype=jnp.int32)
    (tags, last), hits = jax.lax.scan(
        step, (tags0, last0), (set_idx.T, tag.T, now))
    return hits.T, tags, last


def tlb_sim_ref(set_idx: jnp.ndarray, tag: jnp.ndarray, total_sets: int, ways: int) -> jnp.ndarray:
    """Per-access hit bits (bool) for a set-associative LRU structure."""
    return _scan_tlb(set_idx, tag, total_sets, ways)


def tlb_sim_batched_ref(
    set_idx: jnp.ndarray,
    tag: jnp.ndarray,
    total_sets: int,
    ways: int,
    valid_ways: Tuple[int, ...],
) -> jnp.ndarray:
    """Hit bits (bool [B, N]) for B configs advancing through one trace pass."""
    return _scan_tlb_batched(set_idx, tag, total_sets, ways, valid_ways)


def tlb_sim_batched_carry_ref(
    set_idx: jnp.ndarray,
    tag: jnp.ndarray,
    tags: jnp.ndarray,
    last: jnp.ndarray,
    now0,
):
    """Chunk-resumable batched scan: (hits [B, L], tags', last')."""
    return _scan_tlb_batched_carry(set_idx, tag, tags, last, jnp.asarray(now0))
