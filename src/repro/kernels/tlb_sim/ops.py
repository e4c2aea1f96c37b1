"""Public TLB-simulation ops with kernel-mode dispatch."""
from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from repro.kernels.common import resolve_mode
from repro.kernels.tlb_sim.kernel import (
    tlb_sim_batched_pallas,
    tlb_sim_batched_pallas_carry,
    tlb_sim_pallas,
)
from repro.kernels.tlb_sim.ref import (
    tlb_sim_batched_carry_ref,
    tlb_sim_batched_ref,
    tlb_sim_ref,
)

__all__ = ["tlb_sim", "tlb_sim_batched", "tlb_sim_batched_carry"]


def tlb_sim(
    set_idx: jnp.ndarray,
    tag: jnp.ndarray,
    total_sets: int,
    ways: int,
    *,
    block: int = 512,
    kernel_mode: str = "auto",
) -> jnp.ndarray:
    mode = resolve_mode(kernel_mode)
    if mode == "reference":
        return tlb_sim_ref(set_idx, tag, total_sets, ways)
    return tlb_sim_pallas(
        set_idx, tag, total_sets, ways,
        block=block, interpret=(mode == "pallas_interpret"),
    )


def tlb_sim_batched(
    set_idx: jnp.ndarray,   # int32 [B, N]
    tag: jnp.ndarray,       # int32 [B, N]
    total_sets: int,        # padded envelope over configs
    ways: int,              # padded envelope over configs
    valid_ways: Optional[Sequence[int]] = None,
    *,
    block: int = 512,
    kernel_mode: str = "auto",
) -> jnp.ndarray:
    """Batched-config TLB simulation (the sweep-engine hot loop): B configs'
    LRU states advance together through ONE pass over the trace.  Returns
    hit bits bool [B, N]; bit-identical per config to :func:`tlb_sim` on
    that config's own (unpadded) geometry."""
    vw = tuple(valid_ways) if valid_ways is not None else (ways,) * set_idx.shape[0]
    mode = resolve_mode(kernel_mode)
    if mode == "reference":
        return tlb_sim_batched_ref(set_idx, tag, total_sets, ways, vw)
    return tlb_sim_batched_pallas(
        set_idx, tag, total_sets, ways, vw,
        block=block, interpret=(mode == "pallas_interpret"),
    )


def tlb_sim_batched_carry(
    set_idx: jnp.ndarray,   # int32 [B, L] one trace chunk
    tag: jnp.ndarray,       # int32 [B, L]
    tags: jnp.ndarray,      # int32 [B, TS, W] carried state (caller-owned)
    last: jnp.ndarray,      # int32 [B, TS, W]
    now0: int,              # accesses consumed before this chunk
    *,
    block: int = 512,
    kernel_mode: str = "auto",
):
    """Chunk-resumable :func:`tlb_sim_batched`: run ONE trace chunk against
    caller-owned carried LRU state (initialise with
    :func:`repro.kernels.tlb_sim.ref.padded_tlb_state`) and the global access counter
    ``now0``.  Returns ``(hits bool [B, L], tags', last')``; feeding chunks
    sequentially is bit-identical to the monolithic op — in any mode, and
    across mode *changes* at chunk boundaries (the degradation ladder), since
    all backends share one state layout and timestamp rule.

    State layout contract: the carried state must include one spare *parked*
    set row at index ``TS - 1`` that no real access ever indexes.  Pallas
    chunks whose length is not a block multiple are padded with accesses into
    that row — their stamps live only there, so mid-stream padding is
    unobservable (the padded hit bits are dropped)."""
    mode = resolve_mode(kernel_mode)
    if mode == "reference":
        return tlb_sim_batched_carry_ref(set_idx, tag, tags, last, now0)
    n = int(set_idx.shape[1])
    pad = (-n) % min(block, n) if n else 0
    if pad:
        parked = int(tags.shape[1]) - 1
        set_idx = jnp.concatenate(
            [set_idx, jnp.full((set_idx.shape[0], pad), parked, set_idx.dtype)],
            axis=1)
        tag = jnp.concatenate(
            [tag, jnp.zeros((tag.shape[0], pad), tag.dtype)], axis=1)
    hits, tags, last = tlb_sim_batched_pallas_carry(
        set_idx, tag, tags, last, now0,
        block=block, interpret=(mode == "pallas_interpret"),
    )
    return hits[:, :n], tags, last
