"""Public batched joint-system op with kernel-mode dispatch.

Mode policy mirrors the timeline engine (PR 4): sweep-only backends are
rejected loudly — the joint pipeline's cache-hit-conditional TLB probes break
the LRU stack-inclusion property, so the exact stack-distance engine cannot
serve it, and silently falling back would misreport which backend produced a
figure.  ``"auto"`` resolves to the batched Pallas kernel on TPU backends and
the batched scan reference elsewhere.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels.common import SWEEP_MODES, VALID_MODES, resolve_mode
from repro.kernels.system_sim.kernel import (
    Share,
    system_sim_batched_pallas,
    system_sim_batched_pallas_carry,
)
from repro.kernels.system_sim.ref import (
    system_sim_batched_carry_ref,
    system_sim_batched_ref,
)

__all__ = ["system_sim_batched", "system_sim_batched_carry",
           "resolve_system_mode"]


def resolve_system_mode(kernel_mode: str) -> str:
    """Validate and resolve ``kernel_mode`` for the joint system sweep.

    ``"stackdist"`` (and any future sweep-only backend) raises: stack
    inclusion does not hold when TLB probes are conditional on cache hits, so
    there is no exact stack-distance execution of the joint pipeline — no
    silent coercion (the PR 4 policy that removed the timeline's).
    """
    if kernel_mode in SWEEP_MODES and kernel_mode not in VALID_MODES:
        raise ValueError(
            f"kernel_mode={kernel_mode!r} is a sweep_tlb/miss_ratio_curve-only "
            f"backend: the joint system sweep's cache-hit-conditional TLB "
            f"probes break the LRU stack-inclusion property, so the "
            f"stack-distance engine cannot serve it; expected one of "
            f"{VALID_MODES}")
    return resolve_mode(kernel_mode, engine="sweep_system")


def system_sim_batched(
    c_set: jnp.ndarray, c_tag: jnp.ndarray,   # int32 [B, N]
    a_set: jnp.ndarray, a_tag: jnp.ndarray,   # int32 [B, N]
    m_set: jnp.ndarray, m_tag: jnp.ndarray,   # int32 [B, N]
    flags: jnp.ndarray,                       # int32 [B, 3]
    geom: Tuple[int, int, int, int, int, int],
    valid: Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]],
    *,
    share: Share,
    block: int = 512,
    kernel_mode: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched-config joint cache + accel-TLB + mem-TLB simulation (the
    ``sweep_system`` hot loop): B configs' three LRU states advance together
    through ONE pass over the trace.  Returns (cache_hit, accel_tlb_hit,
    mem_tlb_hit) bool [B, N]; bit-identical per config to
    :func:`repro.core.tlbsim.simulate_system` on that config's own (unpadded)
    geometry.  ``share`` maps each design to the representative whose
    structures it shares (:mod:`repro.kernels.system_sim.kernel`); the
    Pallas kernel probes only representatives, the scan every design."""
    mode = resolve_system_mode(kernel_mode)
    if mode == "reference":
        bools = tuple(flags[:, c].astype(bool) for c in range(3))
        return system_sim_batched_ref(
            (c_set, c_tag, a_set, a_tag, m_set, m_tag), bools, geom, valid)
    return system_sim_batched_pallas(
        c_set, c_tag, a_set, a_tag, m_set, m_tag, flags, geom, valid,
        share=share, block=block, interpret=(mode == "pallas_interpret"))


def system_sim_batched_carry(
    c_set: jnp.ndarray, c_tag: jnp.ndarray,   # int32 [B, L] one trace chunk
    a_set: jnp.ndarray, a_tag: jnp.ndarray,
    m_set: jnp.ndarray, m_tag: jnp.ndarray,
    flags: jnp.ndarray,                       # int32 [B, 3]
    state,                                    # 6-tuple int32 [B, S, W]
    now0: int,                                # accesses consumed before chunk
    *,
    share: Share,
    n_valid: int,
    block: int = 512,
    kernel_mode: str = "auto",
):
    """Chunk-resumable :func:`system_sim_batched`: run ONE trace chunk
    against caller-owned carried state (three
    :func:`repro.kernels.tlb_sim.ref.padded_tlb_state` pairs) and the global access
    counter.  Returns ``((c, a, m) hit bits bool [B, n_valid], state')``; chunked
    execution is bit-identical to the monolithic op in any mode and across
    mode changes at chunk boundaries.

    State layout contract: each structure's carried state must include one
    spare *parked* set row at its last index that no real access ever
    indexes.  The caller pads the streams: they hold the chunk's
    ``n_valid`` own accesses, then accesses into those rows (tag 0) up to
    a whole number of kernel blocks.  The kernel runs only the blocks that
    hold the chunk's own accesses (stamps of the pads live only in the
    parked rows, their hit bits are dropped), so mid-stream padding is
    unobservable and chunks padded to one length share one compiled
    kernel; the reference mode runs the first ``n_valid`` accesses.  Each
    follower's carried state under the ``share`` map must equal its
    representative's: it does from fresh state on, in any mode."""
    mode = resolve_system_mode(kernel_mode)
    state = tuple(state)
    n = int(n_valid)
    streams = (c_set, c_tag, a_set, a_tag, m_set, m_tag)
    if mode == "reference":
        bools = tuple(flags[:, c].astype(bool) for c in range(3))
        return system_sim_batched_carry_ref(
            tuple(s[:, :n] for s in streams), bools, state, jnp.asarray(now0))
    hits, state = system_sim_batched_pallas_carry(
        *streams, flags, state, now0, n,
        share=share, block=block, interpret=(mode == "pallas_interpret"))
    return tuple(h[:, :n] for h in hits), state
