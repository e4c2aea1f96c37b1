"""Public timeline-simulation ops with kernel-mode dispatch.

``"auto"`` resolution is *batch-aware*: a single sequential simulation gives
the Pallas kernel nothing to amortize (measured 0.87x of the ``lax.scan``
reference in BENCH_sweep.json), so the degenerate batch — ``timeline_sim``,
or ``timeline_sim_batched`` with one sim — always auto-selects the scan
reference; multi-sim batches auto-select the batched kernel on TPU backends.
Explicit ``"pallas"`` / ``"pallas_interpret"`` are honoured as given.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.common import SWEEP_MODES, VALID_MODES, resolve_mode
from repro.kernels.timeline.kernel import (
    timeline_sim_batched_pallas,
    timeline_sim_batched_pallas_carry,
    timeline_sim_pallas,
)
from repro.kernels.timeline.ref import (
    FP_COLS,
    IP_COLS,
    TimelineParams,
    pack_params,
    timeline_init_state_batched,
    timeline_scan_batched_carry_ref,
    timeline_scan_batched_ref,
    timeline_scan_ref,
)

__all__ = ["TimelineParams", "timeline_sim", "timeline_sim_batched",
           "timeline_sim_batched_carry", "timeline_init_state_batched",
           "pack_params", "resolve_timeline_mode", "FP_COLS", "IP_COLS"]


def resolve_timeline_mode(kernel_mode: str, *, batch: int = 1) -> str:
    """Validate and resolve ``kernel_mode`` for the timeline engine.

    Sweep-only backends are rejected loudly (no silent coercion): the
    timeline is not a pure-LRU sweep, so ``"stackdist"`` cannot apply.
    ``"auto"`` resolves through :func:`repro.kernels.common.auto_mode`: the
    scan reference for a single-sim batch, the generic rule otherwise.
    """
    if kernel_mode in SWEEP_MODES and kernel_mode not in VALID_MODES:
        raise ValueError(
            f"kernel_mode={kernel_mode!r} is a sweep_tlb/miss_ratio_curve-only "
            f"backend, not a timeline backend; the timeline engine accepts "
            f"one of {VALID_MODES}")
    return resolve_mode(kernel_mode, engine="sweep_timeline", batch=batch)


def timeline_sim(
    accel: jnp.ndarray,      # int32 [N]
    part: jnp.ndarray,       # int32 [N]
    bank_data: jnp.ndarray,  # int32 [N]
    bank_pte: jnp.ndarray,   # int32 [N]
    cache_hit: jnp.ndarray,  # int32 [N]
    tlb_hit: jnp.ndarray,    # int32 [N]
    mem_hit: jnp.ndarray,    # int32 [N]
    pen: jnp.ndarray,        # f32   [N]
    params: TimelineParams,
    *,
    block: int = 512,
    kernel_mode: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-access (latency, overhead, completion-time) for one trace.

    The Pallas path streams whole blocks; the trace is padded with trailing
    cache hits from accelerator 0 (they read state but complete locally and
    cannot perturb any earlier access), then the padding's outputs dropped.
    """
    mode = resolve_timeline_mode(kernel_mode, batch=1)
    n = int(accel.shape[0])
    if mode == "reference" or n == 0:
        return timeline_scan_ref(
            accel, part, bank_data, bank_pte,
            cache_hit, tlb_hit, mem_hit, pen, params)
    pad = (-n) % min(block, n)
    if pad:
        def pad_i(x, v):
            return jnp.concatenate(
                [x, jnp.full((pad,), v, dtype=x.dtype)])
        accel, part = pad_i(accel, 0), pad_i(part, 0)
        bank_data, bank_pte = pad_i(bank_data, 0), pad_i(bank_pte, 0)
        cache_hit = pad_i(cache_hit, 1)  # padding = local cache hits
        tlb_hit, mem_hit = pad_i(tlb_hit, 1), pad_i(mem_hit, 1)
        pen = pad_i(pen, np.float32(0.0))
    lat, ov, done = timeline_sim_pallas(
        accel, part, bank_data, bank_pte,
        cache_hit, tlb_hit, mem_hit, pen, params,
        block=block, interpret=(mode == "pallas_interpret"))
    return lat[:n], ov[:n], done[:n]


def timeline_sim_batched(
    accel: jnp.ndarray,      # int32 [B, N]
    part: jnp.ndarray,       # int32 [B, N]
    bank_data: jnp.ndarray,  # int32 [B, N]
    bank_pte: jnp.ndarray,   # int32 [B, N]
    cache_hit: jnp.ndarray,  # int32 [B, N]
    tlb_hit: jnp.ndarray,    # int32 [B, N]
    mem_hit: jnp.ndarray,    # int32 [B, N]
    pen: jnp.ndarray,        # f32   [B, N]
    fparams: np.ndarray,     # f32   [B, 8]  (FP_COLS, see pack_params)
    iparams: np.ndarray,     # int32 [B, 7]  (IP_COLS)
    *,
    block: int = 512,
    kernel_mode: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """B-sim batched timeline simulation (the ``sweep_timeline`` hot loop):
    every sim's queueing state advances together through ONE pass over the
    stacked trace.  Returns (latency, overhead, done), each f32 [B, N];
    per sim bit-identical to :func:`timeline_sim` on that sim's own
    configuration.

    ``iparams`` must be a *concrete* array — the resource envelope (max
    num_accels / mshrs / partitions / tlb_ports / dram_banks across sims,
    each floored at 1) is derived from it as a static state shape.
    """
    ip = np.asarray(iparams)
    envelope = tuple(
        max(int(ip[:, c].max()), 1) for c in (2, 3, 4, 5, 6))
    mode = resolve_timeline_mode(kernel_mode, batch=int(accel.shape[0]))
    n = int(accel.shape[1])
    if mode == "reference" or n == 0:
        return timeline_scan_batched_ref(
            accel, part, bank_data, bank_pte,
            cache_hit, tlb_hit, mem_hit, pen,
            jnp.asarray(fparams), jnp.asarray(ip), envelope)
    pad = (-n) % min(block, n)
    if pad:
        def pad_i(x, v):
            return jnp.concatenate(
                [x, jnp.full((x.shape[0], pad), v, dtype=x.dtype)], axis=1)
        accel, part = pad_i(accel, 0), pad_i(part, 0)
        bank_data, bank_pte = pad_i(bank_data, 0), pad_i(bank_pte, 0)
        cache_hit = pad_i(cache_hit, 1)  # padding = local cache hits
        tlb_hit, mem_hit = pad_i(tlb_hit, 1), pad_i(mem_hit, 1)
        pen = pad_i(pen, np.float32(0.0))
    lat, ov, done = timeline_sim_batched_pallas(
        accel, part, bank_data, bank_pte,
        cache_hit, tlb_hit, mem_hit, pen,
        jnp.asarray(fparams), jnp.asarray(ip), envelope,
        block=block, interpret=(mode == "pallas_interpret"))
    return lat[:, :n], ov[:, :n], done[:, :n]


def timeline_sim_batched_carry(
    accel: jnp.ndarray,      # int32 [B, L] one trace chunk
    part: jnp.ndarray,
    bank_data: jnp.ndarray,
    bank_pte: jnp.ndarray,
    cache_hit: jnp.ndarray,
    tlb_hit: jnp.ndarray,
    mem_hit: jnp.ndarray,
    pen: jnp.ndarray,        # f32 [B, L]
    fparams: np.ndarray,     # f32 [B, 8]
    iparams: np.ndarray,     # int32 [B, 7]
    state,                   # 5-tuple carried queueing state
    *,
    block: int = 512,
    kernel_mode: str = "auto",
):
    """Chunk-resumable :func:`timeline_sim_batched`: run ONE trace chunk
    against caller-owned carried queueing state (initialise with
    :func:`timeline_init_state_batched` on the batch's resource envelope).
    Returns ``((latency, overhead, done) f32 [B, L], state')``; chunked
    execution is bit-identical to the monolithic op in any mode and across
    mode changes at chunk boundaries (state layout and step function are
    shared by all backends).  Unlike the monolithic op this does NOT pad the
    chunk — mid-stream padding would perturb accelerator 0's issue clock —
    so a Pallas-mode chunk length must be a block multiple (or a single
    short block, ``L <= block``); the stream layer enforces that.
    """
    ip = np.asarray(iparams)
    mode = resolve_timeline_mode(kernel_mode, batch=int(accel.shape[0]))
    if mode == "reference" or int(accel.shape[1]) == 0:
        return timeline_scan_batched_carry_ref(
            accel, part, bank_data, bank_pte,
            cache_hit, tlb_hit, mem_hit, pen,
            jnp.asarray(fparams), jnp.asarray(ip), tuple(state))
    return timeline_sim_batched_pallas_carry(
        accel, part, bank_data, bank_pte,
        cache_hit, tlb_hit, mem_hit, pen,
        jnp.asarray(fparams), jnp.asarray(ip), tuple(state),
        block=block, interpret=(mode == "pallas_interpret"))
