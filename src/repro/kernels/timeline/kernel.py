"""Cycle-approximate timeline simulation as a Pallas TPU kernel.

Same architecture as the ``tlb_sim`` trace kernel: the full queueing state
(per-accelerator issue/MSHR windows, per-partition TLB port free times, DRAM
bank free times — a few KB at any realistic configuration) stays **resident
in VMEM scratch** for the entire trace.  TPU grids execute sequentially, so
scratch persists across grid steps while each step streams one trace block
(the eight per-access input columns) HBM->SMEM and writes the block's
(latency, overhead, done) columns back.

The per-access update is
:func:`repro.kernels.timeline.ref.timeline_step_dyn` — *shared* with the
batched ``lax.scan`` reference, so the two paths are bit-identical by
construction.  Inside the kernel each sim's state is read from scratch as
whole (small) rows and planes, advanced functionally, and stored back; the
per-access inputs and outputs are SMEM scalars.  The access loop is
inherently serial (queue state carries a dependency) but each step is a
handful of one-hot selects and reductions plus a ports-wide min.

``timeline_sim_batched_pallas`` adds the **sim batch dimension** for the
``sweep_timeline`` engine (:mod:`repro.core.timeline`): B sims' queueing
states are stacked as the leading VMEM scratch axis (padded to the batch's
common resource envelope, poisoned per ``ref.timeline_init_state_batched``),
each grid step fetches one trace block once for all sims, and the
per-sim configuration rides along as packed ``fparams``/``iparams`` rows
consumed by the shared :func:`~repro.kernels.timeline.ref.timeline_step_dyn`.
The sim axis is what gives this kernel something to amortize — a single
sequential sim is better served by the scan reference (see the ``"auto"``
dispatch note in ``ops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import smem_block
from repro.kernels.timeline.ref import (
    PORT_POISON,
    TimelineParams,
    pack_params,
    timeline_step_dyn,
)



def _sim_step(b, j, streams, fp_ref, ip_ref, outs, scratch):
    """Advance sim ``b`` by its access ``j``: the shared
    :func:`timeline_step_dyn` on that sim's VMEM-resident state, with the
    per-access inputs, parameters and outputs as SMEM scalars.  The
    per-accelerator and per-bank vectors are read and written as ``[1, n]``
    rows (a dynamic sublane offset), the 2-D queues as whole planes."""
    acc_scr, mshr_scr, cnt_scr, port_scr, bank_scr = scratch
    row = (pl.ds(b, 1), slice(None))
    state = (acc_scr[row], mshr_scr[b], cnt_scr[row], port_scr[b],
             bank_scr[row])
    inp = tuple(ref[b, j] for ref in streams)
    fp = [fp_ref[b, k] for k in range(fp_ref.shape[1])]
    ip = [ip_ref[b, k] for k in range(ip_ref.shape[1])]
    (acc, mshr, cnt, port, bank), ys = timeline_step_dyn(state, inp, fp, ip)
    acc_scr[row] = acc
    mshr_scr[b] = mshr
    cnt_scr[row] = cnt
    port_scr[b] = port
    bank_scr[row] = bank
    for ref, y in zip(outs, ys):
        ref[b, j] = y


def _access_loop(streams, fp_ref, ip_ref, outs, scratch, *, block: int,
                 num_sims: int):
    """Advance every sim through the grid step's ``block`` accesses."""

    def body(j, _):
        def per_sim(b, _):
            _sim_step(b, j, streams, fp_ref, ip_ref, outs, scratch)
            return 0

        jax.lax.fori_loop(0, num_sims, per_sim, 0)
        return 0

    jax.lax.fori_loop(0, block, body, 0)


def _timeline_batched_kernel(
    a_ref, p_ref, bd_ref, bp_ref,   # int32 [B, BLK] ids
    c_ref, th_ref, mh_ref,          # int32 [B, BLK] hit bits
    pen_ref,                        # f32   [B, BLK] serialized penalty
    fp_ref,                         # f32   [B, 8]  per-sim latency table
    ip_ref,                         # int32 [B, 7]  per-sim flags/counts
    lat_ref, ov_ref, done_ref,      # f32   [B, BLK] outputs
    acc_scr, mshr_scr, cnt_scr, port_scr, bank_scr,  # stacked VMEM state
    *,
    block: int,
    num_sims: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        mshr_scr[...] = jnp.zeros_like(mshr_scr)
        cnt_scr[...] = jnp.zeros_like(cnt_scr)
        # Port columns beyond each sim's own tlb_ports are poisoned as
        # always-busy so the earliest-free argmin never selects them (the
        # exact init of ref.timeline_init_state_batched).
        col = jax.lax.broadcasted_iota(jnp.int32, port_scr.shape[1:], 1)

        def poison(b, _):
            port_scr[b] = jnp.where(col < ip_ref[b, 5], jnp.float32(0.0),
                                    jnp.float32(PORT_POISON))
            return 0

        jax.lax.fori_loop(0, num_sims, poison, 0)
        bank_scr[...] = jnp.zeros_like(bank_scr)

    _access_loop(
        (a_ref, p_ref, bd_ref, bp_ref, c_ref, th_ref, mh_ref, pen_ref),
        fp_ref, ip_ref, (lat_ref, ov_ref, done_ref),
        (acc_scr, mshr_scr, cnt_scr, port_scr, bank_scr),
        block=block, num_sims=num_sims)


def _timeline_batched_carry_kernel(
    a_ref, p_ref, bd_ref, bp_ref,   # int32 [B, BLK] ids
    c_ref, th_ref, mh_ref,          # int32 [B, BLK] hit bits
    pen_ref,                        # f32   [B, BLK]
    fp_ref,                         # f32   [B, 8]
    ip_ref,                         # int32 [B, 7]
    acc_in, mshr_in, cnt_in, port_in, bank_in,       # carried state in
    lat_ref, ov_ref, done_ref,      # f32   [B, BLK] outputs
    acc_scr, mshr_scr, cnt_scr, port_scr, bank_scr,  # carried state out =
    *,                                               # working state
    block: int,
    num_sims: int,
):
    """Chunk-resumable variant of :func:`_timeline_batched_kernel`: the five
    state-out refs (constant-index BlockSpecs, VMEM-resident across the
    sequential grid) are the working state, loaded from the carried state-in
    at grid step 0 — the caller owns the zero/poison init.  Queueing state
    holds absolute times, so no access counter is threaded; chunked execution
    is bit-identical to the monolithic kernel."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _load():
        acc_scr[...] = acc_in[...]
        mshr_scr[...] = mshr_in[...]
        cnt_scr[...] = cnt_in[...]
        port_scr[...] = port_in[...]
        bank_scr[...] = bank_in[...]

    _access_loop(
        (a_ref, p_ref, bd_ref, bp_ref, c_ref, th_ref, mh_ref, pen_ref),
        fp_ref, ip_ref, (lat_ref, ov_ref, done_ref),
        (acc_scr, mshr_scr, cnt_scr, port_scr, bank_scr),
        block=block, num_sims=num_sims)


_STATE_DTYPES = (jnp.float32, jnp.float32, jnp.int32, jnp.float32,
                 jnp.float32)


def _specs(B: int, block: int):
    """Per-access streams and the per-sim parameter rows live in SMEM (the
    kernel reads and writes them one scalar at a time)."""
    stream = pl.BlockSpec((B, block), lambda i: (0, i), memory_space=pltpu.SMEM)

    def params(c):
        return pl.BlockSpec((B, c), lambda i: (0, 0), memory_space=pltpu.SMEM)

    return [stream] * 8 + [params(8), params(7)], [stream] * 3


def _cast_inputs(accel, part, bank_data, bank_pte, cache_hit, tlb_hit,
                 mem_hit, pen, fparams, iparams):
    ints = (accel, part, bank_data, bank_pte, cache_hit, tlb_hit, mem_hit)
    return (*(x.astype(jnp.int32) for x in ints), pen.astype(jnp.float32),
            fparams.astype(jnp.float32), iparams.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def timeline_sim_batched_pallas_carry(
    accel: jnp.ndarray,      # int32 [B, L] one trace chunk
    part: jnp.ndarray,
    bank_data: jnp.ndarray,
    bank_pte: jnp.ndarray,
    cache_hit: jnp.ndarray,
    tlb_hit: jnp.ndarray,
    mem_hit: jnp.ndarray,
    pen: jnp.ndarray,        # f32 [B, L]
    fparams: jnp.ndarray,    # f32 [B, 8]
    iparams: jnp.ndarray,    # int32 [B, 7]
    state,                   # 5-tuple carried queueing state
    *,
    block: int = 512,
    interpret: bool = False,
):
    """Chunk-resumable batched timeline simulation; returns
    ``((latency, overhead, done), state')``."""
    B, n = accel.shape
    block = min(block, n)
    assert n % block == 0, f"chunk length {n} must be a multiple of block {block}"
    block = smem_block(block, B, 11)
    grid = (n // block,)
    in_specs, out_specs = _specs(B, block)

    def whole(arr):
        return pl.BlockSpec(arr.shape, lambda i: (0,) * arr.ndim)

    outs = pl.pallas_call(
        functools.partial(
            _timeline_batched_carry_kernel, block=block, num_sims=B),
        grid=grid,
        in_specs=in_specs + [whole(s) for s in state],
        out_specs=out_specs + [whole(s) for s in state],
        out_shape=[jax.ShapeDtypeStruct((B, n), jnp.float32)] * 3
        + [jax.ShapeDtypeStruct(s.shape, d)
           for s, d in zip(state, _STATE_DTYPES)],
        interpret=interpret,
        name="timeline_carry",
    )(*_cast_inputs(accel, part, bank_data, bank_pte, cache_hit, tlb_hit,
                    mem_hit, pen, fparams, iparams),
      *(s.astype(d) for s, d in zip(state, _STATE_DTYPES)))
    return tuple(outs[:3]), tuple(outs[3:])


@functools.partial(
    jax.jit, static_argnames=("envelope", "block", "interpret"))
def timeline_sim_batched_pallas(
    accel: jnp.ndarray,      # int32 [B, N]
    part: jnp.ndarray,
    bank_data: jnp.ndarray,
    bank_pte: jnp.ndarray,
    cache_hit: jnp.ndarray,
    tlb_hit: jnp.ndarray,
    mem_hit: jnp.ndarray,
    pen: jnp.ndarray,        # f32 [B, N]
    fparams: jnp.ndarray,    # f32 [B, 8]
    iparams: jnp.ndarray,    # int32 [B, 7]
    envelope,                # (A, M, P, T, D) resource envelope
    *,
    block: int = 512,
    interpret: bool = False,
):
    """B-sim batched timeline simulation: every sim's queueing state is
    stacked on the leading VMEM scratch axis and each grid step streams one
    trace block (all sims' per-access columns) once.  Returns
    (latency, overhead, done), each f32 [B, N]; per sim bit-identical to
    the scan reference on that sim's own configuration (they all run one
    shared step)."""
    B, n = accel.shape
    A, M, P, T, D = envelope
    block = min(block, n)
    assert n % block == 0, f"trace length {n} must be a multiple of block {block}"
    block = smem_block(block, B, 11)
    grid = (n // block,)
    in_specs, out_specs = _specs(B, block)
    outs = pl.pallas_call(
        functools.partial(_timeline_batched_kernel, block=block, num_sims=B),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((B, n), jnp.float32)] * 3,
        scratch_shapes=[
            pltpu.VMEM((B, A), jnp.float32),
            pltpu.VMEM((B, A, M), jnp.float32),
            pltpu.VMEM((B, A), jnp.int32),
            pltpu.VMEM((B, P, T), jnp.float32),
            pltpu.VMEM((B, D), jnp.float32),
        ],
        interpret=interpret,
        name="timeline_batched",
    )(*_cast_inputs(accel, part, bank_data, bank_pte, cache_hit, tlb_hit,
                    mem_hit, pen, fparams, iparams))
    return tuple(outs)


@functools.partial(jax.jit, static_argnames=("params", "block", "interpret"))
def timeline_sim_pallas(
    accel: jnp.ndarray,
    part: jnp.ndarray,
    bank_data: jnp.ndarray,
    bank_pte: jnp.ndarray,
    cache_hit: jnp.ndarray,
    tlb_hit: jnp.ndarray,
    mem_hit: jnp.ndarray,
    pen: jnp.ndarray,
    params: TimelineParams,
    *,
    block: int = 512,
    interpret: bool = False,
):
    """One sim with static parameters: the batched kernel on a batch of one
    (its parameters packed as data).  Returns (latency, overhead, done),
    each f32 [N]."""
    fp, ip = pack_params(params)
    envelope = (params.num_accels, max(params.mshrs, 1),
                max(params.num_partitions, 1), max(params.tlb_ports, 1),
                max(params.dram_banks, 1))
    outs = timeline_sim_batched_pallas(
        *(x[None] for x in (accel, part, bank_data, bank_pte, cache_hit,
                            tlb_hit, mem_hit, pen)),
        jnp.asarray(fp)[None], jnp.asarray(ip)[None], envelope,
        block=block, interpret=interpret)
    return tuple(o[0] for o in outs)
