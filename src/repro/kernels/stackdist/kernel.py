"""Segmented LRU-stack scan as a Pallas TPU kernel.

TPU port of the stack-distance engine's hot loop (:mod:`repro.core.stackdist`):
``L`` lanes each advance a capped LRU stack — the W most-recently-used
distinct tags of the current set segment — through ``C`` in-lane accesses.
The kernel works on the transposed layout: lanes run across the TPU lanes,
ways down the sublanes ([W, L] stacks) and accesses down the rows of the
[C, L] streams, so every block is tile-aligned.  Grid: (lane blocks, row
blocks); each lane block's stacks live in **VMEM scratch** while its rows
stream through, ``ROWS`` accesses per grid step.  The per-step update is a
W-deep vector compare/rotate per lane — no gathers, no sorts.

This is the same role the batched ``tlb_sim`` kernel plays for the scan
backend, but the sequential walk is only ``C`` long (the lane dimension
carries the parallelism), not one step per trace element.

Host-side oracle: :func:`repro.kernels.stackdist.ref.stack_scan_ref`.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import next_multiple
# The per-access update is shared with the reference backend (pure jnp, so it
# lowers in both): one definition keeps the two paths bit-identical forever.
from repro.kernels.stackdist.ref import lru_stack_step

ROWS = 8          # accesses per grid step: one int32 sublane tile
LANE_BLOCK = 2048  # lanes per grid step, at most


def _stack_scan_kernel(
    init_ref,                 # int32 [W, LB] initial (carry-in) stacks
    tag_ref, flag_ref,        # int32 [ROWS, LB] current access rows
    depth_ref,                # int32 [ROWS, LB] output rows
    final_ref,                # int32 [W, LB] final stacks (last write wins)
    stack_scr,                # int32 [W, LB] persistent VMEM state
    *,
    accesses: int,            # C: rows past it are padding
):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        stack_scr[...] = init_ref[...]

    stack = stack_scr[...]
    for r in range(ROWS):
        new, depth = lru_stack_step(
            stack, tag_ref[r:r + 1, :], flag_ref[r:r + 1, :] != 0)
        depth_ref[r:r + 1, :] = depth
        # Padding rows (only in the last row block) leave the stacks as
        # they are, so the final stacks are those after access C - 1.
        stack = new if accesses % ROWS == 0 else jnp.where(
            c * ROWS + r < accesses, new, stack)
    stack_scr[...] = stack
    final_ref[...] = stack


@functools.partial(jax.jit, static_argnames=("interpret",))
def stack_scan_pallas(
    tags: jnp.ndarray,        # int32 [L, C]
    seg_flags: jnp.ndarray,   # bool  [L, C]
    init_stack: jnp.ndarray,  # int32 [L, W]
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (depths int32 [L, C], final stacks int32 [L, W])."""
    L, C = tags.shape
    W = init_stack.shape[-1]
    # Pad lanes to whole lane blocks and accesses to whole row blocks: pad
    # lanes are dropped, pad rows come after every real access of a lane.
    lb = min(LANE_BLOCK, next_multiple(L, 128))
    lp, cp = next_multiple(L, lb), next_multiple(C, ROWS)
    rows_t = jnp.pad(tags.astype(jnp.int32).T, ((0, cp - C), (0, lp - L)))
    rows_f = jnp.pad(seg_flags.astype(jnp.int32).T, ((0, cp - C), (0, lp - L)))
    init = jnp.pad(init_stack.astype(jnp.int32).T, ((0, 0), (0, lp - L)),
                   constant_values=-1)
    rows = pl.BlockSpec((ROWS, lb), lambda l, c: (c, l))
    stacks = pl.BlockSpec((W, lb), lambda l, c: (0, l))
    depths, final = pl.pallas_call(
        functools.partial(_stack_scan_kernel, accesses=C),
        grid=(lp // lb, cp // ROWS),
        in_specs=[stacks, rows, rows],
        out_specs=[rows, stacks],
        out_shape=[
            jax.ShapeDtypeStruct((cp, lp), jnp.int32),
            jax.ShapeDtypeStruct((W, lp), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((W, lb), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(init, rows_t, rows_f)
    return depths[:C, :L].T, final[:, :L].T
