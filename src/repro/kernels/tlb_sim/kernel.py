"""Trace-driven TLB simulation as a Pallas TPU kernel.

TPU adaptation of the paper's evaluation hot loop (millions of trace
accesses x hundreds of configs).  The full TLB state (tags + last-use) stays
**resident in VMEM** for the entire trace: TPU grids execute sequentially,
so scratch persists across grid steps while each step streams one trace
block HBM->SMEM.  The simulated per-partition TLB array (SPARTA's "divide")
is the leading state dimension: probing partition p touches only sets
[p*sets, (p+1)*sets).

``tlb_sim_batched_pallas`` adds a **config batch dimension** for the sweep
engine (:mod:`repro.core.sweep`): B configs' states are stacked as the
leading VMEM axis and each grid step fetches one trace block once, carrying
every config's (set, tag) view of that chunk, so all configs advance through
the trace together in a single pallas_call.  Geometry padding is poisoned
exactly like the host-side batched scan (`padded_tlb_state`), keeping the
kernel bit-identical per config.

State layout.  A ``[sets, W]`` LRU array would waste 128/W of every vector
register and of VMEM (the lane axis pads to 128), so the kernels keep it
lane-dense (:func:`lane_layout`): ways padded to a power of two with
poisoned slots, ``128 / W`` sets per 128-lane row (or ``W / 128`` rows per
set for very wide sets).  The per-access trace words and hit bits are SMEM
scalars.

The access loop is inherently serial (LRU state carries a dependency), but
each probe is one vector compare/select over the set's row.  The host-side
oracles are ``repro.core.tlbsim._scan_tlb`` and
``repro.core.tlbsim._scan_tlb_batched``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared with the host-side batched oracle: kernel/oracle bit-identity
# depends on both using the same poison scheme.
from repro.core.tlbsim import _POISON_LAST, _POISON_TAG
from repro.kernels.common import next_multiple, smem_block

LANES = 128


class LaneLayout(NamedTuple):
    """Lane-dense layout of a ``[sets, ways]`` LRU array: ``ways`` padded to
    a power of two, then packed row-major into ``[rows, 128]``."""

    sets: int
    ways: int   # padded, a power of two
    rows: int   # a multiple of 8 (one int32 sublane tile)


def lane_layout(sets: int, ways: int) -> LaneLayout:
    wp = 1 << max(ways - 1, 0).bit_length()
    rows = (-(-sets // (LANES // wp)) if wp <= LANES
            else sets * (wp // LANES))
    return LaneLayout(sets, wp, next_multiple(rows, 8))


def to_lanes(x: jnp.ndarray, lay: LaneLayout, fill: int) -> jnp.ndarray:
    """``[..., sets, W]`` -> ``[..., rows, 128]``; padded ways and sets hold
    ``fill`` (the poison of their array: never matched, never LRU)."""
    lead = x.shape[:-2]
    slots = lay.rows * LANES // lay.ways
    pad = [(0, 0)] * len(lead) + [(0, slots - x.shape[-2]),
                                  (0, lay.ways - x.shape[-1])]
    x = jnp.pad(x.astype(jnp.int32), pad, constant_values=fill)
    return x.reshape(*lead, lay.rows, LANES)


def from_lanes(x: jnp.ndarray, lay: LaneLayout, ways: int) -> jnp.ndarray:
    """Inverse of :func:`to_lanes` for the first ``ways`` ways."""
    lead = x.shape[:-2]
    x = x.reshape(*lead, lay.rows * LANES // lay.ways, lay.ways)
    return x[..., :lay.sets, :ways]


def way_plane(lay: LaneLayout) -> jnp.ndarray:
    """int32 ``[rows, 128]``: the way index of every slot."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (lay.rows, LANES), 1)
    if lay.ways <= LANES:
        return lane % lay.ways
    row = jax.lax.broadcasted_iota(jnp.int32, (lay.rows, LANES), 0)
    return (row % (lay.ways // LANES)) * LANES + lane


def poisoned_state(lay: LaneLayout, valid_ways: int):
    """Empty ``(tags, last)`` planes of one config with ``valid_ways`` real
    ways: the lane-dense form of ``padded_tlb_state``."""
    pad = way_plane(lay) >= valid_ways
    return (jnp.where(pad, _POISON_TAG, -1).astype(jnp.int32),
            jnp.where(pad, _POISON_LAST, 0).astype(jnp.int32))


def lru_probe(tags_ref, last_ref, b, s, t, now, do_update=True, *,
              ways: int):
    """Probe set ``s`` of config ``b`` for tag ``t``; on ``do_update`` write
    ``t`` and the stamp ``now`` into the hit way, or the LRU way on a miss.
    Returns the hit bit.  ``ways`` is the layout's padded associativity.

    The set's row is read and written whole through a dynamic sublane
    offset (``pl.ds``), its slots masked out of the 128 lanes, and the way
    chosen with ``min(where(mask, way, W))`` -- the first matching /
    least-recent way, the same first-index tie-break as the oracle's
    ``argmax`` / ``argmin``.  Mosaic lowers neither scalar VMEM stores nor
    integer arg-reductions, so this is the form that compiles for the chip.
    """
    if ways <= LANES:
        per_row = LANES // ways
        idx = (b, pl.ds(s // per_row, 1), slice(None))
        way_ix = (jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
                  - (s % per_row) * ways)
        in_set = (way_ix >= 0) & (way_ix < ways)
    else:
        span = ways // LANES
        idx = (b, pl.ds(s * span, span), slice(None))
        way_ix = (jax.lax.broadcasted_iota(jnp.int32, (span, LANES), 0) * LANES
                  + jax.lax.broadcasted_iota(jnp.int32, (span, LANES), 1))
        in_set = way_ix >= 0
    row_t = tags_ref[idx]
    row_l = last_ref[idx]
    hit_way = jnp.min(jnp.where(in_set & (row_t == t), way_ix, ways))
    hit = hit_way < ways
    lru = jnp.min(jnp.where(in_set, row_l, _POISON_LAST))
    lru_way = jnp.min(jnp.where(in_set & (row_l == lru), way_ix, ways))
    sel = in_set & (way_ix == jnp.where(hit, hit_way, lru_way)) & do_update
    tags_ref[idx] = jnp.where(sel, t, row_t)
    last_ref[idx] = jnp.where(sel, now, row_l)
    return hit


def _tlb_kernel(
    set_ref, tag_ref,     # int32 [BLK] trace block (SMEM)
    hit_ref,              # int32 [BLK] output (SMEM)
    tags_scr, last_scr,   # [1, R, 128] persistent lane-dense VMEM state
    *,
    block: int,
    lay: LaneLayout,
    valid_ways: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        tags_scr[0], last_scr[0] = poisoned_state(lay, valid_ways)

    base = i * block

    def body(j, _):
        hit = lru_probe(tags_scr, last_scr, 0, set_ref[j], tag_ref[j],
                        base + j + 1, ways=lay.ways)
        hit_ref[j] = hit.astype(jnp.int32)
        return 0

    jax.lax.fori_loop(0, block, body, 0)


@functools.partial(jax.jit, static_argnames=("total_sets", "ways", "block", "interpret"))
def tlb_sim_pallas(
    set_idx: jnp.ndarray,
    tag: jnp.ndarray,
    total_sets: int,
    ways: int,
    *,
    block: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    n = set_idx.shape[0]
    block = min(block, n)
    assert n % block == 0, f"trace length {n} must be a multiple of block {block}"
    block = smem_block(block, 1, 3)
    lay = lane_layout(total_sets, ways)
    stream = pl.BlockSpec((block,), lambda i: (i,), memory_space=pltpu.SMEM)
    hits = pl.pallas_call(
        functools.partial(_tlb_kernel, block=block, lay=lay, valid_ways=ways),
        grid=(n // block,),
        in_specs=[stream, stream],
        out_specs=stream,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, lay.rows, LANES), jnp.int32)] * 2,
        interpret=interpret,
    )(set_idx.astype(jnp.int32), tag.astype(jnp.int32))
    return hits.astype(bool)


def _batched_access_loop(set_ref, tag_ref, hit_ref, tags, last, base, *,
                         block: int, num_cfgs: int, ways: int):
    """Advance every config through the grid step's ``block`` accesses."""

    def access(j, _):
        now = base + j + 1

        def per_cfg(b, _):
            hit = lru_probe(tags, last, b, set_ref[b, j], tag_ref[b, j], now,
                            ways=ways)
            hit_ref[b, j] = hit.astype(jnp.int32)
            return 0

        jax.lax.fori_loop(0, num_cfgs, per_cfg, 0)
        return 0

    jax.lax.fori_loop(0, block, access, 0)


def _tlb_batched_kernel(
    set_ref, tag_ref,     # int32 [B, BLK] trace block (all configs' key views)
    hit_ref,              # int32 [B, BLK] output
    tags_scr, last_scr,   # [B, R, 128] persistent stacked lane-dense state
    *,
    block: int,
    lay: LaneLayout,
    valid_ways: Tuple[int, ...],
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        # Poison ways beyond each config's associativity: their tag never
        # matches and their last-use stamp is never the LRU minimum.
        # valid_ways is static, so the per-config masks are compile-time
        # constants (no captured arrays), unrolled over the B axis.
        for b, vw in enumerate(valid_ways):
            tags_scr[b], last_scr[b] = poisoned_state(lay, vw)

    _batched_access_loop(set_ref, tag_ref, hit_ref, tags_scr, last_scr,
                         i * block, block=block, num_cfgs=len(valid_ways),
                         ways=lay.ways)


def _tlb_batched_carry_kernel(
    set_ref, tag_ref,       # int32 [B, BLK] trace block (SMEM)
    tags_in, last_in,       # int32 [B, R, 128] carried state in (HBM)
    nb_ref,                 # int32 [1, 1] global access count before chunk
    hit_ref,                # int32 [B, BLK] output (SMEM)
    tags_out, last_out,     # int32 [B, R, 128] carried state out (HBM)
    tags_v, last_v,         # int32 [B, R, 128] working state (VMEM)
    *,
    block: int,
    num_cfgs: int,
    ways: int,
):
    """Chunk-resumable variant of :func:`_tlb_batched_kernel`.

    The carried state is copied HBM->VMEM once at grid step 0 (the caller
    owns the poison init), mutated in place across the sequential grid, and
    copied back once after the last step -- one VMEM copy of the state,
    whatever its size.  Timestamps continue the global access counter
    (``nb_ref``), so chunked execution is bit-identical to the monolithic
    kernel.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _load():
        pltpu.sync_copy(tags_in, tags_v)
        pltpu.sync_copy(last_in, last_v)

    _batched_access_loop(set_ref, tag_ref, hit_ref, tags_v, last_v,
                         nb_ref[0, 0] + i * block, block=block,
                         num_cfgs=num_cfgs, ways=ways)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        pltpu.sync_copy(tags_v, tags_out)
        pltpu.sync_copy(last_v, last_out)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def tlb_sim_batched_pallas_carry(
    set_idx: jnp.ndarray,   # int32 [B, L]
    tag: jnp.ndarray,       # int32 [B, L]
    tags: jnp.ndarray,      # int32 [B, TS, W] carried state
    last: jnp.ndarray,      # int32 [B, TS, W]
    now0: jnp.ndarray,      # int32 scalar
    *,
    block: int = 512,
    interpret: bool = False,
):
    """Chunk-resumable batched LRU simulation; returns (hits, tags', last')."""
    num_cfgs, n = set_idx.shape
    total_sets, ways = tags.shape[1], tags.shape[2]
    block = min(block, n)
    assert n % block == 0, f"chunk length {n} must be a multiple of block {block}"
    block = smem_block(block, num_cfgs, 3)
    lay = lane_layout(total_sets, ways)
    stream = pl.BlockSpec((num_cfgs, block), lambda i: (0, i),
                          memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    state = jax.ShapeDtypeStruct((num_cfgs, lay.rows, LANES), jnp.int32)
    hits, tags_l, last_l = pl.pallas_call(
        functools.partial(
            _tlb_batched_carry_kernel, block=block, num_cfgs=num_cfgs,
            ways=lay.ways,
        ),
        grid=(n // block,),
        in_specs=[stream, stream, hbm, hbm,
                  pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=[stream, hbm, hbm],
        out_shape=[jax.ShapeDtypeStruct((num_cfgs, n), jnp.int32), state,
                   state],
        scratch_shapes=[pltpu.VMEM(state.shape, jnp.int32)] * 2,
        input_output_aliases={2: 1, 3: 2},
        interpret=interpret,
    )(set_idx.astype(jnp.int32), tag.astype(jnp.int32),
      to_lanes(tags, lay, _POISON_TAG), to_lanes(last, lay, _POISON_LAST),
      jnp.asarray(now0, jnp.int32).reshape(1, 1))
    return (hits.astype(bool), from_lanes(tags_l, lay, ways),
            from_lanes(last_l, lay, ways))


@functools.partial(
    jax.jit,
    static_argnames=("total_sets", "ways", "valid_ways", "block", "interpret"),
)
def tlb_sim_batched_pallas(
    set_idx: jnp.ndarray,   # int32 [B, N]
    tag: jnp.ndarray,       # int32 [B, N]
    total_sets: int,
    ways: int,
    valid_ways: Tuple[int, ...],
    *,
    block: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """B-config batched LRU simulation; returns hit bits bool [B, N]."""
    num_cfgs, n = set_idx.shape
    assert len(valid_ways) == num_cfgs
    block = min(block, n)
    assert n % block == 0, f"trace length {n} must be a multiple of block {block}"
    block = smem_block(block, num_cfgs, 3)
    lay = lane_layout(total_sets, ways)
    stream = pl.BlockSpec((num_cfgs, block), lambda i: (0, i),
                          memory_space=pltpu.SMEM)
    hits = pl.pallas_call(
        functools.partial(
            _tlb_batched_kernel, block=block, lay=lay, valid_ways=valid_ways,
        ),
        grid=(n // block,),
        in_specs=[stream, stream],
        out_specs=stream,
        out_shape=jax.ShapeDtypeStruct((num_cfgs, n), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((num_cfgs, lay.rows, LANES), jnp.int32)] * 2,
        interpret=interpret,
    )(set_idx.astype(jnp.int32), tag.astype(jnp.int32))
    return hits.astype(bool)
