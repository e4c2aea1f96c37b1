"""Batched joint-system (cache + accel TLB + mem TLB) trace simulation as a
Pallas TPU kernel.

Same architecture as ``repro.kernels.tlb_sim.tlb_sim_batched_pallas``, with
THREE LRU structures per config instead of one: the (tags, last-use) state
of the data cache, the accelerator-side TLB, and the partitioned
memory-side TLB array stays **resident in VMEM** for the entire trace (TPU
grids execute sequentially, so scratch persists across grid steps), each in
the tlb_sim kernel's lane-dense layout.  Each grid step streams one trace
block HBM->SMEM once, carrying all six per-config (set, tag) key views of
that chunk, and writes back a single packed hit word per access (bit 0
cache, bit 1 accel TLB, bit 2 mem TLB) — 7 streamed words per (config,
access).

No value leaves the vector unit inside the access loop: the shared
``lru_probe`` returns its hit as a ``[1, 1]`` vector, the three hits are
combined as vector masks, and each packed word is selected into a
``[B, 128]`` tile (config on the sublane, access on the lane) that is
stored into a VMEM hit block once per 128 accesses.  Scalars only flow the
other way: key words, set indices, flags and the stamp.

Designs often share structures: a figure compares translation designs
behind one accelerator cache, and designs with one memory-TLB geometry see
the same cache misses.  Two designs share a structure when its geometry,
key rule and probe condition match, because its inputs are then equal at
every access.  A static *share map* from the host names, per structure,
each design's representative (the first design with that structure), or
``-1`` where the design has none; each access probes every representative
once, in straight-line code, and no absent structure.  Each kept structure
has VMEM refs of its own, so no probe's row may alias another's and the
scheduler overlaps independent probes.  A follower's state is its
representative's: the carry kernel writes it back to every follower once
per call.  Where nothing is shared, every design is its own representative.

The carry kernel's grid length is data: it runs only the grid steps that
hold the chunk's own accesses, so a caller may pad every chunk of a trace
to one length and trace, lower and compile the kernel once.

Per-config structure presence and the virtual-cache probe policy ride along
as an int32 ``[B, 3]`` flag row (``has_cache``, ``has_accel``,
``accel_probe_on_miss_only``) consumed as *data* by the representatives'
probes, exactly like the batched scan oracle
(:func:`repro.kernels.system_sim.ref.system_sim_batched_ref`): updates and
hit bits are gated by the flags, so heterogeneous design points (cacheless
accelerators, physical vs virtual caches) share one pallas_call.  Way
padding beyond each config's own associativity is poisoned with the shared
``_POISON_TAG`` / ``_POISON_LAST`` scheme, keeping the kernel bit-identical
per config to the oracle.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared with the host-side batched oracle (via padded_tlb_state):
# kernel/oracle bit-identity depends on both using the same poison scheme.
from repro.core.tlbsim import _POISON_LAST, _POISON_TAG
from repro.kernels.common import smem_block
from repro.kernels.tlb_sim.kernel import (
    LANES,
    LaneLayout,
    from_lanes,
    lane_layout,
    lru_probe,
    poisoned_state,
    to_lanes,
)


# Per structure (cache, accel TLB, mem TLB): each design's representative,
# the design whose state and hits it shares, or -1 where it has none.
Share = Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]]


def _representatives(share: Share):
    """``(k, b)`` of every structure the kernel keeps and probes: structure
    ``k`` (0 cache, 1 accel TLB, 2 mem TLB) of representative ``b``."""
    return [(k, b) for k, rep in enumerate(share)
            for b in sorted({r for r in rep if r >= 0})]


def _access_loop(streams, flags_ref, hit_ref, states, base, *, block: int,
                 share: Share, lays: Tuple[LaneLayout, ...]):
    """Advance every representative structure through the grid step's
    ``block`` accesses, one probe each per access: the caches first, then
    the TLBs, whose probe conditions read their cache's hit.  ``states``
    maps each ``(k, b)`` of :func:`_representatives` to its own
    ``(tags, last)`` refs, ``[1, R, 128]`` each: refs of their own, so no
    probe's row may alias another's.

    The packed hit words of each group of 128 accesses are carried as one
    ``[B, 128]`` tile and stored into the VMEM block ``hit_ref`` together;
    a block that is no multiple of 128 ends with a shorter group."""
    c_rep, a_rep, m_rep = share
    num_cfgs = len(c_rep)
    # Designs with the same three representatives have the same hit word.
    words = {}
    for b, key in enumerate(zip(c_rep, a_rep, m_rep)):
        words.setdefault(key, []).append(b)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (num_cfgs, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (num_cfgs, LANES), 1)

    def access(lo, i, tile):
        j = lo + i
        now = base + j + 1

        def probe(k, b, do_update):
            tags, last = states[k, b]
            return lru_probe(tags, last, 0, streams[2 * k][b, j],
                             streams[2 * k + 1][b, j], now, do_update,
                             ways=lays[k].ways)

        off = jnp.zeros((1, 1), jnp.bool_)
        hits = ({-1: off}, {-1: off}, {})
        for k, b in _representatives(share):
            if k == 0:
                has_c = flags_ref[b, 0] > 0
                hits[0][b] = has_c & probe(0, b, has_c)
            elif k == 1:
                has_a = flags_ref[b, 1] > 0
                miss_only = flags_ref[b, 2] > 0
                # Physical cache: accel TLB probed every access.  Virtual
                # cache: only on cache misses (translation needed only to
                # leave the accelerator).
                do_a = (~hits[0][c_rep[b]] | ~miss_only) & has_a
                hits[1][b] = (probe(1, b, do_a) | ~do_a) & has_a
            else:
                # Memory-side TLB sees only cache misses.
                c = hits[0][c_rep[b]]
                hits[2][b] = probe(2, b, ~c) | c

        on_lane = lane == i
        for (c, a, m), rows in words.items():
            word = (hits[0][c].astype(jnp.int32)
                    | (hits[1][a].astype(jnp.int32) << 1)
                    | (hits[2][m].astype(jnp.int32) << 2))
            mine = functools.reduce(jnp.logical_or,
                                    [sublane == b for b in rows])
            tile = jnp.where(on_lane & mine, word, tile)
        return tile

    def run(lo, n):
        """The hit tile of accesses ``lo .. lo + n`` (``n <= 128``)."""
        return jax.lax.fori_loop(
            0, n, functools.partial(access, lo),
            jnp.zeros((num_cfgs, LANES), jnp.int32))

    def group(g, _):
        lo = pl.multiple_of(g * LANES, LANES)
        hit_ref[:, pl.ds(lo, LANES)] = run(lo, LANES)
        return 0

    full, tail = divmod(block, LANES)
    if full:
        jax.lax.fori_loop(0, full, group, 0)
    if tail:
        hit_ref[:, full * LANES:] = run(full * LANES, tail)[:, :tail]


def _state_scratch(share: Share, lays):
    """VMEM scratch of the kept structures: a ``[1, R, 128]`` tags and a
    last-use plane for each of :func:`_representatives`."""
    return [pltpu.VMEM((1, lays[k].rows, LANES), jnp.int32)
            for k, _ in _representatives(share) for _ in range(2)]


def _state_refs(share: Share, refs):
    """:func:`_state_scratch`'s refs, keyed by ``(k, b)``."""
    return {kb: (refs[2 * n], refs[2 * n + 1])
            for n, kb in enumerate(_representatives(share))}


def _system_batched_kernel(
    c_set_ref, c_tag_ref,   # int32 [B, BLK] cache (set, tag) views (SMEM)
    a_set_ref, a_tag_ref,   # int32 [B, BLK] accel-TLB views
    m_set_ref, m_tag_ref,   # int32 [B, BLK] mem-TLB views
    flags_ref,              # int32 [B, 3]  (has_cache, has_accel, miss_only)
    hit_ref,                # int32 [B, BLK] packed hit words out (VMEM)
    *state_refs,            # [1, R, 128] persistent lane-dense VMEM state
    block: int,
    share: Share,
    lays: Tuple[LaneLayout, LaneLayout, LaneLayout],
    valid: Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]],
):
    i = pl.program_id(0)
    states = _state_refs(share, state_refs)

    @pl.when(i == 0)
    def _init():
        # Poison ways beyond each config's associativity in each structure:
        # their tag never matches and their last-use stamp is never the LRU
        # minimum.  valid is static, so the per-config masks are compile-time
        # constants (the tlb_sim kernel's scheme, per kept structure).
        for (k, b), (tags, last) in states.items():
            tags[0], last[0] = poisoned_state(lays[k], valid[k][b])

    _access_loop((c_set_ref, c_tag_ref, a_set_ref, a_tag_ref, m_set_ref,
                  m_tag_ref), flags_ref, hit_ref, states, i * block,
                 block=block, share=share, lays=lays)


def _system_batched_carry_kernel(
    c_set_ref, c_tag_ref,   # int32 [B, BLK] cache (set, tag) views (SMEM)
    a_set_ref, a_tag_ref,   # int32 [B, BLK] accel-TLB views
    m_set_ref, m_tag_ref,   # int32 [B, BLK] mem-TLB views
    flags_ref,              # int32 [B, 3]
    c_tags_in, c_last_in,   # int32 [B, R, 128] carried state in (HBM)
    a_tags_in, a_last_in,
    m_tags_in, m_last_in,
    nb_ref,                 # int32 [1, 1] global access count before chunk
    hit_ref,                # int32 [B, BLK] packed hit words out (VMEM)
    c_tags_out, c_last_out,  # int32 [B, R, 128] carried state out (HBM),
    a_tags_out, a_last_out,  # aliased to the state in
    m_tags_out, m_last_out,
    *state_refs,            # int32 [1, R, 128] working state (VMEM)
    block: int,
    share: Share,
    lays: Tuple[LaneLayout, LaneLayout, LaneLayout],
):
    """Chunk-resumable variant of :func:`_system_batched_kernel`: each kept
    structure's carried planes are copied HBM->VMEM once at grid step 0
    (the caller owns the poison init), mutated in place across the
    sequential grid, and copied back after the last step to every design
    that shares it.  An absent structure's planes are not touched: the
    state out is the state in.  Timestamps continue the global access
    counter, so chunked execution is bit-identical to the monolithic
    kernel."""
    i = pl.program_id(0)
    ins = (c_tags_in, c_last_in, a_tags_in, a_last_in, m_tags_in, m_last_in)
    outs = (c_tags_out, c_last_out, a_tags_out, a_last_out, m_tags_out,
            m_last_out)
    states = _state_refs(share, state_refs)

    @pl.when(i == 0)
    def _load():
        for (k, b), work in states.items():
            for src, dst in zip(ins[2 * k:2 * k + 2], work):
                pltpu.sync_copy(src.at[pl.ds(b, 1)], dst)

    _access_loop((c_set_ref, c_tag_ref, a_set_ref, a_tag_ref, m_set_ref,
                  m_tag_ref), flags_ref, hit_ref, states,
                 nb_ref[0, 0] + i * block, block=block, share=share,
                 lays=lays)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        for (k, b), work in states.items():
            for d, r in enumerate(share[k]):
                if r == b:
                    for src, dst in zip(work, outs[2 * k:2 * k + 2]):
                        pltpu.sync_copy(src, dst.at[pl.ds(d, 1)])


def _hit_block(num_cfgs: int, n: int, block: int):
    """(spec, shape) of the packed hit words: each grid step's ``[B, block]``
    VMEM block is the whole trailing tile of a ``[n // block, B, block]``
    array, so a block of any length (no multiple of 128 needed) compiles."""
    return (pl.BlockSpec((None, num_cfgs, block), lambda i: (i, 0, 0)),
            jax.ShapeDtypeStruct((n // block, num_cfgs, block), jnp.int32))


def _unpack(hits):
    """``[n // block, B, block]`` packed words -> (cache, accel TLB, mem TLB)
    hit bits, each bool ``[B, n]``."""
    hits = jnp.swapaxes(hits, 0, 1).reshape(hits.shape[1], -1)
    return (
        (hits & 1).astype(bool),
        ((hits >> 1) & 1).astype(bool),
        ((hits >> 2) & 1).astype(bool),
    )


@functools.partial(jax.jit, static_argnames=("block", "share", "interpret"))
def system_sim_batched_pallas_carry(
    c_set: jnp.ndarray, c_tag: jnp.ndarray,   # int32 [B, L]
    a_set: jnp.ndarray, a_tag: jnp.ndarray,
    m_set: jnp.ndarray, m_tag: jnp.ndarray,
    flags: jnp.ndarray,                       # int32 [B, 3]
    state,                                    # 6-tuple int32 [B, S, W]
    now0: jnp.ndarray,                        # int32 scalar
    n_valid: jnp.ndarray,                     # int32 scalar, <= L
    *,
    share: Share,
    block: int = 512,
    interpret: bool = False,
):
    """Chunk-resumable batched joint-pipeline simulation; returns
    ``((cache_hit, accel_tlb_hit, mem_tlb_hit), state')``.  Only the grid
    steps that hold the first ``n_valid`` accesses run; hit bits past them
    are undefined.  ``share`` is the share map (module docstring); a
    follower's carried state must equal its representative's, as it does
    from fresh state on."""
    num_cfgs, n = c_set.shape
    assert all(len(rep) == num_cfgs for rep in share)
    block = min(block, n)
    assert n % block == 0, f"chunk length {n} must be a multiple of block {block}"
    # Only the six key views stream through SMEM; budgeting the hit word as
    # a seventh keeps the grid step the benchmark cells run with.
    block = smem_block(block, num_cfgs, 7)
    lays = tuple(lane_layout(*state[2 * k].shape[1:]) for k in range(3))
    fills = (_POISON_TAG, _POISON_LAST)
    lanes = [to_lanes(s, lays[k // 2], fills[k % 2])
             for k, s in enumerate(state)]
    stream = pl.BlockSpec((num_cfgs, block), lambda i: (0, i),
                          memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    hit_spec, hit_shape = _hit_block(num_cfgs, n, block)
    outs = pl.pallas_call(
        functools.partial(
            _system_batched_carry_kernel, block=block, share=share,
            lays=lays,
        ),
        grid=((jnp.asarray(n_valid, jnp.int32) + block - 1) // block,),
        in_specs=[stream] * 6
        + [pl.BlockSpec((num_cfgs, 3), lambda i: (0, 0), memory_space=pltpu.SMEM)]
        + [hbm] * 6
        + [pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=[hit_spec] + [hbm] * 6,
        out_shape=[hit_shape]
        + [jax.ShapeDtypeStruct(x.shape, jnp.int32) for x in lanes],
        scratch_shapes=_state_scratch(share, lays),
        input_output_aliases={7 + k: 1 + k for k in range(6)},
        interpret=interpret,
        name="system_sim_carry",
    )(c_set.astype(jnp.int32), c_tag.astype(jnp.int32),
      a_set.astype(jnp.int32), a_tag.astype(jnp.int32),
      m_set.astype(jnp.int32), m_tag.astype(jnp.int32),
      flags.astype(jnp.int32), *lanes,
      jnp.asarray(now0, jnp.int32).reshape(1, 1))
    state_out = tuple(from_lanes(x, lays[k // 2], state[k].shape[2])
                      for k, x in enumerate(outs[1:]))
    return _unpack(outs[0]), state_out


@functools.partial(
    jax.jit, static_argnames=("geom", "valid", "block", "share", "interpret"))
def system_sim_batched_pallas(
    c_set: jnp.ndarray, c_tag: jnp.ndarray,   # int32 [B, N]
    a_set: jnp.ndarray, a_tag: jnp.ndarray,   # int32 [B, N]
    m_set: jnp.ndarray, m_tag: jnp.ndarray,   # int32 [B, N]
    flags: jnp.ndarray,                       # int32 [B, 3]
    geom: Tuple[int, int, int, int, int, int],
    valid: Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]],
    *,
    share: Share,
    block: int = 512,
    interpret: bool = False,
):
    """B-config batched joint-pipeline simulation; returns
    (cache_hit, accel_tlb_hit, mem_tlb_hit), each bool [B, N], bit-identical
    per config to the batched scan oracle on the same padded envelope.
    ``share`` is the share map (module docstring)."""
    num_cfgs, n = c_set.shape
    assert all(len(v) == num_cfgs for v in valid + share)
    block = min(block, n)
    assert n % block == 0, f"trace length {n} must be a multiple of block {block}"
    # Only the six key views stream through SMEM; budgeting the hit word as
    # a seventh keeps the grid step the benchmark cells run with.
    block = smem_block(block, num_cfgs, 7)
    lays = tuple(lane_layout(geom[2 * k], geom[2 * k + 1]) for k in range(3))
    stream = pl.BlockSpec((num_cfgs, block), lambda i: (0, i),
                          memory_space=pltpu.SMEM)
    hit_spec, hit_shape = _hit_block(num_cfgs, n, block)
    hits = pl.pallas_call(
        functools.partial(
            _system_batched_kernel, block=block, share=share, lays=lays,
            valid=valid,
        ),
        grid=(n // block,),
        in_specs=[stream] * 6
        + [pl.BlockSpec((num_cfgs, 3), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=hit_spec,
        out_shape=hit_shape,
        scratch_shapes=_state_scratch(share, lays),
        interpret=interpret,
        name="system_sim_batched",
    )(c_set.astype(jnp.int32), c_tag.astype(jnp.int32),
      a_set.astype(jnp.int32), a_tag.astype(jnp.int32),
      m_set.astype(jnp.int32), m_tag.astype(jnp.int32),
      flags.astype(jnp.int32))
    return _unpack(hits)
