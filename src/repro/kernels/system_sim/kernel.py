"""Batched joint-system (cache + accel TLB + mem TLB) trace simulation as a
Pallas TPU kernel.

Same architecture as ``repro.kernels.tlb_sim.tlb_sim_batched_pallas``, with
THREE stacked LRU structures instead of one: every config's (tags, last-use)
state for the data cache, the accelerator-side TLB, and the partitioned
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

Per-config structure presence and the virtual-cache probe policy ride along
as an int32 ``[B, 3]`` flag row (``has_cache``, ``has_accel``,
``accel_probe_on_miss_only``) consumed as *data*, exactly like the batched
scan oracle (:func:`repro.kernels.system_sim.ref.system_sim_batched_ref`):
probes always execute, updates and hit bits are gated by the flags, so
heterogeneous design points (cacheless accelerators, physical vs virtual
caches) share one pallas_call.  Way padding beyond each config's own
associativity is poisoned with the shared ``_POISON_TAG`` / ``_POISON_LAST``
scheme, keeping the kernel bit-identical per config to the oracle.
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


def _access_loop(streams, flags_ref, hit_ref, states, base, *, block: int,
                 num_cfgs: int, lays: Tuple[LaneLayout, ...]):
    """Advance every config's three structures through the grid step's
    ``block`` accesses (``states`` = (tags, last) refs per structure).

    The packed hit words of each group of 128 accesses are carried as one
    ``[B, 128]`` tile and stored into the VMEM block ``hit_ref`` together;
    a block that is no multiple of 128 ends with a shorter group."""
    c_set, c_tag, a_set, a_tag, m_set, m_tag = streams
    (c_tags, c_last), (a_tags, a_last), (m_tags, m_last) = states
    sublane = jax.lax.broadcasted_iota(jnp.int32, (num_cfgs, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (num_cfgs, LANES), 1)

    def access(lo, i, tile):
        j = lo + i
        now = base + j + 1
        on_lane = lane == i

        def per_cfg(b, tile):
            has_c = flags_ref[b, 0] > 0
            has_a = flags_ref[b, 1] > 0
            miss_only = flags_ref[b, 2] > 0

            def probe(k, tags, last, s, t, do_update):
                return lru_probe(tags, last, b, s, t, now, do_update,
                                 ways=lays[k].ways)

            c_raw = probe(0, c_tags, c_last, c_set[b, j], c_tag[b, j], has_c)
            c_hit = has_c & c_raw
            # Physical cache: accel TLB probed every access.  Virtual cache:
            # only on cache misses (translation needed only to leave the
            # accelerator).
            do_a = (~c_hit | ~miss_only) & has_a
            a_raw = probe(1, a_tags, a_last, a_set[b, j], a_tag[b, j], do_a)
            a_hit = (a_raw | ~do_a) & has_a
            # Memory-side TLB sees only cache misses.
            m_raw = probe(2, m_tags, m_last, m_set[b, j], m_tag[b, j], ~c_hit)
            m_hit = m_raw | c_hit

            word = (c_hit.astype(jnp.int32)
                    | (a_hit.astype(jnp.int32) << 1)
                    | (m_hit.astype(jnp.int32) << 2))
            return jnp.where(on_lane & (sublane == b), word, tile)

        return jax.lax.fori_loop(0, num_cfgs, per_cfg, tile)

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


def _system_batched_kernel(
    c_set_ref, c_tag_ref,   # int32 [B, BLK] cache (set, tag) views (SMEM)
    a_set_ref, a_tag_ref,   # int32 [B, BLK] accel-TLB views
    m_set_ref, m_tag_ref,   # int32 [B, BLK] mem-TLB views
    flags_ref,              # int32 [B, 3]  (has_cache, has_accel, miss_only)
    hit_ref,                # int32 [B, BLK] packed hit words out (VMEM)
    c_tags, c_last,         # [B, R, 128] persistent lane-dense VMEM state
    a_tags, a_last,
    m_tags, m_last,
    *,
    block: int,
    lays: Tuple[LaneLayout, LaneLayout, LaneLayout],
    valid: Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]],
):
    i = pl.program_id(0)
    states = ((c_tags, c_last), (a_tags, a_last), (m_tags, m_last))

    @pl.when(i == 0)
    def _init():
        # Poison ways beyond each config's associativity in each structure:
        # their tag never matches and their last-use stamp is never the LRU
        # minimum.  valid is static, so the per-config masks are compile-time
        # constants, unrolled over the B axis (the tlb_sim kernel's scheme,
        # three times over).
        for (tags, last), lay, vws in zip(states, lays, valid):
            for b, vw in enumerate(vws):
                tags[b], last[b] = poisoned_state(lay, vw)

    _access_loop((c_set_ref, c_tag_ref, a_set_ref, a_tag_ref, m_set_ref,
                  m_tag_ref), flags_ref, hit_ref, states, i * block,
                 block=block, num_cfgs=len(valid[0]), lays=lays)


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
    c_tags_out, c_last_out,  # int32 [B, R, 128] carried state out (HBM)
    a_tags_out, a_last_out,
    m_tags_out, m_last_out,
    c_tags, c_last,         # int32 [B, R, 128] working state (VMEM)
    a_tags, a_last,
    m_tags, m_last,
    *,
    block: int,
    num_cfgs: int,
    lays: Tuple[LaneLayout, LaneLayout, LaneLayout],
):
    """Chunk-resumable variant of :func:`_system_batched_kernel`: the six
    carried state arrays are copied HBM->VMEM once at grid step 0 (the
    caller owns the poison init), mutated in place across the sequential
    grid, and copied back after the last step.  Timestamps continue the
    global access counter, so chunked execution is bit-identical to the
    monolithic kernel."""
    i = pl.program_id(0)
    ins = (c_tags_in, c_last_in, a_tags_in, a_last_in, m_tags_in, m_last_in)
    outs = (c_tags_out, c_last_out, a_tags_out, a_last_out, m_tags_out,
            m_last_out)
    work = (c_tags, c_last, a_tags, a_last, m_tags, m_last)

    @pl.when(i == 0)
    def _load():
        for src, dst in zip(ins, work):
            pltpu.sync_copy(src, dst)

    _access_loop((c_set_ref, c_tag_ref, a_set_ref, a_tag_ref, m_set_ref,
                  m_tag_ref), flags_ref, hit_ref,
                 ((c_tags, c_last), (a_tags, a_last), (m_tags, m_last)),
                 nb_ref[0, 0] + i * block, block=block, num_cfgs=num_cfgs,
                 lays=lays)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        for src, dst in zip(work, outs):
            pltpu.sync_copy(src, dst)


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


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def system_sim_batched_pallas_carry(
    c_set: jnp.ndarray, c_tag: jnp.ndarray,   # int32 [B, L]
    a_set: jnp.ndarray, a_tag: jnp.ndarray,
    m_set: jnp.ndarray, m_tag: jnp.ndarray,
    flags: jnp.ndarray,                       # int32 [B, 3]
    state,                                    # 6-tuple int32 [B, S, W]
    now0: jnp.ndarray,                        # int32 scalar
    *,
    block: int = 512,
    interpret: bool = False,
):
    """Chunk-resumable batched joint-pipeline simulation; returns
    ``((cache_hit, accel_tlb_hit, mem_tlb_hit), state')``."""
    num_cfgs, n = c_set.shape
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
            _system_batched_carry_kernel, block=block, num_cfgs=num_cfgs,
            lays=lays,
        ),
        grid=(n // block,),
        in_specs=[stream] * 6
        + [pl.BlockSpec((num_cfgs, 3), lambda i: (0, 0), memory_space=pltpu.SMEM)]
        + [hbm] * 6
        + [pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=[hit_spec] + [hbm] * 6,
        out_shape=[hit_shape]
        + [jax.ShapeDtypeStruct(x.shape, jnp.int32) for x in lanes],
        scratch_shapes=[pltpu.VMEM(x.shape, jnp.int32) for x in lanes],
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
    jax.jit, static_argnames=("geom", "valid", "block", "interpret"))
def system_sim_batched_pallas(
    c_set: jnp.ndarray, c_tag: jnp.ndarray,   # int32 [B, N]
    a_set: jnp.ndarray, a_tag: jnp.ndarray,   # int32 [B, N]
    m_set: jnp.ndarray, m_tag: jnp.ndarray,   # int32 [B, N]
    flags: jnp.ndarray,                       # int32 [B, 3]
    geom: Tuple[int, int, int, int, int, int],
    valid: Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]],
    *,
    block: int = 512,
    interpret: bool = False,
):
    """B-config batched joint-pipeline simulation; returns
    (cache_hit, accel_tlb_hit, mem_tlb_hit), each bool [B, N], bit-identical
    per config to the batched scan oracle on the same padded envelope."""
    num_cfgs, n = c_set.shape
    assert all(len(v) == num_cfgs for v in valid)
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
            _system_batched_kernel, block=block, lays=lays, valid=valid,
        ),
        grid=(n // block,),
        in_specs=[stream] * 6
        + [pl.BlockSpec((num_cfgs, 3), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=hit_spec,
        out_shape=hit_shape,
        scratch_shapes=[pltpu.VMEM((num_cfgs, lay.rows, LANES), jnp.int32)
                        for lay in lays for _ in range(2)],
        interpret=interpret,
        name="system_sim_batched",
    )(c_set.astype(jnp.int32), c_tag.astype(jnp.int32),
      a_set.astype(jnp.int32), a_tag.astype(jnp.int32),
      m_set.astype(jnp.int32), m_tag.astype(jnp.int32),
      flags.astype(jnp.int32))
    return _unpack(hits)
