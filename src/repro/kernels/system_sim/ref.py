"""Oracle for the batched joint-system kernel: the pure-JAX batched scan.

This is the scan that used to live as ``repro.core.sweep._scan_system_batched``
— moved here so the kernel package owns both sides of the bit-identity
contract (``repro.core.sweep`` re-exports it under the old name).  Per-config
semantics are identical to :func:`repro.core.tlbsim._scan_system`: structure
presence (``has_cache`` / ``has_accel``) and the virtual-cache probe policy
(``accel_probe_on_miss_only``) become per-config *data* instead of static
Python flags, so heterogeneous design points ride one scan.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.tlb_sim.ref import padded_tlb_state


@functools.partial(jax.jit, static_argnames=("geom", "valid"))
def system_sim_batched_ref(
    inputs,   # 6 x int32 [B, N]: cache/accel/mem (set, tag) streams
    flags,    # 3 x bool  [B]:    has_cache, has_accel, accel_on_miss_only
    geom: Tuple[int, int, int, int, int, int],
    valid: Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]],
):
    """Batched joint pipeline scan; returns (cache, accel, mem) hit bits,
    each bool [B, N]."""
    (c_set, c_tag, a_set, a_tag, m_set, m_tag) = inputs
    has_cache, has_accel, on_miss_only = flags
    cs, cw, asets, aw, ms, mw = geom
    B = c_set.shape[0]

    state0 = (
        *padded_tlb_state(B, cs, cw, valid[0]),
        *padded_tlb_state(B, asets, aw, valid[1]),
        *padded_tlb_state(B, ms, mw, valid[2]),
    )

    def probe(tags, last, s, t, now, do_update):
        row_t = tags[s]
        hit_vec = row_t == t
        hit = jnp.any(hit_vec)
        way = jnp.where(hit, jnp.argmax(hit_vec), jnp.argmin(last[s]))
        tags = tags.at[s, way].set(jnp.where(do_update, t, tags[s, way]))
        last = last.at[s, way].set(jnp.where(do_update, now, last[s, way]))
        return tags, last, hit

    def step_one(state_b, flags_b, inp_b, now):
        ct, cl, at, al, mt, ml = state_b
        has_c, has_a, miss_only = flags_b
        cs_i, ctag_i, as_i, atag_i, ms_i, mtag_i = inp_b
        ct, cl, c_raw = probe(ct, cl, cs_i, ctag_i, now, has_c)
        c_hit = jnp.where(has_c, c_raw, jnp.bool_(False))
        # Physical cache: accel TLB probed every access.  Virtual cache: only
        # on cache misses (translation needed only to leave the accelerator).
        do_a = jnp.where(miss_only, ~c_hit, jnp.bool_(True)) & has_a
        at, al, a_raw = probe(at, al, as_i, atag_i, now, do_a)
        a_hit = jnp.where(
            has_a, jnp.where(do_a, a_raw, jnp.bool_(True)), jnp.bool_(False)
        )
        # Memory-side TLB sees only cache misses (hits never leave the accel).
        mt, ml, m_raw = probe(mt, ml, ms_i, mtag_i, now, ~c_hit)
        m_hit = jnp.where(~c_hit, m_raw, jnp.bool_(True))
        return (ct, cl, at, al, mt, ml), (c_hit, a_hit, m_hit)

    vstep = jax.vmap(step_one, in_axes=(0, 0, 0, None))

    def step(state, inp):
        *streams, now = inp
        return vstep(state, flags, tuple(streams), now)

    n = c_set.shape[1]
    now = jnp.arange(1, n + 1, dtype=jnp.int32)
    xs = tuple(x.T for x in inputs) + (now,)
    (_, ys) = jax.lax.scan(step, state0, xs)
    return tuple(y.T for y in ys)


@jax.jit
def system_sim_batched_carry_ref(
    inputs,   # 6 x int32 [B, L]: one trace chunk's key streams
    flags,    # 3 x bool  [B]
    state,    # 6 x int32 [B, S, W]: carried (tags, last) x 3 structures
    now0,     # int32 scalar: accesses consumed before this chunk
):
    """Chunk-resumable :func:`system_sim_batched_ref`: explicit carried state.

    The caller owns the initial state (three :func:`padded_tlb_state` pairs)
    and the global access counter; feeding the trace in chunks is
    bit-identical to one monolithic pass.  Returns ``((c, a, m) hit bits,
    state')``.
    """
    (c_set, *_) = inputs

    def probe(tags, last, s, t, now, do_update):
        row_t = tags[s]
        hit_vec = row_t == t
        hit = jnp.any(hit_vec)
        way = jnp.where(hit, jnp.argmax(hit_vec), jnp.argmin(last[s]))
        tags = tags.at[s, way].set(jnp.where(do_update, t, tags[s, way]))
        last = last.at[s, way].set(jnp.where(do_update, now, last[s, way]))
        return tags, last, hit

    def step_one(state_b, flags_b, inp_b, now):
        ct, cl, at, al, mt, ml = state_b
        has_c, has_a, miss_only = flags_b
        cs_i, ctag_i, as_i, atag_i, ms_i, mtag_i = inp_b
        ct, cl, c_raw = probe(ct, cl, cs_i, ctag_i, now, has_c)
        c_hit = jnp.where(has_c, c_raw, jnp.bool_(False))
        do_a = jnp.where(miss_only, ~c_hit, jnp.bool_(True)) & has_a
        at, al, a_raw = probe(at, al, as_i, atag_i, now, do_a)
        a_hit = jnp.where(
            has_a, jnp.where(do_a, a_raw, jnp.bool_(True)), jnp.bool_(False)
        )
        mt, ml, m_raw = probe(mt, ml, ms_i, mtag_i, now, ~c_hit)
        m_hit = jnp.where(~c_hit, m_raw, jnp.bool_(True))
        return (ct, cl, at, al, mt, ml), (c_hit, a_hit, m_hit)

    vstep = jax.vmap(step_one, in_axes=(0, 0, 0, None))

    def step(carry, inp):
        *streams, now = inp
        return vstep(carry, flags, tuple(streams), now)

    n = c_set.shape[1]
    now = now0.astype(jnp.int32) + jnp.arange(1, n + 1, dtype=jnp.int32)
    xs = tuple(x.T for x in inputs) + (now,)
    (state, ys) = jax.lax.scan(step, tuple(state), xs)
    return tuple(y.T for y in ys), state
