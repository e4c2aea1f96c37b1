"""Plain set-associative LRU reference: which probes of a structure hit.

A structure of ``S`` sets and ``W`` ways, probed by a stream of (set, tag)
keys, hits when the tag is resident in its set, and on a miss replaces the
set's least recently used way (an empty way first).  Sets never interact,
so each set's probes are simulated on their own, in trace order: the sets of
all streams are packed onto independent lanes (whole sets, largest first
onto the least loaded lane; as few lanes as the largest set allows, up to
``MAX_LANES``) and one sequential loop advances every lane by one probe per
step.  The loop runs on JAX's CPU backend in blocks of
``BLOCK`` steps, so one compiled program serves every stream and the chip is
never touched.

The semantics are the paper's (§6.2: set-associative LRU TLBs, SPARTA's
partition = ``vpn % P``), written from that description alone.
"""
from __future__ import annotations

import heapq
from typing import List, Sequence, Tuple

import numpy as np

MAX_LANES = 256
BLOCK = 8192


def _cpu():
    import jax

    return jax.devices("cpu")[0]


def _lane_step_fn(ways: int):
    import jax
    import jax.numpy as jnp

    def block(state, keys, reset, valid, step0):
        def step(carry, x):
            tags, last, t = carry
            key, rs, v = x
            tags = jnp.where(rs[:, None], -1, tags)
            last = jnp.where(rs[:, None], -1, last)
            match = tags == key[:, None]
            hit = jnp.any(match, axis=1)
            way = jnp.where(hit, jnp.argmax(match, axis=1),
                            jnp.argmin(last, axis=1))
            put = (jnp.arange(ways)[None, :] == way[:, None]) & v[:, None]
            tags = jnp.where(put, key[:, None], tags)
            last = jnp.where(put, t, last)
            return (tags, last, t + 1), hit & v

        (tags, last, _), hits = jax.lax.scan(
            step, (state[0], state[1], step0), (keys, reset, valid))
        return (tags, last), hits

    return jax.jit(block)


_STEP_FNS = {}


def _pack(sets: Sequence[np.ndarray]):
    """Lane and first step of every set's probes (each ``sets[i]`` sorted):
    whole sets, largest first, onto the least loaded lane."""
    groups = []   # (-count, stream, start, count)
    for i, ss in enumerate(sets):
        if ss.size == 0:
            continue
        starts = np.flatnonzero(np.r_[True, ss[1:] != ss[:-1]])
        counts = np.diff(np.r_[starts, ss.size])
        groups += [(-int(c), i, int(st), int(c)) for st, c in zip(starts, counts)]
    groups.sort()
    total = sum(c for *_, c in groups)
    lanes = 8
    while lanes < MAX_LANES and lanes * (-groups[0][0] if groups else 1) < total:
        lanes *= 2
    heap = [(0, lane) for lane in range(lanes)]
    placed = []
    for _, i, st, c in groups:
        load, lane = heapq.heappop(heap)
        placed.append((i, st, c, lane, load))
        heapq.heappush(heap, (load + c, lane))
    return placed, max(load for load, _ in heap), lanes


def lru_hits(streams: Sequence[Tuple[np.ndarray, np.ndarray]], ways: int,
             *, tag_bits: int = 32) -> List[np.ndarray]:
    """Hit bits (bool, one per probe, in stream order) of each (set, tag)
    stream on its own ``ways``-way LRU structure, all starting empty.
    ``tag_bits`` below 32 keeps only that many low tag bits (the control's
    narrower tags)."""
    import jax
    import jax.numpy as jnp

    mask = (1 << tag_bits) - 1
    res, orders, sets, tags = [], [], [], []
    for s, t in streams:
        s, t = np.asarray(s, np.int64), np.asarray(t, np.int64)
        if t.size and (t.min() < 0 or t.max() >= 2**31):
            raise ValueError("tags must lie in [0, 2**31)")
        order = np.argsort(s, kind="stable")
        ss, tt = s[order], t[order] & mask
        # A probe of the tag its set saw last is a hit on the most recently
        # used way and leaves the LRU order as it was: only the others run.
        again = np.r_[False, (ss[1:] == ss[:-1]) & (tt[1:] == tt[:-1])]
        hit = np.zeros(s.size, bool)
        hit[order[again]] = True
        res.append(hit)
        orders.append(order[~again])
        sets.append(ss[~again])
        tags.append(tt[~again])
    placed, steps, lanes = _pack(sets)
    n_steps = max(BLOCK, -(-steps // BLOCK) * BLOCK)
    keys = np.full((n_steps, lanes), -2, np.int32)
    reset = np.zeros((n_steps, lanes), bool)
    valid = np.zeros((n_steps, lanes), bool)
    for i, st, c, lane, pos in placed:
        keys[pos:pos + c, lane] = tags[i][st:st + c]
        valid[pos:pos + c, lane] = True
        reset[pos, lane] = True
    if ways not in _STEP_FNS:
        _STEP_FNS[ways] = _lane_step_fn(ways)
    fn = _STEP_FNS[ways]
    cpu = _cpu()
    with jax.default_device(cpu):
        state = (jnp.full((lanes, ways), -1, jnp.int32),
                 jnp.full((lanes, ways), -1, jnp.int32))
        out = []
        for b0 in range(0, n_steps, BLOCK):
            state, h = fn(state, *(jax.device_put(a[b0:b0 + BLOCK], cpu)
                                   for a in (keys, reset, valid)),
                          jnp.int32(b0))
            out.append(np.asarray(h))
    hits = np.concatenate(out)
    for i, st, c, lane, pos in placed:
        res[i][orders[i][st:st + c]] = hits[pos:pos + c, lane]
    return res


def set_keys(vpns: np.ndarray, sets: int, partitions: int = 1):
    """(set, tag) of each probe of a structure of ``partitions`` partitions of
    ``sets`` sets: partition ``vpn % P`` (the paper's hash), then the set and
    tag of the partition-local key ``vpn // P``."""
    v = np.asarray(vpns, np.int64)
    p, k = v % partitions, v // partitions
    return p * sets + k % sets, k // sets
