"""Trace-driven set-associative LRU TLB / cache simulation (paper §6.2).

The paper probes set-associative TLB models with Pin traces.  We reproduce
the pipeline with a vectorised ``jax.lax.scan`` simulator:

* :func:`simulate_tlb` — one TLB (conventional) or an array of ``P``
  per-partition SPARTA TLBs, as a single scan whose state holds tags and
  last-use timestamps.  SPARTA partitioning maps virtual page ``v`` to
  partition ``v % P`` and probes only that partition's sets — the paper's
  ``MEM_PARTITION_INDEX_HASH``.
* :func:`simulate_system` — the *joint* accelerator pipeline: data cache +
  accelerator-side TLB + memory-side (per-partition) TLB in a single pass,
  emitting per-access hit bits for each structure.  This feeds the CPI
  timeline model (:mod:`repro.core.cpi`) for Figs 9/10.

The same machinery doubles as the accelerator *cache* simulator (a cache is
a set-associative LRU structure keyed by line address).

A Pallas TPU kernel with the identical semantics lives in
``repro.kernels.tlb_sim`` (state resident in VMEM scratch, trace streamed
HBM->VMEM); :func:`simulate_tlb` here is its pure-JAX oracle and the default
execution path on CPU.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparta import TLBConfig

# The LRU state layout and the scan oracles live with the kernels that share
# them; the names stay importable from here.
from repro.kernels.tlb_sim.ref import (  # noqa: F401
    _POISON_LAST,
    _POISON_TAG,
    _scan_tlb,
    _scan_tlb_batched,
    _scan_tlb_batched_carry,
    padded_tlb_state,
)

LINE_SHIFT = 6


# ---------------------------------------------------------------------------
# Key preparation (numpy; cheap) — maps addresses to (set, tag) streams.
# ---------------------------------------------------------------------------

def _prepare_keys(
    vpns: np.ndarray, sets: int, num_partitions: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Compute per-access (global_set_index, tag) for a (possibly partitioned)
    set-associative structure.

    Partition ``p = vpn % P`` (the paper's hash), partition-local key
    ``k = vpn // P``; global set index is ``p * sets + (k % sets)``.
    """
    v = vpns.astype(np.int64)
    if num_partitions > 1:
        p = v % num_partitions
        k = v // num_partitions
    else:
        p = np.zeros_like(v)
        k = v
    set_idx = (p * sets + (k % sets)).astype(np.int32)
    # Store only the true tag (set bits excluded) so it fits int32 on CPU
    # without x64 mode; (set, tag) uniquely identifies the key.
    tag64 = k // sets
    if tag64.size and int(tag64.max()) >= 2**31:
        raise ValueError("tag overflow: key space too large for int32 tags")
    tag = tag64.astype(np.int32)
    return set_idx, tag


def simulate_tlb(
    vpns: np.ndarray,
    cfg: TLBConfig,
    *,
    num_partitions: int = 1,
    warmup_frac: float = 0.25,
) -> "TLBResult":
    """Simulate one conventional TLB (``num_partitions == 1``) or SPARTA's
    array of per-partition TLBs (``num_partitions == P``) on a VPN stream.

    Each partition TLB has ``cfg.entries`` entries (the paper compares equal
    *per-TLB* sizes; total entries = P * entries for SPARTA).
    """
    sets, ways = _geom(cfg)
    set_idx, tag = _prepare_keys(vpns, sets, num_partitions)
    hits = np.asarray(_scan_tlb(jnp.asarray(set_idx), jnp.asarray(tag), sets * num_partitions, ways))
    return TLBResult.from_hits(hits, warmup_frac)


class TLBResult(NamedTuple):
    hits: np.ndarray       # bool [N] (full stream, incl. warmup)
    n_warm: int            # accesses considered after warmup

    @classmethod
    def from_hits(cls, hits: np.ndarray, warmup_frac: float) -> "TLBResult":
        n0 = int(hits.shape[0] * warmup_frac)
        return cls(hits=hits, n_warm=hits.shape[0] - n0)

    @property
    def miss_ratio(self) -> float:
        h = self.hits[self.hits.shape[0] - self.n_warm:]
        return float(1.0 - h.mean()) if h.size else 1.0

    @property
    def hit_ratio(self) -> float:
        return 1.0 - self.miss_ratio


# ---------------------------------------------------------------------------
# Joint system simulation: cache + accel TLB + memory-side TLBs in one scan.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SystemSimConfig:
    """Joint pipeline configuration (Figs 9/10 setups).

    cache        — accelerator data cache geometry (keyed by line address);
                   ``None`` = cacheless accelerator.
    accel_tlb    — accelerator-side TLB; ``None`` = none (virtual cache /
                   pure SPARTA).  ``accel_probe_on_miss_only`` models virtual
                   caches (translation needed only for cache misses).
    mem_tlb      — memory-side TLB geometry (per partition).
    num_partitions — SPARTA P; 1 = conventional/centralised.
    page_shift   — 12 (4 KB) or 21 (2 MB) for both TLB levels.
    """

    cache: Optional[TLBConfig] = TLBConfig(entries=256, ways=4)  # 16KB / 64B
    accel_tlb: Optional[TLBConfig] = None
    mem_tlb: TLBConfig = TLBConfig(entries=128, ways=4)
    num_partitions: int = 1
    page_shift: int = 12
    accel_probe_on_miss_only: bool = True


class SystemEvents(NamedTuple):
    """Per-access hit bits (True = hit) for each structure, after warmup."""

    cache_hit: np.ndarray
    accel_tlb_hit: np.ndarray
    mem_tlb_hit: np.ndarray
    n_warm: int

    def _rate(self, x: np.ndarray) -> float:
        w = x[x.shape[0] - self.n_warm:]
        return float(w.mean()) if w.size else 0.0

    @property
    def cache_hit_ratio(self) -> float:
        return self._rate(self.cache_hit)

    @property
    def accel_tlb_hit_ratio(self) -> float:
        return self._rate(self.accel_tlb_hit)

    def mem_tlb_hit_ratio_given_cache_miss(self) -> float:
        n0 = self.cache_hit.shape[0] - self.n_warm
        cm = ~self.cache_hit[n0:]
        if cm.sum() == 0:
            return 1.0
        return float(self.mem_tlb_hit[n0:][cm].mean())

    def accel_tlb_hit_ratio_given_cache_hit(self) -> float:
        n0 = self.cache_hit.shape[0] - self.n_warm
        ch = self.cache_hit[n0:]
        if ch.sum() == 0:
            return 1.0
        return float(self.accel_tlb_hit[n0:][ch].mean())

    def accel_tlb_hit_ratio_given_cache_miss(self) -> float:
        """Accel-TLB hit rate on the cache-miss stream (virtual caches probe
        the TLB only on misses; bits for cache hits are forced True)."""
        n0 = self.cache_hit.shape[0] - self.n_warm
        cm = ~self.cache_hit[n0:]
        if cm.sum() == 0:
            return 1.0
        return float(self.accel_tlb_hit[n0:][cm].mean())


def _geom(cfg: Optional[TLBConfig]) -> Tuple[int, int]:
    """(sets, ways) of a structure; absent structures degrade to 1x1.

    The single source of geometry truth is :class:`TLBConfig` itself
    (``sets`` / ``effective_ways`` — including the entries < ways
    normalisation); every simulator and sweep derives through here."""
    if cfg is None:
        return 1, 1
    return cfg.sets, cfg.effective_ways


@functools.partial(
    jax.jit,
    static_argnames=("geom", "has_cache", "has_accel", "accel_on_miss_only"),
)
def _scan_system(
    inputs,
    geom: Tuple[int, int, int, int, int, int],
    has_cache: bool,
    has_accel: bool,
    accel_on_miss_only: bool,
):
    (c_set, c_tag, a_set, a_tag, m_set, m_tag) = inputs
    cs, cw, asets, aw, ms, mw = geom

    state0 = (
        jnp.full((cs, cw), -1, dtype=jnp.int32), jnp.zeros((cs, cw), jnp.int32),
        jnp.full((asets, aw), -1, dtype=jnp.int32), jnp.zeros((asets, aw), jnp.int32),
        jnp.full((ms, mw), -1, dtype=jnp.int32), jnp.zeros((ms, mw), jnp.int32),
    )

    def probe(tags, last, s, t, now, do_update):
        row_t = tags[s]
        hit_vec = row_t == t
        hit = jnp.any(hit_vec)
        way = jnp.where(hit, jnp.argmax(hit_vec), jnp.argmin(last[s]))
        upd = do_update
        tags = tags.at[s, way].set(jnp.where(upd, t, tags[s, way]))
        last = last.at[s, way].set(jnp.where(upd, now, last[s, way]))
        return tags, last, hit

    def step(state, inp):
        ct, cl, at, al, mt, ml = state
        cs_i, ctag_i, as_i, atag_i, ms_i, mtag_i, now = inp
        if has_cache:
            ct, cl, c_hit = probe(ct, cl, cs_i, ctag_i, now, jnp.bool_(True))
        else:
            c_hit = jnp.bool_(False)
        if has_accel:
            # Physical cache: TLB probed every access.  Virtual cache: TLB
            # consulted (and filled) only when the access misses the cache.
            do = jnp.where(jnp.bool_(accel_on_miss_only), ~c_hit, jnp.bool_(True))
            at, al, a_hit = probe(at, al, as_i, atag_i, now, do)
            a_hit = jnp.where(do, a_hit, jnp.bool_(True))  # not needed => free
        else:
            a_hit = jnp.bool_(False)
        # Memory-side TLB sees only cache misses (hits never leave the accel).
        mt, ml, m_hit = probe(mt, ml, ms_i, mtag_i, now, ~c_hit)
        m_hit = jnp.where(~c_hit, m_hit, jnp.bool_(True))
        return (ct, cl, at, al, mt, ml), (c_hit, a_hit, m_hit)

    n = c_set.shape[0]
    now = jnp.arange(1, n + 1, dtype=jnp.int32)
    (_, ys) = jax.lax.scan(step, state0, (c_set, c_tag, a_set, a_tag, m_set, m_tag, now))
    return ys


def simulate_system(
    lines: np.ndarray,
    cfg: SystemSimConfig,
    *,
    warmup_frac: float = 0.25,
) -> SystemEvents:
    """Run the joint cache + accel-TLB + memory-TLB pipeline on a line trace."""
    vpns = lines >> (cfg.page_shift - LINE_SHIFT)

    cs, cw = _geom(cfg.cache)
    if cfg.cache is not None:
        c_set, c_tag = _prepare_keys(lines, cs, 1)
    else:
        c_set = np.zeros(lines.shape[0], np.int32)
        c_tag = np.zeros(lines.shape[0], np.int32)

    asets, aw = _geom(cfg.accel_tlb)
    if cfg.accel_tlb is not None:
        a_set, a_tag = _prepare_keys(vpns, asets, 1)
    else:
        a_set = np.zeros(lines.shape[0], np.int32)
        a_tag = np.zeros(lines.shape[0], np.int32)

    ms, mw = _geom(cfg.mem_tlb)
    m_set, m_tag = _prepare_keys(vpns, ms, cfg.num_partitions)

    ys = _scan_system(
        tuple(jnp.asarray(x) for x in (c_set, c_tag, a_set, a_tag, m_set, m_tag)),
        (cs, cw, asets, aw, ms * cfg.num_partitions, mw),
        cfg.cache is not None,
        cfg.accel_tlb is not None,
        cfg.accel_probe_on_miss_only,
    )
    c_hit, a_hit, m_hit = (np.asarray(y) for y in ys)
    n0 = int(lines.shape[0] * warmup_frac)
    return SystemEvents(c_hit, a_hit, m_hit, n_warm=lines.shape[0] - n0)


# ---------------------------------------------------------------------------
# Convenience sweeps.
# ---------------------------------------------------------------------------

def miss_ratio(
    vpns: np.ndarray,
    entries: int,
    *,
    ways: int = 4,
    num_partitions: int = 1,
) -> float:
    # TLBConfig normalizes entries < ways itself (effective_ways).
    return simulate_tlb(vpns, TLBConfig(entries=entries, ways=ways), num_partitions=num_partitions).miss_ratio


def miss_ratio_curve(
    lines: np.ndarray,
    sizes,
    *,
    ways: int = 4,
    num_partitions: int = 1,
    page_shift: int = 12,
    kernel_mode: str = "auto",
) -> "np.ndarray":
    """Miss ratio at each TLB size, via the batched sweep engine: under the
    default ``kernel_mode="auto"`` the exact stack-distance backend
    (``kernel_mode="stackdist"``, :mod:`repro.core.stackdist`) computes every
    size's hit bits from data-parallel depth passes — no per-element scan;
    other modes stream the trace once for all sizes through the batched scan
    or Pallas kernel.  ``repro.core.sweep`` holds the engine;
    :func:`simulate_tlb` remains the single-config oracle path."""
    from repro.core import sweep  # local import: sweep builds on this module

    specs = [
        sweep.TLBSweepSpec(
            cfg=TLBConfig(entries=int(e), ways=ways),
            num_partitions=num_partitions,
            page_shift=page_shift,
        )
        for e in sizes
    ]
    return sweep.sweep_tlb(lines, specs, kernel_mode=kernel_mode).miss_ratios
