"""Batched multi-configuration sweep engine for the TLB/system simulator.

Every paper figure (Figs 4, 8, 9, 10) sweeps TLB geometries and partition
counts over the *same* trace.  The single-config simulators in
:mod:`repro.core.tlbsim` replay the trace once per configuration; this module
simulates **B configurations in a single pass**:

* geometries are padded to a common ``(max_total_sets, max_ways)`` envelope,
* per-config ``(tags, last)`` LRU state is stacked on a leading config axis
  (mirroring SPARTA's own per-partition-TLB-array state layout, paper §4.2),
* one ``lax.scan`` walks the trace while a vmapped probe updates all configs
  concurrently, so the trace is streamed exactly once per sweep instead of
  once per (trace x config) pair.

Way-padding is made invisible by *poisoning* (see
:func:`repro.core.tlbsim.padded_tlb_state`): the batched results are
**bit-identical** to the per-config oracles :func:`~repro.core.tlbsim.simulate_tlb`
and :func:`~repro.core.tlbsim.simulate_system`, which remain the reference
path (tests/test_sweep.py asserts equivalence).

``kernel_mode`` selects the execution backend for the TLB sweep:

* ``"stackdist"`` — the exact sort-based stack-distance engine
  (:mod:`repro.core.stackdist`): specs are bucketed by set-mapping
  (sets, partitions, page_shift) and ONE data-parallel depth pass per bucket
  yields hit bits for every associativity in it — no per-element sequential
  scan at all.  ``"auto"`` prefers this whenever every spec is a pure-LRU TLB
  with small associativity (:data:`repro.core.stackdist.AUTO_MAX_WAYS`),
  which is every sweep in the paper.
* ``"pallas"`` / ``"pallas_interpret"`` — the batched sequential Pallas TPU
  kernel (``repro.kernels.tlb_sim.tlb_sim_batched``, stacked VMEM scratch,
  trace blocks streamed HBM->VMEM once and shared by all configs).
* ``"reference"`` — the pure-JAX batched scan, the bit-exactness oracle.

The joint system sweep (:func:`sweep_system`) has the same two execution
backends, minus ``"stackdist"``: it is not pure-LRU (cache-hit-conditional
TLB probes break the stack-inclusion property), so requesting the
stack-distance engine raises a ``ValueError`` instead of being silently
ignored (the PR 4 policy).  Its Pallas backend is
``repro.kernels.system_sim.system_sim_batched``: all THREE stacked LRU
structures (cache, accel TLB, partitioned mem TLB) stay resident in VMEM
scratch per config, each trace block streams HBM->VMEM once with all six
(set, tag) key views, and per-config structure presence / probe policy ride
along as data flags; the batched scan oracle lives in
``repro.kernels.system_sim.ref`` (re-exported here as
``_scan_system_batched``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import dispatch, stackdist
from repro.core.sparta import TLBConfig
from repro.core.tlbsim import (
    LINE_SHIFT,
    SystemEvents,
    SystemSimConfig,
    TLBResult,
    _geom,
    _prepare_keys,
    _scan_tlb_batched,
    padded_tlb_state,
)
from repro.kernels.common import resolve_mode
from repro.kernels.system_sim import resolve_system_mode, system_sim_batched
from repro.kernels.system_sim.ref import system_sim_batched_ref as _scan_system_batched
from repro.runtime import telemetry

__all__ = [
    "TLBSweepSpec",
    "BatchedTLBResult",
    "BatchedSystemEvents",
    "TLBSweepStream",
    "SystemSweepStream",
    "sweep_tlb",
    "sweep_system",
]


def _note_envelope(stream) -> None:
    """Telemetry event + gauge describing a stream's VMEM-envelope grouping
    (how the chunker packed the batch, and the carried-state footprint).
    Free when no telemetry run is active."""
    tr = telemetry.get_tracer()
    if not tr.active:
        return
    state = stream.export_state()
    state_bytes = int(sum(v.nbytes for k, v in state.items() if k != "now"))
    tr.event("vmem_envelope", engine=stream.engine,
             configs=stream.batch_size, groups=len(stream.groups),
             group_sizes=[len(g) for g in stream.groups],
             state_bytes=state_bytes, block=stream.block)
    tr.gauge(f"{stream.engine}.state_bytes").set(state_bytes)


def _count_sim_accesses(stream, n: int) -> None:
    """Counters for one committed chunk: trace accesses consumed and
    simulated (config x access) pairs advanced."""
    tr = telemetry.get_tracer()
    if not tr.active:
        return
    tr.counter(f"{stream.engine}.trace_accesses").add(int(n))
    tr.counter(f"{stream.engine}.sim_accesses").add(int(n) * stream.batch_size)


# ---------------------------------------------------------------------------
# TLB sweep.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TLBSweepSpec:
    """One point of a TLB sweep: geometry + partitioning + page size.

    ``page_shift=None`` means the input stream is already a VPN stream;
    otherwise the input is a 64-byte line-address stream and VPNs are derived
    per spec (``lines >> (page_shift - LINE_SHIFT)``), so 4 KB and 2 MB
    configs can ride in one batch.
    """

    cfg: TLBConfig
    num_partitions: int = 1
    page_shift: Optional[int] = None

    @property
    def geometry(self) -> Tuple[int, int]:
        """(total_sets, ways) of the simulated structure."""
        sets, ways = _geom(self.cfg)
        return sets * self.num_partitions, ways


@dataclasses.dataclass(frozen=True)
class BatchedTLBResult:
    """Per-access hit bits for B configs sharing one trace."""

    hits: np.ndarray   # bool [B, N] (full stream, incl. warmup)
    n_warm: int

    def __len__(self) -> int:
        return self.hits.shape[0]

    def __getitem__(self, i: int) -> TLBResult:
        return TLBResult(hits=self.hits[i], n_warm=self.n_warm)

    @property
    def miss_ratios(self) -> np.ndarray:
        """Post-warmup miss ratio per config, [B]."""
        w = self.hits[:, self.hits.shape[1] - self.n_warm:]
        if w.shape[1] == 0:
            return np.ones(self.hits.shape[0])
        return 1.0 - w.mean(axis=1)


# The kernels' scoped VMEM is 16 MiB by default on v5e; cap the stacked
# state (as the kernels lay it out) at half of that and chunk the batch when
# a sweep's padded envelope would not fit.  Chunks still stream the trace
# once each.
_VMEM_STATE_BUDGET_BYTES = 8 * 1024 * 1024


def _keys_for_mapping(
    addrs: np.ndarray, sets: int, num_partitions: int, page_shift: Optional[int]
) -> Tuple[np.ndarray, np.ndarray]:
    """(set, tag) streams for one set-mapping — the single address-to-key rule
    every sweep backend shares (bit-identity depends on it)."""
    vpns = addrs if page_shift is None else addrs >> (page_shift - LINE_SHIFT)
    return _prepare_keys(vpns, sets, num_partitions)


def _sweep_keys(
    addrs: np.ndarray, specs: Sequence[TLBSweepSpec]
) -> Tuple[np.ndarray, np.ndarray]:
    """Stacked [B, N] (set, tag) streams, one row per spec."""
    rows = [_keys_for_mapping(addrs, *_mapping_key(sp)) for sp in specs]
    return np.stack([r[0] for r in rows]), np.stack([r[1] for r in rows])


def sweep_tlb(
    addrs: np.ndarray,
    specs: Sequence[TLBSweepSpec],
    *,
    warmup_frac: float = 0.25,
    kernel_mode: str = "auto",
    block: int = 512,
) -> BatchedTLBResult:
    """Simulate every spec on one address stream in a single trace pass.

    ``addrs`` is a VPN stream for specs with ``page_shift=None`` and a line
    stream otherwise (mixing both in one batch is a caller error).  Results
    are bit-identical to calling :func:`repro.core.tlbsim.simulate_tlb` once
    per spec.
    """
    if not specs:
        raise ValueError("sweep_tlb needs at least one spec")
    shifted = [sp.page_shift is not None for sp in specs]
    if any(shifted) and not all(shifted):
        raise ValueError(
            "sweep_tlb batch mixes page_shift=None (VPN-stream) specs with "
            "page_shift-set (line-stream) specs; one input stream cannot be both"
        )
    # Backend selection is the dispatch layer's job; a bare (unorchestrated)
    # call makes a cold-start decision — the orchestrator passes calibrated,
    # already-concrete modes down to the streams instead.
    mode = dispatch.decide_tlb(
        kernel_mode, specs, n_accesses=len(addrs)).mode
    if mode == "stackdist":
        hits = _sweep_tlb_stackdist(addrs, specs)
        n0 = int(hits.shape[1] * warmup_frac)
        return BatchedTLBResult(hits=hits, n_warm=hits.shape[1] - n0)
    set_b, tag_b = _sweep_keys(addrs, specs)
    geoms = [sp.geometry for sp in specs]
    total_sets = max(g[0] for g in geoms)
    ways = max(g[1] for g in geoms)
    valid_ways = tuple(g[1] for g in geoms)

    n = set_b.shape[1]
    if mode == "reference":
        hits = np.asarray(
            _scan_tlb_batched(jnp.asarray(set_b), jnp.asarray(tag_b), total_sets, ways, valid_ways)
        )
    else:
        from repro.kernels.tlb_sim import tlb_sim_batched

        pad = (-n) % min(block, n)
        hits = np.empty((len(specs), n), dtype=bool)
        for chunk in _vmem_chunks(geoms, block=min(block, n)):
            c_sets = max(geoms[i][0] for i in chunk)
            c_ways = max(geoms[i][1] for i in chunk)
            s_c, t_c = set_b[chunk], tag_b[chunk]
            if pad:
                # The kernel streams whole blocks; park padding accesses in an
                # extra set row (index c_sets) that no real config ever
                # indexes, then drop their hit bits.
                s_c = np.pad(s_c, ((0, 0), (0, pad)), constant_values=c_sets)
                t_c = np.pad(t_c, ((0, 0), (0, pad)), constant_values=0)
            hits[chunk] = np.asarray(
                tlb_sim_batched(
                    jnp.asarray(s_c), jnp.asarray(t_c),
                    c_sets + (1 if pad else 0), c_ways,
                    tuple(geoms[i][1] for i in chunk),
                    block=block, kernel_mode=mode,
                )
            )[:, :n]
    n0 = int(n * warmup_frac)
    return BatchedTLBResult(hits=hits, n_warm=n - n0)


def envelope_chunks(
    dims: Sequence[Tuple[int, ...]],
    state_elems,
    *,
    stream_words: int,
    budget_bytes: int,
) -> list:
    """Greedy VMEM chunker shared by every batched engine (TLB sweep here,
    timeline sweep in :mod:`repro.core.timeline`): partition item indices so
    each chunk's scratch footprint — per-item state on the chunk's
    elementwise-max envelope (``state_elems(dims)`` 4-byte words) plus the
    streamed trace columns (``stream_words`` per item) — fits the budget.

    Sorting by padded footprint groups like-sized configurations, so a few
    huge items don't inflate the envelope of every small one.  A chunk always
    takes at least one item.
    """
    order = sorted(range(len(dims)), key=lambda i: state_elems(dims[i]))
    chunks, cur = [], []
    env: Tuple[int, ...] = ()
    for i in order:
        new_env = dims[i] if not cur else tuple(map(max, env, dims[i]))
        vmem_bytes = (state_elems(new_env) + stream_words) * (len(cur) + 1) * 4
        if cur and vmem_bytes > budget_bytes:
            chunks.append(cur)
            cur, new_env = [], dims[i]
        cur.append(i)
        env = new_env
    chunks.append(cur)
    return chunks


def _lru_state_words(sets: int, ways: int) -> int:
    """int32 words of one config's (tags, last) pair as the Pallas kernels
    hold it in VMEM: ``sets`` + 1 rows (the +1 is the parked row that
    trace-padding accesses may use) in the lane-dense layout."""
    from repro.kernels.tlb_sim.kernel import LANES, lane_layout

    return 2 * lane_layout(sets + 1, ways).rows * LANES


def _vmem_chunks(geoms: Sequence[Tuple[int, int]], *, block: int = 512) -> list:
    """TLB-sweep instantiation of :func:`envelope_chunks`: stacked LRU state
    is :func:`_lru_state_words` per config and each config streams
    3 x block words (set/tag/hit)."""
    return envelope_chunks(
        geoms, lambda g: _lru_state_words(*g),
        stream_words=3 * block, budget_bytes=_VMEM_STATE_BUDGET_BYTES)


class TLBSweepStream:
    """Resumable chunked execution of :func:`sweep_tlb` (minus the
    non-chunkable ``"stackdist"`` backend).

    The stream owns the carried per-config LRU state; each
    :meth:`run_chunk` call advances every config through one slice of the
    address stream and returns that slice's hit bits.  Feeding the chunks of
    a trace in order is **bit-identical** to one monolithic
    :func:`sweep_tlb` call — in any backend, and across backend *changes* at
    chunk boundaries (the orchestrator's degradation ladder): the batch is
    always grouped by the Pallas VMEM envelope (:func:`_vmem_chunks`) and
    every group's state always allocates the spare parked set row, so the
    state layout is independent of the mode a chunk happens to run in.

    :meth:`export_state` / :meth:`import_state` round-trip the carried state
    through plain numpy arrays (the checkpoint payload of
    :mod:`repro.core.orchestrator`).
    """

    engine = "sweep_tlb"

    def __init__(self, specs: Sequence[TLBSweepSpec], *, block: int = 512):
        if not specs:
            raise ValueError("TLBSweepStream needs at least one spec")
        shifted = [sp.page_shift is not None for sp in specs]
        if any(shifted) and not all(shifted):
            raise ValueError(
                "TLBSweepStream batch mixes page_shift=None (VPN-stream) specs "
                "with page_shift-set (line-stream) specs; one input stream "
                "cannot be both")
        self.specs = tuple(specs)
        self.block = int(block)
        self._geoms = [sp.geometry for sp in self.specs]
        self.groups = _vmem_chunks(self._geoms, block=self.block)
        self._state = []
        for g in self.groups:
            sets = max(self._geoms[i][0] for i in g)
            ways = max(self._geoms[i][1] for i in g)
            valid = tuple(self._geoms[i][1] for i in g)
            # One spare parked set row (index `sets`) in every mode, so a
            # chunk may be block-padded mid-stream without observable effect.
            self._state.append(padded_tlb_state(len(g), sets + 1, ways, valid))
        self.now = 0
        _note_envelope(self)

    @property
    def batch_size(self) -> int:
        return len(self.specs)

    def fingerprint(self) -> dict:
        """JSON-able identity of the stream's layout: a checkpoint taken by
        one stream may only be imported by a stream with an equal one."""
        return {
            "engine": self.engine,
            "block": self.block,
            "specs": [[g[0], g[1], sp.num_partitions,
                       sp.page_shift if sp.page_shift is not None else -1]
                      for g, sp in zip(self._geoms, self.specs)],
        }

    def run_chunk(self, addrs: np.ndarray, *, kernel_mode: str = "auto") -> np.ndarray:
        """Advance every config through ``addrs`` (the next trace slice);
        returns hit bits bool [B, len(addrs)].  State commits only after the
        whole chunk computed, so a failed call leaves the stream unchanged
        and the chunk can be retried (possibly in a different mode)."""
        mode = resolve_mode(kernel_mode)
        set_b, tag_b = _sweep_keys(np.asarray(addrs), self.specs)
        n = set_b.shape[1]
        from repro.kernels.tlb_sim import tlb_sim_batched_carry

        hits = np.empty((len(self.specs), n), dtype=bool)
        new_state = []
        for gi, g in enumerate(self.groups):
            h, tags, last = tlb_sim_batched_carry(
                jnp.asarray(set_b[g]), jnp.asarray(tag_b[g]),
                *self._state[gi], self.now,
                block=self.block, kernel_mode=mode)
            hits[g] = np.asarray(h)   # forces the computation (commit gate)
            new_state.append((tags, last))
        self._state = new_state
        self.now += n
        _count_sim_accesses(self, n)
        return hits

    def export_state(self) -> dict:
        out = {"now": np.array([self.now], np.int64)}
        for gi, (tags, last) in enumerate(self._state):
            out[f"g{gi}_tags"] = np.asarray(tags)
            out[f"g{gi}_last"] = np.asarray(last)
        return out

    def import_state(self, arrays: dict) -> None:
        state = []
        for gi in range(len(self.groups)):
            pair = []
            for part in ("tags", "last"):
                key = f"g{gi}_{part}"
                if key not in arrays:
                    raise ValueError(f"{self.engine} state missing array {key!r}")
                arr = np.asarray(arrays[key])
                want = tuple(np.asarray(self._state[gi][0]).shape)
                if tuple(arr.shape) != want:
                    raise ValueError(
                        f"{self.engine} state array {key!r} has shape "
                        f"{tuple(arr.shape)}, expected {want}")
                pair.append(jnp.asarray(arr.astype(np.int32)))
            state.append(tuple(pair))
        self._state = state
        self.now = int(np.asarray(arrays["now"]).reshape(-1)[0])


# ---------------------------------------------------------------------------
# Stack-distance backend: bucket specs by set-mapping, one depth pass each.
# ---------------------------------------------------------------------------

def _mapping_key(sp: TLBSweepSpec) -> Tuple[int, int, Optional[int]]:
    """The (set, tag) stream of a spec depends only on this triple — specs
    differing only in associativity share one stack-depth pass."""
    sets, _ = _geom(sp.cfg)
    return sets, sp.num_partitions, sp.page_shift


def _sweep_tlb_stackdist(addrs: np.ndarray, specs: Sequence[TLBSweepSpec]) -> np.ndarray:
    """Hit bits [B, N] via one stack-depth pass per distinct set-mapping.

    Keys are prepared once per *mapping* (not per spec), every mapping's
    depth pass runs data-parallel (no per-element sequential scan), and each
    spec reads its hit bits off its bucket's depths at its own associativity.
    """
    keys = [_mapping_key(sp) for sp in specs]
    uniq = list(dict.fromkeys(keys))
    rows = [_keys_for_mapping(addrs, *k) for k in uniq]
    set_rows = [r[0] for r in rows]
    tag_rows = [r[1] for r in rows]
    cap = max(sp.cfg.effective_ways for sp in specs)
    depth = stackdist.stack_depths_batched(
        np.stack(set_rows), np.stack(tag_rows), cap=cap
    )
    bucket = {k: i for i, k in enumerate(uniq)}
    return np.stack([
        stackdist.hits_from_depths(depth[bucket[k]], sp.cfg.effective_ways)
        for k, sp in zip(keys, specs)
    ])


# ---------------------------------------------------------------------------
# Joint system sweep: cache + accel TLB + memory-side TLBs, B configs at once.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchedSystemEvents:
    """Stacked per-access hit bits for B system configs on one trace."""

    cache_hit: np.ndarray      # bool [B, N]
    accel_tlb_hit: np.ndarray  # bool [B, N]
    mem_tlb_hit: np.ndarray    # bool [B, N]
    n_warm: int

    def __len__(self) -> int:
        return self.cache_hit.shape[0]

    def __getitem__(self, i: int) -> SystemEvents:
        return SystemEvents(
            cache_hit=self.cache_hit[i],
            accel_tlb_hit=self.accel_tlb_hit[i],
            mem_tlb_hit=self.mem_tlb_hit[i],
            n_warm=self.n_warm,
        )


def _system_vmem_chunks(
    dims: Sequence[Tuple[int, int, int, int, int, int]], *, block: int = 512
) -> list:
    """Joint-system instantiation of :func:`envelope_chunks`: per config the
    stacked LRU state is :func:`_lru_state_words` for each of the three
    structures (cache, accel TLB, mem TLB) and each config streams
    7 x block words per grid step (six (set, tag) key views in, one packed
    hit word out)."""
    return envelope_chunks(
        dims,
        lambda g: sum(_lru_state_words(g[k], g[k + 1]) for k in (0, 2, 4)),
        stream_words=7 * block, budget_bytes=_VMEM_STATE_BUDGET_BYTES)


def _system_keys(lines: np.ndarray, cfg: SystemSimConfig):
    """Per-config (cache, accel, mem) (set, tag) streams — the exact key
    preparation of :func:`repro.core.tlbsim.simulate_system`."""
    vpns = lines >> (cfg.page_shift - LINE_SHIFT)
    n = lines.shape[0]
    zeros = np.zeros(n, np.int32)

    cs, _ = _geom(cfg.cache)
    c_set, c_tag = _prepare_keys(lines, cs, 1) if cfg.cache is not None else (zeros, zeros)
    asets, _ = _geom(cfg.accel_tlb)
    a_set, a_tag = _prepare_keys(vpns, asets, 1) if cfg.accel_tlb is not None else (zeros, zeros)
    ms, _ = _geom(cfg.mem_tlb)
    m_set, m_tag = _prepare_keys(vpns, ms, cfg.num_partitions)
    return c_set, c_tag, a_set, a_tag, m_set, m_tag


def sweep_system(
    lines: np.ndarray,
    cfgs: Sequence[SystemSimConfig],
    *,
    warmup_frac: float = 0.25,
    kernel_mode: str = "auto",
    block: int = 512,
) -> BatchedSystemEvents:
    """Run the joint cache + accel-TLB + memory-TLB pipeline for every config
    in ONE pass over the line trace.

    Configs may differ in every dimension (cache/accel presence, geometries,
    partitions, page size, probe policy); results are bit-identical to
    calling :func:`repro.core.tlbsim.simulate_system` once per config.

    ``kernel_mode`` selects the batched scan reference or the batched Pallas
    kernel (``repro.kernels.system_sim``); ``"stackdist"`` raises (no exact
    stack-distance execution exists for cache-hit-conditional probes).
    """
    if not cfgs:
        raise ValueError("sweep_system needs at least one config")
    mode = dispatch.decide_system(
        kernel_mode, cfgs, n_accesses=int(lines.shape[0])).mode

    streams = [np.stack(rows) for rows in zip(*(_system_keys(lines, c) for c in cfgs))]

    def envelope(geoms):
        return max(g[0] for g in geoms), max(g[1] for g in geoms), tuple(g[1] for g in geoms)

    c_geo = [_geom(c.cache) for c in cfgs]
    a_geo = [_geom(c.accel_tlb) for c in cfgs]
    m_geo = [(_geom(c.mem_tlb)[0] * c.num_partitions, _geom(c.mem_tlb)[1]) for c in cfgs]

    n = lines.shape[0]
    n0 = int(n * warmup_frac)
    if mode == "reference":
        cs, cw, c_valid = envelope(c_geo)
        asets, aw, a_valid = envelope(a_geo)
        ms, mw, m_valid = envelope(m_geo)
        flags = tuple(
            jnp.asarray([f(c) for c in cfgs], jnp.bool_)
            for f in (
                lambda c: c.cache is not None,
                lambda c: c.accel_tlb is not None,
                lambda c: c.accel_probe_on_miss_only,
            )
        )
        ys = _scan_system_batched(
            tuple(jnp.asarray(s) for s in streams),
            flags,
            (cs, cw, asets, aw, ms, mw),
            (c_valid, a_valid, m_valid),
        )
        c_hit, a_hit, m_hit = (np.asarray(y) for y in ys)
        return BatchedSystemEvents(c_hit, a_hit, m_hit, n_warm=n - n0)

    # Pallas path: chunk the batch so each chunk's three-structure envelope
    # fits the VMEM scratch budget, and pad the trace tail to whole blocks
    # with accesses parked in an extra set row (index = envelope sets) that
    # no real config ever indexes.
    flags_np = np.asarray(
        [[c.cache is not None, c.accel_tlb is not None, c.accel_probe_on_miss_only]
         for c in cfgs], np.int32)
    dims = [c_geo[i] + a_geo[i] + m_geo[i] for i in range(len(cfgs))]
    blk = min(block, n)
    pad = (-n) % blk
    hits = [np.empty((len(cfgs), n), dtype=bool) for _ in range(3)]
    for chunk in _system_vmem_chunks(dims, block=blk):
        geom, valid, chunk_streams = [], [], []
        for k, geos in enumerate((c_geo, a_geo, m_geo)):
            sets = max(geos[i][0] for i in chunk)
            ways = max(geos[i][1] for i in chunk)
            s_c, t_c = streams[2 * k][chunk], streams[2 * k + 1][chunk]
            if pad:
                s_c = np.pad(s_c, ((0, 0), (0, pad)), constant_values=sets)
                t_c = np.pad(t_c, ((0, 0), (0, pad)), constant_values=0)
            geom += [sets + (1 if pad else 0), ways]
            valid.append(tuple(geos[i][1] for i in chunk))
            chunk_streams += [jnp.asarray(s_c), jnp.asarray(t_c)]
        ys = system_sim_batched(
            *chunk_streams, jnp.asarray(flags_np[chunk]),
            tuple(geom), tuple(valid), block=blk, kernel_mode=mode)
        for h, y in zip(hits, ys):
            h[chunk] = np.asarray(y)[:, :n]
    return BatchedSystemEvents(*hits, n_warm=n - n0)


class SystemSweepStream:
    """Resumable chunked execution of :func:`sweep_system`.

    Same contract as :class:`TLBSweepStream`, with three carried LRU
    structures per config (cache, accel TLB, partitioned mem TLB): feeding a
    line trace chunk by chunk is bit-identical to one monolithic
    :func:`sweep_system` call in any backend and across backend changes at
    chunk boundaries.  The batch grouping (:func:`_system_vmem_chunks`) and
    the spare parked set row per structure are mode-independent.
    """

    engine = "sweep_system"
    _STRUCTS = ("c", "a", "m")

    def __init__(self, cfgs: Sequence[SystemSimConfig], *, block: int = 512):
        if not cfgs:
            raise ValueError("SystemSweepStream needs at least one config")
        self.cfgs = tuple(cfgs)
        self.block = int(block)
        c_geo = [_geom(c.cache) for c in self.cfgs]
        a_geo = [_geom(c.accel_tlb) for c in self.cfgs]
        m_geo = [(_geom(c.mem_tlb)[0] * c.num_partitions, _geom(c.mem_tlb)[1])
                 for c in self.cfgs]
        self._geos = (c_geo, a_geo, m_geo)
        dims = [c_geo[i] + a_geo[i] + m_geo[i] for i in range(len(self.cfgs))]
        self.groups = _system_vmem_chunks(dims, block=self.block)
        self._flags = np.asarray(
            [[c.cache is not None, c.accel_tlb is not None,
              c.accel_probe_on_miss_only] for c in self.cfgs], np.int32)
        self._state = []
        for g in self.groups:
            st = []
            for geos in self._geos:
                sets = max(geos[i][0] for i in g)
                ways = max(geos[i][1] for i in g)
                valid = tuple(geos[i][1] for i in g)
                st += list(padded_tlb_state(len(g), sets + 1, ways, valid))
            self._state.append(tuple(st))
        self.now = 0
        _note_envelope(self)

    @property
    def batch_size(self) -> int:
        return len(self.cfgs)

    def fingerprint(self) -> dict:
        return {
            "engine": self.engine,
            "block": self.block,
            "cfgs": [[*self._geos[0][i], *self._geos[1][i], *self._geos[2][i],
                      int(self._flags[i][0]), int(self._flags[i][1]),
                      int(self._flags[i][2]), c.num_partitions, c.page_shift]
                     for i, c in enumerate(self.cfgs)],
        }

    def run_chunk(self, lines: np.ndarray, *, kernel_mode: str = "auto"):
        """Advance every config through ``lines`` (the next trace slice);
        returns (cache, accel_tlb, mem_tlb) hit bits, each bool
        [B, len(lines)].  Commit-on-success like :class:`TLBSweepStream`."""
        mode = resolve_system_mode(kernel_mode)
        lines = np.asarray(lines)
        streams = [np.stack(rows) for rows in
                   zip(*(_system_keys(lines, c) for c in self.cfgs))]
        n = lines.shape[0]
        from repro.kernels.system_sim import system_sim_batched_carry

        hits = [np.empty((len(self.cfgs), n), dtype=bool) for _ in range(3)]
        new_state = []
        for gi, g in enumerate(self.groups):
            ys, st = system_sim_batched_carry(
                *(jnp.asarray(s[g]) for s in streams),
                jnp.asarray(self._flags[g]), self._state[gi], self.now,
                block=self.block, kernel_mode=mode)
            for h, y in zip(hits, ys):
                h[g] = np.asarray(y)   # forces the computation (commit gate)
            new_state.append(st)
        self._state = new_state
        self.now += n
        _count_sim_accesses(self, n)
        return tuple(hits)

    def export_state(self) -> dict:
        out = {"now": np.array([self.now], np.int64)}
        for gi, st in enumerate(self._state):
            for k, s in enumerate(self._STRUCTS):
                out[f"g{gi}_{s}_tags"] = np.asarray(st[2 * k])
                out[f"g{gi}_{s}_last"] = np.asarray(st[2 * k + 1])
        return out

    def import_state(self, arrays: dict) -> None:
        state = []
        for gi in range(len(self.groups)):
            st = []
            for k, s in enumerate(self._STRUCTS):
                for j, part in enumerate(("tags", "last")):
                    key = f"g{gi}_{s}_{part}"
                    if key not in arrays:
                        raise ValueError(
                            f"{self.engine} state missing array {key!r}")
                    arr = np.asarray(arrays[key])
                    want = tuple(np.asarray(self._state[gi][2 * k + j]).shape)
                    if tuple(arr.shape) != want:
                        raise ValueError(
                            f"{self.engine} state array {key!r} has shape "
                            f"{tuple(arr.shape)}, expected {want}")
                    st.append(jnp.asarray(arr.astype(np.int32)))
            state.append(tuple(st))
        self._state = state
        self.now = int(np.asarray(arrays["now"]).reshape(-1)[0])
