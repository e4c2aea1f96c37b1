"""Cycle-approximate event-timeline engine (per-access latency + queueing).

:mod:`repro.core.cpi` turns measured hit *rates* into average per-access
latency — it cannot express queueing contention on shared memory-side TLBs
or latency *distributions*, exactly the effects SPARTA's partitioning is
designed to remove.  This module composes a **per-access completion time**
from the per-access hit/miss event bits already produced by
:func:`repro.core.tlbsim.simulate_system` / :func:`repro.core.sweep.sweep_system`,
threading three bounded resources through the Fig 3 timelines:

* an MSHR-style window of outstanding misses per accelerator,
* per-partition memory-side TLB service ports with FIFO queueing (SPARTA),
* banked DRAM service slots (page walks, PTE reads and data fetches all
  occupy a bank).

Outputs are per-access latency/overhead arrays reduced to total cycles,
throughput and p50/p95/p99 tails for the four designs
(``conventional`` / ``sparta`` / ``dipta`` / ``ideal``).

**Oracle property**: with every resource unbounded
(:meth:`TimelineConfig.unbounded`) all queue waits vanish and the
post-warmup *mean* latency / translation overhead reproduce
:mod:`repro.core.cpi`'s analytical averages exactly (``tests/test_timeline.py``
asserts <= 1e-6 relative error for all designs and workloads).

The sequential hot loop lives in :mod:`repro.kernels.timeline` (jnp
``lax.scan`` oracle + Pallas TPU kernel with the state resident in VMEM
scratch, dispatched by ``kernel_mode`` like every other kernel package).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.cpi import DIPTA_WAY_PREDICTION_ACCURACY
from repro.core.sparta import SystemLatencies
from repro.core.tlbsim import LINE_SHIFT, SystemEvents
from repro.kernels.timeline import (
    TimelineParams,
    pack_params,
    resolve_timeline_mode,
    timeline_init_state_batched,
    timeline_sim,
    timeline_sim_batched,
    timeline_sim_batched_carry,
)

__all__ = ["TimelineConfig", "TimelineResult", "TimelineSpec",
           "TimelineSweepStream", "simulate_timeline", "sweep_timeline",
           "round_robin_accel_ids", "DESIGNS"]

DESIGNS = ("conventional", "sparta", "dipta", "ideal")


@dataclasses.dataclass(frozen=True)
class TimelineConfig:
    """Queueing-resource configuration (defaults logged in EXPERIMENTS.md).

    A count of 0 means the resource is *unbounded* — no queueing on it.
    ``mshrs`` bounds outstanding misses per accelerator, ``tlb_ports`` is the
    number of service ports of each partition's memory-side TLB, and
    ``dram_banks`` the machine-wide number of DRAM banks.  ``tlb_service`` /
    ``dram_service`` are the port/bank *occupancy* times per request and
    default to the corresponding probe/access latencies (``l_tlb`` /
    ``l_dram``); ``issue_interval`` is the cycles between successive issue
    attempts of one accelerator (offered-load knob).
    """

    mshrs: int = 8
    tlb_ports: int = 1
    dram_banks: int = 16
    tlb_service: Optional[float] = None
    dram_service: Optional[float] = None
    issue_interval: float = 1.0

    @classmethod
    def unbounded(cls, **kw) -> "TimelineConfig":
        """No queueing anywhere — the cpi-consistency configuration."""
        return cls(mshrs=0, tlb_ports=0, dram_banks=0, **kw)


@dataclasses.dataclass(frozen=True)
class TimelineResult:
    """Per-access timing arrays + reductions (post-warmup like SystemEvents)."""

    latency: np.ndarray    # f32 [N] issue -> completion cycles
    overhead: np.ndarray   # f32 [N] translation-induced component (incl. waits)
    done: np.ndarray       # f32 [N] absolute completion times
    cache_hit: np.ndarray  # bool [N]
    n_warm: int

    def _warm(self, x: np.ndarray) -> np.ndarray:
        return x[x.shape[0] - self.n_warm:]

    @property
    def mean_latency(self) -> float:
        w = self._warm(self.latency)
        return float(w.mean(dtype=np.float64)) if w.size else 0.0

    @property
    def mean_overhead(self) -> float:
        w = self._warm(self.overhead)
        return float(w.mean(dtype=np.float64)) if w.size else 0.0

    def latency_percentile(self, q: float) -> float:
        w = self._warm(self.latency)
        return float(np.percentile(w, q)) if w.size else 0.0

    def overhead_percentile(self, q: float, *, misses_only: bool = True) -> float:
        """Tail of the translation-induced latency.  ``misses_only`` restricts
        to cache-missing accesses (the translated stream): with high cache
        hit rates an all-access p99 would be identically zero for every
        design and say nothing about translation."""
        w = self._warm(self.overhead)
        if misses_only:
            w = w[~self._warm(self.cache_hit)]
        return float(np.percentile(w, q)) if w.size else 0.0

    @property
    def total_cycles(self) -> float:
        """Makespan: first issue happens at t=0."""
        return float(self.done.max()) if self.done.size else 0.0

    @property
    def throughput(self) -> float:
        """Accesses completed per cycle over the whole stream."""
        return self.done.shape[0] / max(self.total_cycles, 1e-9)

    def summary(self) -> Dict[str, float]:
        return {
            "mean_latency": self.mean_latency,
            "mean_overhead": self.mean_overhead,
            "p50_latency": self.latency_percentile(50),
            "p95_latency": self.latency_percentile(95),
            "p99_latency": self.latency_percentile(99),
            "p99_overhead": self.overhead_percentile(99),
            "total_cycles": self.total_cycles,
            "throughput": self.throughput,
        }


def round_robin_accel_ids(n: int, num_accels: int, granularity: int = 1) -> np.ndarray:
    """Issuing-accelerator ids for a :func:`repro.core.traces.interleave`'d
    trace (round-robin at ``granularity`` accesses per turn)."""
    return ((np.arange(n) // granularity) % num_accels).astype(np.int32)


def _pte_banks(vpns: np.ndarray, banks: int) -> np.ndarray:
    """DRAM bank of each page's PTE: a cheap stateless scatter of the VPN so
    walk/PTE traffic spreads over banks independently of the data lines."""
    v = vpns.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    return ((v >> np.uint64(17)) % np.uint64(banks)).astype(np.int32)


def _timeline_inputs(
    lines: np.ndarray,
    events: SystemEvents,
    design: str,
    lat: SystemLatencies,
    cfg: TimelineConfig,
    num_partitions: int,
    page_shift: int,
    num_accelerators: int,
    accel_ids: Optional[np.ndarray],
    workload: str,
    way_accuracy: Optional[float],
) -> Tuple[Tuple[np.ndarray, ...], TimelineParams]:
    """The single address/event-to-input rule every timeline backend shares
    (bit-identity of the batched engine depends on it): per-access id/hit/pen
    columns plus the static :class:`TimelineParams` of one simulation."""
    if design not in DESIGNS:
        raise ValueError(f"unknown design {design!r}; options: {DESIGNS}")
    n = int(lines.shape[0])
    if accel_ids is None:
        accel_ids = round_robin_accel_ids(n, num_accelerators)
    vpns = lines >> (page_shift - LINE_SHIFT)

    P = num_partitions if design == "sparta" else 1
    part = (vpns % P).astype(np.int32)
    banks = max(cfg.dram_banks, 1)
    bank_d = (lines % banks).astype(np.int32)
    bank_p = _pte_banks(vpns, banks)

    c = events.cache_hit.astype(np.int32)
    th = events.accel_tlb_hit.astype(np.int32)
    mh = events.mem_tlb_hit.astype(np.int32)

    pen = np.zeros(n, np.float32)
    if design == "dipta":
        acc = way_accuracy if way_accuracy is not None else \
            DIPTA_WAY_PREDICTION_ACCURACY.get(workload, 0.75)
        pen[:] = (1.0 - acc) * 2.0 * lat.l_dram

    params = TimelineParams(
        serial_walk=(design == "conventional"),
        mem_tlb=(design == "sparta"),
        num_accels=int(num_accelerators),
        mshrs=int(cfg.mshrs),
        num_partitions=int(P),
        tlb_ports=int(cfg.tlb_ports),
        dram_banks=int(cfg.dram_banks),
        l_cache=float(lat.l_cache),
        l_tlb=float(lat.l_tlb),
        l_dram=float(lat.l_dram),
        t_net=float(lat.t_net),
        tlb_occ=float(cfg.tlb_service if cfg.tlb_service is not None else lat.l_tlb),
        dram_occ=float(cfg.dram_service if cfg.dram_service is not None else lat.l_dram),
        issue_interval=float(cfg.issue_interval),
    )
    return (accel_ids.astype(np.int32), part, bank_d, bank_p, c, th, mh, pen), params


def simulate_timeline(
    lines: np.ndarray,
    events: SystemEvents,
    design: str,
    lat: SystemLatencies,
    *,
    cfg: TimelineConfig = TimelineConfig(),
    num_partitions: int = 1,
    page_shift: int = 12,
    num_accelerators: int = 1,
    accel_ids: Optional[np.ndarray] = None,
    workload: str = "",
    way_accuracy: Optional[float] = None,
    kernel_mode: str = "auto",
    block: int = 512,
) -> TimelineResult:
    """Per-access completion times for one (design, trace, events) triple.

    ``events`` must come from the simulation of the *same* trace (``lines``)
    with the matching geometry/partitioning (``simulate_system`` or a
    ``sweep_system`` row).  ``num_accelerators`` > 1 models N accelerators
    sharing the memory-side structures: the trace is their interleaved
    stream (``traces.thread_traces`` + ``interleave``) and ``accel_ids``
    names the issuer of each access (round-robin by default).

    This is the reference path; for a sweep of many (design x workload x
    accel-count) cells use :func:`sweep_timeline`, which streams all cells
    in one pass and is bit-identical per cell.
    """
    inputs, params = _timeline_inputs(
        lines, events, design, lat, cfg, num_partitions, page_shift,
        num_accelerators, accel_ids, workload, way_accuracy)
    latency, overhead, done = timeline_sim(
        *(jnp.asarray(x) for x in inputs),
        params, block=block, kernel_mode=kernel_mode)
    return TimelineResult(
        latency=np.asarray(latency),
        overhead=np.asarray(overhead),
        done=np.asarray(done),
        cache_hit=events.cache_hit.astype(bool),
        n_warm=events.n_warm,
    )


# ---------------------------------------------------------------------------
# Batched multi-simulation sweep: all (design x workload x accel-count) cells
# advance per trace element in ONE pass.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class TimelineSpec:
    """One cell of a timeline sweep: (trace, events, design, queue config,
    accelerator count) plus the per-design knobs of
    :func:`simulate_timeline`.

    ``events`` must come from the simulation of the *same* ``lines`` trace
    with the matching geometry/partitioning (a ``sweep_system`` row — one
    batched system pass can feed many specs).  ``lat=None`` falls back to the
    ``lat`` argument of :func:`sweep_timeline`, so a shared latency table is
    stated once per sweep.
    """

    lines: np.ndarray
    events: SystemEvents
    design: str
    lat: Optional[SystemLatencies] = None
    cfg: TimelineConfig = TimelineConfig()
    num_partitions: int = 1
    page_shift: int = 12
    num_accelerators: int = 1
    accel_ids: Optional[np.ndarray] = None
    workload: str = ""
    way_accuracy: Optional[float] = None


# Same per-core scratch discipline as repro.core.sweep: cap the stacked VMEM
# footprint (queueing state + streamed trace blocks per sim) and chunk the
# sim axis when a sweep's padded envelope would not fit.  Chunks still stream
# the trace once each.
_VMEM_STATE_BUDGET_BYTES = 8 * 1024 * 1024


def _timeline_vmem_chunks(
    dims: Sequence[Tuple[int, int, int, int, int]], *, block: int = 512
) -> List[List[int]]:
    """Timeline instantiation of :func:`repro.core.sweep.envelope_chunks`:
    the stacked queueing state on a chunk's (A, M, P, T, D) envelope is
    A + A*M + A + P*T + D words per sim and each sim streams 11 x block
    words (8 input + 3 output per-access columns)."""
    from repro.core.sweep import envelope_chunks

    def state_elems(d):
        A, M, P, T, D = d
        return A + A * M + A + P * T + D

    return envelope_chunks(
        dims, state_elems,
        stream_words=11 * block, budget_bytes=_VMEM_STATE_BUDGET_BYTES)


def sweep_timeline(
    specs: Sequence[TimelineSpec],
    lat: Optional[SystemLatencies] = None,
    *,
    kernel_mode: str = "auto",
    block: int = 512,
) -> List[TimelineResult]:
    """Simulate every spec's timeline in a single pass over the trace axis.

    Specs are padded to a common resource envelope (accelerators, MSHRs,
    partitions, TLB ports, DRAM banks, trace length), their queueing states
    stacked on a leading sim axis, and all sims advanced per trace element
    through one vmapped ``lax.scan`` (or the batched Pallas kernel, chunked
    over the sim axis to the VMEM scratch budget).  Padding is poisoned so it
    is unobservable: trailing trace padding is zero-latency cache hits from
    accelerator 0 (reads state, completes locally, outputs dropped) and
    padded resource slots are never selected (see
    ``repro.kernels.timeline.ref``).  Per-spec results are **bit-identical**
    to :func:`simulate_timeline`, which stays the reference path
    (tests/test_timeline_sweep.py asserts equivalence).
    """
    if not specs:
        raise ValueError("sweep_timeline needs at least one spec")
    prepared = []
    for sp in specs:
        sp_lat = sp.lat if sp.lat is not None else lat
        if sp_lat is None:
            raise ValueError(
                "sweep_timeline: spec has lat=None and no sweep-level lat given")
        prepared.append(_timeline_inputs(
            sp.lines, sp.events, sp.design, sp_lat, sp.cfg, sp.num_partitions,
            sp.page_shift, sp.num_accelerators, sp.accel_ids, sp.workload,
            sp.way_accuracy))

    lens = [int(p[0][0].shape[0]) for p in prepared]
    n_max = max(lens)
    packed = [pack_params(params) for _, params in prepared]
    fparams = np.stack([fp for fp, _ in packed])
    iparams = np.stack([ip for _, ip in packed])

    # Trace-length padding: trailing zero-latency cache hits from accel 0
    # (exactly the Pallas block-padding discipline; outputs are dropped).
    cols = []
    for (inputs, _), n in zip(prepared, lens):
        row = [np.concatenate([x, np.full(n_max - n, v, dtype=x.dtype)])
               if n < n_max else x
               for x, v in zip(inputs, _PAD_VALS)]
        cols.append(row)
    stacked = [np.stack([row[k] for row in cols]) for k in range(8)]

    mode = resolve_timeline_mode(kernel_mode, batch=len(specs))
    if mode == "reference":
        chunks = [list(range(len(specs)))]
    else:
        dims = [tuple(max(int(x), 1) for x in ip[2:7]) for ip in iparams]
        chunks = _timeline_vmem_chunks(dims, block=min(block, max(n_max, 1)))

    lat_b = np.empty((len(specs), n_max), np.float32)
    ov_b = np.empty((len(specs), n_max), np.float32)
    done_b = np.empty((len(specs), n_max), np.float32)
    for chunk in chunks:
        out = timeline_sim_batched(
            *(jnp.asarray(s[chunk]) for s in stacked),
            fparams[chunk], iparams[chunk],
            block=block, kernel_mode=mode)
        lat_b[chunk], ov_b[chunk], done_b[chunk] = (np.asarray(o) for o in out)

    return [
        TimelineResult(
            latency=lat_b[i, :n], overhead=ov_b[i, :n], done=done_b[i, :n],
            cache_hit=sp.events.cache_hit.astype(bool),
            n_warm=sp.events.n_warm,
        )
        for i, (sp, n) in enumerate(zip(specs, lens))
    ]


# Trailing trace padding shared by sweep_timeline and TimelineSweepStream:
# zero-latency cache hits from accelerator 0 (read state, complete locally,
# outputs dropped).
_PAD_VALS = (0, 0, 0, 0, 1, 1, 1, np.float32(0.0))


class TimelineSweepStream:
    """Resumable chunked execution of :func:`sweep_timeline`.

    The stream prepares the stacked per-access columns of every spec once
    (identically to :func:`sweep_timeline`, including the trailing per-spec
    length padding) and owns the carried queueing state; each
    :meth:`run_chunk` call advances every sim through one slice
    ``[lo, hi)`` of the stacked trace axis.  Feeding the slices in order is
    **bit-identical** to one monolithic :func:`sweep_timeline` call in any
    backend and across backend changes at chunk boundaries: the sim grouping
    (:func:`_timeline_vmem_chunks`) is mode-independent and all backends
    share one state layout and step function.

    Unlike the LRU streams, a timeline chunk can NOT be padded mid-stream
    (padding perturbs accelerator 0's issue clock), so every chunk except
    the final one must be a multiple of ``block`` (or at most ``block``
    long); the final chunk is tail-padded exactly like the monolithic op.
    """

    engine = "sweep_timeline"

    def __init__(self, specs: Sequence[TimelineSpec],
                 lat: Optional[SystemLatencies] = None, *, block: int = 512):
        if not specs:
            raise ValueError("TimelineSweepStream needs at least one spec")
        self.specs = tuple(specs)
        self.block = int(block)
        prepared = []
        for sp in self.specs:
            sp_lat = sp.lat if sp.lat is not None else lat
            if sp_lat is None:
                raise ValueError(
                    "TimelineSweepStream: spec has lat=None and no "
                    "stream-level lat given")
            prepared.append(_timeline_inputs(
                sp.lines, sp.events, sp.design, sp_lat, sp.cfg,
                sp.num_partitions, sp.page_shift, sp.num_accelerators,
                sp.accel_ids, sp.workload, sp.way_accuracy))
        self.lens = [int(p[0][0].shape[0]) for p in prepared]
        self.n = max(self.lens)
        packed = [pack_params(params) for _, params in prepared]
        self.fparams = np.stack([fp for fp, _ in packed])
        self.iparams = np.stack([ip for _, ip in packed])
        cols = []
        for (inputs, _), n in zip(prepared, self.lens):
            cols.append([
                np.concatenate([x, np.full(self.n - n, v, dtype=x.dtype)])
                if n < self.n else x
                for x, v in zip(inputs, _PAD_VALS)])
        self._stacked = [np.stack([row[k] for row in cols]) for k in range(8)]

        dims = [tuple(max(int(x), 1) for x in ip[2:7]) for ip in self.iparams]
        self.groups = _timeline_vmem_chunks(
            dims, block=min(self.block, max(self.n, 1)))
        self._envelopes = []
        self._state = []
        for g in self.groups:
            env = tuple(int(self.iparams[g, c].max()) if int(
                self.iparams[g, c].max()) > 0 else 1 for c in (2, 3, 4, 5, 6))
            self._envelopes.append(env)
            self._state.append(timeline_init_state_batched(
                len(g), env, jnp.asarray(self.iparams[g, 5])))
        self.now = 0
        from repro.core.sweep import _note_envelope
        _note_envelope(self)

    @property
    def batch_size(self) -> int:
        return len(self.specs)

    def fingerprint(self) -> dict:
        return {
            "engine": self.engine,
            "block": self.block,
            "n": self.n,
            "lens": list(self.lens),
            "fparams": [[float(x) for x in row] for row in self.fparams],
            "iparams": [[int(x) for x in row] for row in self.iparams],
        }

    def run_chunk(self, lo: int, hi: int, *, kernel_mode: str = "auto"):
        """Advance every sim through the stacked-trace slice ``[lo, hi)``;
        returns (latency, overhead, done), each f32 [B, hi - lo].  Commit-on-
        success: a failed call leaves the stream unchanged."""
        if lo != self.now:
            raise ValueError(
                f"{self.engine} chunk starts at {lo}, stream is at {self.now}")
        if not lo < hi <= self.n:
            raise ValueError(
                f"{self.engine} chunk [{lo}, {hi}) outside stream [0, {self.n})")
        L = hi - lo
        if hi != self.n and L > self.block and L % self.block:
            raise ValueError(
                f"{self.engine} mid-stream chunk length {L} must be a "
                f"multiple of block {self.block} (or <= block): mid-stream "
                f"padding would perturb accelerator 0's issue clock")
        cols = [s[:, lo:hi] for s in self._stacked]
        pad = (-L) % min(self.block, L) if hi == self.n else 0
        if pad:
            # Final-chunk tail padding — the monolithic op's own discipline;
            # padded outputs dropped, and no further chunk reads the state.
            cols = [np.concatenate(
                [x, np.full((x.shape[0], pad), v, dtype=x.dtype)], axis=1)
                for x, v in zip(cols, _PAD_VALS)]
        outs = [np.empty((len(self.specs), L), np.float32) for _ in range(3)]
        new_state = []
        for gi, g in enumerate(self.groups):
            ys, st = timeline_sim_batched_carry(
                *(jnp.asarray(c[g]) for c in cols),
                self.fparams[g], self.iparams[g], self._state[gi],
                block=self.block, kernel_mode=kernel_mode)
            for o, y in zip(outs, ys):
                o[g] = np.asarray(y)[:, :L]   # forces compute (commit gate)
            new_state.append(st)
        self._state = new_state
        self.now = hi
        from repro.core.sweep import _count_sim_accesses
        _count_sim_accesses(self, L)
        return tuple(outs)

    def export_state(self) -> dict:
        out = {"now": np.array([self.now], np.int64)}
        names = ("acc_next", "mshr_ring", "mshr_cnt", "port_free", "bank_free")
        for gi, st in enumerate(self._state):
            for name, arr in zip(names, st):
                out[f"g{gi}_{name}"] = np.asarray(arr)
        return out

    def import_state(self, arrays: dict) -> None:
        names = ("acc_next", "mshr_ring", "mshr_cnt", "port_free", "bank_free")
        state = []
        for gi in range(len(self.groups)):
            st = []
            for j, name in enumerate(names):
                key = f"g{gi}_{name}"
                if key not in arrays:
                    raise ValueError(f"{self.engine} state missing array {key!r}")
                arr = np.asarray(arrays[key])
                ref = np.asarray(self._state[gi][j])
                if tuple(arr.shape) != tuple(ref.shape):
                    raise ValueError(
                        f"{self.engine} state array {key!r} has shape "
                        f"{tuple(arr.shape)}, expected {tuple(ref.shape)}")
                st.append(jnp.asarray(arr.astype(ref.dtype)))
            state.append(tuple(st))
        self._state = state
        self.now = int(np.asarray(arrays["now"]).reshape(-1)[0])

    def finalize(self, latency: np.ndarray, overhead: np.ndarray,
                 done: np.ndarray) -> List[TimelineResult]:
        """Assemble per-spec results from the accumulated [B, n] output
        buffers (each spec sliced back to its own unpadded length)."""
        return [
            TimelineResult(
                latency=latency[i, :n], overhead=overhead[i, :n],
                done=done[i, :n],
                cache_hit=sp.events.cache_hit.astype(bool),
                n_warm=sp.events.n_warm,
            )
            for i, (sp, n) in enumerate(zip(self.specs, self.lens))
        ]
