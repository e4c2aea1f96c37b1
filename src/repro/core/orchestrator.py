"""Crash-safe streaming sweep orchestrator: chunked engines + checkpoint/
resume + a graceful-degradation backend ladder.

Every figure driver funnels its batched engine calls through this module's
three entry points — :func:`run_sweep_tlb`, :func:`run_sweep_system`,
:func:`run_sweep_timeline` — which wrap the resumable stream classes
(:class:`repro.core.sweep.TLBSweepStream`,
:class:`repro.core.sweep.SystemSweepStream`,
:class:`repro.core.timeline.TimelineSweepStream`) in one shared chunk loop:

* **Bounded-memory streaming.**  The trace is consumed in
  ``chunk_accesses``-sized slices; per-config carried state (LRU tags +
  last-use stamps, MSHR/port/bank queues) lives in the stream object and the
  per-chunk working set is bounded regardless of trace length.  Chunked
  results are bit-identical to the monolithic engines (the stream classes'
  contract, asserted by tests/test_orchestrator.py).

* **Checkpoint/resume.**  With ``SweepRunConfig.checkpoint_dir`` set, every
  committed chunk atomically replaces a single checkpoint blob (write-tmp,
  fsync, rename + content checksum — :func:`repro.checkpoint.checkpoint.
  write_checkpoint_blob`) holding the carried state, the partial result
  buffers and a JSON meta record.  On restart with ``resume=True`` the blob
  is validated (checksum + engine/layout fingerprint) and the run re-enters
  at the first uncommitted chunk, bit-identically to an uninterrupted run.
  A corrupt, truncated or layout-mismatched checkpoint is **refused with a
  clear error** (the PR 6 ``_append_bench_entry`` policy: never silently
  regenerate over data you did not write).

* **Graceful degradation.**  Each chunk runs under a ladder: on a transient
  runtime fault (:func:`repro.runtime.fault_tolerance.is_transient` —
  RESOURCE_EXHAUSTED / XLA runtime faults, OOM, ...) the chunk is retried
  with bounded exponential backoff, then split in half (block-aligned), and
  finally the backend is downgraded ``pallas -> pallas_interpret ->
  reference`` (sticky for the rest of the run — and, via the checkpoint,
  across restarts).  ``pallas_interpret`` is a rung only on CPU backends:
  on a TPU the ladder goes ``pallas -> reference``, and a compile or
  lowering error is never transient, so it surfaces instead of descending.
  Every retry/halve/downgrade is recorded in the run's ``meta["events"]``
  so a run that silently fell back is visible in the recorded
  figure/benchmark metadata.  Non-transient errors raise immediately.

* **Preemption.**  A :class:`repro.runtime.fault_tolerance.PreemptionHandler`
  (installed automatically when checkpointing is on) turns SIGTERM/SIGINT
  into a clean checkpoint-and-exit at the next chunk boundary, raising
  :class:`Preempted` (drivers exit with code 75, the sysexits.h "temp
  failure; rerun with --resume" convention).

The TLB sweep's ``"stackdist"`` backend is a global sort over the whole
trace and cannot carry state across chunk boundaries; ``run_sweep_tlb``
runs it monolithically (``meta["resumable"] = False``) and only the
sequential backends stream.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import pathlib
import random
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.checkpoint.checkpoint import (
    CheckpointCorruptError,
    read_checkpoint_blob,
    write_checkpoint_blob,
)
from repro.core import dispatch
from repro.core.sweep import (
    BatchedSystemEvents,
    BatchedTLBResult,
    SystemSweepStream,
    TLBSweepSpec,
    TLBSweepStream,
    sweep_tlb,
)
from repro.core.timeline import TimelineResult, TimelineSpec, TimelineSweepStream
from repro.core.tlbsim import SystemSimConfig
from repro.kernels.common import interpret_allowed, resolve_mode
from repro.runtime import telemetry
from repro.runtime.fault_tolerance import (
    PreemptionHandler,
    backoff_delays,
    is_transient,
)

_LOG = logging.getLogger("repro.core.orchestrator")

# Narration level per ladder event: anything that changes how the run
# executes (fell back, split, degraded, preempted) is a warning; resuming is
# the expected happy path of --resume.
_EVENT_LEVELS = {"retry": logging.WARNING, "halve": logging.WARNING,
                 "downgrade": logging.WARNING, "preempt": logging.WARNING,
                 "resume": logging.INFO}

__all__ = [
    "SweepRunConfig",
    "Preempted",
    "LADDER",
    "CKPT_FORMAT",
    "run_sweep_tlb",
    "run_sweep_system",
    "run_sweep_timeline",
    "merge_throughput",
]

# Degradation ladder, fastest first; a run enters at its resolved mode and
# only ever moves right (see ladder_from for the rungs a backend has).
LADDER = ("pallas", "pallas_interpret", "reference")


def ladder_from(mode: str) -> Tuple[str, ...]:
    """The rungs a run entering at ``mode`` may descend through.  The
    interpreter is a rung only where it may run at all (CPU backends), so a
    TPU run can never fall back onto it."""
    rungs = tuple(m for m in LADDER
                  if m != "pallas_interpret" or interpret_allowed())
    return rungs[rungs.index(mode):]

CKPT_FORMAT = "repro-sweep-ckpt-v1"


class Preempted(BaseException):
    """SIGTERM/SIGINT arrived; state was checkpointed at a chunk boundary.

    Deliberately a BaseException (like KeyboardInterrupt): the retry/ladder
    machinery catches transient ``Exception``s only, so a preemption can
    never be mistaken for a recoverable kernel fault.
    """

    def __init__(self, checkpoint: Optional[pathlib.Path], now: int, total: int):
        self.checkpoint = checkpoint
        self.now, self.total = now, total
        super().__init__(
            f"preempted at chunk boundary {now}/{total}; "
            + (f"state checkpointed to {checkpoint} — rerun with --resume"
               if checkpoint else "no checkpoint_dir, state discarded"))


@dataclasses.dataclass(frozen=True)
class SweepRunConfig:
    """How a streamed sweep executes (checkpointing, chunking, the ladder).

    ``chunk_accesses`` is the macro-chunk: the trace-slice granularity of
    checkpoint commits (rounded up to a whole number of kernel blocks).
    ``fault_hook(engine, lo, hi, mode, attempt)`` is a test seam invoked
    before every chunk attempt — the fault-injection harness raises
    simulated transient faults there; ``on_chunk_committed(chunk_idx)``
    fires after a chunk's checkpoint is durably on disk — the harness
    raises a simulated hard kill there.

    ``calibration_dir`` points ``kernel_mode="auto"`` at a measured-rate
    calibration table directory (:mod:`repro.core.dispatch`) and feeds
    achieved rates back into it after every run.  ``None`` (the default)
    keeps decisions on the deterministic cold-start heuristics —
    calibration is strictly opt-in so test/library behavior never depends
    on what a particular machine has measured.
    """

    checkpoint_dir: Optional[str] = None
    calibration_dir: Optional[str] = None
    resume: bool = False
    chunk_accesses: int = 65_536
    max_retries: int = 2
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 2.0
    keep_checkpoint: bool = False
    preemption: Optional[PreemptionHandler] = None
    fault_hook: Optional[Callable] = None
    on_chunk_committed: Optional[Callable] = None
    rng_seed: Optional[int] = 0   # backoff jitter; None -> wall-clock seeded


def _fingerprint_json(fp: dict) -> str:
    return json.dumps(fp, sort_keys=True)


class _ChunkRunner:
    """The shared chunk loop: ladder + checkpointing around one stream."""

    def __init__(self, stream, total: int, out_names: Sequence[str],
                 out_dtypes: Sequence, run_chunk: Callable,
                 start_mode: str, cfg: SweepRunConfig, *, name: str,
                 trace_sha: str,
                 decision: Optional[dispatch.DispatchDecision] = None):
        self.stream = stream
        self.decision = decision
        self.total = int(total)
        self.out_names = tuple(out_names)
        self.run_chunk = run_chunk     # (lo, hi, mode) -> tuple of [B, L]
        self.cfg = cfg
        self.name = name
        B = len(stream.specs) if hasattr(stream, "specs") else len(stream.cfgs)
        self.batch = B
        self.bufs = [np.zeros((B, self.total), dt) for dt in out_dtypes]
        # mode -> {chunks, accesses, sim_accesses, elapsed_s}: achieved
        # throughput per backend actually executed (meta()["throughput"],
        # thence the figure-JSON _telemetry stamp) — recorded even with the
        # tracer disabled, it is plain accumulation.
        self.throughput: dict = {}
        start_mode = resolve_mode(start_mode)  # never "auto" past this point
        self.ladder = ladder_from(start_mode)
        self.rung = 0
        self.events: List[dict] = []
        self.chunks_committed = 0
        self.resumed_from: Optional[int] = None
        self._rng = random.Random(cfg.rng_seed)
        fp = dict(stream.fingerprint())
        fp["trace_sha256"] = trace_sha
        fp["total"] = self.total
        self._fp = _fingerprint_json(fp)
        self.path = (pathlib.Path(cfg.checkpoint_dir) / f"{name}.ckpt"
                     if cfg.checkpoint_dir else None)

    # -- checkpointing ------------------------------------------------------

    def _meta(self, completed: bool, *,
              chunks_committed: Optional[int] = None) -> dict:
        return {
            "format": CKPT_FORMAT,
            "engine": self.stream.engine,
            "name": self.name,
            "fingerprint": self._fp,
            "now": int(self.stream.now),
            "total": self.total,
            "completed": completed,
            "mode": self.ladder[self.rung],
            "events": self.events,
            "chunks_committed": (self.chunks_committed if chunks_committed
                                 is None else chunks_committed),
            "dispatch": (self.decision.to_json() if self.decision is not None
                         else None),
        }

    def _write_checkpoint(self, completed: bool, *,
                          chunks_committed: Optional[int] = None) -> None:
        if self.path is None:
            return
        self.path.parent.mkdir(parents=True, exist_ok=True)
        arrays = {f"s_{k}": v for k, v in self.stream.export_state().items()}
        now = int(self.stream.now)
        for nm, buf in zip(self.out_names, self.bufs):
            arrays[f"r_{nm}"] = buf[:, :now]
        write_checkpoint_blob(
            self.path, arrays,
            self._meta(completed, chunks_committed=chunks_committed))

    def try_resume(self) -> Optional[dict]:
        """Load the checkpoint if resuming.  Returns the blob meta when the
        checkpointed run had already completed (results restored), else
        None; raises :class:`CheckpointCorruptError` on a corrupt or
        mismatched blob."""
        if not (self.cfg.resume and self.path is not None and self.path.exists()):
            return None
        arrays, meta = read_checkpoint_blob(self.path)
        if meta.get("format") != CKPT_FORMAT or meta.get("engine") != self.stream.engine:
            raise CheckpointCorruptError(
                f"checkpoint {self.path} was written by "
                f"{meta.get('engine')!r}/{meta.get('format')!r}, not "
                f"{self.stream.engine!r}/{CKPT_FORMAT!r}; refusing to resume "
                f"from it — delete it deliberately (or start without "
                f"--resume) to begin a fresh run")
        if meta.get("fingerprint") != self._fp:
            raise CheckpointCorruptError(
                f"checkpoint {self.path} was taken on a different sweep "
                f"layout or trace (fingerprint mismatch); refusing to resume "
                f"from it — delete it deliberately (or start without "
                f"--resume) to begin a fresh run")
        self.stream.import_state(
            {k[2:]: v for k, v in arrays.items() if k.startswith("s_")})
        now = int(self.stream.now)
        for nm, buf in zip(self.out_names, self.bufs):
            buf[:, :now] = arrays[f"r_{nm}"]
        self.events = list(meta.get("events", []))
        # Resume-stickiness: the checkpointed run's DispatchDecision wins
        # over whatever this process just decided — a calibration table that
        # changed between runs must never flip the backend mid-stream (the
        # resumed tail has to be bit-identical to the uninterrupted run).
        dd = meta.get("dispatch")
        if dd:
            blob_dec = dispatch.DispatchDecision.from_json(dd)
            if blob_dec.mode in LADDER:
                self.ladder = ladder_from(blob_dec.mode)
                self.rung = 0
            self.decision = dataclasses.replace(
                blob_dec, reason=blob_dec.reason + " (reused from checkpoint)",
                calibration=f"checkpoint:{blob_dec.calibration}")
        mode = meta.get("mode")
        if mode in self.ladder:   # sticky downgrade survives the restart
            self.rung = self.ladder.index(mode)
        self.chunks_committed = int(meta.get("chunks_committed", 0))
        self.resumed_from = now
        self._log("resume", now, self.total,
                  chunks_committed=self.chunks_committed,
                  completed=bool(meta.get("completed")))
        return meta if meta.get("completed") else None

    # -- the ladder ---------------------------------------------------------

    def _commit(self, lo: int, hi: int, outs) -> None:
        for buf, out in zip(self.bufs, outs):
            buf[:, lo:hi] = out
        # The blob (written with the incremented count) is the commit point:
        # the in-memory counter moves only once the write has succeeded, so
        # meta/events never claim one more durable chunk than disk holds.
        t0 = time.perf_counter()
        self._write_checkpoint(completed=False,
                               chunks_committed=self.chunks_committed + 1)
        self.chunks_committed += 1
        if self.path is not None:
            telemetry.get_tracer().event(
                "checkpoint_write", engine=self.stream.engine, name=self.name,
                chunk=self.chunks_committed,
                dur_s=round(time.perf_counter() - t0, 6))
        if self.cfg.on_chunk_committed is not None:
            self.cfg.on_chunk_committed(self.chunks_committed - 1)
        pre = self.cfg.preemption
        if pre is not None and pre.requested:
            self._log("preempt", int(self.stream.now), self.total,
                      chunks_committed=self.chunks_committed)
            raise Preempted(self.path, int(self.stream.now), self.total)

    def _log(self, event: str, lo: int, hi: int, **kw) -> None:
        """Record one ladder event into meta["events"], the telemetry run
        log, and the narration logger.  Every event carries a wall-clock
        (``ts``) and monotonic (``t_mono``) stamp so a degraded run can be
        reconstructed post-hoc."""
        rec = {"event": event, "lo": int(lo), "hi": int(hi),
               "mode": self.ladder[self.rung],
               "ts": time.time(), "t_mono": time.perf_counter(), **kw}
        self.events.append(rec)
        telemetry.get_tracer().event(
            event, engine=self.stream.engine, name=self.name,
            **{k: v for k, v in rec.items()
               if k not in ("event", "ts", "t_mono")})
        _LOG.log(_EVENT_LEVELS.get(event, logging.INFO),
                 "%s[%s] %s [%d, %d) mode=%s%s",
                 self.stream.engine, self.name, event, rec["lo"], rec["hi"],
                 rec["mode"],
                 "".join(f" {k}={v}" for k, v in kw.items()))

    def _note_chunk(self, lo: int, hi: int, mode: str, attempt: int,
                    dur_s: float) -> None:
        """Account a successful chunk attempt: per-mode throughput (always)
        plus a telemetry chunk span (when a run is active)."""
        n = int(hi - lo)
        agg = self.throughput.setdefault(
            mode, {"chunks": 0, "accesses": 0, "sim_accesses": 0,
                   "elapsed_s": 0.0})
        agg["chunks"] += 1
        agg["accesses"] += n
        agg["sim_accesses"] += n * self.batch
        agg["elapsed_s"] += dur_s
        telemetry.get_tracer().record_span(
            "chunk", dur_s, engine=self.stream.engine, name=self.name,
            lo=int(lo), hi=int(hi), mode=mode, attempt=attempt,
            accesses=n, configs=self.batch,
            accesses_per_s=round(n / dur_s, 1) if dur_s > 0 else None,
            sim_accesses_per_s=(round(n * self.batch / dur_s, 1)
                                if dur_s > 0 else None))

    def _exec(self, lo: int, hi: int) -> None:
        """Run span [lo, hi) through retries -> halving -> downgrade."""
        delays = backoff_delays(
            self.cfg.max_retries, base_s=self.cfg.backoff_base_s,
            cap_s=self.cfg.backoff_cap_s, rng=self._rng)
        last_exc: Optional[Exception] = None
        for attempt in range(self.cfg.max_retries + 1):
            mode = self.ladder[self.rung]
            # Only the chunk attempt itself may be retried.  _commit stays
            # OUTSIDE the try: once run_chunk has returned, the stream has
            # already advanced past `lo`, so re-entering this loop after a
            # checkpoint-write failure would re-apply the chunk to the
            # advanced state (double-applied hits, drifted `now`) and then
            # checkpoint the corrupted prefix as good.  A failed commit must
            # propagate, leaving the previous blob as the resume point.
            t0 = time.perf_counter()
            try:
                if self.cfg.fault_hook is not None:
                    self.cfg.fault_hook(self.stream.engine, lo, hi, mode, attempt)
                outs = self.run_chunk(lo, hi, mode)
            except Exception as exc:
                if not is_transient(exc):
                    raise
                last_exc = exc
                self._log("retry", lo, hi, attempt=attempt,
                          elapsed_s=round(time.perf_counter() - t0, 6),
                          error=f"{type(exc).__name__}: {exc}")
                if attempt < self.cfg.max_retries:
                    time.sleep(delays[attempt])
                continue
            self._note_chunk(lo, hi, mode, attempt, time.perf_counter() - t0)
            self._commit(lo, hi, outs)
            return
        # Retries exhausted.  Halve if the span spans more than one block,
        # else (or eventually) take the next rung down the ladder.
        block = self.stream.block
        if hi - lo > block:
            half = ((hi - lo) // 2 // block) * block
            mid = lo + max(half, block)
            self._log("halve", lo, hi, mid=int(mid))
            self._exec(lo, mid)
            self._exec(mid, hi)
            return
        if self.rung + 1 < len(self.ladder):
            self._log("downgrade", lo, hi,
                      to_mode=self.ladder[self.rung + 1],
                      error=f"{type(last_exc).__name__}: {last_exc}")
            self.rung += 1   # sticky for the rest of the run
            self._exec(lo, hi)
            return
        raise last_exc

    # -- the loop -----------------------------------------------------------

    def run(self) -> dict:
        block = self.stream.block
        chunk = max(int(self.cfg.chunk_accesses), 1)
        chunk += (-chunk) % block   # whole kernel blocks per macro-chunk
        while self.stream.now < self.total:
            lo = int(self.stream.now)
            self._exec(lo, min(lo + chunk, self.total))
        if self.path is not None and not self.cfg.keep_checkpoint \
                and not self.cfg.resume:
            # A fresh (non-resume) run that finished cleanly leaves no blob
            # behind unless asked to — the completed blob would be deleted
            # straight away, so don't serialize the full result prefix only
            # to unlink it; just drop the last chunk blob.
            try:
                os.remove(self.path)
            except OSError:
                pass
        else:
            # keep_checkpoint or --resume: the completed blob stays so an
            # identical rerun is a pure checkpoint read.
            self._write_checkpoint(completed=True)
        return self.meta()

    def meta(self, *, completed_from_checkpoint: bool = False) -> dict:
        return {
            "engine": self.stream.engine,
            "resumable": True,
            "start_mode": self.ladder[0],
            "final_mode": self.ladder[self.rung],
            "events": self.events,
            "chunks_committed": self.chunks_committed,
            "resumed_from": self.resumed_from,
            "completed_from_checkpoint": completed_from_checkpoint,
            "checkpoint": str(self.path) if self.path else None,
            "throughput": _throughput_meta(self.throughput),
            "dispatch": (self.decision.to_json() if self.decision is not None
                         else None),
        }


def _throughput_meta(agg_by_mode: dict) -> dict:
    """Finish the per-mode accumulators into achieved accesses/s (trace
    accesses and simulated config x access pairs per second of engine
    wall time)."""
    out = {}
    for mode, a in agg_by_mode.items():
        dt = a["elapsed_s"]
        out[mode] = {
            "chunks": a["chunks"], "accesses": a["accesses"],
            "sim_accesses": a["sim_accesses"],
            "elapsed_s": round(dt, 6),
            "accesses_per_s": round(a["accesses"] / dt, 1) if dt > 0 else None,
            "sim_accesses_per_s": (round(a["sim_accesses"] / dt, 1)
                                   if dt > 0 else None),
        }
    return out


def merge_throughput(metas: Sequence[dict]) -> dict:
    """Merge the ``meta["throughput"]`` stamps of several runs (the shard
    scheduler's per-shard orchestrator runs) into one per-mode aggregate
    with recomputed achieved rates."""
    agg: dict = {}
    for m in metas:
        for mode, d in (m.get("throughput") or {}).items():
            a = agg.setdefault(mode, {"chunks": 0, "accesses": 0,
                                      "sim_accesses": 0, "elapsed_s": 0.0})
            a["chunks"] += d["chunks"]
            a["accesses"] += d["accesses"]
            a["sim_accesses"] += d["sim_accesses"]
            a["elapsed_s"] += d["elapsed_s"]
    return _throughput_meta(agg)


def _sha256_arrays(*arrays: np.ndarray) -> str:
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _maybe_handler(cfg: SweepRunConfig) -> Tuple[SweepRunConfig, Optional[PreemptionHandler]]:
    """Install a PreemptionHandler for the duration of a checkpointing run
    when the caller did not supply one."""
    if cfg.checkpoint_dir is None or cfg.preemption is not None:
        return cfg, None
    handler = PreemptionHandler()
    return dataclasses.replace(cfg, preemption=handler), handler


def run_sweep_tlb(
    addrs: np.ndarray,
    specs: Sequence[TLBSweepSpec],
    *,
    warmup_frac: float = 0.25,
    kernel_mode: str = "auto",
    block: int = 512,
    run: SweepRunConfig = SweepRunConfig(),
    name: str = "sweep_tlb",
) -> Tuple[BatchedTLBResult, dict]:
    """Crash-safe :func:`repro.core.sweep.sweep_tlb`.

    Returns ``(BatchedTLBResult, meta)`` — the result is bit-identical to
    the monolithic engine.  ``"stackdist"`` (and ``"auto"`` resolving to it)
    runs monolithically: the sort-based engine needs the whole trace, so it
    is not resumable (``meta["resumable"] = False``).
    """
    addrs = np.asarray(addrs)
    store = dispatch.store_for(run.calibration_dir)
    decision = dispatch.decide_tlb(
        kernel_mode, specs, n_accesses=int(addrs.shape[0]), store=store)
    dispatch.record_decision(decision, name=name)
    mode = decision.mode
    if mode == "stackdist":
        # Monolithic, but still measured: the stackdist engine's achieved
        # accesses/s lands in meta["throughput"] (and a single whole-trace
        # "chunk" span in the run log) just like the streamed backends'.
        n = int(addrs.shape[0])
        t0 = time.perf_counter()
        res = sweep_tlb(addrs, specs, warmup_frac=warmup_frac,
                        kernel_mode=mode, block=block)
        dur = time.perf_counter() - t0
        telemetry.get_tracer().record_span(
            "chunk", dur, engine="sweep_tlb", name=name, lo=0, hi=n,
            mode=mode, attempt=0, accesses=n, configs=len(specs),
            accesses_per_s=round(n / dur, 1) if dur > 0 else None,
            sim_accesses_per_s=(round(n * len(specs) / dur, 1)
                                if dur > 0 else None))
        agg = {mode: {"chunks": 1, "accesses": n,
                      "sim_accesses": n * len(specs), "elapsed_s": dur}}
        throughput = _throughput_meta(agg)
        dispatch.observe(decision, throughput, store=store, name=name)
        return res, {"engine": "sweep_tlb", "resumable": False,
                     "start_mode": mode, "final_mode": mode, "events": [],
                     "chunks_committed": 0, "resumed_from": None,
                     "completed_from_checkpoint": False, "checkpoint": None,
                     "throughput": throughput,
                     "dispatch": decision.to_json()}

    run, handler = _maybe_handler(run)
    try:
        stream = TLBSweepStream(specs, block=block)
        n = int(addrs.shape[0])
        runner = _ChunkRunner(
            stream, n, ("hits",), (bool,),
            lambda lo, hi, m: (stream.run_chunk(addrs[lo:hi], kernel_mode=m),),
            mode, run, name=name, trace_sha=_sha256_arrays(addrs),
            decision=decision)
        done = runner.try_resume()
        meta = runner.meta(completed_from_checkpoint=True) if done else runner.run()
        dispatch.observe(runner.decision, meta.get("throughput") or {},
                         store=store, name=name)
        n0 = int(n * warmup_frac)
        return BatchedTLBResult(hits=runner.bufs[0], n_warm=n - n0), meta
    finally:
        if handler is not None:
            handler.uninstall()


def run_sweep_system(
    lines: np.ndarray,
    cfgs: Sequence[SystemSimConfig],
    *,
    warmup_frac: float = 0.25,
    kernel_mode: str = "auto",
    block: int = 512,
    run: SweepRunConfig = SweepRunConfig(),
    name: str = "sweep_system",
) -> Tuple[BatchedSystemEvents, dict]:
    """Crash-safe :func:`repro.core.sweep.sweep_system`; returns
    ``(BatchedSystemEvents, meta)``, bit-identical to the monolithic
    engine."""
    lines = np.asarray(lines)
    store = dispatch.store_for(run.calibration_dir)
    decision = dispatch.decide_system(
        kernel_mode, cfgs, n_accesses=int(lines.shape[0]), store=store)
    dispatch.record_decision(decision, name=name)
    run, handler = _maybe_handler(run)
    try:
        stream = SystemSweepStream(cfgs, block=block)
        n = int(lines.shape[0])
        runner = _ChunkRunner(
            stream, n, ("cache_hit", "accel_tlb_hit", "mem_tlb_hit"),
            (bool, bool, bool),
            lambda lo, hi, m: stream.run_chunk(lines[lo:hi], kernel_mode=m),
            decision.mode, run, name=name, trace_sha=_sha256_arrays(lines),
            decision=decision)
        done = runner.try_resume()
        meta = runner.meta(completed_from_checkpoint=True) if done else runner.run()
        dispatch.observe(runner.decision, meta.get("throughput") or {},
                         store=store, name=name)
        n0 = int(n * warmup_frac)
        return BatchedSystemEvents(*runner.bufs, n_warm=n - n0), meta
    finally:
        if handler is not None:
            handler.uninstall()


def run_sweep_timeline(
    specs: Sequence[TimelineSpec],
    lat=None,
    *,
    kernel_mode: str = "auto",
    block: int = 512,
    run: SweepRunConfig = SweepRunConfig(),
    name: str = "sweep_timeline",
) -> Tuple[List[TimelineResult], dict]:
    """Crash-safe :func:`repro.core.timeline.sweep_timeline`; returns
    ``(results, meta)``, bit-identical to the monolithic engine."""
    store = dispatch.store_for(run.calibration_dir)
    n_acc = max((int(np.asarray(sp.lines).shape[0]) for sp in specs),
                default=0) if specs else None
    decision = dispatch.decide_timeline(
        kernel_mode, batch=len(specs), n_accesses=n_acc, store=store)
    dispatch.record_decision(decision, name=name)
    run, handler = _maybe_handler(run)
    try:
        stream = TimelineSweepStream(specs, lat, block=block)
        runner = _ChunkRunner(
            stream, stream.n, ("latency", "overhead", "done"),
            (np.float32, np.float32, np.float32),
            lambda lo, hi, m: stream.run_chunk(lo, hi, kernel_mode=m),
            decision.mode, run, name=name,
            trace_sha=_sha256_arrays(*stream._stacked),
            decision=decision)
        done = runner.try_resume()
        meta = runner.meta(completed_from_checkpoint=True) if done else runner.run()
        dispatch.observe(runner.decision, meta.get("throughput") or {},
                         store=store, name=name)
        return stream.finalize(*runner.bufs), meta
    finally:
        if handler is not None:
            handler.uninstall()
