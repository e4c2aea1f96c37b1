"""Shared benchmark plumbing: trace cache, CSV output, claim checks, and the
per-run telemetry scope (JSONL run logs + the ``_telemetry`` figure stamp)."""
from __future__ import annotations

import functools
import json
import os
import pathlib
import time
import uuid
from typing import Dict, List, Optional

import numpy as np

from repro.core import traces
from repro.runtime import telemetry

CACHE = pathlib.Path(__file__).resolve().parent / "_cache"
FIGS = CACHE / "figs"
RUNLOGS = CACHE / "runlogs"
GIB = 1 << 30

_TRACE_CACHE: Dict = {}

# Paper's four index workloads (Table 2) + server workload.
W4 = ("bst_external", "bst_internal", "hash_table", "skip_list")


def trace(workload: str, *, n_ops: int = 40_000, seed: int = 0,
          footprint_bytes: int = 128 * GIB, max_accesses: int = 1_400_000):
    key = (workload, n_ops, seed, footprint_bytes, max_accesses)
    if key not in _TRACE_CACHE:
        _TRACE_CACHE[key] = traces.generate(
            workload, n_ops=n_ops, seed=seed,
            footprint_bytes=footprint_bytes, max_accesses=max_accesses,
        )
    return _TRACE_CACHE[key]


class Claim:
    """A checked reproduction claim (paper §7), printed and persisted."""

    def __init__(self, name: str, desc: str, value: float, band: tuple, unit: str = ""):
        self.name, self.desc, self.value, self.band, self.unit = name, desc, value, band, unit
        self.ok = band[0] <= value <= band[1]

    def row(self) -> dict:
        return {
            "claim": self.name, "description": self.desc,
            "value": self.value, "band": list(self.band),
            "unit": self.unit, "ok": self.ok,
        }

    def __str__(self):
        mark = "PASS" if self.ok else "MISS"
        return (f"[{mark}] {self.name}: {self.value:.3g}{self.unit} "
                f"(band {self.band[0]:.3g}..{self.band[1]:.3g}) — {self.desc}")


def run_config(fig: str, *, resume: bool = False, chunk_accesses=None):
    """The :class:`repro.core.orchestrator.SweepRunConfig` of one figure
    driver: checkpoints live under ``_cache/ckpt/<fig>/`` (one blob per
    engine call), ``resume`` re-enters them, ``chunk_accesses`` overrides
    the commit granularity (the CI fault-injection smoke shrinks it so a
    quick run still crosses several chunk boundaries)."""
    from repro.core.orchestrator import SweepRunConfig

    kw = {"checkpoint_dir": str(CACHE / "ckpt" / fig), "resume": bool(resume)}
    if chunk_accesses:
        kw["chunk_accesses"] = int(chunk_accesses)
    return SweepRunConfig(**kw)


def sched_config(*, workers: int = 1, shards: int = 0,
                 deadline: Optional[float] = None, executor: str = "auto"):
    """Build the driver-facing :class:`repro.core.scheduler.ScheduleConfig`
    — or ``None`` (pure unsharded passthrough) when nothing asks for
    scheduling.  Worker run logs land next to the figure's own
    (``_cache/runlogs/``); ``REPRO_SCHED_HOLD_S`` is the CI smoke's seam for
    holding each shard's first attempt open long enough to SIGKILL a worker
    mid-shard."""
    from repro.core.scheduler import ScheduleConfig

    sched = ScheduleConfig(
        workers=int(workers), shards=int(shards), deadline_s=deadline,
        executor=executor,
        lease_ttl_s=float(os.environ.get("REPRO_SCHED_LEASE_TTL_S", 5.0)),
        heartbeat_s=float(os.environ.get("REPRO_SCHED_HEARTBEAT_S", 1.0)),
        hold_s=float(os.environ.get("REPRO_SCHED_HOLD_S", 0.0) or 0.0),
        runlog_dir=str(RUNLOGS))
    return sched if sched.enabled else None


# Figures whose last run completed degraded (quarantined shards): the run.py
# driver loop and standalone figure mains exit with scheduler.EX_DEGRADED
# when this is non-empty.
_DEGRADED_RUNS: List[str] = []


def degraded_runs() -> List[str]:
    return list(_DEGRADED_RUNS)


def crash_safety(metas: Dict[str, dict]) -> dict:
    """Figure-JSON stamp of how each orchestrated engine call executed:
    backend ladder start/end, every retry/halve/downgrade event, where a
    resumed run re-entered — and, for scheduled (sharded) calls, the shard
    map and the quarantined-shard manifest.  Underscore-prefixed in payloads
    (like ``_written_at`` / ``_device``) so resume-identity comparisons drop
    it."""
    out = {}
    quarantined = {}
    for name, m in metas.items():
        rec = {
            "start_mode": m["start_mode"], "final_mode": m["final_mode"],
            "resumable": m["resumable"], "resumed_from": m["resumed_from"],
            "completed_from_checkpoint": m["completed_from_checkpoint"],
            "events": m["events"],
        }
        s = m.get("scheduler")
        if s:
            rec["scheduler"] = {
                "shards": s["shards"], "workers": s["workers"],
                "executor": s["executor"], "shard_map": s["shard_map"],
                "events": [e["event"] for e in s["events"]],
            }
            if s.get("quarantined_shards"):
                quarantined[name] = s["quarantined_shards"]
        out[name] = rec
    out["quarantined_shards"] = quarantined
    if quarantined:
        run = telemetry.get_tracer().run or "?"
        if run not in _DEGRADED_RUNS:
            _DEGRADED_RUNS.append(run)
    return out


def with_runlog(fig: str):
    """Decorator bracketing a figure/bench driver's ``run()`` in a telemetry
    run scope: every orchestrated engine call, chunk span, ladder event and
    measured row of the run lands in ``_cache/runlogs/<fig>.jsonl`` (one
    file per driver, overwritten per run — the stable paths CI uploads and
    ``benchmarks/obs_report.py`` renders)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            from repro.core import benchtime

            with telemetry.run_scope(RUNLOGS / f"{fig}.jsonl", run=fig,
                                     device=benchtime.device_metadata()):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def telemetry_stamp(metas: Dict[str, dict] = None) -> dict:
    """Figure-JSON ``_telemetry`` stamp: the tracer's run summary (total
    spans, event counts, counters/gauges) plus, per orchestrated engine
    call, the achieved accesses/s of every backend that actually executed.
    ``_crash_safety`` says *what degraded*; this says *what it cost*."""
    stamp = telemetry.get_tracer().summary()
    if metas:
        stamp["engines"] = {
            name: {"engine": m.get("engine"),
                   "final_mode": m.get("final_mode"),
                   "throughput": m.get("throughput", {}),
                   "dispatch": m.get("dispatch")}
            for name, m in metas.items()}
    return stamp


def save_fig(name: str, payload: dict):
    from repro.checkpoint.checkpoint import file_lock
    from repro.core import benchtime

    FIGS.mkdir(parents=True, exist_ok=True)
    payload = dict(payload)
    payload["_written_at"] = time.strftime("%Y-%m-%d %H:%M:%S")
    # Same schema stamp as BENCH_sweep.json rows: figure outputs say what
    # device they were produced on (interpret-mode CPU vs real TPU).
    payload["_device"] = benchtime.device_metadata()
    # Drivers with orchestrated engine calls pass an explicit stamp (with
    # per-engine throughput); anything else written inside a telemetry run
    # gets the plain run summary.
    if "_telemetry" not in payload and telemetry.get_tracer().active:
        payload["_telemetry"] = telemetry_stamp()
    # Lock + write-tmp + atomic replace: concurrent scheduler workers (or
    # two driver invocations) can never interleave into a torn figure JSON.
    path = FIGS / f"{name}.json"
    with file_lock(path.with_name(path.name + ".lock")):
        tmp = path.with_name(f"{path.name}.tmp-{uuid.uuid4().hex[:8]}")
        tmp.write_text(json.dumps(payload, indent=1, default=float))
        os.replace(tmp, path)


def load_fig(name: str):
    p = FIGS / f"{name}.json"
    return json.loads(p.read_text()) if p.exists() else None


def print_csv(title: str, header: List[str], rows: List[list]):
    print(f"\n# {title}")
    print(",".join(header))
    for r in rows:
        print(",".join(f"{x:.4g}" if isinstance(x, float) else str(x) for x in r))
