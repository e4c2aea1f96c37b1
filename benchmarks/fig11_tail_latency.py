"""Fig 11 (beyond-paper): translation-induced tail latency under contention.

The paper's figures stop at averages; this benchmark uses the
cycle-approximate timeline engine (:mod:`repro.core.timeline`) to put 1-16
accelerators on the shared memory-side structures and measure the p50/p95/p99
of the *translation-induced* per-access latency (queue waits included) for
conventional vs SPARTA-32, with bounded MSHRs, one service port per
partition TLB and banked DRAM (EXPERIMENTS.md logs the queueing assumptions).

Batched execution: each workload's reference stream is the interleave of
``A_MAX`` thread traces, generated ONCE; every accelerator count replays the
*same* stream with a different round-robin issuer assignment, so one
``sweep_system`` call per workload feeds every cell in its accel loop (no
per-cell event re-derivation) and the full (workload x accel-count x design)
matrix — 40 cells at defaults — runs as ONE ``sweep_timeline`` pass.  Paying
the scan overhead once is what lets the default trace cap sit at 150k
accesses (2.5x the looped engine's 60k).

``kernel_mode`` is passed through unmodified; sweep-only modes such as
``"stackdist"`` raise a ValueError naming the valid timeline backends
instead of being silently coerced.

Claims (C9): at 16 accelerators SPARTA's p99 translation-induced latency is
below conventional's for every workload (the serialized page walk queues on
the same DRAM banks as the data stream, while SPARTA's probes spread over
P partition ports and its PTE walks stay local).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (Claim, W4, crash_safety, print_csv, run_config,
                               save_fig, telemetry_stamp, with_runlog)
from repro.core import timeline, traces
from repro.core.scheduler import run_sweep_system, run_sweep_timeline
from repro.core.sparta import SystemLatencies, TLBConfig
from repro.core.tlbsim import SystemSimConfig

CACHE = TLBConfig(entries=256, ways=4)      # 16 KB virtual cache
ACCEL_TLB = TLBConfig(entries=128, ways=4)  # conventional accel-side TLB
MEM_TLB = TLBConfig(entries=128, ways=4)    # per-partition memory-side TLB
PARTITIONS = 32
QUEUES = timeline.TimelineConfig(mshrs=8, tlb_ports=1, dram_banks=16)


LAT = SystemLatencies(n_sockets=8)


def accel_counts(quick: bool = False):
    return (1, 4, 16) if quick else (1, 2, 4, 8, 16)


def matrix(quick: bool = False, kernel_mode: str = "auto", *, run,
           sched=None, seed: int = 7):
    """The figure's (design x workload x accel-count) timeline specs.

    One trace + one system sweep per workload (through the crash-safe
    orchestrator under ``run``), shared by the whole accel loop.  Returns
    ``(specs, cells, metas)``: ``cells[i]`` is the (workload, accels) of
    specs ``2i`` (conventional) and ``2i + 1`` (SPARTA), ``metas`` the
    system sweeps' run metadata."""
    accels = accel_counts(quick)
    n_ops = 1_000 if quick else 8_000
    # The crash-safe chunked engines stream the trace with a bounded
    # per-chunk working set, so the full-mode cap is no longer pinned to the
    # monolithic pass's 150k ceiling.
    cap = 24_000 if quick else 400_000
    metas = {}
    specs, cells = [], []
    for w in W4:
        streams = traces.thread_traces(w, accels[-1], n_ops=n_ops, seed=seed)
        inter = traces.interleave(streams)[:cap]
        evs, metas[f"system-{w}"] = run_sweep_system(inter, [
            SystemSimConfig(cache=CACHE, accel_tlb=ACCEL_TLB,
                            mem_tlb=MEM_TLB, num_partitions=1, page_shift=12),
            SystemSimConfig(cache=CACHE, accel_tlb=None,
                            mem_tlb=MEM_TLB, num_partitions=PARTITIONS,
                            page_shift=12),
        ], kernel_mode=kernel_mode, run=run, name=f"system-{w}", sched=sched)
        for A in accels:
            ids = timeline.round_robin_accel_ids(inter.shape[0], A)
            specs.append(timeline.TimelineSpec(
                inter, evs[0], "conventional", cfg=QUEUES,
                num_accelerators=A, accel_ids=ids))
            specs.append(timeline.TimelineSpec(
                inter, evs[1], "sparta", cfg=QUEUES,
                num_partitions=PARTITIONS, num_accelerators=A, accel_ids=ids))
            cells.append((w, A))
    return specs, cells, metas


@with_runlog("fig11")
def run(quick: bool = False, kernel_mode: str = "auto",
        resume: bool = False, chunk_accesses=None, sched=None):
    accels = accel_counts(quick)
    a_max = accels[-1]
    rc = run_config("fig11", resume=resume, chunk_accesses=chunk_accesses)
    # One timeline sweep pass for the whole figure.  Every sweep runs
    # through the crash-safe orchestrator: chunked, checkpointed, resumable.
    specs, cells, metas = matrix(quick, kernel_mode, run=rc, sched=sched)
    results, metas["timeline"] = run_sweep_timeline(
        specs, LAT, kernel_mode=kernel_mode, run=rc, name="timeline",
        sched=sched)

    rows = []
    p99 = {}       # (workload, A) -> (conventional, sparta)
    for i, (w, A) in enumerate(cells):
        conv, spa = results[2 * i], results[2 * i + 1]
        p99[(w, A)] = (conv.overhead_percentile(99), spa.overhead_percentile(99))
        rows.append([
            w, A,
            conv.overhead_percentile(50), spa.overhead_percentile(50),
            conv.overhead_percentile(99), spa.overhead_percentile(99),
            conv.mean_latency, spa.mean_latency,
            conv.throughput, spa.throughput,
        ])

    wins = sum(1 for w in W4 if p99[(w, a_max)][1] < p99[(w, a_max)][0])
    c9a = Claim("C9a", f"SPARTA p99 translation latency < conventional at {a_max} accels (workloads won)",
                float(wins), (4, 4), "/4")
    red = [p99[(w, a_max)][0] / max(p99[(w, a_max)][1], 1e-9) for w in W4]
    c9b = Claim("C9b", f"p99 translation-tail reduction conv/SPARTA at {a_max} accels (mean)",
                float(np.mean(red)), (1.5, 100.0), "x")

    print_csv(
        "Fig11 translation-induced latency tails vs accelerators",
        ["workload", "accels", "conv_p50", "sparta_p50", "conv_p99",
         "sparta_p99", "conv_mean_lat", "sparta_mean_lat",
         "conv_throughput", "sparta_throughput"],
        rows)
    print(c9a); print(c9b)
    save_fig("fig11", {
        "accels": list(accels), "partitions": PARTITIONS,
        "queues": {"mshrs": QUEUES.mshrs, "tlb_ports": QUEUES.tlb_ports,
                   "dram_banks": QUEUES.dram_banks,
                   "issue_interval": QUEUES.issue_interval},
        "rows": rows,
        "claims": [c9a.row(), c9b.row()],
        "_crash_safety": crash_safety(metas),
        "_telemetry": telemetry_stamp(metas),
    })
    return [c9a, c9b]


def main(argv=None) -> int:
    """Standalone entry point with resume + scheduler support (the CI
    fault-injection smokes SIGTERM this mid-sweep and rerun it with
    ``--resume``, or SIGKILL one of its ``--workers`` mid-shard)."""
    import argparse
    import sys

    from benchmarks import common
    from repro.core.orchestrator import Preempted
    from repro.core.scheduler import EX_DEGRADED
    from repro.runtime import telemetry

    telemetry.setup_logging()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--kernel-mode", default="auto")
    ap.add_argument("--resume", action="store_true",
                    help="re-enter from the last committed chunk checkpoint")
    ap.add_argument("--chunk-accesses", type=int, default=None,
                    help="checkpoint-commit granularity (trace accesses)")
    ap.add_argument("--workers", type=int, default=1,
                    help="parallel sweep workers (sharded scheduler)")
    ap.add_argument("--shards", type=int, default=0,
                    help="shards per engine call (0 = auto, 2x workers)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-shard straggler deadline (seconds)")
    ap.add_argument("--executor", default="auto",
                    choices=("auto", "serial", "thread", "process"))
    args = ap.parse_args(argv)
    sched = common.sched_config(workers=args.workers, shards=args.shards,
                                deadline=args.deadline, executor=args.executor)
    try:
        claims = run(quick=args.quick, kernel_mode=args.kernel_mode,
                     resume=args.resume, chunk_accesses=args.chunk_accesses,
                     sched=sched)
    except Preempted as p:
        print(f"fig11: {p}", file=sys.stderr)
        return 75   # EX_TEMPFAIL: rerun with --resume
    if common.degraded_runs():
        print(f"fig11: degraded — quarantined shards "
              f"(see _crash_safety in the figure JSON)", file=sys.stderr)
        return EX_DEGRADED
    return 0 if all(c.ok for c in claims) else 1


if __name__ == "__main__":
    raise SystemExit(main())
