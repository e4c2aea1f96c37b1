"""Fig 10 (+ §7.7): end-to-end performance — SPARTA vs conventional vs DIPTA
vs ideal, 8-socket 128 GB machine, 16 KB virtual caches.

Per workload: the joint trace simulation provides (cache, accel-TLB,
memory-TLB) hit rates, the Fig 3 timeline/CPI model turns them into
speedups over conventional-4K.  Claims (C6): conventional 2MB gains only
~14%; SPARTA-32 improves ~1.57x (4K), within ~94% of ideal; translation
overhead drops ~31.5x on average (up to 47x); (C8) idealized DIPTA trails
SPARTA due to way misprediction."""
from __future__ import annotations

import numpy as np

from benchmarks.common import (Claim, W4, crash_safety, print_csv, run_config,
                               save_fig, telemetry_stamp, trace, with_runlog)
from repro.core import cpi
from repro.core.scheduler import run_sweep_system
from repro.core.sparta import SystemLatencies, TLBConfig
from repro.core.tlbsim import SystemSimConfig

CACHE = TLBConfig(entries=256, ways=4)      # 16 KB virtual cache
ACCEL_TLB = TLBConfig(entries=128, ways=4)  # baseline accel-side TLB
MEM_TLB = TLBConfig(entries=128, ways=4)
CONFIGS = (  # (label, partitions, page_shift, design)
    ("conv-4K", 1, 12, "conventional"),
    ("conv-2M", 1, 21, "conventional"),
    ("sparta8-4K", 8, 12, "sparta"),
    ("sparta8-2M", 8, 21, "sparta"),
    ("sparta32-4K", 32, 12, "sparta"),
    ("sparta32-2M", 32, 21, "sparta"),
    ("sparta128-2M", 128, 21, "sparta"),
    ("dipta", 1, 12, "dipta"),
    ("ideal", 1, 12, "ideal"),
)


def configs():
    """The nine designs (4K/2M x partition counts x DIPTA/ideal) as one
    batch of system configs, in CONFIGS order."""
    return [
        SystemSimConfig(
            cache=CACHE,
            accel_tlb=ACCEL_TLB if design == "conventional" else None,
            mem_tlb=MEM_TLB, num_partitions=parts, page_shift=shift,
            accel_probe_on_miss_only=True,
        )
        for _, parts, shift, design in CONFIGS
    ]


@with_runlog("fig10")
def run(quick: bool = False, kernel_mode: str = "auto",
        resume: bool = False, chunk_accesses=None, sched=None):
    n_ops = 8_000 if quick else 25_000
    lat = SystemLatencies(n_sockets=8)
    rc = run_config("fig10", resume=resume, chunk_accesses=chunk_accesses)
    metas = {}
    speedups = {c[0]: [] for c in CONFIGS}
    overhead_reduction = []
    overhead_reduction_2m = []
    rows = []
    for w in W4:
        tr = trace(w, n_ops=n_ops)
        ipa = tr.instr_per_access
        # All nine designs (4K/2M x partition counts x DIPTA/ideal) share one
        # batched pass over the trace.
        evs, metas[f"system-{w}"] = run_sweep_system(
            tr.lines, configs(), kernel_mode=kernel_mode, run=rc,
            name=f"system-{w}", sched=sched)
        perfs = {}
        for i_c, (label, parts, shift, design) in enumerate(CONFIGS):
            perfs[label] = cpi.evaluate_design(
                design, evs[i_c], lat, instr_per_access=ipa, workload=w,
            )
        base = perfs["conv-4K"]
        row = [w]
        for label, *_ in CONFIGS:
            s = perfs[label].speedup_over(base)
            speedups[label].append(float(s))
            row.append(float(s))
        rows.append(row)
        overhead_reduction.append(
            base.access.translation_overhead
            / max(perfs["sparta128-2M"].access.translation_overhead, 1e-9)
        )
        overhead_reduction_2m.append(
            perfs["conv-2M"].access.translation_overhead
            / max(perfs["sparta128-2M"].access.translation_overhead, 1e-9)
        )

    mean = {k: float(np.mean(v)) for k, v in speedups.items()}
    frac_ideal = mean["sparta32-4K"] / mean["ideal"]
    c6a = Claim("C6a", "conventional 2MB mean speedup (paper: ~1.14x)",
                mean["conv-2M"], (1.0, 1.45), "x")
    c6b = Claim("C6b", "SPARTA-32 4K mean speedup (paper: ~1.57x)",
                mean["sparta32-4K"], (1.3, 1.9), "x")
    c6c = Claim("C6c", "SPARTA-32 4K fraction of ideal (paper: 93.7%)",
                frac_ideal, (0.85, 1.0), "")
    c6d = Claim("C6d", "translation overhead reduction, mean (paper: 31.5x)",
                float(np.mean(overhead_reduction)), (10.0, 80.0), "x")
    c6e = Claim("C6e", "translation overhead reduction, max (paper: up to 47x)",
                float(np.max(overhead_reduction)), (15.0, 200.0), "x")
    c6f = Claim("C6f", "overhead reduction over huge pages, mean (paper: 19x)",
                float(np.mean(overhead_reduction_2m)), (4.0, 60.0), "x")
    c8 = Claim("C8", "SPARTA-32 4K beats idealized DIPTA (workloads won)",
               float(sum(1 for a, b in zip(speedups["sparta32-4K"], speedups["dipta"]) if a >= b)),
               (3, 4), "/4")

    print_csv("Fig10 speedup over conventional-4K",
              ["workload"] + [c[0] for c in CONFIGS], rows)
    for c in (c6a, c6b, c6c, c6d, c6e, c6f, c8):
        print(c)
    save_fig("fig10", {"configs": [c[0] for c in CONFIGS], "rows": rows,
                       "mean": mean,
                       "overhead_reduction": list(map(float, overhead_reduction)),
                       "claims": [x.row() for x in (c6a, c6b, c6c, c6d, c6e, c6f, c8)],
                       "_crash_safety": crash_safety(metas),
                       "_telemetry": telemetry_stamp(metas)})
    return [c6a, c6b, c6c, c6d, c6e, c6f, c8]
