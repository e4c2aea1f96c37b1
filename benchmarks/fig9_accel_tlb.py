"""Fig 9: accelerator-side TLB capacity under SPARTA with physical caches.

SPARTA-8, 16 KB 4-way physical cache per accelerator, accel-side TLB swept
1..128 entries; the rightmost point is SPARTA with a virtual cache and NO
accelerator-side translation hardware.  Baseline: conventional translation
with a 128-entry accel TLB and perfect MMU caches (virtual cache).

Claims (C7): ~8 accel-TLB entries suffice to beat the 128-entry baseline;
capacity beyond that gives diminishing returns."""
from __future__ import annotations

import numpy as np

from benchmarks.common import (Claim, W4, crash_safety, print_csv, run_config,
                               save_fig, telemetry_stamp, trace, with_runlog)
from repro.core import cpi
from repro.core.scheduler import run_sweep_system
from repro.core.sparta import SystemLatencies, TLBConfig
from repro.core.tlbsim import SystemSimConfig

ENTRIES = (1, 2, 4, 8, 16, 32, 64, 128)
P = 8
MEM_TLB = TLBConfig(entries=128, ways=4)
CACHE = TLBConfig(entries=256, ways=4)  # 16KB / 64B lines


def configs():
    """The figure's batch: the conventional baseline, the accel-TLB capacity
    sweep, and the virtual-cache/no-TLB point."""
    cfgs = [SystemSimConfig(
        cache=CACHE, accel_tlb=TLBConfig(entries=128, ways=4),
        mem_tlb=MEM_TLB, num_partitions=1, accel_probe_on_miss_only=True)]
    cfgs += [SystemSimConfig(
        cache=CACHE, accel_tlb=TLBConfig(entries=e, ways=4),
        mem_tlb=MEM_TLB, num_partitions=P, accel_probe_on_miss_only=False)
        for e in ENTRIES]
    cfgs.append(SystemSimConfig(
        cache=CACHE, accel_tlb=None, mem_tlb=MEM_TLB, num_partitions=P))
    return cfgs


@with_runlog("fig9")
def run(quick: bool = False, kernel_mode: str = "auto",
        resume: bool = False, chunk_accesses=None, sched=None):
    n_ops = 8_000 if quick else 25_000
    lat = SystemLatencies()
    rc = run_config("fig9", resume=resume, chunk_accesses=chunk_accesses)
    metas = {}
    results, rows = {}, []
    for w in W4:
        tr = trace(w, n_ops=n_ops)
        ipa = tr.instr_per_access
        # Baseline (conventional, virtual cache + 128-entry accel TLB), the
        # accel-TLB capacity sweep, and the virtual-cache/no-TLB point all
        # ride ONE batched pass over the trace.
        cfgs = configs()
        evs, metas[f"system-{w}"] = run_sweep_system(
            tr.lines, cfgs, kernel_mode=kernel_mode, run=rc, name=f"system-{w}",
            sched=sched)

        base = cpi.evaluate_design("conventional", evs[0], lat, instr_per_access=ipa)
        line = []
        for i_e, _ in enumerate(ENTRIES):
            sp = cpi.evaluate_design("sparta", evs[1 + i_e], lat, instr_per_access=ipa,
                                     physical_cache=True)
            line.append(float(sp.speedup_over(base)))
        # Virtual cache, no accel TLB.
        sp_v = cpi.evaluate_design("sparta", evs[len(cfgs) - 1], lat, instr_per_access=ipa)
        line.append(float(sp_v.speedup_over(base)))
        results[w] = line
        rows.append([w] + line)

    idx8 = ENTRIES.index(8)
    wins8 = sum(1 for w in W4 if results[w][idx8] >= 1.0)
    c7a = Claim("C7a", "SPARTA with 8 accel-TLB entries beats 128-entry baseline (workloads won)",
                float(wins8), (3, 4), "/4")
    gains = [results[w][-2] - results[w][idx8] for w in W4]  # 128 vs 8 entries
    c7b = Claim("C7b", "beyond 8 entries: diminishing returns (mean extra speedup 8->128)",
                float(np.mean(gains)), (-0.2, 0.25), "x")
    print_csv("Fig9 speedup vs accel TLB entries",
              ["workload"] + [str(e) for e in ENTRIES] + ["virt$ no TLB"], rows)
    print(c7a); print(c7b)
    save_fig("fig9", {"entries": ENTRIES, "results": results,
                      "claims": [c7a.row(), c7b.row()],
                      "_crash_safety": crash_safety(metas),
                      "_telemetry": telemetry_stamp(metas)})
    return [c7a, c7b]
