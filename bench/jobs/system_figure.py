"""Job kind ``system_figure``: the paper's Fig. 10 job.

One trace, every design of the configuration in one batched system sweep
(``repro.core.orchestrator.run_sweep_system`` under the library-default
``SweepRunConfig()``, ``kernel_mode="auto"``), then the figure numbers
(``repro.core.cpi.evaluate_design``): each design's speed-up over the
baseline design and its translation overhead per access.

What is compared with the plain reference (``bench.reference.system``):
every design's cache, accelerator-TLB and memory-TLB hit bit of every
access, and every figure number.
"""
from __future__ import annotations

import numpy as np

from bench.reference.system import figure_numbers, system_hits

# Each number's limit, set from the readings recorded in PERF.md: sound runs
# read 0 hit bits and a relative gap of ~1e-16 (the same float64 formulas in
# another order); the control (16-bit tags) flips hit bits and moves the
# figure numbers by 1.7e-4 or more.
LIMITS = {"hit_bits_differing": 0, "figure_rel_gap": 1e-9}


def _geometry(s):
    from repro.core.sparta import TLBConfig

    return TLBConfig(entries=int(s["entries"]), ways=int(s["ways"]))


def build(config: dict):
    from repro.core.orchestrator import SweepRunConfig
    from repro.core.sparta import SystemLatencies
    from repro.core.tlbsim import SystemSimConfig

    cfgs = [SystemSimConfig(
        cache=_geometry(config["cache"]),
        accel_tlb=_geometry(config["accel_tlb"]) if d["design"] == "conventional" else None,
        mem_tlb=_geometry(config["mem_tlb"]), num_partitions=int(d["partitions"]),
        page_shift=int(d["page_shift"]), accel_probe_on_miss_only=True)
        for d in config["designs"]]
    lat = config["latencies"]
    return {"config": config, "cfgs": cfgs, "run": SweepRunConfig(),
            "lat": SystemLatencies(l_cache=lat["l_cache"], l_tlb=lat["l_tlb"],
                                   l_dram=lat["l_dram"], l_noc=lat["l_noc"],
                                   l_offchip=lat["l_offchip"],
                                   n_sockets=int(lat["n_sockets"]))}


def state_words(config: dict, designs) -> int:
    """int32 words of the LRU state (tags + last-use) of ``designs``."""
    def words(s, parts=1):
        return 2 * int(s["entries"]) * parts

    return sum(words(config["cache"]) + words(config["mem_tlb"], int(d["partitions"]))
               + (words(config["accel_tlb"]) if d["design"] == "conventional" else 0)
               for d in designs)


def sweep(prog, lines, ctx, *, designs=None, name="system"):
    """One batched system sweep through the orchestrator; returns the
    program's events."""
    from repro.core.orchestrator import run_sweep_system

    config = prog["config"]
    idx = range(len(config["designs"])) if designs is None else designs
    cfgs = [prog["cfgs"][i] for i in idx]
    ev, _ = ctx.call("system_sim", run_sweep_system, lines, cfgs,
                     work=int(lines.shape[0]) * len(cfgs),
                     state_words=state_words(config, [config["designs"][i] for i in idx]),
                     run=prog["run"], name=name)
    return ev


def run(prog, job, ctx):
    """The program's outputs for every trace of ``job``."""
    from repro.core import cpi

    config = prog["config"]
    labels = [d["label"] for d in config["designs"]]
    out = []
    for tr in job:
        ev = sweep(prog, tr["lines"], ctx, name=f"system-{tr['workload']}")
        with ctx.span("figure"):
            perfs = [cpi.evaluate_design(d["design"], ev[i], prog["lat"],
                                         instr_per_access=tr["instr_per_access"],
                                         workload=tr["workload"])
                     for i, d in enumerate(config["designs"])]
            base = perfs[labels.index(config["baseline"])]
            fig = ([float(p.speedup_over(base)) for p in perfs]
                   + [float(p.access.translation_overhead) for p in perfs])
        out.append({"hits": np.stack([ev.cache_hit, ev.accel_tlb_hit, ev.mem_tlb_hit]),
                    "figure": np.asarray(fig)})
    return out


def reference(config: dict, job, *, tag_bits: int = 32):
    out = []
    for tr in job:
        hits = system_hits(tr["lines"], config, tag_bits=tag_bits)
        out.append({"hits": np.stack(hits),
                    "figure": np.asarray(figure_numbers(
                        config, hits, workload=tr["workload"],
                        instr_per_access=tr["instr_per_access"]))})
    return out


def control(config: dict, job):
    """The reference with the control's narrower tags in the program's place."""
    return reference(config, job, tag_bits=16)


def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return 1e300
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-9),
                        initial=0.0))


def compare(out, ref) -> dict:
    bits = 0
    gap = 0.0
    for o, r in zip(out, ref, strict=True):
        bits += (int(np.count_nonzero(o["hits"] != r["hits"]))
                 if o["hits"].shape == r["hits"].shape else int(r["hits"].size))
        gap = max(gap, rel_gap(o["figure"], r["figure"]))
    return {"hit_bits_differing": bits, "figure_rel_gap": gap}
