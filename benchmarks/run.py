"""Benchmark driver: one module per paper figure/table + kernels + roofline.

``python -m benchmarks.run [--quick] [--only figN,...] [--kernel-mode MODE]``
Prints per-figure CSVs, the checked claims, and the roofline summary table
(if the dry-run cache exists).  Machine output (CSVs, claim lines) goes to
stdout; narration (per-figure timings, fallback notices) goes through the
``repro`` Python logger on stderr — ``-v`` raises it to DEBUG, ``--quiet``
drops it to WARNING.  ``--profile DIR`` additionally captures a
``jax.profiler`` trace of the whole run (one ``StepTraceAnnotation`` per
figure) for TensorBoard/Perfetto.  ``--kernel-mode`` selects the sweep-engine
backend (auto/reference/pallas/pallas_interpret/stackdist) for the figures
that run trace sweeps (fig4/5/8/9/10/11); ``stackdist`` is the exact
sort-based stack-distance engine, which ``auto`` already prefers for the
pure-LRU TLB sweeps (fig4/fig5/fig8) — see EXPERIMENTS.md.  fig9/fig10 run
the joint 3-structure system sweep (``repro.core.sweep.sweep_system``,
batched scan or the ``repro.kernels.system_sim`` Pallas kernel) and fig11
additionally the batched cycle-approximate timeline engine
(``repro.core.timeline.sweep_timeline``); both engines reject sweep-only
modes such as ``stackdist`` with a ValueError naming their valid backends
(no silent coercion) — run those figures with ``auto`` or ``--only`` the
pure-TLB sweep figures.  fig5 is a hybrid: its miss-ratio grid threads the
mode through (``stackdist`` applies), and its system-sweep/timeline half
falls back to ``auto`` for sweep-only modes with a warning logged through
the ``repro.bench.fig5`` logger on stderr (never stdout — piped CSV output
stays machine-clean).  ``auto`` itself resolves through one static rule
(``repro.kernels.common.auto_mode``)."""
from __future__ import annotations

import argparse
import contextlib
import inspect
import logging
import sys
import time

from repro.core.orchestrator import Preempted
from repro.kernels.common import SWEEP_MODES
from repro.runtime import telemetry

_LOG = logging.getLogger("repro.bench.run")


FIGS = ("fig2", "fig4", "fig5", "fig6", "fig7", "fig8", "fig9", "fig10",
        "fig11", "kernels")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small traces (CI mode)")
    ap.add_argument("--only", default=None, help="comma-separated figure list")
    ap.add_argument("--kernel-mode", default="auto", choices=SWEEP_MODES,
                    help="sweep-engine backend for the trace-sweep figures")
    ap.add_argument("--resume", action="store_true",
                    help="re-enter interrupted trace sweeps from their last "
                         "committed chunk checkpoint (fig5/8/9/10/11)")
    ap.add_argument("--chunk-accesses", type=int, default=None,
                    help="checkpoint-commit granularity for the crash-safe "
                         "chunked sweeps (trace accesses per chunk)")
    ap.add_argument("--workers", type=int, default=1,
                    help="parallel sweep workers for the sharded scheduler "
                         "(fig5/8/9/10/11); 1 = unsharded passthrough")
    ap.add_argument("--shards", type=int, default=0,
                    help="shards per scheduled engine call "
                         "(0 = auto, 2x workers)")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-shard straggler deadline in seconds (past it, "
                         "idle workers run a duplicate; first completion wins)")
    ap.add_argument("--executor", default="auto",
                    choices=("auto", "serial", "thread", "process"),
                    help="scheduler executor (auto = thread when --workers>1)")
    ap.add_argument("--gc", action="store_true",
                    help="garbage-collect expired checkpoint blobs and stale "
                         "leases under benchmarks/_cache/ckpt, then exit "
                         "(in-progress runs — fresh leases — are kept)")
    ap.add_argument("--gc-age-s", type=float, default=7 * 86400.0, metavar="S",
                    help="age threshold for --gc (default: 7 days)")
    ap.add_argument("-v", action="count", default=0, dest="verbose",
                    help="DEBUG narration on stderr (repeatable)")
    ap.add_argument("--quiet", action="store_true",
                    help="narration at WARNING only (stdout CSVs unaffected)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the run into DIR "
                         "(one StepTraceAnnotation per figure)")
    args = ap.parse_args(argv)
    telemetry.setup_logging(-1 if args.quiet else args.verbose)

    if args.gc:
        from benchmarks import common
        from repro.core.scheduler import gc_checkpoints

        summary = gc_checkpoints(common.CACHE / "ckpt", age_s=args.gc_age_s)
        print(f"# gc {common.CACHE / 'ckpt'}")
        for k in ("deleted", "kept_in_progress", "kept_young", "skipped_foreign"):
            for p in summary[k]:
                print(f"{k},{p}")
        print(f"# {len(summary['deleted'])} deleted, "
              f"{len(summary['kept_in_progress'])} in-progress kept, "
              f"{len(summary['kept_young'])} young kept, "
              f"{len(summary['skipped_foreign'])} foreign skipped")
        return

    from repro.runtime.compile_cache import configure_compile_cache

    configure_compile_cache()
    from benchmarks import (
        fig2_pagewalk, fig4_tlb_sensitivity, fig5_contention, fig6_pagefault,
        fig7_miss_penalty, fig8_multiprog, fig9_accel_tlb, fig10_performance,
        fig11_tail_latency, kernel_bench,
    )
    modules = {
        "fig2": fig2_pagewalk, "fig4": fig4_tlb_sensitivity,
        "fig5": fig5_contention, "fig6": fig6_pagefault,
        "fig7": fig7_miss_penalty, "fig8": fig8_multiprog,
        "fig9": fig9_accel_tlb, "fig10": fig10_performance,
        "fig11": fig11_tail_latency, "kernels": kernel_bench,
    }
    chosen = args.only.split(",") if args.only else list(modules)

    from benchmarks import common
    sched = common.sched_config(workers=args.workers, shards=args.shards,
                                deadline=args.deadline, executor=args.executor)

    profile_cm = contextlib.nullcontext()
    if args.profile:
        import jax
        profile_cm = jax.profiler.trace(args.profile)

    claims = []
    with profile_cm:
        for name in chosen:
            t0 = time.perf_counter()
            kwargs = {"quick": args.quick}
            params = inspect.signature(modules[name].run).parameters
            if "kernel_mode" in params:
                kwargs["kernel_mode"] = args.kernel_mode
            if "resume" in params:
                kwargs["resume"] = args.resume
            if "chunk_accesses" in params and args.chunk_accesses:
                kwargs["chunk_accesses"] = args.chunk_accesses
            if "sched" in params and sched is not None:
                kwargs["sched"] = sched
            step_cm = contextlib.nullcontext()
            if args.profile:
                import jax
                step_cm = jax.profiler.StepTraceAnnotation(name)
            try:
                with step_cm:
                    claims += modules[name].run(**kwargs)
            except Preempted as exc:
                _LOG.warning("%s preempted: %s", name, exc)
                sys.exit(75)   # EX_TEMPFAIL: rerun with --resume
            _LOG.info("%s: %.1fs", name, time.perf_counter() - t0)
    if args.profile:
        _LOG.info("jax profiler trace written under %s", args.profile)

    print("\n# Claim summary")
    n_ok = sum(c.ok for c in claims)
    for c in claims:
        print(str(c))
    print(f"\n{n_ok}/{len(claims)} claims in band")

    # Roofline table (from the dry-run cache, if present).
    try:
        from benchmarks import roofline
        rows = roofline.table("16x16")
        if rows:
            print("\n# Roofline (16x16, per-device seconds/step)")
            print("arch,shape,compute,memory,collective,dominant,roofline_frac")
            for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
                print(f"{r['arch']},{r['shape']},{r['t_compute_s']:.4g},"
                      f"{r['t_memory_s']:.4g},{r['t_collective_s']:.4g},"
                      f"{r['dominant']},{r['roofline_fraction']:.3f}")
    except Exception as e:  # dry-run cache may not exist yet
        _LOG.info("roofline table skipped: %s", e)

    # C2b is a documented out-of-band cell (EXPERIMENTS.md §Paper claims);
    # fail only if reproduction quality actually regresses.
    if claims and n_ok < len(claims) - 1:
        sys.exit(1)

    # Degraded completion: a scheduled sweep quarantined at least one shard
    # (its figure carries zero placeholder rows + a manifest in
    # _crash_safety).  Distinct from both success (0) and failure (1) so CI
    # and operators can tell "finished, but incomplete" apart.
    if common.degraded_runs():
        from repro.core.scheduler import EX_DEGRADED
        _LOG.error("degraded run(s) with quarantined shards: %s",
                   ", ".join(common.degraded_runs()))
        sys.exit(EX_DEGRADED)


if __name__ == "__main__":
    main()
