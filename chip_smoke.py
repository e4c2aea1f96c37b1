#!/usr/bin/env python3
"""One-chip smoke run: the SPARTA sweeps and the paged serving engine.

    python3 chip_smoke.py [--seed N]

Runs on one TPU chip and refuses anything else.  Every phase goes through
the entry points a user calls, on data made from ``--seed``, and compares
the Pallas path with the reference path on the same chip:

* ``tlb``      -- ``run_sweep_tlb``: the 8-config TLB sweep over a fig4
                  trace (bst_external, 40k ops, 1.28M accesses), in
                  ``pallas``, ``stackdist`` and ``reference``; hit bits must
                  be bit-identical.
* ``system``   -- ``run_sweep_system``: the fig9 (10 configs) and fig10
                  (9 configs) batches over a 25k-op trace; all hit bits
                  bit-identical.
* ``timeline`` -- the fig11 matrix: four ``run_sweep_system`` passes feed
                  ``run_sweep_timeline`` over 40 sims x 400k accesses; hit
                  bits, latencies, overheads and completion times
                  bit-identical.
* ``serve``    -- ``SpartaEngine`` on the internvl2-2b language backbone at
                  its published widths (24 layers, d_model 2048, 16/8 heads,
                  head_dim 128, vocab 92608, bf16), built like
                  ``repro.launch.serve --full``: 8 text requests of 128-512
                  prompt tokens, 32 new tokens each, 4 at a time.  The
                  ``pallas`` engine must produce the ``reference`` engine's
                  greedy tokens, except where the two backends' choice was a
                  near tie (see ``TIE_TOL``).

A sweep phase fails on any retry/halve/downgrade event or a final mode other
than the one asked for.  Each phase prints one line (wall and compile
seconds, the mode that ran, the ladder event count, the comparison); the
last line of stdout is one JSON object, ``"ok"`` true only if every phase
passed.  Wall times here include compilation and host work: they are a
smoke run's, not device metrics.  The sweeps run without checkpoints or
calibration tables, so nothing is read from disk.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

# Greedy picks of two numerically different backends may only differ where
# the top two logits are within bf16 noise of each other: a divergence is
# accepted where the two engines' top-1 minus top-2 margins sum to at most
# 2 * TIE_TOL, i.e. where a logit discrepancy of TIE_TOL between the
# backends explains it.  2^-3 is eight bf16 steps at logit magnitudes in
# [2, 4).
TIE_TOL = 0.125

# What each phase runs: the Pallas mode under test and the workload sizes
# (the figures' full-mode sizes and the served model's published widths).
KERNEL = "pallas"
TLB_OPS = 40_000          # fig4: ops per workload trace
SYSTEM_OPS = 25_000       # fig9/fig10
FIG11_QUICK = False       # fig11: 40 sims x 400k accesses
SERVE_ARCH, SERVE_FULL = "internvl2-2b", True
# 128-512 prompt tokens: four lengths, two of them off flash attention's
# 128-row block, each served twice (the second wave reuses the first's
# compiled shapes).
PROMPT_LENGTHS = (128, 333, 200, 512) * 2
MAX_NEW = 32


def _fail_without_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this smoke runs on a TPU chip only",
              file=sys.stderr)
        sys.exit(2)
    return dev


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since the last
    :meth:`take` (from JAX's own monitoring events)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration

    def take(self) -> float:
        t, self.total = self.total, 0.0
        return t


def _report(phase, results, *, wall, compile_s, **fields):
    ok = all(r["ok"] for r in results)
    line = {"phase": phase, "ok": ok, "wall_s": round(wall, 3),
            "compile_s": round(compile_s, 3), **fields}
    print(json.dumps(line), flush=True)
    return ok


def _sweep(run_fn, mode, *args, **kw):
    """One orchestrated sweep: (result, meta, wall seconds)."""
    t0 = time.perf_counter()
    res, meta = run_fn(*args, kernel_mode=mode, **kw)
    return res, meta, time.perf_counter() - t0


def _mode_record(mode, meta, wall):
    events = meta.get("events") or []
    return {"requested": mode, "final_mode": meta.get("final_mode"),
            "events": len(events), "wall_s": round(wall, 3),
            "ok": meta.get("final_mode") == mode and not events}


def phase_tlb(rc, clock, seed):
    from benchmarks.common import trace
    from repro.core.orchestrator import run_sweep_tlb
    from repro.core.sparta import TLBConfig
    from repro.core.sweep import TLBSweepSpec

    tr = trace("bst_external", n_ops=TLB_OPS, seed=seed)
    specs = [TLBSweepSpec(TLBConfig(entries=e, ways=4), num_partitions=p,
                          page_shift=12)
             for p in (1, 128) for e in (64, 128, 256, 512)]
    t0 = time.perf_counter()
    clock.take()
    runs, recs = {}, []
    for mode in (KERNEL, "stackdist", "reference"):
        res, meta, wall = _sweep(run_sweep_tlb, mode, tr.lines, specs, run=rc)
        runs[mode] = res.hits
        recs.append(_mode_record(mode, meta, wall))
    for r in recs:
        r["bit_identical"] = bool(np.array_equal(runs[r["requested"]],
                                                 runs["reference"]))
        r["ok"] = r["ok"] and r["bit_identical"]
    return _report("tlb", recs, wall=time.perf_counter() - t0,
                   compile_s=clock.take(), accesses=int(tr.num_accesses),
                   configs=len(specs), modes=recs)


def _system_bits(ev):
    return (ev.cache_hit, ev.accel_tlb_hit, ev.mem_tlb_hit)


def phase_system(rc, clock, seed):
    from benchmarks import fig9_accel_tlb, fig10_performance
    from benchmarks.common import trace
    from repro.core.orchestrator import run_sweep_system

    tr = trace("bst_external", n_ops=SYSTEM_OPS, seed=seed)
    t0 = time.perf_counter()
    clock.take()
    recs = []
    for fig, cfgs in (("fig9", fig9_accel_tlb.configs()),
                      ("fig10", fig10_performance.configs())):
        runs = {}
        for mode in (KERNEL, "reference"):
            ev, meta, wall = _sweep(run_sweep_system, mode, tr.lines, cfgs,
                                    run=rc, name=f"system-{fig}")
            runs[mode] = _system_bits(ev)
            recs.append(dict(_mode_record(mode, meta, wall), batch=fig,
                             configs=len(cfgs)))
        same = all(np.array_equal(a, b)
                   for a, b in zip(runs[KERNEL], runs["reference"]))
        for r in recs[-2:]:
            r["bit_identical"] = same
            r["ok"] = r["ok"] and same
    return _report("system", recs, wall=time.perf_counter() - t0,
                   compile_s=clock.take(), accesses=int(tr.num_accesses),
                   modes=recs)


def phase_timeline(rc, clock, seed):
    from benchmarks import fig11_tail_latency as fig11
    from repro.core.orchestrator import run_sweep_timeline

    t0 = time.perf_counter()
    clock.take()
    recs, specs = [], {}
    for mode in (KERNEL, "reference"):
        # The matrix's inputs: one system sweep per workload, in this mode.
        t1 = time.perf_counter()
        specs[mode], _, metas = fig11.matrix(FIG11_QUICK, mode, run=rc,
                                             seed=seed)
        finals = sorted({m.get("final_mode") for m in metas.values()})
        events = sum(len(m.get("events") or []) for m in metas.values())
        recs.append({"stage": f"system x{len(metas)}", "requested": mode,
                     "final_mode": "/".join(finals), "events": events,
                     "wall_s": round(time.perf_counter() - t1, 3),
                     "ok": finals == [mode] and not events})
    sys_same = all(
        np.array_equal(a, b)
        for sp, sr in zip(specs[KERNEL], specs["reference"])
        for a, b in zip(_system_bits(sp.events), _system_bits(sr.events)))
    outs = {}
    for mode in (KERNEL, "reference"):
        # Both modes time the same specs: the timeline inputs are identical.
        res, meta, wall = _sweep(run_sweep_timeline, mode, specs[KERNEL],
                                 fig11.LAT, run=rc)
        outs[mode] = [(r.latency, r.overhead, r.done) for r in res]
        recs.append(dict(_mode_record(mode, meta, wall), stage="timeline"))
    tl_same = all(np.array_equal(a, b)
                  for rp, rr in zip(outs[KERNEL], outs["reference"])
                  for a, b in zip(rp, rr))
    for r in recs:
        r["bit_identical"] = tl_same if r["stage"] == "timeline" else sys_same
        r["ok"] = r["ok"] and r["bit_identical"]
    return _report("timeline", recs, wall=time.perf_counter() - t0,
                   compile_s=clock.take(), sims=len(specs[KERNEL]),
                   accesses=int(max(len(sp.lines) for sp in specs[KERNEL])),
                   modes=recs)


def _serve(cfg, prompts, *, seed, mode, slots):
    from repro.launch.serve import build_engine

    eng = build_engine(cfg, seed=seed, kernel_mode=mode, partitions=4,
                       slots_per_partition=slots, max_batch=4)
    rids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    eng.run_to_completion()
    wall = time.perf_counter() - t0
    eng.kv.check_invariants()
    out = [(eng.finished[r].generated, eng.finished[r].margins) for r in rids]
    del eng
    gc.collect()
    return out, wall


def _compare_greedy(ref, pal):
    """Per request: the tokens match, or they first differ where both
    engines' picks were near ties (margins summing to <= 2 * TIE_TOL)."""
    rows = []
    for (toks_r, marg_r), (toks_p, marg_p) in zip(ref, pal):
        k = next((i for i, (a, b) in enumerate(zip(toks_r, toks_p)) if a != b),
                 None)
        if k is None:
            rows.append({"match": len(toks_r) == len(toks_p),
                         "ok": len(toks_r) == len(toks_p)})
            continue
        gap = marg_r[k] + marg_p[k]
        rows.append({"match": False, "first_divergence": k,
                     "margin_sum": round(gap, 5), "ok": gap <= 2 * TIE_TOL})
    return rows


def phase_serve(seed, clock):
    from repro.launch.serve import engine_config, slots_per_partition

    cfg = engine_config(SERVE_ARCH, full=SERVE_FULL)
    rng = np.random.default_rng(seed)
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab, n)]
               for n in PROMPT_LENGTHS]
    slots = slots_per_partition(cfg, requests=len(prompts),
                                max_tokens=max(PROMPT_LENGTHS) + MAX_NEW,
                                partitions=4)
    t0 = time.perf_counter()
    clock.take()
    outs, recs, compile_s = {}, [], 0.0
    for mode in (KERNEL, "reference"):
        t1 = time.perf_counter()
        outs[mode], serve_wall = _serve(cfg, prompts, seed=seed, mode=mode,
                                        slots=slots)
        c = clock.take()
        compile_s += c
        recs.append({"mode": mode, "wall_s": round(time.perf_counter() - t1, 3),
                     "serve_wall_s": round(serve_wall, 3),
                     "compile_s": round(c, 3),
                     "tokens": sum(len(t) for t, _ in outs[mode])})
    rows = _compare_greedy(outs["reference"], outs[KERNEL])
    return _report("serve", rows, wall=time.perf_counter() - t0,
                   compile_s=compile_s, arch=cfg.name,
                   params=cfg.param_count(), dtype=cfg.dtype,
                   requests=len(prompts), prompt_lengths=list(PROMPT_LENGTHS),
                   max_new=MAX_NEW, tie_tol=TIE_TOL,
                   identical_requests=sum(r["match"] for r in rows),
                   divergences=[r for r in rows if not r["match"]],
                   engines=recs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the traces, the served model's random "
                         "weights and its prompts")
    args = ap.parse_args(argv)

    dev = _fail_without_tpu()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.core.orchestrator import SweepRunConfig
    from repro.runtime.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    print(json.dumps({"phase": "setup", "device_kind": dev.device_kind,
                      "compile_cache": cache}), flush=True)
    import jax

    clock = _CompileClock()
    rc = SweepRunConfig(checkpoint_dir=None, calibration_dir=None)
    ok = True
    for phase in (phase_tlb, phase_system, phase_timeline):
        ok = phase(rc, clock, args.seed) and ok
    ok = phase_serve(args.seed, clock) and ok
    devices = jax.devices()
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
