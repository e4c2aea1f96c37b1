"""Kernel micro-benchmarks: reference-vs-Pallas allclose + XLA-path timing.

The correctness rows compare each Pallas kernel against its oracle: the
Mosaic kernel on a TPU backend, the Pallas interpreter on CPU.  The timing
column measures the *reference* (XLA) path; the allclose column is the
correctness deliverable.

The sweep-engine section times the batched-scan reference against the exact
stack-distance backend on a fig4-style sweep; the timeline section times the
Pallas queueing kernel against its ``lax.scan`` reference on a fig11-style
contended run.  Both append their result to ``BENCH_sweep.json`` at the repo
root, so the perf trajectory is tracked PR-over-PR.

All timing goes through ``repro.core.benchtime.measure`` (blocked warm-up,
block-until-ready inside every rep's window, min-of-N with spread recorded)
and every appended row carries the ``benchtime.device_metadata()`` schema
stamp.  ``--check`` is the CI gate: bit-identity + required-bench coverage
here, then the ReFrame-style tolerance-band regression gate in
``benchmarks/perfcheck.py`` against ``benchmarks/references.json``
(``--update-refs`` re-baselines deliberately).
"""
from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import print_csv, save_fig, with_runlog
from repro.core import benchtime
from repro.core.benchtime import measure

BENCH_SWEEP_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_sweep.json"

# Engine benches run seconds-scale calls; two blocked reps (min kept) after
# one blocked warm-up bound the cost while still rejecting one-sided noise.
ENGINE_REPS = 2


def _timeit(fn, *args, reps=5, label=None):
    return measure(fn, *args, reps=reps, label=label).best_us


@with_runlog("kernels")
def run(quick: bool = False, profile_dir=None):
    """One telemetry run (``_cache/runlogs/kernels.jsonl``): every measured
    row lands as a ``measure`` span.  ``profile_dir`` additionally captures a
    ``jax.profiler`` trace with one ``StepTraceAnnotation`` per engine bench
    (don't pass it when already inside ``benchmarks/run.py --profile`` —
    nested profiler traces error)."""
    import contextlib

    cm = (jax.profiler.trace(str(profile_dir)) if profile_dir
          else contextlib.nullcontext())
    with cm:
        return _run_benches(quick, profile=bool(profile_dir))


def _step(name: str, profile: bool):
    import contextlib

    return (jax.profiler.StepTraceAnnotation(name) if profile
            else contextlib.nullcontext())


def _pallas_mode() -> str:
    """The Pallas mode this backend runs: the Mosaic kernel on a TPU, the
    interpreter on CPU."""
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"


def _run_benches(quick: bool, profile: bool = False):
    rng = np.random.default_rng(0)
    rows = []
    pallas_mode = _pallas_mode()

    # flash attention
    from repro.kernels.flash_attention import flash_attention
    B, Hq, Hkv, T, D = 2, 8, 2, 256, 64
    q = jnp.asarray(rng.standard_normal((B, Hq, T, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
    ref = flash_attention(q, k, v, kernel_mode="reference")
    pal = flash_attention(q, k, v, block_q=64, block_k=64, kernel_mode=pallas_mode)
    err = float(jnp.abs(ref - pal).max())
    us = _timeit(lambda a, b, c: flash_attention(a, b, c, kernel_mode="reference"), q, k, v,
                 label="kernel:flash_attention")
    rows.append(["flash_attention", us, err])

    # paged attention
    from repro.kernels.paged_attention import paged_attention
    slots, page, pages = 64, 32, 8
    q1 = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((slots, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((slots, page, Hkv, D)), jnp.float32)
    tbl = jnp.asarray(rng.choice(slots, (B, pages), replace=False).astype(np.int32))
    ctx = jnp.asarray(rng.integers(1, pages * page, B).astype(np.int32))
    ref = paged_attention(q1, kp, vp, tbl, ctx, kernel_mode="reference")
    pal = paged_attention(q1, kp, vp, tbl, ctx, kernel_mode=pallas_mode)
    err = float(jnp.abs(ref - pal).max())
    us = _timeit(lambda *a: paged_attention(*a, kernel_mode="reference"), q1, kp, vp, tbl, ctx,
                 label="kernel:paged_attention")
    rows.append(["paged_attention", us, err])

    # rwkv6 scan
    from repro.kernels.rwkv6_scan import rwkv6_scan
    Bh, H, Ts, N = 2, 4, 128, 32
    r = jnp.asarray(rng.standard_normal((Bh, H, Ts, N)) * 0.5, jnp.float32)
    kk = jnp.asarray(rng.standard_normal((Bh, H, Ts, N)) * 0.5, jnp.float32)
    vv = jnp.asarray(rng.standard_normal((Bh, H, Ts, N)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.uniform(0.8, 0.999, (Bh, H, Ts, N)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, N)) * 0.5, jnp.float32)
    oref, sref = rwkv6_scan(r, kk, vv, w, u, kernel_mode="reference")
    opal, spal = rwkv6_scan(r, kk, vv, w, u, chunk=32, kernel_mode=pallas_mode)
    err = float(jnp.abs(oref - opal).max())
    us = _timeit(lambda *a: rwkv6_scan(*a, kernel_mode="reference")[0], r, kk, vv, w, u,
                 label="kernel:rwkv6_scan")
    rows.append(["rwkv6_scan", us, err])

    # mamba2 scan
    from repro.kernels.mamba2_scan import mamba2_scan
    P, Nst = 32, 16
    x = jnp.asarray(rng.standard_normal((Bh, H, Ts, P)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (Bh, H, Ts)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 4.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((Bh, Ts, Nst)) * 0.5, jnp.float32)
    C = jnp.asarray(rng.standard_normal((Bh, Ts, Nst)) * 0.5, jnp.float32)
    Dp = jnp.asarray(rng.standard_normal((H,)), jnp.float32)
    yref, _ = mamba2_scan(x, dt, A, Bm, C, Dp, kernel_mode="reference")
    ypal, _ = mamba2_scan(x, dt, A, Bm, C, Dp, chunk=32, kernel_mode=pallas_mode)
    err = float(jnp.abs(yref - ypal).max())
    us = _timeit(lambda *a: mamba2_scan(*a, kernel_mode="reference")[0], x, dt, A, Bm, C, Dp,
                 label="kernel:mamba2_scan")
    rows.append(["mamba2_scan", us, err])

    # tlb_sim
    from repro.kernels.tlb_sim import tlb_sim
    s = jnp.asarray(rng.integers(0, 64, 4096), jnp.int32)
    t = jnp.asarray(rng.integers(0, 50, 4096), jnp.int32)
    ref = tlb_sim(s, t, 64, 4, kernel_mode="reference")
    pal = tlb_sim(s, t, 64, 4, block=512, kernel_mode=pallas_mode)
    err = float((np.asarray(ref) != np.asarray(pal)).mean())
    us = _timeit(lambda a, b: tlb_sim(a, b, 64, 4, kernel_mode="reference"), s, t,
                 label="kernel:tlb_sim")
    rows.append(["tlb_sim", us, err])

    # stackdist segmented stack scan
    from repro.kernels.stackdist import stack_scan
    L, C, W = 8, 128, 4
    tags = jnp.asarray(rng.integers(0, 40, (L, C)), jnp.int32)
    flags = np.zeros((L, C), bool)
    flags[:, 0] = True
    flags[rng.random((L, C)) < 0.02] = True
    flags = jnp.asarray(flags)
    init = jnp.asarray(rng.integers(0, 40, (L, W)), jnp.int32)
    dref, fref = stack_scan(tags, flags, init, kernel_mode="reference")
    dpal, fpal = stack_scan(tags, flags, init, kernel_mode=pallas_mode)
    err = float((np.asarray(dref) != np.asarray(dpal)).mean()
                + (np.asarray(fref) != np.asarray(fpal)).mean())
    us = _timeit(lambda a, b, c: stack_scan(a, b, c, kernel_mode="reference")[0],
                 tags, flags, init, label="kernel:stackdist_scan")
    rows.append(["stackdist_scan", us, err])

    # timeline queueing scan
    from repro.kernels.timeline import TimelineParams, timeline_sim
    n = 4096
    tp = TimelineParams(mem_tlb=True, num_accels=4, mshrs=4,
                        num_partitions=8, tlb_ports=2, dram_banks=8)
    tl_inputs = (
        jnp.asarray(rng.integers(0, tp.num_accels, n), jnp.int32),
        jnp.asarray(rng.integers(0, tp.num_partitions, n), jnp.int32),
        jnp.asarray(rng.integers(0, tp.dram_banks, n), jnp.int32),
        jnp.asarray(rng.integers(0, tp.dram_banks, n), jnp.int32),
        jnp.asarray(rng.random(n) < 0.5, jnp.int32),
        jnp.asarray(rng.random(n) < 0.6, jnp.int32),
        jnp.asarray(rng.random(n) < 0.7, jnp.int32),
        jnp.zeros(n, jnp.float32),
    )
    ref = timeline_sim(*tl_inputs, tp, kernel_mode="reference")
    pal = timeline_sim(*tl_inputs, tp, block=512, kernel_mode=pallas_mode)
    err = float(sum((np.asarray(r) != np.asarray(p)).mean()
                    for r, p in zip(ref, pal)))
    us = _timeit(lambda *a: timeline_sim(*a, tp, kernel_mode="reference")[0],
                 *tl_inputs, label="kernel:timeline_sim")
    rows.append(["timeline_sim", us, err])

    print_csv("Kernel benches", ["kernel", "us_per_call(ref/XLA)", "max_err_vs_oracle"], rows)
    save_fig("kernel_bench", {"rows": rows})
    for name, _, err in rows:
        assert err < 5e-4, (name, err)

    with _step("sweep_bench", profile):
        _sweep_bench(quick)
    with _step("timeline_bench", profile):
        _timeline_bench(quick)
    with _step("timeline_batched_bench", profile):
        _timeline_batched_bench(quick)
    with _step("system_batched_bench", profile):
        _system_batched_bench(quick)
    check_bench_history()
    return []


def _append_bench_entry(entry: dict) -> None:
    """Append one record to the BENCH_sweep.json history at the repo root.

    Every entry is stamped with the ``benchtime.device_metadata()`` schema
    (device_kind / platform / device_count / jax_version / schema_version).
    A corrupt history file raises instead of being silently overwritten —
    the file is the repo's entire perf trajectory.

    The read-modify-write cycle runs under an advisory file lock and commits
    via tmp + ``os.replace``, so two concurrent bench runs (e.g. scheduler
    workers, or parallel CI jobs on one host) serialize their appends instead
    of losing one, and a reader never observes a torn file.
    """
    from repro.checkpoint.checkpoint import file_lock

    lock = BENCH_SWEEP_PATH.with_name(BENCH_SWEEP_PATH.name + ".lock")
    with file_lock(lock):
        hist = {"history": []}
        if BENCH_SWEEP_PATH.exists():
            try:
                prior = json.loads(BENCH_SWEEP_PATH.read_text())
            except json.JSONDecodeError as e:
                raise RuntimeError(
                    f"{BENCH_SWEEP_PATH} exists but is not valid JSON ({e}); "
                    f"refusing to overwrite the recorded perf history — restore "
                    f"it from git (or delete it deliberately) and re-run"
                ) from e
            if not isinstance(prior, dict):
                raise RuntimeError(
                    f"{BENCH_SWEEP_PATH} is valid JSON but not the expected "
                    f"{{'history': [...]}} document; refusing to overwrite it")
            hist = prior
        hist.setdefault("history", []).append(
            {**benchtime.device_metadata(), **entry})
        tmp = BENCH_SWEEP_PATH.with_name(
            f"{BENCH_SWEEP_PATH.name}.tmp-{os.getpid()}")
        tmp.write_text(json.dumps(hist, indent=1))
        os.replace(tmp, BENCH_SWEEP_PATH)


def _sweep_bench(quick: bool):
    """fig4-style sweep: batched-scan reference vs the stack-distance backend
    (plus the Pallas TPU kernel where a TPU backend is available).

    Each backend runs twice and reports the second (steady-state) time so
    one-off XLA compilation doesn't pollute the PR-over-PR trajectory.
    Results append to BENCH_sweep.json at the repo root.
    """
    from repro.core import traces
    from repro.core.sparta import TLBConfig
    from repro.core.sweep import TLBSweepSpec, sweep_tlb

    n_acc = 120_000 if quick else 1_000_000
    tr = traces.generate("bst_external", n_ops=2 * n_acc // 5, max_accesses=n_acc)
    specs = [
        TLBSweepSpec(TLBConfig(entries=e, ways=4), num_partitions=p, page_shift=12)
        for p in (1, 128) for e in (64, 128, 256, 512)
    ]

    def timed(mode):
        m = measure(sweep_tlb, tr.lines, specs, kernel_mode=mode,
                    reps=ENGINE_REPS, label=f"sweep:{mode}")
        return m, m.result

    m_ref, ref = timed("reference")
    m_sd, sd = timed("stackdist")
    t_ref, t_sd = m_ref.best_s, m_sd.best_s
    bit_identical = bool(np.array_equal(ref.hits, sd.hits))
    spread = {"t_reference_s": round(m_ref.spread_frac, 3),
              "t_stackdist_s": round(m_sd.spread_frac, 3)}
    entry = {
        "written_at": time.strftime("%Y-%m-%d %H:%M:%S"),
        "bench": "sweep",
        "backend": jax.default_backend(),
        "quick": quick,
        "n_accesses": int(tr.num_accesses),
        "n_configs": len(specs),
        "t_reference_s": round(t_ref, 3),
        "t_stackdist_s": round(t_sd, 3),
        "speedup": round(t_ref / t_sd, 2),
        "bit_identical": bit_identical,
        "reps": ENGINE_REPS,
        "spread_frac": spread,
    }
    if jax.default_backend() == "tpu":
        m_pal, pal = timed("pallas")
        entry["t_pallas_s"] = round(m_pal.best_s, 3)
        spread["t_pallas_s"] = round(m_pal.spread_frac, 3)
        entry["pallas_bit_identical"] = bool(np.array_equal(ref.hits, pal.hits))

    print_csv(
        "Sweep engine (fig4-style, one trace, 8 configs)",
        ["backend", "seconds", "vs_reference"],
        [["reference(batched scan)", t_ref, 1.0],
         ["stackdist", t_sd, t_ref / t_sd]],
    )
    print(f"  stackdist bit-identical to reference: {bit_identical}")
    # Assert BEFORE recording: a diverging run must fail loudly, not poison
    # the BENCH_sweep.json history the CI gate scans.
    assert bit_identical, "stackdist sweep diverged from the batched-scan oracle"
    assert entry.get("pallas_bit_identical", True), \
        "pallas sweep diverged from the batched-scan oracle"
    _append_bench_entry(entry)


def _timeline_bench(quick: bool):
    """fig11-style timeline run: the Pallas queueing kernel vs its jnp
    ``lax.scan`` reference, appended to BENCH_sweep.json.

    On this CPU container the Pallas path runs under the interpreter (the
    ``mode`` field records which); on a TPU backend the same entry captures
    the compiled-kernel speedup.  Bit-identity is asserted either way — the
    two paths share one ``timeline_step``.
    """
    from repro.core import timeline, traces
    from repro.core.sparta import SystemLatencies, TLBConfig
    from repro.core.sweep import sweep_system
    from repro.core.tlbsim import SystemSimConfig

    n_acc = 30_000 if quick else 120_000
    streams = traces.thread_traces("bst_external", 4, n_ops=2 * n_acc // 20, seed=7)
    inter = traces.interleave(streams)[:n_acc]
    ev = sweep_system(inter, [SystemSimConfig(
        cache=TLBConfig(entries=256, ways=4), accel_tlb=None,
        mem_tlb=TLBConfig(entries=128, ways=4), num_partitions=32,
        page_shift=12)])[0]
    lat = SystemLatencies()
    kw = dict(cfg=timeline.TimelineConfig(mshrs=8, tlb_ports=1, dram_banks=16),
              num_partitions=32, num_accelerators=4)

    pallas_mode = _pallas_mode()

    def timed(mode):
        m = measure(timeline.simulate_timeline, inter, ev, "sparta", lat,
                    kernel_mode=mode, reps=ENGINE_REPS,
                    label=f"timeline:{mode}", **kw)
        return m, m.result

    m_ref, ref = timed("reference")
    m_pal, pal = timed(pallas_mode)
    t_ref, t_pal = m_ref.best_s, m_pal.best_s
    bit_identical = bool(
        np.array_equal(ref.latency, pal.latency)
        and np.array_equal(ref.overhead, pal.overhead)
        and np.array_equal(ref.done, pal.done))
    entry = {
        "written_at": time.strftime("%Y-%m-%d %H:%M:%S"),
        "bench": "timeline",
        "backend": jax.default_backend(),
        "mode": pallas_mode,
        "quick": quick,
        "n_accesses": int(inter.shape[0]),
        "t_reference_s": round(t_ref, 3),
        "t_pallas_s": round(t_pal, 3),
        "speedup": round(t_ref / t_pal, 2),
        "bit_identical": bit_identical,
        "reps": ENGINE_REPS,
        "spread_frac": {"t_reference_s": round(m_ref.spread_frac, 3),
                        "t_pallas_s": round(m_pal.spread_frac, 3)},
    }
    print_csv(
        "Timeline engine (fig11-style, 4 accels, SPARTA-32)",
        ["backend", "seconds", "vs_reference"],
        [["reference(lax.scan)", t_ref, 1.0],
         [pallas_mode, t_pal, t_ref / t_pal]],
    )
    print(f"  timeline kernel bit-identical to reference: {bit_identical}")
    # Assert BEFORE recording (see _sweep_bench).
    assert bit_identical, "timeline kernel diverged from the lax.scan oracle"
    _append_bench_entry(entry)


def _timeline_batched_bench(quick: bool):
    """fig11-scale batched timeline sweep: the looped per-sim reference
    (one ``simulate_timeline`` scan per cell) vs ``sweep_timeline``'s single
    batched scan vs the batched Pallas kernel, appended to BENCH_sweep.json.

    The non-quick matrix is the full fig11 cell grid (4 workloads x 5 accel
    counts x 2 designs = 40 sims); the batched engine must stay bit-identical
    per sim and is the fix for the recorded 0.87x single-sim kernel entry —
    the sim axis gives the kernel (and the scan) something to amortize.
    """
    from repro.core import timeline, traces
    from repro.core.sparta import SystemLatencies, TLBConfig
    from repro.core.sweep import sweep_system
    from repro.core.tlbsim import SystemSimConfig

    workloads = ("bst_external", "hash_table") if quick else \
        ("bst_external", "bst_internal", "hash_table", "skip_list")
    accel_counts = (1, 4, 16) if quick else (1, 2, 4, 8, 16)
    n_acc = 8_000 if quick else 60_000
    lat = SystemLatencies(n_sockets=8)
    queues = timeline.TimelineConfig(mshrs=8, tlb_ports=1, dram_banks=16)
    cache = TLBConfig(entries=256, ways=4)
    mem = TLBConfig(entries=128, ways=4)
    accel_tlb = TLBConfig(entries=128, ways=4)

    specs = []
    for w in workloads:
        streams = traces.thread_traces(w, max(accel_counts), n_ops=2 * n_acc // 20, seed=7)
        inter = traces.interleave(streams)[:n_acc]
        evs = sweep_system(inter, [
            SystemSimConfig(cache=cache, accel_tlb=accel_tlb, mem_tlb=mem,
                            num_partitions=1, page_shift=12),
            SystemSimConfig(cache=cache, accel_tlb=None, mem_tlb=mem,
                            num_partitions=32, page_shift=12)])
        for A in accel_counts:
            ids = timeline.round_robin_accel_ids(inter.shape[0], A)
            specs.append(timeline.TimelineSpec(
                inter, evs[0], "conventional", cfg=queues,
                num_accelerators=A, accel_ids=ids))
            specs.append(timeline.TimelineSpec(
                inter, evs[1], "sparta", cfg=queues, num_partitions=32,
                num_accelerators=A, accel_ids=ids))

    def timed(fn, label):
        m = measure(fn, reps=ENGINE_REPS, label=label)
        return m, m.result

    def looped():
        return [timeline.simulate_timeline(
            sp.lines, sp.events, sp.design, lat, cfg=sp.cfg,
            num_partitions=sp.num_partitions,
            num_accelerators=sp.num_accelerators, accel_ids=sp.accel_ids,
            kernel_mode="reference") for sp in specs]

    pallas_mode = _pallas_mode()
    m_loop, ref = timed(looped, "timeline_batched:looped")
    m_bat, bat = timed(
        lambda: timeline.sweep_timeline(specs, lat, kernel_mode="reference"),
        "timeline_batched:reference")
    m_pal, pal = timed(
        lambda: timeline.sweep_timeline(specs, lat, kernel_mode=pallas_mode),
        f"timeline_batched:{pallas_mode}")
    t_loop, t_bat, t_pal = m_loop.best_s, m_bat.best_s, m_pal.best_s

    def identical(xs):
        return bool(all(
            np.array_equal(getattr(x, k), getattr(r, k))
            for x, r in zip(xs, ref) for k in ("latency", "overhead", "done")))

    bit_identical = identical(bat)
    pallas_identical = identical(pal)
    entry = {
        "written_at": time.strftime("%Y-%m-%d %H:%M:%S"),
        "bench": "timeline_batched",
        "backend": jax.default_backend(),
        "mode": pallas_mode,
        "quick": quick,
        "n_sims": len(specs),
        "n_accesses": int(n_acc),
        "t_looped_s": round(t_loop, 3),
        "t_batched_s": round(t_bat, 3),
        "t_pallas_s": round(t_pal, 3),
        "speedup": round(t_loop / t_bat, 2),
        "bit_identical": bit_identical and pallas_identical,
        "reps": ENGINE_REPS,
        "spread_frac": {"t_looped_s": round(m_loop.spread_frac, 3),
                        "t_batched_s": round(m_bat.spread_frac, 3),
                        "t_pallas_s": round(m_pal.spread_frac, 3)},
    }
    print_csv(
        f"Batched timeline engine ({len(specs)} sims x {n_acc} accesses)",
        ["backend", "seconds", "vs_looped"],
        [["looped reference (per-sim scans)", t_loop, 1.0],
         ["sweep_timeline (batched scan)", t_bat, t_loop / t_bat],
         [f"sweep_timeline ({pallas_mode})", t_pal, t_loop / t_pal]],
    )
    print(f"  batched scan bit-identical to looped oracle: {bit_identical}")
    print(f"  batched {pallas_mode} bit-identical to looped oracle: {pallas_identical}")
    # Assert BEFORE recording (see _sweep_bench).
    assert bit_identical, "sweep_timeline diverged from the per-sim oracle"
    assert pallas_identical, "batched timeline kernel diverged from the per-sim oracle"
    _append_bench_entry(entry)


def _system_batched_bench(quick: bool):
    """fig10-scale joint system sweep: the looped per-config reference (one
    ``simulate_system`` scan per design point) vs ``sweep_system``'s single
    batched scan vs the batched 3-structure Pallas kernel
    (``repro.kernels.system_sim``), appended to BENCH_sweep.json.

    The config matrix is the fig10 design grid (4K/2M pages x partition
    counts x cache/accel-TLB presence — a heterogeneous 9-point batch, every
    envelope-padding axis exercised).  On this CPU container the Pallas path
    runs under the interpreter (the ``mode`` field records which); all three
    paths must stay bit-identical per config.
    """
    from repro.core import traces
    from repro.core.sparta import TLBConfig
    from repro.core.sweep import sweep_system
    from repro.core.tlbsim import SystemSimConfig, simulate_system

    n_acc = 10_000 if quick else 60_000
    tr = traces.generate("bst_external", n_ops=2 * n_acc // 5, max_accesses=n_acc)
    cache = TLBConfig(entries=256, ways=4)
    accel = TLBConfig(entries=128, ways=4)
    mem = TLBConfig(entries=128, ways=4)
    cfgs = [
        SystemSimConfig(cache=cache, accel_tlb=accel, mem_tlb=mem,
                        num_partitions=1, page_shift=12),
        SystemSimConfig(cache=cache, accel_tlb=accel, mem_tlb=mem,
                        num_partitions=1, page_shift=21),
        SystemSimConfig(cache=cache, accel_tlb=None, mem_tlb=mem,
                        num_partitions=8, page_shift=12),
        SystemSimConfig(cache=cache, accel_tlb=None, mem_tlb=mem,
                        num_partitions=8, page_shift=21),
        SystemSimConfig(cache=cache, accel_tlb=None, mem_tlb=mem,
                        num_partitions=32, page_shift=12),
        SystemSimConfig(cache=cache, accel_tlb=None, mem_tlb=mem,
                        num_partitions=32, page_shift=21),
        SystemSimConfig(cache=cache, accel_tlb=None, mem_tlb=mem,
                        num_partitions=128, page_shift=21),
        SystemSimConfig(cache=None, accel_tlb=None, mem_tlb=mem,
                        num_partitions=32, page_shift=12),
        SystemSimConfig(cache=cache, accel_tlb=TLBConfig(entries=8, ways=4),
                        mem_tlb=mem, num_partitions=8, page_shift=12,
                        accel_probe_on_miss_only=False),
    ]

    def timed(fn, label):
        m = measure(fn, reps=ENGINE_REPS, label=label)
        return m, m.result

    pallas_mode = _pallas_mode()
    m_loop, ref = timed(lambda: [simulate_system(tr.lines, c) for c in cfgs],
                        "system_batched:looped")
    m_bat, bat = timed(lambda: sweep_system(tr.lines, cfgs, kernel_mode="reference"),
                       "system_batched:reference")
    m_pal, pal = timed(lambda: sweep_system(tr.lines, cfgs, kernel_mode=pallas_mode),
                       f"system_batched:{pallas_mode}")
    t_loop, t_bat, t_pal = m_loop.best_s, m_bat.best_s, m_pal.best_s

    def identical(bev):
        return bool(all(
            np.array_equal(getattr(bev, k)[i], getattr(ev, k))
            for i, ev in enumerate(ref)
            for k in ("cache_hit", "accel_tlb_hit", "mem_tlb_hit")))

    bit_identical = identical(bat)
    pallas_identical = identical(pal)
    entry = {
        "written_at": time.strftime("%Y-%m-%d %H:%M:%S"),
        "bench": "system_batched",
        "backend": jax.default_backend(),
        "mode": pallas_mode,
        "quick": quick,
        "n_configs": len(cfgs),
        "n_accesses": int(tr.num_accesses),
        "t_looped_s": round(t_loop, 3),
        "t_batched_s": round(t_bat, 3),
        "t_pallas_s": round(t_pal, 3),
        "speedup": round(t_loop / t_bat, 2),
        "bit_identical": bit_identical and pallas_identical,
        "reps": ENGINE_REPS,
        "spread_frac": {"t_looped_s": round(m_loop.spread_frac, 3),
                        "t_batched_s": round(m_bat.spread_frac, 3),
                        "t_pallas_s": round(m_pal.spread_frac, 3)},
    }
    print_csv(
        f"Batched system sweep ({len(cfgs)} configs x {tr.num_accesses} accesses)",
        ["backend", "seconds", "vs_looped"],
        [["looped reference (per-config scans)", t_loop, 1.0],
         ["sweep_system (batched scan)", t_bat, t_loop / t_bat],
         [f"sweep_system ({pallas_mode})", t_pal, t_loop / t_pal]],
    )
    print(f"  batched scan bit-identical to looped oracle: {bit_identical}")
    print(f"  batched {pallas_mode} bit-identical to looped oracle: {pallas_identical}")
    # Assert BEFORE recording (see _sweep_bench).
    assert bit_identical, "sweep_system diverged from the per-config oracle"
    assert pallas_identical, "batched system kernel diverged from the per-config oracle"
    _append_bench_entry(entry)


# Every engine the bench suite gates: ``--check`` fails when a bench has no
# recorded row at all, so a silently-skipped engine (e.g. the system_batched
# row added with the 3-structure kernel) cannot pass CI unverified.
REQUIRED_BENCHES = ("sweep", "timeline", "timeline_batched", "system_batched")


def check_bench_history(path: pathlib.Path = BENCH_SWEEP_PATH,
                        refs_path: pathlib.Path = None) -> dict:
    """The CI perf gate over the recorded BENCH_sweep.json history.

    Fails on (1) a corrupt/unparseable history file, (2) any recorded row
    reporting a bit-identity violation — a perf number from a diverging
    backend is not a result — (3) a required bench with no recorded row,
    and (4) any recorded wall time outside its references.json tolerance
    band (the ReFrame-style regression gate, ``benchmarks/perfcheck.py``).
    Returns the perfcheck machine-readable summary (``{}`` when no history
    file exists yet).
    """
    from benchmarks import perfcheck

    if not path.exists():
        return {}
    hist = perfcheck.load_history(path).get("history", [])
    bad = [
        (i, e) for i, e in enumerate(hist)
        if any(k.endswith("bit_identical") and e[k] is False for k in e)
    ]
    if bad:
        lines = "\n".join(
            f"  history[{i}]: bench={e.get('bench', 'sweep')!r} "
            f"written_at={e.get('written_at')!r}" for i, e in bad)
        raise SystemExit(
            f"BENCH_sweep.json records {len(bad)} non-bit-identical row(s):\n{lines}")
    seen = {e.get("bench", "sweep") for e in hist}
    missing = [b for b in REQUIRED_BENCHES if b not in seen]
    if missing:
        raise SystemExit(
            f"BENCH_sweep.json has no recorded row for bench(es) {missing}; "
            f"run `python -m benchmarks.kernel_bench` so every engine's "
            f"bit_identical field is on record")
    print(f"  BENCH_sweep.json: all {len(hist)} recorded rows bit-identical "
          f"({', '.join(REQUIRED_BENCHES)} covered)")
    return perfcheck.check_perf_history(
        path, refs_path or perfcheck.REFS_PATH, history=hist)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the engine benches "
                         "into DIR (one StepTraceAnnotation per bench)")
    ap.add_argument("--check", action="store_true",
                    help="verify BENCH_sweep.json: bit-identity, required-"
                         "bench coverage, and the references.json "
                         "tolerance-band perf gate")
    ap.add_argument("--update-refs", action="store_true",
                    help="re-baseline benchmarks/references.json from the "
                         "latest recorded row per (bench, backend, mode, "
                         "quick) key, then run the gate")
    args = ap.parse_args()
    if args.update_refs:
        from benchmarks import perfcheck

        hist = perfcheck.load_history(BENCH_SWEEP_PATH).get("history", [])
        perfcheck.update_references(hist)
        check_bench_history()
    elif args.check:
        check_bench_history()
    else:
        from repro.runtime.compile_cache import configure_compile_cache

        configure_compile_cache()
        run(quick=args.quick, profile_dir=args.profile)
