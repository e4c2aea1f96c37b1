"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix; the
configuration names its job kind (``bench/jobs/``).  Set-up makes every
trace from ``--seed``, builds the program's objects and runs each shape the
window will use once (every trace cut to one whole chunk plus its own
tail).  The window then runs jobs back to back, as one architect waiting on
each sweep, until ``--seconds`` have passed; the last job runs to its end.

``--trace 0`` reports the cell's end-to-end metrics: ``sim_accesses_per_s``
(simulated configuration or simulation x access pairs of every engine call
in the window, over the window's host-clock length) and ``setup_s``
(process start to the first timed job).  ``--trace 1`` records a profiler
trace of the window and reports the cell's per-layer metrics
(``bench/metrics/``), the device's busy time and a breakdown.

Once the window has closed and the device's peak memory is read, every
job's output is compared with the plain references (``bench/reference/``);
each compared number is printed beside its limit, last on stderr and under
``checks``, the last key of the result.  The last line of stdout is the
result as one JSON object.  The run refuses any device but a TPU, and fewer
chips than the cell asks for, before doing anything else.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
# The plain references run on the host's CPU backend, beside the chip.
_PLATFORMS = os.environ.get("JAX_PLATFORMS")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import kernels, registry  # noqa: E402
from bench.traffic import generators  # noqa: E402

# Compilations are counted from JAX's own events: a lowering happens on every
# in-process cache miss, a backend compile where the persistent cache missed.
COMPILE_EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
                  "/jax/core/compile/backend_compile_duration": "backend_compiles"}


class ChipError(RuntimeError):
    pass


def require_chips(n: int):
    """The TPU devices of this machine; refuses any other platform or fewer
    than ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise ChipError(f"no TPU: JAX's first device is {devs[0].platform!r} "
                        f"({devs[0].device_kind}); this benchmark runs on TPU chips only")
    if len(devs) < n:
        raise ChipError(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else the fixed ``<checkout>/.jax_cache``; every program is
    cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    def __init__(self):
        import jax

        self.active = False
        self.counts = {v: 0 for v in COMPILE_EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event in COMPILE_EVENTS:
            self.counts[COMPILE_EVENTS[event]] += 1


class Context:
    """What a job kind calls the program through: every engine call is timed
    on the host clock, annotated for the profiler and recorded."""

    def __init__(self):
        self.calls = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield

    def call(self, kernel: str, fn, *args, work: int, state_words: int, **kw):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.engine:{kernel}"):
            res, meta = fn(*args, **kw)
        t1 = time.perf_counter()
        self.calls.append({
            "kernel": kernel, "name": kw.get("name"), "work": int(work),
            "state_words": int(state_words),
            "seconds": t1 - t0, "backend": meta.get("final_mode"),
            "dispatch": (meta.get("dispatch") or {}).get("reason"),
            "events": [e.get("event") for e in meta.get("events") or []]})
        return res, meta


class MetricContext:
    """What a per-layer metric reader sees."""

    def __init__(self, trace, calls, window_s, device_kind):
        self.trace, self.calls, self.window_s = trace, calls, window_s
        self.kernels = kernels
        self._kind = device_kind

    def peaks(self) -> dict:
        return kernels.peaks(self._kind)


def warmup_jobs(jobs, chunk: int):
    """One job per distinct shape: every trace of a job cut by the same whole
    number of chunks, leaving at least one whole chunk plus the trace's own
    tail, so each engine call compiles exactly what the window will use."""
    seen, out = set(), []
    for job in jobs:
        lens = [int(tr["lines"].shape[0]) for tr in job]
        drop = chunk * max(0, (min(lens) - 1) // chunk - 1)
        key = tuple(n - drop for n in lens)
        if key in seen:
            continue
        seen.add(key)
        out.append([dict(tr, lines=tr["lines"][:n]) for tr, n in zip(job, key)])
    return out


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def emit(line: dict):
    print(json.dumps(line), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bm = registry.load_benchmark()
    cell = registry.workload(bm, args.workload)
    try:
        devs = require_chips(int(cell["chips"]))
    except ChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    cache = configure_compile_cache()
    compiles = CompileCounter()
    config = registry.config(bm, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    kind = registry.job_kind(config["job"])
    jobs = generators.job_traces(traffic, args.seed)
    prog = kind.build(config)
    chunk = int(prog["run"].chunk_accesses)
    warm = Context()
    for job in warmup_jobs(jobs, chunk):
        kind.run(prog, job, warm)
    setup_s = time.perf_counter() - T_START
    emit({"setup": {"seconds": setup_s, "compile_cache": cache,
                    "warmup_calls": len(warm.calls),
                    "backends": sorted({c["backend"] for c in warm.calls}),
                    "dispatch": sorted({c["dispatch"] for c in warm.calls})}})

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx = Context()
    results, failed_jobs = [], set()
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles.active = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        k = 0
        while True:
            j = k % len(jobs)
            n_calls = len(ctx.calls)
            t_job = time.perf_counter()
            try:
                with ctx.span("job"):
                    results.append((k, j, kind.run(prog, jobs[j], ctx)))
            except Exception:
                traceback.print_exc()
                failed_jobs.add(k)
            emit({"job": k, "jobs_index": j, "seconds": time.perf_counter() - t_job,
                  "calls": [[c["name"], c["backend"], c["events"]]
                            for c in ctx.calls[n_calls:]]})
            k += 1
            if time.perf_counter() - t0 >= args.seconds:
                break
    t1 = time.perf_counter()
    compiles.active = False
    if trace_dir:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    device = device_info(devs)
    work = sum(c["work"] for c in ctx.calls)
    emit({"window": {"seconds": window_s, "jobs": k, "engine_calls": len(ctx.calls),
                     "sim_accesses": work, "compiles": compiles.counts,
                     "backends": sorted({c["backend"] for c in ctx.calls}),
                     "ladder_events": sum(len(c["events"]) for c in ctx.calls)}})

    out = {"correct": False, "attempted": k, "failed": 0, "metrics": {},
           "device": device}
    if trace_dir:
        from bench import trace_reduce

        pb = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        tr = trace_reduce.read(str(pb[0]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        mctx = MetricContext(tr, ctx.calls, window_s, device["kind"])
        for m in registry.metrics_for(bm, args.workload, "per_layer"):
            v = registry.metric(m["name"]).read(mctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["device"].update(busy_s=tr.busy_s(), window_s=tr.window_s)
        out["breakdown"] = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
    else:
        values = {"sim_accesses_per_s": work / window_s, "setup_s": setup_s}
        for m in registry.metrics_for(bm, args.workload, "end_to_end"):
            out["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    del prog
    t_check = time.perf_counter()
    checks, over = check(kind, config, jobs, results)
    emit({"check": {"seconds": time.perf_counter() - t_check,
                    "jobs": len(results), "distinct": len({j for _, j, _ in results})}})
    checks["jobs_raised"] = {"value": len(failed_jobs), "limit": 0}
    bad_jobs = over | failed_jobs
    out["failed"] = len(bad_jobs)
    out["correct"] = bool(results) and not bad_jobs and all(
        c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    out["checks"] = checks
    emit(out)
    return 0


def check(kind, config, jobs, results):
    """Every job's output against the reference of its traces: the largest
    reading of each compared number over the jobs, beside its limit, and
    the jobs that went over a limit."""
    refs = {}
    worst = {name: 0 for name in kind.LIMITS}
    over = set()
    for k, j, output in results:
        if j not in refs:
            refs[j] = kind.reference(config, jobs[j])
        for n, v in kind.compare(output, refs[j]).items():
            worst[n] = max(worst[n], v)
            if v > kind.LIMITS[n]:
                over.add(k)
    return {n: {"value": v, "limit": kind.LIMITS[n]} for n, v in worst.items()}, over


if __name__ == "__main__":
    sys.exit(main())
