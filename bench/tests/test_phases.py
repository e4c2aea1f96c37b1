"""The reduction of a trace to the program's phases (``bench/phases.py``):
its arithmetic on events laid out by hand, and ``read`` on a trace that the
profiler records here around one engine call of the program."""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from bench import phases, registry
from bench.run import MetricContext
from bench.trace_reduce import Op, Trace

# Window [0, 1000) ns, device 0 busy in [100, 300) and [600, 800): one
# engine call of two chunks, a compilation inside the second launch.
PROGRAM = [
    ("repro.engine", 50, 950), ("repro.engine.prepare", 50, 90),
    ("repro.chunk", 90, 400), ("repro.chunk.keys", 90, 95),
    ("repro.chunk.upload", 95, 98), ("repro.chunk.launch", 98, 100),
    ("repro.chunk.pull", 100, 390), ("repro.chunk.commit", 400, 420),
    ("repro.chunk", 420, 850), ("repro.chunk.keys", 420, 500),
    ("repro.chunk.upload", 500, 550), ("repro.chunk.launch", 550, 600),
    ("repro.chunk.pull", 600, 840), ("repro.chunk.commit", 850, 870),
    ("repro.engine.finish", 870, 940),
]
BENCH = [("bench.window", 0, 1000), ("bench.job", 0, 1000),
         ("bench.engine:system_sim", 40, 960), ("bench.figure", 960, 990)]


def _phases(program=PROGRAM):
    ops = [Op("system_sim_carry.1", 100, 300, 0),
           Op("system_sim_carry.1", 600, 800, 0)]
    modules = [Op("jit_system_sim_batched_pallas_carry", 100, 300, 0),
               Op("jit_system_sim_batched_pallas_carry", 600, 800, 0)]
    trace = Trace((0, 1000), [0], ops, modules, list(BENCH))
    return phases.Phases(trace, list(program),
                         [("lower_sharding_computation", 555, 580)])


def test_idle_time_is_split_among_the_phases_it_runs_through():
    got = phases.idle_by_phase(_phases())
    want = {"job": 50, "engine:system_sim": 20, "engine.prepare": 40,
            "chunk.keys": 85, "chunk.upload": 53, "chunk.launch": 27,
            "chunk.pull": 130, "chunk": 20, "chunk.commit": 40,
            "compile": 25, "engine.finish": 70, "engine": 10, "figure": 30}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(600e-9)   # all idle time


def test_engine_idle_and_the_share_named_by_child_phases():
    eng = phases.engine_idle(_phases())
    assert "job" not in eng and "figure" not in eng
    assert sum(eng.values()) == pytest.approx(520e-9)
    # Everything but the engine span's own 10 ns and the benchmark's 20.
    assert phases.child_phase_share(eng) == pytest.approx(490 / 520)


def test_fixed_time_per_call_leaves_out_the_per_chunk_spans():
    # 900 ns of engine call, 310 + 430 ns of chunks, 2 x 20 ns of commits.
    assert phases.fixed_ms_per_call(_phases()) == pytest.approx(120e-6)


def test_chunk_device_idle_is_the_chunks_share_without_a_device_op():
    # 740 ns of chunks hold 400 ns of device time.
    assert phases.chunk_device_idle_pct(_phases()) == pytest.approx(
        100 * 340 / 740)


def test_compiles_in_window_counts_lowerings():
    ph = _phases()
    assert phases.compiles_in_window(ph) == 1
    ph.compiles += [("backend_compile_and_load", 580, 590),
                    ("lower_sharding_computation", 1200, 1300)]
    assert phases.compiles_in_window(ph) == 1   # one inside the window


def test_a_trace_without_program_spans_reads_nothing():
    ph = _phases(program=[])
    assert phases.fixed_ms_per_call(ph) is None
    assert phases.chunk_device_idle_pct(ph) is None
    assert "chunk.pull" not in phases.idle_by_phase(ph)


def test_the_accepted_metrics_do_not_read_the_program_spans():
    """The five accepted per-layer metrics read the same values whether or
    not the trace's spans hold the program's."""
    calls = [{"kernel": "system_sim", "name": "x", "work": 9 * 1000,
              "state_words": 1000, "seconds": 9e-7}]
    bm = registry.load_benchmark()
    names = [m["name"] for m in bm["per_layer"]]

    def values(spans):
        t = _phases().trace
        t.spans = spans
        ctx = MetricContext(t, calls, 1e-6, "TPU v5 lite")
        ctx.peaks = lambda: {"hbm_bytes_per_s": 819e9}
        return {n: registry.metric(n).read(ctx) for n in names}

    plain = values(list(BENCH))
    assert len(plain) == 5 and None not in plain.values()
    assert values(list(BENCH) + PROGRAM) == plain


def test_read_finds_the_program_phases_in_a_recorded_trace(tmp_path):
    import jax

    from bench import trace_reduce
    from repro.core.orchestrator import SweepRunConfig, run_sweep_system
    from repro.core.tlbsim import SystemSimConfig

    lines = np.random.default_rng(5).integers(0, 1 << 26, 2048)

    def sweep():
        run_sweep_system(lines, [SystemSimConfig(num_partitions=8)],
                         kernel_mode="reference", block=128, name="sys",
                         run=SweepRunConfig(chunk_accesses=1024))

    sweep()   # every shape compiled before the window
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.engine:system_sim"):
            sweep()
        jax.block_until_ready(jax.jit(lambda x: x * 5)(np.arange(4)))
    jax.profiler.stop_trace()
    (pb,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    ph = phases.read(str(pb))
    assert len(ph.named("repro.engine")) == 1
    assert len(ph.named("repro.chunk")) == 2
    assert len(ph.named("repro.chunk.pull")) == 2
    assert phases.compiles_in_window(ph) == 1   # the fresh function only
    assert phases.fixed_ms_per_call(ph) > 0
    # The accepted reduction still keeps only the benchmark's spans.
    assert all(n.startswith("bench.") for n, _, _ in trace_reduce.read(str(pb)).spans)
    summary = phases.summary(ph)
    assert summary["engine_calls"] == 1 and summary["chunks"] == 2
