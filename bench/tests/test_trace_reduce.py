"""The trace reduction: its interval arithmetic on events laid out by hand,
and ``read`` on a trace that the profiler records here around the
benchmark's own spans."""
from __future__ import annotations

import pathlib

import pytest

from bench import kernels, trace_reduce
from bench.trace_reduce import Op, Trace


def _trace():
    """Window [0, 100) ns on device 0: ops at [10, 30) and [20, 40) overlap,
    [60, 70) stands alone, [95, 120) was clipped to the window by ``read``."""
    ops = [Op("fusion", 10, 30, 0), Op("system_kernel", 20, 40, 0),
           Op("copy", 60, 70, 0), Op("fusion", 95, 100, 0)]
    modules = [Op("jit_system_sim_batched_pallas_carry", 10, 40, 0),
               Op("jit_convert", 60, 70, 0)]
    spans = [("bench.window", 0, 100), ("bench.job", 0, 90),
             ("bench.engine:system_sim", 5, 75), ("bench.figure", 80, 90)]
    return Trace((0, 100), [0], ops, modules, spans)


def test_busy_time_is_the_union_of_device_ops():
    t = _trace()
    assert t.busy(0).tolist() == [[10, 40], [60, 70], [95, 100]]
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.window_s == pytest.approx(100e-9)
    # Engine span [5, 75) holds [10, 40) and [60, 70) of busy time.
    assert t.busy_within(t.spans_named("bench.engine:")) == pytest.approx(40e-9)


def test_idle_gaps_are_named_by_the_innermost_span():
    gaps = dict(_trace().idle_gaps())
    # Gaps [0, 10) and [40, 60) have their midpoints in the engine span (the
    # job span holds it), [70, 95) has its midpoint 82 in the figure span.
    assert gaps == pytest.approx({"engine:system_sim": 30e-9, "figure": 25e-9})
    assert sum(gaps.values()) == pytest.approx(100e-9 - 45e-9)


def test_program_seconds_and_top_ops():
    t = _trace()
    secs, n = t.module_seconds(kernels.PROGRAMS["system_sim"])
    assert n == 1 and secs == pytest.approx(30e-9)
    assert t.module_seconds("timeline_sim") == (0, 0)
    top = t.top_ops()
    assert top[0] == ["fusion", pytest.approx(25e-9)] and len(top) == 3


def test_read_takes_the_window_and_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.engine:system_sim"):
            jax.block_until_ready(jnp.arange(1024.0).sum())
    jax.profiler.stop_trace()
    (pb,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    t = trace_reduce.read(str(pb))
    names = [n for n, _, _ in t.spans]
    assert names.count("bench.window") == 1 and "bench.engine:system_sim" in names
    (w,) = t.spans_named("bench.window")
    assert t.window == w and t.window_s > 0
    (e,) = t.spans_named("bench.engine:")
    assert w[0] <= e[0] < e[1] <= w[1]
    # The CPU backend has no TPU plane: no device, nothing busy.
    assert t.devices == [] and t.busy_s() == 0.0
