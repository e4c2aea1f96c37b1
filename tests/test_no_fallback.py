"""No fallback hides the device.

On a TPU backend nothing may stand in for the chip unseen:

* a compile or lowering error (Mosaic, the XLA compiler, ValueError,
  NotImplementedError, INVALID_ARGUMENT) is never transient, so the
  orchestrator neither retries, halves nor downgrades it;
* the interpreter is no rung of the degradation ladder, never what
  ``"auto"`` resolves to and no explicit mode there;
* the scheduler's process executor refuses to start, since the parent
  already holds the chip.

These run on CPU with ``jax.default_backend`` steered to ``"tpu"`` (the
backend-rule tests' seam); the interpret-mode behaviour on CPU stays as it
was.
"""
import multiprocessing

import numpy as np
import pytest
from _faultinject import transient_faults

import jax
import repro.kernels.common as kc
from repro.core import scheduler
from repro.core.orchestrator import SweepRunConfig, ladder_from, run_sweep_tlb
from repro.core.sparta import TLBConfig
from repro.core.sweep import TLBSweepSpec, resolve_tlb_mode, sweep_tlb
from repro.kernels.common import resolve_mode
from repro.kernels.system_sim import resolve_system_mode
from repro.kernels.timeline import resolve_timeline_mode
from repro.runtime.fault_tolerance import PreemptionHandler, is_transient

BLOCK = 128


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(kc.jax, "default_backend", lambda: "tpu")


def _inputs():
    rng = np.random.default_rng(3)
    addrs = rng.integers(0, 4096, 4 * BLOCK).astype(np.int64)
    specs = [TLBSweepSpec(TLBConfig(entries=64, ways=w), num_partitions=p)
             for w in (4, 32) for p in (1, 4)]
    return addrs, specs


def _run_cfg(**kw):
    return SweepRunConfig(backoff_base_s=0.0, backoff_cap_s=0.0,
                          chunk_accesses=2 * BLOCK,
                          preemption=PreemptionHandler(install=False), **kw)


_COMPILE_ERRORS = [
    jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unsupported shape"),
    jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space smem."),
    jax.errors.JaxRuntimeError("INVALID_ARGUMENT: bad operand layout"),
    ValueError("The Pallas TPU lowering currently requires that the last "
               "two dimensions of your block shape are divisible by 8 and 128"),
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering"),
]


@pytest.mark.parametrize("exc", _COMPILE_ERRORS,
                         ids=["mosaic", "smem", "invalid", "block", "unimpl"])
def test_compile_errors_are_never_transient(exc):
    assert not is_transient(exc)


@pytest.mark.parametrize("exc", _COMPILE_ERRORS[:2], ids=["mosaic", "smem"])
def test_compile_error_is_not_retried_or_downgraded(on_tpu, exc):
    """The first attempt's compile error propagates as is: one attempt, no
    ladder event."""
    addrs, specs = _inputs()
    seen = []

    def hook(engine, lo, hi, mode, attempt):
        seen.append((lo, mode, attempt))
        raise exc

    with pytest.raises(type(exc)):
        run_sweep_tlb(addrs, specs, kernel_mode="pallas", block=BLOCK,
                      run=_run_cfg(fault_hook=hook))
    assert seen == [(0, "pallas", 0)]


def test_tpu_ladder_skips_the_interpreter(on_tpu):
    assert ladder_from("pallas") == ("pallas", "reference")
    addrs, specs = _inputs()
    seen = []
    res, meta = run_sweep_tlb(
        addrs, specs, kernel_mode="pallas", block=BLOCK,
        run=_run_cfg(fault_hook=transient_faults(fail_modes=("pallas",),
                                                 log=seen)))
    modes = {s[3] for s in seen}
    assert modes == {"pallas", "reference"}
    downs = [e for e in meta["events"] if e["event"] == "downgrade"]
    assert [e["to_mode"] for e in downs] == ["reference"]
    assert meta["final_mode"] == "reference"
    oracle = sweep_tlb(addrs, specs, kernel_mode="reference", block=BLOCK)
    np.testing.assert_array_equal(res.hits, oracle.hits)


def test_cpu_ladder_keeps_the_interpreter():
    assert jax.default_backend() == "cpu"
    assert ladder_from("pallas") == ("pallas", "pallas_interpret", "reference")
    assert ladder_from("pallas_interpret") == ("pallas_interpret", "reference")


def _resolvers(specs):
    """Each engine's entry point, asked for ``mode`` (timeline at batch 1
    and 8, TLB on a stackdist-ineligible sweep)."""
    return [lambda m: resolve_tlb_mode(m, [s for s in specs
                                           if s.cfg.ways == 32]),
            resolve_system_mode,
            lambda m: resolve_timeline_mode(m, batch=1),
            lambda m: resolve_timeline_mode(m, batch=8),
            resolve_mode]


def test_no_interpret_candidate_or_mode_on_tpu(on_tpu):
    _, specs = _inputs()
    for resolve in _resolvers(specs):
        assert resolve("auto") in ("pallas", "reference")
        with pytest.raises(ValueError, match="pallas_interpret"):
            resolve("pallas_interpret")
    assert resolve_system_mode("auto") == "pallas"


def test_interpret_is_the_cpu_candidate():
    _, specs = _inputs()
    for resolve in _resolvers(specs):
        assert resolve("auto") == "reference"
        assert resolve("pallas_interpret") == "pallas_interpret"


def test_process_executor_refuses_a_tpu_backend(on_tpu):
    addrs, specs = _inputs()
    sched = scheduler.ScheduleConfig(workers=2, executor="process")
    with pytest.raises(ValueError, match="holds the chip"):
        scheduler.run_sweep_tlb(addrs, specs, kernel_mode="reference",
                                block=BLOCK, run=_run_cfg(), sched=sched)
    assert multiprocessing.active_children() == []
