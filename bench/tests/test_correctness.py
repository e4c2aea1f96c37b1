"""The comparison that decides ``correct`` can fail: the control (the
reference in a lower precision) fails it, and so does every fault a cell can
have, planted in the timed path underneath a whole run.  One chip exchanges
nothing, so the fault "exchange between chips left out" does not apply."""
from __future__ import annotations

import importlib

import numpy as np
import pytest

from bench import registry
from bench import run as bench_run
from bench.traffic import generators


def _cell(workload, seed=2**31 + 99):
    bm = registry.load_benchmark()
    cell = registry.workload(bm, workload)
    config = registry.config(bm, cell["config"])
    kind = registry.job_kind(config["job"])
    return config, kind, generators.job_traces(registry.traffic(cell["traffic"]), seed)


def test_control_fails_the_system_figure():
    # One job at the short cell's own size (60k accesses), judged by the
    # run's own comparison: the reference passes it, 16-bit tags alias.
    config, kind, jobs = _cell("fig10-short-traces")
    jobs = jobs[:1]
    ref = kind.reference(config, jobs[0])
    checks, over = bench_run.check(kind, config, jobs, [(0, 0, ref)])
    assert not over and all(c["value"] <= c["limit"] for c in checks.values())
    checks, over = bench_run.check(kind, config, jobs, [(0, 0, kind.control(config, jobs[0]))])
    assert over == {0}
    assert checks["hit_bits_differing"]["value"] > checks["hit_bits_differing"]["limit"]


def _keep_state(orig):
    def carry(*args, **kw):
        ys, _ = orig(*args, **kw)
        return ys, tuple(args[7])
    return carry


def _half_batch(orig):
    def sweep(lines, cfgs, **kw):
        half = max(1, len(cfgs) // 2)
        ev, meta = orig(lines, list(cfgs[:half]), **kw)
        rows = [i % half for i in range(len(cfgs))]
        return type(ev)(ev.cache_hit[rows], ev.accel_tlb_hit[rows], ev.mem_tlb_hit[rows],
                        n_warm=ev.n_warm), meta
    return sweep


def _flip_system_bit(orig):
    def run_chunk(self, lines, **kw):
        c, a, m = orig(self, lines, **kw)
        c = c.copy()
        c[0, 0] = ~c[0, 0]
        return c, a, m
    return run_chunk



# fault -> (cell, module, class or None, attribute, wrapper)
FAULTS = {
    "system-state-unchanged": ("fig10-short-traces", "repro.kernels.system_sim", None,
                               "system_sim_batched_carry", _keep_state),
    "system-half-batch": ("fig10-short-traces", "repro.core.orchestrator", None,
                          "run_sweep_system", _half_batch),
    "system-answer-altered": ("fig10-short-traces", "repro.core.sweep", "SystemSweepStream",
                              "run_chunk", _flip_system_bit),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_makes_the_run_incorrect(drive, monkeypatch, fault):
    workload, module, cls, attr, wrap = FAULTS[fault]
    target = importlib.import_module(module)
    if cls:
        target = getattr(target, cls)
    monkeypatch.setattr(target, attr, wrap(getattr(target, attr)))
    rc, lines, err, last = drive(workload, seconds=0.1, chunk=2048)
    assert rc == 0
    assert last["correct"] is False and last["failed"] >= 1, last["checks"]
