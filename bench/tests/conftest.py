"""CPU self-tests of the benchmark: ``python -m pytest bench/tests``.

A run is driven in-process with the harness's look for a chip skipped, at
tiny sizes: the program's scan backend stands in for the chip's kernels,
which compute the same bits.
"""
from __future__ import annotations

import copy
import dataclasses
import io
import json
import pathlib
import sys
from contextlib import redirect_stderr, redirect_stdout

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)


def shrink(traffic: dict) -> dict:
    """A traffic mix with every trace cut to a few thousand accesses
    (threads keep their count)."""
    t = copy.deepcopy(traffic)
    for job in t["jobs"]:
        for sp in job["traces"]:
            threaded = sp.get("threads", 1) > 1
            sp["n_ops"] = 150 if threaded else 2500
            sp["length"] = 4000 if threaded else 5000
    return t


@pytest.fixture
def drive(monkeypatch):
    """``go(workload, ...)`` runs one cell in-process on the CPU at tiny
    sizes and returns (exit code, stdout lines, stderr, parsed last line)."""
    import jax

    from bench import registry
    from bench import run as bench_run

    monkeypatch.setattr(bench_run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(bench_run, "configure_compile_cache", lambda: "off")
    monkeypatch.setattr(bench_run.kernels, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    traffic = registry.traffic
    monkeypatch.setattr(registry, "traffic", lambda name: shrink(traffic(name)))

    def go(workload, *, seconds=0.5, trace=0, seed=2**31 + 7, chunk=None):
        if chunk:
            small_chunks(monkeypatch, registry, chunk)
        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                                 "--seconds", str(seconds), "--trace", str(trace)])
        lines = out.getvalue().splitlines()
        last = json.loads(lines[-1]) if lines else None
        return rc, lines, err.getvalue(), last

    return go


def small_chunks(monkeypatch, registry, chunk: int):
    """Job kinds built with ``chunk``-access engine chunks, so a tiny trace
    still crosses chunk boundaries."""
    job_kind = registry.job_kind

    def patched(name):
        mod = job_kind(name)
        build = mod.build

        def small_build(config):
            prog = build(config)
            prog["run"] = dataclasses.replace(prog["run"], chunk_accesses=chunk)
            return prog

        monkeypatch.setattr(mod, "build", small_build)
        return mod

    monkeypatch.setattr(registry, "job_kind", patched)
