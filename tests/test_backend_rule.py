"""The one backend rule for ``kernel_mode="auto"`` (repro.kernels.common).

The contract under test:

* **One static rule**, row by row: stackdist for an eligible TLB sweep,
  the scan for a single-sim timeline batch, "pallas on TPU else reference"
  everywhere else — whatever the engine entry point that asks.
* **Explicit modes are honoured as given**; sweep-only modes still raise
  for the engines that cannot run them.
* **The record the benchmark reads**: every orchestrated run's
  ``meta["dispatch"]`` is a plain dict whose ``reason`` names the rule.
* **Resume stickiness**: the checkpoint's mode outlives the resuming call's
  ``kernel_mode`` — kill, resume with another mode, and the run completes
  bit-identically on the original backend.
* **Layering**: nothing under ``repro.kernels`` imports ``repro.core``.
"""
import ast
import pathlib

import numpy as np
import pytest
from _faultinject import SimulatedKill, kill_after

import repro.kernels.common as kc
from repro.core.orchestrator import SweepRunConfig, run_sweep_tlb
from repro.core.sparta import TLBConfig
from repro.core.sweep import TLBSweepSpec, resolve_tlb_mode, sweep_tlb
from repro.kernels.common import resolve_mode
from repro.kernels.system_sim import resolve_system_mode
from repro.kernels.timeline import resolve_timeline_mode
from repro.runtime.fault_tolerance import PreemptionHandler

BLOCK = 128
KERNELS = pathlib.Path(kc.__file__).resolve().parent


def _specs(ways):
    return [TLBSweepSpec(TLBConfig(entries=64, ways=ways), num_partitions=p)
            for p in (1, 8)]


# ---------------------------------------------------------------------------
# The rule, one case per row and backend.
# ---------------------------------------------------------------------------

_ROWS = {
    # TLB: eligible pure-LRU sweep -> stackdist on every backend.
    "tlb-eligible": lambda: resolve_tlb_mode("auto", _specs(4)),
    # TLB: ways > AUTO_MAX_WAYS -> ineligible -> the generic rule.
    "tlb-32-ways": lambda: resolve_tlb_mode("auto", _specs(32)),
    "system": lambda: resolve_system_mode("auto"),
    # Timeline: a single sim -> the scan everywhere; a real batch -> generic.
    "timeline-batch-1": lambda: resolve_timeline_mode("auto", batch=1),
    "timeline-batch-8": lambda: resolve_timeline_mode("auto", batch=8),
    # A bare per-op call: the generic rule.
    "per-op": lambda: resolve_mode("auto"),
}

_EXPECTED = {
    "cpu": {"tlb-eligible": "stackdist", "tlb-32-ways": "reference",
            "system": "reference", "timeline-batch-1": "reference",
            "timeline-batch-8": "reference", "per-op": "reference"},
    "tpu": {"tlb-eligible": "stackdist", "tlb-32-ways": "pallas",
            "system": "pallas", "timeline-batch-1": "reference",
            "timeline-batch-8": "pallas", "per-op": "pallas"},
}


@pytest.mark.parametrize("backend", sorted(_EXPECTED))
@pytest.mark.parametrize("row", list(_ROWS))
def test_auto_rule(monkeypatch, backend, row):
    monkeypatch.setattr(kc.jax, "default_backend", lambda: backend)
    assert _ROWS[row]() == _EXPECTED[backend][row]


def test_explicit_mode_is_honoured_verbatim():
    assert resolve_timeline_mode("pallas_interpret", batch=8) == \
        "pallas_interpret"
    assert resolve_timeline_mode("pallas", batch=1) == "pallas"
    assert resolve_tlb_mode("stackdist", _specs(4)) == "stackdist"
    assert resolve_tlb_mode("reference", _specs(4)) == "reference"
    assert resolve_system_mode("pallas_interpret") == "pallas_interpret"


def test_sweep_only_modes_still_raise_for_other_engines():
    with pytest.raises(ValueError, match="timeline"):
        resolve_timeline_mode("stackdist", batch=8)
    with pytest.raises(ValueError, match="stack"):
        resolve_system_mode("stackdist")
    with pytest.raises(ValueError, match="bogus"):
        resolve_tlb_mode("bogus", _specs(4))
    with pytest.raises(ValueError, match="stackdist"):
        resolve_mode("stackdist")


# ---------------------------------------------------------------------------
# The record in the run meta, and resume stickiness.
# ---------------------------------------------------------------------------

def test_run_meta_carries_decision_cold_and_explicit(tmp_path):
    rng = np.random.default_rng(9)
    addrs = rng.integers(0, 1 << 22, 1024).astype(np.int64)
    run = SweepRunConfig(preemption=PreemptionHandler(install=False))
    # Explicit mode: stamped as such.
    _, meta = run_sweep_tlb(addrs, _specs(32), kernel_mode="reference",
                            block=BLOCK, run=run, name="t")
    assert meta["dispatch"] == {
        "engine": "sweep_tlb", "requested": "reference", "mode": "reference",
        "reason": "kernel_mode='reference' given explicitly"}
    # Auto on the chunked stream path names the rule.
    _, meta = run_sweep_tlb(addrs, _specs(32), kernel_mode="auto",
                            block=BLOCK, run=run, name="t")
    assert meta["dispatch"]["mode"] == meta["final_mode"] == "reference"
    assert meta["dispatch"]["reason"] == "auto: reference on a cpu backend"
    # Auto on the monolithic stackdist path stamps too.
    _, meta = run_sweep_tlb(addrs, _specs(4), kernel_mode="auto",
                            block=BLOCK, run=run, name="t")
    assert meta["dispatch"]["mode"] == "stackdist"
    assert meta["dispatch"]["requested"] == "auto"
    assert meta["dispatch"]["reason"].startswith("auto: stackdist")
    assert all(isinstance(v, str) for v in meta["dispatch"].values())
    assert meta["final_mode"] == "stackdist" and "throughput" in meta


def test_resume_sticks_to_checkpointed_decision(tmp_path):
    rng = np.random.default_rng(7)
    addrs = rng.integers(0, 1 << 22, 4096).astype(np.int64)
    specs = _specs(32)  # stackdist-ineligible -> the chunked stream path
    oracle = sweep_tlb(addrs, specs, kernel_mode="reference", block=BLOCK).hits
    modes = []

    def cfg(**kw):
        return SweepRunConfig(
            checkpoint_dir=str(tmp_path / "ckpt"), chunk_accesses=1024,
            backoff_base_s=0.0, backoff_cap_s=0.0,
            preemption=PreemptionHandler(install=False),
            fault_hook=lambda engine, lo, hi, mode, attempt: modes.append(mode),
            **kw)

    with pytest.raises(SimulatedKill):
        run_sweep_tlb(addrs, specs, kernel_mode="reference", block=BLOCK,
                      run=cfg(on_chunk_committed=kill_after(2)), name="tlb")
    assert modes == ["reference", "reference"]

    # Resume with another explicit mode: the checkpoint's mode wins — same
    # backend, no interpreter chunk, bit-identical output.
    res, meta = run_sweep_tlb(addrs, specs, kernel_mode="pallas_interpret",
                              block=BLOCK, run=cfg(resume=True), name="tlb")
    assert modes == ["reference"] * 4
    assert meta["resumed_from"] == 2048
    assert meta["final_mode"] == meta["start_mode"] == "reference"
    assert meta["dispatch"]["mode"] == "reference"
    assert "resumed from checkpoint" in meta["dispatch"]["reason"]
    np.testing.assert_array_equal(res.hits, oracle)


# ---------------------------------------------------------------------------
# Layering: the kernels layer imports nothing from the core.
# ---------------------------------------------------------------------------

def _kernel_units():
    return sorted(p.stem if p.is_file() else p.name
                  for p in KERNELS.iterdir()
                  if (p.suffix == ".py" and p.name != "__init__.py")
                  or (p.is_dir() and (p / "__init__.py").exists()))


def _core_imports(path: pathlib.Path):
    for node in ast.walk(ast.parse(path.read_text(), filename=str(path))):
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
            if node.module == "repro":
                names += [f"repro.{a.name}" for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        for name in names:
            if name == "repro.core" or name.startswith("repro.core."):
                yield f"{path.name}:{node.lineno}: {name}"


@pytest.mark.parametrize("unit", _kernel_units())
def test_kernels_import_nothing_from_core(unit):
    root = KERNELS / unit
    files = sorted(root.rglob("*.py")) if root.is_dir() else [
        root.with_suffix(".py")]
    assert files
    found = [hit for f in files for hit in _core_imports(f)]
    assert found == [], f"repro.kernels.{unit} imports repro.core: {found}"
