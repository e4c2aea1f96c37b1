"""Finds everything a cell needs by the names in ``BENCHMARK.json``:

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a job kind: ``bench/jobs/<job>.py``, named by the configuration's ``job``;
* a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the metric's value, or ``None`` where it finds nothing to read.

A new cell, configuration, traffic mix or metric is a new file and a new
entry; no file that is already there needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent


def load_benchmark(repo: pathlib.Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def _entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    return _entry(bm["workloads"], name, "workload")


def config(bm: dict, name: str, repo: pathlib.Path = REPO) -> dict:
    return json.loads((repo / _entry(bm["configs"], name, "config")["file"]).read_text())


def traffic(name: str, bench: pathlib.Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def _module(path: pathlib.Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def job_kind(name: str, bench: pathlib.Path = BENCH) -> ModuleType:
    return _module(bench / "jobs" / f"{name}.py", f"bench_job_{name}")


def metric(name: str, bench: pathlib.Path = BENCH) -> ModuleType:
    return _module(bench / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def metrics_for(bm: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metric entries a cell reports."""
    return [m for m in bm[kind] if cell in m.get("workloads", [cell])]
