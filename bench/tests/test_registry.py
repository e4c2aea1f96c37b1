"""The registry finds every file by its name, a new configuration and traffic
mix dropped into a copy are picked up with no existing file edited, and
``BENCHMARK.json`` keeps to the benchmark's contract."""
from __future__ import annotations

import json
import re
import shutil

from conftest import REPO

from bench import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
            "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_every_name_resolves():
    bm = registry.load_benchmark()
    for c in bm["configs"]:
        cfg = registry.config(bm, c["name"])
        kind = registry.job_kind(cfg["job"])
        for fn in ("build", "run", "reference", "control", "compare"):
            assert callable(getattr(kind, fn)), (cfg["job"], fn)
        assert set(kind.LIMITS)
    for w in bm["workloads"]:
        registry.config(bm, w["config"])
        assert registry.traffic(w["traffic"])["jobs"]
    for m in bm["per_layer"]:
        assert callable(registry.metric(m["name"]).read)


def test_new_cell_files_are_picked_up(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench/configs/sparta-fig10-8socket.json").read_text())
    cfg["name"] = "two-designs"
    cfg["designs"] = cfg["designs"][:2]
    (tmp_path / "bench/configs/two-designs.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/rocksdb-10k.json").write_text(json.dumps(
        {"jobs": [{"traces": [{"workload": "rocksdb", "n_ops": 10000, "length": 80000}]}]}))
    (tmp_path / "bench/metrics/calls_per_job.py").write_text(
        "def read(ctx):\n    return len(ctx.calls)\n")
    bm["configs"].append({"name": "two-designs", "source": "x",
                          "file": "bench/configs/two-designs.json", "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "two-rocksdb", "config": "two-designs",
                            "traffic": "rocksdb-10k", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    for f in ("bench/configs/sparta-fig10-8socket.json", "bench/run.py", "bench/registry.py"):
        assert (tmp_path / f).read_bytes() == (REPO / f).read_bytes()

    got = registry.load_benchmark(tmp_path)
    cell = registry.workload(got, "two-rocksdb")
    cfg2 = registry.config(got, cell["config"], tmp_path)
    assert [d["label"] for d in cfg2["designs"]] == ["conv-4K", "conv-2M"]
    traffic = registry.traffic(cell["traffic"], tmp_path / "bench")
    assert traffic["jobs"][0]["traces"][0]["workload"] == "rocksdb"
    assert registry.job_kind(cfg2["job"], tmp_path / "bench").LIMITS
    assert registry.metric("calls_per_job", tmp_path / "bench").read(
        type("C", (), {"calls": [1, 2]})) == 2


def test_benchmark_json_keeps_the_contract():
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(bm) == KEYS["top"]
    assert bm["command"][0] == "python3" and len(bm["command"]) <= 32
    for p in bm["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/")
    assert all(not w.startswith("/") and ".." not in w for w in bm["command"])
    assert 1 <= bm["run_seconds"] <= 51 and isinstance(bm["run_seconds"], int)
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bm[section]:
            extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
            assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
            assert NAME.match(e["name"]), e["name"]
            names.append((section, e["name"]))
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    cfg_names = {c["name"] for c in bm["configs"]}
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    for c in bm["configs"]:
        assert (REPO / c["file"]).is_file() and c["file"].startswith("bench/")
        assert c["name"] in {w["config"] for w in bm["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in bm["workloads"]:
        assert w["config"] in cfg_names and w["chips"] in (1, 4)
        reported = [m for m in bm["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
        assert reported, w["name"]
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    perf = (REPO / "PERF.md").read_text()
    for layer in {m["layer"] for m in bm["per_layer"]}:
        assert layer in perf, layer
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
