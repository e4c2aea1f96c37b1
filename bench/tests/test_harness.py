"""A whole run, driven on the CPU with the look for a chip skipped: the
result line's keys, the traced run, the refusal without a TPU, and the
warm-up's shapes."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
from conftest import REPO

from bench import registry
from bench import run as bench_run

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_result_line_has_the_contract_keys(drive):
    rc, lines, err, last = drive("fig10-short-traces")
    assert rc == 0
    assert list(last)[:5] == RESULT_KEYS and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    bm = registry.load_benchmark()
    assert set(last["metrics"]) == {m["name"] for m in bm["end_to_end"]}
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])
    checks = last["checks"]
    assert checks["hit_bits_differing"] == {"value": 0, "limit": 0}
    tail = err.strip().splitlines()[-len(checks):]
    assert [t.split(":")[0] for t in tail] == [f"check {n}" for n in checks]
    window = next(json.loads(x)["window"] for x in lines if x.startswith('{"window"'))
    assert window["compiles"] == {"lowerings": 0, "backend_compiles": 0}
    assert window["ladder_events"] == 0


def test_traced_run_reports_per_layer_metrics(drive):
    rc, lines, err, last = drive("fig10-short-traces", trace=1, seconds=0.1)
    assert rc == 0 and last["correct"] is True
    bm = registry.load_benchmark()
    allowed = {m["name"] for m in registry.metrics_for(bm, "fig10-short-traces", "per_layer")}
    assert set(last["metrics"]) <= allowed and "job_host_pct" in last["metrics"]
    assert {"busy_s", "window_s"} <= set(last["device"])
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(last)[-1] == "checks"
    assert set(last["checks"]) >= {"hit_bits_differing", "figure_rel_gap"}


def test_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "fig10-short-traces",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_warmup_keeps_every_shape_of_the_window():
    chunk = 65536
    jobs = [[{"lines": np.zeros(n, np.int64)} for n in (400000, 400000, 300000, 400000)],
            [{"lines": np.zeros(800000, np.int64)}], [{"lines": np.zeros(800000, np.int64)}],
            [{"lines": np.zeros(60000, np.int64)}]]
    warm = bench_run.warmup_jobs(jobs, chunk)
    assert len(warm) == 3
    for w, job in zip(warm, (jobs[0], jobs[1], jobs[3])):
        got = [tr["lines"].shape[0] for tr in w]
        want = [tr["lines"].shape[0] for tr in job]
        assert [g % chunk for g in got] == [n % chunk for n in want]
        assert all(g >= min(n, chunk) for g, n in zip(got, want))
        assert max(got) % chunk == max(want) % chunk
