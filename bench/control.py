"""Readings of a cell's control: the plain reference in the next precision
down, put in the program's place, and judged by the same comparison that
decides a run's ``correct`` (``bench.run.check``).

    python3 bench/control.py --workload <cell> --seeds 1,2,3

Prints one JSON line per seed with each compared number beside its limit.
These are the upper readings the limits are set from (PERF.md); the
benchmark's own runs never run the control.  The references and the control
run on JAX's CPU backend, so the chip stays free.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import registry, run  # noqa: E402
from bench.traffic import generators  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    bm = registry.load_benchmark()
    cell = registry.workload(bm, args.workload)
    config = registry.config(bm, cell["config"])
    kind = registry.job_kind(config["job"])
    traffic = registry.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        jobs = generators.job_traces(traffic, seed)
        results = [(j, j, kind.control(config, job)) for j, job in enumerate(jobs)]
        checks, over = run.check(kind, config, jobs, results)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0,
                          "jobs_over": len(over), "control": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
