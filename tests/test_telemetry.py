"""Tests for the run-telemetry layer (repro.runtime.telemetry), its threading
through the orchestrator/engines, and the obs_report renderer.

The contract under test:

* **JSONL schema round-trip** — one run produces ``run_start`` / ``event`` /
  ``span`` / ``run_end`` records, every record stamped with wall-clock
  (``ts``) and monotonic (``t_mono``) time, and the ``run_end`` summary
  aggregates spans/events/counters/gauges.
* **Nesting** — spans link ``parent_id`` -> ``span_id``; ``Span.block``
  accumulates device-blocked time.
* **No-op fast path** — with no active run every instrument call returns a
  shared null object, and the total instrument cost of a disabled-tracer
  ``run_sweep_tlb`` stays under 2% of the sweep's own wall time.
* **Orchestrator threading** — ladder events carry timestamps and
  per-attempt elapsed time; chunk spans and per-backend achieved accesses/s
  land in the run log and in ``meta["throughput"]`` (streamed and
  monolithic-stackdist paths both).
* **obs_report** — renders, diffs, tolerates torn tails, and fails on
  banned events (the CI ``--fail-on-event downgrade`` gate).
"""
import json
import logging

import jax
import numpy as np
import pytest

from benchmarks import obs_report
from repro.core import benchtime
from repro.core.orchestrator import SweepRunConfig, run_sweep_tlb
from repro.core.sparta import TLBConfig
from repro.core.sweep import TLBSweepSpec, sweep_tlb
from repro.runtime import telemetry

BLOCK = 128


@pytest.fixture(autouse=True)
def _clean_tracer():
    tr = telemetry.get_tracer()
    if tr.active:
        tr.end_run(error="leaked from a previous test")
    yield
    if tr.active:
        tr.end_run(error="leaked by test")


def _sweep_inputs():
    rng = np.random.default_rng(7)
    addrs = rng.integers(0, 1 << 22, 4096).astype(np.int64)
    specs = [TLBSweepSpec(TLBConfig(entries=64, ways=4), num_partitions=p)
             for p in (1, 8)]
    return addrs, specs


def _read(path):
    return [json.loads(line) for line in path.read_text().splitlines()]


# ------------------------------------------------------------ schema/lifecycle


def test_jsonl_schema_roundtrip(tmp_path):
    path = tmp_path / "run.jsonl"
    with telemetry.run_scope(path, run="t", device={"platform": "cpu"}):
        tr = telemetry.get_tracer()
        with tr.span("phase", k=1):
            tr.event("retry", lo=0, hi=10)
        tr.counter("c").add(3)
        tr.gauge("g").set(2.0)
    recs = _read(path)
    assert [r["kind"] for r in recs] == ["run_start", "event", "span", "run_end"]
    for r in recs:
        assert isinstance(r["ts"], float) and r["ts"] > 1e9
        assert isinstance(r["t_mono"], float)
    start, event, span, end = recs
    assert start["schema_version"] == telemetry.SCHEMA_VERSION
    assert start["run"] == "t" and start["meta"]["device"]["platform"] == "cpu"
    assert event["name"] == "retry" and event["attrs"] == {"lo": 0, "hi": 10}
    assert span["name"] == "phase" and span["dur_s"] >= 0
    assert span["attrs"]["k"] == 1
    s = end["summary"]
    assert s["n_spans"] == 1 and s["events"] == {"retry": 1}
    assert s["counters"]["c"] == {"value": 3, "updates": 1}
    assert s["gauges"]["g"]["value"] == 2.0


def test_run_scope_closes_log_on_error(tmp_path):
    path = tmp_path / "crash.jsonl"
    with pytest.raises(KeyboardInterrupt):
        with telemetry.run_scope(path, run="t"):
            raise KeyboardInterrupt  # BaseException still closes the log
    end = _read(path)[-1]
    assert end["kind"] == "run_end" and "KeyboardInterrupt" in end["error"]
    assert not telemetry.get_tracer().active


def test_span_nesting_parent_ids(tmp_path):
    path = tmp_path / "nest.jsonl"
    with telemetry.run_scope(path, run="t"):
        tr = telemetry.get_tracer()
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                assert inner.parent_id == outer.span_id
            tr.record_span("measured", 0.01)  # also parented to the stack top
    spans = {r["name"]: r for r in _read(path) if r["kind"] == "span"}
    assert spans["outer"]["parent_id"] is None
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["measured"]["parent_id"] == spans["outer"]["span_id"]


def test_span_block_accumulates_blocked_time(tmp_path):
    path = tmp_path / "blk.jsonl"
    x = np.arange(8)
    with telemetry.run_scope(path, run="t"):
        with telemetry.get_tracer().span("s") as sp:
            assert sp.block(x) is x
    rec = [r for r in _read(path) if r["kind"] == "span"][0]
    assert rec["attrs"]["blocked_s"] > 0


def test_counter_and_gauge_aggregation():
    tr = telemetry.get_tracer()
    tr.start_run(None, run="mem")
    c = tr.counter("hits")
    assert tr.counter("hits") is c  # registry, not a new object per call
    c.add().add(5)
    g = tr.gauge("bytes")
    g.set(5).set(3)
    s = tr.end_run()
    assert s["counters"]["hits"] == {"value": 6, "updates": 2}
    assert s["gauges"]["bytes"] == {"value": 3.0, "min": 3.0, "max": 5.0,
                                    "updates": 2}


def test_start_run_supersedes_leaked_run(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    telemetry.start_run(a, run="a")
    telemetry.start_run(b, run="b")   # closes "a" with an error, no raise
    telemetry.end_run()
    assert "superseded" in _read(a)[-1]["error"]
    assert _read(b)[-1]["kind"] == "run_end"


# -------------------------------------------------------------- no-op fast path


def test_disabled_tracer_is_noop():
    tr = telemetry.get_tracer()
    assert not tr.active
    assert tr.span("x", a=1) is telemetry._NULL_SPAN
    assert tr.counter("c") is telemetry._NULL_INSTRUMENT
    assert tr.gauge("g") is telemetry._NULL_INSTRUMENT
    tr.event("e")             # records nothing, raises nothing
    tr.record_span("s", 0.5)
    assert tr.end_run() == {}
    obj = object()
    assert telemetry._NULL_SPAN.block(obj) is obj  # no device sync added
    with tr.span("x") as sp:
        sp.set(k=1).block(obj)


def test_disabled_tracer_overhead_under_2_percent():
    """The <2% guard: the instrument ops one sweep performs, costed at the
    measured disabled-tracer per-op price, must stay under 2% of the sweep's
    own measured wall time.  (Op-counting x micro-cost instead of an A/B
    wall-time diff: a 2% delta drowns in run-to-run noise.)"""
    addrs, specs = _sweep_inputs()
    cfg = SweepRunConfig(chunk_accesses=1024)
    tr = telemetry.get_tracer()

    # Probe run (in-memory) counts the ops an instrumented sweep performs.
    tr.start_run(None, run="probe")
    run_sweep_tlb(addrs, specs, kernel_mode="reference", block=BLOCK, run=cfg)
    s = tr.end_run()
    n_ops = (s["n_spans"] + sum(s["events"].values())
             + sum(c["updates"] for c in s["counters"].values())
             + sum(g["updates"] for g in s["gauges"].values()))
    assert n_ops >= 4  # at least the four chunk spans

    # Disabled per-op cost (4 instrument calls per iteration).
    def ops(k=1000):
        for _ in range(k):
            with tr.span("x"):
                pass
            tr.record_span("y", 0.0)
            tr.event("e")
            tr.counter("c").add()

    assert not tr.active
    per_op = benchtime.measure(ops, reps=3).best_s / (1000 * 4)

    m_sweep = benchtime.measure(run_sweep_tlb, addrs, specs,
                                kernel_mode="reference", block=BLOCK, run=cfg,
                                reps=2)
    assert n_ops * per_op < 0.02 * m_sweep.best_s, (
        f"{n_ops} ops x {per_op:.2e}s/op vs sweep {m_sweep.best_s:.4f}s")


# ------------------------------------------------------- orchestrator threading


def test_ladder_events_carry_timestamps_and_elapsed():
    addrs, specs = _sweep_inputs()
    failures = {"left": 1}

    def hook(engine, lo, hi, mode, attempt):
        if failures["left"]:
            failures["left"] -= 1
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: injected")

    cfg = SweepRunConfig(fault_hook=hook, backoff_base_s=0.0,
                         backoff_cap_s=0.0, chunk_accesses=1024)
    res, meta = run_sweep_tlb(addrs, specs, kernel_mode="reference",
                              block=BLOCK, run=cfg)
    retries = [e for e in meta["events"] if e["event"] == "retry"]
    assert len(retries) == 1
    e = retries[0]
    assert e["ts"] > 1e9 and isinstance(e["t_mono"], float)
    assert e["elapsed_s"] >= 0 and e["attempt"] == 0
    assert "RESOURCE_EXHAUSTED" in e["error"]
    # The faulted-then-retried run stays bit-identical to the oracle.
    oracle = sweep_tlb(addrs, specs, kernel_mode="reference", block=BLOCK)
    np.testing.assert_array_equal(res.hits, oracle.hits)


def test_runlog_chunks_and_throughput_meta(tmp_path):
    addrs, specs = _sweep_inputs()
    path = tmp_path / "fig.jsonl"
    with telemetry.run_scope(path, run="fig"):
        _, meta = run_sweep_tlb(addrs, specs, kernel_mode="reference",
                                block=BLOCK,
                                run=SweepRunConfig(chunk_accesses=1024),
                                name="tlb")
    tp = meta["throughput"]["reference"]
    assert tp["chunks"] == 4 and tp["accesses"] == 4096
    assert tp["sim_accesses"] == 4096 * len(specs)
    assert tp["accesses_per_s"] > 0 and tp["sim_accesses_per_s"] > 0

    recs = _read(path)
    chunks = [r for r in recs
              if r["kind"] == "span" and r["name"] == "chunk"]
    assert len(chunks) == 4
    a = chunks[0]["attrs"]
    assert a["engine"] == "sweep_tlb" and a["name"] == "tlb"
    assert a["mode"] == "reference" and a["configs"] == len(specs)
    assert (a["lo"], a["hi"]) == (0, 1024) and a["accesses_per_s"] > 0
    env = [r for r in recs
           if r["kind"] == "event" and r["name"] == "vmem_envelope"]
    assert env and env[0]["attrs"]["configs"] == len(specs)
    assert env[0]["attrs"]["state_bytes"] > 0
    summary = recs[-1]["summary"]
    assert summary["counters"]["sweep_tlb.sim_accesses"]["value"] == \
        4096 * len(specs)
    assert summary["gauges"]["sweep_tlb.state_bytes"]["value"] > 0


def test_stackdist_monolithic_path_records_throughput(tmp_path):
    addrs, specs = _sweep_inputs()
    path = tmp_path / "sd.jsonl"
    with telemetry.run_scope(path, run="sd"):
        _, meta = run_sweep_tlb(addrs, specs, kernel_mode="stackdist",
                                block=BLOCK, name="tlb")
    assert meta["resumable"] is False
    tp = meta["throughput"]["stackdist"]
    assert tp["chunks"] == 1 and tp["accesses"] == 4096
    assert tp["accesses_per_s"] > 0
    chunks = [r for r in _read(path)
              if r["kind"] == "span" and r["name"] == "chunk"]
    assert len(chunks) == 1 and chunks[0]["attrs"]["mode"] == "stackdist"


def test_measure_label_records_span(tmp_path):
    path = tmp_path / "m.jsonl"
    with telemetry.run_scope(path, run="m"):
        benchtime.measure(lambda: np.arange(16), reps=2, label="unit:probe")
    spans = [r for r in _read(path)
             if r["kind"] == "span" and r["name"] == "measure"]
    assert len(spans) == 1
    a = spans[0]["attrs"]
    assert a["label"] == "unit:probe" and a["reps"] == 2
    assert a["best_s"] >= 0 and a["spread_frac"] >= 0


# --------------------------------------------------------------- setup_logging


def test_setup_logging_levels_and_idempotent():
    log = telemetry.setup_logging(0)
    n_handlers = len(log.handlers)
    assert log.level == logging.INFO
    assert telemetry.setup_logging(1).level == logging.DEBUG
    assert telemetry.setup_logging(-1).level == logging.WARNING
    assert len(log.handlers) == n_handlers  # no handler stacking
    telemetry.setup_logging(0)


# ------------------------------------------------------------------ obs_report


def _mklog(tmp_path, name, rate, events=("retry",)):
    path = tmp_path / name
    with telemetry.run_scope(path, run=name):
        tr = telemetry.get_tracer()
        for i in range(2):
            tr.record_span(
                "chunk", 0.5, engine="sweep_tlb", name="tlb",
                lo=1024 * i, hi=1024 * (i + 1), mode="reference", attempt=0,
                accesses=1024, configs=2, accesses_per_s=rate,
                sim_accesses_per_s=2 * rate)
        for ev in events:
            tr.event(ev, lo=0, hi=1024)
    return path


def test_obs_report_render(tmp_path, capsys):
    path = _mklog(tmp_path, "a.jsonl", rate=2048.0)
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "phase breakdown" in out and "chunk" in out
    assert "engine throughput" in out and "sweep_tlb" in out
    assert "throughput timeline" in out
    assert "retry" in out and "end=clean" in out


def test_obs_report_aggregates(tmp_path):
    recs = obs_report.load_log(_mklog(tmp_path, "a.jsonl", rate=2048.0))
    phases = obs_report.phase_breakdown(recs)
    assert phases["chunk"] == {"count": 2, "total_s": 1.0}
    tput = obs_report.engine_throughput(recs)
    st = tput[("sweep_tlb", "reference")]
    assert st["chunks"] == 2 and st["accesses"] == 2048
    assert st["accesses_per_s"] == pytest.approx(2048.0)
    assert obs_report.event_counts(recs) == {"retry": 1}


def test_obs_report_diff(tmp_path, capsys):
    a = _mklog(tmp_path, "a.jsonl", rate=1000.0)
    b = _mklog(tmp_path, "b.jsonl", rate=2000.0, events=("downgrade",))
    assert obs_report.main([str(a), str(b), "--diff"]) == 0
    out = capsys.readouterr().out
    assert "phase totals" in out and "->" in out
    assert "downgrade" in out
    with pytest.raises(SystemExit):   # --diff needs exactly two logs
        obs_report.main([str(a), "--diff"])


def test_obs_report_fail_on_event(tmp_path, capsys):
    path = _mklog(tmp_path, "a.jsonl", rate=100.0, events=("downgrade",))
    assert obs_report.main([str(path), "--fail-on-event", "preempt"]) == 0
    capsys.readouterr()
    assert obs_report.main([str(path), "--fail-on-event",
                            "downgrade,preempt"]) == 1
    assert "downgrade" in capsys.readouterr().err


def test_obs_report_tolerates_torn_tail(tmp_path):
    path = _mklog(tmp_path, "a.jsonl", rate=100.0)
    n = len(obs_report.load_log(path))
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"kind": "event", "name": "tr')   # crashed mid-write
    recs = obs_report.load_log(path)
    assert len(recs) == n and recs[-1]["kind"] == "run_end"


def test_obs_report_rejects_mid_log_corruption(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"kind": "run_start"}\nnot json\n{"kind": "run_end"}\n')
    with pytest.raises(SystemExit, match="corrupt record"):
        obs_report.load_log(path)
