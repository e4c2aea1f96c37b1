"""Run telemetry: spans, counters/gauges and per-run append-only JSONL logs.

SPARTA's headline claim is an *attribution* claim — translation overhead is
where the cycles go — yet a sweep run is otherwise opaque: the orchestrator
records retries/halves/downgrades, but nothing says how long each chunk
took, which backend it ran on, or what accesses/s the engine actually
achieved (the measured-crossover feed the roofline ``kernel_mode="auto"``
item needs).  This module is the one place all of that flows through:

* :class:`RunLog` — an append-only JSONL sink, one file per figure/bench
  run, one self-describing record per line (``kind`` = ``run_start`` /
  ``span`` / ``event`` / ``run_end``; every record carries ``ts`` wall-clock
  seconds and ``t_mono`` = ``time.perf_counter()``).  The first record
  stamps ``schema_version`` (:data:`SCHEMA_VERSION`) like BENCH_sweep.json
  rows do.
* :meth:`Tracer.span` — the program's one span API.  Every span, with or
  without a run, opens a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>`` carrying the attributes known at entry, so a profiler
  trace holds the program's phases on the same clock as the device's
  operations.  With a run active the span is also a :class:`Span`
  recording wall duration into the JSONL log; spans nest, with ``span_id``
  / ``parent_id`` linking the records, and ``set(**attrs)`` adds attributes
  discovered inside the span to that record.  :meth:`Tracer.record_span`
  logs a span whose duration was measured externally
  (``benchtime.measure``).
* :class:`Counter` / :class:`Gauge` — a per-run registry (simulated-access
  counts, VMEM state footprints, ...), aggregated into the ``run_end``
  summary.  While a run is active, JAX's compilations are counted into
  ``jax.lowerings`` and ``jax.backend_compiles``, each with a ``compile``
  event naming the innermost open span.
* :class:`Tracer` — the global instance (:func:`get_tracer`).  When no run
  is active a span only annotates the profiler (about a microsecond) and
  every other call is a no-op returning shared null objects, so hot loops
  can be instrumented unconditionally (tests/test_telemetry.py holds the
  <2% overhead guard on a disabled-tracer ``run_sweep_tlb``).

Lifecycle: :func:`run_scope` (or :func:`start_run`/:func:`end_run`) brackets
one run; ``run_scope`` catches ``BaseException`` so a ``Preempted`` exit
still closes the log with an ``error`` on the ``run_end`` record.
:meth:`Tracer.summary` is the in-memory aggregate the figure drivers stamp
into their JSON as ``_telemetry`` (next to ``_device`` / ``_crash_safety``).

Importing this module never imports jax: the profiler annotation is looked
up on the first span and the compile listener on the first run, so
``benchmarks/obs_report.py`` reads the logs without jax.
"""
from __future__ import annotations

import contextlib
import json
import logging
import pathlib
import sys
import threading
import time
from typing import IO, Any, Dict, List, Optional, Union

# Version of the JSONL record schema below; bump on any incompatible change
# (the BENCH_sweep.json `schema_version` discipline).
SCHEMA_VERSION = 1

_LOG = logging.getLogger("repro.runtime.telemetry")


def _stamp() -> Dict[str, float]:
    """Wall-clock + monotonic timestamps carried by every record."""
    return {"ts": time.time(), "t_mono": time.perf_counter()}


def _jsonable(x: Any):
    """json.dumps default: numpy scalars/arrays degrade to Python values."""
    item = getattr(x, "item", None)
    if callable(item):
        try:
            return x.item()
        except (TypeError, ValueError):
            pass
    tolist = getattr(x, "tolist", None)
    if callable(tolist):
        return x.tolist()
    return str(x)


class _ProfilerSpan:
    """The disabled-tracer span: a profiler annotation and nothing else, so
    instrumented code needs no ``if enabled`` guard.  ``set`` records
    nothing."""

    __slots__ = ("_ann",)

    def __init__(self, ann):
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def set(self, **attrs):
        return self


class _NullInstrument:
    """Disabled-tracer counter/gauge."""

    __slots__ = ()

    def add(self, n=1):
        return self

    def set(self, value):
        return self


_NULL_INSTRUMENT = _NullInstrument()

_TRACE_ANNOTATION = None


def _annotation(name: str, attrs: dict):
    """A ``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (jax is
    imported on the first call)."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(f"repro.{name}", **attrs)


# JAX's compile-duration events -> the counters they feed: a lowering on
# every in-process cache miss, a backend compile where the persistent cache
# missed too.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lowerings",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compiles",
}


# One lock for all counter/gauge mutation: scheduler worker threads update
# shared instruments concurrently, and `+=` on a float is not atomic.  The
# disabled-tracer path never reaches these (it returns _NULL_INSTRUMENT), so
# the <2% no-op overhead guard is unaffected.
_AGG_LOCK = threading.Lock()


class Counter:
    """Monotonically accumulated value (e.g. simulated accesses)."""

    __slots__ = ("name", "value", "updates")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self.updates = 0

    def add(self, n=1):
        with _AGG_LOCK:
            self.value += n
            self.updates += 1
        return self

    def summary(self) -> dict:
        return {"value": self.value, "updates": self.updates}


class Gauge:
    """Last-set value with min/max tracking (e.g. VMEM state bytes)."""

    __slots__ = ("name", "value", "min", "max", "updates")

    def __init__(self, name: str):
        self.name = name
        self.value: Optional[float] = None
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.updates = 0

    def set(self, value):
        value = float(value)
        with _AGG_LOCK:
            self.value = value
            self.min = value if self.min is None else min(self.min, value)
            self.max = value if self.max is None else max(self.max, value)
            self.updates += 1
        return self

    def summary(self) -> dict:
        return {"value": self.value, "min": self.min, "max": self.max,
                "updates": self.updates}


class RunLog:
    """Append-only JSONL sink for one run: one json record per line,
    flushed per write so a crashed/preempted run keeps every completed
    record (at worst the final line is torn, which readers tolerate)."""

    def __init__(self, path: Union[str, pathlib.Path]):
        self.path = pathlib.Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._f: Optional[IO[str]] = open(self.path, "w", encoding="utf-8")

    def write(self, rec: dict) -> None:
        if self._f is None:
            return
        self._f.write(json.dumps(rec, default=_jsonable) + "\n")
        self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


class Span:
    """An in-progress span; obtained from :meth:`Tracer.span` while a run is
    active and used as a context manager.  It holds the profiler annotation
    and records its wall duration into the run; ``set(**attrs)`` attaches
    attributes discovered while the span runs (e.g. achieved accesses/s) to
    the JSONL record."""

    __slots__ = ("_tracer", "name", "attrs", "span_id", "parent_id", "ts",
                 "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict, ann):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id: Optional[int] = None
        self.parent_id: Optional[int] = None
        self._ann = ann

    def __enter__(self) -> "Span":
        tr = self._tracer
        self.parent_id = tr._stack[-1].span_id if tr._stack else None
        self.span_id = tr._next_id()
        tr._stack.append(self)
        self._ann.__enter__()
        self.ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dur_s = time.perf_counter() - self._t0
        self._ann.__exit__(et, ev, tb)
        tr = self._tracer
        if tr._stack and tr._stack[-1] is self:
            tr._stack.pop()
        if et is not None:
            self.attrs.setdefault("error", f"{et.__name__}: {ev}")
        tr._finish_span(self.name, dur_s, self.span_id, self.parent_id,
                        self.ts, self.attrs)
        return False


class Tracer:
    """The global spans/counters/events registry for one run.

    ``active`` is the no-op gate: with no run started (the default), every
    instrument call returns a shared null object and records nothing.  The
    per-name aggregates (``summary()``) survive :meth:`end_run`, so a driver
    can stamp the finished run's summary into its figure JSON.

    Thread-safety: scheduler worker *threads* share this tracer, so the
    span stack is thread-local (each thread nests its own spans; a worker
    span never claims another thread's span as parent) while the shared
    registries (span stats, event counts, counters/gauges, id allocation)
    and the JSONL sink are guarded by one re-entrant lock.  The disabled
    path stays lock-free — the <2% no-op overhead guard still holds."""

    def __init__(self):
        self._lock = threading.RLock()
        self._tls = threading.local()
        self._listening = False
        self._reset()

    def _reset(self) -> None:
        self.active = False
        self.run: Optional[str] = None
        self._log: Optional[RunLog] = None
        self._tls.stack = []
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._span_stats: Dict[str, dict] = {}
        self._event_counts: Dict[str, int] = {}
        self._id = 0

    @property
    def _stack(self) -> List[Span]:
        """This thread's open-span stack (created lazily per thread)."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    # -- lifecycle ----------------------------------------------------------

    def start_run(self, path: Union[str, pathlib.Path, None] = None, *,
                  run: Optional[str] = None, **meta) -> "Tracer":
        """Begin a run, resetting all registries.  ``path=None`` keeps the
        run in-memory only (aggregates, no JSONL)."""
        with self._lock:
            if self.active:
                _LOG.warning("telemetry run %r still active; closing it to start %r",
                             self.run, run)
                self.end_run(error=f"superseded by run {run!r}")
            self._reset()
            self._listen_for_compiles()
            self.run = run
            self.active = True
            if path is not None:
                self._log = RunLog(path)
            rec = {"kind": "run_start", "schema_version": SCHEMA_VERSION,
                   "run": run, **_stamp()}
            if meta:
                rec["meta"] = meta
            self._emit(rec)
            return self

    def end_run(self, error: Optional[str] = None) -> dict:
        """Close the run (writing the ``run_end`` summary record) and return
        the summary.  No-op returning ``{}`` when no run is active."""
        with self._lock:
            if not self.active:
                return {}
            s = self.summary()
            rec = {"kind": "run_end", "run": self.run, **_stamp(), "summary": s}
            if error is not None:
                rec["error"] = str(error)
            self._emit(rec)
            if self._log is not None:
                self._log.close()
                self._log = None
            self.active = False
            del self._stack[:]
            return s

    # -- instruments --------------------------------------------------------

    # `name` is positional-only so callers can attach a `name=...` attribute
    # (e.g. the orchestrator labels chunk spans with the figure name).
    def span(self, name: str, /, **attrs):
        """Open a span context manager: a profiler annotation
        ``repro.<name>`` with ``attrs``, which a run also records."""
        ann = _annotation(name, attrs)
        if not self.active:
            return _ProfilerSpan(ann)
        return Span(self, name, attrs, ann)

    def record_span(self, name: str, dur_s: float, /, **attrs) -> None:
        """Record an already-measured span (duration timed externally)."""
        if not self.active:
            return
        parent = self._stack[-1].span_id if self._stack else None
        self._finish_span(name, float(dur_s), self._next_id(), parent,
                          time.time(), attrs)

    def event(self, name: str, /, **attrs) -> None:
        """Record a point-in-time structured event (retry, downgrade, ...)."""
        if not self.active:
            return
        with self._lock:
            self._event_counts[name] = self._event_counts.get(name, 0) + 1
            rec = {"kind": "event", "name": name, **_stamp()}
            if attrs:
                rec["attrs"] = attrs
            self._emit(rec)

    def counter(self, name: str):
        if not self.active:
            return _NULL_INSTRUMENT
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str):
        if not self.active:
            return _NULL_INSTRUMENT
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def summary(self) -> dict:
        """Aggregate view of the (last) run: per-name span stats, event
        counts, counter/gauge values — the figure-JSON ``_telemetry`` base."""
        with self._lock:
            return self._summary_locked()

    def _summary_locked(self) -> dict:
        return {
            "schema_version": SCHEMA_VERSION,
            "run": self.run,
            "n_spans": sum(s["count"] for s in self._span_stats.values()),
            "spans": {k: {"count": v["count"],
                          "total_s": round(v["total_s"], 6)}
                      for k, v in sorted(self._span_stats.items())},
            "events": dict(sorted(self._event_counts.items())),
            "counters": {k: c.summary()
                         for k, c in sorted(self._counters.items())},
            "gauges": {k: g.summary()
                       for k, g in sorted(self._gauges.items())},
        }

    # -- internals ----------------------------------------------------------

    def _listen_for_compiles(self) -> None:
        """Register :meth:`_on_compile` with ``jax.monitoring``, once."""
        if self._listening:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        self._listening = True

    def _on_compile(self, event: str, duration_secs: float, **_) -> None:
        counter = COMPILE_EVENTS.get(event)
        if counter is None or not self.active:
            return
        self.counter(counter).add()
        stack = self._stack
        self.event("compile", counter=counter,
                   span=stack[-1].name if stack else None,
                   dur_s=round(duration_secs, 6))

    def _next_id(self) -> int:
        with self._lock:
            self._id += 1
            return self._id

    def _emit(self, rec: dict) -> None:
        with self._lock:
            if self._log is not None:
                self._log.write(rec)

    def _finish_span(self, name: str, dur_s: float, span_id: Optional[int],
                     parent_id: Optional[int], ts: float, attrs: dict) -> None:
        with self._lock:
            st = self._span_stats.setdefault(name, {"count": 0, "total_s": 0.0})
            st["count"] += 1
            st["total_s"] += dur_s
            rec = {"kind": "span", "name": name, "span_id": span_id,
                   "parent_id": parent_id, "ts": ts,
                   "t_mono": time.perf_counter(), "dur_s": round(dur_s, 6)}
            if attrs:
                rec["attrs"] = dict(attrs)
            self._emit(rec)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def start_run(path=None, *, run=None, **meta) -> Tracer:
    return _TRACER.start_run(path, run=run, **meta)


def end_run(error: Optional[str] = None) -> dict:
    return _TRACER.end_run(error=error)


@contextlib.contextmanager
def run_scope(path=None, *, run=None, **meta):
    """Bracket one run.  Catches ``BaseException`` deliberately: a
    :class:`repro.core.orchestrator.Preempted` (or KeyboardInterrupt) must
    still close the JSONL log, with the error recorded on ``run_end``."""
    _TRACER.start_run(path, run=run, **meta)
    try:
        yield _TRACER
    except BaseException as exc:
        _TRACER.end_run(error=f"{type(exc).__name__}: {exc}")
        raise
    else:
        _TRACER.end_run()


def setup_logging(verbosity: int = 0,
                  stream: Optional[IO[str]] = None) -> logging.Logger:
    """Configure the ``repro`` logger hierarchy for driver narration.

    The handler writes to **stderr** so stdout stays machine output (CSV
    rows, claim lines, figure paths).  ``verbosity < 0`` -> WARNING
    (``--quiet``), ``0`` -> INFO (default), ``>= 1`` -> DEBUG (``-v``).
    Idempotent: repeated calls adjust the level instead of stacking
    handlers."""
    level = (logging.WARNING if verbosity < 0
             else logging.INFO if verbosity == 0 else logging.DEBUG)
    root = logging.getLogger("repro")
    root.setLevel(level)
    if not any(getattr(h, "_repro_narration", False) for h in root.handlers):
        h = logging.StreamHandler(stream if stream is not None else sys.stderr)
        h.setFormatter(logging.Formatter("%(levelname).1s %(name)s: %(message)s"))
        h._repro_narration = True
        root.addHandler(h)
    root.propagate = False
    return root
