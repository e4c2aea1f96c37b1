"""Reduction of a profiler trace to the program's own phases: the device's
idle time named by what the host was doing, and three numbers read off the
program's spans.

The program annotates its phases with ``jax.profiler.TraceAnnotation``
events named ``repro.<phase>`` (``repro.runtime.telemetry``):
``repro.engine`` is one engine call, ``repro.engine.prepare`` and
``repro.engine.finish`` its set-up and finish, ``repro.chunk`` one chunk
attempt holding ``repro.chunk.keys``, ``.upload``, ``.launch`` and
``.pull``, and ``repro.chunk.commit`` the commit after it.  JAX's own host
events ``lower_sharding_computation`` and ``backend_compile_and_load`` are
compilations.  The window, the device operations and the benchmark's
``bench.*`` spans are those of ``bench/trace_reduce.py``, read the same way.

    python3 bench/phases.py TRACE.xplane.pb

prints the reduction as one JSON object.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "repro."
# JAX's host events of one compilation: lowering on every in-process cache
# miss, then the backend compile (or its load from the persistent cache).
LOWERING = "lower_sharding_computation"
COMPILE_EVENTS = (LOWERING, "backend_compile_and_load")
# Spans that hold per-chunk work; the rest of an engine call is fixed.
CHUNK_SPANS = ("repro.chunk", "repro.chunk.commit")


@dataclasses.dataclass
class Phases:
    trace: trace_reduce.Trace
    program: List[Tuple[str, int, int]]   # repro.* host spans (name, start, end)
    compiles: List[Tuple[str, int, int]]  # JAX's compile host events

    def named(self, name: str) -> List[Tuple[int, int]]:
        """The program's spans called exactly ``name`` that start inside
        the window, clipped to it."""
        w0, w1 = self.trace.window
        return [(max(s, w0), min(e, w1)) for n, s, e in self.program
                if n == name and w0 <= s < w1]


def read(path: str) -> Phases:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    trace = trace_reduce.read(path)
    program, compiles = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                ev = (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                if e.name.startswith(PROGRAM_PREFIX):
                    program.append(ev)
                elif e.name in COMPILE_EVENTS:
                    compiles.append(ev)
    return Phases(trace, program, compiles)


def label(name: str) -> str:
    """A span's name in the idle breakdown: ``compile`` for JAX's compile
    events, else the name without its ``repro.`` or ``bench.`` prefix."""
    if name in COMPILE_EVENTS:
        return "compile"
    for prefix in (PROGRAM_PREFIX, trace_reduce.SPAN_PREFIX):
        if name.startswith(prefix):
            return name[len(prefix):]
    return name


def idle_by_phase(ph: Phases, within: Optional[List[Tuple[int, int]]] = None
                  ) -> Dict[str, float]:
    """Idle device seconds inside the window (and inside ``within`` where
    given), each instant named by the innermost span covering it: a program
    span, a compile event or a benchmark span; ``host`` where none does.
    Unlike ``Trace.idle_gaps``, a gap is split at every span boundary, so a
    gap that runs through several phases is shared among them.  Averaged
    over the devices."""
    t = ph.trace
    w0, w1 = t.window
    spans = [(s, e, label(n)) for n, s, e in
             ph.program + ph.compiles + [x for x in t.spans if x[0] != "bench.window"]
             if e > w0 and s < w1]
    starts = np.asarray([s for s, _, _ in spans], np.int64)
    ends = np.asarray([e for _, e, _ in spans], np.int64)
    lengths = ends - starts
    cuts = np.unique(np.r_[starts, ends]) if spans else np.zeros(0, np.int64)
    region = trace_reduce._merge(within if within is not None else [(w0, w1)])
    tot: Dict[str, int] = {}
    for d in t.devices:
        idle = _complement(t.busy(d), w0, w1)
        for s, e in _intersect(idle, region):
            inner = cuts[(cuts > s) & (cuts < e)]
            edges = np.r_[s, inner, e]
            for a, b in zip(edges[:-1], edges[1:]):
                cover = (starts <= a) & (ends >= b)
                lab = (spans[int(np.flatnonzero(cover)[np.argmin(lengths[cover])])][2]
                       if cover.any() else "host")
                tot[lab] = tot.get(lab, 0) + int(b - a)
    n = max(len(t.devices), 1)
    return {k: v * 1e-9 / n for k, v in sorted(tot.items(), key=lambda x: -x[1])}


def engine_idle(ph: Phases) -> Dict[str, float]:
    """:func:`idle_by_phase` inside the benchmark's ``bench.engine:*``
    spans."""
    return idle_by_phase(ph, ph.trace.spans_named("bench.engine:"))


def child_phase_share(idle: Dict[str, float]) -> Optional[float]:
    """Share of an idle breakdown named by a phase inside an engine call (a
    program span under ``repro.engine``, or ``compile``)."""
    total = sum(idle.values())
    if total <= 0:
        return None
    inside = sum(v for k, v in idle.items()
                 if k.startswith(("engine.", "chunk")) or k == "compile")
    return inside / total


def fixed_ms_per_call(ph: Phases) -> Optional[float]:
    """Mean milliseconds of an engine call outside its per-chunk spans
    (``repro.chunk`` and ``repro.chunk.commit``): the per-call set-up and
    finish."""
    engines = ph.named("repro.engine")
    if not engines:
        return None
    chunks = trace_reduce._merge(
        [iv for name in CHUNK_SPANS for iv in ph.named(name)])
    fixed = [(e - s) - trace_reduce._overlap(trace_reduce._merge([(s, e)]), chunks)
             for s, e in engines]
    return float(np.mean(fixed)) * 1e-6


def chunk_device_idle_pct(ph: Phases) -> Optional[float]:
    """Share of the ``repro.chunk`` spans' time in which the device is idle:
    the kernel waiting on the chunk's serial host phases."""
    chunks = ph.named("repro.chunk")
    total = sum(e - s for s, e in chunks) * 1e-9
    if not ph.trace.devices or total <= 0:
        return None
    return 100.0 * (1.0 - ph.trace.busy_within(chunks) / total)


def compiles_in_window(ph: Phases) -> int:
    """Lowerings (in-process compile-cache misses) that start inside the
    window."""
    w0, w1 = ph.trace.window
    return sum(1 for n, s, _ in ph.compiles if n == LOWERING and w0 <= s < w1)


def _complement(busy: np.ndarray, w0: int, w1: int) -> List[Tuple[int, int]]:
    edges = np.r_[w0, busy.ravel(), w1].reshape(-1, 2)
    return [(int(s), int(e)) for s, e in edges if e > s]


def _intersect(a: List[Tuple[int, int]], b: np.ndarray) -> List[Tuple[int, int]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], int(b[j, 0])), min(a[i][1], int(b[j, 1]))
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return out


def summary(ph: Phases) -> dict:
    eng = engine_idle(ph)
    return {
        "window_s": ph.trace.window_s, "busy_s": ph.trace.busy_s(),
        "engine_calls": len(ph.named("repro.engine")),
        "chunks": len(ph.named("repro.chunk")),
        "idle_by_phase": idle_by_phase(ph), "engine_idle_by_phase": eng,
        "engine_idle_child_share": child_phase_share(eng),
        "engine.fixed_ms_per_call": fixed_ms_per_call(ph),
        "chunk.device_idle_pct": chunk_device_idle_pct(ph),
        "compiles_in_window": compiles_in_window(ph),
    }


if __name__ == "__main__":
    print(json.dumps(summary(read(sys.argv[1]))))
