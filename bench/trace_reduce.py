"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device busy time, per-operation device time, and idle gaps
named by the benchmark's own host spans.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; busy time is the union of their intervals inside
the window.  Programs are the events of its ``XLA Modules`` line, named
after the jitted function.  Host spans are the ``TraceAnnotation`` events
whose names start with ``bench.`` on the host plane: ``bench.window``
bounds the measured window, ``bench.engine:<kernel>`` covers one engine
call, ``bench.job`` one job and ``bench.figure`` the reduction to figure
numbers.  Both clocks are
the profiler's own nanoseconds, so device and host events share one axis.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    name: str
    start: int   # ns
    end: int
    device: int


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]          # ns, from the bench.window span
    devices: List[int]
    ops: List[Op]                    # device operations inside the window
    modules: List[Op]                # device programs inside the window
    spans: List[Tuple[str, int, int]]  # host bench.* spans (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, device: int) -> np.ndarray:
        """Merged [start, end) intervals of ``device``'s operations."""
        iv = sorted((o.start, o.end) for o in self.ops if o.device == device)
        out: List[List[int]] = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return np.asarray(out, np.int64).reshape(-1, 2)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([_length(self.busy(d)) for d in self.devices])) * 1e-9

    def busy_within(self, spans: List[Tuple[int, int]]) -> float:
        """Device-busy seconds inside the union of ``spans``, averaged over
        the devices."""
        sp = _merge(spans)
        if not self.devices or sp.size == 0:
            return 0.0
        return float(np.mean([_overlap(self.busy(d), sp) for d in self.devices])) * 1e-9

    def spans_named(self, prefix: str) -> List[Tuple[int, int]]:
        return [(s, e) for n, s, e in self.spans if n.startswith(prefix)]

    def module_seconds(self, pattern: str) -> Tuple[float, int]:
        """Total device seconds and count of the programs whose name matches
        ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        hit = [m for m in self.modules if rx.search(m.name)]
        return sum(m.end - m.start for m in hit) * 1e-9, len(hit)

    def top_ops(self, k: int = 10) -> List[list]:
        tot: Dict[str, int] = {}
        for o in self.ops:
            tot[o.name] = tot.get(o.name, 0) + o.end - o.start
        return [[n, t * 1e-9] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """Idle device time inside the window, summed by the innermost
        benchmark span covering each gap's midpoint ("host" where none)."""
        tot: Dict[str, int] = {}
        w0, w1 = self.window
        inner = [(n, s, e) for n, s, e in self.spans if n != "bench.window"]
        for d in self.devices:
            b = self.busy(d)
            edges = np.r_[w0, b.ravel(), w1].reshape(-1, 2)
            for s, e in edges:
                if e <= s:
                    continue
                mid = (s + e) // 2
                cover = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid < e2]
                label = min(cover)[1][len(SPAN_PREFIX):] if cover else "host"
                tot[label] = tot.get(label, 0) + (e - s)
        n = max(len(self.devices), 1)
        return [[lab, t * 1e-9 / n]
                for lab, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def _merge(spans) -> np.ndarray:
    out: List[List[int]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64).reshape(-1, 2)


def _length(iv: np.ndarray) -> int:
    return int((iv[:, 1] - iv[:, 0]).sum()) if iv.size else 0


def _overlap(a: np.ndarray, b: np.ndarray) -> int:
    """Total length of the intersection of two merged interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += int(hi - lo)
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


def read(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    raw = {OPS_LINE: [], MODULES_LINE: []}
    spans, devices = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in raw:
                    continue
                evs = [Op(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), dev)
                       for e in line.events]
                if evs and line.name == OPS_LINE:
                    devices.append(dev)
                raw[line.name] += evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not win:
        raise ValueError(f"{path}: no bench.window span")
    w0, w1 = win[0]

    def clip(evs):
        return [Op(o.name, max(o.start, w0), min(o.end, w1), o.device)
                for o in evs if o.end > w0 and o.start < w1]

    return Trace((w0, w1), sorted(set(devices)), clip(raw[OPS_LINE]),
                 clip(raw[MODULES_LINE]), spans)
