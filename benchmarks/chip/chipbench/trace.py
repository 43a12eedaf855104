"""Reduction of a profiler trace to the benchmark's device metrics.

A ``Trace`` holds what the reduction needs and nothing else: the traced
window, each chip's device operations and the benchmark's own host
spans, all on the profiler's one clock in nanoseconds.  ``from_xspace``
reads it from the ``.xplane.pb`` that ``jax.profiler`` writes;
``to_json``/``from_json`` keep a small one as a test fixture.

- busy time is the union of a chip's operation intervals inside the
  window, and the idle share 1 minus busy over the window;
- an operation is named by its HLO instruction name without the numeric
  suffix (``%batched_gram.7 = (...) custom-call(...)`` is
  ``batched_gram``), and a kernel's time is the summed duration of the
  operations so named;
- an idle gap is attributed to the host span that covers it, piece by
  piece, so the longest gaps can be read by what the host was doing.
"""
from __future__ import annotations

import bisect
import json
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

# the device planes jax.profiler writes for TPU chips, and the line in
# each that holds one event per executed operation
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
NO_SPAN = "(no host span)"


def op_name(hlo: str) -> str:
    """``batched_gram`` for ``%batched_gram.7 = (...) custom-call(...)``;
    an unnamed custom call is named by its target
    (``custom-call:Cholesky``)."""
    head = hlo.split(" = ", 1)[0].lstrip("%").strip()
    base, dot, num = head.rpartition(".")
    name = base if dot and num.isdigit() else head
    if name == "custom-call" and 'custom_call_target="' in hlo:
        name += ":" + hlo.split('custom_call_target="', 1)[1].split('"')[0]
    return name


@dataclass
class Trace:
    window: Tuple[float, float]
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def to_json(self) -> Dict:
        return {"window": list(self.window),
                "devices": {k: [list(e) for e in v]
                            for k, v in self.devices.items()},
                "spans": [list(e) for e in self.spans]}

    @classmethod
    def from_json(cls, obj: Dict) -> "Trace":
        return cls(window=tuple(obj["window"]),
                   devices={k: [tuple(e) for e in v]
                            for k, v in obj["devices"].items()},
                   spans=[tuple(e) for e in obj["spans"]])


def from_xspace(path: str, span_prefix: str, window_span: str,
                n_devices: Optional[int] = None) -> Trace:
    """Read a Trace from an ``.xplane.pb``.  The window is the host span
    named ``window_span``; host spans are those whose name starts with
    ``span_prefix``; device planes are the TPU chips, the first
    ``n_devices`` of them by id when given."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [(op_name(e.name), float(e.start_ns),
                    float(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(span_prefix)]
    wins = [s for s in spans if s[0] == window_span]
    if not wins:
        raise ValueError(f"no {window_span!r} span in {path}")
    _, w0, wd = wins[-1]
    if n_devices is not None:
        keep = sorted(devices, key=lambda k: int(k.rsplit(":", 1)[1]))
        devices = {k: devices[k] for k in keep[:n_devices]}
    return Trace(window=(w0, w0 + wd), devices=devices,
                 spans=[s for s in spans if s[0] != window_span])


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy_intervals(ops: Sequence[Event], window) -> List[Tuple[float, float]]:
    return union(((s, s + d) for _, s, d in ops), *window)


def busy_ns(trace: Trace) -> Dict[str, float]:
    """Per chip: nanoseconds of the window in which an operation ran."""
    return {dev: sum(e - s for s, e in _busy_intervals(ops, trace.window))
            for dev, ops in trace.devices.items()}


def mean_busy_s(trace: Trace) -> float:
    busy = busy_ns(trace)
    return sum(busy.values()) / len(busy) / 1e9 if busy else 0.0


def idle_frac(trace: Trace) -> Optional[float]:
    """1 - busy / window, the mean over chips; None without a chip."""
    busy = busy_ns(trace)
    if not busy or trace.window_ns <= 0:
        return None
    return sum(1.0 - b / trace.window_ns for b in busy.values()) / len(busy)


def kernel_ns(trace: Trace, names: Sequence[str]) -> float:
    """Summed device time, over all chips, of the operations named one
    of ``names``, clipped to the window."""
    lo, hi = trace.window
    names = set(names)
    total = 0.0
    for ops in trace.devices.values():
        for name, s, d in ops:
            if name in names:
                total += max(0.0, min(s + d, hi) - max(s, lo))
    return total


def _leaves(ops: Sequence[Event]) -> List[Event]:
    """The operations that contain no other: a loop's own event spans
    the operations of its body, which are listed as well."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ops)
            if not (i + 1 < len(ops)
                    and ops[i + 1][1] + ops[i + 1][2] <= e[1] + e[2])]


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operation names with the most device seconds in the
    window, averaged over chips; operations that contain others (loops)
    are left out, their bodies counted instead."""
    lo, hi = trace.window
    by: Dict[str, float] = defaultdict(float)
    for ops in trace.devices.values():
        for name, s, d in _leaves(ops):
            by[name] += max(0.0, min(s + d, hi) - max(s, lo))
    n = max(len(trace.devices), 1)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def gaps(trace: Trace, dev: str) -> List[Tuple[float, float]]:
    """Idle intervals of one chip inside the window."""
    lo, hi = trace.window
    out, t = [], lo
    for s, e in _busy_intervals(trace.devices[dev], trace.window):
        if s > t:
            out.append((t, s))
        t = e
    if t < hi:
        out.append((t, hi))
    return out


def idle_by_span(trace: Trace, k: int = 10) -> List[List]:
    """Idle device seconds by the host span that covered them, averaged
    over chips; idle time no span covers is ``NO_SPAN``.  The spans come
    from one thread and do not overlap one another."""
    spans = sorted(trace.spans, key=lambda e: e[1])
    ends = [s + d for _, s, d in spans]
    by: Dict[str, float] = defaultdict(float)
    for dev in trace.devices:
        for g0, g1 in gaps(trace, dev):
            covered = 0.0
            for i in range(bisect.bisect_right(ends, g0), len(spans)):
                name, s, d = spans[i]
                if s >= g1:
                    break
                ov = min(s + d, g1) - max(s, g0)
                if ov > 0:
                    by[name] += ov
                    covered += ov
            by[NO_SPAN] += max(0.0, (g1 - g0) - covered)
    n = max(len(trace.devices), 1)
    ranked = sorted(((nm, v) for nm, v in by.items() if v > 0),
                    key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def save_json(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
