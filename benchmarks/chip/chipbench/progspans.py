"""The service's own spans, read beside the device trace.

While a profiler session runs, the service records a span at each of
its layer boundaries (``repro.obs``): name, start and end on
``time.perf_counter_ns``, depth, parent, request id and args.  The
reduced trace holds the benchmark's ``bench:`` spans on the profiler's
clock, and each call into the session (``submit``, ``poll``,
``result``) sits in one of them.  ``load`` pairs the k-th program root
span of a name with the k-th ``bench:`` span of that name; each pair
bounds the offset from the program's clock to the trace's::

    bench_start - prog_start  <=  offset  <=  bench_end - prog_end

The offset is the midpoint of the intersection over all pairs, and the
intersection's width is the alignment residual.  ``load`` returns None
when the program recorded no spans (a service without ``repro.obs``),
dropped any, or recorded a different number of root calls than the
benchmark made, or when the intersection is empty by more than
``TOLERANCE_NS``.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace as tr

ROOTS = ("session.poll", "session.submit", "session.result")
BENCH_PREFIX = "bench:"
TOLERANCE_NS = 50_000.0

# a program span as repro.obs.spans() gives it:
# (name, start_ns, end_ns, depth, parent, rid, args)
Span = Tuple


def recorded() -> Optional[List[Span]]:
    """The service's spans, or None where it records none or dropped
    some."""
    try:
        from repro import obs
    except ImportError:
        return None
    if obs.dropped() > 0:
        return None
    return [tuple(s) for s in obs.spans()]


def align(spans: Sequence[Span], bench: Iterable[tr.Event]
          ) -> Optional[Tuple[float, float]]:
    """(offset, residual) from the program's clock to the trace's, or
    None where the root calls cannot be paired."""
    lo, hi, pairs = float("-inf"), float("inf"), 0
    for name in ROOTS:
        prog = [(s[1], s[2]) for s in spans
                if s[0] == name and s[3] == 0 and s[2] is not None]
        marks = sorted((s, s + d) for n, s, d in bench
                       if n == BENCH_PREFIX + name)
        if len(prog) != len(marks):
            return None
        for (ps, pe), (bs, be) in zip(prog, marks):
            lo, hi = max(lo, bs - ps), min(hi, be - pe)
        pairs += len(prog)
    if not pairs or lo > hi + TOLERANCE_NS:
        return None
    return (lo + hi) / 2.0, hi - lo


def _covered_ns(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in tr.union(intervals, float("-inf"),
                                          float("inf")))


@dataclass
class Program:
    """The program's spans of the traced window, on the trace's clock
    once shifted by ``offset_ns``."""
    spans: List[Span]
    offset_ns: float
    residual_ns: float

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s[0] == name and s[2] is not None]

    def count(self, name: str) -> int:
        return len(self.named(name))

    def total_ns(self, name: str) -> float:
        return float(sum(s[2] - s[1] for s in self.named(name)))

    def arg_sum(self, name: str, key: str) -> float:
        return float(sum(s[6].get(key, 0) for s in self.named(name)))

    def self_ns(self) -> Dict[str, float]:
        """Summed self time by name: each span's duration less the
        union of its children's intervals."""
        kids: Dict[int, List[Tuple[float, float]]] = defaultdict(list)
        for s in self.spans:
            if s[4] >= 0 and s[2] is not None:
                kids[s[4]].append((s[1], s[2]))
        out: Dict[str, float] = defaultdict(float)
        for i, s in enumerate(self.spans):
            if s[2] is not None:
                out[s[0]] += (s[2] - s[1]) - _covered_ns(kids.get(i, []))
        return dict(out)

    def intervals(self, names: Sequence[str]) -> List[Tuple[float, float]]:
        """The named spans' intervals on the trace's clock."""
        off = self.offset_ns
        return [(s[1] + off, s[2] + off) for s in self.spans
                if s[0] in names and s[2] is not None]


def load(w) -> Optional[Program]:
    """The aligned program spans of a traced window, or None."""
    if w.trace is None:
        return None
    spans = recorded()
    al = align(spans, w.trace.spans) if spans else None
    return None if al is None else Program(spans, *al)


def per_estimate_ms(w, ns: Optional[float]) -> Optional[float]:
    """Nanoseconds over the window's completed estimates, in ms."""
    if ns is None or not w.completed:
        return None
    return ns / len(w.completed) / 1e6


def idle_under(prog: Program, trace: tr.Trace,
               names: Sequence[str]) -> Optional[float]:
    """Share of the window in which a chip is idle while the host is
    inside one of the named spans, the mean over chips."""
    if not trace.devices or trace.window_ns <= 0:
        return None
    host = tr.union(prog.intervals(names), *trace.window)
    total = 0.0
    for dev in trace.devices:
        gaps, i = tr.gaps(trace, dev), 0
        for g0, g1 in gaps:
            while i < len(host) and host[i][1] <= g0:
                i += 1
            j = i
            while j < len(host) and host[j][0] < g1:
                total += min(g1, host[j][1]) - max(g0, host[j][0])
                j += 1
    return total / len(trace.devices) / trace.window_ns
