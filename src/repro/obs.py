"""Program spans: where the service's host time goes, layer by layer.

``span(name, **args)`` marks one layer boundary (admission, a backend
step, staging a launch, a harvest, booking, result assembly).  It is
inactive unless a JAX profiler session is running
(``jax.profiler.start_trace``) or ``recording()`` is on; inactive, it
costs one check.  Active, it does two things:

- it opens a ``jax.profiler.TraceAnnotation`` named ``repro:<name>``,
  so the span sits in the profiler's trace beside the device
  operations, with its args as event stats;
- it appends a record to an in-process buffer on
  ``time.perf_counter_ns()``: name, start, end, depth, the index of the
  enclosing span, the request id and the args.

``spans()`` returns a snapshot of the buffer, ``dropped()`` the number
of spans the full buffer refused, ``clear()`` empties it.  Spans go at
function boundaries only, never inside a per-lane or per-block loop:
sizes go into ``args``.  A span without a ``rid`` of its own takes its
parent's, so every span under one request's call shares that id.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax
from jax._src.lib import _profiler

PREFIX = "repro:"
CAPACITY = 1 << 20                      # records kept before dropping

_profiling = _profiler.TraceMe.is_enabled


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: Optional[int]               # None while the span is open
    depth: int
    parent: int                         # index in spans(); -1 for a root
    rid: Optional[int]
    args: Dict


class _Buffer:
    """The process's span records, and each thread's stack of open
    spans (indices into ``records``)."""

    def __init__(self):
        self.records: List[list] = []
        self.dropped = 0
        self.forced = 0
        self.local = threading.local()

    def stack(self) -> List[int]:
        st = getattr(self.local, "stack", None)
        if st is None:
            st = self.local.stack = []
        return st


_BUF = _Buffer()


class span:
    """Context manager marking one layer boundary; ``set(**args)``
    adds args known only inside the span (sizes, counts)."""
    __slots__ = ("name", "rid", "args", "_rec", "_tm")

    def __init__(self, name: str, rid: Optional[int] = None, **args):
        self.name = name
        self.rid = rid
        self.args = args
        self._rec = None

    def set(self, **args) -> None:
        self.args.update(args)

    def __enter__(self) -> "span":
        if not (_BUF.forced or _profiling()):
            return self
        recs = _BUF.records
        if len(recs) >= CAPACITY:
            _BUF.dropped += 1
            return self
        stack = _BUF.stack()
        parent = stack[-1] if stack else -1
        rid = self.rid
        if rid is None and parent >= 0:
            rid = recs[parent][5]
        stack.append(len(recs))
        self._rec = rec = [self.name, 0, None, len(stack) - 1, parent,
                           rid, self.args]
        recs.append(rec)
        self._tm = tm = jax.profiler.TraceAnnotation(PREFIX + self.name)
        tm.__enter__()
        rec[1] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        rec = self._rec
        if rec is None:
            return False
        rec[2] = time.perf_counter_ns()
        meta = dict(self.args)
        if rec[5] is not None:
            meta["rid"] = rec[5]
        if meta:
            self._tm.set_metadata(**meta)
        self._tm.__exit__(None, None, None)
        stack, recs = _BUF.stack(), _BUF.records
        if stack and stack[-1] < len(recs) and recs[stack[-1]] is rec:
            stack.pop()                 # else clear() ran inside the span
        self._rec = self._tm = None
        return False


@contextmanager
def recording() -> Iterator[None]:
    """Record spans while inside, with or without the profiler."""
    _BUF.forced += 1
    try:
        yield
    finally:
        _BUF.forced -= 1


def spans() -> List[Span]:
    """Every span recorded since the last ``clear()``, in start order;
    ``parent`` indexes this list."""
    return [Span(*r[:6], dict(r[6])) for r in _BUF.records]


def dropped() -> int:
    """Spans not recorded because the buffer held ``CAPACITY``."""
    return _BUF.dropped


def clear() -> None:
    """Forget every record and the calling thread's open spans."""
    _BUF.records = []
    _BUF.dropped = 0
    _BUF.stack().clear()
