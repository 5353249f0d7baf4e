"""Host spans and counters of one call, written to two sinks at once.

``span(name)`` marks a stretch of host work at a call-level boundary of the
``run_op`` -> ``solve`` -> selection -> engine path (docs/ENGINES.md,
"Tracing").  It is a ``jax.profiler.TraceAnnotation``, so a profiler
session shows it on the host thread on the device trace's own clock, and an
entry ``(name, parent index, start ns, end ns)`` on ``time.monotonic_ns``
in the calling thread's open record.  The outermost span of a thread opens
the record and closes it; :func:`recorded` hands the closed record to the
call's ``SolveStats`` (``spans``, ``host_syncs``).  :func:`host_int` and
:func:`host_ints` are the device -> host reads of that path, each counted
once in the open record.

Spans go at call-level boundaries only, never inside a per-round, per-tile
or per-worker loop; work on other threads (the scheduler's workers) is not
recorded.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

_local = threading.local()


class Record:
    """The spans and device -> host reads under one outermost span."""

    def __init__(self):
        self.spans: List[span] = []
        self.open: List[int] = []          # indices of the spans still open
        self.host_syncs = 0


class span:
    """``with span(name) as sp:`` -- one span of the open record (opened
    here if none is); ``sp.seconds`` once it has closed."""

    __slots__ = ("name", "parent", "start_ns", "end_ns", "record",
                 "_outermost", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self.end_ns: Optional[int] = None  # None while open

    def __enter__(self) -> "span":
        rec = getattr(_local, "record", None)
        self._outermost = rec is None
        if rec is None:
            rec = _local.record = Record()
        self.record = rec
        self.parent = rec.open[-1] if rec.open else -1   # -1 at the root
        rec.open.append(len(rec.spans))
        rec.spans.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.monotonic_ns()
        self._annotation.__exit__(*exc)
        self.record.open.pop()
        if self._outermost:
            _local.record = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def _count_sync() -> None:
    rec = getattr(_local, "record", None)
    if rec is not None:
        rec.host_syncs += 1


def host_int(x) -> int:
    """``int(x)`` of a device value: a device -> host read, counted."""
    _count_sync()
    return int(x)


def host_ints(x) -> Tuple[int, ...]:
    """The ints of a device vector in one device -> host read, counted
    once."""
    _count_sync()
    return tuple(int(v) for v in jax.device_get(x))


def _attach(stats, root: span):
    """``stats`` with ``root``'s record, once its outermost span has closed;
    unchanged while an enclosing span is open (that one attaches it)."""
    rec = root.record
    if rec.spans[0].end_ns is None:
        return stats
    return dataclasses.replace(
        stats, host_syncs=rec.host_syncs,
        spans=tuple((s.name, s.parent, s.start_ns, s.end_ns)
                    for s in rec.spans))


def recorded(name: str):
    """Run the decorated entry point under span ``name`` and attach the
    record to the ``SolveStats`` it returns: ``(out, stats)``, or a list
    of such pairs (each gets the whole record)."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name) as root:
                result = fn(*args, **kwargs)
            if isinstance(result, list):
                return [(out, _attach(st, root)) for out, st in result]
            out, st = result
            return out, _attach(st, root)
        return call
    return wrap
