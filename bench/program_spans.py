"""The program's own spans (``repro.core.spans``) in the benchmark's numbers.

Each call's ``SolveStats.spans`` holds its span tree: ``(name, parent
index, start ns, end ns)`` on ``time.monotonic_ns``, the clock of the
harness's own spans; ``SolveStats.host_syncs`` counts its device -> host
reads.  A program without them (no such fields, or none filled) reads as
nothing: every function here returns None for it.

- :func:`ms_per_call` and :func:`syncs_per_call` are what the per-layer
  readers in ``layers/`` report, over every call of the window.
- :func:`reduce_slice` is ``devtrace.reduce_slice`` with each piece of an
  idle gap put on the innermost program span open over it; a piece that no
  program span covers keeps the benchmark's owner (``solve``, ``ready`` or
  ``none``).  It reads the spans from the calls' records, not from the
  slice's XSpace: a profiler session records a ``TraceAnnotation`` only if
  it was open both when the annotation began and when it ended, so a
  slice holds none of the spans that cross its edges.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import devtrace


def _records(stats) -> list:
    return [s for s in stats if getattr(s, "spans", None)]


def ms_per_call(stats, names: Sequence[str], less: Sequence[str] = ()
                ) -> Optional[float]:
    """Mean per call of the time in spans named ``names``, less that in
    their direct children named ``less``, in ms."""
    calls = _records(stats)
    if not calls:
        return None
    total = 0
    for st in calls:
        for i, (name, _, start, end) in enumerate(st.spans):
            if name in names:
                total += end - start
                total -= sum(e - s for n, p, s, e in st.spans
                             if p == i and n in less)
    return total / len(calls) / 1e6


def syncs_per_call(stats) -> Optional[float]:
    calls = _records(stats)
    if not calls:
        return None
    return sum(s.host_syncs for s in calls) / len(calls)


def innermost(spans: Sequence[Tuple[str, int, int]]
              ) -> List[Tuple[str, int, int]]:
    """Nested ``(name, start, end)`` spans cut into disjoint pieces, each
    named for the innermost span open over it."""
    out: List[Tuple[str, int, int]] = []
    stack: list = []                       # (name, end), innermost last
    t = 0

    def close_until(s):
        nonlocal t
        while stack and stack[-1][1] <= s:
            name, end = stack.pop()
            if end > t:
                out.append((name, t, end))
                t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if stack and s > t:
            out.append((stack[-1][0], t, s))
        stack.append((name, e))
        t = s
    close_until(float("inf"))
    return out


def reduce_slice(profile, spans: Sequence[Tuple[str, int, int]],
                 host_t0: int, stats) -> Optional[dict]:
    """``devtrace.reduce_slice(profile, spans, host_t0)`` with idle put on
    the innermost program span of ``stats``' records where one is open.
    Only the ``idle`` sums differ from devtrace's."""
    pieces = innermost([(n, s, e) for st in _records(stats)
                        for n, _, s, e in st.spans])
    covered = [(s, e) for _, s, e in pieces]
    rest = []
    for name, s, e in spans:
        if name == devtrace.ASIDE:
            rest.append((name, s, e))
        else:
            rest += [(name, a, b) for a, b in devtrace._keep(s, e, covered)]
    return devtrace.reduce_slice(profile, rest + pieces, host_t0)
