"""Reduce profiler trace slices to the benchmark's device numbers.

A traced run records a few short slices of its window (``harness``:
``TraceSlicer``), each an XSpace with a ``trace_slice`` span on a host
thread that marks the slice's extent.  For each slice:

- busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the slice
  and averaged over the devices;
- Mosaic time: busy time of Pallas kernels, which reach the device as
  Mosaic custom calls (``tpu_custom_call``);
- per-op self time: an op's time not covered by ops nested in it (a
  ``while`` op's event spans its whole body);
- idle time by host span: the benchmark's own spans (``solve``, ``ready``),
  taken on the host's monotonic clock and moved onto the trace's clock by
  the slice span's start.

Time inside an ``aside`` span, the benchmark's own work between calls
that the window's seconds leave out, is left out of the slice too: of its
length, its busy time and its ops.

The slices' numbers add up to one ``TraceSummary``.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

SLICE = "trace_slice"
ASIDE = "aside"
OPS_LINE = "XLA Ops"
_MOSAIC_MARKS = ("tpu_custom_call", "mosaic")


@dataclasses.dataclass(frozen=True)
class TraceSummary:
    window_s: float          # total length of the slices
    busy_s: float            # mean over devices of the union of op intervals
    mosaic_s: float          # mean over devices, inside the busy time
    n_devices: int
    n_ops: int
    n_slices: int
    top_ops: List[Tuple[str, float]]      # (op name, device self seconds)
    idle_by_span: List[Tuple[str, float]]  # (host span or "none", seconds)


def op_name(event_name: str) -> str:
    """``%fusion.56 = (pred[4]...) fusion(...)`` -> ``fusion.56``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _is_mosaic(event) -> bool:
    text = [event.name] + [v for _, v in event.stats if isinstance(v, str)]
    return any(m in t.lower() for t in text for m in _MOSAIC_MARKS)


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(ivals) -> Dict[str, float]:
    """Per-name time not covered by nested ops."""
    out: Dict[str, float] = collections.Counter()
    stack: list = []                 # [start, end, name, covered by children]

    def close(top):
        out[top[2]] += (top[1] - top[0]) - top[3]

    # Outer ops first where a parent and its first child start together.
    for s, e, name in sorted(ivals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, name, 0.0])
    while stack:
        close(stack.pop())
    return out


def _overlap(a, b) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def _pieces(s, t, keep):
    """The parts of ``[s, t)`` inside the sorted disjoint ``keep``."""
    return [(max(s, a), min(t, b)) for a, b in keep if min(t, b) > max(s, a)]


def _keep(lo, hi, aside) -> List[Tuple[float, float]]:
    """``[lo, hi)`` less the union of the ``aside`` intervals."""
    keep, t = [], lo
    for s, e in _union(aside):
        if s > t:
            keep.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        keep.append((t, hi))
    return [(a, b) for a, b in keep if b > a]


def reduce_slice(profile, spans: Sequence[Tuple[str, int, int]],
                 host_t0: int) -> Optional[dict]:
    """One slice's sums (ns).  ``spans``: (name, start, end) on the host's
    monotonic clock in ns; ``host_t0``: that clock just before the slice
    span opened.  None when the slice holds no slice span or no device op.
    """
    window = None
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SLICE:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events = list(line.events)
                    if events:
                        devices.append(events)
    if window is None or not devices:
        return None
    lo, hi = window
    shift = lo - host_t0
    spans = [(n, s + shift, e + shift) for n, s, e in spans]
    keep = _keep(lo, hi, [(s, e) for n, s, e in spans if n == ASIDE])
    spans = [x for x in spans if x[0] != ASIDE]
    out = {"window": sum(b - a for a, b in keep), "busy": 0.0, "mosaic": 0.0,
           "n_ops": 0, "n_devices": len(devices),
           "ops": collections.Counter(), "idle": collections.Counter()}
    mosaic_names: Dict[str, bool] = {}
    for events in devices:
        ivals, mosaic_ivals, n_ops = [], [], 0
        for e in events:
            pieces = _pieces(e.start_ns, e.start_ns + e.duration_ns, keep)
            if not pieces:
                continue
            n_ops += 1
            name = op_name(e.name)
            ivals += [(s, t, name) for s, t in pieces]
            if name not in mosaic_names:
                mosaic_names[name] = _is_mosaic(e)
            if mosaic_names[name]:
                mosaic_ivals += pieces
        out["n_ops"] += n_ops
        out["ops"].update(_self_times(ivals))
        busy = _union([(s, t) for s, t, _ in ivals])
        out["busy"] += sum(t - s for s, t in busy)
        out["mosaic"] += sum(t - s for s, t in _union(mosaic_ivals))
        gaps = []
        for a, b in keep:
            t = a
            for s, e in _pieces(a, b, busy):
                if s > t:
                    gaps.append((t, s))
                t = e
            if t < b:
                gaps.append((t, b))
        for gap in gaps:
            left = gap[1] - gap[0]
            for name, s, e in spans:
                o = _overlap(gap, (s, e))
                if o:
                    out["idle"][name] += o
                    left -= o
            if left > 0:
                out["idle"]["none"] += left
    return out


def summarize(slices: Sequence[Optional[dict]], top: int = 10
              ) -> Optional[TraceSummary]:
    """Add up the slices' sums; None when no slice read anything."""
    slices = [s for s in slices if s is not None]
    if not slices:
        return None
    n = slices[0]["n_devices"]
    ops: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    for s in slices:
        ops.update(s["ops"])
        idle.update(s["idle"])
    ns = 1e-9
    return TraceSummary(
        window_s=sum(s["window"] for s in slices) * ns,
        busy_s=sum(s["busy"] for s in slices) * ns / n,
        mosaic_s=sum(s["mosaic"] for s in slices) * ns / n,
        n_devices=n, n_ops=sum(s["n_ops"] for s in slices),
        n_slices=len(slices),
        top_ops=[(k, v * ns / n) for k, v in ops.most_common(top)],
        idle_by_span=[(k, v * ns / n) for k, v in idle.most_common(top)])
