"""The trace reduction, on a slice recorded on a TPU v5e and on hand-made
events.

``testdata/morph256_slice_v5e.*``: one 0.25 s slice taken by
``harness.TraceSlicer`` while a 256² seeded morph tile was solved again and
again with ``tiled-pallas`` (T=64, drain batch 4, compiled Mosaic kernels)
and ``frontier``; the ``.spans.json`` holds the benchmark's host spans on
the host's monotonic clock and the clock's reading as the slice opened.
"""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "testdata"


@pytest.fixture(scope="module")
def chip_summary():
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(gzip.decompress(
        (DATA / "morph256_slice_v5e.xspace.pb.gz").read_bytes()))
    host = json.loads((DATA / "morph256_slice_v5e.spans.json").read_text())
    one = devtrace.reduce_slice(profile, host["spans"], host["host_t0"])
    return devtrace.summarize([one, None])


def test_chip_slice_busy_window_and_idle(chip_summary):
    s = chip_summary
    assert s.n_devices == 1 and s.n_slices == 1 and s.n_ops > 1000
    assert s.window_s == pytest.approx(0.25, rel=0.01)
    assert 0 < s.busy_s < s.window_s
    idle = dict(s.idle_by_span)
    # Idle time is accounted to the spans (or to none) exactly once, and a
    # 256² solve is host-bound: most of the gaps fall inside run_op.
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    assert idle["solve"] > 0.5 * (s.window_s - s.busy_s)


def test_chip_slice_finds_the_mosaic_kernel(chip_summary):
    s = chip_summary
    assert 0 < s.mosaic_s < s.busy_s
    names = [n for n, _ in s.top_ops]
    assert any(n.startswith("morph_tile_solve_batched") for n in names)
    assert all(" = " not in n for n in names)
    assert sum(t for _, t in s.top_ops) <= s.busy_s * 1.000001


def _event(name, start, dur, stats=()):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=list(stats))


def _profile(host, ops):
    line = lambda n, ev: SimpleNamespace(name=n, events=ev)  # noqa: E731
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("python", host)]),
        SimpleNamespace(name="/device:TPU:0",
                        lines=[line(devtrace.OPS_LINE, ops)])])


def test_nested_ops_union_self_time_and_idle_attribution():
    # The slice opens at trace time 1000, host clock 5000: host spans move
    # by -4000 onto the trace's clock.
    host = [_event(devtrace.SLICE, 1000, 100)]
    spans = [("solve", 5000, 5080), ("ready", 5080, 5100)]
    ops = [_event("%while.1 = (s32[]) while(...)", 1010, 50),
           _event("%fusion.2 = s32[] fusion(...)", 1020, 10),
           _event("%k.3 = s32[] custom-call(...), custom_call_target="
                  "\"tpu_custom_call\"", 1030, 20),
           _event("%copy.4 = s32[] copy(...)", 1090, 5),
           _event("%late = s32[] copy(...)", 1200, 5)]   # after the slice
    s = devtrace.summarize([devtrace.reduce_slice(_profile(host, ops), spans,
                                                  5000)] * 2)
    assert s.n_slices == 2
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx(110e-9)
    assert s.mosaic_s == pytest.approx(40e-9)
    assert dict(s.top_ops) == pytest.approx(
        {"while.1": 40e-9, "k.3": 40e-9, "fusion.2": 20e-9, "copy.4": 10e-9})
    assert dict(s.idle_by_span) == pytest.approx(
        {"solve": 60e-9, "ready": 30e-9})


def test_aside_spans_leave_the_slice():
    """The benchmark's own work between calls is no part of the window:
    neither its length, nor the ops and idle time inside it."""
    host = [_event(devtrace.SLICE, 1000, 100)]
    spans = [("solve", 5000, 5040), (devtrace.ASIDE, 5040, 5070),
             ("solve", 5070, 5100)]
    ops = [_event("%fusion.1 = s32[] fusion(...)", 1010, 20),
           _event("%copy.2 = s32[] copy(...)", 1045, 10),   # inside aside
           _event("%fusion.3 = s32[] fusion(...)", 1065, 15)]
    s = devtrace.summarize([devtrace.reduce_slice(_profile(host, ops), spans,
                                                  5000)])
    assert s.window_s == pytest.approx(70e-9)
    assert s.busy_s == pytest.approx(30e-9)
    assert dict(s.top_ops) == pytest.approx({"fusion.1": 20e-9,
                                             "fusion.3": 10e-9})
    assert dict(s.idle_by_span) == pytest.approx({"solve": 40e-9})


def test_no_device_ops_or_no_slice_span_reads_nothing():
    slice_span = [_event(devtrace.SLICE, 0, 80)]
    assert devtrace.reduce_slice(_profile(slice_span, []), [], 0) is None
    assert devtrace.reduce_slice(_profile([], [_event("op", 0, 5)]), [],
                                 0) is None
    assert devtrace.summarize([None]) is None
