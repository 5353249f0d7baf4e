"""The program's spans in the benchmark: the four per-layer readers, and
idle time put on program spans, on hand-made records, on a slice recorded
on a TPU v5e, and through a traced run on the CPU.

``testdata/morph256_spans_v5e.*``: one 0.25 s slice taken by
``harness.TraceSlicer`` while ``harness.measure`` solved a pool of four
256² tiles of the ``seeded`` traffic through ``run_op`` at its defaults;
the ``.spans.json`` holds the benchmark's host spans and the calls'
``SolveStats.spans`` on the host's monotonic clock, and the clock's
reading as the slice opened.
"""

import gzip
import io
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace
import harness
import program_spans
from loader import BENCH_DIR, load_module
from test_bench_trace import _event, _profile

from repro.solve import SolveStats

DATA = Path(__file__).resolve().parent / "testdata"
READERS = ("entry_ms_per_solve", "select_ms_per_solve",
           "engine_host_ms_per_solve", "host_syncs_per_solve")


def _read(name, stats):
    mod = load_module(BENCH_DIR / "layers" / f"{name}.py")
    return mod.read(harness.LayerContext(stats, 1.0, 0, None))


def _call(t0, syncs):
    """A run_op record: run_op 100 us, of which solve 80 (select 30 with
    its probes 20, engine 40 with waits 25 and 5, calibrate 2)."""
    us = 1000
    spans = (("iwpp.run_op", -1, t0, t0 + 100 * us),
             ("iwpp.build_state", 0, t0, t0 + 10 * us),
             ("iwpp.solve", 0, t0 + 10 * us, t0 + 90 * us),
             ("iwpp.select", 2, t0 + 10 * us, t0 + 40 * us),
             ("iwpp.select.input_stats", 3, t0 + 10 * us, t0 + 30 * us),
             ("iwpp.engine", 2, t0 + 40 * us, t0 + 80 * us),
             ("iwpp.engine.wait", 5, t0 + 50 * us, t0 + 75 * us),
             ("iwpp.engine.wait", 5, t0 + 75 * us, t0 + 80 * us),
             ("iwpp.calibrate", 2, t0 + 80 * us, t0 + 82 * us),
             ("iwpp.extract", 0, t0 + 90 * us, t0 + 100 * us))
    return SolveStats("frontier", spans=spans, host_syncs=syncs)


def test_readers_on_hand_made_records():
    stats = [_call(0, 7), _call(10 ** 9, 9)]
    assert _read("entry_ms_per_solve", stats) == pytest.approx(0.020)
    assert _read("select_ms_per_solve", stats) == pytest.approx(0.032)
    assert _read("engine_host_ms_per_solve", stats) == pytest.approx(0.010)
    assert _read("host_syncs_per_solve", stats) == pytest.approx(8.0)


def test_readers_read_nothing_without_spans():
    empty = [SolveStats("frontier"), SolveStats("tiled", host_syncs=3)]
    older = [SimpleNamespace(engine="frontier", rounds=3)]  # no such fields
    for name in READERS:
        assert _read(name, empty) is None
        assert _read(name, older) is None
        assert _read(name, []) is None


def test_innermost_cuts_nested_spans():
    spans = [("a", 0, 100), ("b", 10, 50), ("c", 20, 30), ("d", 60, 70),
             ("e", 200, 210)]
    assert program_spans.innermost(spans) == [
        ("a", 0, 10), ("b", 10, 20), ("c", 20, 30), ("b", 30, 50),
        ("a", 50, 60), ("d", 60, 70), ("a", 70, 100), ("e", 200, 210)]
    assert program_spans.innermost([]) == []


def test_idle_goes_to_the_innermost_program_span():
    # The slice opens at trace time 1000, host clock 5000 (a shift of
    # -4000).  One call: solve 5000-5080 holding run_op 5005-5075 >
    # select 5010-5040 > input_stats 5010-5030, ready 5080-5100.
    host = [_event(devtrace.SLICE, 1000, 100)]
    spans = [("solve", 5000, 5080), ("ready", 5080, 5100)]
    stats = [SolveStats("frontier", spans=(
        ("iwpp.run_op", -1, 5005, 5075),
        ("iwpp.select", 0, 5010, 5040),
        ("iwpp.select.input_stats", 1, 5010, 5030)))]
    ops = [_event("%fusion.1 = s32[] fusion(...)", 1020, 5),
           _event("%copy.2 = s32[] copy(...)", 1060, 30)]
    profile = _profile(host, ops)
    plain = devtrace.reduce_slice(profile, spans, 5000)
    ours = program_spans.reduce_slice(profile, spans, 5000, stats)
    assert dict(plain["idle"]) == {"solve": 55, "ready": 10}
    assert dict(ours["idle"]) == {
        "solve": 5,                        # 5000-5005
        "iwpp.run_op": 25,                 # 5005-5010, 5040-5060
        "iwpp.select": 10,                 # 5030-5040
        "iwpp.select.input_stats": 15,     # 5010-5030 less the fusion
        "ready": 10}
    assert sum(ours["idle"].values()) == ours["window"] - ours["busy"]
    for k in ("window", "busy", "mosaic", "n_ops", "n_devices", "ops"):
        assert ours[k] == plain[k], k


def test_without_program_spans_idle_is_devtraces():
    host = [_event(devtrace.SLICE, 1000, 100)]
    spans = [("solve", 5000, 5040), (devtrace.ASIDE, 5040, 5070),
             ("solve", 5070, 5100)]
    ops = [_event("%fusion.1 = s32[] fusion(...)", 1010, 20)]
    profile = _profile(host, ops)
    older = [SimpleNamespace(engine="frontier")]
    assert (program_spans.reduce_slice(profile, spans, 5000, older)
            == devtrace.reduce_slice(profile, spans, 5000))


@pytest.fixture(scope="module")
def chip_slice():
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(gzip.decompress(
        (DATA / "morph256_spans_v5e.xspace.pb.gz").read_bytes()))
    host = json.loads((DATA / "morph256_spans_v5e.spans.json").read_text())
    stats = [SimpleNamespace(spans=tuple(tuple(s) for s in call))
             for call in host["program"]]
    return profile, host, stats


def test_chip_slice_idle_lands_on_program_spans(chip_slice):
    profile, host, stats = chip_slice
    one = program_spans.reduce_slice(profile, host["spans"], host["host_t0"],
                                     stats)
    plain = devtrace.summarize([devtrace.reduce_slice(
        profile, host["spans"], host["host_t0"])])
    ours = devtrace.summarize([one])
    # 0.25 s less the benchmark's own work between calls (aside spans).
    assert ours.n_ops > 1000 and 0.2 < ours.window_s <= 0.25
    assert (ours.window_s, ours.busy_s, ours.top_ops) == (
        plain.window_s, plain.busy_s, plain.top_ops)
    idle = one["idle"]                     # every owner, not the top ten
    assert sum(idle.values()) == pytest.approx(one["window"] - one["busy"])
    on_program = sum(v for k, v in idle.items() if k.startswith("iwpp."))
    assert on_program >= 0.9 * (one["window"] - one["busy"]), idle


def test_chip_slice_holds_the_program_spans(chip_slice):
    """The XSpace holds the spans as TraceAnnotations on the host thread,
    each one that the slice saw whole lying on the record's clock once
    moved by the slice's shift."""
    profile, host, stats = chip_slice
    lo = next(e.start_ns for p in profile.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name == devtrace.SLICE)
    shift = lo - host["host_t0"]
    recorded = {(n, s + shift, e + shift) for st in stats
                for n, _, s, e in st.spans}
    traced = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in profile.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name.startswith("iwpp.")]
    assert traced
    for name, s, e in traced:
        # The two clocks are read a few microseconds apart.
        assert any(n == name and abs(s - a) < 50_000 and abs(e - b) < 50_000
                   for n, a, b in recorded), name


@pytest.fixture
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def test_traced_cpu_run_reports_the_four_metrics(_no_persistent_cache):
    line = harness.run_cell("wsi-morph-4k.ih", 2 ** 31 + 7, 0.0, True,
                            t_start=0.0, side=64, require_chip=False,
                            log=io.StringIO())
    m = line["metrics"]
    assert line["correct"]
    # 7 where auto runs frontier, 8 where it runs tiled (tests/test_spans.py)
    assert m["host_syncs_per_solve"]["value"] in (7, 8)
    for name in READERS[:3]:
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms"
