"""The host span tree and device -> host read counter of a solve
(``repro.core.spans``; docs/ENGINES.md "Tracing")."""

import glob
import threading

import jax
import numpy as np
import pytest

import repro.solve as solve_mod
from repro.core import spans
from repro.ops import run_op
from repro.solve import CostModel, solve, solve_batch

SHAPE = (48, 48)


class Prefer(CostModel):
    """Ranks one engine first, whatever the input."""

    def __init__(self, engine):
        super().__init__()
        self.engine = engine

    def cost(self, stats, cfg):
        return 0.0 if cfg.engine == self.engine else 1.0


def _inputs(seed=0, shape=SHAPE):
    rng = np.random.default_rng(seed)
    mask = rng.integers(0, 200, shape).astype(np.int32)
    marker = np.where(rng.random(shape) < 0.05, mask, 0).astype(np.int32)
    return marker, mask


def _tree(st):
    return [(name, parent) for name, parent, _, _ in st.spans]


SELECT = [("iwpp.select", 2), ("iwpp.select.input_stats", 3),
          ("iwpp.select.rank", 3)]
AUTO_FRONTIER = ([("iwpp.run_op", -1), ("iwpp.build_state", 0),
                  ("iwpp.solve", 0)] + SELECT
                 + [("iwpp.engine", 2), ("iwpp.engine.wait", 6),
                    ("iwpp.engine.wait", 6), ("iwpp.calibrate", 2),
                    ("iwpp.extract", 0)])
AUTO_TILED = ([("iwpp.run_op", -1), ("iwpp.build_state", 0),
               ("iwpp.solve", 0)] + SELECT
              + [("iwpp.engine", 2), ("iwpp.engine.prepare", 6),
                 ("iwpp.engine.wait", 6), ("iwpp.engine.wait", 6),
                 ("iwpp.calibrate", 2), ("iwpp.extract", 0)])
EXPLICIT_TILED = [("iwpp.run_op", -1), ("iwpp.build_state", 0),
                  ("iwpp.solve", 0), ("iwpp.engine", 2),
                  ("iwpp.engine.prepare", 3), ("iwpp.engine.wait", 3),
                  ("iwpp.engine.wait", 3), ("iwpp.extract", 0)]

# (solve keywords, span tree, host syncs): 1 read of the selection probe
# (the frontier population and the active tiles at each of 3 tile sizes, in
# one vector); the dense adapter reads rounds and the two words of the
# source count, the tiled one its four counters.
CASES = {
    "auto-frontier": (dict(cost_model=Prefer("frontier")), AUTO_FRONTIER, 4),
    "auto-tiled": (dict(cost_model=Prefer("tiled")), AUTO_TILED, 5),
    "explicit-tiled": (dict(engine="tiled"), EXPLICIT_TILED, 4),
}


def _assert_nested(st):
    for i, (name, parent, start, end) in enumerate(st.spans):
        assert start <= end, name
        if parent >= 0:
            _, _, p_start, p_end = st.spans[parent]
            assert parent < i and p_start <= start and end <= p_end, name
    # Siblings follow one another.
    for i, (_, parent, start, _) in enumerate(st.spans):
        earlier = [s for s in st.spans[:i] if s[1] == parent]
        if earlier:
            assert earlier[-1][3] <= start


@pytest.mark.parametrize("case", sorted(CASES))
def test_span_tree_names_parents_and_nesting(case):
    kw, tree, _ = CASES[case]
    marker, mask = _inputs()
    _, st = run_op("morph", marker, mask, connectivity=8, **kw)
    assert st.engine == ("frontier" if case == "auto-frontier" else "tiled")
    assert _tree(st) == tree
    _assert_nested(st)


@pytest.mark.parametrize("case", sorted(CASES))
def test_host_syncs_pinned(case):
    kw, _, syncs = CASES[case]
    marker, mask = _inputs()
    for _ in range(2):                     # cold, then warm: the same reads
        _, st = run_op("morph", marker, mask, connectivity=8, **kw)
        assert st.host_syncs == syncs


@pytest.mark.parametrize("engine", ["frontier", "sweep", "tiled"])
def test_wall_time_is_the_engine_span(engine):
    marker, mask = _inputs()
    _, st = run_op("morph", marker, mask, connectivity=8, engine=engine)
    (engine_span,) = [s for s in st.spans if s[0] == "iwpp.engine"]
    assert st.wall_time_s == (engine_span[3] - engine_span[2]) / 1e9 > 0


def test_solve_called_directly_is_the_root():
    marker, mask = _inputs()
    _, st = solve("morph", (marker, mask), connectivity=8, engine="frontier")
    assert _tree(st) == [("iwpp.solve", -1), ("iwpp.build_state", 0),
                         ("iwpp.engine", 0), ("iwpp.engine.wait", 2),
                         ("iwpp.engine.wait", 2)]
    assert st.host_syncs == 3


def test_solve_batch_shares_one_record():
    pairs = [_inputs(seed) for seed in range(3)]
    res = solve_batch("morph", pairs, engine="frontier")
    for _, st in res:
        assert _tree(st) == [("iwpp.solve_batch", -1), ("iwpp.engine", 0),
                             ("iwpp.engine.prepare", 1),
                             ("iwpp.engine.wait", 1)]
        assert st.spans == res[0][1].spans
        assert st.host_syncs == 3 * len(pairs)
        (engine_span,) = [s for s in st.spans if s[0] == "iwpp.engine"]
        assert st.wall_time_s == (engine_span[3] - engine_span[2]) / 1e9


def test_span_count_does_not_grow_with_rounds():
    """Spans sit at call-level boundaries: a solve of many rounds records
    as many as one of a few."""
    mask = np.full((64, 64), 100, np.int32)
    one_seed = np.zeros_like(mask)
    one_seed[0, 0] = 100
    dense_seeds = np.where(np.indices(mask.shape).sum(0) % 4 == 0, 100, 0)
    counts = {}
    for name, marker in (("long", one_seed), ("short", dense_seeds)):
        for engine, kw in (("frontier", {}), ("tiled", dict(tile=16))):
            _, st = run_op("morph", marker.astype(np.int32), mask,
                           connectivity=8, engine=engine, **kw)
            counts[name, engine] = (st.rounds, len(st.spans), st.host_syncs)
    for engine in ("frontier", "tiled"):
        long, short = counts["long", engine], counts["short", engine]
        assert long[0] > short[0]
        assert long[1:] == short[1:]


def test_engine_that_raises_leaves_no_span_open(monkeypatch):
    def broken(*args, **kwargs):
        with spans.span("iwpp.engine.wait"):
            raise RuntimeError("injected")

    marker, mask = _inputs()
    with monkeypatch.context() as m:
        m.setitem(solve_mod._ENGINE_RUNNERS, "frontier", broken)
        with pytest.raises(RuntimeError, match="injected"):
            run_op("morph", marker, mask, connectivity=8,
                   cost_model=Prefer("frontier"))
    assert getattr(spans._local, "record", None) is None
    _, st = run_op("morph", marker, mask, connectivity=8,
                   cost_model=Prefer("frontier"))
    assert _tree(st) == AUTO_FRONTIER and st.host_syncs == 4
    _assert_nested(st)


def test_records_are_per_thread():
    marker, mask = _inputs()
    results, errors = {}, []

    def call(engine):
        try:
            results[engine] = run_op("morph", marker, mask, connectivity=8,
                                     engine=engine)[1]
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=call, args=(e,))
               for e in ("frontier", "tiled")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors
    assert _tree(results["tiled"]) == EXPLICIT_TILED
    assert results["frontier"].host_syncs == 3
    assert getattr(spans._local, "record", None) is None


def test_spans_reach_the_profiler_and_change_no_output(tmp_path):
    marker, mask = _inputs()
    kw = dict(connectivity=8, cost_model=Prefer("tiled"))
    plain, st_plain = run_op("morph", marker, mask, **kw)
    with jax.profiler.trace(str(tmp_path)):
        traced, st_traced = run_op("morph", marker, mask, **kw)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(traced))
    assert _tree(st_traced) == _tree(st_plain) == AUTO_TILED
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            host |= {e.name for line in plane.lines for e in line.events}
    assert {name for name, _ in AUTO_TILED} <= host
