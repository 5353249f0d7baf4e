"""The selection probe (``collect_input_stats``): one jitted program and one
device -> host read, equal field for field to the eager probes it replaced
(docs/ENGINES.md "Tracing")."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compile_cache, spans
from repro.core.pattern import shiftnd, tree_shape
from repro.ops import get_op, run_op, spec_for
from repro.solve import DEFAULT_TILES, InputStats, collect_input_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def eager_input_stats(op, state, n_devices=1, tiles=DEFAULT_TILES):
    """The probes as they ran before the jitted one, op by op: the frontier
    population, then at each tile size the tiles holding a pixel of the
    frontier dilated by the op's offsets, each count read on its own."""
    spatial = tree_shape(state, op.ndim)
    ndim = op.ndim
    n_sources = int(jnp.sum(op.init_frontier(state)))
    active = {}
    for t in tiles:
        f = op.init_frontier(state)
        dil = f
        for off in op.offsets:
            dil = dil | shiftnd(f, off, False)
        grid = tuple(-(-s // t) for s in spatial)
        fp = jnp.pad(dil, [(0, g * t - s) for g, s in zip(grid, spatial)])
        inter = []
        for g in grid:
            inter += [g, t]
        active[t] = int(jnp.sum(fp.reshape(tuple(inter)).any(
            axis=tuple(range(1, 2 * ndim, 2)))))
    spec = spec_for(op)
    return InputStats(spatial[-2], spatial[-1], n_sources, active, n_devices,
                      bytes_per_pixel=spec.bytes_per_pixel,
                      round_cost_weight=spec.round_cost_weight,
                      shape=spatial, n_offsets=len(op.offsets),
                      op_name=spec.name)


def _morph(rng, shape, conn, kind="seeded"):
    mask = rng.integers(1, 200, shape).astype(np.int32)
    if kind == "empty":            # J == I: nothing can still propagate
        marker = mask
    else:
        marker = np.where(rng.random(shape) < 0.02, mask, 0).astype(np.int32)
    op = get_op("morph").make_op(conn)
    return op, op.make_state(jnp.asarray(marker), jnp.asarray(mask))


def _morph_on_tile_corners(shape, conn):
    """Two seeds on the last pixel of a 32- and a 64-px tile: only the
    dilation puts their neighbours' tiles in the count."""
    mask = np.full(shape, 100, np.int32)
    marker = np.zeros(shape, np.int32)
    marker[31, 31] = marker[63, 63] = 100
    op = get_op("morph").make_op(conn)
    return op, op.make_state(jnp.asarray(marker), jnp.asarray(mask))


def _edt(rng, shape, conn):
    op = get_op("edt").make_op(conn)
    return op, op.make_state(jnp.asarray(rng.random(shape) < 0.9))


def _fill(rng, shape, conn):
    op = get_op("fill_holes").make_op(conn)
    return op, op.make_state(jnp.asarray(rng.random(shape) < 0.45))


def _label(rng, shape, conn, kind="random"):
    op = get_op("label").make_op(conn)
    fg = (np.ones(shape, bool) if kind == "full"
          else rng.random(shape) < 0.55)
    return op, op.make_state(jnp.asarray(fg))


# name -> (state builder(rng), tiles)
CASES = {
    "morph-conn4": (lambda r: _morph(r, (64, 64), "conn4"), DEFAULT_TILES),
    "morph-conn8-100x77": (lambda r: _morph(r, (100, 77), "conn8"),
                           DEFAULT_TILES),
    "morph-conn6-3d": (lambda r: _morph(r, (20, 24, 18), "conn6"),
                       (8, 16)),
    "morph-conn26-3d": (lambda r: _morph(r, (20, 24, 18), "conn26"),
                        (8, 16)),
    "morph-seeds-on-tile-corners": (
        lambda r: _morph_on_tile_corners((100, 77), 8), DEFAULT_TILES),
    "morph-empty-frontier": (lambda r: _morph(r, (64, 48), 8, "empty"),
                             DEFAULT_TILES),
    "edt-conn4": (lambda r: _edt(r, (64, 64), "conn4"), DEFAULT_TILES),
    "edt-conn8-100x77": (lambda r: _edt(r, (100, 77), "conn8"),
                         DEFAULT_TILES),
    "edt-conn6-3d": (lambda r: _edt(r, (20, 24, 18), "conn6"), (8, 16)),
    "edt-conn26-3d": (lambda r: _edt(r, (20, 24, 18), "conn26"), (8, 16)),
    "fill_holes-conn4": (lambda r: _fill(r, (64, 64), "conn4"),
                         DEFAULT_TILES),
    "fill_holes-conn8-100x77": (lambda r: _fill(r, (100, 77), "conn8"),
                                DEFAULT_TILES),
    "label-conn4": (lambda r: _label(r, (64, 64), "conn4"), DEFAULT_TILES),
    "label-conn8-full-frontier": (lambda r: _label(r, (64, 80), "conn8",
                                                   "full"), DEFAULT_TILES),
    "morph-explicit-tile": (lambda r: _morph(r, (100, 77), 8), (48,)),
    "edt-explicit-tile": (lambda r: _edt(r, (64, 64), 8), (16,)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_probe_equals_eager_probes(case):
    build, tiles = CASES[case]
    op, state = build(np.random.default_rng(1700))
    got = collect_input_stats(op, state, 1, tiles)
    want = eager_input_stats(op, state, 1, tiles)
    assert got == want
    assert all(type(v) is int for v in
               (got.n_sources, *got.active_tiles, *got.active_tiles.values()))
    if case == "morph-empty-frontier":
        assert got.n_sources == 0 and set(got.active_tiles.values()) == {0}
    if case == "morph-seeds-on-tile-corners":
        assert got.n_sources == 2
        assert got.active_tiles == {32: 7, 64: 4, 128: 1}
    if case == "label-conn8-full-frontier":
        # Every pixel but the one holding the smallest label is a source.
        assert got.n_sources == got.area - 1
        assert all(got.active_tiles[t] == got.n_tiles(t) for t in tiles)


def test_probe_on_a_sharded_state_equals_eager_probes():
    """A state sharded over a 2x4 mesh of virtual CPU devices gives the same
    counts, read as one replicated vector."""
    shape = (96, 80)
    body = f"""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import spans
        from repro.ops import get_op
        from repro.solve import collect_input_stats
        rng = np.random.default_rng(1700)
        mask = rng.integers(1, 200, {shape}).astype(np.int32)
        marker = np.where(rng.random({shape}) < 0.02, mask, 0).astype(np.int32)
        op = get_op("morph").make_op(8)
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
        state = jax.device_put(
            op.make_state(jnp.asarray(marker), jnp.asarray(mask)),
            NamedSharding(mesh, P("data", "model")))
        assert len(state["J"].sharding.device_set) == 8
        with spans.span("probe") as sp:
            st = collect_input_stats(op, state, n_devices=8)
        assert sp.record.host_syncs == 1
        d = dataclasses.asdict(st)
        d["active_tiles"] = sorted(d["active_tiles"].items())
        print(json.dumps(d))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    got = json.loads(r.stdout.strip().splitlines()[-1])
    got["active_tiles"] = dict(got["active_tiles"])
    got["shape"] = tuple(got["shape"])
    op, state = _morph(np.random.default_rng(1700), shape, 8)
    assert InputStats(**got) == eager_input_stats(op, state, n_devices=8)


def _inputs(seed, shape=(48, 48)):
    rng = np.random.default_rng(seed)
    mask = rng.integers(0, 200, shape).astype(np.int32)
    marker = np.where(rng.random(shape) < 0.05, mask, 0).astype(np.int32)
    return marker, mask


def test_auto_reads_the_probe_once_and_does_not_retrace(monkeypatch):
    reads = []                      # innermost open span at each read
    count = spans._count_sync

    def where(*args):
        rec = spans._local.record
        reads.append(rec.spans[rec.open[-1]].name)
        count(*args)

    monkeypatch.setattr(spans, "_count_sync", where)
    _, st = run_op("morph", *_inputs(0), connectivity=8)
    assert reads.count("iwpp.select.input_stats") == 1
    assert len(reads) == st.host_syncs

    compiles = []

    def on_event(name, secs, **_):
        if name == BACKEND_COMPILE:
            compiles.append(name)

    misses = compile_cache.misses()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        reads.clear()
        _, st2 = run_op("morph", *_inputs(1), connectivity=8)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert st2.engine == st.engine
    assert reads.count("iwpp.select.input_stats") == 1
    assert compile_cache.misses() == misses
    assert compiles == []


def test_probe_is_built_once_per_op_and_tiles():
    op, state = _morph(np.random.default_rng(3), (40, 40), 8)
    key = ("input-probe", type(op), op.connectivity, op.ndim, (8, 16))
    collect_input_stats(op, state, tiles=[8, 16])
    assert compile_cache.contains(key)
    misses = compile_cache.misses()
    op2, state2 = _morph(np.random.default_rng(4), (40, 40), 8)
    assert (collect_input_stats(op2, state2, tiles=(8, 16))
            == eager_input_stats(op2, state2, tiles=(8, 16)))
    assert compile_cache.misses() == misses
