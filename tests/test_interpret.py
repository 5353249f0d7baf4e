"""The one interpret decision (``repro.kernels.default_interpret``) and the
policies that hang off it: the ``SolveStats.interpret`` echo, the queued
kernels' lowering gap, and the persistent compile-cache directory rule."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as kernels
from repro.core import compile_cache
from repro.data.images import seeded_marker, tissue_image
from repro.kernels.morph_tile import morph_tile_solve_queued
from repro.morph.ops import MorphReconstructOp
from repro.solve import CostModel, InputStats, collect_input_stats, solve


@pytest.fixture(scope="module")
def morph_case():
    _, mask = tissue_image(48, 48, coverage=0.8, seed=1)
    marker = seeded_marker(mask, n_seeds=3, seed=1)
    op = MorphReconstructOp(connectivity=8)
    return op, op.make_state(jnp.asarray(marker.astype(np.int32)),
                             jnp.asarray(mask.astype(np.int32)))


@pytest.fixture
def compiled_backend(monkeypatch):
    """Steer the decision to 'compiled', as on a TPU backend."""
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)


def test_cpu_backend_resolves_to_interpret():
    assert jax.default_backend() != "tpu"
    assert kernels.default_interpret() is True
    assert kernels.resolve_interpret(None) is True
    assert kernels.resolve_interpret(False) is False     # explicit wins
    assert CostModel().interpret is True


@pytest.mark.parametrize("engine", ["frontier", "tiled-pallas"])
def test_solve_stats_echo_resolved_interpret(morph_case, engine):
    op, state = morph_case
    _, st = solve(op, state, engine=engine, tile=16)
    assert st.interpret is True
    _, st = solve(op, state, engine=engine, tile=16, interpret=True)
    assert st.interpret is True


def test_kernel_queue_raises_when_kernels_compile(morph_case, compiled_backend):
    op, state = morph_case
    for engine in ("tiled-pallas", "auto"):
        with pytest.raises(NotImplementedError, match="cumsum"):
            solve(op, state, engine=engine, tile=16, kernel_queue=True)


def test_queued_kernel_guard_names_lowering_gap():
    blk = jnp.zeros((18, 18), jnp.int32)
    with pytest.raises(NotImplementedError, match="cumsum"):
        morph_tile_solve_queued(blk, blk, blk > 0, interpret=False)


def test_candidates_drop_queued_configs_when_compiled(morph_case, monkeypatch):
    op, state = morph_case
    stats = collect_input_stats(op, state)
    queued = [c for c in CostModel().candidates(stats) if c.kernel_queue]
    assert queued                                   # interpret: offered
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    model = CostModel()
    assert model.interpret is False
    assert not [c for c in model.candidates(stats) if c.kernel_queue]
    assert [c for c in model.candidates(stats) if c.engine == "tiled-pallas"]


# The engine grid a TPU v5e measured (benchmarks/engine_grid.py): for each
# input, the InputStats the cost model saw there and the warm seconds of
# each configuration.  Whole-slide tiles of the benchmark's three pools,
# fill_holes and label at 4096², a 3-D morph and EDT volume at 256³.
GRID = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                   / "ENGINE_GRID_v5e.json").read_text())["inputs"]
ROWS = {row["name"]: row for row in GRID}
# Inputs where the model's (engine, tile) is not the measured winner's.
# label: every foreground pixel is a source, so InputStats.depth_est
# guesses ~1.4 rounds where frontier runs 6,059; frontier ran 7% behind
# tiled-pallas T128 K4, and the model before the compiled terms picked it
# too.
MISSES = {"label": ("frontier", None)}


def _grid_stats(row, sources=1.0, tiles=1.0):
    """The row's InputStats, its source count and every active-tile count
    scaled by ``sources`` and ``tiles``."""
    d = dict(row["stats"], shape=tuple(row["stats"]["shape"]))
    d["n_sources"] = max(1, round(d["n_sources"] * sources))
    d["active_tiles"] = {int(t): max(1, round(n * tiles))
                         for t, n in d["active_tiles"].items()}
    return InputStats(**d)


def _label(cfg):
    return (cfg.engine if cfg.tile is None
            else f"{cfg.engine}/T{cfg.tile}/K{cfg.drain_batch}")


@pytest.mark.parametrize("name", sorted(ROWS))
def test_compiled_ranking_picks_the_chip_winner(name, compiled_backend):
    row = ROWS[name]
    top = CostModel().rank(_grid_stats(row))[0][1]
    assert not top.kernel_queue
    win = row["runs"][row["winner"]]
    if name in MISSES:
        assert (top.engine, top.tile) == MISSES[name]
        return
    assert (top.engine, top.tile) == (win["engine"], win["tile"])
    # The drain batch too, or one that ran within 5% of the winner (K=1
    # and K=4 at one tile can sit that close, on stats the model cannot
    # tell apart).
    assert row["runs"][_label(top)]["median_s"] <= 1.05 * win["median_s"]


@pytest.mark.parametrize("sources,tiles", [(0.7, 1.0), (1.3, 1.0),
                                           (1.0, 0.7), (1.0, 1.3),
                                           (0.7, 0.7), (1.3, 1.3),
                                           (0.7, 1.3), (1.3, 0.7)])
def test_compiled_ranking_holds_within_30pct_of_the_grid(sources, tiles,
                                                         compiled_backend):
    """An input like a grid input, with 30% more or fewer sources or active
    tiles, gets the same engine and tile."""
    model = CostModel()
    for name, row in ROWS.items():
        want = model.rank(_grid_stats(row))[0][1]
        got = model.rank(_grid_stats(row, sources, tiles))[0][1]
        assert (got.engine, got.tile) == (want.engine, want.tile), name


def test_compiled_pallas_only_at_compiled_tiles(compiled_backend):
    model = CostModel()
    flat = model.candidates(_grid_stats(ROWS["seeded-1.0"]))
    got = {(c.tile, c.drain_batch) for c in flat if c.engine == "tiled-pallas"}
    assert got == {(64, 1), (64, 4), (128, 1), (128, 4)}
    vol = InputStats(256, 256, 50, {32: 20, 64: 10, 128: 4}, 1,
                     shape=(256, 256, 256), n_offsets=26, op_name="morph")
    got = {c.tile for c in model.candidates(vol) if c.engine == "tiled-pallas"}
    assert got == {32}


# The interpreted ranking of 4096² seeded-morph stats before the compiled
# terms existed: (cost, engine, tile, drain batch, kernel_queue), cheapest
# first.
SEEDED = InputStats(4096, 4096, 4072, {32: 306, 64: 242, 128: 198}, 1,
                    shape=(4096, 4096), op_name="morph")
INTERPRET_RANK = [
    (2205787.762885724, "tiled", 32, 4, False),
    (5407826.51786706, "tiled", 64, 1, False),
    (18790912.053743396, "hybrid", 32, 4, False),
    (21476452.029651683, "hybrid", 64, 1, False),
    (30215000.0, "tiled", 128, 1, False),
    (41367924.55857964, "scheduler", 32, None, False),
    (42527823.90988123, "hybrid", 128, 1, False),
    (71742062.61291198, "tiled-pallas", 32, 4, False),
    (90492014.22765505, "scheduler", 64, None, False),
    (212629605.4877906, "tiled-pallas", 64, 1, False),
    (324571466.9750728, "tiled-pallas", 64, 1, True),
    (406639498.8957482, "tiled-pallas", 32, 4, True),
    (542698400.0, "scheduler", 128, None, False),
    (581766275.0, "tiled-pallas", 128, 1, True),
    (1076914281.5135078, "frontier", None, None, False),
    (1341925400.0, "tiled-pallas", 128, 1, False),
    (1388082682.475484, "sweep", None, None, False),
]


def test_interpreted_costs_unchanged_by_compiled_terms():
    model = CostModel()
    assert model.interpret is True
    got = [(c, cfg.engine, cfg.tile, cfg.drain_batch, cfg.kernel_queue)
           for c, cfg in model.rank(SEEDED)]
    assert got == INTERPRET_RANK


def test_persistent_cache_honours_env_dir(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # set nothing


def test_persistent_cache_defaults_to_checkout_dir(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_persistent_cache()
        assert got == jax.config.jax_compilation_cache_dir
        assert got.endswith("/.jax_cache")
        assert (compile_cache.PERSISTENT_CACHE_DIR.parent / "src" / "repro"
                ).is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
