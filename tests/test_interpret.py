"""The one interpret decision (``repro.kernels.default_interpret``) and the
policies that hang off it: the ``SolveStats.interpret`` echo, the queued
kernels' lowering gap, and the persistent compile-cache directory rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as kernels
from repro.core import compile_cache
from repro.data.images import seeded_marker, tissue_image
from repro.kernels.morph_tile import morph_tile_solve_queued
from repro.morph.ops import MorphReconstructOp
from repro.solve import CostModel, collect_input_stats, solve


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
