"""``correct`` on the CPU at a small size: a sound run passes; the
lower-precision control and each fault the cells can have fail.

Each case drives a whole run through ``harness.run_cell`` with the device
gate skipped and the timed path replaced underneath.
"""

import io
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

import harness

CELLS = ["wsi-morph-4k.seeded", "wsi-edt-4k.disks", "wsi-morph-4k.ih"]
SIDE = 128
SEED = 2 ** 31 + 99


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    # Test workers run other files too: leave JAX's global cache alone.
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def _stats():
    return SimpleNamespace(engine="fake", tile=None, drain_batch=None,
                           rounds=1, tiles_processed=0)


def _run(cell, solver=None):
    return harness.run_cell(cell, SEED, 0.0, False, t_start=0.0,
                            solver=solver, side=SIDE, require_chip=False,
                            log=io.StringIO())


def _parts(cell):
    c = harness.load_cell(cell)
    opmod = harness.load_module(harness.BENCH_DIR / "ops" /
                                f"{c.config['op']}.py")
    return c.config, opmod, harness.default_solver(c.config, opmod)


def _initial(config, opmod, tile):
    """The op's result read from its initial state: no step taken."""
    from repro.ops import get_op
    spec = get_op(config["op"])
    op = spec.make_op(config["connectivity"])
    return spec.extract(op, spec.build_state(op, *opmod.inputs(tile)))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"mpix_per_s", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_fails(cell):
    config, opmod, _ = _parts(cell)
    line = _run(cell, lambda tile: (opmod.control(tile, config), _stats()))
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_fails(cell, fault):
    config, opmod, solve = _parts(cell)

    def broken(tile):
        out, st = solve(tile)
        out = jnp.asarray(out)
        if fault == "unchanged":
            return _initial(config, opmod, tile), st
        if fault == "half_left_out":
            init = jnp.asarray(_initial(config, opmod, tile))
            h = out.shape[0] // 2
            return out.at[h:].set(init[h:]), st
        # One answer altered where it is produced: a foreground pixel's
        # distance set to 0 (EDT), a gray level raised by one (morph).
        if config["op"] == "edt":
            r, c = np.unravel_index(int(jnp.argmax(out)), out.shape)
            return out.at[r, c].set(0), st
        return out.at[0, 0].add(1), st

    line = _run(cell, broken)
    assert not line["correct"], (fault, line["checks"])


def test_window_keeps_its_sample_on_the_host_and_counts_pixels(monkeypatch):
    """Every result leaves the device with its call, and the collector runs
    after each; the sample is a
    reservoir of host copies, whose seconds the window leaves out with the
    collector's; the
    pixels solved come from each tile's own input, whatever its shape."""
    pool = [{"x": jnp.zeros((4, 6), jnp.int32) + i} for i in range(3)]
    pool.append({"x": jnp.ones((2, 3, 5), jnp.int32)})
    px = [24, 24, 24, 30]
    calls, collected = [], []

    def solver(tile):
        calls.append(len(collected))
        return tile["x"] + 1, _stats()

    monkeypatch.setattr(harness, "collect", lambda: collected.append(1))
    w = harness.measure(pool, px, solver, 0.0, SEED, None, io.StringIO())
    assert calls == [0, 1, 2, 3] and len(collected) == len(pool)
    assert len(w.stats) == len(pool)
    assert w.px == sum(px) and w.failed == 0
    assert len(w.sample) == harness.SAMPLES
    for i, got in w.sample:
        assert isinstance(got, np.ndarray)
        np.testing.assert_array_equal(got, np.asarray(pool[i]["x"]) + 1)
    assert w.aside_s > 0 and w.seconds == pytest.approx(sum(w.pass_s))


def test_failed_call_in_window_makes_run_incorrect():
    _, _, solve = _parts("wsi-morph-4k.ih")
    tiles = len(harness.load_cell("wsi-morph-4k.ih").traffic["coverages"])
    calls = []

    def fails_once_warm(tile):
        calls.append(1)
        if len(calls) == tiles + 2:    # the window's second call
            raise RuntimeError("device lost")
        return solve(tile)

    line = _run("wsi-morph-4k.ih", fails_once_warm)
    assert line["failed"] == 1 and not line["correct"]
    assert line["attempted"] == tiles
