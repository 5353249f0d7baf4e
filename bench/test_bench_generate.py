"""The traffic generator: deterministic from the seed, near its coverages,
and its generators found by name."""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import generate
from loader import BENCH_DIR, BenchError, load_module

TRAFFIC = BENCH_DIR / "traffic"
BIG_SEED = 2 ** 31 + 12345


def _traffic(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _gen(name):
    return load_module(BENCH_DIR / "generators" / f"{name}.py")


def _host(pool):
    return [{k: np.asarray(v) for k, v in t.items()} for t in pool]


@pytest.mark.parametrize("name", ["seeded", "ih", "disks"])
def test_pool_is_deterministic_in_the_seed(name):
    traffic = _traffic(name)
    a = _host(generate.make_pool(traffic, 96, BIG_SEED))
    b = _host(generate.make_pool(traffic, 96, BIG_SEED))
    c = _host(generate.make_pool(traffic, 96, BIG_SEED + 3))
    assert len(a) == len(traffic["coverages"])
    for ta, tb in zip(a, b):
        assert ta.keys() == tb.keys()
        for k in ta:
            np.testing.assert_array_equal(ta[k], tb[k])
    assert any(not np.array_equal(ta[k], tc[k])
               for ta, tc in zip(a, c) for k in ta)


@pytest.mark.parametrize("coverage", [0.25, 0.5, 0.75, 1.0])
def test_tissue_coverage_and_gray_levels(coverage):
    mask = np.asarray(_gen("tissue").tissue(
        generate.seed_key(3), jnp.float32(coverage), side=128))
    assert mask.dtype == np.int32
    assert abs((mask > 0).mean() - coverage) < 0.01
    tissue = mask[mask > 0]
    assert tissue.min() >= 30 and tissue.max() <= 230


def test_seeded_marker_is_mask_on_patches_only():
    mask = _gen("tissue").tissue(generate.seed_key(5), jnp.float32(0.5),
                                 side=128)
    marker = np.asarray(_gen("seeded_marker").seeded_marker(
        generate.seed_key(6), mask, n_seeds=8, patch=3))
    mask = np.asarray(mask)
    on = marker > 0
    np.testing.assert_array_equal(marker[on], mask[on])
    # 8 patches of at most 6x6 pixels, at least one tissue pixel each.
    assert 8 <= on.sum() <= 8 * 36


def test_ih_marker_drops_h():
    mask = _gen("tissue").tissue(generate.seed_key(7), jnp.float32(0.75),
                                 side=64)
    marker = np.asarray(_gen("ih_marker").ih_marker(mask, jnp.int32(40)))
    np.testing.assert_array_equal(marker, np.maximum(np.asarray(mask) - 40, 0))


@pytest.mark.parametrize("coverage", [0.25, 0.5, 0.75, 0.9])
def test_bg_disks_coverage(coverage):
    """Six disks at uniform centres cover at most their area, less where
    they overlap or cross the edge: the foreground share is at least the
    nominal coverage."""
    gen = _gen("bg_disks")
    side, n = 256, 6
    r = gen.disk_radius(side, coverage, n)
    for s in range(4):
        fg = np.asarray(gen.bg_disks(generate.seed_key(s), jnp.int32(r),
                                     side=side, n_disks=n))
        assert fg.dtype == bool and not fg.all()
        assert coverage - 0.01 <= fg.mean() < 1.0


def test_pinned_steps_and_shuffled_order_give_every_run_the_same_tiles():
    """``disks`` pins its layouts: every seed solves the same four tiles,
    in an order drawn from the seed."""
    traffic = _traffic("disks")
    pools = {s: _host(generate.make_pool(traffic, 128, s))
             for s in (1, 2, 3, BIG_SEED)}
    key = lambda pool: sorted(t["fg"].tobytes() for t in pool)  # noqa: E731
    assert key(pools[1]) == key(pools[2]) == key(pools[BIG_SEED])
    assert len(set(key(pools[1]))) == len(traffic["coverages"])
    orders = {tuple(t["fg"].tobytes() for t in pool)
              for pool in pools.values()}
    assert len(orders) > 1


def test_a_new_generator_is_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "generators").mkdir()
    (tmp_path / "generators" / "ramp.py").write_text(
        "import jax.numpy as jnp\n"
        "def make(key, tile, *, coverage, side, step):\n"
        "    return {'v': jnp.arange(side) * step + coverage}\n")
    monkeypatch.setattr(generate, "BENCH_DIR", tmp_path)
    pool = generate.make_pool({"coverages": [0.5, 2.0],
                               "steps": [{"gen": "ramp", "step": 3}]}, 4, 9)
    assert [np.asarray(t["v"]).tolist() for t in pool] == [
        [0.5, 3.5, 6.5, 9.5], [2.0, 5.0, 8.0, 11.0]]
    with pytest.raises(BenchError):
        generate.make_pool({"coverages": [1.0], "steps": [{"gen": "nope"}]},
                           4, 9)


def test_seed_key_rejects_out_of_range():
    with pytest.raises(ValueError):
        generate.seed_key(-1)
