"""The one traffic generator: a cell's tile pool, made on the device from a seed.

A traffic file (``bench/traffic/<name>.json``) gives the tile ``coverages``
of one pass and a list of ``steps``; the configuration gives the tile
``side``.  Each step names a generator, ``bench/generators/<gen>.py``, whose
``make(key, tile, coverage=, side=, **params)`` returns planes to set in the
tile; the step's other keys are its parameters.  Adding a kind of image or
marker adds a generator file and a traffic file, and edits neither this
module nor the harness.

Tile ``i``'s step ``j`` draws from key ``j`` of ``split(fold_in(key(seed),
i), len(steps))``, so every seed gives the same sizes and coverages and
only the content changes.  A step with a ``seed`` of its own draws from that
number instead of ``--seed``: its draw is the same in every run.  With
``"order": "shuffled"`` the pass holds its tiles in an order drawn from
``--seed``; with every step pinned, that gives every run the same tiles,
and so the same work, in another order.
"""

from __future__ import annotations

import random

import jax

from loader import BENCH_DIR, load_module

_RESERVED = ("gen", "seed")


def seed_key(seed: int):
    """A PRNG key for any whole number up to 64 bits (seeds may pass
    2**31)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_pool(traffic: dict, side: int, seed: int) -> list:
    """The pass: one tile per entry of ``traffic["coverages"]``."""
    steps = [(step, load_module(BENCH_DIR / "generators" / f"{step['gen']}.py"))
             for step in traffic["steps"]]
    pool = []
    for i, coverage in enumerate(traffic["coverages"]):
        tile: dict = {}
        for j, (step, gen) in enumerate(steps):
            base = seed_key(step.get("seed", seed))
            key = jax.random.split(jax.random.fold_in(base, i), len(steps))[j]
            params = {k: v for k, v in step.items() if k not in _RESERVED}
            tile.update(gen.make(key, tile, coverage=float(coverage),
                                 side=side, **params))
        pool.append(tile)
    if traffic.get("order", "listed") == "shuffled":
        random.Random(seed).shuffle(pool)
    return pool
