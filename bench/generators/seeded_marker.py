"""Sparse seed marker over the tile's ``mask`` (``repro/data/images.py:
seeded_marker`` on the device): ``max(8, side // seeds_per_side)`` tissue
pixels drawn without replacement; the marker equals the mask on the
``2*patch``-square patch ``[r-patch, r+patch)`` round each, 0 elsewhere."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n_seeds", "patch"))
def seeded_marker(key, mask, *, n_seeds: int, patch: int):
    side = mask.shape[0]
    score = jnp.where(mask > 0, jax.random.uniform(key, mask.shape), -1.0)
    idx = jax.lax.top_k(score.ravel(), n_seeds)[1]
    seeds = jnp.zeros(mask.shape, jnp.int32).at[idx // side, idx % side].set(1)
    window = 2 * patch
    covered = jax.lax.reduce_window(
        seeds, 0, jax.lax.max, (window, window), (1, 1),
        ((patch - 1, patch), (patch - 1, patch)))
    return jnp.where(covered > 0, mask, 0)


def make(key, tile: dict, *, coverage: float, side: int,
         seeds_per_side: int, patch: int):
    return {"marker": seeded_marker(key, tile["mask"],
                                    n_seeds=max(8, side // seeds_per_side),
                                    patch=patch)}
