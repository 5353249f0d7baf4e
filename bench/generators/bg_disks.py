"""Foreground with ``n_disks`` background disks, the ``fg`` plane
(``repro/data/images.py: bg_disks`` on the device): centres drawn uniformly
over the tile, one radius for all, so the disks cover about
``1 - coverage`` of it.  bool, True = foreground."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def disk_radius(side: int, coverage: float, n_disks: int) -> int:
    """``images.bg_disks``' radius."""
    return int(math.sqrt((1.0 - coverage) * side * side
                         / (max(n_disks, 1) * math.pi)))


@functools.partial(jax.jit, static_argnames=("side", "n_disks"))
def bg_disks(key, radius, *, side: int, n_disks: int):
    centres = jax.random.randint(key, (n_disks, 2), 0, side)
    yy = jax.lax.broadcasted_iota(jnp.int32, (side, side), 0)
    xx = jax.lax.broadcasted_iota(jnp.int32, (side, side), 1)
    fg = jnp.ones((side, side), bool)
    for i in range(n_disks):
        d2 = (yy - centres[i, 0]) ** 2 + (xx - centres[i, 1]) ** 2
        fg = fg & (d2 > radius * radius)
    return fg


def make(key, tile: dict, *, coverage: float, side: int, n_disks: int):
    return {"fg": bg_disks(key, jnp.int32(disk_radius(side, coverage, n_disks)),
                           side=side, n_disks=n_disks)}
