"""The ``I - h`` marker over the tile's ``mask``: every tissue pixel starts
in the wavefront."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def ih_marker(mask, h):
    return jnp.maximum(mask - h, 0)


def make(key, tile: dict, *, coverage: float, side: int, h: int):
    return {"marker": ih_marker(tile["mask"], jnp.int32(h))}
