"""Tissue-like gray mask, the ``mask`` plane (``repro/data/images.py:
tissue_image`` on the device): smoothed noise, the top ``coverage`` share of
it kept as tissue with gray levels 30..230, background 0.  int32."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _smooth(x, iters: int):
    """Periodic separable 3-tap box smoothing (``images._smooth``)."""
    for _ in range(iters):
        x = (x + jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0)) / 3.0
        x = (x + jnp.roll(x, 1, 1) + jnp.roll(x, -1, 1)) / 3.0
    return x


@functools.partial(jax.jit, static_argnames=("side", "smooth"))
def tissue(key, coverage, *, side: int, smooth: int = 4):
    noise = _smooth(jax.random.uniform(key, (side, side), jnp.float32), smooth)
    thresh = jnp.where(coverage < 1.0, jnp.quantile(noise, 1.0 - coverage),
                       -jnp.inf)
    lo, hi = noise.min(), noise.max()
    gray = ((noise - lo) / jnp.maximum(hi - lo, 1e-9) * 200 + 30).astype(
        jnp.int32)
    return jnp.where(noise >= thresh, gray, 0)


def make(key, tile: dict, *, coverage: float, side: int, smooth: int = 4):
    return {"mask": tissue(key, jnp.float32(coverage), side=side,
                           smooth=smooth)}
