"""Morphological reconstruction: run_op inputs, plain reference, comparison.

The reference is the definition of grayscale reconstruction by dilation
(Vincent 1993): iterate ``J <- min(dilate(J), I)`` from ``min(marker,
mask)`` until nothing changes.  It is written here in plain ``jax.numpy``
and shares no code with the program's engines.  Integer max and min are
exact, so the result must equal the reference on every pixel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np


def inputs(tile: dict) -> tuple:
    """``run_op("morph", marker, mask)``'s positional inputs."""
    return tile["marker"], tile["mask"]


def _offsets(connectivity: int):
    return [(dr, dc) for dr in (-1, 0, 1) for dc in (-1, 0, 1)
            if (dr, dc) != (0, 0) and (connectivity == 8 or 0 in (dr, dc))]


@functools.partial(jax.jit, static_argnames="connectivity")
def reconstruct(marker, mask, connectivity: int):
    """Fixed point of ``J <- min(dilate(J), mask)`` (int32, exact)."""
    H, W = mask.shape
    lowest = jnp.iinfo(jnp.int32).min

    def body(carry):
        J, _ = carry
        P = jnp.pad(J, 1, constant_values=lowest)
        D = J
        for dr, dc in _offsets(connectivity):
            D = jnp.maximum(D, P[1 + dr:1 + dr + H, 1 + dc:1 + dc + W])
        Jn = jnp.minimum(D, mask)
        return Jn, jnp.any(Jn != J)

    J0 = jnp.minimum(marker, mask).astype(jnp.int32)
    return jax.lax.while_loop(lambda c: c[1], body, (J0, jnp.bool_(True)))[0]


def reference(tile: dict, config: dict):
    return reconstruct(tile["marker"], tile["mask"],
                       connectivity=config["connectivity"])


def compare(result, ref) -> dict:
    """``mismatch_px``: pixels where the result differs from the reference
    (every pixel when the shape differs)."""
    result = jnp.asarray(result)
    if result.shape != ref.shape:
        return {"mismatch_px": float(ref.size)}
    return {"mismatch_px": float(jnp.sum(result != ref))}


def _fp8(x):
    """Gray levels held in float8 e4m3 (3 mantissa bits): exact up to 16,
    steps of 16 at 128..255."""
    x = np.asarray(x).astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
    return jnp.asarray(x.astype(np.float32).astype(np.int32))


def control(tile: dict, config: dict):
    """The reference with its gray levels in float8: the lower-precision
    control that the comparison has to refuse.  (bfloat16 and float16 hold
    8-bit levels exactly, so they would be no control.)"""
    return reconstruct(_fp8(tile["marker"]), _fp8(tile["mask"]),
                       connectivity=config["connectivity"])
