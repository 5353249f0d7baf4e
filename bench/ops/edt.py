"""Squared EDT: run_op inputs, exact reference, Danielsson-bound comparison.

The reference is the exact EDT (``scipy.ndimage``, nearest background
pixel by index, squared distance in integers), independent of the program.
The 8-neighbour propagation the program runs is not exact: its fixed point
depends on the update order and may stop at a background pixel that is not
the nearest.  So the comparison holds the result to the bound of that
scheme instead of to equality: no distance below the exact one (every
answer points at a real background pixel), a small excess in pixels, on a
small share of the pixels.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy import ndimage


def inputs(tile: dict) -> tuple:
    """``run_op("edt", fg)``'s positional input."""
    return (tile["fg"],)


def reference(tile: dict, config: dict) -> np.ndarray:
    """Exact squared distance to the nearest background pixel (int64)."""
    if config["connectivity"] != 8:
        raise ValueError("the EDT comparison holds 8-neighbour propagation "
                         "to its bound; other connectivities have none here")
    fg = np.asarray(tile["fg"])
    near = ndimage.distance_transform_edt(fg, return_distances=False,
                                          return_indices=True)
    rows, cols = np.indices(fg.shape, dtype=np.int64)
    return (near[0] - rows) ** 2 + (near[1] - cols) ** 2


def compare(result, ref: np.ndarray) -> dict:
    """``below_exact_px``: pixels nearer than the exact distance.
    ``max_excess_px``: largest distance above the exact one, in px.
    ``approx_pct``: share of pixels above the exact distance, in %."""
    got = np.asarray(result)
    if got.shape != ref.shape:
        return {"below_exact_px": float(ref.size), "max_excess_px": np.inf,
                "approx_pct": 100.0}
    got = got.astype(np.int64)
    excess = np.sqrt(got) - np.sqrt(ref)
    return {"below_exact_px": float(np.sum(got < ref)),
            "max_excess_px": float(excess.max()),
            "approx_pct": float(100.0 * np.mean(got > ref))}


def control(tile: dict, config: dict) -> np.ndarray:
    """The exact reference with its squared distances held in bfloat16,
    the step below float32: the control the comparison has to refuse."""
    d2 = reference(tile, config).astype(np.float32)
    return d2.astype(ml_dtypes.bfloat16).astype(np.float64).round().astype(
        np.int32)
