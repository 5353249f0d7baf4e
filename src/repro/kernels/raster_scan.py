"""Pallas TPU kernel: directional raster pass for the FH initialization.

The column-direction pass propagates down rows:
    v[r, :] = min(I[r, :], max(J[r, :], v[r-1, :]))
— one W-lane vector op per row with a row-vector carry, the natural TPU
layout (the GPU version launches one thread per column; paper Algorithm 5).
Other directions are realized by flips/transposes in `ops.py`.

The grid is split along columns into (H, Wb) VMEM panels so wide images
stream through VMEM; the row recurrence stays within each panel (columns
are independent for this direction).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(j_ref, i_ref, o_ref):
    H = j_ref.shape[0]

    def body(r, prev):
        row = jnp.maximum(j_ref[pl.ds(r, 1), :], prev)
        row = jnp.minimum(row, i_ref[pl.ds(r, 1), :])
        o_ref[pl.ds(r, 1), :] = row
        return row

    neut = (jnp.iinfo(j_ref.dtype).min if jnp.issubdtype(j_ref.dtype, jnp.integer)
            else -jnp.inf)
    init = jnp.full((1, j_ref.shape[1]), neut, dtype=j_ref.dtype)
    jax.lax.fori_loop(0, H, body, init)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def raster_down(J, I, *, block_w: int = 512, interpret: Optional[bool] = None):
    """Top-to-bottom FH pass: v[r] = min(I[r], max(J[r], v[r-1]))."""
    H, W = J.shape
    bw = min(block_w, W)
    assert W % bw == 0, (W, bw)
    grid = (W // bw,)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(J.shape, J.dtype),
        in_specs=[pl.BlockSpec((H, bw), lambda c: (0, c)),
                  pl.BlockSpec((H, bw), lambda c: (0, c))],
        out_specs=pl.BlockSpec((H, bw), lambda c: (0, c)),
        grid=grid,
        interpret=resolve_interpret(interpret),
    )(J, I)
