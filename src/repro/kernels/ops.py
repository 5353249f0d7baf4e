"""Jit'd public wrappers around the Pallas kernels.

* dtype policy: TPU vector units want >=int16 payloads; uint8 images are
  upcast to int32 for the kernel and cast back (exactness preserved — the
  ops are min/max/compare).
* `tile_solver_morph` / `tile_solver_edt` / `tile_solver_label` adapt the
  kernels to the tiled engine's `tile_solver` interface (block pytree ->
  (block pytree, unconverged)) — the label solver is the *morph kernel
  parametrized* (mask = fg ? LABEL_CAP : 0), the registry-level kernel
  reuse of DESIGN.md §2.4; the `*_batched` variants adapt the grid-over-batch kernels
  to the engine's `batched_tile_solver` interface (leaves carry a leading
  (K,) batch dim — the paper's parallel queue drain, DESIGN.md §2).  The
  same batched contract backs the hybrid engine's device workers
  (`solve(engine="hybrid", hybrid_pallas=True)` — DESIGN.md §2.3), so a
  `DeviceWorker` drains its claimed chunks through these kernels unchanged.
* the adapters take the engine's iteration bound as ``max_iters`` (the
  tiled engine passes its (T+2)² geodesic bound) and report
  ``iters >= max_iters`` as the *unconverged* flag, so a drain cut off at
  the bound is re-queued by the engine instead of silently accepted as a
  fixed point.  The flag is conservative: a drain that stabilized exactly
  at the bound re-queues once and converges immediately on the re-drain.
* every directional raster pass is expressed through the single
  `raster_down` kernel via flips/transposes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.edt.ops import COORD_LEAVES
from repro.kernels.edt_tile import (edt_tile_solve_batched_nd,
                                    edt_tile_solve_nd,
                                    edt_tile_solve_queued_batched_nd,
                                    edt_tile_solve_queued_nd)
from repro.kernels.morph_tile import (morph_tile_solve,
                                      morph_tile_solve_batched,
                                      morph_tile_solve_queued,
                                      morph_tile_solve_queued_batched)
from repro.kernels.raster_scan import raster_down
from repro.label.ops import LABEL_CAP

DEFAULT_MAX_ITERS = 1024


def default_kernel_queue_capacity(block) -> int:
    """Default in-kernel queue capacity for a halo block.

    ``block`` is the block's spatial shape tuple (an int means a square 2D
    block — the historical spelling).  The queue holds last round's
    *improved* pixels — a propagating wavefront crossing the block is a
    band of O(prod(B)/min(B)) of them (a row of a 2D block, a slab of a 3D
    one).  A push round's cost scales with the capacity whether or not the
    slots are occupied, so the default tracks the band, floored at 64 so
    tiny tiles don't thrash the dense-spill path and capped at the block
    size (a queue bigger than the block is just the block).  See DESIGN.md
    §2.5/§2.7.
    """
    shape = (block, block) if isinstance(block, int) else tuple(block)
    band = math.prod(shape) // min(shape)
    return int(min(math.prod(shape), max(64, band)))


def _up(x):
    if x.dtype in (jnp.uint8, jnp.int8, jnp.uint16, jnp.int16):
        return x.astype(jnp.int32), x.dtype
    return x, None


def morph_tile_pallas(J, I, valid, connectivity: int = 8, interpret: Optional[bool] = None,
                      max_iters: int = DEFAULT_MAX_ITERS):
    Ju, orig = _up(J)
    Iu, _ = _up(I)
    out, iters = morph_tile_solve(Ju, Iu, valid, connectivity=connectivity,
                                  max_iters=max_iters, interpret=interpret)
    return (out.astype(orig) if orig is not None else out), iters


def tile_solver_morph(connectivity: int = 8, interpret: Optional[bool] = None,
                      max_iters: int = DEFAULT_MAX_ITERS):
    """Adapter: tiled-engine `tile_solver` backed by the Pallas kernel."""
    def solver(block):
        J, iters = morph_tile_pallas(block["J"], block["I"], block["valid"],
                                     connectivity, interpret, max_iters)
        out = dict(block)
        out["J"] = J
        return out, iters >= max_iters
    return solver


def morph_tile_pallas_batched(J, I, valid, connectivity: int = 8,
                              interpret: Optional[bool] = None,
                              max_iters: int = DEFAULT_MAX_ITERS):
    """(K, T+2, T+2) batch drain; returns (J_out, iters[K])."""
    Ju, orig = _up(J)
    Iu, _ = _up(I)
    out, iters = morph_tile_solve_batched(Ju, Iu, valid,
                                          connectivity=connectivity,
                                          max_iters=max_iters,
                                          interpret=interpret)
    return (out.astype(orig) if orig is not None else out), iters


def tile_solver_morph_batched(connectivity: int = 8, interpret: Optional[bool] = None,
                              max_iters: int = DEFAULT_MAX_ITERS):
    """Adapter: tiled-engine `batched_tile_solver` backed by the grid kernel."""
    def solver(blocks):
        J, iters = morph_tile_pallas_batched(blocks["J"], blocks["I"],
                                             blocks["valid"], connectivity,
                                             interpret, max_iters)
        out = dict(blocks)
        out["J"] = J
        return out, iters >= max_iters
    return solver


# LABEL_CAP is an op-level invariant (label_seeds raises above it); here
# it is the "mask" plane value when the morph kernel is parametrized into
# the label solver: min(LABEL_CAP, ·) is then the identity on foreground,
# and 0 clamps background — the masked-max label update.
def _label_as_morph(blocks):
    """Express a label block in morph-kernel terms: J = lab, I = fg-mask."""
    I = jnp.where(blocks["fg"], jnp.int32(LABEL_CAP), jnp.int32(0))
    return blocks["lab"], I


def tile_solver_label(connectivity: int = 8, interpret: Optional[bool] = None,
                      max_iters: int = DEFAULT_MAX_ITERS):
    """Adapter: the *morph* Pallas kernel, parametrized into the label op's
    masked-max update (DESIGN.md §2.4 — new ops reuse kernels through the
    registry instead of shipping their own)."""
    def solver(block):
        J, I = _label_as_morph(block)
        lab, iters = morph_tile_solve(J, I, block["valid"],
                                      connectivity=connectivity,
                                      max_iters=max_iters,
                                      interpret=interpret)
        out = dict(block)
        out["lab"] = lab
        return out, iters >= max_iters
    return solver


def tile_solver_label_batched(connectivity: int = 8, interpret: Optional[bool] = None,
                              max_iters: int = DEFAULT_MAX_ITERS):
    """Batched (K, T+2, T+2) variant over the morph grid-over-batch kernel."""
    def solver(blocks):
        J, I = _label_as_morph(blocks)
        lab, iters = morph_tile_solve_batched(J, I, blocks["valid"],
                                              connectivity=connectivity,
                                              max_iters=max_iters,
                                              interpret=interpret)
        out = dict(blocks)
        out["lab"] = lab
        return out, iters >= max_iters
    return solver


def _edt_coords(state_block, ndim: int, stack_axis: int = 0):
    """Stack the op's coordinate leaves ((row, col) or (dep, row, col))
    into the (ndim, *spatial) array the ``*_nd`` kernels take."""
    return jnp.stack([state_block[k] for k in COORD_LEAVES[ndim]],
                     axis=stack_axis)


def edt_tile_pallas(state_block, connectivity=8, interpret: Optional[bool] = None,
                    max_iters: int = DEFAULT_MAX_ITERS):
    vr = state_block["vr"]  # (ndim, *spatial)
    o, iters = edt_tile_solve_nd(
        vr, state_block["valid"], _edt_coords(state_block, vr.shape[0]),
        connectivity=connectivity, max_iters=max_iters, interpret=interpret)
    out = dict(state_block)
    out["vr"] = o
    return out, iters


def tile_solver_edt(connectivity: int = 8, interpret: Optional[bool] = None,
                    max_iters: int = DEFAULT_MAX_ITERS):
    def solver(block):
        out, iters = edt_tile_pallas(block, connectivity, interpret, max_iters)
        return out, iters >= max_iters
    return solver


def edt_tile_pallas_batched(state_blocks, connectivity=8,
                            interpret: Optional[bool] = None,
                            max_iters: int = DEFAULT_MAX_ITERS):
    """Batched EDT drain over leaves with a leading (K,) batch dim."""
    vr = state_blocks["vr"]  # (K, ndim, *spatial)
    o, iters = edt_tile_solve_batched_nd(
        vr, state_blocks["valid"],
        _edt_coords(state_blocks, vr.shape[1], stack_axis=1),
        connectivity=connectivity, max_iters=max_iters, interpret=interpret)
    out = dict(state_blocks)
    out["vr"] = o
    return out, iters


def tile_solver_edt_batched(connectivity: int = 8, interpret: Optional[bool] = None,
                            max_iters: int = DEFAULT_MAX_ITERS):
    def solver(blocks):
        out, iters = edt_tile_pallas_batched(blocks, connectivity, interpret,
                                             max_iters)
        return out, iters >= max_iters
    return solver


# ---------------------------------------------------------------------------
# Queued-kernel adapters (DESIGN.md §2.5).  Same tile_solver contract as the
# dense adapters above — the per-kernel `spills` counter is an intra-kernel
# diagnostic and is not surfaced through the engine's block pytree.
#
# Every queued solver additionally accepts ``queue=(indices, count)`` — a
# *resident* in-kernel queue (DESIGN.md §2.6): flat block indices of the
# pixels whose values have not yet been offered to their neighbors (compact
# layout, dead slots -1) plus the live count.  When given, the kernel drain
# starts from that frontier and skips its O(block) seeding sweep; a count
# above the kernel's queue capacity safely spills to a dense first round.
# Batched solvers take per-block (K, n) indices and (K,) counts.
# ---------------------------------------------------------------------------

def morph_tile_pallas_queued(J, I, valid, connectivity: int = 8,
                             interpret: Optional[bool] = None,
                             max_iters: int = DEFAULT_MAX_ITERS,
                             queue_capacity: int | None = None,
                             queue=None):
    if queue_capacity is None:
        queue_capacity = default_kernel_queue_capacity(J.shape)
    Ju, orig = _up(J)
    Iu, _ = _up(I)
    out, iters, spills = morph_tile_solve_queued(
        Ju, Iu, valid, queue, connectivity=connectivity, max_iters=max_iters,
        queue_capacity=queue_capacity, interpret=interpret)
    return (out.astype(orig) if orig is not None else out), iters, spills


def tile_solver_morph_queued(connectivity: int = 8, interpret: Optional[bool] = None,
                             max_iters: int = DEFAULT_MAX_ITERS,
                             queue_capacity: int | None = None):
    """`tile_solver` backed by the queued morph kernel."""
    def solver(block, queue=None):
        J, iters, _ = morph_tile_pallas_queued(
            block["J"], block["I"], block["valid"], connectivity, interpret,
            max_iters, queue_capacity, queue)
        out = dict(block)
        out["J"] = J
        return out, iters >= max_iters
    return solver


def tile_solver_morph_queued_batched(connectivity: int = 8,
                                     interpret: Optional[bool] = None,
                                     max_iters: int = DEFAULT_MAX_ITERS,
                                     queue_capacity: int | None = None):
    """`batched_tile_solver` over the queued grid-over-batch morph kernel."""
    def solver(blocks, queue=None):
        cap = (default_kernel_queue_capacity(blocks["J"].shape[1:])
               if queue_capacity is None else queue_capacity)
        Ju, orig = _up(blocks["J"])
        Iu, _ = _up(blocks["I"])
        J, iters, _ = morph_tile_solve_queued_batched(
            Ju, Iu, blocks["valid"], queue, connectivity=connectivity,
            max_iters=max_iters, queue_capacity=cap, interpret=interpret)
        out = dict(blocks)
        out["J"] = J.astype(orig) if orig is not None else J
        return out, iters >= max_iters
    return solver


def tile_solver_label_queued(connectivity: int = 8, interpret: Optional[bool] = None,
                             max_iters: int = DEFAULT_MAX_ITERS,
                             queue_capacity: int | None = None):
    """Queued morph kernel parametrized into the label masked-max update."""
    def solver(block, queue=None):
        J, I = _label_as_morph(block)
        cap = (default_kernel_queue_capacity(J.shape)
               if queue_capacity is None else queue_capacity)
        lab, iters, _ = morph_tile_solve_queued(
            J, I, block["valid"], queue, connectivity=connectivity,
            max_iters=max_iters, queue_capacity=cap, interpret=interpret)
        out = dict(block)
        out["lab"] = lab
        return out, iters >= max_iters
    return solver


def tile_solver_label_queued_batched(connectivity: int = 8,
                                     interpret: Optional[bool] = None,
                                     max_iters: int = DEFAULT_MAX_ITERS,
                                     queue_capacity: int | None = None):
    def solver(blocks, queue=None):
        J, I = _label_as_morph(blocks)
        cap = (default_kernel_queue_capacity(J.shape[1:])
               if queue_capacity is None else queue_capacity)
        lab, iters, _ = morph_tile_solve_queued_batched(
            J, I, blocks["valid"], queue, connectivity=connectivity,
            max_iters=max_iters, queue_capacity=cap, interpret=interpret)
        out = dict(blocks)
        out["lab"] = lab
        return out, iters >= max_iters
    return solver


def tile_solver_edt_queued(connectivity=8, interpret: Optional[bool] = None,
                           max_iters: int = DEFAULT_MAX_ITERS,
                           queue_capacity: int | None = None):
    def solver(block, queue=None):
        vr = block["vr"]
        cap = (default_kernel_queue_capacity(block["valid"].shape)
               if queue_capacity is None else queue_capacity)
        o, iters, _ = edt_tile_solve_queued_nd(
            vr, block["valid"], _edt_coords(block, vr.shape[0]), queue,
            connectivity=connectivity, max_iters=max_iters,
            queue_capacity=cap, interpret=interpret)
        out = dict(block)
        out["vr"] = o
        return out, iters >= max_iters
    return solver


def tile_solver_edt_queued_batched(connectivity=8,
                                   interpret: Optional[bool] = None,
                                   max_iters: int = DEFAULT_MAX_ITERS,
                                   queue_capacity: int | None = None):
    def solver(blocks, queue=None):
        vr = blocks["vr"]  # (K, ndim, *spatial)
        cap = (default_kernel_queue_capacity(blocks["valid"].shape[1:])
               if queue_capacity is None else queue_capacity)
        o, iters, _ = edt_tile_solve_queued_batched_nd(
            vr, blocks["valid"], _edt_coords(blocks, vr.shape[1], stack_axis=1),
            queue, connectivity=connectivity, max_iters=max_iters,
            queue_capacity=cap, interpret=interpret)
        out = dict(blocks)
        out["vr"] = o
        return out, iters >= max_iters
    return solver


@partial(jax.jit, static_argnames=("interpret",))
def raster_pass_kernel(J, I, interpret: Optional[bool] = None):
    """Full raster half-pass (left->right then top->down) via the kernel.

    Left->right is the same recurrence on the transpose.
    """
    Ju, orig = _up(J)
    Iu, _ = _up(I)
    Jt = raster_down(Ju.T, Iu.T, interpret=interpret).T     # row-wise forward
    Jv = raster_down(Jt, Iu, interpret=interpret)           # column-wise forward
    return Jv.astype(orig) if orig is not None else Jv


@partial(jax.jit, static_argnames=("interpret",))
def antiraster_pass_kernel(J, I, interpret: Optional[bool] = None):
    Ju, orig = _up(J)
    Iu, _ = _up(I)
    Jt = raster_down(Ju[:, ::-1].T, Iu[:, ::-1].T, interpret=interpret).T[:, ::-1]
    Jv = raster_down(Jt[::-1], Iu[::-1], interpret=interpret)[::-1]
    return Jv.astype(orig) if orig is not None else Jv
