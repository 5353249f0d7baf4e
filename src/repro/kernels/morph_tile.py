"""Pallas TPU kernel: drain one morphological-reconstruction tile in VMEM.

This is the hot spot the paper optimizes with its BQ/TQ queues: repeated
neighbor propagation over one tile.  The TPU formulation keeps the whole
halo block (``(T+2, T+2)`` in 2D, ``(T+2, T+2, T+2)`` in 3D — DESIGN.md
§2.7) resident in VMEM and iterates the neighbor max-propagate + min-clamp
to local stability *inside the kernel* — zero HBM traffic between
iterations (the BQ analogue; DESIGN.md §2).  The neighbor combine is one
statically-shifted VREG plane per offset in the op's
:class:`~repro.core.geometry.Neighborhood` (TQ analogue).

Two entry points:

* :func:`morph_tile_solve`          — one halo block;
* :func:`morph_tile_solve_batched`  — a (K, T+2, ...) batch of blocks,
  drained concurrently with a ``pl.pallas_call`` grid over the batch
  dimension (the paper's parallel consumption of the global queue,
  DESIGN.md §2 "batched queue drain"); each grid step iterates its own
  block to stability independently.

Block shapes should keep the (8, 128) vector layout: T in {64, 128, 256} and
int32/float32 payloads (wrappers upcast uint8 — TPU-native dtype policy).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.geometry import ravel_index, unravel_index
from repro.core.pattern import offsets_for
from repro.kernels import resolve_interpret
from repro.kernels.queue import fit_seed as _fit_seed
from repro.kernels.queue import queued_fixed_point, queued_interpret


def _neutral(dtype):
    return jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer) else -jnp.inf


def _full(shape):
    """BlockSpec for a whole-array block of any rank."""
    shape = tuple(shape)
    return pl.BlockSpec(shape, lambda: (0,) * len(shape))


def _batch_blk(spatial):
    """BlockSpec for one (1, *spatial) slab of a batched array under grid=(K,)."""
    spatial = tuple(spatial)
    return pl.BlockSpec((1,) + spatial, lambda k: (k,) + (0,) * len(spatial))


def _shifted_slice(xp, off, shape):
    """The neighbor plane at `off` of a halo-padded block (rank-generic)."""
    return jax.lax.slice(xp, tuple(1 + d for d in off),
                         tuple(1 + d + s for d, s in zip(off, shape)))


def _make_kernel(connectivity, max_iters: int, batched: bool = False):
    offsets = offsets_for(connectivity)

    def kernel(j_ref, i_ref, valid_ref, o_ref, iters_ref):
        if batched:  # refs carry a leading (1,)-block batch dim under the grid
            J = j_ref[0]
            I = i_ref[0]
            valid = valid_ref[0]
        else:
            J = j_ref[...]
            I = i_ref[...]
            valid = valid_ref[...]
        shp = J.shape  # halo block: (T+2, ...) over the spatial rank
        neut = _neutral(J.dtype)
        # Invalid in-block pixels (non-rectangular masks) must neither source
        # nor hold propagation: pin them to the neutral value — the morph
        # analogue of the EDT kernel's sentinel clamp.
        J = jnp.where(valid, J, neut)

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iters)

        def body(carry):
            J, _, it = carry
            # Full-block update (halo ring evolves too): keeps pass-through
            # propagation paths identical to the dense-round oracle.
            Jp = jnp.pad(J, 1, constant_values=neut)
            cand = jnp.full_like(J, neut)
            for off in offsets:
                cand = jnp.maximum(cand, _shifted_slice(Jp, off, shp))
            new = jnp.minimum(I, jnp.maximum(J, cand))
            new = jnp.where(valid, new, neut)
            changed = jnp.any(new != J)
            return new, changed, it + 1

        J, _, iters = jax.lax.while_loop(cond, body, (J, jnp.bool_(True), jnp.int32(0)))
        o_ref[...] = J.reshape(o_ref.shape)
        # Full-block store: Mosaic cannot store a scalar to VMEM.
        iters_ref[...] = jnp.full(iters_ref.shape, iters)

    return kernel


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters", "interpret"))
def morph_tile_solve(J, I, valid, *, connectivity=8, max_iters: int = 1024,
                     interpret: Optional[bool] = None):
    """Drain one (T+2, ...) halo block to local stability.

    Returns (J_out, iters).  Halo faces are read as propagation sources
    but their output values are unspecified (callers write back interiors
    only, as the tiled engine does).  Invalid cells come back neutral.
    """
    kernel = _make_kernel(connectivity, max_iters)
    out_shape = (
        jax.ShapeDtypeStruct(J.shape, J.dtype),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )
    J_out, iters = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[_full(J.shape), _full(I.shape), _full(valid.shape)],
        out_specs=(_full(J.shape), _full((1, 1))),
        interpret=resolve_interpret(interpret),
    )(J, I, valid)
    return J_out, iters[0, 0]


def _make_queued_kernel(connectivity, max_iters: int, capacity: int,
                        batched: bool = False, seeded: bool = False):
    """Queued variant (DESIGN.md §2.5), push formulation: the queue holds
    last round's *improved* pixels, and each round gathers only those and
    pushes ``min(I[t], J[s])`` to every neighbor ``t`` — O(capacity) work
    per round instead of O(block).  Queue overflow spills to one dense
    full-block round.  Accepted updates coincide exactly with the dense
    kernel's (a non-improved neighbor's offer was already max-merged when
    it last improved), so outputs and iteration counts are bit-identical
    to :func:`_make_kernel` — only the work per round shrinks.

    ``seeded`` adds two input refs (resident queue indices + live count,
    DESIGN.md §2.6) and starts the drain from them, skipping the O(block)
    seeding sweep — the re-entry path when the caller already knows the
    frontier."""
    offsets = offsets_for(connectivity)

    def kernel(j_ref, i_ref, valid_ref, *refs):
        if seeded:
            seed_ref, cnt_ref = refs[0], refs[1]
            o_ref, iters_ref, spills_ref = refs[2], refs[3], refs[4]
        else:
            o_ref, iters_ref, spills_ref = refs[0], refs[1], refs[2]
        if batched:  # refs carry a leading (1,)-block batch dim under the grid
            J = j_ref[0]
            I = i_ref[0]
            valid = valid_ref[0]
        else:
            J = j_ref[...]
            I = i_ref[...]
            valid = valid_ref[...]
        shp = J.shape  # halo block: (T+2, ...) over the spatial rank
        n = math.prod(shp)
        neut = _neutral(J.dtype)
        J = jnp.where(valid, J, neut)

        def dense_round(J):
            # Same body as the dense kernel's while-loop step.
            Jp = jnp.pad(J, 1, constant_values=neut)
            cand = jnp.full_like(J, neut)
            for off in offsets:
                cand = jnp.maximum(cand, _shifted_slice(Jp, off, shp))
            new = jnp.minimum(I, jnp.maximum(J, cand))
            new = jnp.where(valid, new, neut)
            return new, new != J

        I_flat = I.reshape(-1)
        valid_flat = valid.reshape(-1)

        def queued_round(J, queue):
            # Push formulation: gather the queued (improved) pixels' values
            # once, offer min(I[t], J[s]) to each neighbor t, and scatter-max
            # the improving offers back.  Duplicate targets (several sources
            # improving one pixel) are safe: max is order-free and duplicate
            # enqueues are idempotent (DESIGN.md §2.5).
            Jf = J.reshape(-1)
            live = queue >= 0
            src = jnp.where(live, queue, 0)
            vs = Jf[src]                    # pre-round source values
            sco = unravel_index(src, shp)   # per-axis source coords
            tgts = []                       # offsets unrolled in Python:
            for off in offsets:             # Pallas forbids captured arrays
                tco = tuple(c + d for c, d in zip(sco, off))
                inb = live
                for c, s in zip(tco, shp):
                    inb = inb & (c >= 0) & (c < s)
                tgts.append(jnp.where(inb, ravel_index(tco, shp), n))  # n -> dropped
            tgt = jnp.concatenate(tgts)
            offer = jnp.minimum(
                jnp.take(I_flat, tgt, mode="fill", fill_value=neut),
                jnp.concatenate([vs] * len(offsets)))
            old = jnp.take(Jf, tgt, mode="fill", fill_value=neut)
            imp = (offer > old) & jnp.take(valid_flat, tgt, mode="fill",
                                           fill_value=False)
            Jf = Jf.at[jnp.where(imp, tgt, n)].max(offer, mode="drop")
            return Jf.reshape(shp), tgt, imp

        initial_queue = None
        if seeded:
            if batched:
                initial_queue = (seed_ref[0], cnt_ref[0, 0, 0])
            else:
                initial_queue = (seed_ref[0], cnt_ref[0, 0])
        J, iters, spills = queued_fixed_point(
            dense_round, queued_round, J,
            max_iters=max_iters, capacity=capacity,
            initial_queue=initial_queue)
        o_ref[...] = J.reshape(o_ref.shape)
        iters_ref[...] = jnp.full(iters_ref.shape, iters)
        spills_ref[...] = jnp.full(spills_ref.shape, spills)

    return kernel


def _clip_capacity(queue_capacity: int, n: int, n_offsets: int) -> int:
    # The queue counts per-contribution (duplicates included), so up to
    # n_offsets*n slots are meaningful — that capacity can never overflow.
    return max(1, min(int(queue_capacity), n_offsets * n))


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters",
                                             "queue_capacity", "interpret"))
def morph_tile_solve_queued(J, I, valid, seed=None, *, connectivity=8,
                            max_iters: int = 1024, queue_capacity: int = 64,
                            interpret: Optional[bool] = None):
    """Queued drain of one (T+2, ...) halo block (DESIGN.md §2.5).

    Returns (J_out, iters, spills): bit-identical J_out and iters to
    :func:`morph_tile_solve`; ``spills`` counts the rounds whose candidate
    set overflowed ``queue_capacity`` and fell back to a dense sweep.

    ``seed`` — optional resident queue ``(indices, count)`` (DESIGN.md
    §2.6): flat int32 block indices of the pixels whose values have not yet
    been offered to their neighbors (dead slots ``-1``), plus the live
    count.  The drain then starts from this frontier instead of paying the
    O(block) seeding sweep; a count above the (clipped) capacity safely
    spills to a dense first round.
    """
    n_off = len(offsets_for(connectivity))
    cap = _clip_capacity(queue_capacity, math.prod(J.shape), n_off)
    kernel = _make_queued_kernel(connectivity, max_iters, cap,
                                 seeded=seed is not None)
    out_shape = (
        jax.ShapeDtypeStruct(J.shape, J.dtype),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )
    scalar = _full((1, 1))
    in_specs = [_full(J.shape), _full(I.shape), _full(valid.shape)]
    args = (J, I, valid)
    if seed is not None:
        sq, cnt = seed
        sq = _fit_seed(sq, cap)[None, :]            # (1, cap)
        cnt = jnp.asarray(cnt, jnp.int32).reshape(1, 1)
        in_specs += [_full(sq.shape), scalar]
        args += (sq, cnt)
    J_out, iters, spills = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=(_full(J.shape), scalar, scalar),
        interpret=queued_interpret(interpret),
    )(*args)
    return J_out, iters[0, 0], spills[0, 0]


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters",
                                             "queue_capacity", "interpret"))
def morph_tile_solve_queued_batched(J, I, valid, seed=None, *,
                                    connectivity=8,
                                    max_iters: int = 1024,
                                    queue_capacity: int = 64,
                                    interpret: Optional[bool] = None):
    """Queued drain of a (K, T+2, ...) batch; each grid step owns one block
    and one local queue.  Returns (J_out, iters, spills), both (K,).

    ``seed`` — optional per-block resident queues ``(indices, counts)``
    with shapes (K, n) / (K,) (same contract as
    :func:`morph_tile_solve_queued`)."""
    K = J.shape[0]
    spatial = J.shape[1:]
    n_off = len(offsets_for(connectivity))
    cap = _clip_capacity(queue_capacity, math.prod(spatial), n_off)
    kernel = _make_queued_kernel(connectivity, max_iters, cap, batched=True,
                                 seeded=seed is not None)
    out_shape = (
        jax.ShapeDtypeStruct(J.shape, J.dtype),
        jax.ShapeDtypeStruct((K, 1, 1), jnp.int32),
        jax.ShapeDtypeStruct((K, 1, 1), jnp.int32),
    )
    blk = _batch_blk(spatial)
    scalar = pl.BlockSpec((1, 1, 1), lambda k: (k, 0, 0))
    in_specs = [blk, blk, blk]
    args = (J, I, valid)
    if seed is not None:
        sq, cnt = seed
        sq = jax.vmap(lambda s: _fit_seed(s, cap))(sq)        # (K, cap)
        cnt = jnp.asarray(cnt, jnp.int32).reshape(K, 1, 1)
        in_specs += [pl.BlockSpec((1, cap), lambda k: (k, 0)), scalar]
        args += (sq, cnt)
    J_out, iters, spills = pl.pallas_call(
        kernel,
        grid=(K,),
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=(blk, scalar, scalar),
        interpret=queued_interpret(interpret),
    )(*args)
    return J_out, iters[:, 0, 0], spills[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters", "interpret"))
def morph_tile_solve_batched(J, I, valid, *, connectivity=8,
                             max_iters: int = 1024, interpret: Optional[bool] = None):
    """Drain a (K, T+2, ...) batch of halo blocks concurrently.

    One ``pallas_call`` with ``grid=(K,)``: each grid step owns one block and
    iterates it to *its own* local stability (no cross-block sync, unlike a
    vmapped while_loop which runs every block for the batch max).  Returns
    (J_out, iters) with iters shaped (K,).
    """
    K = J.shape[0]
    spatial = J.shape[1:]
    kernel = _make_kernel(connectivity, max_iters, batched=True)
    out_shape = (
        jax.ShapeDtypeStruct(J.shape, J.dtype),
        jax.ShapeDtypeStruct((K, 1, 1), jnp.int32),
    )
    blk = _batch_blk(spatial)
    J_out, iters = pl.pallas_call(
        kernel,
        grid=(K,),
        out_shape=out_shape,
        in_specs=[blk, blk, blk],
        out_specs=(blk, pl.BlockSpec((1, 1, 1), lambda k: (k, 0, 0))),
        interpret=resolve_interpret(interpret),
    )(J, I, valid)
    return J_out, iters[:, 0, 0]
