"""Pallas TPU kernel: drain one EDT (Voronoi-pointer) tile in VMEM.

Same structure as morph_tile: the halo block (``(T+2, T+2)`` in 2D,
``(T+2, T+2, T+2)`` in 3D — DESIGN.md §2.7) iterates the neighbor
candidate min-reduction to local stability without leaving VMEM.
Distances are int32 (exact for grids < 8192 with the far sentinel; see
repro.edt.ref.SENTINEL).  This kernel replaces Algorithm 6's atomicCAS retry
loop with a race-free vector reduction — the TPU-native adaptation.

Entry points come in two spellings:

* rank-generic ``*_nd`` — stacked ``(ndim, *spatial)`` pointer/coordinate
  arrays, one plane per spatial axis (what the engine adapters call);
* the historical 2D ``(vr_r, vr_c, valid, row, col)`` signatures, kept as
  thin wrappers over the ``*_nd`` forms.

:func:`edt_tile_solve_batched` drains a (K, T+2, ...) batch with a
``pallas_call`` grid over the batch dimension (DESIGN.md §2 "batched queue
drain"); each grid step converges independently.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import ravel_index, unravel_index
from repro.core.pattern import offsets_for
from repro.edt.ref import SENTINEL
from repro.kernels import resolve_interpret
from repro.kernels.queue import fit_seed as _fit_seed
from repro.kernels.queue import queued_fixed_point, queued_interpret


# The 3-D conn26 kernel at T=32 (34³ blocks, lanes padded 34 -> 128) needs
# ~39 MiB of scoped VMEM, past Mosaic's 16 MiB default; a v5e has 128 MiB.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 * 2**20)


def _full(shape):
    shape = tuple(shape)
    return pl.BlockSpec(shape, lambda: (0,) * len(shape))


def _batch_blk(spatial):
    spatial = tuple(spatial)
    return pl.BlockSpec((1,) + spatial, lambda k: (k,) + (0,) * len(spatial))


def _dist2(coords, ptrs):
    d = None
    for g, p in zip(coords, ptrs):
        dd = g - p
        d = dd * dd if d is None else d + dd * dd
    return d


def _make_kernel(connectivity, max_iters: int, batched: bool = False):
    offsets = offsets_for(connectivity)
    ndim = len(offsets[0])

    def kernel(*refs):
        ins = refs[:2 * ndim + 1]
        outs = refs[2 * ndim + 1:]
        if batched:  # refs carry a leading (1,)-block batch dim under the grid
            vr = [r[0] for r in ins[:ndim]]
            valid = ins[ndim][0]
            coords = [r[0] for r in ins[ndim + 1:]]
        else:
            vr = [r[...] for r in ins[:ndim]]
            valid = ins[ndim][...]
            coords = [r[...] for r in ins[ndim + 1:]]
        shp = valid.shape
        s = jnp.int32(SENTINEL)
        # Invalid in-block pixels must never source propagation: pin them to
        # the sentinel before the first iteration reads them as neighbors.
        vr = [jnp.where(valid, p, s) for p in vr]

        def shifted(x, off):
            xp = jnp.pad(x, 1, constant_values=s)
            return jax.lax.slice(xp, tuple(1 + d for d in off),
                                 tuple(1 + d + n for d, n in zip(off, shp)))

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iters)

        def body(carry):
            vr, _, it = carry
            best = list(vr)
            bd = _dist2(coords, best)
            for off in offsets:
                cand = [shifted(p, off) for p in vr]
                cd = _dist2(coords, cand)
                upd = cd < bd
                best = [jnp.where(upd, cp, bp) for cp, bp in zip(cand, best)]
                bd = jnp.where(upd, cd, bd)
            best = [jnp.where(valid, bp, s) for bp in best]
            changed = jnp.bool_(False)
            for bp, p in zip(best, vr):
                changed = changed | jnp.any(bp != p)
            return tuple(best), changed, it + 1

        vr, _, iters = jax.lax.while_loop(
            cond, body, (tuple(vr), jnp.bool_(True), jnp.int32(0)))
        for o_ref, p in zip(outs[:ndim], vr):
            o_ref[...] = p.reshape(o_ref.shape)
        # Full-block store: Mosaic cannot store a scalar to VMEM.
        outs[ndim][...] = jnp.full(outs[ndim].shape, iters)

    return kernel


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters", "interpret"))
def edt_tile_solve_nd(vr, valid, coords, *, connectivity=8,
                      max_iters: int = 1024, interpret: Optional[bool] = None):
    """Drain one EDT halo block, any spatial rank.

    ``vr``/``coords``: (ndim, *spatial) stacked pointer/coordinate planes;
    ``valid``: (*spatial,) bool.  Returns (vr_out, iters).
    """
    ndim = vr.shape[0]
    shp = valid.shape
    kernel = _make_kernel(connectivity, max_iters)
    out_shape = tuple(jax.ShapeDtypeStruct(shp, vr.dtype) for _ in range(ndim))
    out_shape += (jax.ShapeDtypeStruct((1, 1), jnp.int32),)
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[_full(shp)] * (2 * ndim + 1),
        out_specs=tuple([_full(shp)] * ndim) + (_full((1, 1)),),
        interpret=resolve_interpret(interpret),
        compiler_params=_COMPILER_PARAMS,
    )(*[vr[i] for i in range(ndim)], valid, *[coords[i] for i in range(ndim)])
    return jnp.stack(outs[:ndim]), outs[ndim][0, 0]


def edt_tile_solve(vr_r, vr_c, valid, row, col, *, connectivity=8,
                   max_iters: int = 1024, interpret: Optional[bool] = None):
    """Drain one (T+2, T+2) EDT halo block.  Returns (vr_r, vr_c, iters) —
    the historical 2D spelling of :func:`edt_tile_solve_nd`."""
    o, iters = edt_tile_solve_nd(
        jnp.stack([vr_r, vr_c]), valid, jnp.stack([row, col]),
        connectivity=connectivity, max_iters=max_iters, interpret=interpret)
    return o[0], o[1], iters


def _make_queued_kernel(connectivity, max_iters: int, capacity: int,
                        batched: bool = False, seeded: bool = False):
    """Queued EDT variant (DESIGN.md §2.5), push formulation: the queue
    holds last round's improved pixels; each round gathers only their
    pre-round pointers and pushes them to neighbors with one sequential
    scatter pass per offset, in the dense kernel's offset order.  Each pass
    compares against the target's *current* (partially updated) pointer —
    the dense round's evolving per-pixel best accumulator — so even Voronoi
    *tie* resolution, not just distances, is bit-identical to
    :func:`_make_kernel`, as is the iteration count.  Queue overflow spills
    to one dense full-block round.

    ``seeded`` adds two input refs (resident queue indices + live count,
    DESIGN.md §2.6) and starts the drain from them, skipping the O(block)
    seeding sweep."""
    offsets = offsets_for(connectivity)
    ndim = len(offsets[0])

    def kernel(*refs):
        ins = refs[:2 * ndim + 1]
        rest = refs[2 * ndim + 1:]
        if seeded:
            seed_ref, cnt_ref = rest[0], rest[1]
            out_refs = rest[2:2 + ndim]
            iters_ref, spills_ref = rest[2 + ndim], rest[3 + ndim]
        else:
            out_refs = rest[0:ndim]
            iters_ref, spills_ref = rest[ndim], rest[ndim + 1]
        if batched:  # refs carry a leading (1,)-block batch dim under the grid
            vr = [r[0] for r in ins[:ndim]]
            valid = ins[ndim][0]
            coords = [r[0] for r in ins[ndim + 1:]]
        else:
            vr = [r[...] for r in ins[:ndim]]
            valid = ins[ndim][...]
            coords = [r[...] for r in ins[ndim + 1:]]
        shp = valid.shape
        n = math.prod(shp)
        s = jnp.int32(SENTINEL)
        vr = [jnp.where(valid, p, s) for p in vr]

        def shifted(x, off):
            xp = jnp.pad(x, 1, constant_values=s)
            return jax.lax.slice(xp, tuple(1 + d for d in off),
                                 tuple(1 + d + m for d, m in zip(off, shp)))

        def dense_round(carry):
            # Same body as the dense kernel's while-loop step.
            vr = carry
            best = list(vr)
            bd = _dist2(coords, best)
            for off in offsets:
                cand = [shifted(p, off) for p in vr]
                cd = _dist2(coords, cand)
                upd = cd < bd
                best = [jnp.where(upd, cp, bp) for cp, bp in zip(cand, best)]
                bd = jnp.where(upd, cd, bd)
            best = [jnp.where(valid, bp, s) for bp in best]
            changed = jnp.zeros(shp, dtype=bool)
            for bp, p in zip(best, vr):
                changed = changed | (bp != p)
            return tuple(best), changed

        coord_flat = [g.reshape(-1) for g in coords]
        valid_flat = valid.reshape(-1)

        def queued_round(carry, queue):
            # Push formulation: gather the queued sources' pre-round pointers
            # once, then one sequential scatter pass per offset in the dense
            # kernel's order.  Each pass reads the target's current pointer —
            # the dense round's evolving best accumulator — and targets are
            # unique within a pass (distinct sources, one common shift), so
            # every scatter is race-free and deterministic.
            pf = [p.reshape(-1) for p in carry]
            live = queue >= 0
            src = jnp.where(live, queue, 0)
            ptr = [f[src] for f in pf]     # pre-round source pointers (offers)
            sglob = [g[src] for g in coord_flat]  # global coords are affine in
            sco = unravel_index(src, shp)         # the local index, so target
            tgts, flags = [], []                  # coords are arithmetic
            for off in offsets:
                # The pixel that reads source s under offset d is t = s - d:
                # dense's shifted() hands p the neighbor at p + d.
                tco = tuple(c - d for c, d in zip(sco, off))
                inb = live
                for c, m in zip(tco, shp):
                    inb = inb & (c >= 0) & (c < m)
                tg = jnp.where(inb, ravel_index(tco, shp), n)  # n -> dropped
                tglob = [g - d for g, d in zip(sglob, off)]
                cd = _dist2(tglob, ptr)
                od = _dist2(tglob, [jnp.take(f, tg, mode="fill",
                                             fill_value=SENTINEL) for f in pf])
                upd = (inb & (cd < od)
                       & jnp.take(valid_flat, tg, mode="fill", fill_value=False))
                tdrop = jnp.where(upd, tg, n)
                pf = [f.at[tdrop].set(p, mode="drop") for f, p in zip(pf, ptr)]
                tgts.append(tg)
                flags.append(upd)
            return (tuple(f.reshape(shp) for f in pf),
                    jnp.concatenate(tgts), jnp.concatenate(flags))

        initial_queue = None
        if seeded:
            if batched:
                initial_queue = (seed_ref[0], cnt_ref[0, 0, 0])
            else:
                initial_queue = (seed_ref[0], cnt_ref[0, 0])
        vr, iters, spills = queued_fixed_point(
            dense_round, queued_round, tuple(vr),
            max_iters=max_iters, capacity=capacity,
            initial_queue=initial_queue)
        for o_ref, p in zip(out_refs, vr):
            o_ref[...] = p.reshape(o_ref.shape)
        iters_ref[...] = jnp.full(iters_ref.shape, iters)
        spills_ref[...] = jnp.full(spills_ref.shape, spills)

    return kernel


def _clip_capacity(queue_capacity: int, n: int, n_offsets: int) -> int:
    # The queue counts per-contribution (duplicates included), so up to
    # n_offsets*n slots are meaningful — that capacity can never overflow.
    return max(1, min(int(queue_capacity), n_offsets * n))


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters",
                                             "queue_capacity", "interpret"))
def edt_tile_solve_queued_nd(vr, valid, coords, seed=None, *, connectivity=8,
                             max_iters: int = 1024, queue_capacity: int = 64,
                             interpret: Optional[bool] = None):
    """Queued drain of one EDT halo block, any rank (DESIGN.md §2.5).

    ``vr``/``coords``: (ndim, *spatial).  Returns (vr_out, iters, spills) —
    pointer planes and iters bit-identical to :func:`edt_tile_solve_nd`;
    ``spills`` counts overflow rounds that fell back to a dense sweep.

    ``seed`` — optional resident queue ``(indices, count)`` (DESIGN.md
    §2.6; see :func:`repro.kernels.morph_tile.morph_tile_solve_queued` for
    the contract): start the drain from a known frontier instead of the
    O(block) seeding sweep.
    """
    ndim = vr.shape[0]
    shp = valid.shape
    n_off = len(offsets_for(connectivity))
    cap = _clip_capacity(queue_capacity, math.prod(shp), n_off)
    kernel = _make_queued_kernel(connectivity, max_iters, cap,
                                 seeded=seed is not None)
    out_shape = tuple(jax.ShapeDtypeStruct(shp, vr.dtype) for _ in range(ndim))
    out_shape += (jax.ShapeDtypeStruct((1, 1), jnp.int32),
                  jax.ShapeDtypeStruct((1, 1), jnp.int32))
    in_specs = [_full(shp)] * (2 * ndim + 1)
    args = tuple(vr[i] for i in range(ndim)) + (valid,)
    args += tuple(coords[i] for i in range(ndim))
    if seed is not None:
        sq, cnt = seed
        sq = _fit_seed(sq, cap)[None, :]            # (1, cap)
        cnt = jnp.asarray(cnt, jnp.int32).reshape(1, 1)
        in_specs += [_full(sq.shape), _full((1, 1))]
        args += (sq, cnt)
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=tuple([_full(shp)] * ndim) + (_full((1, 1)), _full((1, 1))),
        interpret=queued_interpret(interpret),
        compiler_params=_COMPILER_PARAMS,
    )(*args)
    return jnp.stack(outs[:ndim]), outs[ndim][0, 0], outs[ndim + 1][0, 0]


def edt_tile_solve_queued(vr_r, vr_c, valid, row, col, seed=None, *,
                          connectivity=8,
                          max_iters: int = 1024, queue_capacity: int = 64,
                          interpret: Optional[bool] = None):
    """Queued drain of one 2D EDT halo block — the historical spelling of
    :func:`edt_tile_solve_queued_nd`.  Returns (vr_r, vr_c, iters, spills)."""
    o, iters, spills = edt_tile_solve_queued_nd(
        jnp.stack([vr_r, vr_c]), valid, jnp.stack([row, col]), seed,
        connectivity=connectivity, max_iters=max_iters,
        queue_capacity=queue_capacity, interpret=interpret)
    return o[0], o[1], iters, spills


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters",
                                             "queue_capacity", "interpret"))
def edt_tile_solve_queued_batched_nd(vr, valid, coords, seed=None, *,
                                     connectivity=8, max_iters: int = 1024,
                                     queue_capacity: int = 64,
                                     interpret: Optional[bool] = None):
    """Queued drain of a (K, ndim, *spatial) EDT batch; one local queue per
    grid step.  Returns (vr_out, iters, spills) with (K,) counters.

    ``seed`` — optional per-block resident queues ``(indices, counts)``
    with shapes (K, n) / (K,)."""
    K, ndim = vr.shape[0], vr.shape[1]
    spatial = valid.shape[1:]
    n_off = len(offsets_for(connectivity))
    cap = _clip_capacity(queue_capacity, math.prod(spatial), n_off)
    kernel = _make_queued_kernel(connectivity, max_iters, cap, batched=True,
                                 seeded=seed is not None)
    out_shape = tuple(jax.ShapeDtypeStruct((K,) + spatial, vr.dtype)
                      for _ in range(ndim))
    out_shape += (jax.ShapeDtypeStruct((K, 1, 1), jnp.int32),
                  jax.ShapeDtypeStruct((K, 1, 1), jnp.int32))
    blk = _batch_blk(spatial)
    scalar = pl.BlockSpec((1, 1, 1), lambda k: (k, 0, 0))
    in_specs = [blk] * (2 * ndim + 1)
    args = tuple(vr[:, i] for i in range(ndim)) + (valid,)
    args += tuple(coords[:, i] for i in range(ndim))
    if seed is not None:
        sq, cnt = seed
        sq = jax.vmap(lambda s_: _fit_seed(s_, cap))(sq)      # (K, cap)
        cnt = jnp.asarray(cnt, jnp.int32).reshape(K, 1, 1)
        in_specs += [pl.BlockSpec((1, cap), lambda k: (k, 0)), scalar]
        args += (sq, cnt)
    outs = pl.pallas_call(
        kernel,
        grid=(K,),
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=tuple([blk] * ndim) + (scalar, scalar),
        interpret=queued_interpret(interpret),
        compiler_params=_COMPILER_PARAMS,
    )(*args)
    return (jnp.stack(outs[:ndim], axis=1),
            outs[ndim][:, 0, 0], outs[ndim + 1][:, 0, 0])


def edt_tile_solve_queued_batched(vr_r, vr_c, valid, row, col, seed=None, *,
                                  connectivity=8, max_iters: int = 1024,
                                  queue_capacity: int = 64,
                                  interpret: Optional[bool] = None):
    """Queued drain of a (K, T+2, T+2) 2D EDT batch — historical spelling of
    :func:`edt_tile_solve_queued_batched_nd`."""
    o, iters, spills = edt_tile_solve_queued_batched_nd(
        jnp.stack([vr_r, vr_c], axis=1), valid,
        jnp.stack([row, col], axis=1), seed,
        connectivity=connectivity, max_iters=max_iters,
        queue_capacity=queue_capacity, interpret=interpret)
    return o[:, 0], o[:, 1], iters, spills


@functools.partial(jax.jit, static_argnames=("connectivity", "max_iters", "interpret"))
def edt_tile_solve_batched_nd(vr, valid, coords, *, connectivity=8,
                              max_iters: int = 1024, interpret: Optional[bool] = None):
    """Drain a (K, ndim, *spatial) batch of EDT halo blocks concurrently.

    Returns (vr_out, iters) with iters shaped (K,); each grid step iterates
    its own block to stability independently.
    """
    K, ndim = vr.shape[0], vr.shape[1]
    spatial = valid.shape[1:]
    kernel = _make_kernel(connectivity, max_iters, batched=True)
    out_shape = tuple(jax.ShapeDtypeStruct((K,) + spatial, vr.dtype)
                      for _ in range(ndim))
    out_shape += (jax.ShapeDtypeStruct((K, 1, 1), jnp.int32),)
    blk = _batch_blk(spatial)
    outs = pl.pallas_call(
        kernel,
        grid=(K,),
        out_shape=out_shape,
        in_specs=[blk] * (2 * ndim + 1),
        out_specs=tuple([blk] * ndim) + (pl.BlockSpec((1, 1, 1), lambda k: (k, 0, 0)),),
        interpret=resolve_interpret(interpret),
        compiler_params=_COMPILER_PARAMS,
    )(*[vr[:, i] for i in range(ndim)], valid, *[coords[:, i] for i in range(ndim)])
    return jnp.stack(outs[:ndim], axis=1), outs[ndim][:, 0, 0]


def edt_tile_solve_batched(vr_r, vr_c, valid, row, col, *, connectivity=8,
                           max_iters: int = 1024, interpret: Optional[bool] = None):
    """Drain a (K, T+2, T+2) batch of 2D EDT halo blocks — historical
    spelling of :func:`edt_tile_solve_batched_nd`."""
    o, iters = edt_tile_solve_batched_nd(
        jnp.stack([vr_r, vr_c], axis=1), valid,
        jnp.stack([row, col], axis=1),
        connectivity=connectivity, max_iters=max_iters, interpret=interpret)
    return o[:, 0], o[:, 1], iters
