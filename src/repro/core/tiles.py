"""E2: the tiled active-set engine — TPU analogue of the paper's
multi-level queue (§3.2).

Hierarchy mapping (DESIGN.md §2):
  * within a tile, propagation is dense vector work in VMEM (BQ analogue);
    the tile iterates *locally to stability* before returning — one "queue
    drain" per activation, amortizing HBM traffic exactly like the paper
    amortizes shared-memory traffic;
  * across tiles, a fixed-capacity **active-tile queue** lives at the outer
    level (GBQ analogue).  Each outer round compacts the active bitmap into
    at most ``queue_capacity`` tile ids (`jnp.where(..., size=)` — the
    prefix-sum of the paper, done by XLA) and drains them — **in parallel
    batches of ``drain_batch`` blocks** (the paper's concurrent consumption
    of the global queue across SMs, §3.2) or sequentially under `lax.scan`
    when ``drain_batch <= 1`` — then marks neighbor tiles whose halo became
    stale.  Monotone commutative updates make any order (and any degree of
    concurrency) reach the same fixed point; interior writes of distinct
    tiles are disjoint, and a stale halo read at worst re-queues a tile via
    the dirty-neighbor marks.
  * overflow: tiles beyond capacity are simply *retained* in the bitmap for
    the next round — the same re-execution-from-partial-output semantics as
    the paper's §5.2.4 GBQ overflow, without ever dropping information.

The engine is rank-generic (DESIGN.md §2.7): tiles are ``tile``-sized boxes
over the op's trailing ``ndim`` spatial axes (2D images, 3D volumes), the
tile grid and active bitmap have one axis per spatial axis, and dirty marks
cover the full Moore neighborhood of a tile — every face, edge and (in 3D)
corner ghost a conn26 update can stale.  All blocking math comes from
:class:`repro.core.geometry.Geometry`.

Persistent round state (DESIGN.md §2.6): the engine is split into
``prepare`` (build the padded planes + active-tile queue once — a
:class:`TiledRunState` carrier), a pure ``step``/``drain`` that advances the
carrier, and ``finalize`` (strip the padding, apply the invalid-pixel
contract once).  Re-entry — the composed `shard_map-tiled` engine's BP
rounds, truncation re-drains — goes through :func:`reseed` on the *same*
carrier instead of re-padding and re-building the queue from scratch.  The
jitted drain is compiled once per :class:`TiledPlan` through the shared
compile cache (``repro.core.compile_cache``) and donates the carrier, so
repeated entries update the padded buffers in place on backends that
support donation.  :func:`run_tiled` stays as the thin
prepare→drain→finalize wrapper with the historical signature.

The engine is fully jittable; the per-tile inner solver can be swapped for
the Pallas kernel (`repro.kernels.ops`) via ``tile_solver`` (and its
grid-over-batch form via ``batched_tile_solver``).
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import compile_cache
from repro.core.geometry import (Geometry, _moore_offsets, tree_spatial_shape,
                                 unravel_index)
from repro.core.pattern import PropagationOp, restore_invalid, shiftnd


class TileStats(NamedTuple):
    outer_rounds: jnp.ndarray
    tiles_processed: jnp.ndarray
    overflow_events: jnp.ndarray   # rounds where active > capacity (paper §5.2.4)
    tiles_requeued: jnp.ndarray    # drains cut off at max_iters -> self-requeued


class TiledPlan(NamedTuple):
    """Static (hashable) description of one tiled run — the jit key.

    Everything that shapes the compiled drain lives here: the op, the
    blocking, the queue geometry, and the (optional) solver callables.
    Two solves with equal plans share one compiled step through the
    compile cache; the dynamic data rides in :class:`TiledRunState`.
    """
    op: PropagationOp
    tile: int
    shape: Tuple[int, ...]  # original (unpadded) spatial domain
    grid: Tuple[int, ...]   # tiles per spatial axis of the padded layout
    queue_capacity: int    # clipped to the tile-grid size
    K: int                 # blocks drained concurrently per dispatch
    n_chunks: int          # queue slots = n_chunks * K
    max_outer_rounds: int
    tile_solver: Optional[Callable]
    batched_tile_solver: Optional[Callable]

    @property
    def n_slots(self) -> int:
        return self.n_chunks * self.K

    # 2D-compat spellings (the composed shard_map-tiled engine is 2D-only)
    @property
    def H(self) -> int:
        return self.shape[0]

    @property
    def W(self) -> int:
        return self.shape[1]

    @property
    def nty(self) -> int:
        return self.grid[0]

    @property
    def ntx(self) -> int:
        return self.grid[1]


class TiledRunState(NamedTuple):
    """The persistent device-resident carrier (DESIGN.md §2.6).

    ``padded``: the op state in padded layout — a +1 halo ring plus
    padding up to a tile multiple (`_pad_state`), built once by
    :func:`prepare` and updated in place by the donated drain.
    ``active``: the tile-grid active-tile queue bitmap.
    ``stats``: cumulative :class:`TileStats` across every (re-)entry.
    """
    padded: dict
    active: jnp.ndarray
    stats: TileStats


def _geom(op: PropagationOp, tile: int) -> Geometry:
    return Geometry.of(op.ndim, tile)


def _pad_state(op, state, tile: int):
    """Pad spatially: +1 halo ring plus padding up to a tile multiple.

    Extra padding area is marked invalid; neutral fill values guarantee the
    padding can never propagate (see PropagationOp.pad_value contract).
    """
    geom = _geom(op, tile)
    shape = geom.spatial(state)
    padded = geom.pad_state(state, op.pad_value(state))
    return padded, (shape, geom.grid(shape))


def _tile_local_solve(op: PropagationOp, block, max_iters: int):
    """Drain one tile: dense rounds on the (T+2, ...) halo block until stable.

    Seeded with an all-*valid* frontier (halo included) so incoming halo
    values propagate inward on the first round.  Invalid cells are excluded
    from the seed: `op.round` masks sources by the frontier, so seeding them
    would let invalid pixels (non-rectangular masks, engine padding) source
    one round of propagation.

    Returns ``(block, unconverged)``: ``unconverged`` is True iff the loop
    was cut off at ``max_iters`` with a non-empty frontier — the caller must
    treat the result as a *partial* drain and re-queue the tile, never as a
    fixed point.
    """
    frontier0 = jnp.ones(tree_spatial_shape(block, op.ndim), dtype=bool)
    if "valid" in block:
        frontier0 = frontier0 & block["valid"]

    def cond(c):
        _, f, it = c
        return jnp.any(f) & (it < max_iters)

    def body(c):
        blk, f, it = c
        blk, f = op.round(blk, f)
        return blk, f, it + 1

    block, f, _ = jax.lax.while_loop(cond, body, (block, frontier0, jnp.int32(0)))
    return block, jnp.any(f)


def active_tiles_from_frontier(op: PropagationOp, frontier, tile: int,
                               grid: Optional[Tuple[int, ...]] = None):
    """Tiles containing (or *adjacent to*) a frontier pixel.

    The frontier marks *source* pixels; a source on a tile border must also
    activate the receiving tile (its own tile may drain without any interior
    change, producing no neighbor marks).  Hence the 1-px dilation before
    the per-tile reduction.  This is also the BP->TP seam of the composed
    `shard_map-tiled` engine: each BP round seeds the per-device queue with
    exactly the tiles the halo exchange improved (core/distributed.py).
    """
    return tiles_any(dilate_frontier(op, frontier), tile, grid)


def dilate_frontier(op: PropagationOp, frontier):
    """``frontier`` or'ed with its shifts by each of the op's offsets."""
    dil = frontier
    for off in op.offsets:
        dil = dil | shiftnd(frontier, off, False)
    return dil


def tiles_any(mask, tile: int, grid: Optional[Tuple[int, ...]] = None):
    """Per-tile ``any`` of a spatial bool ``mask``, zero-padded up to whole
    ``tile``-sided tiles (``grid`` tiles per axis)."""
    ndim = mask.ndim
    if grid is None:
        grid = tuple(-(-s // tile) for s in mask.shape)
    fp = jnp.pad(mask, [(0, g * tile - s) for g, s in zip(grid, mask.shape)])
    inter = []
    for g in grid:
        inter += [g, tile]
    return fp.reshape(tuple(inter)).any(
        axis=tuple(range(1, 2 * ndim, 2)))


def initial_active_tiles(op: PropagationOp, state, tile: int,
                         grid: Optional[Tuple[int, ...]] = None):
    """Tiles activated by the op's own initial frontier (see
    :func:`active_tiles_from_frontier` for the dilation argument)."""
    return active_tiles_from_frontier(op, op.init_frontier(state), tile, grid)


def default_tile_solver(op: PropagationOp, tile: int) -> Callable:
    """The plain dense drain at the engine's prod(T+2) geodesic bound.

    This is `run_tiled`'s default per-tile solver, exposed so other queue
    consumers (the host scheduler's jitted drain, the hybrid engine's
    device workers — DESIGN.md §2.3) run the *same* solver under the same
    truncation contract: returns ``(block, unconverged)``.
    """
    bound = _geom(op, tile).geodesic_bound
    return lambda blk: _tile_local_solve(op, blk, max_iters=bound)


def default_batched_solver(op: PropagationOp, tile: int) -> Callable:
    """`jax.vmap` of :func:`default_tile_solver` over a leading (K,) batch
    dim — the `batched_tile_solver` contract (blocks, unconverged[K])."""
    return jax.vmap(default_tile_solver(op, tile))


def _gather_block(padded, tco, tile: int):
    """Slice one (T+2, ...) halo block at tile coords ``tco`` (one scalar
    per spatial axis)."""
    ndim = len(tco)
    start = tuple(t * tile for t in tco)
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_slice(
            x, (0,) * (x.ndim - ndim) + start,
            x.shape[:-ndim] + (tile + 2,) * ndim),
        padded)


def _interior_writeback(padded, block, tco, tile: int, mutable):
    """Write one block's interior back into the padded state (disjoint)."""
    ndim = len(tco)

    def wb(x, b):
        inner = jax.lax.slice(b, (0,) * (b.ndim - ndim) + (1,) * ndim,
                              b.shape[:-ndim] + (tile + 1,) * ndim)
        start = (0,) * (x.ndim - ndim) + tuple(t * tile + 1 for t in tco)
        return jax.lax.dynamic_update_slice(x, inner, start)

    new_padded = dict(padded)
    for k in mutable:
        new_padded[k] = wb(padded[k], block[k])
    return new_padded


def _faces_changed(pre, post, tile: int, mutable, ndim: int):
    """Did the block's interior face planes change?  (drives marking)

    Returns 2*ndim flags in (axis0-lo, axis0-hi, axis1-lo, axis1-hi, ...)
    order — the 2D spelling was (top, bot, lef, rig).
    """
    i0, i1 = 1, tile + 1

    def ch(sel):
        return jnp.array([jnp.any(pre[k][sel] != post[k][sel]) for k in mutable]).any()

    interior = tuple(slice(i0, i1) for _ in range(ndim))
    flags = []
    for a in range(ndim):
        lo = (Ellipsis,) + interior[:a] + (slice(i0, i0 + 1),) + interior[a + 1:]
        hi = (Ellipsis,) + interior[:a] + (slice(i1 - 1, i1),) + interior[a + 1:]
        flags.append(ch(lo))
        flags.append(ch(hi))
    return tuple(flags)


def _mark_neighbors(marks, tco, faces, grid):
    """Scatter-max dirty marks onto the full Moore neighborhood of tiles
    (8 in 2D, 26 in 3D — an edge/corner ghost is stale iff *any* of the
    faces it projects onto changed).  ``tco`` entries and the face flags
    may be scalars (sequential path) or (K,) vectors (batched)."""
    ndim = len(grid)
    for d in _moore_offsets(ndim, ndim):
        flag = None
        for a, da in enumerate(d):
            if da == 0:
                continue
            f = faces[2 * a + (0 if da < 0 else 1)]
            flag = f if flag is None else (flag | f)
        idx, inb = [], None
        for c, da, g in zip(tco, d, grid):
            nc = c + da
            idx.append(jnp.clip(nc, 0, g - 1))
            ib = (nc >= 0) & (nc < g)
            inb = ib if inb is None else (inb & ib)
        marks = marks.at[tuple(idx)].max(flag & inb)
    return marks


# ---------------------------------------------------------------------------
# Persistent round state: prepare / step / drain / reseed / finalize.
# ---------------------------------------------------------------------------

def _mutable_keys(plan: TiledPlan, padded) -> list:
    return [k for k in padded.keys() if k not in plan.op.static_leaves]


def prepare(op: PropagationOp, state, tile: int = 128,
            queue_capacity: int = 256, max_outer_rounds: int = 100_000,
            tile_solver: Optional[Callable] = None, drain_batch: int = 1,
            batched_tile_solver: Optional[Callable] = None,
            initial_active: Optional[jnp.ndarray] = None):
    """Build the run once: ``(TiledPlan, TiledRunState)``.

    The plan is hashable (the jit key); the run state carries the padded
    planes, the active-tile bitmap and zeroed stats.  Works both eagerly
    and under an outer trace (the composed engine calls it inside
    ``shard_map``).
    """
    padded, (shape, grid) = _pad_state(op, state, tile)
    # a queue longer than the tile grid only adds dead scan slots
    queue_capacity = min(queue_capacity, math.prod(grid))
    K = max(1, min(drain_batch, queue_capacity))
    # queue slots rounded up to whole batches (a dead slot drains a
    # neutralized block — cheap, and its writeback is the identity)
    n_chunks = -(-queue_capacity // K)
    plan = TiledPlan(op, tile, shape, grid, queue_capacity, K, n_chunks,
                     max_outer_rounds, tile_solver, batched_tile_solver)
    active0 = (initial_active if initial_active is not None
               else initial_active_tiles(op, state, tile, grid))
    stats0 = TileStats(jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0))
    return plan, TiledRunState(padded, active0, stats0)


def reseed(plan: TiledPlan, run_state: TiledRunState,
           active: Optional[jnp.ndarray] = None,
           frontier: Optional[jnp.ndarray] = None) -> TiledRunState:
    """Re-enter the carrier: OR new activations into the resident queue.

    ``active`` is a tile-grid bitmap; ``frontier`` a pixel plane in
    *padded* layout (compacted to tiles via
    :func:`active_tiles_from_frontier`).  The padded buffers and stats are
    untouched — this is the BP→TP seam that used to re-pad the whole shard.
    """
    add = jnp.zeros(plan.grid, dtype=bool)
    if active is not None:
        add = add | active
    if frontier is not None:
        add = add | active_tiles_from_frontier(
            plan.op, frontier, plan.tile, plan.grid)
    return run_state._replace(active=run_state.active | add)


def step(plan: TiledPlan, run_state: TiledRunState) -> TiledRunState:
    """One outer queue round: compact the bitmap, drain ≤ capacity tiles,
    re-mark dirty neighbors.  Pure/traceable — usable inside `shard_map`
    traces and `while_loop` bodies alike."""
    op, tile = plan.op, plan.tile
    grid, K, n_chunks = plan.grid, plan.K, plan.n_chunks
    ndim = op.ndim
    n_slots = plan.n_slots
    padded, active, stats = run_state
    mutable = _mutable_keys(plan, padded)
    solver = plan.tile_solver or default_tile_solver(op, tile)
    pv = op.pad_value(padded)

    def process_tile(padded, tid):
        """Sequential path: drain one live queue slot (the dynamic chunk
        loop below never hands this a dead slot)."""
        tco = unravel_index(tid, grid)
        block = _gather_block(padded, tco, tile)
        pre = {k: block[k] for k in mutable}
        block, unconv = solver(block)
        post = {k: block[k] for k in mutable}
        new_padded = _interior_writeback(padded, post, tco, tile, mutable)
        faces = _faces_changed(pre, post, tile, mutable, ndim)
        marks = jnp.zeros(grid, dtype=bool)
        marks = _mark_neighbors(marks, tco, faces, grid)
        # Partial drain: the tile is NOT at a fixed point — self-mark it
        # so it stays in the queue (the truncation self-requeue).
        marks = marks.at[tuple(tco)].max(unconv)
        return new_padded, (marks, unconv.astype(jnp.int32))

    def process_chunk(padded, ids_k):
        """Drain one (K,)-batch of queue slots concurrently.  Only the last
        live chunk can carry dead slots (live count not a K multiple)."""
        live = ids_k >= 0
        safe = jnp.maximum(ids_k, 0)
        tcos = unravel_index(safe, grid)   # tuple of (K,) per-axis coords
        blocks = jax.vmap(
            lambda *tco: _gather_block(padded, tco, tile))(*tcos)
        # Dead slots alias tile 0; neutralize them so they converge
        # immediately and mark nothing.
        blocks = jax.tree_util.tree_map(
            lambda x, v: jnp.where(
                live.reshape((-1,) + (1,) * (x.ndim - 1)), x, jnp.asarray(v, x.dtype)),
            blocks, pv)
        pre = {k: blocks[k] for k in mutable}
        batched_solver = plan.batched_tile_solver or jax.vmap(solver)
        post, unconv = batched_solver(blocks)
        faces = jax.vmap(
            lambda p, q: _faces_changed(p, q, tile, mutable, ndim)
        )(pre, {k: post[k] for k in mutable})
        marks = jnp.zeros(grid, dtype=bool)
        marks = _mark_neighbors(marks, tcos, tuple(f & live for f in faces),
                                grid)
        # Partial drains self-requeue (dead slots never do: unconv & live).
        unconv = unconv & live
        marks = marks.at[tcos].max(unconv)

        def scatter(padded, slot):
            """Per-slot interior write.  A dead slot (aliasing tile 0) must
            not regress a live write of the same tile earlier in this scan,
            so the dead branch re-reads the *current* interior at scatter
            time instead of writing the neutralized drain result."""
            tco, block, live_i = slot

            def wb(x, b):
                inner = jax.lax.slice(b, (0,) * (b.ndim - ndim) + (1,) * ndim,
                                      b.shape[:-ndim] + (tile + 1,) * ndim)
                start = (0,) * (x.ndim - ndim) + tuple(t * tile + 1 for t in tco)
                cur = jax.lax.dynamic_slice(x, start, x.shape[:-ndim] + (tile,) * ndim)
                return jax.lax.dynamic_update_slice(
                    x, jnp.where(live_i, inner, cur), start)

            new = dict(padded)
            for k in mutable:
                new[k] = wb(padded[k], block[k])
            return new, None

        padded, _ = jax.lax.scan(
            scatter, padded, (tcos, {k: post[k] for k in mutable}, live))
        return padded, (marks, jnp.sum(unconv, dtype=jnp.int32))

    flat = active.reshape(-1)
    (ids,) = jnp.where(flat, size=n_slots, fill_value=-1)
    n_active = jnp.sum(flat)
    n_live = jnp.minimum(n_active, n_slots).astype(jnp.int32)
    processed = jnp.zeros_like(flat).at[jnp.maximum(ids, 0)].max(ids >= 0).reshape(grid)
    marks0 = jnp.zeros(grid, dtype=bool)
    # Dynamic trip count: only *live* chunks run.  A mostly-empty queue
    # (sparse wavefronts, BP re-entries touching a few border tiles) costs
    # its live tiles, not the full slot count — the fixed per-round overhead
    # the composed engines used to pay on every nearly-idle round.
    if K > 1:
        n_live_chunks = -(-n_live // K)

        def chunk_body(c):
            i, padded, marks, req = c
            ids_k = jax.lax.dynamic_slice(ids, (i * K,), (K,))
            padded, (m, rq) = process_chunk(padded, ids_k)
            return i + 1, padded, marks | m, req + rq

        _, padded, marks, requeued = jax.lax.while_loop(
            lambda c: c[0] < n_live_chunks, chunk_body,
            (jnp.int32(0), padded, marks0, jnp.int32(0)))
    else:
        def slot_body(c):
            i, padded, marks, req = c
            padded, (m, rq) = process_tile(padded, ids[i])
            return i + 1, padded, marks | m, req + rq

        _, padded, marks, requeued = jax.lax.while_loop(
            lambda c: c[0] < n_live, slot_body,
            (jnp.int32(0), padded, marks0, jnp.int32(0)))
    # Retain overflowed (unprocessed) tiles; add freshly-dirtied ones
    # (including unconverged self-marks — partial drains re-queue).
    active = (active & ~processed) | marks
    stats = TileStats(
        stats.outer_rounds + 1,
        stats.tiles_processed + jnp.sum(ids >= 0),
        stats.overflow_events + (n_active > n_slots).astype(jnp.int32),
        stats.tiles_requeued + jnp.sum(requeued))
    return TiledRunState(padded, active, stats)


def drain(plan: TiledPlan, run_state: TiledRunState) -> TiledRunState:
    """Run :func:`step` until the active queue empties (or the round bound).
    Pure/traceable; the eager entry point is :func:`drain_fn`."""
    def cond(rs):
        return jnp.any(rs.active) & (rs.stats.outer_rounds < plan.max_outer_rounds)
    return jax.lax.while_loop(cond, lambda rs: step(plan, rs), run_state)


def drain_fn(plan: TiledPlan) -> Callable:
    """The compiled re-entrant drain for ``plan``: one build per plan via
    the shared compile cache, carrier donated (its buffers are the engine's
    own padded copies, never the caller's input).
    ``drain_fn(plan)(run_state) -> run_state``."""
    return compile_cache.get(
        ("tiled-drain", plan.op, plan),
        lambda: jax.jit(lambda rs: drain(plan, rs), donate_argnums=(0,)))


def finalize(plan: TiledPlan, run_state: TiledRunState, ref_state,
             restore: bool = True):
    """Strip the padding back to the domain; apply the invalid-pixel
    contract against ``ref_state`` (the original input) unless the caller
    owns that boundary (``restore=False`` — nested engine use)."""
    ndim = plan.op.ndim

    def run(rs, ref):
        out = jax.tree_util.tree_map(
            lambda x: jax.lax.slice(
                x, (0,) * (x.ndim - ndim) + (1,) * ndim,
                x.shape[:-ndim] + tuple(1 + s for s in plan.shape)), rs.padded)
        return restore_invalid(plan.op, ref, out) if restore else out
    leaves = jax.tree_util.tree_leaves((run_state, ref_state))
    if any(isinstance(l, jax.core.Tracer) for l in leaves):
        return run(run_state, ref_state)
    fn = compile_cache.get(("tiled-finalize", plan.op, plan, restore),
                           lambda: jax.jit(run))
    return fn(run_state, ref_state)


def run_tiled(op: PropagationOp, state, tile: int = 128, queue_capacity: int = 256,
              max_outer_rounds: int = 100_000,
              tile_solver: Optional[Callable] = None,
              drain_batch: int = 1,
              batched_tile_solver: Optional[Callable] = None,
              initial_active: Optional[jnp.ndarray] = None,
              restore: bool = True):
    """Run `op` to the global fixed point with the tiled active-set engine.

    Thin wrapper: ``prepare`` → compiled ``drain`` → ``finalize``
    (DESIGN.md §2.6).  Callers that re-enter the drain (BP rounds) should
    hold the ``(plan, run_state)`` pair themselves via
    :func:`prepare`/:func:`reseed`/:func:`step` instead of paying the
    pad/strip round trip per entry.

    ``drain_batch`` > 1 drains the compacted queue in parallel batches of
    (up to) that many (T+2, ...) halo blocks per dispatch: blocks are
    gathered into a (K, T+2, ...) batch, drained concurrently by
    ``batched_tile_solver`` (default: ``jax.vmap`` of the per-tile solver),
    and their interiors scattered back.  Interior writes are disjoint;
    halo values a concurrent neighbor would have refreshed are handled by
    the dirty-neighbor re-marking, and monotone-commutative updates make
    the result exact either way.  ``drain_batch <= 1`` keeps the sequential
    ``lax.scan`` drain.

    Tile solvers map a halo-block pytree to ``(drained block, unconverged)``
    — an ``unconverged`` drain (cut off at the solver's iteration bound) is
    a *partial* result, so the engine re-queues that tile (self-mark) until
    a drain reaches stability.  Without this, a tile whose internal geodesic
    exceeds the bound would be dequeued with a silently-wrong fixed point.

    ``initial_active``: optional tile-grid bool plane overriding the
    op-derived initial queue — the seam the composed `shard_map-tiled`
    engine uses to seed each BP round from only the halo-improved tiles.

    ``restore=False`` skips the final invalid-pixel restore (an O(area)
    `where` over every mutable leaf) — for *nested* use only, where the
    outer engine applies the contract once at its own boundary.
    """
    plan, rs = prepare(op, state, tile=tile, queue_capacity=queue_capacity,
                       max_outer_rounds=max_outer_rounds,
                       tile_solver=tile_solver, drain_batch=drain_batch,
                       batched_tile_solver=batched_tile_solver,
                       initial_active=initial_active)
    if any(isinstance(l, jax.core.Tracer) for l in jax.tree_util.tree_leaves(state)):
        rs = drain(plan, rs)           # inline into the caller's trace
    else:
        rs = drain_fn(plan)(rs)        # compiled once per plan, donated
    return finalize(plan, rs, state, restore), rs.stats
