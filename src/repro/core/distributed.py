"""E3: multi-device IWPP via shard_map — the paper's §4 strategy on a mesh.

The grid is partitioned into one block per device over a 2-D device grid
(rows over the first mesh axis, columns over the second).  For N-D inputs
(DESIGN.md §2.7) the mesh still shards the *trailing two* spatial axes;
leading spatial axes (e.g. a 3-D volume's depth) stay device-local, so the
halo exchange below is exactly the 2-D ring carrying full-depth strips and
conn26's depth-diagonal reaches never cross a device boundary mid-axis.
Each global round is exactly the paper's TP/BP pipeline:

  TP (Tile Propagation)  -> every device drains its local block to stability
                            — the drain is *pluggable*: dense frontier
                            rounds (E1 `_local_drain`) or a per-shard
                            active-tile queue (E2, plain or Pallas-backed,
                            with `drain_batch`), composing the paper's §4
                            inter-device pipeline with its §3.2 multi-level
                            queue *within* each device;
  BP (Border Propagation)-> halo exchange of the 1-px border ring with the
                            4 mesh neighbors via `lax.ppermute` (two-step:
                            columns first, then rows carrying the fresh ring
                            corners, so corners arrive transitively);
  convergence            -> `lax.psum` of per-device "changed" flags; the
                            outer `while_loop` stops when no device changed
                            (paper: "until no more intra- and inter-tile
                            propagations").

Persistent round state (DESIGN.md §2.6): with the tiled TP drain, each
device builds its padded-layout :class:`~repro.core.tiles.TiledRunState`
**once** (`tiles.prepare`) and threads it through the outer BP
`while_loop` — the per-shard active-tile queue, the padded planes, and the
tile stats all persist across BP rounds.  The halo exchange moves only the
O(perimeter) border ring (column/row strips written straight into the
carrier's pad ring), replacing the old O(area) concatenate-rebuild of the
halo-extended block, and each BP round is pipelined the way the paper's §4
overlaps border communication with tile computation:

  (1) one queue `step` over the tiles the previous exchange activated (all
      border tiles by construction) — freshens the outgoing borders;
  (2) the two-step `ppermute` ring exchange is *issued* — it has no data
      dependency on anything after it, so XLA may overlap the collective
      with (3);
  (3) the interior `drain` of the remaining active tiles runs;
  (4) received ring segments are applied to the carrier, compared against
      the previously-received ring (O(perimeter), monotone, so the
      comparison cannot oscillate even when a local drain raced past a ring
      cell), and the changed segments seed the next round's active tiles.

Borders improved *after* the send in (2) are caught by a sent-vs-current
border compare folded into the convergence flag, so the loop never exits
with an unsent improvement.  The jitted shard_map program itself is built
once per (op, mesh, signature, knobs) through the shared compile cache —
repeat solves (autotune probes, benchmark iterations, BP re-entries from
the hybrid engine) reuse the compiled executable instead of re-tracing.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import compile_cache
from repro.core import tiles as _tiles
from repro.core.pattern import PropagationOp, restore_invalid, tree_shape


class ShardStats(NamedTuple):
    """Work record of one sharded run (per-device counters psum-aggregated).

    ``per_device_tiles`` keeps the *unreduced* (nrows, ncols) per-device
    drain counts next to the psum'd total, so the aggregation itself is a
    testable invariant: ``per_device_tiles.sum() == tiles_processed``.
    All tile counters are zero under the dense TP drain.
    """
    bp_rounds: jnp.ndarray         # outer TP/BP rounds (replicated scalar)
    tiles_processed: jnp.ndarray   # psum over devices (tiled TP drain only)
    overflow_events: jnp.ndarray   # psum over devices
    tiles_requeued: jnp.ndarray    # psum over devices (unconverged re-drains)
    per_device_tiles: jnp.ndarray  # (nrows, ncols) per-device drain counts


def _shift_axis(x, axis_name: str, direction: int, fill, mesh_axis_size: int):
    """ppermute x to the neighbor `direction` steps along `axis_name`.

    Device i receives from device i - direction; edge devices receive
    `fill` (non-periodic boundary).
    """
    n = mesh_axis_size
    perm = [(i, i + direction) for i in range(n) if 0 <= i + direction < n]
    y = jax.lax.ppermute(x, axis_name, perm)
    idx = jax.lax.axis_index(axis_name)
    # Devices with no sender hold garbage/zeros -> overwrite with fill.
    no_sender = (idx == 0) if direction > 0 else (idx == n - 1)
    return jnp.where(no_sender, jnp.full_like(y, fill), y)


def _exchange_halo(block, pad_vals, axes: Tuple[str, str], mesh_shape):
    """Build the (h+2, w+2) halo-extended block from mesh neighbors.

    O(area) concatenate — the *dense* TP path only; the tiled path writes
    the received ring straight into its persistent padded carrier instead.
    """
    row_ax, col_ax = axes
    nrows, ncols = mesh_shape

    def extend(x, fill):
        h, w = x.shape[-2:]
        # columns: my left edge goes right, so I receive neighbor's right edge
        left_halo = _shift_axis(x[..., :, w - 1 : w], col_ax, +1, fill, ncols)
        right_halo = _shift_axis(x[..., :, 0:1], col_ax, -1, fill, ncols)
        xe = jnp.concatenate([left_halo, x, right_halo], axis=-1)
        top_halo = _shift_axis(xe[..., h - 1 : h, :], row_ax, +1, fill, nrows)
        bot_halo = _shift_axis(xe[..., 0:1, :], row_ax, -1, fill, nrows)
        return jnp.concatenate([top_halo, xe, bot_halo], axis=-2)

    return jax.tree_util.tree_map(extend, block, pad_vals)


def _local_drain(op: PropagationOp, block, frontier, max_iters: int = 1_000_000):
    def cond(c):
        _, f, it = c
        return jnp.any(f) & (it < max_iters)

    def body(c):
        blk, f, it = c
        blk, f = op.round(blk, f)
        return blk, f, it + 1

    block, _, iters = jax.lax.while_loop(cond, body, (block, frontier, jnp.int32(0)))
    return block, iters


def _shift_bool_1d(v, d: int):
    """Shift a 1-D bool vector by d with False fill (no wraparound)."""
    if d > 0:
        return jnp.concatenate([jnp.zeros((d,), bool), v[:-d]])
    return jnp.concatenate([v[-d:], jnp.zeros((-d,), bool)])


def _dilate_1d(v):
    return v | _shift_bool_1d(v, 1) | _shift_bool_1d(v, -1)


def _tiles_touched_1d(changed, tile: int, n_tiles: int):
    """Map a changed-border-cell vector to the tile indices it can affect
    (±1-cell dilation, then per-tile any)."""
    d = _dilate_1d(changed)
    d = jnp.pad(d, (0, n_tiles * tile - d.shape[0]))
    return d.reshape(n_tiles, tile).any(axis=1)


def _mesh_fingerprint(mesh: Mesh) -> tuple:
    return (tuple(mesh.devices.flatten().tolist()), tuple(mesh.axis_names),
            tuple(mesh.devices.shape))


def _state_signature(state) -> tuple:
    return (jax.tree_util.tree_structure(state),
            tuple((tuple(l.shape), str(l.dtype))
                  for l in jax.tree_util.tree_leaves(state)))


def run_sharded(op: PropagationOp, state, mesh: Mesh,
                axes: Tuple[str, str] = ("data", "model"), *,
                tile: Optional[int] = None,
                queue_capacity: int = 256,
                drain_batch: int = 1,
                tile_solver: Optional[Callable] = None,
                batched_tile_solver: Optional[Callable] = None,
                max_bp_rounds: int = 10_000,
                donate: bool = False):
    """Run `op` to the global fixed point on `mesh`.

    `state` leaves are (..., H, W) with H divisible by mesh.shape[axes[0]]
    and W by mesh.shape[axes[1]].  Returns ``(state, ShardStats)``.

    ``tile=None`` drains each device's block densely (E1 rounds) per TP
    stage — the flat `shard_map` engine.  With ``tile`` set, each TP stage
    drains a *persistent* per-shard active-tile queue (the composed
    `shard_map-tiled` engine; see the module docstring for the BP round
    structure): the first TP drains from the op's own initial frontier;
    every later TP is seeded with *only the tiles the halo exchange
    improved* — monotone commutative updates make re-draining any superset
    of those tiles reach the same fixed point, so the compaction is free of
    correctness risk and skips the (typically vast) stable interior of each
    shard.  ``tile_solver`` / ``batched_tile_solver`` plug the Pallas VMEM
    drains in, exactly as in `run_tiled`; solvers must honor the
    ``(block, unconverged)`` contract so partial drains self-requeue.

    The compiled program is memoized in the shared compile cache — calling
    again with the same (op, mesh, state signature, knobs) reuses the
    executable.  ``donate=True`` additionally donates the input buffers to
    the compiled call (pass it only when the caller owns a private copy,
    e.g. after padding to a mesh multiple).
    """
    row_ax, col_ax = axes
    nrows, ncols = mesh.shape[row_ax], mesh.shape[col_ax]
    spatial = tree_shape(state, op.ndim)
    H, W = spatial[-2:]
    assert H % nrows == 0 and W % ncols == 0, (H, W, nrows, ncols)
    if tile is not None and op.ndim != 2:
        raise NotImplementedError(
            "the composed shard_map-tiled TP drain is 2-D only; "
            f"op has ndim={op.ndim} — use tile=None (dense TP) or the "
            "single-device tiled engines for volumes")
    pad_vals = op.pad_value(state)
    bh, bw = H // nrows, W // ncols

    spec = jax.tree_util.tree_map(
        lambda x: P(*([None] * (x.ndim - 2) + [row_ax, col_ax])), state)

    zero = jnp.int32(0)

    def device_fn_dense(block):
        block, _ = _local_drain(op, block, op.init_frontier(block))

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_bp_rounds)

        def body(carry):
            block, _, it = carry
            # BP: halo exchange, then one masked round sourcing only from the
            # halo ring, to find which border pixels the neighbors improved.
            ext = _exchange_halo(block, pad_vals, (row_ax, col_ax), (nrows, ncols))
            sp = tree_shape(block, op.ndim)
            # Ring frontier on the trailing-2 halo only: leading spatial axes
            # are device-local, so their boundaries are *global* boundaries
            # (op.round's neutral shift fill handles them, no exchange).
            halo_frontier = jnp.zeros(sp[:-2] + (sp[-2] + 2, sp[-1] + 2),
                                      dtype=bool)
            halo_frontier = (halo_frontier.at[..., 0, :].set(True)
                             .at[..., -1, :].set(True)
                             .at[..., :, 0].set(True)
                             .at[..., :, -1].set(True))
            # Only *valid* halo cells may source: an invalid border pixel of
            # the neighbor shard holds arbitrary input values (the invalid-
            # pixel contract preserves them), and an unmasked seed would let
            # it propagate into this shard's valid region.
            if "valid" in ext:
                halo_frontier = halo_frontier & ext["valid"]
            ext_new, f_ext = op.round(ext, halo_frontier)
            inner = lambda x: x[..., 1:-1, 1:-1]
            block = jax.tree_util.tree_map(lambda _, b: inner(b), block, ext_new)
            f_in = inner(f_ext)
            # TP: drain local propagation seeded by improved border pixels.
            block, _ = _local_drain(op, block, f_in)
            changed_local = jnp.any(f_in)
            changed = jax.lax.psum(changed_local.astype(jnp.int32), (row_ax, col_ax)) > 0
            return block, changed, it + 1

        block, _, rounds = jax.lax.while_loop(
            cond, body, (block, jnp.bool_(True), jnp.int32(0)))
        totals = (zero, zero, zero)
        return block, rounds, tuple(jax.lax.psum(c, (row_ax, col_ax)) for c in totals), \
            zero.reshape(1, 1)

    def device_fn_tiled(block):
        # Build the persistent carrier ONCE; it survives every BP round.
        plan, rs = _tiles.prepare(
            op, block, tile=tile, queue_capacity=queue_capacity,
            tile_solver=tile_solver, drain_batch=drain_batch,
            batched_tile_solver=batched_tile_solver)
        # TP round 0: drain from the op's own init frontier.
        rs = _tiles.drain(plan, rs)
        nty, ntx = plan.nty, plan.ntx
        mutable = [k for k in rs.padded if k not in op.static_leaves]

        def fill_rings():
            """What the ring 'received' before any exchange: the pad fill."""
            out = {}
            for k in mutable:
                x = rs.padded[k]
                lead = x.shape[:-2]
                f = pad_vals[k]
                mk = lambda shp: jnp.full(lead + shp, f, x.dtype)
                out[k] = (mk((bh, 1)), mk((bh, 1)),
                          mk((1, 2 + bw)), mk((1, 2 + bw)))
            return out

        def exchange(padded, keys):
            """Issue the two-step ring exchange for ``keys`` (reads only —
            the received segments are applied to the carrier later, after
            the interior drain, so the collective can overlap it).

            Returns ``(recv, sent)``: per-leaf received
            (left, right, top, bottom) ring segments, and the *domain*
            border values that were sent (mutable leaves only — for the
            sent-vs-current convergence compare).
            """
            recv, sent = {}, {}
            for k in keys:
                x = padded[k]
                f = pad_vals[k]
                send_l = x[..., 1:1 + bh, 1:2]         # my left domain col
                send_r = x[..., 1:1 + bh, bw:bw + 1]   # my right domain col
                left = _shift_axis(send_r, col_ax, +1, f, ncols)
                right = _shift_axis(send_l, col_ax, -1, f, ncols)
                # Row sends span the full padded width and carry the ring
                # corners *just received* in the column step (set without
                # writing the plane), so diagonal values arrive transitively.
                send_t = x[..., 1:2, 0:2 + bw]
                send_b = x[..., bh:bh + 1, 0:2 + bw]
                send_t = send_t.at[..., :, 0:1].set(left[..., 0:1, :])
                send_t = send_t.at[..., :, 1 + bw:2 + bw].set(right[..., 0:1, :])
                send_b = send_b.at[..., :, 0:1].set(left[..., bh - 1:bh, :])
                send_b = send_b.at[..., :, 1 + bw:2 + bw].set(right[..., bh - 1:bh, :])
                top = _shift_axis(send_b, row_ax, +1, f, nrows)
                bot = _shift_axis(send_t, row_ax, -1, f, nrows)
                recv[k] = (left, right, top, bot)
                if k in mutable:
                    sent[k] = (send_l, send_r,
                               send_t[..., :, 1:1 + bw], send_b[..., :, 1:1 + bw])
            return recv, sent

        def apply_recv(padded, recv, keys):
            """Write the received ring segments into the carrier's pad ring.

            The bottom/right ring rows sit *inside* the last tile's interior
            when the shard is not a tile multiple, so a local drain may have
            raced past them — overwriting with the (possibly older) received
            value is still sound: ring cells are conduits, never part of the
            stripped output, and the improvement travels the proper BP path
            (our border was sent; the neighbor drains and sends it back).
            """
            new = dict(padded)
            for k in keys:
                x = padded[k]
                l, r, t, b = recv[k]
                x = x.at[..., 1:1 + bh, 0:1].set(l)
                x = x.at[..., 1:1 + bh, 1 + bw:2 + bw].set(r)
                x = x.at[..., 0:1, 0:2 + bw].set(t)
                x = x.at[..., 1 + bh:2 + bh, 0:2 + bw].set(b)
                new[k] = x
            return new

        def ring_changes(recv, prev):
            """Per-cell received-vs-previously-received compare (monotone in
            the sender's own timeline, so this cannot oscillate)."""
            ch_l = jnp.zeros((bh,), bool)
            ch_r = jnp.zeros((bh,), bool)
            ch_t = jnp.zeros((2 + bw,), bool)
            ch_b = jnp.zeros((2 + bw,), bool)
            for k in mutable:
                l, r, t, b = recv[k]
                pl, pr, pt, pb = prev[k]
                col_red = tuple(range(l.ndim - 2)) + (-1,)
                row_red = tuple(range(t.ndim - 2)) + (-2,)
                ch_l = ch_l | jnp.any(l != pl, axis=col_red)
                ch_r = ch_r | jnp.any(r != pr, axis=col_red)
                ch_t = ch_t | jnp.any(t != pt, axis=row_red)
                ch_b = ch_b | jnp.any(b != pb, axis=row_red)
            return ch_l, ch_r, ch_t, ch_b

        def ring_activation(ch_l, ch_r, ch_t, ch_b):
            """Changed ring cells -> the border tiles they can affect."""
            act = jnp.zeros((nty, ntx), bool)
            act = act.at[:, 0].max(_tiles_touched_1d(ch_l, tile, nty))
            act = act.at[:, ntx - 1].max(_tiles_touched_1d(ch_r, tile, nty))
            act = act.at[0, :].max(_tiles_touched_1d(ch_t[1:1 + bw], tile, ntx))
            act = act.at[nty - 1, :].max(_tiles_touched_1d(ch_b[1:1 + bw], tile, ntx))
            return act

        def border_dirty(padded, sent):
            """Did a drain improve a domain border *after* it was sent?
            Keeps the loop alive until every improvement has been shipped."""
            dirty = jnp.bool_(False)
            for k in mutable:
                x = padded[k]
                sl, sr, st, sb = sent[k]
                dirty = dirty | jnp.any(x[..., 1:1 + bh, 1:2] != sl)
                dirty = dirty | jnp.any(x[..., 1:1 + bh, bw:bw + 1] != sr)
                dirty = dirty | jnp.any(x[..., 1:2, 1:1 + bw] != st)
                dirty = dirty | jnp.any(x[..., bh:bh + 1, 1:1 + bw] != sb)
            return dirty

        def cond(carry):
            _, _, changed, it = carry
            return changed & (it < max_bp_rounds)

        def body(carry):
            rs, prev, _, it = carry
            # (1) Freshen outgoing borders: one queue step over the tiles the
            # previous exchange activated (all border tiles by construction).
            rs = jax.lax.cond(jnp.any(rs.active),
                              lambda r: _tiles.step(plan, r), lambda r: r, rs)
            # (2) Issue the ring exchange — no dependency on (3).  Only the
            # mutable leaves travel: the static rings (masks, valid planes,
            # coordinate grids) were exchanged once before the loop.
            recv, sent = exchange(rs.padded, mutable)
            # (3) Interior drain of whatever the step left active.
            rs = _tiles.drain(plan, rs)
            # (4) Apply received rings; seed next round from what changed.
            ch = ring_changes(recv, prev)
            rs = _tiles.TiledRunState(apply_recv(rs.padded, recv, mutable),
                                      rs.active | ring_activation(*ch),
                                      rs.stats)
            prev = {k: recv[k] for k in mutable}
            changed_local = (jnp.any(ch[0]) | jnp.any(ch[1]) | jnp.any(ch[2])
                             | jnp.any(ch[3]) | border_dirty(rs.padded, sent))
            changed = jax.lax.psum(
                changed_local.astype(jnp.int32), (row_ax, col_ax)) > 0
            return rs, prev, changed, it + 1

        # One-time exchange of the static rings: the neighbor's mask/valid/
        # coordinate border cells never change, so they need not ride the
        # per-round collective.
        static_keys = [k for k in rs.padded if k in op.static_leaves]
        recv_static, _ = exchange(rs.padded, static_keys)
        rs = rs._replace(padded=apply_recv(rs.padded, recv_static, static_keys))
        rs, _, _, rounds = jax.lax.while_loop(
            cond, body, (rs, fill_rings(), jnp.bool_(True), jnp.int32(0)))
        # One final drain: the last exchange may have activated tiles.
        rs = _tiles.drain(plan, rs)
        st = rs.stats
        counters = (st.tiles_processed, st.overflow_events, st.tiles_requeued)
        # Per-device counters + psum totals: stats aggregation is itself a
        # collective (the record is replicated; the per-device plane is not).
        totals = tuple(jax.lax.psum(c, (row_ax, col_ax)) for c in counters)
        block = _tiles.finalize(plan, rs, None, restore=False)
        return block, rounds, totals, st.tiles_processed.reshape(1, 1)

    device_fn = device_fn_dense if tile is None else device_fn_tiled

    def build():
        fn = jax.shard_map(
            device_fn, mesh=mesh, in_specs=(spec,),
            out_specs=(spec, P(), (P(), P(), P()), P(row_ax, col_ax)),
            check_vma=False)
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    key = ("sharded-fn", op, _mesh_fingerprint(mesh), axes,
           _state_signature(state), tile, queue_capacity, drain_batch,
           tile_solver, batched_tile_solver, max_bp_rounds, donate)
    out, rounds, (tiles, ovf, req), per_dev = compile_cache.get(key, build)(state)
    # Engine output contract: invalid cells hold their input values.
    out = restore_invalid(op, state, out)
    return out, ShardStats(rounds, tiles, ovf, req, per_dev)
