"""Demand-driven host tile scheduler — the paper's runtime (§4, Fig. 8).

The paper dispatches Tile-Propagation (TP) task instances to CPU cores and
GPUs demand-driven (FCFS) and re-instantiates the pipeline when Border
Propagation (BP) finds cross-tile waves.  This module reproduces that
runtime at the host level with worker threads over jitted tile tasks, and
— via :class:`DeviceWorker` — the paper's *cooperative* CPU+GPU execution:
host threads and accelerator drain streams consume the **same** FCFS queue
(DESIGN.md §2.3, the `hybrid` engine's substrate).

* demand-driven FCFS queue -> natural straggler mitigation (fast workers
  take more tiles, exactly the paper's load-balance argument);
* device workers claim variable-size *chunks* of the queue per request —
  the paper's larger-GPU-chunk policy — sized by a measured relative-speed
  estimate (:class:`ChunkPolicy`: cost-model seed, online EWMA refinement);
* IWPP updates are monotone + commutative and tiles are re-executable from
  current state, so a worker failure is handled by re-queuing its tile(s) —
  the same §5.2.4 argument that makes queue overflow benign.

Threads genuinely overlap because jitted JAX CPU computations release the
GIL.  Writes are per-tile-interior (disjoint) and happen under the array
lock; halo *reads* happen outside it (a block slice is O(tile²) numpy copy
— serializing every slice behind the claim lock was the workers=2
regression).  A read torn against a concurrent interior write observes a
per-pixel mix of old and new values, every one of which is a valid
monotone state; the writer's changed edge re-marks this tile, so a stale
or torn read at worst re-queues a tile (never corrupts).
"""

from __future__ import annotations

import queue
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.geometry import _moore_offsets, pad_value_for


@dataclass
class SchedulerStats:
    tiles_processed: int = 0
    rounds: int = 0
    requeues_from_failures: int = 0
    tiles_requeued: int = 0        # unconverged (partial) drains re-queued
    per_worker: Dict[int, int] = field(default_factory=dict)
    device_tiles: int = 0          # of tiles_processed, drained by DeviceWorkers
    # repr of every exception a worker died on, except the injected ones
    # (InjectedFailure): a device drain that fails to compile lands here
    # instead of silently leaving the queue to the host threads.
    worker_errors: List[str] = field(default_factory=list)
    # True iff run() gave up with work still queued (every survivor wave
    # died, max_survivor_waves exhausted): the state is NOT at its fixed
    # point and must not be treated as one.
    incomplete: bool = False


class InjectedFailure(RuntimeError):
    """Raised by the ``fail_worker`` fault-injection hook; the one worker
    death that is expected and therefore not recorded as an error."""


class ChunkPolicy:
    """The paper's larger-GPU-chunk policy (§4): how many queue entries a
    device worker claims per FCFS request.

    A device consumer amortizes its dispatch overhead over a whole chunk,
    so it should claim ``rel_speed`` tiles for every single tile a host
    thread claims, where ``rel_speed`` is the device:host throughput ratio.
    The ratio is *seeded* from the cost model — analytically on a cold
    start, from the measured ``hybrid_rel_speed`` once a calibration
    profile is installed (DESIGN.md §2.8; ``seed_kind`` records which) —
    and *refined online*: every worker reports its measured
    seconds-per-tile and the policy keeps one EWMA per worker class —
    demand-driven FCFS then converges the split to the actual relative
    speeds, the paper's load-balance argument made quantitative.
    """

    def __init__(self, rel_speed: float = 4.0, max_chunk: int = 16,
                 alpha: float = 0.25, seed_kind: str = "analytic"):
        self.seed_rel_speed = max(1.0, float(rel_speed))
        self.seed_kind = seed_kind
        self.max_chunk = max(1, int(max_chunk))
        self.alpha = alpha
        self._host_spt: Optional[float] = None    # EWMA host seconds/tile
        self._dev_spt: Optional[float] = None     # EWMA device seconds/tile
        self._lock = threading.Lock()

    def _ewma(self, old: Optional[float], x: float) -> float:
        return x if old is None else (1 - self.alpha) * old + self.alpha * x

    def observe_host(self, seconds_per_tile: float) -> None:
        with self._lock:
            self._host_spt = self._ewma(self._host_spt, seconds_per_tile)

    def observe_device(self, seconds_per_tile: float) -> None:
        with self._lock:
            self._dev_spt = self._ewma(self._dev_spt, seconds_per_tile)

    @property
    def rel_speed(self) -> float:
        """Measured host:device seconds-per-tile ratio (falls back to the
        analytic seed until both classes have been observed)."""
        with self._lock:
            if self._host_spt is None or self._dev_spt is None or \
                    self._dev_spt <= 0.0:
                return self.seed_rel_speed
            return self._host_spt / self._dev_spt

    def chunk(self) -> int:
        """Tiles a device worker should claim per FCFS request.

        Floored at 2: even a speed-parity device stream claims one tile of
        look-ahead, amortizing the per-claim lock/wakeup overhead across
        two dispatches — the same reason ``max_chunk`` allows two batched
        dispatches ahead.  The claim-time half-queue cap still degrades
        the chunk to 1 at the wavefront's end, so look-ahead never
        starves the other consumers of the last tiles.
        """
        return int(np.clip(round(self.rel_speed), 2, self.max_chunk))


@dataclass
class DeviceWorker:
    """One accelerator consumer of the shared FCFS queue (DESIGN.md §2.3).

    ``batch_fn`` is the tiled engine's ``batched_tile_solver`` contract:
    a pytree of halo blocks with a leading (K,) batch dim maps to
    ``(drained blocks, unconverged (K,) bools)`` — the same solvers that
    back ``run_tiled(drain_batch=K)`` (plain ``jax.vmap`` of the per-tile
    solve, or the Pallas grid-over-batch kernels) plug in unchanged.  The
    worker splits its claimed chunk into groups of exactly ``drain_batch``
    blocks (short groups padded with neutral blocks from ``pad_block``),
    so the jitted solver sees a single static batch shape.
    """

    batch_fn: Callable
    drain_batch: int = 4
    name: str = "device"


class TileScheduler:
    """FCFS demand-driven scheduler over a shared N-D state.

    The spatial rank is inferred from ``init_active``: a (nty, ntx) activity
    grid schedules 2-D tiles over the trailing two state axes, a 3-D grid
    schedules (T+2)^3 halo cubes over the trailing three, and so on
    (DESIGN.md §2.7).  Tile ids are grid-coordinate tuples throughout.

    Parameters
    ----------
    state : dict of str -> np.ndarray, all sharing the trailing spatial dims.
    tile_fn : callable (block_state, ) -> (new_block_state, info)
        Drains one (T+2,)^ndim halo block to local stability.  ``info`` may
        be ``True`` to signal an *unconverged* (partial) drain — the
        scheduler then writes the partial progress back (monotone updates
        make that safe) and re-queues the tile, the host-side analogue of
        the tiled engine's truncation self-requeue.  Any other value
        (``None``, a border-changed dict) is ignored.
    init_active : boolean grid-shaped array of initially-active tiles; its
        rank sets the scheduler's spatial ndim.
    merge_block_fn : optional coordinate-aware merge: called as
        ``merge_block_fn(origin, old_inner, new_inner) -> merged`` (origin
        is the interior's global ndim-tuple, e.g. ``(r0, c0)`` in 2-D) with
        dicts of all mutable leaves' tile interiors and the interior's
        global origin.  Needed when the commutative merge couples leaves or
        depends on pixel coordinates (e.g. EDT's Voronoi-pointer distance
        compare); overrides ``merge_fn`` when given.
    pad_values : optional per-leaf scalars for out-of-array halo cells (the
        op's *neutral* fills, ``PropagationOp.pad_value``).  Without them the
        scheduler falls back to dtype-min/``-inf`` (False for bool), which is
        only correct for max-propagating payloads — EDT's coordinate planes,
        for instance, need their far-sentinel fill instead.
    device_workers : optional sequence of :class:`DeviceWorker` — batched
        accelerator consumers sharing this queue with the host threads (the
        cooperative `hybrid` pool).  ``n_workers`` may be 0 for a
        device-only pool; at least one worker of either kind must exist.
    chunk_policy : optional :class:`ChunkPolicy` sizing device claims
        (default: a fresh policy with the seed ratio 4).  Pass a shared
        instance to keep the EWMA learning across scheduler passes.
    """

    def __init__(self, state: Dict[str, np.ndarray], tile: int,
                 tile_fn: Optional[Callable], init_active: np.ndarray,
                 n_workers: int = 4, mutable=("J",),
                 merge_fn: Optional[Callable] = None,
                 merge_block_fn: Optional[Callable] = None,
                 pad_values: Optional[Dict[str, object]] = None,
                 device_workers: Sequence[DeviceWorker] = (),
                 chunk_policy: Optional[ChunkPolicy] = None,
                 fail_worker: Optional[int] = None, fail_after: int = 3):
        init_active = np.asarray(init_active)
        ndim = init_active.ndim
        spatial = next(iter(state.values())).shape[-ndim:]
        assert all(s % tile == 0 for s in spatial), \
            "host scheduler expects tile-aligned grids"
        self.state = state
        self.tile = tile
        self.tile_fn = tile_fn
        self.ndim = ndim
        self.grid = tuple(s // tile for s in spatial)
        assert self.grid == init_active.shape, \
            "init_active grid does not match state shape / tile"
        self.n_workers = n_workers
        self.device_workers = list(device_workers)
        if n_workers <= 0 and not self.device_workers:
            raise ValueError("TileScheduler needs at least one worker "
                             "(n_workers >= 1 or a DeviceWorker)")
        if n_workers > 0 and tile_fn is None:
            raise ValueError("host workers need a tile_fn")
        self.chunk_policy = chunk_policy or ChunkPolicy()
        self.mutable = mutable
        # Commutative merge at write-back — the scheduler analogue of the
        # paper's atomicMax/atomicCAS: a worker that raced with a fresher
        # update must not regress it.  Default: elementwise max (morph).
        self.merge_fn = merge_fn or (lambda key, old, new: np.maximum(old, new))
        self.merge_block_fn = merge_block_fn
        self.pad_values = pad_values or {}
        self.fail_worker = fail_worker     # a worker id, or "all"
        self.fail_after = fail_after
        self._lock = threading.Lock()
        self._q: "queue.Queue[Tuple[int, ...]]" = queue.Queue()
        self._in_queue: Set[Tuple[int, ...]] = set()
        self._inflight = 0
        self._done = threading.Condition(self._lock)
        self.stats = SchedulerStats()
        with self._lock:   # _push notifies `_done`, which requires the lock
            for tid in np.ndindex(*self.grid):
                if init_active[tid]:
                    self._push(tid)

    # 2-D compatibility aliases (grid is the canonical N-D spelling).
    @property
    def nty(self) -> int:
        return self.grid[0]

    @property
    def ntx(self) -> int:
        return self.grid[-1]

    # -- queue ops (lock held) ---------------------------------------------
    def _push(self, tid):
        if tid not in self._in_queue:
            self._in_queue.add(tid)
            self._q.put(tid)
            self._done.notify_all()   # wake idle workers waiting for work

    def _slice_block(self, tid):
        T, nd = self.tile, self.ndim
        spatial = next(iter(self.state.values())).shape[-nd:]
        origin = tuple(t * T for t in tid)
        out = {}
        for k, arr in self.state.items():
            pad_val = pad_value_for(self.pad_values, k, arr.dtype)
            blk = np.full(arr.shape[:-nd] + (T + 2,) * nd, pad_val,
                          dtype=arr.dtype)
            src, dst = [], []
            for o, s in zip(origin, spatial):
                lo, hi = max(0, o - 1), min(s, o + T + 1)
                src.append(slice(lo, hi))
                dst.append(slice(lo - (o - 1), lo - (o - 1) + (hi - lo)))
            blk[(Ellipsis,) + tuple(dst)] = arr[(Ellipsis,) + tuple(src)]
            out[k] = blk
        return out

    def pad_block(self):
        """A fully-neutral halo block: converges immediately, marks nothing.

        Device workers use it to pad short chunks up to their static
        ``drain_batch`` shape (the same dead-slot neutralization as
        `run_tiled`'s batched drain).
        """
        T, nd = self.tile, self.ndim
        return {k: np.full(arr.shape[:-nd] + (T + 2,) * nd,
                           pad_value_for(self.pad_values, k, arr.dtype),
                           dtype=arr.dtype)
                for k, arr in self.state.items()}

    def _write_back(self, tid, block) -> List[bool]:
        """Merge one block's interior; return 2*ndim changed-face flags in
        (axis0-lo, axis0-hi, axis1-lo, axis1-hi, ...) order (2-D: top,
        bottom, left, right)."""
        T, nd = self.tile, self.ndim
        origin = tuple(t * T for t in tid)
        inner = (Ellipsis,) + tuple(slice(o, o + T) for o in origin)
        crop = (Ellipsis,) + (slice(1, -1),) * nd
        faces = [False] * (2 * nd)
        merged_all = None
        if self.merge_block_fn is not None:
            old_all = {k: self.state[k][inner] for k in self.mutable}
            new_all = {k: np.asarray(block[k])[crop] for k in self.mutable}
            merged_all = self.merge_block_fn(origin, old_all, new_all)
        for k in self.mutable:
            new_inner = np.asarray(block[k])[crop]
            old_inner = self.state[k][inner]
            merged = (merged_all[k] if merged_all is not None
                      else self.merge_fn(k, old_inner, new_inner))
            diff = merged != old_inner
            if diff.any():
                for a in range(nd):
                    axis = diff.ndim - nd + a
                    faces[2 * a] |= bool(np.take(diff, 0, axis=axis).any())
                    faces[2 * a + 1] |= bool(np.take(diff, -1, axis=axis).any())
                self.state[k][inner] = merged
        return faces

    def _mark_neighbors(self, tid, faces):
        """Queue every Moore neighbor whose shared boundary saw a change:
        an offset is marked iff some axis it moves along has its matching
        face flag set (a corner/edge ghost is reachable iff one of its
        incident faces changed — conn26's corner semantics, DESIGN.md §2.7).
        """
        nd = self.ndim
        for off in _moore_offsets(nd, nd):
            flag = any(faces[2 * a + (0 if off[a] < 0 else 1)]
                       for a in range(nd) if off[a] != 0)
            if not flag:
                continue
            nb = tuple(t + d for t, d in zip(tid, off))
            if all(0 <= c < g for c, g in zip(nb, self.grid)):
                self._push(nb)

    def _commit(self, tid, block, unconverged: bool, wid: int,
                device: bool = False):
        """Write one drained block back and update marks/stats (lock held)."""
        edges = self._write_back(tid, block)
        self._mark_neighbors(tid, edges)
        if unconverged:
            # Partial drain (cut off at the solver's iteration bound): the
            # written-back progress is monotone-safe, but the tile is NOT at
            # its fixed point — keep it queued (truncation self-requeue).
            self._push(tid)
            self.stats.tiles_requeued += 1
        self.stats.tiles_processed += 1
        self.stats.device_tiles += device
        self.stats.per_worker[wid] = self.stats.per_worker.get(wid, 0) + 1

    def _record_death(self, who: str, err: Exception) -> None:
        """Record and warn about a worker killed by a real exception."""
        if isinstance(err, InjectedFailure):
            return
        with self._lock:
            self.stats.worker_errors.append(f"{who}: {err!r}")
        warnings.warn(f"TileScheduler {who} died on {err!r}; its tiles were "
                      "re-queued for the surviving workers", RuntimeWarning,
                      stacklevel=2)

    def _should_fail(self, wid: int, n_done: int) -> bool:
        """Fault-injection hook: kill worker ``fail_worker`` (or every
        worker, ``"all"``) after it has processed ``fail_after`` tiles."""
        return (self.fail_worker is not None
                and (self.fail_worker == "all" or self.fail_worker == wid)
                and n_done >= self.fail_after)

    # -- host worker loop ----------------------------------------------------
    def _worker(self, wid: int):
        n_done = 0
        while True:
            # Atomic claim-then-get: the queue pop and the inflight increment
            # happen under ONE lock acquisition.  The previous unlocked
            # `q.get()` left a window between a successful pop and
            # `_inflight += 1` in which the tile was in a worker's hands but
            # visible nowhere — idle peers observing `inflight == 0 and
            # q.empty()` exited, silently degrading the pool to one worker.
            with self._lock:
                try:
                    tid = self._q.get_nowait()
                except queue.Empty:
                    if self._inflight == 0:
                        return      # genuinely done: nothing queued, nothing claimed
                    # A peer holds a tile; it may mark neighbors (push) or
                    # finish (inflight drop) — both notify `_done`.  The
                    # timeout is only a safety net against a lost wakeup.
                    self._done.wait(timeout=0.05)
                    tid = None
                else:
                    self._inflight += 1
                    self._in_queue.discard(tid)
            if tid is None:
                continue
            # Slice outside the lock: the copy is the expensive part of a
            # claim, and a torn read against a concurrent interior write is
            # monotone-safe (module docstring) — the writer's edge change
            # re-marks this tile, so nothing is ever lost.
            block = self._slice_block(tid)
            try:
                if self._should_fail(wid, n_done):
                    raise InjectedFailure(f"injected failure on worker {wid}")
                t0 = time.perf_counter()
                new_block, info = self.tile_fn(block)
                self.chunk_policy.observe_host(time.perf_counter() - t0)
                with self._lock:
                    self._commit(tid, new_block, info is True, wid)
                    n_done += 1
            except Exception as e:
                # Fault tolerance: re-queue the tile; state untouched (tiles
                # are idempotent under IWPP's monotone commutative updates).
                with self._lock:
                    self._push(tid)
                    self.stats.requeues_from_failures += 1
                    self._inflight -= 1
                    self._done.notify_all()
                self._record_death(f"host worker {wid}", e)
                return  # worker dies; remaining workers pick up the slack
            with self._lock:
                self._inflight -= 1
                self._done.notify_all()   # idle peers re-check the exit condition

    # -- device worker loop --------------------------------------------------
    def _device_worker(self, wid: int, dev: DeviceWorker):
        """Batched accelerator consumer: claim a chunk, drain it, merge back.

        The chunk is claimed under ONE lock acquisition (the same atomic
        claim-then-get invariant as the host loop, generalized to K tiles),
        then drained and committed one ``drain_batch`` group at a time:
        each group is sliced *after* the previous group committed, so
        claim-ahead costs queue ordering only, never halo staleness across
        groups (a chunk-wide pre-claim snapshot measurably inflated the
        cooperative pool's tile count ~3-5% in re-drains).  Tiles *within*
        a group still drain concurrently from each other's pre-group
        snapshots — exactly `run_tiled`'s batched-drain seam: interior
        writes are disjoint, writeback goes through the commutative merge,
        and a changed edge re-marks the neighbor, so a stale read at worst
        costs a re-drain, never a wrong fixed point (DESIGN.md §2.1/§2.3).
        """
        n_done = 0
        K = max(1, dev.drain_batch)
        while True:
            with self._lock:
                # Claim at most half the queue (ceil): a chunk bigger than
                # the device's measured speed advantage starves the other
                # consumers and serializes the wavefront — demand-driven
                # means leaving work for whoever is free.
                want = min(self.chunk_policy.chunk(),
                           max(1, -(-self._q.qsize() // 2)))
                tids: List[Tuple[int, int]] = []
                while len(tids) < want:
                    try:
                        tids.append(self._q.get_nowait())
                    except queue.Empty:
                        break
                if not tids:
                    if self._inflight == 0:
                        return
                    self._done.wait(timeout=0.05)
                    continue
                self._inflight += len(tids)
                for t in tids:
                    self._in_queue.discard(t)
            for g0 in range(0, len(tids), K):
                gtids = tids[g0:g0 + K]
                # Group block copies outside the lock (same torn-read
                # argument as the host loop; the tiles were claimed above).
                blocks = [self._slice_block(t) for t in gtids]
                t0 = time.perf_counter()
                try:
                    if self._should_fail(wid, n_done):
                        raise InjectedFailure(
                            f"injected failure on device worker {wid}")
                    results = self._drain_chunk(dev, blocks)
                except Exception as e:
                    with self._lock:
                        # Re-queue this group and every unstarted one; the
                        # groups already committed stay committed (monotone
                        # updates make partial chunk progress safe).
                        rest = tids[g0:]
                        for t in rest:
                            self._push(t)
                        self.stats.requeues_from_failures += len(rest)
                        self._inflight -= len(rest)
                        self._done.notify_all()
                    self._record_death(f"device worker {wid} ({dev.name})", e)
                    return  # device worker dies; survivors take over
                self.chunk_policy.observe_device(
                    (time.perf_counter() - t0) / len(gtids))
                with self._lock:
                    for t, (nb, unconv) in zip(gtids, results):
                        self._commit(t, nb, unconv, wid, device=True)
                    n_done += len(gtids)
                    self._inflight -= len(gtids)
                    self._done.notify_all()

    def _drain_chunk(self, dev: DeviceWorker, blocks):
        """Drain a claimed chunk in groups of exactly ``drain_batch`` blocks.

        Short groups are padded with neutral blocks (see :meth:`pad_block`)
        so the jitted batched solver only ever sees one static (K, T+2, T+2)
        shape; pad slots converge immediately and are dropped unmerged.
        """
        K = max(1, dev.drain_batch)
        results = []
        neutral = None
        for g0 in range(0, len(blocks), K):
            group = blocks[g0:g0 + K]
            n_live = len(group)
            if n_live < K:
                if neutral is None:
                    neutral = self.pad_block()
                group = group + [neutral] * (K - n_live)
            if K == 1:
                # Singleton group: a length-1 np.stack would copy the whole
                # block again just to add the batch axis — a view does it.
                stacked = {k: v[None] for k, v in group[0].items()}
            else:
                stacked = {k: np.stack([b[k] for b in group])
                           for k in group[0].keys()}
            out, unconv = dev.batch_fn(stacked)
            out = {k: np.asarray(v) for k, v in out.items()}
            unconv = np.asarray(unconv)
            for i in range(n_live):
                results.append(({k: v[i] for k, v in out.items()},
                                bool(unconv[i])))
        return results

    # -- pool composition ----------------------------------------------------
    def _roles(self):
        """The mixed worker pool: ('host', None) x n_workers + device specs."""
        return ([("host", None)] * self.n_workers
                + [("device", d) for d in self.device_workers])

    def _spawn(self, role, wid: int) -> threading.Thread:
        kind, dev = role
        if kind == "host":
            return threading.Thread(target=self._worker, args=(wid,),
                                    daemon=True)
        return threading.Thread(target=self._device_worker, args=(wid, dev),
                                daemon=True)

    # Survivor waves after the initial pass (fault tolerance); bounds the
    # pathological case of a tile_fn that fails deterministically forever.
    max_survivor_waves = 32

    def run(self) -> SchedulerStats:
        roles = self._roles()
        workers = [self._spawn(role, w) for w, role in enumerate(roles)]
        for t in workers:
            t.start()
        for t in workers:
            t.join()
        # Killed workers re-queue their tile(s) and die, so a wave can end
        # with work still pending — and a survivor wave can *itself* lose
        # workers.  Re-check after every wave (the old single survivor pass
        # returned with a non-empty queue if its workers also died).  Waves
        # respawn from the same mixed role pool, one short of the original
        # (the model: one worker died).  The dropped role is the *first*
        # one — a host thread when any exist (roles list hosts first) — so
        # a hybrid pool keeps its device consumers alive across waves.
        next_wid = len(roles)
        surv_roles = roles[1:] if len(roles) > 1 else roles
        waves = 0
        while not self._q.empty() and waves < self.max_survivor_waves:
            survivors = [self._spawn(role, next_wid + w)
                         for w, role in enumerate(surv_roles)]
            for t in survivors:
                t.start()
            for t in survivors:
                t.join()
            next_wid += len(survivors)
            waves += 1
        if not self._q.empty():
            # Every wave died with work still queued (a deterministically
            # failing tile_fn).  Never report this as a fixed point.
            self.stats.incomplete = True
            warnings.warn(
                f"TileScheduler gave up after {waves} survivor waves with "
                f"~{self._q.qsize()} tiles still queued; the state is NOT at "
                "its fixed point (stats.incomplete=True)", RuntimeWarning,
                stacklevel=2)
        return self.stats
