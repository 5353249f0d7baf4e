"""Unified entry point for every IWPP engine: ``solve(op, state, ...)``.

The paper's central claim (§3-§4) is that the *right* execution strategy for
the irregular wavefront propagation pattern depends on the input: wavefront
density, grid size, and the devices available.  The repo implements the
strategies as separate engines; this module is the seam that picks among
them:

  engine name        implementation                        paper analogue
  ----------------   -----------------------------------   -------------------
  "sweep"            core.frontier.run_dense  (E0)         SR_GPU full sweeps
  "frontier"         core.frontier.run_dense  (E1)         Naive/PF queue
  "tiled"            core.tiles.run_tiled     (E2)         TQ/BQ/GBQ hierarchy
  "tiled-pallas"     run_tiled + kernels.ops tile solver   BQ drain in VMEM
  "shard_map"        core.distributed.run_sharded (E3)     §4 TP/BP multi-GPU
  "shard_map-tiled"  run_sharded w/ per-shard run_tiled    §4 pipeline over
                     TP drains (E3∘E2, DESIGN.md §2.2)     §3.2 queues
  "scheduler"        core.scheduler.TileScheduler          §4 Fig. 8 host FCFS
  "hybrid"           TileScheduler + DeviceWorker pool     §4 cooperative
                     (DESIGN.md §2.3)                      CPU+GPU execution
  "auto"             CostModel ranking (+ autotune)        §4 demand-driven map

``engine="auto"`` ranks candidate ``(engine, tile, queue_capacity)``
configurations with a pluggable :class:`CostModel` — transfer cost plus
per-tile drain cost, in the style of MATCH's ZigZag cost model — fed by
cheap input statistics (seed-pixel density from ``op.init_frontier``, grid
size, device count).  ``autotune=True`` additionally micro-benchmarks the
model's top candidates on the real input and caches the winner keyed by an
input signature, so repeated solves of same-shaped inputs pay nothing.

Every engine returns the same normalized :class:`SolveStats` record so
benchmarks and docs can compare engines uniformly.  See DESIGN.md §4 for
the architecture and README.md for the engine-selection matrix.

Operations plug in through the first-class ``repro.ops`` registry
(DESIGN.md §2.4, docs/OPS.md): an :class:`~repro.ops.OpSpec` declares the
op factory, state builder, result extractor, Pallas tile-solver factories,
the host scheduler's commutative merge, and the cost-model weights.
``solve()`` accepts either a :class:`PropagationOp` instance or a
registered op *name* — ``solve("edt", fg_image)`` builds the op and state
through the spec.  The legacy per-plug-point registrars
(:func:`register_pallas_solver`, :func:`register_scheduler_merge`) remain
as shims over the registry.
"""

from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune_disk, calibrate, compile_cache, spans
from repro.core import tiles as _tiles
from repro.core.distributed import run_sharded
from repro.core.frontier import run_dense
from repro.core.pattern import PropagationOp, restore_invalid, tree_shape
from repro.core.scheduler import ChunkPolicy, DeviceWorker, TileScheduler
from repro.core.tiles import (active_tiles_from_frontier, default_batched_solver,
                              default_tile_solver, initial_active_tiles)
from repro.kernels import resolve_interpret
from repro.kernels.queue import QUEUE_LOWERING_GAP
# Importing repro.ops registers the built-in op catalog (morph, edt,
# fill_holes, label) before any dispatch can happen.
from repro.ops import (amend_op_class, get_op, list_ops, on_spec_change,
                       spec_for)

ENGINES = ("sweep", "frontier", "tiled", "tiled-pallas", "shard_map",
           "shard_map-tiled", "scheduler", "hybrid", "auto")

DEFAULT_TILES = (32, 64, 128)
DEFAULT_QUEUE_CAPACITY = 64
# Queue slots drained concurrently per dispatch by the tiled engines (the
# paper's parallel consumption of the global queue; DESIGN.md §2).
DEFAULT_DRAIN_BATCH = 4
# Largest tile that batches by default.  Small blocks are dispatch-bound, so
# draining K=4 of them per dispatch is a measured ~4-5x win on CPU hosts
# (BENCH_tiled.json); large blocks are bandwidth-bound and the batch pays
# max-of-batch iteration inflation plus cache pressure, so they stay
# sequential unless the caller (or autotune) asks otherwise.  Compiled, the
# Pallas grid kernels batch at every tile: `auto` offers them at K=4 too.
BATCH_DEFAULT_MAX_TILE = 32


def _default_drain_batch(tile: int) -> int:
    return DEFAULT_DRAIN_BATCH if tile <= BATCH_DEFAULT_MAX_TILE else 1


# (ndim, tile) pairs whose Pallas tile kernels compile for a TPU v5e, dense
# and batched (tests/test_tpu_compile.py compiles each; benchmarks/
# ENGINE_GRID_v5e.json records each run on one).  Compiled, `auto`
# offers `tiled-pallas` only at these: a 3-D 130³ block is far past VMEM,
# and no 2-D 32-tile kernel has been compiled.
COMPILED_PALLAS_TILES = ((2, 64), (2, 128), (3, 32))


# ---------------------------------------------------------------------------
# Normalized stats — the uniform record every engine reports.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolveStats:
    """Engine-independent work record (rounds / sources / tiles / overflow).

    ``rounds`` counts the engine's outermost convergence loop: dense rounds
    for E0/E1, outer queue rounds for E2, BP rounds for E3, and FCFS
    passes (always reported as 1) for the host scheduler.
    """

    engine: str
    rounds: int = 0
    sources_processed: int = 0     # frontier pixels acted on (dense engines)
    tiles_processed: int = 0       # tile drains (tiled/scheduler engines)
    overflow_events: int = 0       # rounds where active tiles > queue capacity
    requeues: int = 0              # scheduler fault-tolerance requeues
    tiles_requeued: int = 0        # unconverged (partial) drains re-queued
    tile: Optional[int] = None
    queue_capacity: Optional[int] = None
    drain_batch: Optional[int] = None        # blocks drained per dispatch
    kernel_queue: bool = False               # in-kernel queue (DESIGN.md §2.5)
    kernel_queue_capacity: Optional[int] = None  # resolved local-queue slots
    n_devices: int = 1
    predicted_cost: Optional[float] = None   # CostModel units (auto only)
    autotuned: bool = False
    # True iff the engine gave up before reaching (and verifying) the fixed
    # point — the result is a monotone-valid *partial* state, never to be
    # treated as converged.  Filled by the `hybrid` engine when its BP
    # verification round still finds a residual frontier at max_rounds; the
    # `scheduler` engine raises instead (no BP loop to recover through).
    incomplete: bool = False
    # Compiled-step builds (core.compile_cache misses) that happened during
    # this run.  The persistent-RunState contract (DESIGN.md §2.6) is that
    # this stays *constant in the round count*: a warm re-solve reports 0,
    # and an engine whose recompiles grow with `rounds` is leaking traces.
    recompiles: int = 0
    # Which cost model decided an `auto` run: "analytic" (cold start) or
    # "measured" (a calibration profile was installed; DESIGN.md §2.8).
    # None for explicitly-chosen engines — nothing decided anything.
    cost_model: Optional[str] = None
    # Seconds of the run's ``iwpp.engine`` span: the engine adapter with the
    # output forced resident (block_until_ready).
    wall_time_s: float = 0.0
    # Requests coalesced into the one solve that produced this record
    # (solve_batch's vmapped dense path); None for solo solves.
    batch_size: Optional[int] = None
    # The resolved Pallas mode the run used (repro.kernels.resolve_interpret:
    # compiled exactly on a TPU backend unless the caller forced it).
    interpret: Optional[bool] = None
    # Host-scheduler engines (scheduler, hybrid): tiles the hybrid device
    # streams drained, and the repr of every exception a worker died on
    # (injected test failures excluded; each one was also warned about).
    device_tiles: int = 0
    worker_errors: Tuple[str, ...] = ()
    # The call's host spans, (name, parent index, start ns, end ns) on
    # time.monotonic_ns, and its device -> host reads (repro.core.spans;
    # docs/ENGINES.md "Tracing").  Filled by the outermost entry point.
    spans: Tuple[Tuple[str, int, int, int], ...] = ()
    host_syncs: int = 0


# ---------------------------------------------------------------------------
# Op plug points — backed by the repro.ops registry (DESIGN.md §2.4).
#
# The three legacy Dict[type, Callable] registries that used to live here
# (_PALLAS_SOLVERS / _PALLAS_BATCH_SOLVERS / _SCHEDULER_MERGES) are gone:
# every per-op plug point is a field of the op's OpSpec.  The two functions
# below are compatibility shims re-exported for callers of the old API.
# ---------------------------------------------------------------------------


def register_pallas_solver(op_cls: type, factory: Callable,
                           batched_factory: Optional[Callable] = None) -> None:
    """Shim over ``repro.ops``: patch ``OpSpec.pallas_solver`` (and
    optionally ``pallas_batch_solver``) for ``op_cls``.

    ``factory(op, interpret, max_iters) -> tile_solver``; ``max_iters`` is
    the engine's per-drain iteration bound ((T+2)² — the longest geodesic
    inside one halo block); solvers must return ``(block, unconverged)``
    with ``unconverged`` True when the drain was cut off at the bound, so
    the engine re-queues instead of silently accepting a partial drain.
    ``batched_factory(op, interpret, max_iters) -> batched_tile_solver``
    (leaves carry a leading (K,) batch dim) backs the batched drain;
    without one, the engine falls back to ``jax.vmap`` of the per-tile
    solver.  New code should ship a full ``OpSpec`` via
    :func:`repro.ops.register_op` instead (docs/OPS.md).
    """
    fields: Dict[str, Callable] = {"pallas_solver": factory}
    if batched_factory is not None:
        fields["pallas_batch_solver"] = batched_factory
    amend_op_class(op_cls, **fields)


def register_scheduler_merge(op_cls: type, factory: Callable) -> None:
    """Shim over ``repro.ops``: patch ``OpSpec.scheduler_merge`` for
    ``op_cls`` (``factory(op) -> merge_block_fn``; returning None selects
    the scheduler's built-in elementwise-max merge)."""
    amend_op_class(op_cls, scheduler_merge=factory)


# ---------------------------------------------------------------------------
# Input statistics — the cheap probes that feed the cost model.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputStats:
    """What the cost model knows about one input (all O(N) probes).

    ``bytes_per_pixel`` / ``round_cost_weight`` are the *op's* cost hints,
    copied from its :class:`~repro.ops.OpSpec` by
    :func:`collect_input_stats` (defaults = the morph reference op).  They
    let one CostModel price every registered op without per-op branches.
    """

    height: int
    width: int
    n_sources: int                      # initial frontier population
    active_tiles: Dict[int, int]        # tile size -> initially-active tiles
    n_devices: int
    bytes_per_pixel: float = 4.0        # mutable HBM payload per pixel
    round_cost_weight: float = 1.0      # per-round compute vs morph's max
    shape: Tuple[int, ...] = ()         # full spatial shape (() = 2-D compat)
    n_offsets: int = 8                  # neighborhood size (offsets/pixel)
    op_name: str = ""                   # registry name ("" = unregistered op)

    @property
    def spatial(self) -> Tuple[int, ...]:
        return self.shape if self.shape else (self.height, self.width)

    @property
    def ndim(self) -> int:
        return len(self.spatial)

    @property
    def area(self) -> int:
        return math.prod(self.spatial)

    @property
    def density(self) -> float:
        return self.n_sources / max(self.area, 1)

    @property
    def depth_est(self) -> float:
        """Expected propagation depth (rounds to the fixed point).

        Mean inter-source spacing: sparse seeds must sweep waves across
        O((area / n_sources)^(1/ndim)) pixels; a near-full frontier
        converges in O(1) rounds.  This single number is what separates the
        dense and tiled regimes (paper Table 1 / Fig. 12).
        """
        return max(1.0, (self.area / max(self.n_sources, 1))
                   ** (1.0 / self.ndim))

    def n_tiles(self, tile: int) -> int:
        return math.prod(-(-s // tile) for s in self.spatial)


def _input_probe_for(op: PropagationOp, tiles: Tuple[int, ...]):
    """The selection probes as one jitted program: ``state -> int32[1 +
    len(tiles)]``, the initial frontier's population and then, for each
    tile size, the tiles it activates (:func:`initial_active_tiles`).  The
    frontier is built and dilated once, whatever the number of tiles."""
    def build():
        @jax.jit
        def probe(state):
            f0 = op.init_frontier(state)
            dil = _tiles.dilate_frontier(op, f0)
            return jnp.stack(
                [jnp.sum(f0, dtype=jnp.int32)]
                + [jnp.sum(_tiles.tiles_any(dil, t), dtype=jnp.int32)
                   for t in tiles])
        return probe

    return compile_cache.get(
        ("input-probe", type(op), op.connectivity, op.ndim, tiles), build)


def collect_input_stats(op: PropagationOp, state, n_devices: int = 1,
                        tiles: Sequence[int] = DEFAULT_TILES) -> InputStats:
    spatial = tree_shape(state, op.ndim)
    H, W = spatial[-2:]
    tiles = tuple(tiles)
    with spans.span("iwpp.select.input_stats"):
        n_sources, *counts = spans.host_ints(
            _input_probe_for(op, tiles)(state))
    active = dict(zip(tiles, counts))
    spec = spec_for(op)
    return InputStats(H, W, n_sources, active, n_devices,
                      bytes_per_pixel=spec.bytes_per_pixel if spec else 4.0,
                      round_cost_weight=spec.round_cost_weight if spec else 1.0,
                      shape=spatial, n_offsets=len(op.offsets),
                      op_name=spec.name if spec else "")


# ---------------------------------------------------------------------------
# Cost model — MATCH-style: transfer cost + innermost (drain) cost.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    engine: str
    tile: Optional[int] = None
    queue_capacity: Optional[int] = None
    drain_batch: Optional[int] = None   # queue slots drained per dispatch
    # tiled-pallas only: drain each block through the in-kernel multi-level
    # queue (DESIGN.md §2.5) instead of dense full-block sweeps.
    kernel_queue: bool = False
    kernel_queue_capacity: Optional[int] = None  # None = kernel-side default


class CostModel:
    """Relative-cost model for engine selection (unit: one HBM pixel touch).

    Follows the MATCH/ZigZag split: ``transfer_cost`` charges the data an
    engine moves through the slow memory level, ``drain_cost`` charges the
    compute of the innermost propagation loops.  Subclass and override the
    two methods (and/or the constants) to retarget the model — e.g. measured
    HBM/VMEM bandwidths of a specific TPU generation.

    The qualitative shape mirrors the paper's findings: dense engines pay
    the full grid every round, so they win when the wavefront covers the
    grid and converges in few rounds; the tiled hierarchy pays only active
    tiles plus a per-drain dispatch overhead, so it wins as the wavefront
    sparsifies (paper Fig. 12: speedups grow with wave sparsity).
    """

    # Which model decided, reported through SolveStats.cost_model (the
    # MeasuredCostModel subclass overrides this with "measured").
    kind = "analytic"

    # Relative VMEM:HBM bandwidth — inner drain iterations stay on-chip, so
    # a tile's local rounds are discounted by this factor (the paper's BQ
    # amortization argument).
    vmem_discount = 1.0 / 16.0
    # Fixed cost of dispatching one tile drain (lax.scan step / host call).
    # A batched drain issues one dispatch per `drain_batch` blocks, so the
    # effective per-tile term is tile_dispatch / drain_batch (the paper's
    # point that queue consumption must be parallel across SMs to pay off).
    tile_dispatch = 500.0
    # E0 recomputes every valid pixel with no tracking: constant-factor
    # penalty over E1 plus the extra settle rounds.
    sweep_penalty = 1.25
    # Per-BP-round collective latency on a mesh, per device.
    collective_latency = 5_000.0
    # Host (numpy/threading) path: slower per-pixel than the XLA path, plus
    # Python dispatch per drain.
    host_penalty = 20.0
    host_dispatch = 20_000.0
    # Pallas interpret mode executes the kernel body in Python — only ever
    # competitive when compiled for a real TPU.
    interpret_penalty = 50.0
    # Compiled backend only (``interpret`` False; interpreted, no cost below
    # changes).  Fitted to warm seconds per call on a TPU v5e
    # (benchmarks/ENGINE_GRID_v5e.json, replayed by tests/test_interpret.py).
    # The XLA tile drain (``_xla_drain``) runs each inner iteration as
    # several separate device ops out of HBM: a fixed cost per iteration,
    # paid once by a vmapped batch of K blocks.
    xla_iter_cost = 500_000.0
    # A compiled Pallas tile drain pays a fixed cost per kernel launch (one
    # launch drains a batch of K blocks); it is what ranks 128-tiles above
    # 64-tiles, as the chip does.  Above ~5.9M the grid's 3-D EDT volume
    # goes to ``frontier`` (3.5x slower there), below ~2.25M seeded morph
    # to 64-tiles (2.1x slower); 4M keeps every pick within 30% more or
    # fewer sources or active tiles.
    pallas_launch_cost = 4_000_000.0
    # Queued-kernel push rounds (kernel_queue=True, DESIGN.md §2.5) touch
    # only O(queue capacity) pixels, but their gather/scatter/compaction
    # steps do not fuse the way a dense round's shifted-plane passes do, so
    # each round pays a fixed multi-dispatch overhead (in dense pixel-visit
    # units; calibrated against the measured ~8x round-time gap on a 256²
    # block).  Each drain also pays one dense seeding round up front.
    kernel_queue_round_overhead = 6_000.0
    # Host threads assumed alongside the device stream in the `hybrid`
    # cooperative pool (solve()'s n_workers default).
    hybrid_host_workers = 4
    # Fixed cost an engine pays per outer round regardless of work done:
    # dispatching the round's (already-compiled) step, host-side carry
    # bookkeeping.  The persistent RunState machinery (DESIGN.md §2.6)
    # exists precisely to keep this term *per-round-constant* instead of
    # hiding a retrace in it.
    round_overhead = 200.0
    # One XLA trace+compile, in pixel-visit units.  Deliberately enormous:
    # an engine whose `SolveStats.recompiles` grows with the round count
    # (a leaked trace — what the composed engines did before ISSUE 7)
    # should price itself out of the auto ranking once `calibrate` has
    # observed it.
    recompile_cost = 2_000_000.0

    def __init__(self, interpret: Optional[bool] = None):
        self.interpret = resolve_interpret(interpret)
        # engine name -> EWMA of observed recompiles per outer round,
        # fed by `calibrate`.  Empty = trust the engines' no-leak contract.
        self._recompile_rate: Dict[str, float] = {}

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _lead(stats: InputStats) -> int:
        """Product of the leading (non-mesh-sharded) spatial extents — the
        per-ring-cell depth multiplier of an N-D shard's halo traffic."""
        return max(1, stats.area // max(1, stats.height * stats.width))

    def depth(self, stats: InputStats) -> float:
        """Expected propagation depth (outer rounds to the fixed point).

        The analytic model uses the inter-source-spacing guess
        ``stats.depth_est``; the measured subclass replaces this with the
        rounds-per-extent profile — the single hook through which every
        rounds-dependent term below (dense transfer, drain counts, BP
        rounds) switches from guessed to measured.
        """
        return stats.depth_est

    def _xla_drain(self, block: int, tile: int, drain_batch: int) -> float:
        """Inner rounds of one XLA tile drain (``core.tiles.
        _tile_local_solve``) of a ``block``-pixel block, ~``tile`` of them.
        Interpreted, priced as VMEM-resident like the Pallas kernel's.
        Compiled, each round reads and writes HBM (no discount) and runs as
        several device ops: a fixed ``xla_iter_cost`` that a vmapped batch
        of ``drain_batch`` blocks pays once."""
        if self.interpret:
            return block * tile * self.vmem_discount
        return block * tile + self.xla_iter_cost * tile / max(1, drain_batch)

    def _drains(self, stats: InputStats, tile: int) -> float:
        """Expected tile drains: initially-active tiles, re-drained once per
        tile-layer the wavefront crosses."""
        active0 = max(1, stats.active_tiles.get(tile, stats.n_tiles(tile)))
        return active0 * max(1.0, self.depth(stats) / tile)

    # -- the two MATCH-style plug points -----------------------------------
    def transfer_cost(self, stats: InputStats, cfg: EngineConfig) -> float:
        """Slow-memory traffic (pixels moved between rounds)."""
        e = cfg.engine
        if e == "frontier":
            return self.depth(stats) * stats.area
        if e == "sweep":
            return (self.depth(stats) + 2) * stats.area * self.sweep_penalty
        if e in ("tiled", "tiled-pallas", "scheduler", "hybrid"):
            block = (cfg.tile + 2) ** stats.ndim
            return self._drains(stats, cfg.tile) * block
        if e == "shard_map":
            bp_rounds = self._bp_rounds(stats)
            halo = 2 * (stats.height + stats.width) * self._lead(stats)
            return (self.depth(stats) * stats.area / stats.n_devices
                    + bp_rounds * halo)
        if e == "shard_map-tiled":
            # Composed hierarchy: transfer = the BP halo rings (same
            # collective traffic as the flat shard_map) + only the *active*
            # tile blocks each TP stage touches, split across devices —
            # never the whole shard per round (the flat engine's
            # depth*area/n term).
            bp_rounds = self._bp_rounds(stats)
            halo = 2 * (stats.height + stats.width) * self._lead(stats)
            block = (cfg.tile + 2) ** stats.ndim
            drains = self._drains(stats, cfg.tile) / stats.n_devices
            return drains * block + bp_rounds * halo
        raise ValueError(f"unknown engine {e!r}")

    def drain_cost(self, stats: InputStats, cfg: EngineConfig) -> float:
        """Innermost-loop compute (discounted when resident on-chip)."""
        e = cfg.engine
        if e in ("frontier", "sweep"):
            return 0.0  # dense engines are bandwidth-bound; folded above
        if e in ("tiled", "tiled-pallas"):
            block = (cfg.tile + 2) ** stats.ndim
            k = max(1, cfg.drain_batch or 1)
            if e == "tiled":
                inner = self._xla_drain(block, cfg.tile, k)
            elif cfg.kernel_queue:
                from repro.kernels.ops import default_kernel_queue_capacity
                qcap = (cfg.kernel_queue_capacity
                        or default_kernel_queue_capacity(
                            (cfg.tile + 2,) * stats.ndim))
                # One dense seeding round + ~tile push rounds of fixed
                # dispatch overhead plus (n_offsets + 1) contribution lanes
                # per slot: queued only wins on big blocks with sparse
                # wavefronts.
                inner = ((block + (self.kernel_queue_round_overhead
                                   + (stats.n_offsets + 1) * qcap) * cfg.tile)
                         * self.vmem_discount)
            else:
                inner = block * cfg.tile * self.vmem_discount
            if e == "tiled-pallas":
                if self.interpret:
                    inner *= self.interpret_penalty
                else:
                    inner += self.pallas_launch_cost / k
            drains = self._drains(stats, cfg.tile)
            dispatch = self.tile_dispatch / k
            return drains * inner + drains * dispatch
        if e == "scheduler":
            block = (cfg.tile + 2) ** stats.ndim
            drains = self._drains(stats, cfg.tile)
            if self.interpret:
                return (drains * block * cfg.tile * self.vmem_discount
                        * self.host_penalty + drains * self.host_dispatch)
            # Compiled, each host task is one XLA tile drain on the device
            # (_host_tile_fn_for jits it for the default backend).
            return drains * (self._xla_drain(block, cfg.tile, 1)
                             + self.host_dispatch)
        if e == "hybrid":
            # Cooperative pool: host threads and the batched device stream
            # consume one queue, so throughputs *add* (harmonic combination
            # of the per-drain unit costs) — the paper's §4 claim that the
            # hybrid split beats either processor alone.  Plus a
            # conservative O(area) charge for the pass's host-side overhead
            # (padded-state copies, and the BP recovery probe when a pass
            # loses its workers).
            k = cfg.drain_batch or 1
            if self.interpret:
                host_unit, dev_unit = self._hybrid_units(cfg.tile, k)
            else:
                # Compiled, both worker kinds run the XLA tile drain on the
                # device: a host thread one block per dispatch, the stream k.
                block = (cfg.tile + 2) ** stats.ndim
                host_unit = (self._xla_drain(block, cfg.tile, 1)
                             + self.host_dispatch)
                dev_unit = (self._xla_drain(block, cfg.tile, k)
                            + self.tile_dispatch / k)
            drains = self._drains(stats, cfg.tile)
            rate = self.hybrid_host_workers / host_unit + 1.0 / dev_unit
            return drains / rate + stats.area
        if e == "shard_map":
            return self._bp_rounds(stats) * self.collective_latency * stats.n_devices
        if e == "shard_map-tiled":
            # Per-shard amortized tile dispatch (the E2 drain cost at 1/n
            # devices worth of drains each) + the same per-BP-round
            # collective latency as the flat shard_map.
            block = (cfg.tile + 2) ** stats.ndim
            k = max(1, cfg.drain_batch or 1)
            inner = self._xla_drain(block, cfg.tile, k)
            drains = self._drains(stats, cfg.tile) / stats.n_devices
            dispatch = self.tile_dispatch / k
            return (drains * (inner + dispatch)
                    + self._bp_rounds(stats) * self.collective_latency
                    * stats.n_devices)
        raise ValueError(f"unknown engine {e!r}")

    def _hybrid_units(self, tile: int, drain_batch: int) -> Tuple[float, float]:
        """Per-drain unit costs of the hybrid pool's two worker classes."""
        block = (tile + 2) ** 2
        inner = block * tile * self.vmem_discount
        host_unit = inner * self.host_penalty + self.host_dispatch
        dev_unit = inner + self.tile_dispatch / max(1, drain_batch)
        return host_unit, dev_unit

    def hybrid_rel_speed(self, tile: int, drain_batch: int = 1) -> float:
        """Analytic seed for the hybrid engine's :class:`ChunkPolicy`: how
        many tiles the device stream should claim per host-thread tile.

        Both worker classes run the same jitted drain, so the only *a
        priori* device advantage is dispatch amortization — one host-side
        dispatch per ``drain_batch`` blocks instead of per block.  (A real
        accelerator's compute advantage is discovered by the online EWMA,
        not assumed: a wrong seed only costs the first few claims.)"""
        inner = (tile + 2) ** 2 * tile * self.vmem_discount
        return ((inner + self.host_dispatch)
                / (inner + self.host_dispatch / max(1, drain_batch)))

    def _bp_rounds(self, stats: InputStats) -> float:
        side = max(1.0, math.sqrt(stats.n_devices))
        block_side = min(stats.height, stats.width) / side
        return max(1.0, self.depth(stats) / max(block_side, 1.0))

    # -- per-round fixed overhead (calibrated from SolveStats.recompiles) --
    def rounds_est(self, stats: InputStats, cfg: EngineConfig) -> float:
        """Expected outer rounds — the multiplier of the fixed overhead."""
        e = cfg.engine
        if e in ("sweep", "frontier"):
            return self.depth(stats)
        if e in ("tiled", "tiled-pallas"):
            # Outer queue rounds ~ wavefront layers measured in tiles.
            return max(1.0, self.depth(stats) / max(cfg.tile or 1, 1))
        if e in ("scheduler", "hybrid"):
            return 1.0  # one FCFS pass (hybrid BP recovery is the rare path)
        return self._bp_rounds(stats)

    def round_overhead_cost(self, stats: InputStats,
                            cfg: EngineConfig) -> float:
        """Fixed per-round charge + any *observed* per-round retrace leak."""
        per_round = (self.round_overhead
                     + self._recompile_rate.get(cfg.engine, 0.0)
                     * self.recompile_cost)
        return self.rounds_est(stats, cfg) * per_round

    def calibrate(self, solve_stats: "SolveStats") -> None:
        """Feed one measured run back into the per-round overhead term.

        ``recompiles / rounds`` from a *warm* steady state is the engine's
        trace-leak rate (a healthy engine reports 0).  An EWMA over runs
        lets the first, legitimately-cold solve (one-time compiles) wash
        out instead of permanently branding the engine.  ``solve()`` calls
        this automatically on every ``engine="auto"`` run.
        """
        rounds = max(1, solve_stats.rounds)
        rate = solve_stats.recompiles / rounds
        old = self._recompile_rate.get(solve_stats.engine)
        self._recompile_rate[solve_stats.engine] = (
            rate if old is None else 0.5 * old + 0.5 * rate)

    # Reference op payload: morph's single int32 mutable plane.  OpSpec cost
    # hints are scaled against this so the morph numbers match the model's
    # historical calibration exactly.
    ref_bytes_per_pixel = 4.0

    # -- ranking -----------------------------------------------------------
    def cost(self, stats: InputStats, cfg: EngineConfig) -> float:
        """Total = op-weighted transfer + drain (OpSpec hints via InputStats):
        transfer scales with the op's mutable bytes/pixel, drain with its
        per-round arithmetic weight."""
        scale_t = stats.bytes_per_pixel / self.ref_bytes_per_pixel
        return (scale_t * self.transfer_cost(stats, cfg)
                + stats.round_cost_weight * self.drain_cost(stats, cfg)
                + self.round_overhead_cost(stats, cfg))

    def candidates(self, stats: InputStats,
                   tiles: Sequence[int] = DEFAULT_TILES) -> List[EngineConfig]:
        out = [EngineConfig("frontier"), EngineConfig("sweep")]
        usable = [t for t in tiles if t <= 2 * max(stats.height, stats.width)]
        for t in usable or [min(tiles)]:
            cap = min(max(4, stats.n_tiles(t)), 256)
            db = min(cap, _default_drain_batch(t))
            out.append(EngineConfig("tiled", t, cap, db))
            if self.interpret:
                out.append(EngineConfig("tiled-pallas", t, cap, db))
                # queued kernels do not compile yet
                out.append(EngineConfig("tiled-pallas", t, cap, db,
                                        kernel_queue=True))
            elif (stats.ndim, t) in COMPILED_PALLAS_TILES:
                # A compiled batched kernel drains K blocks per launch at
                # any tile, so the batch competes at every compiled tile.
                for k in sorted({db, min(cap, DEFAULT_DRAIN_BATCH)}):
                    out.append(EngineConfig("tiled-pallas", t, cap, k))
            out.append(EngineConfig("scheduler", t, cap))
            out.append(EngineConfig("hybrid", t, cap, db))
            if stats.n_devices > 1:
                out.append(EngineConfig("shard_map-tiled", t, cap, db))
        if stats.n_devices > 1:
            out.append(EngineConfig("shard_map"))
        return out

    def rank(self, stats: InputStats,
             candidates: Optional[Sequence[EngineConfig]] = None
             ) -> List[Tuple[float, EngineConfig]]:
        cands = candidates if candidates is not None else self.candidates(stats)
        scored = [(self.cost(stats, c), c) for c in cands]
        scored.sort(key=lambda sc: sc[0])
        return scored


class MeasuredCostModel(CostModel):
    """Cost model over a measured :class:`~repro.core.calibrate.
    CalibrationProfile` (DESIGN.md §2.8); unit = wall seconds.

    Same MATCH-style structure as the analytic parent, but every ingredient
    the profile measured replaces its guessed counterpart:

    * ``depth`` — the measured rounds-per-extent curve over seed density
      replaces the inter-source-spacing guess (``InputStats.depth_est``);
      since every rounds-dependent term routes through :meth:`CostModel.
      depth`, the fix propagates to dense transfer, drain counts and BP
      rounds at once.
    * dense engines — measured seconds per round, interpolated over area
      (so the HBM bandwidth knee is in the curve, not a constant).
    * tiled families — measured wall seconds per drain over block pixels,
      scaled by the measured density factor (shallow drains near
      convergence), the measured batched-drain amortization curve, and the
      op's neighborhood-size ratio.  Scheduler/hybrid profiles are wall
      seconds per tile *at the calibration worker counts* (recorded in
      ``profile.meta``).

    Anything the profile did not measure — an unprofiled op, a Pallas
    family measured under a different ``interpret`` mode, the shard_map
    engines — falls back to the *op's cost hints over the morph reference
    curves*, and past that to the analytic formula bridged into seconds,
    so every candidate stays comparable in one ranking.  Construct via
    :func:`default_cost_model`, which picks this subclass exactly when a
    profile is installed.
    """

    kind = "measured"

    def __init__(self, profile, interpret: Optional[bool] = None):
        super().__init__(interpret)
        self.profile = profile

    # -- profile lookups with the op -> morph -> analytic fallback chain ---
    def _op_key(self, stats: InputStats, table: Dict[str, Any],
                need: Optional[str] = None) -> Optional[str]:
        """The table key to price ``stats``'s op from: the op's own entry
        when present (and carrying ``need``), else the morph reference."""
        for key in (stats.op_name, "morph"):
            entry = table.get(key)
            if entry is None:
                continue
            if need is not None and need not in entry:
                continue
            return key
        return None

    def _hint_scale(self, stats: InputStats, key: str, weight: float) -> float:
        """Scaling applied when pricing an op off another op's curves: the
        OpSpec cost hints (bytes for transfer-bound terms, round weight for
        compute-bound terms) — 1.0 when the op owns the curve."""
        if key == stats.op_name:
            return 1.0
        return weight

    def _offs_ratio(self, stats: InputStats, key: str) -> float:
        """Neighborhood-size correction: per-round and per-drain work is
        linear in the offsets applied per pixel (conn26 rounds cost ~3x a
        conn8 round of the same area)."""
        ref = self.profile.ref_n_offsets.get(key)
        return stats.n_offsets / ref if ref else 1.0

    # -- measured ingredients ----------------------------------------------
    def depth(self, stats: InputStats) -> float:
        rc = self.profile.rounds_per_extent.get(stats.op_name)
        if rc is None:
            return stats.depth_est
        ld = math.log10(max(stats.density, 1e-9))
        return max(1.0, rc.interp(ld) * max(stats.spatial))

    def _density_factor(self, stats: InputStats) -> float:
        # Only the op's *own* measured curve: regime-vs-drain-depth
        # dynamics don't transfer across ops the way per-pixel rates do.
        df = self.profile.drain_density_factor.get(stats.op_name)
        if df is None:
            return 1.0
        ld = math.log10(max(stats.density, 1e-9))
        return max(df.interp(ld), 1e-3)

    def _family(self, cfg: EngineConfig) -> str:
        if cfg.engine == "tiled-pallas" and cfg.kernel_queue:
            return "tiled-pallas-queued"
        return cfg.engine

    def _nearest_block(self, curves: Dict[str, Any], block: float) -> str:
        """Key of the measured block size closest (log-distance) to
        ``block`` — 3-D blocks land on the largest measured 2-D one."""
        return min(curves, key=lambda k: abs(math.log(float(k) / block)))

    def _grid_factor(self, stats: InputStats, block: float) -> float:
        """Growth of per-drain cost with the *full grid* (queue compaction
        and block scatter touch every tile each round): the measured
        drain-grid curve at the nearest block size, normalized to its
        calibration-grid anchor (its first point)."""
        curves = self.profile.drain_grid
        if not curves:
            return 1.0
        c = curves[self._nearest_block(curves, block)]
        return max(c.interp(float(stats.area)) / c.ys[0], 1e-3)

    def _batch_factor(self, block: float, drain_batch: float) -> float:
        curves = self.profile.batch_factor
        if not curves:
            return 1.0
        c = curves[self._nearest_block(curves, block)]
        return max(c.interp(drain_batch), 1e-3)

    def _drain_seconds(self, stats: InputStats,
                       cfg: EngineConfig) -> Optional[float]:
        """Measured wall seconds for one drain of ``cfg``'s family at
        ``cfg.tile``, fully corrected — None when unprofiled."""
        fam = self._family(cfg)
        if fam.startswith("tiled-pallas") and \
                self.profile.meta.get("interpret") != self.interpret:
            return None     # interpret-mode timings don't transfer
        key = self._op_key(stats, self.profile.drain, need=fam)
        if key is None:
            return None
        block = float((cfg.tile + 2) ** stats.ndim)
        sec = self.profile.drain[key][fam].scaled(block)
        sec *= self._hint_scale(stats, key, stats.round_cost_weight)
        sec *= self._offs_ratio(stats, key)
        sec *= self._density_factor(stats)
        if fam in ("tiled", "tiled-pallas", "tiled-pallas-queued"):
            # scheduler/hybrid wall-per-tile rates already include their
            # host-side overheads and transfer across grid sizes; the
            # block-drain families need the measured grid and batch
            # corrections (both measured with the tiled outer loop, which
            # the Pallas families share).
            sec *= self._grid_factor(stats, block)
            sec *= self._batch_factor(block, float(cfg.drain_batch or 1))
        return sec

    def _unit_seconds(self, stats: InputStats) -> float:
        """Seconds per analytic pixel-visit unit — the bridge that keeps
        analytically-priced candidates comparable with measured ones.
        Preferred source: the measured HBM byte rate at this input's
        working-set size; else the measured dispatch overhead against the
        analytic per-round charge; else a nominal DRAM-era constant."""
        if self.profile.transfer is not None:
            nbytes = max(1.0, stats.area * stats.bytes_per_pixel)
            return (self.profile.transfer.scaled(nbytes) / nbytes
                    * self.ref_bytes_per_pixel)
        if self.profile.round_overhead_s > 0:
            return self.profile.round_overhead_s / CostModel.round_overhead
        return 1e-9

    def _bridge(self, stats: InputStats, cfg: EngineConfig) -> float:
        return self._unit_seconds(stats) * super().cost(stats, cfg)

    # -- the overridden MATCH plug points (now in seconds) -----------------
    def round_overhead_cost(self, stats: InputStats,
                            cfg: EngineConfig) -> float:
        per_round = (self.profile.round_overhead_s
                     + self._recompile_rate.get(cfg.engine, 0.0)
                     * self.profile.recompile_s)
        return self.rounds_est(stats, cfg) * per_round

    def hybrid_rel_speed(self, tile: int, drain_batch: int = 1) -> float:
        if self.profile.hybrid_rel_speed:
            return self.profile.hybrid_rel_speed
        return super().hybrid_rel_speed(tile, drain_batch)

    def cost(self, stats: InputStats, cfg: EngineConfig) -> float:
        e = cfg.engine
        if e in ("frontier", "sweep"):
            key = self._op_key(stats, self.profile.dense_round, need=e)
            if key is None:
                return self._bridge(stats, cfg)
            sec_per_round = (
                self.profile.dense_round[key][e].scaled(float(stats.area))
                * self._hint_scale(stats, key,
                                   stats.bytes_per_pixel
                                   / self.ref_bytes_per_pixel)
                * self._offs_ratio(stats, key))
            # sweep pays the extra settle rounds past the fixed point (the
            # analytic model's +2) on top of the measured per-round rate
            rounds = self.depth(stats) + (2.0 if e == "sweep" else 0.0)
            return (rounds * sec_per_round
                    + self.round_overhead_cost(stats, cfg))
        if e in ("tiled", "tiled-pallas", "scheduler", "hybrid"):
            sec = self._drain_seconds(stats, cfg)
            if sec is None:
                return self._bridge(stats, cfg)
            return (self._drains(stats, cfg.tile) * sec
                    + self.round_overhead_cost(stats, cfg))
        # shard_map engines: no measured profile (needs a mesh to time);
        # analytic shape, measured depth, bridged into seconds.
        return self._bridge(stats, cfg)


def default_cost_model(interpret: Optional[bool] = None) -> CostModel:
    """The model ``engine="auto"`` uses when the caller passed none: the
    :class:`MeasuredCostModel` over the installed calibration profile when
    one exists for this (device kind, code version), else the analytic
    :class:`CostModel` — the cold-start path (DESIGN.md §2.8)."""
    from repro.core import calibrate
    profile = calibrate.current_profile()
    if profile is not None:
        return MeasuredCostModel(profile, interpret=interpret)
    return CostModel(interpret=interpret)


# ---------------------------------------------------------------------------
# Autotune — micro-benchmark the model's top candidates, cache winners.
# ---------------------------------------------------------------------------

# signature -> (EngineConfig, measured seconds).  Backed by the disk layer
# (core.autotune_disk, ~/.cache/repro-iwpp/autotune.json): a process-local
# miss falls through to disk before re-measuring, and measured winners are
# persisted so a fresh interpreter skips the whole micro-benchmark sweep.
_AUTOTUNE_CACHE: Dict[tuple, Tuple[EngineConfig, float]] = {}
# signature -> tuple of (EngineConfig, repr(exception)) for candidates that
# raised during micro-benchmarking — kept so a fully-failing candidate set is
# distinguishable from a fast one (and surfaced via warnings.warn).
_AUTOTUNE_FAILURES: Dict[tuple, tuple] = {}


def autotune_signature(op: PropagationOp, stats: InputStats,
                       restrictions: tuple = ()) -> tuple:
    """Cache key: op identity + shape + density bucket + device count, plus
    any caller restrictions on the candidate set (tile / queue_capacity) so
    a restricted solve never reuses an unrestricted winner.

    The density bucket (decade of the seed-pixel density) is what the cost
    regimes actually depend on; exact pixel values don't matter.
    """
    bucket = (-99 if stats.n_sources == 0
              else int(math.floor(math.log10(max(stats.density, 1e-9)))))
    return (type(op).__name__, op.neighborhood.name, stats.spatial,
            bucket, stats.n_devices) + tuple(restrictions)


def clear_autotune_cache(disk: bool = False) -> None:
    """Drop the in-process autotune winners; ``disk=True`` also deletes the
    persisted ``autotune.json`` (e.g. before a clean benchmark run)."""
    _AUTOTUNE_CACHE.clear()
    _AUTOTUNE_FAILURES.clear()
    if disk:
        autotune_disk.clear()


def _autotune(op, state, stats, model: CostModel, candidates, restrictions,
              top_k: int, repeats: int, **run_kw) -> EngineConfig:
    sig = autotune_signature(op, stats, restrictions)
    if sig in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[sig][0]
    hit = autotune_disk.load(type(op).__name__, sig, EngineConfig)
    if hit is not None and hit[0] in candidates:
        # A persisted winner from an earlier process on the same device
        # kind + code version: trust it without re-measuring (promote to
        # the in-process cache so the disk is read at most once per sig).
        # Only honored when the persisted config is still in the caller's
        # candidate set — a restricted/custom candidate list must not be
        # bypassed by a winner measured over a different set.
        _AUTOTUNE_CACHE[sig] = hit
        return hit[0]
    ranked = model.rank(stats, candidates)
    best_cfg, best_t = None, float("inf")
    failures = []
    for _, cfg in ranked[:top_k]:
        try:
            runner = lambda: _run_engine(op, state, cfg, **run_kw)
            jax.block_until_ready(runner()[0])       # warm/compile
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(runner()[0])
                ts.append(time.perf_counter() - t0)
            t = min(ts)
        except Exception as e:
            warnings.warn(f"autotune: candidate {cfg} failed with {e!r}; "
                          "excluding it from the measured ranking",
                          RuntimeWarning, stacklevel=2)
            failures.append((cfg, repr(e)))
            continue
        if t < best_t:
            best_cfg, best_t = cfg, t
    if best_cfg is None:                              # all candidates failed
        warnings.warn(
            f"autotune: all {len(ranked[:top_k])} measured candidates failed; "
            "falling back to the cost model's top prediction "
            f"{ranked[0][1]} (unmeasured)", RuntimeWarning, stacklevel=2)
        best_cfg, best_t = ranked[0][1], float("nan")
    _AUTOTUNE_CACHE[sig] = (best_cfg, best_t)
    if failures:
        _AUTOTUNE_FAILURES[sig] = tuple(failures)
    if best_t == best_t:                     # measured (not the NaN fallback)
        autotune_disk.store(type(op).__name__, sig, best_cfg, best_t)
    return best_cfg


# ---------------------------------------------------------------------------
# Engine adapters.
# ---------------------------------------------------------------------------

def pad_state_to(op, state, target: Sequence[int]):
    """High-side-pad every leaf's trailing spatial axes to exactly
    ``target`` with the op's neutral values.

    Padded cells are invalid and hold ``op.pad_value`` fills, so they can
    never source a propagation; cropping afterwards restores the domain.
    Shared by the engines' grid-multiple padding and the serving layer's
    pad-to-bucket coalescing (DESIGN.md §2.9).  Returns ``(padded,
    orig_spatial)``; shrinking is an error.
    """
    nd = op.ndim
    spatial = tree_shape(state, nd)
    target = tuple(target)
    if any(t < s for s, t in zip(spatial, target)):
        raise ValueError(f"pad_state_to cannot shrink {spatial} to {target}")
    if target == spatial:
        return state, spatial
    pv = op.pad_value(state)
    grow = [t - s for s, t in zip(spatial, target)]
    padded = jax.tree_util.tree_map(
        lambda x, v: jnp.pad(
            x, [(0, 0)] * (x.ndim - nd) + [(0, g) for g in grow],
            constant_values=v),
        state, pv)
    return padded, spatial


def _pad_to_multiple(op, state, mults: Sequence[int]):
    """High-side-pad the trailing ``len(mults)`` spatial axes of every leaf
    to grid multiples with neutral values (see :func:`pad_state_to`)."""
    nd = op.ndim
    spatial = tree_shape(state, nd)
    mults = (1,) * (nd - len(mults)) + tuple(mults)
    return pad_state_to(op, state,
                        tuple(-(-s // m) * m for s, m in zip(spatial, mults)))


def _crop(state, spatial: Sequence[int]):
    idx = (Ellipsis,) + tuple(slice(0, s) for s in spatial)
    return jax.tree_util.tree_map(lambda x: x[idx], state)


def _mesh_shape(n: int) -> Tuple[int, int]:
    """Most-square factorization of the device count."""
    r = int(math.sqrt(n))
    while n % r:
        r -= 1
    return r, n // r


def _run_dense_engine(op, state, cfg, max_rounds, **_):
    with spans.span("iwpp.engine.wait"):
        out, st = run_dense(op, state, cfg.engine, max_rounds)
        return out, SolveStats(cfg.engine, rounds=spans.host_int(st.rounds),
                               sources_processed=st.sources_processed)


# Every per-op compiled artifact in this module lives in the one process
# cache (core.compile_cache): keys carry a site tag first and the op class
# second, so ``SolveStats.recompiles`` counts builds uniformly across the
# layers and the spec-change hook below drops every affected entry at once.
# Re-registering/amending a spec invalidates the op's entries, so a replaced
# Pallas solver is picked up instead of a stale memo serving the old kernel.


def _invalidate_solver_memo(op_cls: type) -> None:
    # A subclass may resolve its solver through the amended ancestor's
    # spec, so drop every cache row whose op class sits below op_cls too —
    # collecting the affected class names on the way out for the autotune
    # invalidation below.
    names = {op_cls.__name__}

    def pred(key: tuple) -> bool:
        if len(key) < 2:
            return False
        tagged = key[1]
        cls = tagged if isinstance(tagged, type) else type(tagged)
        if isinstance(cls, type) and issubclass(cls, op_cls):
            names.add(cls.__name__)
            return True
        return False

    compile_cache.invalidate(pred)
    # A spec change can also *fix* a candidate that failed during autotune
    # micro-benchmarking (e.g. a broken queued-kernel factory): entries
    # recorded under the old spec would keep serving the stale winner — and
    # the stale failure verdict — forever, so the fixed candidate would
    # never be retried.  Autotune signatures carry the op class *name* at
    # position 0 (autotune_signature), which is the best subclass net we
    # have here.
    for cache in (_AUTOTUNE_CACHE, _AUTOTUNE_FAILURES):
        for sig in [s for s in cache if s and s[0] in names]:
            del cache[sig]
    # ... and the persisted winners, across ALL code versions: the disk
    # entry records the op name, so a stale winner written by an older
    # build can't outlive the spec that produced it either.
    autotune_disk.invalidate_op(names)


on_spec_change(_invalidate_solver_memo)


def _pallas_solver_for(op, interpret: bool, batched: bool = False,
                       max_iters: int = None, engine: str = "tiled-pallas",
                       kernel_queue: bool = False,
                       kernel_queue_capacity: Optional[int] = None):
    from repro.kernels.ops import DEFAULT_MAX_ITERS
    if max_iters is None:
        max_iters = DEFAULT_MAX_ITERS
    key = ("pallas-solver", type(op), op.connectivity, interpret, batched,
           max_iters, kernel_queue, kernel_queue_capacity)

    def build():
        spec = spec_for(op)
        if kernel_queue:
            factory = (None if spec is None else
                       (spec.pallas_queue_batch_solver if batched
                        else spec.pallas_queue_solver))
            per_tile = None if spec is None else spec.pallas_queue_solver
        else:
            factory = (None if spec is None else
                       (spec.pallas_batch_solver if batched
                        else spec.pallas_solver))
            per_tile = None if spec is None else spec.pallas_solver
        if factory is None:
            if batched and per_tile is not None:
                # Fall back to vmapping the per-tile kernel; a dedicated
                # grid-over-batch kernel is only an optimization.  (The
                # cache lock is re-entrant, so the recursive lookup is
                # safe.)
                return jax.vmap(
                    _pallas_solver_for(op, interpret, max_iters=max_iters,
                                       engine=engine,
                                       kernel_queue=kernel_queue,
                                       kernel_queue_capacity=kernel_queue_capacity))
            what = ("queued Pallas tile solver (OpSpec.pallas_queue_solver, "
                    "required by kernel_queue=True)" if kernel_queue
                    else "Pallas tile solver")
            raise ValueError(
                f"op {type(op).__name__} has no {what} "
                f"registered, which engine {engine!r} requires; registered "
                f"ops: {list_ops()}.  Provide OpSpec.pallas_solver via "
                "repro.ops.register_op() (or the register_pallas_solver "
                "shim), or pick an op-generic engine such as 'tiled'.")
        return (factory(op, interpret, max_iters, kernel_queue_capacity)
                if kernel_queue
                else factory(op, interpret, max_iters))

    return compile_cache.get(key, build)


def _tiled_cfg_defaults(cfg: EngineConfig) -> Tuple[int, int, int]:
    """Resolve (tile, queue_capacity, drain_batch) for the queued engines."""
    tile = cfg.tile or DEFAULT_TILES[1]
    cap = cfg.queue_capacity or DEFAULT_QUEUE_CAPACITY
    drain_batch = (cfg.drain_batch if cfg.drain_batch is not None
                   else _default_drain_batch(tile))
    return tile, cap, drain_batch


def _run_tiled_engine(op, state, cfg, max_rounds, interpret=None, **_):
    # core.tiles.run_tiled's prepare -> drain -> finalize, split so that the
    # host set-up and the wait for the device loop are spans of their own.
    with spans.span("iwpp.engine.prepare"):
        solver = batched_solver = None
        tile, cap, drain_batch = _tiled_cfg_defaults(cfg)
        kq = bool(cfg.kernel_queue)
        kq_cap = None
        if cfg.engine == "tiled-pallas":
            # Thread the engine's prod(T_i+2) geodesic bound into the
            # kernels: the kernel-default 1024 is *below* the bound for any
            # 2-D tile >= 32, and a drain cut off there must re-queue, not
            # masquerade as converged.
            max_iters = (tile + 2) ** op.ndim
            if kq:
                from repro.kernels.ops import default_kernel_queue_capacity
                kq_cap = (cfg.kernel_queue_capacity
                          or default_kernel_queue_capacity(
                              (tile + 2,) * op.ndim))
            solver = _pallas_solver_for(op, interpret, max_iters=max_iters,
                                        engine=cfg.engine, kernel_queue=kq,
                                        kernel_queue_capacity=kq_cap)
            if drain_batch > 1:
                batched_solver = _pallas_solver_for(
                    op, interpret, batched=True, max_iters=max_iters,
                    engine=cfg.engine, kernel_queue=kq,
                    kernel_queue_capacity=kq_cap)
        plan, rs = _tiles.prepare(op, state, tile=tile, queue_capacity=cap,
                                  max_outer_rounds=max_rounds,
                                  tile_solver=solver, drain_batch=drain_batch,
                                  batched_tile_solver=batched_solver)
        drain = _tiles.drain_fn(plan)
    with spans.span("iwpp.engine.wait"):
        rs = drain(rs)
        out = _tiles.finalize(plan, rs, state)
        st = rs.stats
        return out, SolveStats(
            cfg.engine, rounds=spans.host_int(st.outer_rounds),
            tiles_processed=spans.host_int(st.tiles_processed),
            overflow_events=spans.host_int(st.overflow_events),
            tiles_requeued=spans.host_int(st.tiles_requeued),
            tile=tile, queue_capacity=cap, drain_batch=drain_batch,
            kernel_queue=kq, kernel_queue_capacity=kq_cap)


def _run_shard_map_engine(op, state, cfg, max_rounds, devices=None, **_):
    with spans.span("iwpp.engine.prepare"):
        devices = list(devices) if devices is not None else jax.devices()
        nr, nc = _mesh_shape(len(devices))
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(devices).reshape(nr, nc), ("data", "model"))
        padded, orig = _pad_to_multiple(op, state, (nr, nc))
    with spans.span("iwpp.engine.wait"):
        if cfg.engine == "shard_map-tiled":
            tile, cap, drain_batch = _tiled_cfg_defaults(cfg)
            out, st = run_sharded(op, padded, mesh, tile=tile,
                                  queue_capacity=cap, drain_batch=drain_batch,
                                  max_bp_rounds=max_rounds)
            stats = SolveStats(
                cfg.engine, rounds=spans.host_int(st.bp_rounds),
                tiles_processed=spans.host_int(st.tiles_processed),
                overflow_events=spans.host_int(st.overflow_events),
                tiles_requeued=spans.host_int(st.tiles_requeued),
                tile=tile, queue_capacity=cap, drain_batch=drain_batch,
                n_devices=len(devices))
        else:
            out, st = run_sharded(op, padded, mesh, max_bp_rounds=max_rounds)
            stats = SolveStats("shard_map",
                               rounds=spans.host_int(st.bp_rounds),
                               n_devices=len(devices))
    return _crop(out, orig), stats


def _scheduler_drain_for(op, tile: int):
    # (T+2)^2 iterations bound the longest geodesic inside one block
    # (e.g. a spiral mask); the while_loop exits at stability, so the
    # generous bound costs nothing in the common case.  Out-of-array
    # halo cells arrive already holding the op's neutral pad values
    # (TileScheduler pad_values), so no sanitize pass is needed.  The
    # (block, unconverged) pair is the truncation contract: the host
    # scheduler self-requeues an unconverged drain like run_tiled does.
    # Cached process-wide, so every scheduler/hybrid worker thread shares
    # ONE compiled drain instead of re-tracing per worker (the
    # fig10/scheduler workers=2 regression).
    key = ("scheduler-drain", type(op), op.connectivity, tile)
    return compile_cache.get(key,
                             lambda: jax.jit(default_tile_solver(op, tile)))


def _batched_drain_for(op, tile: int, interpret: bool, pallas: bool,
                       drain_batch: int = 1):
    """Jitted `batched_tile_solver` for the hybrid engine's device workers:
    plain `jax.vmap` of the dense drain, or the Pallas grid-over-batch
    kernels — both at the (T+2)² truncation bound.

    ``drain_batch <= 1`` adapts the *unbatched* jitted solver instead of a
    degenerate K=1 vmap: vmapping `lax.while_loop` re-lowers the drain body
    in batched form, which measures several times slower than the plain
    drain even at batch 1 (the same reason `run_tiled` keeps a sequential
    scan path).
    """
    if pallas:
        return _pallas_solver_for(op, interpret, batched=True,
                                  max_iters=(tile + 2) ** op.ndim,
                                  engine="hybrid")
    if drain_batch <= 1:
        per = _scheduler_drain_for(op, tile)

        def batch_fn(stacked):
            # Strip the batch axis host-side: np slicing is a free view,
            # whereas jnp.asarray(v)[0] would issue an *eager* device slice
            # per leaf per tile — measured at ~2x the whole per-tile drain
            # cost for the hybrid device stream.
            out, unconv = per({k: jnp.asarray(np.asarray(v)[0])
                               for k, v in stacked.items()})
            return ({k: np.asarray(v)[None] for k, v in out.items()},
                    np.asarray(unconv)[None])

        return batch_fn
    key = ("hybrid-batched", type(op), op.connectivity, tile)
    return compile_cache.get(key,
                             lambda: jax.jit(default_batched_solver(op, tile)))


def _host_tile_fn_for(op, tile: int):
    """Host-thread tile task: jitted dense drain over a numpy halo block."""
    _drain = _scheduler_drain_for(op, tile)

    def tile_fn(block):
        out, unconv = _drain({k: jnp.asarray(b) for k, b in block.items()})
        return {k: np.asarray(b) for k, b in out.items()}, bool(unconv)

    return tile_fn


def _scheduler_merge_for(op, engine: str):
    """The host engines' commutative write-back merge, from the op's spec.

    ``None`` (the spec default) selects the scheduler's built-in
    elementwise-max merge — correct for any single-plane monotone-max op.
    An *unregistered* op is an error here (not a silent default): the
    default merge is wrong for coupled/coordinate-dependent state (EDT),
    and silently applying it used to surface as a corrupted fixed point.
    """
    spec = spec_for(op)
    if spec is None:
        raise ValueError(
            f"op {type(op).__name__} is not a registered op, and engine "
            f"{engine!r} needs its commutative merge_block_fn; registered "
            f"ops: {list_ops()}.  Register it with repro.ops.register_op() "
            "(OpSpec.scheduler_merge defaults to the elementwise-max merge) "
            "or the register_scheduler_merge shim.")
    return spec.scheduler_merge(op)


def _scheduler_state_for(op, state, tile: int, engine: str):
    """Shared host-engine setup: padded numpy state + scheduler plumbing."""
    padded, orig = _pad_to_multiple(op, state, (tile,) * op.ndim)
    # np.array (not asarray): JAX buffers give read-only numpy views, and the
    # scheduler writes tile interiors back into this state in place.
    np_state = {k: np.array(v) for k, v in padded.items()}
    active = np.asarray(initial_active_tiles(op, padded, tile))
    merge_block_fn = _scheduler_merge_for(op, engine)
    mutable = tuple(k for k in np_state if k not in op.static_leaves)
    pad_values = {k: np.asarray(v).item()
                  for k, v in op.pad_value(padded).items()}
    return np_state, active, merge_block_fn, mutable, pad_values, orig


def _run_scheduler_engine(op, state, cfg, max_rounds, n_workers=4, **_):
    tile = cfg.tile or DEFAULT_TILES[1]
    with spans.span("iwpp.engine.prepare"):
        (np_state, active, merge_block_fn, mutable, pad_values,
         orig) = _scheduler_state_for(op, state, tile, "scheduler")
    sched = TileScheduler(np_state, tile, _host_tile_fn_for(op, tile), active,
                          n_workers=n_workers, mutable=mutable,
                          merge_block_fn=merge_block_fn,
                          pad_values=pad_values)
    st = sched.run()
    if st.incomplete:
        # Never hand back a partial drain as a solve() result (the scheduler
        # already warned); autotune treats this as a failed candidate.
        raise RuntimeError(
            "scheduler engine gave up with tiles still queued "
            f"(requeues_from_failures={st.requeues_from_failures}); "
            "the state did not reach its fixed point")
    out = _crop({k: jnp.asarray(v) for k, v in np_state.items()}, orig)
    # Engine output contract: invalid cells hold their input values.
    out = restore_invalid(op, state, out)
    return out, SolveStats("scheduler", rounds=1,
                           tiles_processed=st.tiles_processed,
                           requeues=st.requeues_from_failures,
                           tiles_requeued=st.tiles_requeued,
                           tile=tile, worker_errors=tuple(st.worker_errors))


def _bp_residual_for(op):
    """One dense round sourcing from every valid pixel.

    ``state`` is at its fixed point iff this round changes nothing — the
    returned frontier is exactly the set of pixels it improved (the
    "halo-improved" seed of the next hybrid pass, DESIGN.md §2.3).
    """
    def build():
        @jax.jit
        def _residual(state):
            f0 = jnp.ones(tree_shape(state, op.ndim), dtype=bool)
            if "valid" in state:
                f0 = f0 & state["valid"]
            return op.round(state, f0)
        return _residual

    return compile_cache.get(("bp-residual", type(op), op.connectivity),
                             build)


# Test hook: (worker_id | "all", fail_after) injected into every hybrid
# scheduler pass — exercises the cooperative pool's fault tolerance without
# widening the public solve() signature.
_HYBRID_FAIL_INJECT: Optional[Tuple[Any, int]] = None


def _run_hybrid_engine(op, state, cfg, max_rounds, interpret=None,
                       n_workers=4, n_device_workers=1,
                       hybrid_pallas=False, cost_model=None, **_):
    """The cooperative CPU+device engine (paper §4, DESIGN.md §2.3).

    One demand-driven FCFS tile queue, consumed concurrently by
    ``n_workers`` host threads (jitted per-tile drains with commutative
    merge writeback) and ``n_device_workers`` device streams (batched
    `run_tiled`-style drains, ``drain_batch`` blocks per dispatch, chunks
    sized by the ChunkPolicy's measured relative speed).  ``queue_capacity``
    does not apply — the host FCFS queue is unbounded, so the stats report
    it as None rather than echoing an inert knob.  A completed pass
    certifies the fixed point; a pass that lost every worker wave triggers
    a BP recovery round (one dense valid-sourced round) that re-seeds the
    queue with only the tiles it improved (`active_tiles_from_frontier` —
    the same seam as the composed `shard_map-tiled` engine's BP re-seed).
    """
    tile, _, drain_batch = _tiled_cfg_defaults(cfg)
    if n_workers <= 0 and n_device_workers <= 0:
        raise ValueError("hybrid engine needs n_workers >= 1 or "
                         "n_device_workers >= 1")
    with spans.span("iwpp.engine.prepare"):
        (np_state, active, merge_block_fn, mutable, pad_values,
         orig) = _scheduler_state_for(op, state, tile, "hybrid")
    grid = tuple(s // tile
                 for s in np_state[mutable[0]].shape[-op.ndim:])

    tile_fn = _host_tile_fn_for(op, tile) if n_workers > 0 else None
    batch_fn = _batched_drain_for(op, tile, interpret, hybrid_pallas,
                                  drain_batch)
    devs = [DeviceWorker(batch_fn, drain_batch=drain_batch,
                         name=f"device{d}") for d in range(n_device_workers)]
    model = (cost_model if cost_model is not None
             else default_cost_model(interpret))
    # One policy across all BP passes: the EWMA keeps learning the real
    # host:device speed ratio over the whole solve.
    # max_chunk ~ two batched dispatches ahead: more claim-ahead only adds
    # halo staleness without further dispatch amortization.
    policy = ChunkPolicy(model.hybrid_rel_speed(tile, drain_batch),
                         max_chunk=max(2 * max(1, drain_batch), 4),
                         seed_kind=model.kind)
    residual = _bp_residual_for(op)
    fail = _HYBRID_FAIL_INJECT

    tiles_processed = requeues = tiles_requeued = device_tiles = 0
    worker_errors: List[str] = []
    bp_rounds = 0
    incomplete = True
    while True:
        sched = TileScheduler(
            np_state, tile, tile_fn, active, n_workers=n_workers,
            mutable=mutable, merge_block_fn=merge_block_fn,
            pad_values=pad_values, device_workers=devs, chunk_policy=policy,
            fail_worker=fail[0] if fail else None,
            fail_after=fail[1] if fail else 3)
        st = sched.run()
        tiles_processed += st.tiles_processed
        requeues += st.requeues_from_failures
        tiles_requeued += st.tiles_requeued
        device_tiles += st.device_tiles
        worker_errors += st.worker_errors
        bp_rounds += 1
        if not st.incomplete:
            # A completed pass certifies the fixed point by construction:
            # queue empty + nothing inflight means no pending dirty marks,
            # so every tile is locally stable against its current halos —
            # the same guarantee the solo scheduler engine rests on.
            incomplete = False
            break
        if bp_rounds >= max(1, max_rounds):
            break
        # BP recovery round (the pass lost every worker wave): one dense
        # valid-sourced round makes monotone progress and yields the
        # improved-pixel frontier, which re-seeds the shared queue with
        # only the tiles it touches.  Re-draining any superset of the
        # dirty tiles is exact (monotone commutative updates), so worker
        # death costs extra rounds, never a wrong result — total failure
        # degrades to E1's dense rounds rather than a partial answer.
        new_state, f_in = residual({k: jnp.asarray(v)
                                    for k, v in np_state.items()})
        if not bool(jnp.any(f_in)):
            incomplete = False
            break
        for k in mutable:
            np_state[k] = np.array(new_state[k])
        active = np.asarray(active_tiles_from_frontier(op, f_in, tile, grid))
    if incomplete:
        warnings.warn(
            f"hybrid engine stopped after {bp_rounds} BP rounds with a "
            "non-empty residual frontier; the state is NOT at its fixed "
            "point (SolveStats.incomplete=True)", RuntimeWarning,
            stacklevel=2)
    out = _crop({k: jnp.asarray(v) for k, v in np_state.items()}, orig)
    # Engine output contract: invalid cells hold their input values.
    out = restore_invalid(op, state, out)
    return out, SolveStats("hybrid", rounds=bp_rounds,
                           tiles_processed=tiles_processed,
                           requeues=requeues, tiles_requeued=tiles_requeued,
                           tile=tile, drain_batch=drain_batch,
                           incomplete=incomplete, device_tiles=device_tiles,
                           worker_errors=tuple(worker_errors))


_ENGINE_RUNNERS = {
    "sweep": _run_dense_engine,
    "frontier": _run_dense_engine,
    "tiled": _run_tiled_engine,
    "tiled-pallas": _run_tiled_engine,
    "shard_map": _run_shard_map_engine,
    "shard_map-tiled": _run_shard_map_engine,
    "scheduler": _run_scheduler_engine,
    "hybrid": _run_hybrid_engine,
}


def _run_engine(op, state, cfg: EngineConfig, **kw):
    # `recompiles` is the compile-cache miss delta across the run: 0 on a
    # warm re-solve, and — the DESIGN.md §2.6 contract — *independent of
    # the round count* even on a cold one (tests/test_runstate.py).
    kw["interpret"] = resolve_interpret(kw.get("interpret"))
    with spans.span("iwpp.engine") as engine_span:
        with compile_cache.MissSnapshot() as snap:
            out, st = _ENGINE_RUNNERS[cfg.engine](op, state, cfg, **kw)
        # Force the result resident before the span closes: with async
        # dispatch an engine may return an unmaterialized future, and
        # wall_time_s would under-report the actual solve.
        with spans.span("iwpp.engine.wait"):
            jax.block_until_ready(out)
    return out, dataclasses.replace(st, recompiles=snap.count,
                                    wall_time_s=engine_span.seconds,
                                    interpret=kw["interpret"])


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------

@spans.recorded("iwpp.solve")
def solve(op, state, *, engine: str = "auto",
          connectivity: Optional[Union[int, str]] = None,
          devices: Optional[Sequence] = None,
          tile: Optional[int] = None,
          queue_capacity: Optional[int] = None,
          drain_batch: Optional[int] = None,
          kernel_queue: Optional[bool] = None,
          kernel_queue_capacity: Optional[int] = None,
          max_rounds: int = 1_000_000,
          cost_model: Optional[CostModel] = None,
          autotune: bool = False,
          autotune_top_k: int = 3,
          autotune_repeats: int = 2,
          interpret: Optional[bool] = None,
          n_workers: int = 4,
          n_device_workers: int = 1,
          hybrid_pallas: bool = False) -> Tuple[Any, SolveStats]:
    """Run ``op`` on ``state`` to its fixed point; return (state, SolveStats).

    Parameters
    ----------
    op : a :class:`PropagationOp` instance, or the *name* of a registered
        op (``repro.ops.list_ops()``: ``"morph"``, ``"edt"``,
        ``"fill_holes"``, ``"label"``, ...).  By name, the op is built via
        its :class:`~repro.ops.OpSpec` factory and ``state`` may be the
        op's natural **raw input** instead of a state pytree — a non-dict
        ``state`` (array, or tuple of arrays for multi-input ops like
        morph's ``(marker, mask)``) is passed through the spec's
        ``make_state`` builder: ``solve("edt", fg_image)``.  The result is
        still the converged *state*; apply ``get_op(name).extract`` (or use
        the per-op wrappers) for the user-facing array.
    connectivity : op-level knob for by-name calls, forwarded to the spec
        factory (each op's default applies when None).  Accepts a
        neighborhood *name* (``"conn4"``/``"conn8"`` in 2-D;
        ``"conn6"``/``"conn18"``/``"conn26"`` in 3-D — DESIGN.md §2.7) or
        the legacy 2-D ints 4/8; an unknown name or one the op does not
        support raises ``ValueError`` naming the op and its supported
        neighborhoods.  Invalid with an op instance — construct the
        instance with the connectivity you want.
    engine : one of :data:`ENGINES`.  ``"auto"`` ranks candidates with
        ``cost_model`` (default :class:`CostModel`) and runs the cheapest.
        ``"shard_map-tiled"`` composes the mesh TP/BP pipeline with a
        per-shard active-tile queue (the paper's full two-level hierarchy;
        DESIGN.md §2.2) — ``tile``/``queue_capacity``/``drain_batch`` all
        apply per shard.  It uses the plain per-tile drain; for
        Pallas-backed TP drains call
        :func:`repro.core.distributed.run_sharded` with ``tile_solver``.
    devices : device list for ``"shard_map"`` / ``"shard_map-tiled"``
        (default: ``jax.devices()``); also sets the device count the cost
        model sees.
    tile, queue_capacity : override the tiled engines' blocking; under
        ``"auto"`` they restrict the candidate set instead.
    drain_batch : queue slots the tiled engines drain concurrently per
        dispatch; ``1`` keeps the sequential per-tile scan.  Default: batch
        by :data:`DEFAULT_DRAIN_BATCH` for tiles up to
        :data:`BATCH_DEFAULT_MAX_TILE` (dispatch-bound regime), sequential
        above.  Under ``"auto"`` it restricts the candidate set like
        ``tile``/``queue_capacity``.
    kernel_queue : ``"tiled-pallas"`` only — drain each block through the
        in-kernel multi-level queue (DESIGN.md §2.5): per kernel round only
        the compacted candidate pixels are updated, spilling to one dense
        sweep when they overflow ``kernel_queue_capacity`` (None = a
        wavefront-band default, ``kernels.ops.default_kernel_queue_capacity``).
        Results and round counts are bit-identical to the dense kernels —
        only the per-round work changes.  Under ``"auto"``, ``None``
        (default) keeps both dense and queued ``tiled-pallas`` candidates
        in the ranking; True/False restricts to that variant.
    autotune : with ``engine="auto"``, micro-benchmark the model's top
        ``autotune_top_k`` candidates on this input (``autotune_repeats``
        timed runs each after a warm-up) and cache the winner keyed by
        :func:`autotune_signature`.
    interpret : run Pallas kernels in interpret mode.  ``None`` (default)
        decides from the backend (:func:`repro.kernels.default_interpret`):
        compiled on a TPU, interpreted elsewhere.  ``SolveStats.interpret``
        reports the resolved value.  ``kernel_queue=True`` raises
        ``NotImplementedError`` when the kernels would run compiled — the
        queued kernels do not lower for a TPU yet.
    n_workers : host threads for the ``"scheduler"`` and ``"hybrid"``
        engines (``"hybrid"`` accepts 0 for a device-only pool).
    n_device_workers : batched device drain streams sharing the
        ``"hybrid"`` engine's queue with the host threads (0 for a
        host-only pool; at least one worker of either kind is required).
    hybrid_pallas : back the ``"hybrid"`` device workers with the Pallas
        grid-over-batch kernels instead of the vmapped dense drain.
    """
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    if isinstance(op, str):
        spec = get_op(op)
        with spans.span("iwpp.build_state"):
            op = spec.make_op(connectivity)
            if not isinstance(state, dict):
                # Raw input(s), not a state pytree: build through the spec.
                inputs = state if isinstance(state, tuple) else (state,)
                state = spec.build_state(op, *inputs)
    elif connectivity is not None:
        raise ValueError(
            "connectivity= applies to by-name solve() calls only; construct "
            "the op instance with the desired connectivity instead")
    interpret = resolve_interpret(interpret)
    if kernel_queue and not interpret:
        raise NotImplementedError(QUEUE_LOWERING_GAP)
    run_kw = dict(max_rounds=max_rounds, devices=devices,
                  interpret=interpret, n_workers=n_workers,
                  n_device_workers=n_device_workers,
                  hybrid_pallas=hybrid_pallas, cost_model=cost_model)
    if (kernel_queue or kernel_queue_capacity is not None) \
            and engine not in ("tiled-pallas", "auto"):
        raise ValueError(
            "kernel_queue / kernel_queue_capacity apply to the "
            f"'tiled-pallas' engine (or 'auto') only, not {engine!r}: the "
            "in-kernel queue lives inside the Pallas tile solvers "
            "(DESIGN.md §2.5)")

    if engine != "auto":
        cfg = EngineConfig(engine, tile, queue_capacity, drain_batch,
                           kernel_queue=bool(kernel_queue),
                           kernel_queue_capacity=kernel_queue_capacity)
        with calibrate.solve_guard():
            return _run_engine(op, state, cfg, **run_kw)

    n_devices = len(devices) if devices is not None else len(jax.devices())
    tiles = (tile,) if tile is not None else DEFAULT_TILES
    with calibrate.solve_guard():
        return _solve_auto(op, state, tile, tiles, n_devices, queue_capacity,
                           drain_batch, kernel_queue, kernel_queue_capacity,
                           cost_model, interpret, autotune, autotune_top_k,
                           autotune_repeats, run_kw)


def _solve_auto(op, state, tile, tiles, n_devices, queue_capacity,
                drain_batch, kernel_queue, kernel_queue_capacity,
                cost_model, interpret, autotune, autotune_top_k,
                autotune_repeats, run_kw) -> Tuple[Any, SolveStats]:
    """The ``engine="auto"`` path: rank candidates, run the winner, report
    which model decided through ``SolveStats.cost_model``."""
    with spans.span("iwpp.select"):
        stats_in = collect_input_stats(op, state, n_devices, tiles)
        model = (cost_model if cost_model is not None
                 else default_cost_model(interpret=interpret))
        with spans.span("iwpp.select.rank"):
            cands = model.candidates(stats_in, tiles)
            if queue_capacity is not None:
                cands = [dataclasses.replace(c, queue_capacity=queue_capacity)
                         if c.queue_capacity is not None else c
                         for c in cands]
            if drain_batch is not None:
                cands = [dataclasses.replace(c, drain_batch=drain_batch)
                         if c.engine in ("tiled", "tiled-pallas",
                                         "shard_map-tiled", "hybrid")
                         else c for c in cands]
            if kernel_queue is not None:
                # True/False restricts the tiled-pallas candidates to that
                # kernel variant; None (the default) lets dense and queued
                # compete.
                cands = [c for c in cands
                         if c.engine != "tiled-pallas"
                         or c.kernel_queue == bool(kernel_queue)]
            if kernel_queue_capacity is not None:
                cands = [dataclasses.replace(
                    c, kernel_queue_capacity=kernel_queue_capacity)
                    if c.engine == "tiled-pallas" and c.kernel_queue
                    else c for c in cands]
            if autotune:
                cfg = _autotune(op, state, stats_in, model, cands,
                                (tile, queue_capacity, drain_batch,
                                 kernel_queue, kernel_queue_capacity),
                                autotune_top_k, autotune_repeats, **run_kw)
            else:
                cost, cfg = model.rank(stats_in, cands)[0]

    out, st = _run_engine(op, state, cfg, **run_kw)
    with spans.span("iwpp.calibrate"):
        model.calibrate(st)
    if autotune:
        return out, dataclasses.replace(
            st, autotuned=True, predicted_cost=model.cost(stats_in, cfg),
            n_devices=max(st.n_devices, 1), cost_model=model.kind)
    return out, dataclasses.replace(st, predicted_cost=cost,
                                    cost_model=model.kind)


# ---------------------------------------------------------------------------
# Batch-of-states entry — the serving layer's coalesced solve
# (DESIGN.md §2.9).
# ---------------------------------------------------------------------------

# Engines whose convergence loop is a pure lax.while_loop over the state,
# and therefore vmap cleanly into ONE batched fixed-point program: the
# batching rule freezes converged elements via per-element select, so each
# request's result (and round/source counters) is bit-identical to its solo
# run — extra rounds past an element's fixed point are no-ops.
BATCHABLE_ENGINES = ("frontier", "sweep")


def _batched_dense_for(op, engine: str, max_rounds: int):
    key = ("batch-dense", type(op), op.connectivity, engine, max_rounds)
    return compile_cache.get(
        key, lambda: jax.jit(jax.vmap(
            lambda s: run_dense(op, s, engine, max_rounds))))


def _tree_signature(state):
    return tuple(sorted((k, tuple(v.shape), str(jnp.asarray(v).dtype))
                        for k, v in state.items()))


@spans.recorded("iwpp.solve_batch")
def solve_batch(op, states: Sequence[Any], *,
                engine: str = "auto",
                connectivity: Optional[Union[int, str]] = None,
                cost_model: Optional[CostModel] = None,
                autotune: bool = False,
                max_rounds: int = 1_000_000,
                interpret: Optional[bool] = None,
                **engine_kw) -> List[Tuple[Any, SolveStats]]:
    """Solve ``len(states)`` independent same-shaped inputs as one batch.

    The coalescing entry the serving layer (``repro.serve``, DESIGN.md
    §2.9) drains its request queue through: all states must share one tree
    signature (leaf names, shapes, dtypes) — the coalescer's grouping
    contract — and the batch runs as **one** solve wherever the engine
    supports it:

    * dense engines (:data:`BATCHABLE_ENGINES`) — the states are stacked on
      a new leading axis and run under one ``jax.vmap``-ed fixed-point
      loop.  Results are bit-identical to per-state solo solves (the
      while_loop batching rule freezes converged elements), and the
      per-element round/source counters stay exact.
    * every other engine (host-loop engines: tiled/scheduler/hybrid/...) —
      the states run sequentially under the chosen config, still amortizing
      the compiled-step cache and the autotune winner across the batch.

    ``engine="auto"`` ranks candidates once on the first state via
    ``cost_model`` (default :func:`default_cost_model` — the calibrated
    profile when installed) and applies the winner to the whole batch;
    ``autotune=True`` micro-benchmarks the top candidates on the first
    state, sharing the process + disk autotune caches with solo solves.

    Returns a list of ``(state, SolveStats)`` in input order.  Batched
    elements report ``batch_size=len(states)`` and the *batch's* wall time
    (one program solved them all); sequential elements report their own.
    ``engine_kw`` takes the same per-engine knobs as :func:`solve`
    (``tile``, ``queue_capacity``, ``drain_batch``, ...).
    """
    if isinstance(op, str):
        spec = get_op(op)
        op = spec.make_op(connectivity)
        states = [s if isinstance(s, dict) else
                  spec.build_state(op, *(s if isinstance(s, tuple) else (s,)))
                  for s in states]
    elif connectivity is not None:
        raise ValueError(
            "connectivity= applies to by-name solve_batch() calls only; "
            "construct the op instance with the desired connectivity instead")
    states = list(states)
    if not states:
        return []
    interpret = resolve_interpret(interpret)
    sig0 = _tree_signature(states[0])
    for i, s in enumerate(states[1:], start=1):
        if _tree_signature(s) != sig0:
            raise ValueError(
                f"solve_batch needs one tree signature across the batch; "
                f"states[{i}] has {_tree_signature(s)} != states[0]'s "
                f"{sig0}.  Group requests by (op, shape, dtype) first — "
                "the serve-layer coalescer's pad-to-bucket policy exists "
                "for exactly this (docs/SERVING.md)")
    if len(states) == 1:
        out, st = solve(op, states[0], engine=engine, cost_model=cost_model,
                        autotune=autotune, max_rounds=max_rounds,
                        interpret=interpret, **engine_kw)
        return [(out, st)]

    if engine == "auto":
        with spans.span("iwpp.select"):
            stats_in = collect_input_stats(op, states[0])
            model = (cost_model if cost_model is not None
                     else default_cost_model(interpret=interpret))
            with spans.span("iwpp.select.rank"), calibrate.solve_guard():
                cands = model.candidates(stats_in)
                if autotune:
                    cfg = _autotune(op, states[0], stats_in, model, cands,
                                    ("batch",), top_k=3, repeats=2,
                                    max_rounds=max_rounds,
                                    interpret=interpret, devices=None,
                                    n_workers=4, n_device_workers=1,
                                    hybrid_pallas=False,
                                    cost_model=cost_model)
                else:
                    cfg = model.rank(stats_in, cands)[0][1]
        chosen, decided_by = cfg, model.kind
    else:
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
        chosen = EngineConfig(engine, engine_kw.get("tile"),
                              engine_kw.get("queue_capacity"),
                              engine_kw.get("drain_batch"),
                              kernel_queue=bool(engine_kw.get("kernel_queue")),
                              kernel_queue_capacity=engine_kw.get(
                                  "kernel_queue_capacity"))
        decided_by = None

    if chosen.engine in BATCHABLE_ENGINES:
        with spans.span("iwpp.engine") as engine_span, \
                calibrate.solve_guard(), compile_cache.MissSnapshot() as snap:
            with spans.span("iwpp.engine.prepare"):
                stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                                 *states)
                fn = _batched_dense_for(op, chosen.engine, max_rounds)
            with spans.span("iwpp.engine.wait"):
                out, rst = fn(stacked)
                jax.block_until_ready(out)
        results = []
        for i in range(len(states)):
            st_i = SolveStats(
                chosen.engine, rounds=spans.host_int(rst.rounds[i]),
                sources_processed=(spans.host_int(rst.sources_hi[i]) << 32)
                | spans.host_int(rst.sources_lo[i]),
                recompiles=snap.count, cost_model=decided_by,
                wall_time_s=engine_span.seconds, batch_size=len(states),
                interpret=interpret)
            results.append(
                (jax.tree_util.tree_map(lambda x: x[i], out), st_i))
        return results

    # Host-loop engines: no single-program batch formulation — run the
    # batch sequentially under the one chosen config (compiled steps and
    # autotune winners are shared across the loop via the process caches).
    run_kw = dict(max_rounds=max_rounds, interpret=interpret,
                  devices=engine_kw.get("devices"),
                  n_workers=engine_kw.get("n_workers", 4),
                  n_device_workers=engine_kw.get("n_device_workers", 1),
                  hybrid_pallas=engine_kw.get("hybrid_pallas", False),
                  cost_model=cost_model)
    results = []
    with calibrate.solve_guard():
        for s in states:
            out, st = _run_engine(op, s, chosen, **run_kw)
            results.append((out, dataclasses.replace(
                st, cost_model=decided_by)))
    return results
