"""Paper Table 1: queue design vs initialization depth — plus the §3.2
sequential-vs-batched global-queue drain comparison.

The paper varies the number of FH init raster scans (7..19) to shrink the
initial queue, then compares Naive / prefix-sum (PF) / +thread-queue (TQ)
GPU queue designs.  Our TPU analogues of increasing locality:

  E0 sweep    — no wavefront tracking at all (queue-less lower bound;
                the SR_GPU-style full-grid pass),
  E1 frontier — wavefront tracked as a dense mask (Naive/PF analogue:
                tracks the queue but pays full-grid bandwidth each round),
  E2 tiled    — hierarchical: active-tile queue + VMEM-local drain (the
                paper's TQ/BQ/GBQ multi-level design).

All runs go through ``repro.solve.solve``, so each row reports the same
normalized SolveStats record (rounds / sources / tile drains / overflow
events) — the uniform comparison EXPERIMENTS.md is built on.  A final row
shows what the cost model would pick for each init depth (engine="auto").

The drain section reproduces the paper's central parallelism claim at the
queue level: popping the compacted active-tile queue in concurrent batches
(``drain_batch`` > 1) versus one tile at a time.  ``--json`` (or
``main(json_path=...)``) writes every record to ``BENCH_tiled.json`` so the
perf trajectory is tracked across PRs.

The paper's trend to reproduce: deeper init -> smaller queue -> faster
wavefront phase; hierarchical queueing wins and its advantage grows as the
wavefront sparsifies; batch-draining the queue wins once occupancy covers
the batch (K >= 4).

The kernel section compares the dense Pallas tile kernels against their
in-kernel-queue variants (``kernel_queue=True``, DESIGN.md §2.5): the
serpentine-corridor rows are the sparse-wavefront regime where the queued
kernels win, the seeded-tissue engine rows the dense regime where they
don't, and ``serpentine_kernel_guard`` is the asserting CI check that the
queued kernel never needs more rounds than the dense one.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (maybe_calibrate as common_calibrate,
                               bench_argparser, morph_state, record,
                               timeit, write_json)
from repro.core.tiles import initial_active_tiles
from repro.kernels.morph_tile import morph_tile_solve, morph_tile_solve_queued
from repro.morph.ops import MorphReconstructOp
from repro.solve import solve

DEFAULT_JSON = "BENCH_tiled.json"


def serpentine_state(n: int):
    """1-px serpentine corridor, seed at (0, 0): geodesic depth ~n²/2 with a
    1-2 pixel wavefront — the sparse-seed regime where the in-kernel queue
    (DESIGN.md §2.5) pays off (the paper's point that the queue advantage
    grows as the wavefront sparsifies).  Mirrors tests/test_truncation.py's
    fixture."""
    corridor = np.zeros((n, n), bool)
    corridor[0::2, :] = True
    for i, r in enumerate(range(1, n - 1, 2)):
        corridor[r, (n - 1) if i % 2 == 0 else 0] = True
    mask = np.where(corridor, 100, 0).astype(np.int32)
    marker = np.zeros_like(mask)
    marker[0, 0] = 100
    op = MorphReconstructOp(connectivity=8)
    return op, op.make_state(jnp.asarray(marker), jnp.asarray(mask))


def table1(size: int, records: list):
    for n_sweeps in (1, 2, 3, 4):
        op, state = morph_state(size, coverage=1.0, seed=0, n_sweeps=n_sweeps)
        init_q = int(jnp.sum(op.init_frontier(state)))
        _, st = solve(op, state, engine="frontier")
        total = st.sources_processed
        t0 = timeit(lambda: solve(op, state, engine="sweep")[0])
        t1 = timeit(lambda: solve(op, state, engine="frontier")[0])
        t2 = timeit(lambda: solve(op, state, engine="tiled",
                                  tile=128, queue_capacity=64)[0])
        _, s2 = solve(op, state, engine="tiled", tile=128, queue_capacity=64)
        record(records, f"table1/sweeps={n_sweeps}/E0_sweep", t0,
                init_q=init_q, total_q=total)
        record(records, f"table1/sweeps={n_sweeps}/E1_frontier", t1,
                rounds=st.rounds, speedup_vs_E0=round(t0 / t1, 2))
        record(records, f"table1/sweeps={n_sweeps}/E2_tiled", t2,
                drains=s2.tiles_processed, overflows=s2.overflow_events,
                speedup_vs_E0=round(t0 / t2, 2), vs_E1=round(t1 / t2, 2))
        _, sa = solve(op, state, engine="auto")
        record(records, f"table1/sweeps={n_sweeps}/auto", 0.0,
                picked=sa.engine, tile=sa.tile,
                predicted_cost=round(sa.predicted_cost))


def drain_comparison(size: int, records: list, tile: int = 32,
                     queue_capacity: int = 64):
    """§3.2 parallel queue consumption: sequential scan vs batched drain.

    Sparse seeded markers on a ``size``² grid keep the wavefront thin, so
    the active-tile queue stays well occupied (K >= 4) for many rounds —
    the regime where draining the queue in concurrent batches pays.
    """
    op, state = morph_state(size, coverage=1.0, seed=0, n_sweeps=0,
                            marker_kind="seeded")
    active0 = int(jnp.sum(initial_active_tiles(op, state, tile)))
    t_seq = timeit(lambda: solve(op, state, engine="tiled", tile=tile,
                                 queue_capacity=queue_capacity,
                                 drain_batch=1)[0])
    _, s_seq = solve(op, state, engine="tiled", tile=tile,
                     queue_capacity=queue_capacity, drain_batch=1)
    occupancy = s_seq.tiles_processed / max(s_seq.rounds, 1)
    record(records, f"drain/size={size}/tile={tile}/sequential", t_seq,
            drain_batch=1, rounds=s_seq.rounds, drains=s_seq.tiles_processed,
            active0=active0, occupancy=round(occupancy, 1))
    for db in (4, 8, 16):
        t_b = timeit(lambda: solve(op, state, engine="tiled", tile=tile,
                                   queue_capacity=queue_capacity,
                                   drain_batch=db)[0])
        _, s_b = solve(op, state, engine="tiled", tile=tile,
                       queue_capacity=queue_capacity, drain_batch=db)
        record(records, f"drain/size={size}/tile={tile}/batched", t_b,
                drain_batch=db, rounds=s_b.rounds, drains=s_b.tiles_processed,
                occupancy=round(s_b.tiles_processed / max(s_b.rounds, 1), 1),
                speedup_vs_seq=round(t_seq / t_b, 2))


def kernel_comparison(records: list, sizes=(128, 256), caps=(16, 64)):
    """Dense vs queued Pallas tile kernels (DESIGN.md §2.5).

    Serpentine rows are the sparse-wavefront regime (1-2 px front, depth
    ~n²/2): each run is one whole-image tile drained in-kernel, dense
    full-block rounds against O(capacity) push rounds.  Both variants reach
    bit-identical fixed points in the same number of rounds; only the work
    per round differs, so ``speedup_vs_dense`` isolates the queue itself.
    """
    for n in sizes:
        op, state = serpentine_state(n)
        t_d = timeit(lambda: solve(op, state, engine="tiled-pallas",
                                   tile=n)[0])
        _, sd = solve(op, state, engine="tiled-pallas", tile=n)
        record(records, f"kernel/serpentine={n}/dense", t_d, rounds=sd.rounds)
        for cap in caps:
            t_q = timeit(lambda: solve(op, state, engine="tiled-pallas",
                                       tile=n, kernel_queue=True,
                                       kernel_queue_capacity=cap)[0])
            _, sq = solve(op, state, engine="tiled-pallas", tile=n,
                          kernel_queue=True, kernel_queue_capacity=cap)
            record(records, f"kernel/serpentine={n}/queued", t_q,
                   capacity=cap, rounds=sq.rounds,
                   speedup_vs_dense=round(t_d / t_q, 2))


def engine_queue_comparison(size: int, records: list, tile: int = 128):
    """The honest non-corridor counterpart: seeded-tissue markers (ring
    wavefronts, shallow per-tile depth).  Dense rounds fuse into a couple
    of XLA kernels here while push rounds pay per-round dispatch overhead,
    so dense typically wins — the cost model's reason for only proposing
    kernel_queue on deep sparse drains."""
    op, state = morph_state(size, coverage=1.0, seed=0, marker_kind="seeded")
    t_d = timeit(lambda: solve(op, state, engine="tiled-pallas", tile=tile)[0])
    _, sd = solve(op, state, engine="tiled-pallas", tile=tile)
    record(records, f"engine/seeded={size}/tile={tile}/dense", t_d,
           rounds=sd.rounds, drains=sd.tiles_processed)
    t_q = timeit(lambda: solve(op, state, engine="tiled-pallas", tile=tile,
                               kernel_queue=True)[0])
    _, sq = solve(op, state, engine="tiled-pallas", tile=tile,
                  kernel_queue=True)
    record(records, f"engine/seeded={size}/tile={tile}/queued", t_q,
           capacity=sq.kernel_queue_capacity, rounds=sq.rounds,
           drains=sq.tiles_processed, speedup_vs_dense=round(t_d / t_q, 2))


def serpentine_kernel_guard(records: list, n: int = 64):
    """CI perf-regression guard (ISSUE 6 satellite): on the serpentine
    fixture the queued kernel must reach the *same* fixed point in *no
    more* rounds than the dense kernel — a silently dropped enqueue would
    stall the wavefront and inflate the round count.  Raises
    ``AssertionError`` (failing the CI step) on violation."""
    op, state = serpentine_state(n)
    neut = np.iinfo(np.int32).min
    J = jnp.asarray(np.pad(np.asarray(state["J"]), 1, constant_values=neut))
    I = jnp.asarray(np.pad(np.asarray(state["I"]), 1, constant_values=neut))
    valid = jnp.asarray(np.pad(np.ones((n, n), bool), 1))
    d, di = morph_tile_solve(J, I, valid, connectivity=8,
                             max_iters=(n + 2) ** 2)
    q, qi, spills = morph_tile_solve_queued(J, I, valid, connectivity=8,
                                            max_iters=(n + 2) ** 2,
                                            queue_capacity=16)
    assert np.array_equal(np.asarray(d), np.asarray(q)), \
        "queued kernel diverged from the dense fixed point"
    assert int(qi) <= int(di), \
        f"queued rounds {int(qi)} exceed dense rounds {int(di)}"
    record(records, f"guard/serpentine={n}", 0.0, dense_rounds=int(di),
           queued_rounds=int(qi), spills=int(spills), passed=True)


def main(size: int = 512, json_path: str | None = None,
         drain_size: int | None = None, smoke: bool = False):
    records: list = []
    if smoke:
        table1(128, records)
        drain_comparison(256, records, tile=32)
        kernel_comparison(records, sizes=(64,), caps=(16,))
        engine_queue_comparison(128, records, tile=64)
    else:
        table1(size, records)
        drain_comparison(
            drain_size if drain_size is not None else max(size, 1024),
            records)
        kernel_comparison(records)
        engine_queue_comparison(256, records)
    serpentine_kernel_guard(records)
    write_json(records, json_path)
    return records


if __name__ == "__main__":
    ap = bench_argparser(DEFAULT_JSON,
                         smoke_help="CI profile: small grids, the queued-vs-"
                                    "dense kernel rows, and the asserting "
                                    "serpentine rounds guard")
    ap.add_argument("--drain-size", type=int, default=None,
                    help="grid side for the drain comparison (default: "
                         "max(size, 1024))")
    a = ap.parse_args()
    common_calibrate(a)
    main(a.size, json_path=a.json, drain_size=a.drain_size, smoke=a.smoke)
