"""Paper Figs. 10/15/16: multi-processor scaling — and the composed
`shard_map-tiled` hierarchy against the flat `shard_map` engine.

Three layers, matching the paper's experiments:
  * host-scheduler scaling (paper Fig. 10 tiled-vs-non-tiled multicore):
    the demand-driven FCFS TileScheduler with 1..4 workers;
  * device-mesh scaling (paper Figs. 15/16 multi-GPU): the E3 shard_map
    engine on 1/2/4/8 host devices, run in subprocesses so the parent
    process keeps a single-device view;
  * engine composition (the §4-over-§3.2 hierarchy): `shard_map` (dense
    per-device TP drains) vs `shard_map-tiled` (per-shard active-tile
    queues re-seeded each BP round from only the halo-improved tiles) on
    sparse-seeded and dense wavefronts over the same meshes.

``--json [PATH]`` writes every record to ``BENCH_multidevice.json`` (the
perf-trajectory seed, tracked per PR like ``BENCH_tiled.json``); ``--smoke``
shrinks sizes/meshes/iterations to the CI profile (8 fake CPU devices).

CPU-host caveat recorded in EXPERIMENTS.md: all "devices" share one socket
here, so scaling saturates at the memory bus — the numbers validate the
TP/BP pipeline's correctness+overhead, not TPU-pod bandwidth.  The
composition comparison is still meaningful on CPU hosts for the *work*
columns (BP rounds, tiles drained vs whole-shard redrains).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np

from benchmarks.common import (maybe_calibrate as common_calibrate,
                               bench_argparser, record, write_json)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_JSON = "BENCH_multidevice.json"

_CHILD = """
import time
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import run_sharded
from repro.data.images import tissue_image, seeded_marker
from repro.morph.ops import MorphReconstructOp
mesh = jax.make_mesh({mesh_shape}, ("data", "model"))
marker, mask = tissue_image({size}, {size}, 1.0, seed=0)
if {sparse}:
    marker = seeded_marker(mask, n_seeds=max(8, {size} // 20), seed=0)
op = MorphReconstructOp(connectivity=8)
state = op.make_state(jnp.asarray(marker.astype(np.int32)),
                      jnp.asarray(mask.astype(np.int32)))
kw = dict(tile={tile}, queue_capacity=64, drain_batch=1) if {tiled} else {{}}
out, st = run_sharded(op, state, mesh, **kw)   # compile+warm
ts = []
for _ in range({iters}):
    t0 = time.perf_counter()
    out, st = run_sharded(op, state, mesh, **kw)
    jax.block_until_ready(out)
    ts.append(time.perf_counter() - t0)
print("RESULT", np.median(ts), int(st.bp_rounds), int(st.tiles_processed),
      int(st.overflow_events))
"""


def _run_child(ndev, mesh_shape, size, sparse=False, tiled=False, tile=128,
               iters=3):
    env = dict(os.environ)
    # The children emulate devices on the host CPU by design; on a TPU host
    # they must not try to take the chip this process may hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = _CHILD.format(mesh_shape=mesh_shape, size=size, sparse=sparse,
                         tiled=tiled, tile=tile, iters=iters)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=560, env=env)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    _, t, rounds, tiles, ovf = line.split()
    return float(t), int(rounds), int(tiles), int(ovf)


def scheduler_scaling(size: int, records: list, workers_list=(1, 2, 4),
                      tag: str = "fig10/scheduler"):
    """Fig 10 analogue: host tile scheduler, 1..N workers.

    Every worker thread drains through solve.py's process-wide compiled
    scheduler drain (the "scheduler-drain" compile-cache entry) — per-bench
    local re-jits used to serialize workers behind tracing and showed up as
    the fig10 workers=2 = 0.47x regression.  Returns {workers: seconds}.
    """
    from repro.core.scheduler import TileScheduler
    from repro.core.tiles import initial_active_tiles
    from repro.data.images import tissue_image
    from repro.morph.ops import MorphReconstructOp
    from repro.solve import _host_tile_fn_for
    import jax.numpy as jnp
    import time

    marker, mask = tissue_image(size, size, 1.0, seed=0)
    op = MorphReconstructOp(connectivity=8)
    T = 128
    tile_fn = _host_tile_fn_for(op, T)

    # warm the shared jitted drain so worker=1 timing excludes compilation
    warm = {"J": np.zeros((T + 2, T + 2), np.int32),
            "I": np.zeros((T + 2, T + 2), np.int32),
            "valid": np.ones((T + 2, T + 2), bool)}
    tile_fn(warm)

    times, base = {}, None
    for workers in workers_list:
        state = {"J": np.minimum(marker, mask).astype(np.int32),
                 "I": mask.astype(np.int32),
                 "valid": np.ones(mask.shape, bool)}
        active = np.asarray(initial_active_tiles(
            op, {k: jnp.asarray(v) for k, v in state.items()}, T))
        t0 = time.perf_counter()
        TileScheduler(state, T, tile_fn, active, n_workers=workers).run()
        t = time.perf_counter() - t0
        times[workers] = t
        base = base or t
        record(records, f"{tag}/workers={workers}", t,
                speedup=round(base / t, 2))
    return times


def scheduler_guard(records: list, size: int = 2048, reps: int = 3):
    """The workers=2 regression guard on a 2048² input.

    On a multi-core host the shared compiled drain makes two workers a
    genuine win, so the floor is 1.0x.  A process pinned to ONE core (this
    repo's CI containers) caps thread parallelism at parity minus GIL +
    XLA-dispatch contention — measured ~0.8-0.9x there — so the floor drops
    to 0.75x, which still trips on the re-trace regression class this
    guards against (workers=2 used to measure 0.47x).  Best-of-`reps`
    ratios, because single-core interleaving is noisy.
    """
    ratios = []
    for rep in range(reps):
        rec_sink = records if rep == 0 else []   # record one rep, time all
        times = scheduler_scaling(size, rec_sink, workers_list=(1, 2),
                                  tag=f"fig10/scheduler{size}")
        ratios.append(times[1] / times[2])
    speedup = max(ratios)
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:                        # non-Linux fallback
        cores = os.cpu_count() or 1
    floor = 1.0 if cores >= 2 else 0.75
    record(records, f"fig10/scheduler{size}/workers=2/guard", 0.0,
           speedup=round(speedup, 2), floor=floor, cores=cores)
    assert speedup >= floor, (
        f"scheduler workers=2 regression: best {speedup:.2f}x vs workers=1 "
        f"on {size}^2 over {reps} reps (floor {floor} at {cores} cores)")


def compose_guard(records: list, threshold: float = 0.5):
    """CI tripwire: the composed shard_map-tiled engine must stay within
    `threshold` of the flat shard_map engine on every recorded config."""
    rows = [r for r in records
            if r["name"].endswith("/shard_map-tiled")
            and "speedup_vs_flat" in r]
    bad = [(r["name"], r["speedup_vs_flat"]) for r in rows
           if r["speedup_vs_flat"] < threshold]
    if bad:
        raise SystemExit(
            f"compose_guard: shard_map-tiled below {threshold}x flat: {bad}")
    print(f"# compose_guard OK: {len(rows)} rows >= {threshold}x flat",
          flush=True)


def mesh_scaling(size: int, records: list, meshes, iters=3):
    """Figs 15/16 analogue: flat shard_map mesh scaling via subprocesses.

    Returns {ndev: (seconds, bp_rounds)} so composition_comparison can
    reuse these dense flat runs instead of re-spawning identical children.
    """
    base, flat_dense = None, {}
    for ndev, mesh_shape in meshes:
        t, rounds, _, _ = _run_child(ndev, mesh_shape, size, iters=iters)
        base = base or t
        flat_dense[ndev] = (t, rounds)
        record(records, f"fig15/mesh/devices={ndev}", t,
                speedup=round(base / t, 2), bp_rounds=rounds)
    return flat_dense


def composition_comparison(size: int, records: list, meshes, tile=128,
                           iters=3, flat_dense=None):
    """shard_map vs shard_map-tiled on sparse/dense seeds over the meshes.

    The regime claim (paper Fig. 12 transplanted to the mesh level): with
    sparse seeds the wavefront touches few tiles per shard, so the composed
    engine's per-shard queue skips the stable interior every BP round; with
    near-full wavefronts the dense drain's full-shard rounds are already
    optimal and the queue is pure overhead.
    """
    for kind, sparse in (("sparse", True), ("dense", False)):
        for ndev, mesh_shape in meshes:
            if not sparse and flat_dense and ndev in flat_dense:
                # identical workload to the fig15 run — reuse, don't respawn
                t_flat, rounds_f = flat_dense[ndev]
            else:
                t_flat, rounds_f, _, _ = _run_child(
                    ndev, mesh_shape, size, sparse=sparse, iters=iters)
            record(records,
                    f"compose/{kind}/devices={ndev}/shard_map", t_flat,
                    bp_rounds=rounds_f)
            t_tiled, rounds_t, tiles, ovf = _run_child(
                ndev, mesh_shape, size, sparse=sparse, tiled=True, tile=tile,
                iters=iters)
            record(records,
                    f"compose/{kind}/devices={ndev}/shard_map-tiled", t_tiled,
                    bp_rounds=rounds_t, tiles=tiles, overflows=ovf,
                    speedup_vs_flat=round(t_flat / t_tiled, 2))


def main(size: int = 512, json_path: str | None = None, smoke: bool = False):
    records: list = []
    if smoke:
        # CI profile: one small grid, the 1-device baseline and the full
        # 8-fake-device mesh, single timed iteration.
        size = 256
        meshes = ((1, (1, 1)), (8, (2, 4)))
        scheduler_scaling(size, records, workers_list=(1, 2))
        # The compose guard needs shards that fit at least one full T=128
        # tile queue: 512²/(2,4) = 256x128 per-shard.  At 256² the tile
        # covers the whole shard and the guard would measure pure queue
        # overhead instead of the hierarchy.
        csize = 512
        flat = mesh_scaling(csize, records, meshes, iters=1)
        composition_comparison(csize, records, meshes, iters=1,
                               flat_dense=flat)
        compose_guard(records)
    else:
        meshes = ((1, (1, 1)), (2, (1, 2)), (4, (2, 2)), (8, (2, 4)))
        scheduler_scaling(size, records)
        scheduler_guard(records)
        flat = mesh_scaling(size, records, meshes)
        composition_comparison(size, records, meshes, flat_dense=flat)
        compose_guard(records)
    write_json(records, json_path)
    return records


if __name__ == "__main__":
    ap = bench_argparser(
        DEFAULT_JSON,
        smoke_help="CI profile: small grid, 1+8 device meshes, 1 iter")
    a = ap.parse_args()
    common_calibrate(a)
    main(a.size, json_path=a.json, smoke=a.smoke)
