"""Shared benchmark helpers: timing, CSV emission, the ``--json``/``--smoke``
record plumbing (one JSON schema for every ``BENCH_*.json`` — see
EXPERIMENTS.md §BENCH JSON schema), and workload builders."""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.compile_cache import enable_persistent_cache

# The canonical workload builders live in the package now
# (repro.ops.workloads) so calibration and the selection-regression tests
# rebuild the exact inputs these benchmarks time; re-exported here so bench
# scripts (and their committed BENCH_*.json record names) are unchanged.
from repro.ops.workloads import (_blob_volume, edt_state, edt_state3d,
                                 fill_state, label_state, morph_state,
                                 morph_state3d)  # noqa: F401


def timeit(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds of fn(*args) (block_until_ready on pytrees)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def emit(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.0f},{derived}", flush=True)


def record(records: list, name: str, seconds: float, **derived):
    """Emit one CSV row and append the matching JSON record.

    This is the single writer behind every ``BENCH_*.json`` row:
    ``{"name": ..., "seconds": ..., <derived fields>}`` — keep the schema in
    sync with EXPERIMENTS.md §BENCH JSON schema.
    """
    emit(name, seconds, ";".join(f"{k}={v}" for k, v in derived.items()))
    records.append({"name": name, "seconds": seconds, **derived})


def write_json(records: list, json_path: Optional[str]):
    """Write the collected records if ``--json`` was requested (no-op else)."""
    if not json_path:
        return
    with open(json_path, "w") as f:
        json.dump(records, f, indent=2)
    print(f"# wrote {len(records)} records to {json_path}", flush=True)


def bench_argparser(default_json: str, *, size: int = 512,
                    smoke_help: Optional[str] = None) -> argparse.ArgumentParser:
    """The shared benchmark CLI: ``--size``, ``--json [PATH]`` and (when
    ``smoke_help`` is given) the ``--smoke`` CI profile flag.  Callers add
    their bench-specific arguments on the returned parser.

    Every bench's ``__main__`` builds this parser before it compiles
    anything, so this is also where the benches turn on JAX's persistent
    compilation cache (:func:`repro.core.compile_cache.enable_persistent_cache`)."""
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=size)
    ap.add_argument("--json", nargs="?", const=default_json, default=None,
                    metavar="PATH",
                    help=f"write records as JSON (default path {default_json})")
    ap.add_argument("--calibrate", action="store_true",
                    help="run/refresh the measured cost-model calibration "
                         "(DESIGN.md §2.8) before benchmarking, so auto "
                         "rows select with the MeasuredCostModel")
    if smoke_help is not None:
        ap.add_argument("--smoke", action="store_true", help=smoke_help)
    return ap


def maybe_calibrate(args) -> None:
    """Honor ``--calibrate``: measure + install + persist a calibration
    profile before the bench runs (a no-op without the flag, so default
    bench runs still exercise the analytic cold-start path)."""
    if not getattr(args, "calibrate", False):
        return
    from repro.core.calibrate import run_calibration
    smoke = bool(getattr(args, "smoke", False))
    print(f"# calibrating (smoke={smoke}) ...", flush=True)
    run_calibration(smoke=smoke, save=True, verbose=True)
