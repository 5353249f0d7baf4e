"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file, ``bench/traffic/<traffic>.json`` and the generators it
names (``bench/generators/``), the op's reference in ``bench/ops/<op>.py``
and each per-layer metric's reader in ``bench/layers/<metric>.py``.  Adding
a cell, a configuration, a mix or a layer metric adds files and entries; it
edits none of these.

The window drives the public entry point as users call it: one
``repro.ops.run_op(op, *inputs)`` per tile, every solve keyword at its
default (engine ``auto``), each result forced with ``block_until_ready``.
It measures whole passes over the cell's tile pool until ``seconds`` have
passed.  A sample of the window's results, drawn from the seed, is copied to
the host as it comes; once the window has closed it is compared with the
plain reference.
"""

from __future__ import annotations

import dataclasses
import gc
import random
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

import numpy as np

import devtrace
import generate
from loader import BENCH_DIR, ROOT, BenchError, load_json, load_module

sys.path.insert(0, str(ROOT / "src"))
# Results of the window copied to the host for the comparison (a reservoir
# sample).
SAMPLES = 4
# With --trace 1 the profiler records this many slices, each this long, at
# points of the window's first pass drawn from the seed.
TRACE_SLICES = 24
TRACE_SLICE_S = 0.04
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _for_cell(metrics, name):
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(name, int(w["chips"]),
                load_json(ROOT / configs[w["config"]]["file"]),
                load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
                _for_cell(bench["end_to_end"], name),
                _for_cell(bench["per_layer"], name))


def check_device(devices, chips: int, peaks: dict) -> dict:
    """The measurement path's device gate: a TPU, as many chips as the
    cell asks for, and a kind the peaks table knows.  Raises otherwise."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else None
        raise NoChip(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json "
                         f"({sorted(peaks)})")
    return {"platform": devices[0].platform, "kind": kind,
            "count": len(devices)}


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache in the checkout's ``.jax_cache``
    (the program's own directory, a fixed path: the path is part of the
    cache key), so only a cell's first run compiles.  It is set even where
    ``JAX_COMPILATION_CACHE_DIR`` names another directory: each checkout
    keeps its own cache, and two checkouts share none."""
    import jax
    from repro.core.compile_cache import PERSISTENT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(PERSISTENT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts programs compiled (or loaded from the compile cache)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read (``bench/layers/<name>.py``)."""
    stats: list              # SolveStats of the window's calls
    mpix: float              # megapixels solved in the window
    compiles: int            # compile events inside the window
    trace: Optional[object]  # devtrace.TraceSummary of the traced calls


def default_solver(config: dict, opmod) -> Callable:
    """The timed path: ``run_op`` with every solve keyword at its default."""
    from repro.ops import run_op

    def solve(tile):
        return run_op(config["op"], *opmod.inputs(tile),
                      connectivity=config["connectivity"])
    return solve


class TraceSlicer(threading.Thread):
    """Records ``TRACE_SLICES`` profiler slices of ``TRACE_SLICE_S`` each
    over ``span_s`` seconds from ``t0`` (the window's first pass): slice
    ``k`` is due at a point drawn from the seed in the ``k``-th of
    ``TRACE_SLICES`` equal parts of the span, so that no slice keeps one
    phase of a pass, whose solves come in a fixed order.  A slice that falls
    due while the last one is still collected starts when that is done.  A
    device op is one trace event, and the tiled engines run over a million a
    second, so a whole pass would take minutes to collect: short slices keep
    a traced run inside its time limit."""

    def __init__(self, t0: float, span_s: float, seed: int):
        super().__init__(daemon=True)
        self.t0, self.span_s = t0, span_s
        rng = random.Random(seed)
        self.offsets = [(k + rng.random()) / TRACE_SLICES
                        for k in range(TRACE_SLICES)]
        self.slices: list = []          # (serialized XSpace, host t0 in ns)
        self.error: Optional[BaseException] = None

    def run(self):
        import jax
        # A profiler session of its own hands back the XSpace in memory;
        # jax.profiler.stop_trace would write it, and a JSON copy, to disk.
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            for offset in self.offsets:
                due = self.t0 + self.span_s * offset
                time.sleep(max(0.0, due - time.monotonic()))
                session = _profiler.ProfilerSession(opts)
                h0 = time.monotonic_ns()
                with jax.profiler.TraceAnnotation(devtrace.SLICE):
                    time.sleep(TRACE_SLICE_S)
                self.slices.append((session.stop(), h0))
        except Exception as e:  # noqa: BLE001 — reported after the window
            self.error = e


def collect() -> None:
    """Run Python's cyclic collector.  Some device buffers of a call stay
    referenced from reference cycles until the collector runs, and it runs
    when Python's allocation counts say so: a 64-128 MiB plane more or less
    alive at a call's peak.  Collecting after every call (and after every
    warm-up call) frees them at the same point in every run, so that the
    device's peak (``memory_peak_bytes``) depends less on when the
    collector happened to run."""
    gc.collect()


@dataclasses.dataclass
class Window:
    """What the measured window leaves behind."""
    stats: list              # SolveStats of each call that returned
    sample: list             # (tile index, result on the host) to compare
    spans: list              # ("solve" | "ready" | "aside", start, end ns)
    failed: int              # calls that raised
    px: int                  # input pixels of the calls that returned
    pass_s: list             # seconds of each pass
    aside_s: float           # the benchmark's own work between calls
    seconds: float           # the whole window, less aside_s


def measure(pool: list, px: list, solver: Callable, seconds: float,
            seed: int, slicer: Optional[TraceSlicer], log) -> Window:
    """Whole passes over ``pool`` (``px[i]``: tile ``i``'s input pixels)
    until ``seconds`` have passed (and the slicer, if any, is done).

    The sample is a reservoir of ``SAMPLES`` results drawn from the seed
    over every call of the window.  A result that enters it is copied to the
    host at once, and every result leaves the device once its call is done,
    so that the device holds the pool, the engine's own buffers and one
    result, whatever the seed draws.  After each call Python's collector
    runs (``collect``).  Both are the benchmark's own work: their seconds
    are left out of the window's."""
    import jax
    rng = random.Random(seed)
    w = Window([], [], [], 0, 0, [], 0.0, 0.0)
    t0 = time.monotonic()
    elapsed = lambda: time.monotonic() - t0 - w.aside_s  # noqa: E731
    if slicer:
        slicer.start()
    while True:
        for i, tile in enumerate(pool):
            a = time.monotonic_ns()
            try:
                out, st = solver(tile)
                b = time.monotonic_ns()
                jax.block_until_ready(out)
            except Exception as e:  # noqa: BLE001 — a failed call counts
                w.failed += 1
                print(f"call {len(w.stats) + w.failed} (tile {i}) failed: "
                      f"{e!r}", file=log)
                continue
            w.spans += [("solve", a, b), ("ready", b, time.monotonic_ns())]
            w.stats.append(st)
            w.px += px[i]
            t = time.monotonic_ns()
            n = len(w.stats)
            slot = n - 1 if n <= SAMPLES else rng.randrange(n)
            if slot < SAMPLES:
                kept = (i, np.asarray(out))
                if slot < len(w.sample):
                    w.sample[slot] = kept
                else:
                    w.sample.append(kept)
            del out
            collect()
            end = time.monotonic_ns()
            w.spans.append((devtrace.ASIDE, t, end))
            w.aside_s += (end - t) / 1e9
        w.seconds = elapsed()
        w.pass_s.append(w.seconds - sum(w.pass_s))
        if w.seconds >= seconds and not (slicer and slicer.is_alive()):
            return w


def compare(sample: list, pool: list, opmod, config: dict) -> dict:
    """The worst of each number over the sampled results, each against the
    reference of its tile (references of distinct tiles in parallel)."""
    tiles = sorted({i for i, _ in sample})
    with ThreadPoolExecutor(max(1, len(tiles))) as ex:
        refs = dict(zip(tiles, ex.map(
            lambda i: opmod.reference(pool[i], config), tiles)))
    worst: dict = {}
    for i, result in sample:
        for k, v in opmod.compare(result, refs[i]).items():
            worst[k] = max(worst.get(k, v), v)
    return worst


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, solver: Optional[Callable] = None,
             side: Optional[int] = None, require_chip: bool = True,
             log=sys.stderr) -> dict:
    """One run; returns the result line as a dict.

    ``solver(tile) -> (result, stats)`` replaces the timed path and
    ``side`` the configuration's tile side, and ``require_chip=False``
    skips the device gate: for the tests, which drive a run on the CPU.
    """
    cell = load_cell(name)
    config = cell.config
    opmod = load_module(BENCH_DIR / "ops" / f"{config['op']}.py")
    readers = {m["name"]: load_module(BENCH_DIR / "layers" / f"{m['name']}.py")
               for m in cell.per_layer}
    enable_compile_cache()
    import jax

    devices = jax.devices()
    if require_chip:
        device = check_device(devices, cell.chips,
                              load_json(BENCH_DIR / "peaks.json"))
    else:
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    if solver is None:
        solver = default_solver(config, opmod)
    side = side or config["side"]
    counter = CompileCounter()
    try:
        pool = generate.make_pool(cell.traffic, side, seed)
        jax.block_until_ready(pool)
        px = [int(np.prod(opmod.inputs(tile)[0].shape)) for tile in pool]
        t = time.monotonic()
        collect()
        for tile in pool:                      # warm-up: one whole pass
            jax.block_until_ready(solver(tile)[0])
            collect()
        warm_s = time.monotonic() - t
        setup_s = time.monotonic() - t_start
        compiles0 = counter.count
        slicer = (TraceSlicer(time.monotonic(), warm_s, seed) if trace
                  else None)
        w = measure(pool, px, solver, seconds, seed, slicer, log)
        compiles = counter.count - compiles0
    finally:
        counter.close()
    device["memory_peak_bytes"] = (devices[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    mpix = w.px / 1e6

    summary = None
    if slicer:
        from jax.profiler import ProfileData
        t = time.monotonic()
        if slicer.error is not None:
            print(f"trace slicer failed: {slicer.error!r}", file=log)
        summary = devtrace.summarize([
            devtrace.reduce_slice(ProfileData.from_serialized_xspace(xs),
                                  w.spans, h0) for xs, h0 in slicer.slices])
        print(f"trace: {len(slicer.slices)} slices, "
              f"{summary.n_ops if summary else 0} device ops, read in "
              f"{time.monotonic() - t:.3f} s", file=log)

    # The comparison, once the window has closed and the peak is read.
    t = time.monotonic()
    n_compared = len(w.sample)
    worst = compare(w.sample, pool, opmod, config)
    w.sample.clear()
    print(f"compared {n_compared} results with the reference in "
          f"{time.monotonic() - t:.3f} s", file=log)
    limits = config["limits"]
    checks = {k: {"value": worst.get(k), "limit": lim}
              for k, lim in limits.items()}
    correct = (w.failed == 0 and bool(w.stats) and set(worst) == set(limits)
               and all(worst[k] <= lim for k, lim in limits.items()))

    metrics = {}
    if trace:
        ctx = LayerContext(w.stats, mpix, compiles, summary)
        for m in cell.per_layer:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"mpix_per_s": mpix / w.seconds, "setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise BenchError(f"no measurement for end-to-end metric "
                                 f"{m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    line = {"correct": correct, "attempted": len(w.stats) + w.failed,
            "failed": w.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": [list(x) for x in summary.top_ops],
                             "idle_gaps": [list(x)
                                           for x in summary.idle_by_span]}
    engines = sorted({(s.engine, s.tile, s.drain_batch) for s in w.stats},
                     key=str)
    print(f"window: {len(w.pass_s)} passes of "
          f"{[round(x, 3) for x in w.pass_s]} s, {len(w.stats)} solves, "
          f"{w.seconds:.3f} s ({w.aside_s:.3f} s of sample copies and "
          f"collection left out), {compiles} compiles; engines {engines}",
          file=log)
    for k, c in checks.items():
        print(f"check {k} = {c['value']} (limit {c['limit']})", file=log)
    line["checks"] = checks
    return line
