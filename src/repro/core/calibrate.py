"""One-time-per-device measured calibration profiles for the cost model
(DESIGN.md §2.8).

The analytic :class:`repro.solve.CostModel` prices engines in abstract
pixel-visit units with guessed constants; ROADMAP item 1 documents where
that goes wrong (BENCH_tiled.json: ``auto`` picking ``frontier`` on inputs
where the tiled engine measures 3-5x faster).  Following the MATCH line of
work (SNIPPETS.md §2) and the paper's own measured relative-device-speed
partitioning (Teodoro et al. 2012 §4), this module *measures* the model's
ingredients once per (device kind, code version) and persists them through
:mod:`repro.core.autotune_disk`:

* **transfer profile** — seconds per byte moved through HBM, swept over a
  grid of sizes and dtypes so the interpolation captures the bandwidth
  knee between cache-resident and memory-bound working sets;
* **dense-round profiles** — seconds per dense propagation round, per op
  and per dense engine (``sweep`` vs ``frontier``), over the size sweep;
* **drain profiles** — wall seconds per innermost tile drain for each
  tiled solver family (plain ``tiled``, Pallas dense, Pallas queued, host
  ``scheduler``, cooperative ``hybrid``), over block pixels; plus the
  **drain-grid curves** (per-drain seconds vs full-grid pixels per block
  size — queue compaction and block scatter touch the whole grid, so a
  drain at 1024^2 costs ~10x the same drain at the calibration grid) and
  the per-block-size **batch-factor curves** over ``drain_batch`` (the
  sign flips with block size: batching amortizes dispatch at 32^2 blocks
  and pays padded compute at 128^2 ones);
* **rounds-per-extent** — measured outer rounds divided by the grid
  extent, per op over seed density: the measured replacement for the
  analytic ``depth_est`` guess (rounds track the *spatial extent* of the
  propagation, not the inter-seed spacing — the root cause of the
  frontier-vs-tiled mispredictions);
* **hybrid_rel_speed** — the measured host-vs-device seconds-per-tile
  ratio seeding the hybrid engine's :class:`~repro.core.scheduler.
  ChunkPolicy` (the paper's measured relative-speed work partitioning).

:class:`repro.solve.MeasuredCostModel` interpolates these profiles
(endpoint-clamped *rates*, so extrapolation stays linear in work) and the
analytic model remains the cold-start fallback.  Calibration is explicit
(`benchmarks/calibrate.py`, ``--calibrate``, or :func:`run_calibration`):
a guard asserts it can never run inside a ``solve()`` call path, so
cold-start solves stay cheap.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import threading
import time
import warnings
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

PROFILE_VERSION = 2

# Families a drain profile can carry; names match EngineConfig.engine with
# the queued-kernel variant split out (it is a different innermost loop).
DRAIN_FAMILIES = ("tiled", "tiled-pallas", "tiled-pallas-queued",
                  "scheduler", "hybrid")

# Worker counts the scheduler/hybrid families are measured at (recorded in
# meta; their profiles are wall seconds per tile *at these counts*).
CAL_N_WORKERS = 2
CAL_N_DEVICE_WORKERS = 1


# ---------------------------------------------------------------------------
# Profile: one measured 1-D curve with clamped interpolation.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Profile:
    """Sorted measured points ``(x, y)`` with piecewise-linear lookup.

    Two lookups, both bounded by the measured endpoints:

    * :meth:`interp` — plain clamped interpolation of ``y`` (for bounded
      quantities: batch factors, density factors, rounds-per-extent).
    * :meth:`scaled` — interpolates the per-unit *rate* ``y/x`` (clamped)
      and multiplies back by ``x``: outside the measured range the cost
      keeps growing linearly in the work ``x`` instead of freezing at the
      endpoint ``y`` (a 3-D block is never priced like the biggest 2-D
      block that happened to be measured).
    """

    xs: Tuple[float, ...]
    ys: Tuple[float, ...]

    def __post_init__(self):
        if not self.xs or len(self.xs) != len(self.ys):
            raise ValueError("Profile needs matching non-empty xs/ys")
        if any(b <= a for a, b in zip(self.xs, self.xs[1:])):
            raise ValueError("Profile xs must be strictly increasing")

    @classmethod
    def from_points(cls, points: Sequence[Tuple[float, float]]) -> "Profile":
        """Sort and merge duplicate x (mean of their y)."""
        by_x: Dict[float, List[float]] = {}
        for x, y in points:
            by_x.setdefault(float(x), []).append(float(y))
        xs = sorted(by_x)
        return cls(tuple(xs), tuple(float(np.mean(by_x[x])) for x in xs))

    def interp(self, x: float) -> float:
        xs, ys = self.xs, self.ys
        if x <= xs[0]:
            return ys[0]
        if x >= xs[-1]:
            return ys[-1]
        i = int(np.searchsorted(xs, x)) - 1
        t = (x - xs[i]) / (xs[i + 1] - xs[i])
        return ys[i] + t * (ys[i + 1] - ys[i])

    def scaled(self, x: float) -> float:
        rates = Profile(self.xs, tuple(y / max(x_, 1e-12)
                                       for x_, y in zip(self.xs, self.ys)))
        return rates.interp(x) * x

    def to_list(self) -> List[List[float]]:
        return [[x, y] for x, y in zip(self.xs, self.ys)]

    @classmethod
    def from_list(cls, pts) -> Optional["Profile"]:
        try:
            return cls.from_points([(float(p[0]), float(p[1])) for p in pts])
        except (TypeError, ValueError, IndexError):
            return None


def _nested_to_json(d: Dict) -> Dict:
    return {k: (_nested_to_json(v) if isinstance(v, dict) else v.to_list())
            for k, v in d.items()}


def _nested_from_json(d: Any, depth: int) -> Dict:
    if not isinstance(d, dict):
        return {}
    if depth == 0:
        out = {}
        for k, v in d.items():
            p = Profile.from_list(v)
            if p is not None:
                out[k] = p
        return out
    return {k: _nested_from_json(v, depth - 1) for k, v in d.items()}


# ---------------------------------------------------------------------------
# CalibrationProfile: everything MeasuredCostModel interpolates.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CalibrationProfile:
    """The persisted measurement bundle (see module docstring for the
    meaning of each section).  All maps are keyed by registered op name;
    unprofiled ops fall back to the ``morph`` reference entries scaled by
    their OpSpec cost hints."""

    # op -> engine ("frontier"/"sweep") -> Profile(area px -> sec/round)
    dense_round: Dict[str, Dict[str, Profile]] = dataclasses.field(
        default_factory=dict)
    # op -> Profile(log10 density -> measured rounds / grid extent)
    rounds_per_extent: Dict[str, Profile] = dataclasses.field(
        default_factory=dict)
    # op -> family -> Profile(block px -> wall sec/drain)
    drain: Dict[str, Dict[str, Profile]] = dataclasses.field(
        default_factory=dict)
    # op -> Profile(log10 density -> per-drain factor vs the sparse regime)
    drain_density_factor: Dict[str, Profile] = dataclasses.field(
        default_factory=dict)
    # block px (str key) -> Profile(grid px -> sec/drain) on the reference
    # op: how per-drain cost grows with the *full grid* (queue compaction
    # and block scatter touch the whole grid every round, so a block's
    # drain at 1024^2 costs ~10x its drain at the 192^2 calibration grid).
    # Measured from round-capped tiled solves at the dense-knee sizes.
    drain_grid: Dict[str, Profile] = dataclasses.field(default_factory=dict)
    # block px (str key) -> Profile(drain_batch -> per-tile factor vs
    # drain_batch=1).  Keyed by block size because the sign flips: batching
    # amortizes per-drain dispatch at small blocks but pays padded compute
    # at large ones (measured: 0.6x at 32^2 vs 4.7x at 128^2 blocks).
    batch_factor: Dict[str, Profile] = dataclasses.field(default_factory=dict)
    # Profile(working-set bytes -> sec/byte): generic memory-bandwidth rate
    transfer: Optional[Profile] = None
    # op -> neighborhood size the op's profiles were measured at
    ref_n_offsets: Dict[str, int] = dataclasses.field(default_factory=dict)
    hybrid_rel_speed: Optional[float] = None
    round_overhead_s: float = 0.0
    recompile_s: float = 0.0
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "profile_version": PROFILE_VERSION,
            "dense_round": _nested_to_json(self.dense_round),
            "rounds_per_extent": _nested_to_json(self.rounds_per_extent),
            "drain": _nested_to_json(self.drain),
            "drain_density_factor": _nested_to_json(self.drain_density_factor),
            "drain_grid": _nested_to_json(self.drain_grid),
            "batch_factor": _nested_to_json(self.batch_factor),
            "transfer": self.transfer.to_list() if self.transfer else None,
            "ref_n_offsets": dict(self.ref_n_offsets),
            "hybrid_rel_speed": self.hybrid_rel_speed,
            "round_overhead_s": self.round_overhead_s,
            "recompile_s": self.recompile_s,
            "meta": dict(self.meta),
        }

    @classmethod
    def from_dict(cls, d: Any) -> Optional["CalibrationProfile"]:
        """Tolerant decode: None on version mismatch or non-dict input (a
        stale or foreign profile must fall back to analytic, not crash)."""
        if not isinstance(d, dict) or d.get("profile_version") != PROFILE_VERSION:
            return None
        prof = cls(
            dense_round=_nested_from_json(d.get("dense_round"), 1),
            rounds_per_extent=_nested_from_json(d.get("rounds_per_extent"), 0),
            drain=_nested_from_json(d.get("drain"), 1),
            drain_density_factor=_nested_from_json(
                d.get("drain_density_factor"), 0),
            drain_grid=_nested_from_json(d.get("drain_grid"), 0),
            batch_factor=_nested_from_json(d.get("batch_factor"), 0),
            transfer=Profile.from_list(d["transfer"])
            if d.get("transfer") else None,
            ref_n_offsets={k: int(v)
                           for k, v in (d.get("ref_n_offsets") or {}).items()
                           if isinstance(v, (int, float))},
            hybrid_rel_speed=d.get("hybrid_rel_speed"),
            round_overhead_s=float(d.get("round_overhead_s") or 0.0),
            recompile_s=float(d.get("recompile_s") or 0.0),
            meta=d.get("meta") if isinstance(d.get("meta"), dict) else {},
        )
        return prof

    @classmethod
    def from_analytic(cls, model, stats, tiles: Sequence[int],
                      unit: float = 1e-6) -> "CalibrationProfile":
        """The degenerate one-point profile: every curve sampled from the
        *analytic* model's own formulas at ``stats``'s area and the given
        tiles, scaled by ``unit`` seconds per pixel-visit.

        By construction, ``MeasuredCostModel`` over this profile agrees
        with the analytic model — cost(cfg) == unit * analytic cost(cfg) —
        for the dense engines and the db=1 tiled/scheduler configs at the
        sampled tiles.  The property test
        (tests/test_calibration.py) pins this, which pins the measured
        model's plumbing: no double-applied hint scaling, no lost terms.
        """
        op = stats.op_name or "morph"
        scale_t = stats.bytes_per_pixel / model.ref_bytes_per_pixel
        w = stats.round_cost_weight
        area = float(stats.area)
        dense = {op: {
            "frontier": Profile((area,), (unit * scale_t * area,)),
            "sweep": Profile((area,),
                             (unit * scale_t * area * model.sweep_penalty,)),
        }}
        drain: Dict[str, Profile] = {}
        for fam in ("tiled", "tiled-pallas", "scheduler"):
            pts = []
            for t in sorted(tiles):
                block = float((t + 2) ** stats.ndim)
                inner = block * t * model.vmem_discount
                if fam == "tiled":
                    y = w * (inner + model.tile_dispatch)
                elif fam == "tiled-pallas":
                    pen = model.interpret_penalty if model.interpret else 1.0
                    y = w * (inner * pen + model.tile_dispatch)
                else:
                    y = w * (inner * model.host_penalty + model.host_dispatch)
                pts.append((block, unit * (scale_t * block + y)))
            drain[fam] = Profile.from_points(pts)
        return cls(
            dense_round=dense,
            drain={op: drain},
            ref_n_offsets={op: stats.n_offsets},
            round_overhead_s=unit * model.round_overhead,
            recompile_s=unit * model.recompile_cost,
            meta={"interpret": model.interpret, "analytic": True},
        )


# ---------------------------------------------------------------------------
# solve() guard: calibration must never run inside a solve call path.
# ---------------------------------------------------------------------------

_SOLVE_DEPTH = threading.local()


@contextlib.contextmanager
def solve_guard() -> Iterator[None]:
    """Entered by ``repro.solve.solve`` for the duration of a call."""
    d = getattr(_SOLVE_DEPTH, "d", 0)
    _SOLVE_DEPTH.d = d + 1
    try:
        yield
    finally:
        _SOLVE_DEPTH.d = d


def in_solve() -> bool:
    return getattr(_SOLVE_DEPTH, "d", 0) > 0


# ---------------------------------------------------------------------------
# Lazy load / install of the current profile.
# ---------------------------------------------------------------------------

_UNSET = object()
_current: Any = _UNSET
_lock = threading.Lock()


def current_profile() -> Optional[CalibrationProfile]:
    """The process's calibration profile: memoized lazy load from the
    autotune disk cache (None when this (device, code version) has never
    been calibrated — the analytic fallback case)."""
    global _current
    with _lock:
        if _current is _UNSET:
            from repro.core import autotune_disk
            _current = CalibrationProfile.from_dict(
                autotune_disk.load_profile())
        return _current


def install_profile(profile: Optional[CalibrationProfile],
                    save: bool = False) -> None:
    """Set the process's profile (None reverts to analytic); ``save=True``
    also persists it through autotune_disk for future processes."""
    global _current
    with _lock:
        _current = profile
    if save and profile is not None:
        from repro.core import autotune_disk
        autotune_disk.store_profile(profile.to_dict())


def reset_profile_cache() -> None:
    """Forget the memoized profile so the next lookup re-reads disk
    (tests repoint ``REPRO_IWPP_CACHE_DIR`` per-case and need this)."""
    global _current
    with _lock:
        _current = _UNSET


def load_profile_json(path: str) -> Optional[CalibrationProfile]:
    """Decode a profile artifact written by ``benchmarks/calibrate.py``."""
    with open(path) as f:
        return CalibrationProfile.from_dict(json.load(f))


# ---------------------------------------------------------------------------
# The calibration bench itself.
# ---------------------------------------------------------------------------

def _timed(fn: Callable, warmup: int = 1, iters: int = 2) -> float:
    import jax
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(min(ts))


def _logd(density: float) -> float:
    return math.log10(max(density, 1e-9))


@contextlib.contextmanager
def _quiet():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        yield


def _measure_transfer(sizes: Sequence[int]) -> Profile:
    """Memory-bandwidth sweep: a fused shift+max pass (one propagation
    lane's traffic) over sizes x dtypes; x = working-set bytes."""
    import jax
    import jax.numpy as jnp
    pts = []
    step = jax.jit(lambda x: jnp.maximum(x, jnp.roll(x, 1, axis=0)))
    for size in sizes:
        for dtype in (np.int8, np.int32, np.float32):
            a = jnp.asarray(np.random.default_rng(0).integers(
                0, 100, (size, size)).astype(dtype))
            t = _timed(lambda a=a: step(a))
            nbytes = size * size * np.dtype(dtype).itemsize
            pts.append((nbytes, t / nbytes))
    # merge to sec/byte at each working-set size, then back to y=sec form
    rate = Profile.from_points(pts)
    return Profile(rate.xs, tuple(r * x for x, r in zip(rate.xs, rate.ys)))


def _measure_overheads() -> Tuple[float, float]:
    """(per-dispatch seconds, one trace+compile seconds)."""
    import jax
    import jax.numpy as jnp
    a = jnp.zeros((8, 8), jnp.int32)
    f = jax.jit(lambda x: x + 1)
    dispatch = _timed(lambda: f(a), warmup=2, iters=5)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 7)(a))
    compile_s = time.perf_counter() - t0
    return dispatch, max(compile_s - dispatch, dispatch)


def _pallas_drain_points(op, spec, state, tiles, interpret: bool,
                         queued: bool) -> List[Tuple[float, float]]:
    """Seconds per Pallas tile-solver call on real (T+2)-halo blocks cut
    from the workload state (the innermost drain of the tiled-pallas
    engines), per tile size."""
    import jax
    factory = spec.pallas_queue_solver if queued else spec.pallas_solver
    if factory is None:
        return []
    pts = []
    for t in tiles:
        side = t + 2
        block = jax.tree_util.tree_map(lambda x: x[..., :side, :side], state)
        max_iters = side * side
        solver = (factory(op, interpret, max_iters, None) if queued
                  else factory(op, interpret, max_iters))
        run = jax.jit(solver)
        sec = _timed(lambda: run(block), warmup=1, iters=1)
        pts.append((float(side ** 2), sec))
    return pts


def run_calibration(ops: Optional[Sequence[str]] = None,
                    smoke: bool = False,
                    save: bool = True,
                    interpret: Optional[bool] = None,
                    cal_size: Optional[int] = None,
                    dense_sizes: Optional[Sequence[int]] = None,
                    verbose: bool = False) -> CalibrationProfile:
    """Measure a full :class:`CalibrationProfile` on this device and
    (by default) install + persist it.

    ``smoke=True`` is the CI profile: tiny grids, morph-only for the
    host/hybrid/Pallas families — enough to exercise every measurement
    path and produce a structurally-complete artifact in well under a
    minute, not enough to trust the magnitudes.

    Raises ``RuntimeError`` when invoked (directly or indirectly) inside a
    ``solve()`` call: calibration is an explicit, one-time step — lazily
    triggering minutes of micro-benchmarks from a user's solve would
    violate the cold-start contract (the analytic model IS the cold-start
    path).
    """
    if in_solve():
        raise RuntimeError(
            "run_calibration() called inside a solve() call path; "
            "calibration is explicit (benchmarks/calibrate.py or the "
            "--calibrate bench flag) — solve() falls back to the analytic "
            "CostModel when no profile exists")
    from repro import solve as S
    from repro.core import autotune_disk
    from repro.kernels import resolve_interpret
    from repro.ops import get_op, list_ops

    interpret = resolve_interpret(interpret)

    def say(msg: str) -> None:
        if verbose:
            print(f"# calibrate: {msg}", flush=True)

    cal_size = cal_size or (96 if smoke else 192)
    dense_sizes = tuple(dense_sizes if dense_sizes is not None
                        else ((128,) if smoke else (256, 512, 1024)))
    tiles = (16, 32) if smoke else (32, 128)
    cap = 64

    prof = CalibrationProfile(
        transfer=None,
        meta={"device_kind": autotune_disk._device_kind(),
              "code_version": autotune_disk.code_version(),
              "interpret": interpret, "smoke": smoke,
              "cal_size": cal_size,
              "n_workers": CAL_N_WORKERS,
              "n_device_workers": CAL_N_DEVICE_WORKERS,
              "timestamp": time.time()})

    say(f"transfer sweep over {dense_sizes}")
    prof.transfer = _measure_transfer(tuple(dense_sizes) + (cal_size,))
    prof.round_overhead_s, prof.recompile_s = _measure_overheads()

    op_names = list(ops) if ops else [n for n in list_ops()
                                      if get_op(n).calibration_states]
    dense_pts: Dict[str, Dict[str, List]] = {}
    rc_pts: Dict[str, List] = {}
    drain_pts: Dict[str, Dict[str, List]] = {}
    dens_pts: Dict[str, List] = {}
    grid_pts: Dict[str, List] = {}
    batch_pts: Dict[str, List] = {}
    rel_speed: Optional[float] = None

    for op_name in op_names:
        spec = get_op(op_name)
        if spec.calibration_states is None:
            continue
        full_families = (op_name == "morph") or not smoke
        primary_spt: Dict[int, float] = {}
        # The first workload is the op's *primary* regime: it feeds every
        # per-drain curve.  Later workloads only contribute (density ->
        # rounds) points and the per-drain density factor vs the primary.
        for idx, (label, op, state) in enumerate(spec.calibration_states(
                cal_size)):
            primary = idx == 0
            stats = S.collect_input_stats(op, state)
            extent = max(stats.spatial)
            ld = _logd(stats.density)
            say(f"{op_name}/{label}: frontier solve at {cal_size}")
            with _quiet():
                res = {}

                def run_frontier(op=op, state=state, res=res):
                    out, res["st"] = S.solve(op, state, engine="frontier",
                                             interpret=interpret)
                    return out

                t_f = _timed(run_frontier, warmup=1, iters=1)
            st = res["st"]
            rounds = max(1, st.rounds)
            rc_pts.setdefault(op_name, []).append((ld, rounds / extent))
            if primary:
                dense_pts.setdefault(op_name, {}).setdefault(
                    "frontier", []).append((float(stats.area), t_f / rounds))
                prof.ref_n_offsets.setdefault(op_name, stats.n_offsets)
                # sweep rate: a few full-grid rounds suffice (same work/round)
                k = min(rounds, 6)
                with _quiet():
                    t_s = _timed(lambda: S.solve(op, state, engine="sweep",
                                                 max_rounds=k,
                                                 interpret=interpret)[0],
                                 warmup=1, iters=1)
                dense_pts[op_name].setdefault("sweep", []).append(
                    (float(stats.area), t_s / k))

            # tiled drains (plain XLA solver, sequential): sec per drain
            for t in tiles:
                with _quiet():
                    res = {}

                    def run_tiled(op=op, state=state, t=t, res=res):
                        out, res["st"] = S.solve(
                            op, state, engine="tiled", tile=t,
                            queue_capacity=cap, drain_batch=1,
                            interpret=interpret)
                        return out

                    t_t = _timed(run_tiled, warmup=1, iters=1)
                spt = t_t / max(1, res["st"].tiles_processed)
                block = float((t + 2) ** stats.ndim)
                if primary:
                    drain_pts.setdefault(op_name, {}).setdefault(
                        "tiled", []).append((block, spt))
                    primary_spt[t] = spt
                elif primary_spt.get(t):
                    # other regime: record the per-drain factor vs the
                    # primary regime instead of a new curve
                    dens_pts.setdefault(op_name, []).append(
                        (ld, spt / primary_spt[t]))
            if primary:
                dens_pts.setdefault(op_name, []).append((ld, 1.0))

            if not (primary and full_families):
                continue
            # host scheduler + cooperative hybrid: wall sec per tile at the
            # recorded worker counts
            t_big = tiles[-1]
            for fam, kw in (("scheduler", dict(engine="scheduler",
                                               tile=t_big,
                                               n_workers=CAL_N_WORKERS)),
                            ("hybrid", dict(engine="hybrid", tile=t_big,
                                            n_workers=CAL_N_WORKERS,
                                            n_device_workers=CAL_N_DEVICE_WORKERS,
                                            drain_batch=4))):
                say(f"{op_name}/{label}: {fam} at tile={t_big}")
                with _quiet():
                    res = {}

                    def run_fam(op=op, state=state, kw=kw, res=res):
                        out, res["st"] = S.solve(op, state,
                                                 interpret=interpret, **kw)
                        return out

                    t_w = _timed(run_fam, warmup=1, iters=1)
                drain_pts.setdefault(op_name, {}).setdefault(fam, []).append(
                    (float((t_big + 2) ** stats.ndim),
                     t_w / max(1, res["st"].tiles_processed)))

            say(f"{op_name}: pallas drain probes")
            for queued, fam in ((False, "tiled-pallas"),
                                (True, "tiled-pallas-queued")):
                try:
                    pts = _pallas_drain_points(op, spec, state, tiles,
                                               interpret, queued)
                except Exception as e:  # op without kernels: skip family
                    say(f"{op_name}: {fam} probe failed ({e!r})")
                    pts = []
                if pts:
                    drain_pts.setdefault(op_name, {}).setdefault(
                        fam, []).extend(pts)

        # dense-rate knee: a few rounds at each larger size (state build is
        # the expensive part; the rounds themselves are cheap)
        for sz in dense_sizes:
            if sz <= cal_size:
                continue
            _, op_sz, state_sz = spec.calibration_states(sz)[0]
            area = float(np.prod(
                np.asarray(S.tree_shape(state_sz, op_sz.ndim))))
            kr = 4
            say(f"{op_name}: dense-round rate at {sz}")
            with _quiet():
                t_r = _timed(lambda: S.solve(op_sz, state_sz,
                                             engine="frontier", max_rounds=kr,
                                             interpret=interpret)[0],
                             warmup=1, iters=1)
                t_w = _timed(lambda: S.solve(op_sz, state_sz, engine="sweep",
                                             max_rounds=kr,
                                             interpret=interpret)[0],
                             warmup=1, iters=1)
            dense_pts[op_name]["frontier"].append((area, t_r / kr))
            dense_pts[op_name]["sweep"].append((area, t_w / kr))

    # Per-drain grid scaling + batched-drain amortization, measured on the
    # reference op with *round-capped* tiled solves (a few outer rounds
    # time the steady per-drain rate without paying a full solve at every
    # size).  Both effects live outside the 192^2 full-solve regime the
    # drain curves were measured in: per-drain cost grows ~10x from the
    # calibration grid to 1024^2 (queue compaction + block scatter touch
    # the whole grid), and the batch factor flips sign with block size
    # (amortized dispatch at 32^2 blocks, padded compute at 128^2 ones) —
    # a single-point measurement gets one committed bench group right and
    # another one wrong.
    ref_op = "morph" if "morph" in op_names else (op_names[0] if op_names
                                                  else None)
    if ref_op is not None:
        spec_r = get_op(ref_op)
        ndim_r = spec_r.calibration_states(cal_size)[0][1].ndim
        grid_sizes = (cal_size,) + tuple(sz for sz in dense_sizes
                                         if sz > cal_size)
        batch_size = grid_sizes[-1] if smoke else min(grid_sizes[-1], 1024)
        kcap = 3    # outer rounds per capped timing

        def capped_spt(op, state, t, db):
            res = {}

            def run(op=op, state=state, t=t, db=db, res=res):
                out, res["st"] = S.solve(op, state, engine="tiled", tile=t,
                                         queue_capacity=cap, drain_batch=db,
                                         max_rounds=kcap, interpret=interpret)
                return out

            with _quiet():
                t_c = _timed(run, warmup=1, iters=1)
            return t_c / max(1, res["st"].tiles_processed)

        for sz in grid_sizes:
            say(f"{ref_op}: drain-grid sweep at {sz}")
            _, op_g, state_g = spec_r.calibration_states(sz)[0]
            area = float(sz ** ndim_r)
            for t in tiles:
                key = str(int((t + 2) ** ndim_r))
                grid_pts.setdefault(key, []).append(
                    (area, capped_spt(op_g, state_g, t, 1)))

        _, op_b, state_b = spec_r.calibration_states(batch_size)[0]
        spt4_small = None
        for t in tiles:
            say(f"{ref_op}: batch sweep at {batch_size}, tile={t}")
            key = str(int((t + 2) ** ndim_r))
            base = None
            for db in (1, 4, 8, 16):
                spt = capped_spt(op_b, state_b, t, db)
                if db == 1:
                    base = spt
                if db == 4 and t == tiles[0]:
                    spt4_small = spt
                batch_pts.setdefault(key, []).append((float(db), spt / base))
        # measured host-vs-device per-tile ratio (the ChunkPolicy seed):
        # host unit = scheduler wall-per-tile x its threads; device unit =
        # the batched tiled drain per tile.
        sched = drain_pts.get(ref_op, {}).get("scheduler")
        if sched and spt4_small:
            rel_speed = max(1.0, (sched[-1][1] * CAL_N_WORKERS) / spt4_small)

    prof.dense_round = {o: {e: Profile.from_points(p)
                            for e, p in fams.items()}
                        for o, fams in dense_pts.items()}
    prof.rounds_per_extent = {o: Profile.from_points(p)
                              for o, p in rc_pts.items()}
    prof.drain = {o: {f: Profile.from_points(p) for f, p in fams.items()}
                  for o, fams in drain_pts.items()}
    prof.drain_density_factor = {o: Profile.from_points(p)
                                 for o, p in dens_pts.items()}
    prof.drain_grid = {k: Profile.from_points(p)
                       for k, p in grid_pts.items()}
    prof.batch_factor = {k: Profile.from_points(p)
                         for k, p in batch_pts.items()}
    prof.hybrid_rel_speed = rel_speed

    if save:
        install_profile(prof, save=True)
    else:
        install_profile(prof)
    return prof
