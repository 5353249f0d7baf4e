#!/usr/bin/env python3
"""Drive the IWPP solve and serve path once on a TPU and check its results.

    python3 chip_smoke.py                # one chip: engines + serving, 4096²
    python3 chip_smoke.py --chips 4      # four chips: sharded engines, 8192²
    python3 chip_smoke.py --tiny         # CPU rehearsal at a small size

Everything runs in this one process (a TPU belongs to one process at a
time) and every input is generated from ``--seed`` by ``repro.data.images``.

One chip: morphological reconstruction of a ``tissue_image`` at coverage
0.8 (the ``I - h`` marker and a ``seeded_marker``) and the EDT of a
``bg_disks`` image at coverage 0.9 run through ``solve()`` with the
``tiled-pallas`` engine (tile 64 and 128, drain batch 1 and 4), the
``hybrid`` engine with a Pallas device stream, and ``auto``.  Each result
must be a fixed point of the op.  Morph results must equal
``engine="frontier"`` bit for bit, and at ``--ref-size`` the sequential
reference ``reconstruct_fh``.  The 8-neighbour EDT fixed point depends on
the update order at isolated pixels (the synchronous frontier, the queued
engines and the sequential ``edt_wavefront`` each land within Danielsson's
bound of the exact EDT, not always on the same value), so EDT results are
held to that bound against the exact EDT (``scipy.ndimage``), the repo
tests' own EDT contract, and the pixels that differ from ``frontier`` and
from ``edt_wavefront`` are reported.  A ``tiled-pallas`` EDT must equal
``tiled`` (the XLA tile solver, same drain order) at the same tile and
drain batch exactly.  The same input state is read back
after every engine (buffer donation must never delete it).  Then an ``IwppService`` serves
morph and EDT requests from two tenants, and each result must equal
``run_op`` solo.

``--chips 4`` runs only the multi-chip phase: ``shard_map`` and
``shard_map-tiled`` on a 2x2 mesh at 8192², compared bit-exact with
single-chip ``tiled`` on device 0, and the output must be spread as one
quarter per chip.

Every line before the last is information (per-phase wall time, compile
time, peak device memory, each labelled with the device kind).  The last
line is ``{"ok": true, "device": {...}}`` only when every phase passed on a
TPU; otherwise the exit code is non-zero and no such line is printed.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))


class Smoke:
    """Runs named phases, prints one labelled line each, keeps failures."""

    def __init__(self, kind: str):
        self.kind = kind
        self.failures = []
        self.compile_s = collections.Counter()
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.compile_s["total"] += secs

    def say(self, msg: str) -> None:
        print(f"[{self.kind}] {msg}", flush=True)

    def phase(self, name: str, fn, *args):
        import jax
        self.say(f"phase {name}: start")
        c0, t0 = self.compile_s["total"], time.monotonic()
        try:
            out = fn(*args)
            status = "ok"
        except Exception as e:  # noqa: BLE001 — report every failed phase
            traceback.print_exc()
            self.failures.append(f"{name}: {e!r}")
            out, status = None, f"FAIL ({e!r})"
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        self.say(f"phase {name}: {status} wall={time.monotonic() - t0:.3f}s "
                 f"compile={self.compile_s['total'] - c0:.3f}s "
                 f"peak_bytes_in_use={peak}")
        return out


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _read_back(state, host_copy, label: str) -> None:
    """The caller's input state must survive every engine unchanged."""
    import numpy as np
    for k, v in host_copy.items():
        _check(np.array_equal(np.asarray(state[k]), v),
               f"{label}: input leaf {k!r} changed")


def _fixed_point(op, out) -> None:
    """One more dense round sourcing from every valid pixel changes nothing."""
    import jax.numpy as jnp
    _, f = op.round(out, jnp.asarray(out["valid"]))
    _check(not bool(jnp.any(f)), "result is not a fixed point")


def _exact_edt2(fg):
    import numpy as np
    from scipy import ndimage
    return np.round(ndimage.distance_transform_edt(fg) ** 2).astype(np.int64)


def _within_danielsson(d2, exact2, what: str) -> None:
    """tests/test_edt.py's EDT contract: never below the exact distance,
    at most 0.5 px above it, on at most 1% of the pixels."""
    import numpy as np
    d = np.sqrt(np.asarray(d2).astype(np.float64))
    e = np.sqrt(exact2.astype(np.float64))
    err = d - e
    _check((err >= -1e-9).all(), f"{what}: distance below the exact EDT")
    _check(err.max() <= 0.5, f"{what}: max error {err.max()} px")
    _check((err > 1e-9).mean() <= 0.01, f"{what}: over 1% approximate")


def make_inputs(size: int, seed: int):
    import numpy as np
    from repro.data.images import bg_disks, seeded_marker, tissue_image
    marker, mask = tissue_image(size, size, 0.8, seed=seed)
    seeded = seeded_marker(mask, n_seeds=max(8, size // 20), seed=seed)
    fg = bg_disks(size, size, 0.9, seed=seed)
    return {"morph/I-h": ("morph", (marker.astype(np.int32),
                                    mask.astype(np.int32))),
            "morph/seeded": ("morph", (seeded.astype(np.int32),
                                       mask.astype(np.int32))),
            "edt/bg_disks": ("edt", (fg,))}


def _state(op_name, raw):
    import jax.numpy as jnp
    from repro.ops import get_op
    spec = get_op(op_name)
    op = spec.make_op(None)
    return spec, op, spec.build_state(op, *(jnp.asarray(x) for x in raw))


def run_reference(smoke: Smoke, size: int, seed: int, interpret: bool):
    """frontier and tiled-pallas against the sequential references."""
    import numpy as np
    from repro.edt.ref import edt_wavefront
    from repro.morph.ref import reconstruct_fh
    from repro.solve import solve
    for name, (op_name, raw) in make_inputs(size, seed).items():
        t0 = time.monotonic()
        if op_name == "morph":
            ref = reconstruct_fh(raw[0].copy(), raw[1], 8).astype(np.int32)
        else:
            ref = edt_wavefront(raw[0], 8)[0]
            exact = _exact_edt2(raw[0])
            _within_danielsson(ref, exact, f"{name} edt_wavefront")
        smoke.say(f"reference {name} {size}²: host reference "
                  f"{time.monotonic() - t0:.3f}s")
        spec, op, state = _state(op_name, raw)
        for kw in (dict(engine="frontier"),
                   dict(engine="tiled-pallas", tile=64, drain_batch=4)):
            out, st = solve(op, state, **kw)
            got = np.asarray(spec.extract(op, out))
            _check(st.interpret is interpret, f"{name} {kw}: interpret="
                   f"{st.interpret}")
            _fixed_point(op, out)
            if op_name == "morph":
                _check(np.array_equal(got, ref), f"{name} {kw} != reference")
                verdict = "equal to reconstruct_fh"
            else:
                _within_danielsson(got, exact, f"{name} {kw}")
                verdict = (f"within Danielsson's bound; "
                           f"{int((got != ref).sum())} px differ from "
                           f"edt_wavefront, {int((got != exact).sum())} px "
                           "from the exact EDT")
            smoke.say(f"reference {name} {size}² {kw}: {verdict}; "
                      f"wall={st.wall_time_s:.3f}s")


ENGINE_RUNS = (
    dict(engine="tiled-pallas", tile=64, drain_batch=1),
    dict(engine="tiled-pallas", tile=64, drain_batch=4),
    dict(engine="tiled-pallas", tile=128, drain_batch=1),
    dict(engine="tiled-pallas", tile=128, drain_batch=4),
    dict(engine="hybrid", tile=128, drain_batch=4, n_device_workers=1,
         hybrid_pallas=True),
    dict(engine="auto"),
)


def run_engines(smoke: Smoke, name: str, op_name: str, raw, interpret: bool):
    """Every engine against frontier on one input; the input survives."""
    import numpy as np
    from repro.solve import solve
    spec, op, state = _state(op_name, raw)
    host_copy = {k: np.asarray(v).copy() for k, v in state.items()}
    exact = _exact_edt2(raw[0]) if op_name == "edt" else None
    out, st = solve(op, state, engine="frontier")
    base = np.asarray(spec.extract(op, out))
    _read_back(state, host_copy, f"{name} frontier")
    _fixed_point(op, out)
    if exact is not None:
        _within_danielsson(base, exact, f"{name} frontier")
    smoke.say(f"{name} frontier: rounds={st.rounds} wall={st.wall_time_s:.3f}s")
    for kw in ENGINE_RUNS:
        out, st = solve(op, state, **kw)
        got = np.asarray(spec.extract(op, out))
        _read_back(state, host_copy, f"{name} {kw}")
        _fixed_point(op, out)
        if exact is None:
            _check(np.array_equal(got, base), f"{name} {kw} != frontier")
            verdict = "equal to frontier"
        else:
            _within_danielsson(got, exact, f"{name} {kw}")
            verdict = (f"within Danielsson's bound, "
                       f"{int((got != base).sum())} px differ from frontier")
            if kw["engine"] == "tiled-pallas":
                # Same tile, same drain order: the Pallas kernel must give
                # the XLA tile solver's distances exactly.
                xla, _ = solve(op, state, **dict(kw, engine="tiled"))
                _check(np.array_equal(got, np.asarray(spec.extract(op, xla))),
                       f"{name} {kw} != tiled at the same tile")
                verdict += "; equal to tiled at the same tile"
        _check(st.interpret is interpret,
               f"{name} {kw}: SolveStats.interpret={st.interpret}")
        if st.engine == "hybrid":
            _check(st.device_tiles > 0,
                   f"{name} hybrid: device workers drained no tile")
            _check(not st.worker_errors,
                   f"{name} hybrid: worker errors {st.worker_errors}")
        _check(not st.incomplete, f"{name} {kw}: incomplete")
        smoke.say(f"{name} {kw}: {verdict}; engine={st.engine} tile={st.tile} "
                  f"drain_batch={st.drain_batch} rounds={st.rounds} "
                  f"tiles={st.tiles_processed} device_tiles={st.device_tiles} "
                  f"interpret={st.interpret} wall={st.wall_time_s:.3f}s")


def run_serving(smoke: Smoke, size: int, seed: int):
    """IwppService: six distinct requests, two tenants, equal to run_op."""
    import numpy as np
    from repro.ops import run_op
    from repro.serve import IwppService
    requests = []
    for i, s in enumerate((seed + 1, seed + 2, seed + 3)):
        inputs = make_inputs(size, s)
        requests.append(("morph", inputs["morph/I-h" if i % 2 else
                                         "morph/seeded"][1], f"tenant{i % 2}"))
        requests.append(("edt", inputs["edt/bg_disks"][1], f"tenant{(i + 1) % 2}"))
    svc = IwppService(engine="auto", start=False)
    try:
        futs = [svc.submit(op_name, raw, tenant=tenant)
                for op_name, raw, tenant in requests]
        svc.start()
        results = [f.result(timeout=900) for f in futs]
    finally:
        svc.close()
    smoke.say(f"serve stats: {svc.stats()}")
    for (op_name, raw, tenant), got in zip(requests, results):
        solo, st = run_op(op_name, *raw)
        _check(np.array_equal(np.asarray(got), np.asarray(solo)),
               f"serve {op_name} ({tenant}) != run_op solo")
        smoke.say(f"serve {op_name} {tenant}: equal to run_op solo "
                  f"(solo engine={st.engine} wall={st.wall_time_s:.3f}s)")


def run_mesh(smoke: Smoke, size: int, seed: int):
    """shard_map and shard_map-tiled on a 2x2 mesh vs single-chip tiled."""
    import jax
    import numpy as np
    from repro.solve import solve
    devices = jax.devices()
    _check(len(devices) == 4, f"need 4 devices, have {len(devices)}")
    spec, op, state = _state("morph", make_inputs(size, seed)["morph/I-h"][1])
    host_copy = {k: np.asarray(v).copy() for k, v in state.items()}
    out, st = solve(op, state, engine="tiled", tile=128)
    _check(out["J"].devices() == {devices[0]}, "single-chip run left device 0")
    base = np.asarray(out["J"])
    smoke.say(f"mesh tiled (device 0): rounds={st.rounds} "
              f"tiles={st.tiles_processed} wall={st.wall_time_s:.3f}s")
    quarter = (size // 2, size // 2)
    for engine in ("shard_map", "shard_map-tiled"):
        out, st = solve(op, state, engine=engine, devices=devices, tile=128)
        _read_back(state, host_copy, engine)
        J = out["J"]
        shards = J.addressable_shards
        _check({s.device for s in shards} == set(devices),
               f"{engine}: output on {[s.device for s in shards]}")
        _check(all(s.data.shape == quarter for s in shards),
               f"{engine}: shard shapes {[s.data.shape for s in shards]}")
        _check(np.array_equal(np.asarray(J), base),
               f"{engine} != single-chip tiled")
        smoke.say(f"mesh {engine}: equal to single-chip tiled; one "
                  f"{quarter} quarter on each of {len(shards)} chips; "
                  f"rounds={st.rounds} tiles={st.tiles_processed} "
                  f"wall={st.wall_time_s:.3f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh phase")
    ap.add_argument("--size", type=int, default=None,
                    help="input side (default 4096, 8192 with --chips 4)")
    ap.add_argument("--ref-size", type=int, default=None,
                    help="side for the sequential-reference check "
                         "(default 1024)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at a small size; never ends ok")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.core.compile_cache import enable_persistent_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package next to this "
              f"script: {e!r}", file=sys.stderr)
        return 2
    cache_dir = enable_persistent_cache()
    import jax
    from repro.kernels import default_interpret

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print("device " + json.dumps(device), flush=True)
    if d0.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: JAX found no TPU (platform {d0.platform!r})",
              file=sys.stderr)
        return 1

    size = args.size or ((64 if args.chips == 4 else 256) if args.tiny
                         else (8192 if args.chips == 4 else 4096))
    ref_size = args.ref_size or (64 if args.tiny else 1024)
    smoke = Smoke(d0.device_kind)
    smoke.say(f"size={size} seed={args.seed} compile_cache={cache_dir}")
    interpret = default_interpret()
    if args.chips == 4:
        smoke.phase(f"mesh-2x2/{size}", run_mesh, smoke, size, args.seed)
    else:
        smoke.phase(f"reference/{ref_size}", run_reference, smoke, ref_size,
                    args.seed, interpret)
        t0 = time.monotonic()
        inputs = make_inputs(size, args.seed)
        smoke.say(f"inputs {size}²: generated in {time.monotonic() - t0:.3f}s")
        for name, (op_name, raw) in inputs.items():
            smoke.phase(f"engines/{name}/{size}", run_engines, smoke, name,
                        op_name, raw, interpret)
        smoke.phase(f"serve/{size}", run_serving, smoke, size, args.seed)

    if smoke.failures:
        for f in smoke.failures:
            print(f"FAILED {f}", file=sys.stderr)
        return 1
    if d0.platform != "tpu":
        print("chip_smoke: rehearsal passed; not a TPU run", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
