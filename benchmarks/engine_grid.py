#!/usr/bin/env python3
"""Warm seconds per call of each tile engine configuration, and of ``auto``,
on whole-slide tiles and on the other ops' inputs, with a check of every
result.

    python3 benchmarks/engine_grid.py                          # on a TPU
    python3 benchmarks/engine_grid.py --side 128 --side3 32    # CPU rehearsal

Inputs, all made from ``--seed``:

* the tiles of the ``bench/`` cells (``bench/generate.make_pool``) at
  ``--side``: every tile of the ``seeded`` and ``disks`` pools and the
  ``ih`` tiles at coverage 0.25 and 1.0;
* the other ops' inputs of ``repro.ops.workloads``: ``label`` and
  ``fill_holes`` at ``--side``, a 3-D ``morph`` and ``edt`` volume at
  ``--side3``.

Configurations: ``tiled`` T=32 K=4, ``tiled-pallas`` at every compiled tile
of the input's rank (``repro.solve.COMPILED_PALLAS_TILES``) with K=1 and 4,
``frontier``, and ``auto`` (``--engines`` keeps a subset; the XLA ``tiled``
drain does not finish ``label`` at 4096² in 15 minutes).  Each runs once to compile, then is timed warm
(each call ended by ``block_until_ready``).  Each result is checked:
morph, label and fill_holes must equal ``frontier``'s (a unique fixed
point); EDT is held to Danielsson's bound against the exact EDT (no
distance below it, at most 0.5 px above it on at most 1% of the pixels).
A configuration that raises is recorded with its error.

The record holds, per input, the :class:`~repro.solve.InputStats` the cost
model sees, each configuration's seconds, counters and check, the fastest
fixed configuration (``winner``) and what ``auto`` ran.
``benchmarks/ENGINE_GRID_v5e.json`` is this record from a TPU v5e; the
compiled-backend constants of ``repro.solve.CostModel`` are fitted to it and
``tests/test_interpret.py`` replays it against the model.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")]

# (cell's traffic, configuration file, indices of the pool's tiles)
POOLS = [("seeded", "wsi-morph-4k", (0, 1, 2, 3)),
         ("disks", "wsi-edt-4k", (0, 1, 2, 3)),
         ("ih", "wsi-morph-4k", (0, 3))]
# (input name, repro.ops.workloads builder, its arguments after side, seed)
OP_INPUTS = [("fill_holes", "fill_state", (0.5,), 2),
             ("morph3d", "morph_state3d", (), 3),
             ("edt3d", "edt_state3d", (), 3),
             ("label", "label_state", (0.55,), 2)]
ENGINES = ("frontier", "tiled", "tiled-pallas", "auto")


def configs(ndim: int):
    """(engine, tile, drain batch) of the grid for one spatial rank; tile
    None = a dense engine.  ``frontier`` first: it is the exact ops'
    reference."""
    from repro.solve import COMPILED_PALLAS_TILES
    return ([("frontier", None, None), ("tiled", 32, 4)]
            + [("tiled-pallas", t, k) for nd, t in COMPILED_PALLAS_TILES
               if nd == ndim for k in (1, 4)])


def label_of(engine, tile, k):
    return engine if tile is None else f"{engine}/T{tile}/K{k}"


def _time(call, budget_s: float):
    """Seconds of each warm call: one untimed call first, then at least one
    timed, up to three while they stay inside ``budget_s``."""
    import jax
    out, st = call()
    jax.block_until_ready(out)
    ts = []
    while len(ts) < 3 and (not ts or sum(ts) + ts[-1] <= budget_s):
        t0 = time.perf_counter()
        out, st = call()
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return ts, st, out


def _exact_edt2(state):
    """Exact squared EDT of an EDT state's foreground (every pixel valid):
    background pixels start at their own coordinates, foreground at
    ``SENTINEL``."""
    import numpy as np
    from scipy import ndimage
    from repro.edt.ops import SENTINEL
    fg = np.asarray(state["vr"][0]) == SENTINEL
    return np.round(ndimage.distance_transform_edt(fg) ** 2).astype(np.int64)


def _check(op_name, got, ref) -> dict:
    """Exact ops: pixels that differ from ``frontier``'s.  EDT: Danielsson's
    bound against the exact squared EDT."""
    import numpy as np
    got = np.asarray(got)
    if op_name != "edt":
        mismatch = int(np.sum(got != ref))
        return {"mismatch_px": mismatch, "ok": mismatch == 0}
    excess = np.sqrt(got.astype(np.float64)) - np.sqrt(ref.astype(np.float64))
    out = {"below_exact_px": int(np.sum(excess < -1e-9)),
           "max_excess_px": float(excess.max()),
           "approx_pct": float(100.0 * np.mean(excess > 1e-9))}
    out["ok"] = (out["below_exact_px"] == 0 and out["max_excess_px"] <= 0.5
                 and out["approx_pct"] <= 1.0)
    return out


def _save(record: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def _inputs(which: str, side: int, side3: int, seed: int):
    """Yield (name, op, state) for the chosen inputs: the other
    ops' first (each is built only when reached)."""
    from repro.ops import get_op, workloads
    if which in ("all", "ops"):
        for name, builder, extra, nd in OP_INPUTS:
            op, state = getattr(workloads, builder)(
                side if nd == 2 else side3, *extra, seed=seed)
            yield name, op, state
    if which in ("all", "bench"):
        import generate
        from loader import BENCH_DIR, load_json, load_module
        for traffic, config_name, picks in POOLS:
            config = load_json(BENCH_DIR / "configs" / f"{config_name}.json")
            opmod = load_module(BENCH_DIR / "ops" / f"{config['op']}.py")
            traffic_json = load_json(BENCH_DIR / "traffic" / f"{traffic}.json")
            traffic_json["order"] = "listed"     # index i is coverage i
            pool = generate.make_pool(traffic_json, side, seed)
            spec = get_op(config["op"])
            op = spec.make_op(config["connectivity"])
            for i in picks:
                cov = traffic_json["coverages"][i]
                yield (f"{traffic}-{cov}", op,
                       spec.build_state(op, *opmod.inputs(pool[i])))
            del pool


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--side", type=int, default=4096)
    ap.add_argument("--side3", type=int, default=256)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 14001)
    ap.add_argument("--inputs", choices=("all", "bench", "ops"),
                    default="all")
    ap.add_argument("--engines", type=lambda s: s.split(","),
                    default=list(ENGINES),
                    help=f"comma-separated subset of {','.join(ENGINES)}")
    ap.add_argument("--budget", type=float, default=6.0,
                    help="seconds of timed calls per configuration and input")
    ap.add_argument("--out", default="engine_grid.json")
    args = ap.parse_args(argv)

    from repro.core.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    import jax
    from repro.ops import spec_for
    from repro.solve import collect_input_stats, solve

    dev = jax.devices()[0]
    record = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "side": args.side, "side3": args.side3, "seed": args.seed,
              "inputs": []}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for name, op, state in _inputs(args.inputs, args.side, args.side3,
                                      args.seed):
        spec = spec_for(op)
        stats = collect_input_stats(op, state)
        op_name = stats.op_name
        row = {"name": name, "stats": dataclasses.asdict(stats), "runs": {}}
        ref = _exact_edt2(state) if op_name == "edt" else None
        record["inputs"].append(row)
        for engine, tile, k in configs(stats.ndim) + [("auto", None, None)]:
            if engine not in args.engines:
                continue
            lab = label_of(engine, tile, k)
            # the queue capacity auto's candidates give this tile
            kw = ({} if tile is None else
                  {"tile": tile, "drain_batch": k, "queue_capacity":
                   min(max(4, stats.n_tiles(tile)), 256)})
            try:
                ts, st, out = _time(
                    lambda: solve(op, state, engine=engine, **kw), args.budget)
                got = jax.device_get(spec.extract(op, out))
                del out
                if ref is None:          # frontier runs first
                    ref = got
                run = {"seconds": ts, "median_s": statistics.median(ts),
                       "engine": st.engine, "tile": st.tile,
                       "drain_batch": st.drain_batch, "rounds": st.rounds,
                       "tiles_processed": st.tiles_processed,
                       "check": _check(op_name, got, ref)}
            except Exception as e:  # noqa: BLE001 — recorded, grid goes on
                run = {"error": f"{type(e).__name__}: {e}"[:2000]}
            row["runs"][lab] = run
            _save(record, args.out)     # a cut run keeps what it measured
            print(f"[{dev.device_kind}] {name} {lab}: "
                  + (run["error"].splitlines()[0] if "error" in run else
                     f"{[round(t, 4) for t in run['seconds']]} s, rounds "
                     f"{run['rounds']}, drains {run['tiles_processed']}, "
                     f"check {run['check']}"), flush=True)
        fixed = {n: r for n, r in row["runs"].items()
                 if n != "auto" and "error" not in r}
        row["winner"] = min(fixed, key=lambda n: fixed[n]["median_s"])
        a = row["runs"].get("auto", {"error": "not run"})
        row["auto"] = ("error" if "error" in a else
                       label_of(a["engine"], a["tile"], a["drain_batch"]))
        print(f"[{dev.device_kind}] {name} winner {row['winner']}, auto ran "
              f"{row['auto']}", flush=True)
        _save(record, args.out)
        del state, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
