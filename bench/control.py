#!/usr/bin/env python3
"""Readings that the comparison's limits are set from, on the chip.

    python3 bench/control.py --workload wsi-edt-4k.disks \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 1 2 3

For each ``--seeds`` seed, one pass of the cell's pool at its own size goes
through the timed path (``run_op`` at its defaults) and every result is
compared with the plain reference; for each ``--control-seeds`` seed, the
lower-precision control (``bench/ops/<op>.py: control``) takes the timed
path's place.  One JSON line per seed gives the worst of each number over
the pool, as a run reports it; the last line gives the program's largest
(the lower reading) and the control's smallest (the upper reading).  The
benchmark's own runs never run the control.
"""

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    config = cell.config
    opmod = harness.load_module(harness.BENCH_DIR / "ops" / f"{config['op']}.py")
    harness.enable_compile_cache()
    import jax
    import generate
    harness.check_device(jax.devices(), cell.chips,
                         harness.load_json(harness.BENCH_DIR / "peaks.json"))
    program = harness.default_solver(config, opmod)
    runs = [("program", s, lambda t: program(t)[0]) for s in args.seeds]
    runs += [("control", s, lambda t: opmod.control(t, config))
             for s in args.control_seeds]
    readings = {"program": {}, "control": {}}
    for who, seed, solve in runs:
        t0 = time.monotonic()
        pool = generate.make_pool(cell.traffic, config["side"], seed)
        worst = {}
        for tile in pool:
            out = jax.block_until_ready(solve(tile))
            for k, v in opmod.compare(out, opmod.reference(tile, config)).items():
                worst[k] = max(worst.get(k, v), v)
            del out
        print(json.dumps({"who": who, "seed": seed, "numbers": worst,
                          "seconds": time.monotonic() - t0}), flush=True)
        for k, v in worst.items():
            readings[who].setdefault(k, []).append(v)
    print(json.dumps({
        "workload": args.workload, "limits": config["limits"],
        "lower": {k: max(v) for k, v in readings["program"].items()},
        "upper": {k: min(v) for k, v in readings["control"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
