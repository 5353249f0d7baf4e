#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload wsi-morph-4k.seeded --seed 7 \\
        --seconds 10 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the plain
reference beside its limit.  The same numbers end standard error.  Without
a TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: no result: {e}", file=sys.stderr)
        return 3
    except Exception:  # noqa: BLE001 — no result line on any failure
        traceback.print_exc()
        print("bench: no result", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
