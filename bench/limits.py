#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/limits.py --workload pk_collab_c10k --seeds 1,2,3 \
        --seconds 10

For each seed, in one process on the chip: the cell's set-up and a window
of ``--seconds`` at the cell's own size and load, then every number the
cell compares, read twice at the same requests: once for the program (the
lower reading) and once for the control, the reference computed in
float32 (the upper reading). One JSON line per seed on standard output.
The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness
    harness.compile_cache(ROOT)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             t_start=t_start, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"], "device": r["device"],
                          "program": {k: c["value"]
                                      for k, c in r["check"].items()},
                          "control": r["control"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
