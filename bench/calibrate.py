#!/usr/bin/env python3
"""Readings for the correctness limits, outside the benchmark's own runs.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 4 \\
        [--control] [--fault frozen|half_batch|token] [--out FILE]

Runs the cell's whole run once per seed in this one process (the compile
cache is shared), and prints one JSON line per seed with the numbers the
check compares: ``readings`` (the program against the reference), and with
``--control`` also ``control`` (the reference computed in float8 in the
program's place, against the reference).  ``--fault`` plants a fault in
the timed path.  Limits are set from these readings (PERF.md).
"""
import argparse
import contextlib
import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        cap = io.StringIO()
        ov = {"control": args.control, "faults": tuple(args.fault)}
        kept = []
        with contextlib.redirect_stdout(cap):
            rc = harness.main(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", str(args.seconds),
                               "--trace", "0"], overrides=ov, keep=kept)
        r = kept[0]
        line = cap.getvalue().strip().splitlines()[-1]
        row = {"workload": args.workload, "seed": seed, "rc": rc,
               "faults": args.fault, "readings": r.readings,
               "control": r.control_readings,
               "result": json.loads(line),
               "s": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
