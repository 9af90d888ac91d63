#!/usr/bin/env python3
"""Run one cell several times, each run its own process, and report each
end-to-end metric's spread: the distance between the first and third
quartiles (``statistics.quantiles(values, n=4)``) as a share of the median.

    python bench/spread.py --workload <cell> --seconds 20 \\
        --seeds 1,2,3,4,5,6 [--seeds 1,2,3,4,5,6] [--out FILE]

Each ``--seeds`` list is one set; the same seeds in two sets give the two
sets the bounds are set from.  Every run's last line goes to ``--out``.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", action="append", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sets = []
    for k, seeds in enumerate(args.seeds):
        rows = []
        for seed in seeds.split(","):
            p = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 args.workload, "--seed", seed, "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True, cwd=os.path.dirname(HERE))
            lines = p.stdout.strip().splitlines()
            row = {"set": k, "seed": int(seed), "rc": p.returncode,
                   "result": json.loads(lines[-1]) if lines else None,
                   "notes": [ln for ln in p.stderr.splitlines()
                             if ln.startswith(("bench iteration",
                                               "bench: compiles",
                                               "bench stream part",
                                               "bench host"))],
                   "stderr_tail": p.stderr[-2000:] if p.returncode else ""}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
        sets.append(rows)
    for k, rows in enumerate(sets):
        ok = [r["result"] for r in rows if r["result"]]
        names = sorted({n for r in ok for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in ok if n in r["metrics"]]
            if len(vals) >= 2:
                print(f"set {k} {n}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals)!r} runs {len(vals)} "
                      f"correct {sum(r['correct'] for r in ok)}/{len(rows)}",
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
