#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, compiles from the checkout's cache, warm-up)
comes first, then a window of ``--seconds`` seconds, then the check of
what the window produced against the plain reference.  The last line of
standard output is one JSON object; ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  The run exits nonzero, and prints no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    import harness

    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
