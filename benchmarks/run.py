"""Benchmark driver — one section per paper table/figure.

  Table 1  -> bench_dispatch       (sample-flow TCV + dispatch times)
  Figure 7 -> bench_e2e            (end-to-end variant throughput)
  Figure 9 -> bench_linearity      (cluster linearity, TD vs central)
  Figure 10-> bench_reshard_memory (allgather-swap memory release)
  kernels  -> bench_kernels        (fused-kernel micro-benchmarks)
  serving  -> bench_serving        (sync vs continuous-batching generation)
  sampling -> bench_sampling       (deterministic-sampling replay A/B)
  swap     -> bench_swap           (host-tier KV swap vs recompute preemption)
  Table 2  -> bench_partial_stream (partial rollout streams mid-drain)
  Fig. 11  -> bench_moe_scale      (400B-class MoE at production scale)
  roofline -> roofline_table       (renders benchmarks/results/*.json)

Sections whose ``run()`` returns a dict get a machine-readable artifact
``BENCH_<name>.json`` (``{"bench", "elapsed_s", "metrics"}``) written next
to the stdout tables — CI asserts on and uploads these; see
docs/observability.md for the schema.

``PYTHONPATH=src python -m benchmarks.run [section ...] [--out DIR]``
"""
from __future__ import annotations

import argparse
import json
import os
import time

SECTIONS = ["dispatch", "linearity", "reshard_memory", "kernels", "e2e",
            "serving", "sampling", "swap", "partial_stream", "moe_scale",
            "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("sections", nargs="*",
                    help=f"sections to run (default: all): {SECTIONS}")
    ap.add_argument("--out", default=".", metavar="DIR",
                    help="directory for BENCH_<name>.json artifacts")
    args = ap.parse_args()
    bad = [s for s in args.sections if s not in SECTIONS]
    if bad:
        ap.error(f"unknown section(s) {bad}; choose from {SECTIONS}")
    wanted = args.sections or SECTIONS
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    for name in wanted:
        mod = __import__(f"benchmarks.bench_{name}"
                         if name != "roofline" else "benchmarks.roofline_table",
                         fromlist=["run"])
        t0 = time.perf_counter()
        print(f"\n{'=' * 70}\n== {name}\n{'=' * 70}")
        result = mod.run()
        dt = time.perf_counter() - t0
        print(f"[{name}: {dt:.1f}s]")
        if isinstance(result, dict):
            path = os.path.join(args.out, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({"bench": name, "elapsed_s": dt,
                           "metrics": result}, f, indent=1, sort_keys=True)
            print(f"[{name}: wrote {path}]")


if __name__ == "__main__":
    main()
