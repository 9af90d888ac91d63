"""Smoke-size overrides that let a cell's whole run go through on the CPU:
the same code paths, with widths, batch and stream cut down.  Only the
tests use them."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

# widths a CPU test can hold, with the initialisation widened so that the
# logits have the spread they have at the cells' sizes (std about 1.3):
# the gaps the check reads then land near the chip's, on either side of
# the limits
SMOKE_CONFIG = {"hidden_size": 1024, "intermediate_size": 2048,
                "num_attention_heads": 8, "num_key_value_heads": 1,
                "num_hidden_layers": 2, "vocab_size": 8192,
                "eos_token_id": 300, "initializer_range": 0.04}
SMOKE_TRAFFIC = {
    "grpo": {"prompts_per_iteration": 2, "generations": 4,
             "prompt_len": [6, 12], "prompt_pad": 12, "response_len": 6},
    "rollout": {"slots": 6, "groups_in_flight": 3, "group_size": 4,
                "prompt_len": [16, 40],
                "max_new": {"median": 8, "sigma": 1.0, "min": 2, "max": 32},
                "fill_groups": 1, "check_requests": 8},
}
# a window long enough that requests finish in it on a loaded CPU, and
# that the check compares some hundred served tokens
SMOKE_SECONDS = {"grpo": 0.5, "rollout": 4.0}


def overrides(generator: str, mesh=None, faults=(), control=False,
              **traffic) -> dict:
    t = dict(SMOKE_TRAFFIC[generator], **traffic)
    if generator == "grpo":
        t["mesh"] = mesh
    return {"config": dict(SMOKE_CONFIG), "traffic": t, "faults": faults,
            "control": control}


def run_cell(cell: str, generator: str, *, seed=1234, seconds=None, trace=0,
             limits=None, env=None, bench_dir=None, bench=None, **kw):
    """Runs the harness on the CPU in a child process (so that nothing it
    sets in JAX reaches the other tests of the worker), with the look for a
    chip skipped; returns (exit code, last stdout line parsed, the run's
    ``limits``, ``readings`` and ``control_readings`` and its window's
    ``counters`` and trace ``spans`` as attributes).  ``bench_dir`` runs
    another copy of the benchmark, ``bench`` names its cells and metrics
    (``BENCHMARK.json`` with the kept-out cells where not given)."""
    ov = overrides(generator, **kw)
    seconds = SMOKE_SECONDS[generator] if seconds is None else seconds
    if limits is not None:
        ov["limits"] = limits
    code = CHILD.format(paths=[bench_dir or BENCH, SRC], ov=json.dumps(ov),
                        kept=KEPT_OUT, bench=json.dumps(bench), argv=[
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace)], cpu_peaks=bool(trace))
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    # one compute thread: the child shares the machine with the other test
    # workers, some of which time their own work
    env["XLA_FLAGS"] = " ".join(filter(None, (
        env.get("XLA_FLAGS"), "--xla_cpu_multi_thread_eigen=false",
        "intra_op_parallelism_threads=1")))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    found = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    if p.returncode != 0 or not found:
        raise RuntimeError(f"harness child failed: {p.stderr[-3000:]}")
    res = json.loads(found[-1][len("RESULT "):])
    return res["rc"], res["out"], types.SimpleNamespace(**res["run"])


BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(os.path.dirname(BENCH), "src")
KEPT_OUT = os.path.join(BENCH, "tests", "kept_out.json")
CHILD = r"""
import contextlib, io, json, sys
sys.path[:0] = {paths!r}
import harness, peaks
bench = json.loads({bench!r})
if bench is None:
    bench, extra = harness.benchmark(), json.load(open({kept!r}))
    for key in ("workloads", "end_to_end", "per_layer"):
        bench[key] += extra[key]
if {cpu_peaks!r}:
    # a stand-in peak for the CPU backend, so the readers that divide by
    # a peak have one; the numbers are never reported as a device's
    peaks.PEAKS["cpu"] = {{"bf16_flops_per_s": 1e12,
                           "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
                           "source": "test stand-in"}}
kept, buf = [], io.StringIO()
with contextlib.redirect_stdout(buf):
    rc = harness.main({argv!r}, require_chip=False,
                      overrides=json.loads({ov!r}), keep=kept, bench=bench)
lines = buf.getvalue().strip().splitlines()
r = kept[0]
w = r.window or {{}}
print("RESULT " + json.dumps({{
    "rc": rc, "out": json.loads(lines[-1]) if lines else None,
    "run": {{"limits": r.limits, "readings": r.readings,
             "control_readings": r.control_readings,
             "counters": w.get("counters"),
             "spans": (w.get("trace") or {{}}).get("spans")}}}}))
"""
