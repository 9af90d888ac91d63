"""Each one-chip cell's whole run at a smoke size on the CPU: traffic,
generator, check and metric readers through the harness, with the look for a
chip skipped.  Asserts the contract's last line, never a speed."""
import json
import os
import subprocess
import sys

import pytest

import harness
import smoke

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the benchmark's cell, and the GRPO cell kept out of it (kept_out.json)
CELLS = [("yi6b.grpo", "grpo"), ("yi6b-8l.rollout", "rollout")]
# the kernel's roofline needs the Pallas kernel's name in a TPU trace
TPU_ONLY = {"paged_attention_roofline.rollout"}


def declared(cell, kind):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(smoke.KEPT_OUT) as f:
        extra = json.load(f)
    for key in ("workloads", "end_to_end", "per_layer"):
        bench[key] += extra[key]
    return {m["name"] for m in harness.metrics_for(bench, cell, kind)}


@pytest.mark.parametrize("cell,generator", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_reports_every_metric(cell, generator, trace):
    rc, out, run = smoke.run_cell(cell, generator, trace=trace)
    assert rc == 0
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    want = declared(cell, kind) - (TPU_ONLY if trace else set())
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    dev = out["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(out["breakdown"]["device_ops"]) <= 10
    assert set(out["checks"]) == set(run.limits)


def test_no_chip_exits_nonzero():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "yi6b.grpo", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
