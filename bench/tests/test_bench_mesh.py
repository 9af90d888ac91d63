"""The GRPO generator on a 2x2 (data, model) mesh, at a smoke size on four
virtual CPU devices: the path of the kept-out cell ``yi6b-8l.grpo.2x2``
(PERF.md section 7).  JAX fixes its device count at start-up, so the run
is a child process, which holds no chip."""
import json
import os

import smoke

LIMITS = os.path.join(smoke.BENCH, "limits", "yi6b.grpo.json")


def test_grpo_on_a_2x2_mesh_is_correct():
    with open(LIMITS) as f:
        limits = json.load(f)["limits"]
    rc, out, run = smoke.run_cell(
        "yi6b-8l.grpo.2x2", "grpo", mesh=[2, 2], prompts_per_iteration=4,
        limits=limits,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert rc == 0
    assert out["device"]["count"] == 4
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"setup_s", "rl_mesh_tokens_per_s"}
