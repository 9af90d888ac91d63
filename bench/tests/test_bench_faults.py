"""The check must fail a run whose timed path is broken underneath: once
for each fault a cell can have (one-chip cells; no exchange between chips
exists there)."""
import pytest

import smoke


@pytest.mark.parametrize("cell,generator,fault", [
    ("yi6b.grpo", "grpo", "frozen"),       # a step returns its state
    ("yi6b.grpo", "grpo", "half_batch"),   # half the batch left out
    ("yi6b.grpo", "grpo", "token"),        # tokens altered where produced
    ("yi6b-8l.rollout", "rollout", "token"),
])
def test_fault_is_not_correct(cell, generator, fault):
    rc, out, run = smoke.run_cell(cell, generator, faults=(fault,), seed=4321)
    assert rc == 0
    assert out["correct"] is False, out["checks"]
