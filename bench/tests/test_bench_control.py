"""The control (the plain reference computed in float8, one step below the
configuration's bfloat16, in the program's place) must fail the check's
limits; the program itself must pass them on the same run."""
import check
import pytest

import smoke


@pytest.mark.parametrize("cell,generator", [("yi6b.grpo", "grpo"),
                                         ("yi6b-8l.rollout", "rollout")])
def test_control_is_not_correct(cell, generator):
    rc, out, run = smoke.run_cell(cell, generator, control=True, seed=2024)
    assert rc == 0 and out["correct"] is True, out["checks"]
    held = check.held(run.control_readings, run.limits)
    assert not all(c["ok"] for c in held), held
