"""The readers of the serving engine's counters and spans: on hand-made
windows, and on the window of a traced rollout run, whose counters hold
every counter of the engine's registry under its own name and whose idle
gaps are named by the program's spans."""
import pytest

import harness
import smoke
import spans

MS = 1e6                                   # trace nanoseconds in a ms


def _read(metric, window):
    return harness.reader(metric).read(window)


def test_counter_readers():
    w = {"counters": {"serve.steps": 40, "serve.launches": 340,
                      "serve.host_reads": 236, "serve.decode.kv_pages": 2048},
         "block_table": [8, 16]}
    assert _read("launches_per_step.rollout", w) == 8.5
    assert _read("host_reads_per_step.rollout", w) == 5.9
    assert _read("kv_pages_share.rollout", w) == 40.0
    for name in ("launches_per_step.rollout", "host_reads_per_step.rollout",
                 "kv_pages_share.rollout"):
        assert _read(name, {"counters": {"serve.steps": 0}}) is None
        assert _read(name, {"kind": "grpo", "iterations": [{}]}) is None


def _step(t0):
    """One engine step as the program and the benchmark nest its spans,
    then a group submitted, starting at ``t0`` ms; 110 ms in all."""
    rows = [("bench.engine.step", 0, 100), ("serve.step", 2, 98),
            ("serve.admit", 4, 40), ("serve.prefill", 5, 20),
            ("serve.first_token", 20, 35), ("serve.decode.prep", 41, 45),
            ("serve.decode.launch", 45, 50), ("serve.decode.wait", 50, 90),
            ("serve.retire", 90, 96), ("bench.submit_group", 100, 110),
            ("serve.submit", 102, 108)]
    return [(n, (t0 + s) * MS, (t0 + e) * MS) for n, s, e in rows]


def test_span_readers_on_a_synthetic_span_list():
    threads = {"main": [("bench.window", 0, 220 * MS)] + _step(0)
               + _step(110)}
    busy = [[s * MS, e * MS] for t0 in (0, 110)
            for s, e in ((t0 + 5, t0 + 20), (t0 + 45, t0 + 85))]
    table = spans.span_table(threads, busy, 0, 220 * MS)
    w = {"trace": {"spans": table}, "counters": {"serve.steps": 2}}
    # self time a step: step 96 - 91 covered, admit 36 - 30, prefill 15,
    # prep 4, launch 5, retire 6, submit 6; the two wait spans left out
    assert _read("host_self_ms.rollout", w) == pytest.approx(47.0)
    # idle a step inside admit (4-5, 20-40) and submit (all of its 6 ms)
    assert _read("admit_idle_ms.rollout", w) == pytest.approx(27.0)
    for name in ("host_self_ms.rollout", "admit_idle_ms.rollout"):
        assert _read(name, {"trace": {"spans": {}},
                            "counters": {"serve.steps": 2}}) is None
        assert _read(name, {"trace": None, "counters": {}}) is None


@pytest.fixture(scope="module")
def traced_rollout():
    return smoke.run_cell("yi6b-8l.rollout", "rollout", trace=1, seed=777)


def test_window_counters_hold_the_registry_names(traced_rollout):
    rc, out, run = traced_rollout
    assert rc == 0 and out["correct"] is True, out["checks"]
    c = run.counters
    for name in ("serve.steps", "serve.launches", "serve.host_reads",
                 "serve.decode.kv_pages", "serve.prefill_tokens",
                 "serve.shared_prefill_tokens", "serve.sampled.tokens",
                 "serve.submitted", "serve.finished"):
        assert c[name] > 0, name
    for short, name in (("steps", "serve.steps"),
                        ("prefill_tokens", "serve.prefill_tokens"),
                        ("sampled_tokens", "serve.sampled.tokens"),
                        ("finished", "serve.finished")):
        assert c[short] == c[name]


def test_traced_window_names_idle_gaps_by_program_spans(traced_rollout):
    rc, out, run = traced_rollout
    names = [n for n, _ in out["breakdown"]["idle_gaps"]]
    assert any(n.startswith("serve.") for n in names), names
    steps = run.spans["serve.step"]["count"]
    assert abs(steps - run.counters["serve.steps"]) <= 1
    for n in ("serve.admit", "serve.decode.launch", "serve.retire"):
        assert run.spans[n]["count"] == steps
        assert 0 <= run.spans[n]["idle_s"] <= run.spans[n]["s"]
