"""Program spans beside the benchmark's: the sweep that names idle gaps,
self time of nested spans, device idle inside spans, on hand-made
intervals, on the recorded v5e trace and on a CPU trace of the program's
own tracer."""
import glob
import os
import time

import numpy as np
import pytest

import spans
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# one engine step as the program and the benchmark nest it
STEP = [("bench.window", 0, 100), ("bench.engine.step", 10, 90),
        ("serve.step", 12, 88), ("serve.admit", 14, 38),
        ("serve.prefill", 15, 30), ("serve.decode.wait", 40, 58),
        ("serve.retire", 60, 80), ("bench.submit_group", 92, 98)]


def test_gap_inside_retire_under_engine_step_is_named_retire():
    gaps = [(58.5, 59.5), (61, 63), (88.5, 89.5), (90.5, 91.5), (93, 95),
            (99, 99.5)]
    assert spans.idle_by_span(STEP, gaps) == {
        "serve.retire": 2, "serve.step": 1, "bench.engine.step": 1,
        "none": 1.5, "bench.submit_group": 2}


def test_program_and_benchmark_names_are_spans():
    for n in ("bench.engine.step", "serve.decode.wait", "stage.reward",
              "reshard.to_update", "iteration"):
        assert spans.is_span(n)
    for n in ("iterations", "while.2", "copy", "$python.py:12 step"):
        assert not spans.is_span(n)


@pytest.mark.parametrize("seed", range(4))
def test_sweep_matches_the_scan(seed):
    """The sweep gives the scan's name (``xplane._innermost``) at every
    instant, ties and shared ends included, over spans of several
    threads that overlap without nesting."""
    rng = np.random.default_rng(seed)
    sp = [("bench.window", 0, 1000)]
    for k in range(300):
        s = int(rng.integers(0, 1000))
        sp.append((f"s{k % 17}", s, s + int(rng.integers(0, 60))))
    points = sorted(rng.uniform(-5, 1005, 400).tolist()
                    + [float(x[1]) for x in sp[:50]]
                    + [float(x[2]) for x in sp[50:100]])
    by_start = sorted(sp, key=lambda x: x[1])
    assert spans.innermost(sp, points) == [
        xplane._innermost(by_start, t) for t in points]


def test_nested_self_time_and_idle_inside():
    threads = {"main": [("A", 0, 100), ("B", 10, 30), ("C", 15, 20),
                        ("D", 40, 60)],
               "worker": [("E", 5, 95)]}
    busy = [[0, 12], [25, 45]]
    t = spans.span_table(threads, busy, 0, 100)
    self_s = {n: round(r["self_s"] * 1e9, 6) for n, r in t.items()}
    assert self_s == {"A": 60, "B": 15, "C": 5, "D": 20, "E": 90}
    idle = {n: round(r["idle_s"] * 1e9, 6) for n, r in t.items()}
    assert idle == {"A": 68, "B": 13, "C": 5, "D": 15, "E": 63}
    assert t["A"]["count"] == 1 and t["A"]["s"] * 1e9 == pytest.approx(100)
    # clipped to a window: A keeps [0, 50], D [40, 50]; E [5, 50]
    t = spans.span_table(threads, busy, 0, 50)
    assert round(t["A"]["self_s"] * 1e9, 6) == 20
    assert round(t["D"]["s"] * 1e9, 6) == 10
    assert round(t["E"]["idle_s"] * 1e9, 6) == 18


@pytest.fixture(scope="module")
def recorded():
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert paths, "the recorded trace is missing"
    return paths[0]


def test_recorded_trace_gaps_named_as_before(recorded):
    """On the recorded v5e trace (benchmark spans only) the sweep names
    every gap as ``xplane.reduce`` does, and finds the same spans."""
    t = xplane.load(recorded)
    lo, hi = t["window"]
    dev = sorted(t["devices"])[0]
    busy = xplane.union(((s, e) for _, s, e in t["devices"][dev]), lo, hi)
    idle = spans.idle_by_span(t["spans"], xplane.gaps(busy, lo, hi))
    want = dict(xplane.reduce(recorded)["idle_gaps"])
    assert {n: v / 1e9 for n, v in idle.items()} == want
    threads = spans.host_spans(recorded)
    assert sorted(x for v in threads.values() for x in v) == sorted(
        t["spans"])
    table = spans.span_table(threads, busy, lo, hi)
    waits = table["bench.host_wait"]
    assert waits["count"] == 4
    assert 0.9 * waits["s"] <= waits["idle_s"] <= waits["s"]
    assert waits["self_s"] == pytest.approx(waits["s"])


def test_program_spans_in_a_cpu_trace(tmp_path):
    """The program's tracer mirrors its spans into a JAX profiler trace;
    ``host_spans`` finds them on one thread, and their self times add up
    to the outer span's time."""
    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tr = Tracer(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("serve.step"):
            with tr.span("serve.decode.launch"):
                y = f(x)
            with tr.span("serve.decode.wait"):
                np.asarray(y)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    threads = spans.host_spans(path)
    mine = [v for v in threads.values() if any(
        n == "serve.step" for n, _, _ in v)]
    assert len(mine) == 1
    names = sorted(n for n, _, _ in mine[0])
    assert names == ["serve.decode.launch", "serve.decode.wait", "serve.step"]
    lo = min(s for _, s, _ in mine[0])
    hi = max(e for _, _, e in mine[0])
    table = spans.span_table({"main": mine[0]}, [], lo, hi)
    assert sum(r["self_s"] for r in table.values()) == pytest.approx(
        table["serve.step"]["s"])
    assert table["serve.step"]["self_s"] >= 0.002
    assert all(r["idle_s"] == pytest.approx(r["s"]) for r in table.values())
