"""The trace reduction: on a small trace recorded on a TPU v5e chip by
``record_trace.py`` (five jitted matrix products, each followed by a 10 ms
host sleep inside a ``bench.host_wait`` span), and on hand-made
intervals."""
import glob
import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    busy = xplane.union([(5, 8), (0, 2), (1, 3), (7, 9), (20, 30)], 0, 25)
    assert busy == [[0, 3], [5, 9], [20, 25]]
    assert xplane.gaps(busy, 0, 25) == [(3, 5), (9, 20)]
    assert xplane.gaps([], 0, 4) == [(0, 4)]


def test_innermost_span_names_the_gap():
    spans = [("bench.iteration", 0, 100), ("bench.generate", 10, 40),
             ("bench.window", 0, 100)]
    assert xplane._innermost(spans, 20) == "bench.generate"
    assert xplane._innermost(spans, 60) == "bench.iteration"
    assert xplane._innermost(spans, 200) == "none"


@pytest.fixture(scope="module")
def recorded():
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert paths, "the recorded trace is missing"
    return xplane.reduce(paths[0])


def test_recorded_trace_busy_and_idle(recorded):
    r = recorded
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # the trace has no bench.window span, so the window runs from the
    # first device op to the last: four of the 10 ms host sleeps lie in it
    idle = dict(r["idle_gaps"])
    assert idle["bench.host_wait"] >= 0.038
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)


def test_recorded_trace_device_ops(recorded):
    ops = recorded["device_ops"]
    assert 0 < len(ops) <= xplane.TOP
    assert all(v > 0 for _, v in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert sum(recorded["op_s"].values()) >= recorded["busy_s"] * 0.999
