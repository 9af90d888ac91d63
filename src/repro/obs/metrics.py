"""MetricsRegistry — named counters / gauges / histograms, snapshot() dict.

Aggregate accounting (totals + distributions), complementary to the
tracer's timeline: the tracer answers WHEN, the registry answers HOW MUCH.
Always on — every operation is a dict lookup plus an add/append, cheap
enough for the serving hot loop — and thread-safe under one lock.

Counters a program keeps on a device (``add_source``) are read when the
registry is snapshotted, not when they change, so keeping them costs the
hot loop no read.

``snapshot()`` is the machine-readable contract: a plain, JSON-serializable
dict with deterministically sorted keys, histograms summarized to
count/sum/mean/min/max + nearest-rank percentiles.  ``engine.stats()`` and
the ``BENCH_*.json`` artifacts are built from it.

Percentile definition (nearest-rank, the one documented in
docs/observability.md): pq over n sorted samples is the element at index
``ceil(q * n) - 1`` — the smallest sample >= q of the distribution.  No
interpolation, so every reported percentile is a value that actually
occurred.
"""
from __future__ import annotations

import math
import threading

PERCENTILES = (0.5, 0.9, 0.95, 0.99)


def nearest_rank(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile over an ASCENDING-sorted non-empty list."""
    idx = max(0, math.ceil(q * len(sorted_vals)) - 1)
    return sorted_vals[min(idx, len(sorted_vals) - 1)]


class MetricsRegistry:
    """Named counters (monotone ints), gauges (last/max value), histograms
    (raw observations, summarized at snapshot time)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}  # guarded-by: _lock
        self._gauges: dict[str, float] = {}  # guarded-by: _lock
        self._hists: dict[str, list[float]] = {}  # guarded-by: _lock
        self._sources: list = []   # [names, read, last totals]

    # -- write --------------------------------------------------------------
    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def set_max(self, name: str, value: float) -> None:
        """Gauge that only ratchets upward (e.g. max prefill tokens/step)."""
        with self._lock:
            if value > self._gauges.get(name, value - 1):
                self._gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._hists.setdefault(name, []).append(float(value))

    def add_source(self, names: tuple, read) -> None:
        """Counters ``names`` whose running totals ``read()`` returns, in
        the same order (a device accumulator, say, wrapping at 2**32); each
        ``snapshot()`` calls it once and adds what the totals grew by."""
        self._sources.append([tuple(names), read, [0] * len(names)])

    def _fold_sources(self) -> None:
        for src in self._sources:
            names, read, last = src
            now = [int(v) for v in read()]
            with self._lock:
                for name, a, b in zip(names, last, now):
                    self._counters[name] = (self._counters.get(name, 0)
                                            + (b - a) % 2**32)
            src[2] = now

    # -- read ---------------------------------------------------------------
    def value(self, name: str, default=0):
        """Current counter (or gauge) value; ``default`` when never set."""
        with self._lock:
            if name in self._counters:
                return self._counters[name]
            return self._gauges.get(name, default)

    def percentile(self, name: str, q: float):
        """Nearest-rank percentile of histogram ``name``; None if empty."""
        with self._lock:
            vals = self._hists.get(name)
            if not vals:
                return None
            return nearest_rank(sorted(vals), q)

    def summarize(self, name: str) -> dict:
        """Histogram summary dict (the snapshot shape); {} if unobserved."""
        with self._lock:
            vals = list(self._hists.get(name, ()))
        if not vals:
            return {}
        vals.sort()
        out = {
            "count": len(vals),
            "sum": float(sum(vals)),
            "mean": float(sum(vals) / len(vals)),
            "min": vals[0],
            "max": vals[-1],
        }
        for q in PERCENTILES:
            out[f"p{int(q * 100)}"] = nearest_rank(vals, q)
        return out

    def snapshot(self) -> dict:
        """Deterministic, JSON-serializable view of everything recorded:
        ``{"counters": {...}, "gauges": {...}, "histograms": {name:
        summary}}`` with sorted keys.  Repeated calls with no writes in
        between return equal dicts (pinned by tests)."""
        self._fold_sources()
        with self._lock:
            counters = dict(sorted(self._counters.items()))
            gauges = dict(sorted(self._gauges.items()))
            hist_names = sorted(self._hists)
        return {
            "counters": counters,
            "gauges": gauges,
            "histograms": {n: self.summarize(n) for n in hist_names},
        }

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
