"""Structured tracer with a Chrome-trace / Perfetto JSON exporter.

Event model (a subset of the Trace Event Format that Perfetto renders):

  * span    — a named interval (``ph: "X"`` complete event, ``ts`` + ``dur``
    in microseconds).  Recorded when the span EXITS, so nested spans appear
    after their children in the raw list; the exporter sorts by ``ts``,
    which restores timeline order (Perfetto reconstructs nesting from
    interval containment per track).
  * instant — a point event (``ph: "i"``, thread scope).
  * counter — a sampled multi-series value (``ph: "C"``); Perfetto draws
    each distinct counter name as its own track with one line per series.

Clock: ``time.perf_counter_ns`` relative to the tracer's construction, so
``ts`` is monotonic, immune to wall-clock steps, and starts near zero
(Perfetto's viewport opens on the data).  ``pid`` is always 0 (one-process
system); ``tid`` is a small dense alias of the Python thread ident, assigned
in first-use order so the main thread is track 0.

Profiler mirroring: an enabled span also enters a
``jax.profiler.TraceAnnotation`` of the same name (the args it holds at
entry become the annotation's stats), so a JAX profiler trace taken
meanwhile holds the span in its ``.xplane.pb`` on the clock the device
operations use.  The profiler's host clock is the wall clock
(``time.time_ns``); the exporter writes the tracer's epoch on it as
``profilerEpochNs``, so a span's ``ts * 1000 + profilerEpochNs`` is its
start on that clock (an xplane's event starts are offsets from its
``profile_start_time``).

Disabled mode is the contract the serving hot loop relies on: ``span()``
returns a module-level singleton null context (no allocation), ``instant``/
``counter`` return before touching any state, nothing is ever appended and
no profiler annotation is created — ``tests/test_obs.py`` pins these
properties with counting probes.
"""
from __future__ import annotations

import json
import threading
import time

from jax.profiler import TraceAnnotation


class _NullSpan:
    """Singleton no-op context manager returned by a disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """Live span: times its ``with`` body, records one complete event, and
    mirrors the body into the profiler as an annotation of the same name."""
    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str, args):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args           # caller may still mutate before __exit__
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        # the args known at entry become the annotation's stats
        self._ann = TraceAnnotation(self.name, **(self.args or {}))
        self._ann.__enter__()
        self._t0 = self._tr._now()
        return self

    def __exit__(self, *exc):
        tr = self._tr
        end = tr._now()
        self._ann.__exit__(*exc)
        tr._append({"name": self.name, "cat": self.cat, "ph": "X",
                    "ts": self._t0, "dur": end - self._t0,
                    "pid": 0, "tid": tr._tid(),
                    "args": self.args if self.args is not None else {}})
        return False


class Tracer:
    """Process-local structured event log (spans / instants / counters)."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._events: list[dict] = []  # guarded-by: _lock
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()
        self._epoch_wall_ns = time.time_ns()    # the same instant, on the
        #                                         profiler's host clock
        self._tids: dict[int, int] = {}  # guarded-by: _lock

    # -- clock / identity ---------------------------------------------------
    def _now(self) -> float:
        """Microseconds since tracer construction (monotonic)."""
        return (time.perf_counter_ns() - self._epoch_ns) / 1e3

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _append(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    # -- control ------------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    @property
    def events(self) -> list[dict]:
        """Snapshot copy of the raw event list (append order)."""
        with self._lock:
            return list(self._events)

    # -- emission -----------------------------------------------------------
    def span(self, name: str, cat: str = "repro", args: dict | None = None):
        """Context manager timing its body as one complete event, mirrored
        into the JAX profiler's trace.  Disabled: returns the singleton
        ``NULL_SPAN`` — no allocation, no event, no annotation."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "repro",
                args: dict | None = None) -> None:
        if not self.enabled:
            return
        self._append({"name": name, "cat": cat, "ph": "i", "s": "t",
                      "ts": self._now(), "pid": 0, "tid": self._tid(),
                      "args": args or {}})

    def counter(self, name: str, values: dict, cat: str = "repro") -> None:
        """One sample of a (multi-series) counter track.  ``values`` maps
        series name -> number; pass CUMULATIVE values so the track reads as
        a running total (Perfetto shows deltas on hover)."""
        if not self.enabled:
            return
        self._append({"name": name, "cat": cat, "ph": "C",
                      "ts": self._now(), "pid": 0, "tid": self._tid(),
                      "args": dict(values)})

    # -- export -------------------------------------------------------------
    def to_chrome(self) -> dict:
        """Chrome-trace JSON object: events sorted by ``ts`` (monotone), as
        chrome://tracing and https://ui.perfetto.dev both ingest, plus
        ``profilerEpochNs``: where ``ts`` 0 lies on the profiler's clock."""
        evs = sorted(self.events, key=lambda e: e["ts"])
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "profilerEpochNs": self._epoch_wall_ns}

    def export(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path`` and return ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path
