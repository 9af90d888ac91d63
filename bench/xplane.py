"""Reduce a JAX profiler trace (``.xplane.pb``) to the device's busy time,
the device operations that took most time, and the idle gaps by what the
benchmark's host code was doing.

Layout read (``jax.profiler.ProfileData``): a TPU is a plane named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
run; the host plane ``/host:CPU`` holds the benchmark's
``jax.profiler.TraceAnnotation`` spans (named ``bench.<call>``), among them
``bench.window`` around the traced part of the window.  On the CPU backend
(the rehearsal tests) no device plane exists, and the host events that
carry an ``hlo_op`` stat stand for the device's operations.  All times are
on the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, ev


def short_name(op: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; the
    instruction's name (before `` = ``) is enough to show it."""
    return op.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """Device op intervals per device, host spans and the window bounds."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices: dict[str, list] = {}
    spans: list = []
    cpu_ops: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((n, s, e) for n, s, e, _ in _events(line))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for n, s, e, ev in _events(line):
                    if n.startswith(SPAN_PREFIX):
                        spans.append((n, s, e))
                    elif e > s and any(k == "hlo_op" for k, _ in ev.stats):
                        cpu_ops.append((n, s, e))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    else:
        allops = [iv for ops in devices.values() for iv in ops]
        w0 = min(s for _, s, _ in allops)
        w1 = max(e for _, _, e in allops)
    return {"devices": devices, "spans": spans, "window": (w0, w1)}


def union(intervals, lo: float, hi: float) -> list:
    """Merged (start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(spans, t: float) -> str:
    best = None
    for n, s, e in spans:
        if n != WINDOW_SPAN and s <= t <= e and (
                best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "none"


def reduce(path: str, chips: int = 1) -> dict:
    """busy_s and window_s (seconds, busy averaged over the devices), the
    device time of every op and the top ten (summed over devices, divided
    by their number), and the idle gaps of the first device summed by the
    innermost benchmark span the host was in at the gap's middle."""
    t = load(path)
    lo, hi = t["window"]
    devs = sorted(t["devices"])[:max(chips, 1)]
    if not devs:
        raise ValueError(f"{path}: no device operations in the trace")
    busy_ns, by_op = 0.0, defaultdict(float)
    for d in devs:
        ops = t["devices"][d]
        busy_ns += sum(e - s for s, e in union(
            ((s, e) for _, s, e in ops), lo, hi))
        for n, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_op[n] += e - s
    nd = len(devs)
    first = union(((s, e) for _, s, e in t["devices"][devs[0]]), lo, hi)
    idle = defaultdict(float)
    spans = sorted(t["spans"], key=lambda x: x[1])
    for s, e in gaps(first, lo, hi):
        idle[_innermost(spans, (s + e) / 2)] += e - s
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_ns / nd / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[short_name(n), v / nd / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / 1e9] for n, v in top_idle],
        "op_s": {n: v / nd / 1e9 for n, v in by_op.items()},
        "devices": nd,
    }
