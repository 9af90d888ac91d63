"""Reduce a JAX profiler trace (``.xplane.pb``) to the device's busy time,
the device operations that took most time, the idle gaps by what the host
was doing, and the host's spans (``spans.span_table``).

Layout read (``jax.profiler.ProfileData``): a TPU is a plane named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
run; the host plane ``/host:CPU`` holds, one line a thread, the
benchmark's ``jax.profiler.TraceAnnotation`` spans (named
``bench.<call>``), among them ``bench.window`` around the traced part of
the window, and the program's own spans (``spans.is_span``).  On the CPU
backend (the rehearsal tests) no device plane exists, and the host events
that carry an ``hlo_op`` stat stand for the device's operations.  All times
are on the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import spans as program_spans

WINDOW_SPAN = program_spans.WINDOW_SPAN
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
    """Device op intervals per device, host spans (all, and per host
    thread) and the window bounds."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices: dict[str, list] = {}
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
                    if (e > s and not program_spans.is_span(n)
                            and any(k == "hlo_op" for k, _ in ev.stats)):
                        cpu_ops.append((n, s, e))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    threads = program_spans.threads_in(pd)
    spans = [sp for thread in threads.values() for sp in thread]
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    else:
        allops = [iv for ops in devices.values() for iv in ops]
        w0 = min(s for _, s, _ in allops)
        w1 = max(e for _, _, e in allops)
    return {"devices": devices, "spans": spans, "threads": threads,
            "window": (w0, w1)}


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
    """The innermost span at ``t`` by a scan over every span: the plain
    answer that ``spans.innermost``'s sweep is tested against."""
    best = None
    for n, s, e in spans:
        if n != WINDOW_SPAN and s <= t <= e and (
                best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "none"


def reduce(path: str, chips: int = 1) -> dict:
    """busy_s and window_s (seconds, busy averaged over the devices), the
    device time of every op and the top ten (summed over devices, divided
    by their number), the idle gaps of the first device summed by the
    innermost span, the benchmark's or the program's, that the host was in
    at the gap's middle, and ``spans``: each span's count, time, self time
    and the first device's idle time inside it (``spans.span_table``)."""
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
    idle = program_spans.idle_by_span(t["spans"], gaps(first, lo, hi))
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_ns / nd / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[short_name(n), v / nd / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / 1e9] for n, v in top_idle],
        "op_s": {n: v / nd / 1e9 for n, v in by_op.items()},
        "spans": program_spans.span_table(t["threads"], first, lo, hi),
        "devices": nd,
    }
