"""Program spans in a profiler trace, beside the benchmark's own.

The benchmark's spans are named ``bench.*``.  The program mirrors its own
``repro.obs`` spans into the trace as well, each on the host thread that
ran it: the serving engine's phases (``serve.*``), the graph executor's
``stage.*`` and ``reshard.*``, the trainer's ``iteration``.  This module
reads both kinds and reduces them against the device's busy intervals;
``xplane.reduce`` names the idle gaps and tabulates the spans with it:

* ``host_spans``: the spans of either kind in a trace, per host thread;
* ``innermost``: the innermost span at each of an ascending run of
  instants, by one sweep: the shortest span that holds the instant, ties to
  the earliest start, ``bench.window`` aside (the answer of ``xplane``'s
  scan over every span, at a cost of O((spans + instants) log spans));
* ``idle_by_span``: idle gaps summed by the innermost span at their middle;
* ``span_table``: per span name, the count, the time inside the window,
  the self time (that time less what the span's children on its thread
  cover) and the device's idle time inside.

All times are the trace's nanoseconds; the table gives seconds.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
PROGRAM_PREFIXES = ("serve.", "stage.", "reshard.")
PROGRAM_NAMES = ("iteration",)


def is_span(name: str) -> bool:
    """A benchmark span or one of the program's spans."""
    return (name.startswith(BENCH_PREFIX)
            or name.startswith(PROGRAM_PREFIXES) or name in PROGRAM_NAMES)


def host_spans(path: str) -> dict:
    """``{(plane, line index): [(name, start, end), ...]}``: the spans of
    either kind on each host thread of the trace at ``path``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        return threads_in(ProfileData.from_serialized_xspace(f.read()))


def threads_in(pd) -> dict:
    """``host_spans`` of a trace already read (``ProfileData``)."""
    threads = defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if is_span(ev.name):
                    threads[(plane.name, k)].append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return dict(threads)


def innermost(spans, points) -> list[str]:
    """The innermost span's name at each of ``points`` (ascending), or
    ``"none"`` where no span but ``bench.window`` holds it.  Spans are
    ``(name, start, end)`` in any order, from any threads."""
    order = sorted((sp for sp in spans if sp[0] != WINDOW_SPAN),
                   key=lambda sp: sp[1])
    heap, out, i = [], [], 0
    for t in points:
        while i < len(order) and order[i][1] <= t:
            n, s, e = order[i]
            heapq.heappush(heap, (e - s, i, e, n))
            i += 1
        while heap and heap[0][2] < t:      # ended: never holds a later t
            heapq.heappop(heap)
        out.append(heap[0][3] if heap else "none")
    return out


def idle_by_span(spans, gap_list) -> dict:
    """Idle nanoseconds per innermost span name at each gap's middle; the
    gaps are ``(start, end)``, ascending and disjoint."""
    idle = defaultdict(float)
    mids = [(s + e) / 2 for s, e in gap_list]
    for n, (s, e) in zip(innermost(spans, mids), gap_list):
        idle[n] += e - s
    return dict(idle)


def _busy_within(busy):
    """A function giving the busy time of ``busy`` (merged, ascending
    ``[start, end]`` intervals) inside ``[a, b]``, by bisection."""
    starts = [s for s, _ in busy]
    ends = [e for _, e in busy]
    cum = [0.0]
    for s, e in busy:
        cum.append(cum[-1] + e - s)

    def within(a: float, b: float) -> float:
        i = bisect.bisect_right(ends, a)     # first interval ending after a
        j = bisect.bisect_left(starts, b)    # intervals starting before b
        if i >= j:
            return 0.0
        return (cum[j] - cum[i] - max(0.0, a - starts[i])
                - max(0.0, ends[j - 1] - b))

    return within


def span_table(threads: dict, busy, lo: float, hi: float) -> dict:
    """Per span name, over the spans of ``threads`` (as ``host_spans``
    gives them) clipped to the window ``[lo, hi]``: ``count``, ``s`` (time
    inside), ``self_s`` (less the time its child spans on the same thread
    cover) and ``idle_s`` (time inside in which ``busy``, the device's
    merged busy intervals, has no operation), in seconds."""
    within = _busy_within(busy)
    table = defaultdict(lambda: {"count": 0, "s": 0.0, "self_s": 0.0,
                                 "idle_s": 0.0})
    for spans in threads.values():
        clipped = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in spans
                          if min(e, hi) > max(s, lo)),
                         key=lambda sp: (sp[1], -sp[2]))
        covered = [0.0] * len(clipped)
        stack = []                          # indices of open ancestors
        for k, (_, s, e) in enumerate(clipped):
            while stack and clipped[stack[-1]][2] <= s:
                stack.pop()
            if stack:
                covered[stack[-1]] += min(e, clipped[stack[-1]][2]) - s
            stack.append(k)
        for (n, s, e), cov in zip(clipped, covered):
            row = table[n]
            row["count"] += 1
            row["s"] += (e - s) / 1e9
            row["self_s"] += (e - s - cov) / 1e9
            row["idle_s"] += (e - s - within(s, e)) / 1e9
    return dict(table)
