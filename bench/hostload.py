"""Host-side readings over a stretch of a run, for telling apart the causes
of a slow host path: this process's CPU seconds (all its threads), the CPU
seconds of its busiest threads by name, the seconds its threads waited,
runnable, for a CPU, the whole host's CPU seconds by kind from
``/proc/stat`` (busy, idle, stolen by the hypervisor), and the time
Python's garbage collector held the process.  A stretch whose wall time
grows while the process's CPU seconds stay put was kept off the CPU; one
whose CPU seconds grow with it did more work or spun."""
from __future__ import annotations

import gc
import glob
import os
import time

_TICK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def _proc_stat() -> dict:
    """The host's CPU seconds since boot, summed over its cores."""
    try:
        with open("/proc/stat") as f:
            vals = [int(x) for x in f.readline().split()[1:]]
    except (OSError, ValueError):
        return {}
    vals += [0] * (8 - len(vals))
    user, nice, system, idle, iowait, irq, softirq, steal = vals[:8]
    return {"host_busy_s": (user + nice + system + irq + softirq) / _TICK,
            "host_idle_s": (idle + iowait) / _TICK,
            "host_steal_s": steal / _TICK}


def _run_delay() -> dict:
    """Seconds this process's threads waited for a CPU while runnable."""
    total, found = 0, False
    for path in glob.glob("/proc/self/task/*/schedstat"):
        try:
            with open(path) as f:
                total += int(f.read().split()[1])
            found = True
        except (OSError, ValueError, IndexError):
            pass
    return {"run_delay_s": total / 1e9} if found else {}


def _threads() -> dict:
    """CPU seconds of this process's live threads, summed by thread name."""
    out: dict[str, float] = {}
    for task in glob.glob("/proc/self/task/*"):
        try:
            with open(f"{task}/comm") as f:
                name = f.read().strip()
            with open(f"{task}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            out[name] = out.get(name, 0.0) + (
                int(fields[11]) + int(fields[12])) / _TICK
        except (OSError, ValueError, IndexError):
            pass
    return out


class HostLoad:
    """``mark()`` returns the readings so far; ``since(mark)`` what changed."""

    def __init__(self):
        self.gc_s = 0.0
        self.gc_n = 0
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_n += 1
            self._gc_t0 = None

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def mark(self) -> dict:
        t = os.times()
        return {"wall_s": time.perf_counter(),
                "proc_cpu_s": t.user + t.system,
                "gc_s": self.gc_s, "gc_n": self.gc_n, **_proc_stat(),
                **_run_delay(), "threads": _threads()}

    def since(self, m: dict, top: int = 4) -> dict:
        now = self.mark()
        d = {k: now[k] - m[k] for k in m if k != "threads"}
        busy = {n: v - m["threads"].get(n, 0.0)
                for n, v in now["threads"].items()}
        d["threads_cpu_s"] = dict(sorted(busy.items(),
                                         key=lambda kv: -kv[1])[:top])
        d["host_cores"] = os.cpu_count()
        d["loadavg_1m"] = os.getloadavg()[0]
        return d
