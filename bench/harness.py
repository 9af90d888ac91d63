"""Harness: finds a cell's configuration, traffic mix, correctness limits
and metric readers by name, runs the cell's generator, prints the result.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found from the names in ``BENCHMARK.json``:

    configs/<config>.json     sizes as run (Hugging Face config.json keys);
                              ``family`` and ``reference`` name the two
                              files below
    families/<family>.py      the model family: ``model_config(c)`` (the
                              program's ``ModelConfig``), ``shapes(c)`` (the
                              weights tree) and ``layer_matmul_params(c)``
                              (matrix weights a token meets in one layer)
    reference/<reference>.py  the plain reference: ``token_logp(w, c,
                              tokens, quant)``, in float32 or the control's
                              precision
    traffic/<traffic>.json    parameters of the mix; ``generator`` names the
                              general generator in generators/<generator>.py
    limits/<cell>.json        the limit of each number the check compares
    metrics/<metric>.py       a reader; or metrics/<family>.py, the family
                              being the metric's name up to its first dot

A generator is a module with ``drive(run, devices) -> window``: it sets up,
calls ``run.start_window()``, measures for ``run.seconds`` (inside
``run.traced()``), then checks what the window produced and returns the
window's record with its ``checks``.  A reader is a module with
``read(window) -> float | None``; ``None`` means it found nothing to read,
and the metric is left out of the line.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    pass


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_spec(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer metrics:
    those whose ``workloads`` list it, or that list none."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(BENCH, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return load_module(path, f"bench_metric_{stem.replace('.', '_')}")
    raise SystemExit(f"no reader metrics/{metric}.py or family file")


def generator(name: str):
    return load_module(os.path.join(BENCH, "generators", f"{name}.py"),
                       f"bench_generator_{name}")


@functools.cache
def _named(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind}/{name}.py")
    return load_module(path, f"bench_{kind}_{name.replace('.', '_')}")


def family(c: dict):
    """The model family the configuration ``c`` names (``families/``)."""
    return _named("families", c["family"])


def reference(c: dict):
    """The plain reference the configuration ``c`` names (``reference/``)."""
    return _named("reference", c["reference"])


class CompileCounter:
    """Counts the traces, backend compiles and persistent-cache loads JAX
    reports, to show that none happens inside the window."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec": "loads"}

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        name = self.EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1
            self.s += secs

    def snapshot(self) -> dict:
        return {**self.counts, "s": self.s}


def use_compile_cache() -> None:
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` in the checkout), with every program
    cached however short its compile: the small generation programs too."""
    import jax

    import program  # noqa: F401  (puts the program's ``src`` on the path)
    from repro.launch.cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_chip and d.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


class Run:
    """What a generator sees of the harness: the cell's files and settings,
    the clock, the window's trace and the benchmark's own spans."""

    def __init__(self, cell: dict, args, t_start: float, overrides=None):
        overrides = overrides or {}
        self.cell = cell
        self.name = cell["name"]
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.chips = cell["chips"]
        self.t_start = t_start
        self.config = {**load_json("configs", f"{cell['config']}.json"),
                       **overrides.get("config", {})}
        self.traffic = {**load_json("traffic", f"{cell['traffic']}.json"),
                        **overrides.get("traffic", {})}
        self.limits = (overrides["limits"] if "limits" in overrides else
                       load_json("limits", f"{self.name}.json")["limits"])
        self.faults = overrides.get("faults", ())
        self.control = bool(overrides.get("control", False))
        self.readings = self.control_readings = None
        self.window_t0 = self.window_t1 = None
        self.window = None
        self.compiles = None
        self.compiles_at_window = {}
        self._trace_dir = None
        self.trace_result = None

    def span(self, name: str):
        """A benchmark span around a call into the program; it lands in
        the profiler's trace, on the same clock as the device."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def start_window(self) -> float:
        if self.compiles is not None:
            self.compiles_at_window = self.compiles.snapshot()
        self.window_t0 = time.perf_counter()
        return self.window_t0

    def compiles_in_window(self) -> dict:
        """Traces, compiles and cache loads since the window started, and
        the seconds they took."""
        if self.compiles is None:
            return {}
        now = self.compiles.snapshot()
        return {k: now[k] - self.compiles_at_window.get(k, 0) for k in now}

    @contextlib.contextmanager
    def traced(self):
        """The measured part of the window, profiled with ``--trace 1``.
        Its clock ends at the close of the enclosed part (``window_t1``);
        traced, it starts again once the profiler runs and ends before the
        profiler collects its trace, so that neither is counted as window.
        The Python tracer stays off: the program's spans and the
        benchmark's are annotations, which the host tracer records."""
        if not self.trace:
            yield
            self.window_t1 = time.perf_counter()
            return
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        try:
            self.window_t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
            self.window_t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()

    def reduce_trace(self, chips: int) -> dict | None:
        if self._trace_dir is None:
            return None
        import xplane

        try:
            path = xplane.find_xplane(self._trace_dir)
            self.trace_result = xplane.reduce(path, chips=chips)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None
        return self.trace_result


def parse(argv):
    ap = argparse.ArgumentParser(description="chip benchmark, one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, *, t_start: float | None = None, require_chip: bool = True,
         overrides: dict | None = None, keep: list | None = None,
         bench: dict | None = None) -> int:
    """``require_chip=False``, ``overrides`` and ``bench`` are for the CPU
    tests: they skip the look for a TPU, shrink the configuration and name
    the cells (``BENCHMARK.json`` where not given).  ``keep`` (a list)
    receives the run's ``Run``, whose ``readings`` and ``control_readings``
    the calibration reads, and its ``window``."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    bench = benchmark() if bench is None else bench
    cell = cell_spec(bench, args.workload)
    run = Run(cell, args, t_start, overrides)
    if keep is not None:
        keep.append(run)
    kind = "per_layer" if run.trace else "end_to_end"
    wanted = metrics_for(bench, run.name, kind)
    readers = {m["name"]: reader(m["name"]) for m in wanted}

    import jax

    try:
        device = device_info(run.chips, require_chip)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if require_chip:
        use_compile_cache()
    devices = jax.devices()[:run.chips]
    run.compiles = CompileCounter()
    gen = generator(run.traffic["generator"])
    window = run.window = gen.drive(run, devices)
    device["memory_peak_bytes"] = window.pop("memory_peak_bytes")
    if run.trace and window.get("trace"):
        device["busy_s"] = window["trace"]["busy_s"]
        device["window_s"] = window["trace"]["window_s"]
    window["setup_s"] = run.window_t0 - run.t_start
    window["chips"] = run.chips
    window["device_kind"] = device["kind"]

    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {}
    for name, mod in readers.items():
        value = mod.read(window)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    checks = window["checks"]
    correct = bool(checks) and all(c["ok"] for c in checks)
    out = {"correct": correct, "attempted": window["attempted"],
           "failed": window["failed"], "metrics": metrics, "device": device}
    if run.trace and window.get("trace"):
        out["breakdown"] = {"device_ops": window["trace"]["device_ops"],
                            "idle_gaps": window["trace"]["idle_gaps"]}
    if "stream" in window:
        out["stream"] = {k: v for k, v in window["stream"].items()
                         if k != "parts"}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    for it in window.get("iterations", []):
        print(f"bench iteration: {json.dumps(it)}", file=sys.stderr)
    for part in window.get("stream", {}).get("parts", []):
        print(f"bench stream part: {json.dumps(part)}", file=sys.stderr)
    print(f"bench host: {json.dumps(window.get('host'))}", file=sys.stderr)
    print(f"bench: compiles inside the window: "
          f"{json.dumps(window.get('window_compiles'))}", file=sys.stderr)
    for name, v in sorted((run.readings or {}).items()):
        print(f"bench reading: {name} = {v!r}", file=sys.stderr)
    if run.trace and window.get("trace"):
        top = sorted(window["trace"]["op_s"].items(), key=lambda kv: -kv[1])
        for n, v in top[:40]:
            print(f"bench device op: {n[:160]} {v!r}", file=sys.stderr)
        for n, v in top:
            if "custom-call" in n[:200]:
                print(f"bench custom call: {v!r} {n[:600]}", file=sys.stderr)
        for n, row in sorted(window["trace"]["spans"].items()):
            print(f"bench span: {n} {json.dumps(row)}", file=sys.stderr)
    sys.stderr.flush()
    print(f"bench: correct={correct}", file=sys.stderr)
    for c in checks:
        print(f"bench check: {c['name']} = {c['value']!r} "
              f"(limit {c['limit']!r}, {'ok' if c['ok'] else 'FAILED'})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
