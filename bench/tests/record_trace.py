"""Record the small profiler trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace.py OUT_DIR

Runs a jitted matrix product five times on the first device, with a host
sleep inside a ``bench.host_wait`` annotation between the calls, under the
JAX profiler, and writes the ``.xplane.pb`` into OUT_DIR.  It also prints
the planes, lines and first events of the trace, which is how the layout
that ``bench/trace.py`` expects was read off.
"""
from __future__ import annotations

import glob
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) * 0.001
    f(a, b).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for _ in range(5):
        with jax.profiler.TraceAnnotation("bench.call"):
            f(a, b).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True))[-1]
    print("trace", path)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print("plane", repr(plane.name), "lines", len(lines))
        for line in lines[:12]:
            evs = list(line.events)
            print("  line", repr(line.name), "events", len(evs))
            for ev in evs[:4]:
                print("    ev", repr(ev.name), ev.start_ns, ev.duration_ns,
                      dict(list(ev.stats)[:6]) if ev.stats else {})
    print("prngkey", jax.random.PRNGKey(2**31 + 12345))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
