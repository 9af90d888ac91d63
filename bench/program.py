"""The system under test as the benchmark drives it: its configuration
built from a configuration file, and the chip's memory peak."""
from __future__ import annotations

import os
import sys

import harness

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def model_config(c: dict):
    """The program's ``ModelConfig`` for a Hugging Face style config, as
    the configuration's model family builds it."""
    return harness.family(c).model_config(c)


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, as JAX reports."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def release() -> None:
    """Collect the program's dropped objects, so that their device
    buffers are freed before the reference runs; report what is left."""
    import gc

    import jax

    gc.collect()
    live = jax.live_arrays()
    print(f"bench: {len(live)} arrays, {sum(a.nbytes for a in live)} bytes "
          f"left on the device before the reference", file=sys.stderr)
