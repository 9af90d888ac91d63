"""The system under test as the benchmark drives it: its configuration
built from a configuration file, and the chip's memory peak."""
from __future__ import annotations

import os
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def model_config(c: dict):
    """The program's ``ModelConfig`` for a Hugging Face style config."""
    from repro.configs.base import ModelConfig

    heads = c["num_attention_heads"]
    if c.get("use_sliding_window"):
        raise ValueError("sliding-window layers are not modelled here")
    return ModelConfig(
        name=f"{c['model_type']}-{c['num_hidden_layers']}l",
        arch_type="dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        vocab_size=c["vocab_size"],
        num_heads=heads,
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        qkv_bias=bool(c.get("attention_bias", False)),
        rope_theta=float(c["rope_theta"]),
        d_ff=c["intermediate_size"],
        dtype=c["torch_dtype"],
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
    )


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
