"""A toy decoder family, for the test that a family is added as new files
only: Llama's attention under LayerNorm with a bias (one leaf more in each
norm) and a GELU FFN of two matrices (one leaf fewer), both of which the
program has."""
from __future__ import annotations

import flops


def model_config(c: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=f"{c['model_type']}-{c['num_hidden_layers']}l",
        arch_type="dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        vocab_size=c["vocab_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=flops.head_dim(c),
        rope_theta=float(c["rope_theta"]),
        d_ff=c["intermediate_size"],
        dtype=c["torch_dtype"],
        norm_eps=float(c["norm_epsilon"]),
        norm_type="layernorm",
        mlp_type="gelu",
    )


def shapes(c: dict) -> dict:
    n, dm, f, v = (c["num_hidden_layers"], c["hidden_size"],
                   c["intermediate_size"], c["vocab_size"])
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 flops.head_dim(c))
    norm = {"scale": (n, dm), "bias": (n, dm)}
    return {
        "embed": (v, dm),
        "lm_head": (dm, v),
        "layers": {"ln1": norm, "ln2": dict(norm),
                   "attn": {"wq": (n, dm, h * hd), "wk": (n, dm, kv * hd),
                            "wv": (n, dm, kv * hd), "wo": (n, h * hd, dm)},
                   "mlp": {"w_up": (n, dm, f), "w_down": (n, f, dm)}},
        "ln_f": {"scale": (dm,), "bias": (dm,)},
    }


def layer_matmul_params(c: dict) -> int:
    return flops.attention_params(c) + 2 * c["hidden_size"] * c[
        "intermediate_size"]
