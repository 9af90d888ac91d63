"""The Llama/Qwen2 decoder (Yi-6B, Qwen2.5-7B): grouped-query attention
with RoPE, a bias on q, k and v where ``attention_bias`` says so, RMSNorm,
and a dense SwiGLU FFN of width ``intermediate_size``."""
from __future__ import annotations

import flops


def model_config(c: dict):
    """The program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig

    if c.get("use_sliding_window"):
        raise ValueError("sliding-window layers are not modelled here")
    return ModelConfig(
        name=f"{c['model_type']}-{c['num_hidden_layers']}l",
        arch_type="dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        vocab_size=c["vocab_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=flops.head_dim(c),
        qkv_bias=has_qkv_bias(c),
        rope_theta=float(c["rope_theta"]),
        d_ff=c["intermediate_size"],
        dtype=c["torch_dtype"],
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
    )


def has_qkv_bias(c: dict) -> bool:
    return bool(c.get("attention_bias", False))


def shapes(c: dict) -> dict:
    """Leaf name -> shape, per-layer weights stacked on a leading axis."""
    n, dm, f, v = (c["num_hidden_layers"], c["hidden_size"],
                   c["intermediate_size"], c["vocab_size"])
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 flops.head_dim(c))
    attn = {"wq": (n, dm, h * hd), "wk": (n, dm, kv * hd),
            "wv": (n, dm, kv * hd), "wo": (n, h * hd, dm)}
    if has_qkv_bias(c):
        attn.update(bq=(n, h * hd), bk=(n, kv * hd), bv=(n, kv * hd))
    return {
        "embed": (v, dm),
        "lm_head": (dm, v),
        "layers": {"ln1": {"scale": (n, dm)}, "attn": attn,
                   "ln2": {"scale": (n, dm)},
                   "mlp": {"w_gate": (n, dm, f), "w_up": (n, dm, f),
                           "w_down": (n, f, dm)}},
        "ln_f": {"scale": (dm,)},
    }


def layer_matmul_params(c: dict) -> int:
    """Attention's projections and the FFN's three matrices."""
    return flops.attention_params(c) + 3 * c["hidden_size"] * c[
        "intermediate_size"]
