"""The Qwen3-MoE decoder (Qwen3-30B-A3B): grouped-query attention with a
per-head RMSNorm on q and k before RoPE, RMSNorm, and in every layer a
sparse expert FFN: a softmax router over the published expert count, the
top ``num_experts_per_tok`` picks with their gates renormalised, SwiGLU
experts of width ``moe_intermediate_size``, no shared expert.

A configuration holds a share of each layer's experts, as one chip of an
expert-parallel deployment does: ``num_experts`` counts the experts held
here, ``reduced.num_experts.published`` the router's width, and
``experts_held_from`` the first expert id held.
"""
from __future__ import annotations

import flops

# the configurations ``model_config`` built in this process (one cell a
# process), for readers that take the run's sizes (``gmm_roofline``)
BUILT: list = []


def router_width(c: dict) -> int:
    """The published expert count, over which the router scores."""
    return c["reduced"]["num_experts"]["published"]


def held(c: dict) -> tuple[int, int]:
    """(first expert id, count) of the experts this chip holds."""
    return c.get("experts_held_from", 0), c["num_experts"]


def model_config(c: dict):
    """The program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig

    if c.get("use_sliding_window") or c.get("mlp_only_layers") or c.get(
            "decoder_sparse_step", 1) != 1:
        raise ValueError("only all-sparse, full-attention layers are "
                         "modelled here")
    if not c.get("norm_topk_prob", False):
        raise ValueError("the gates are renormalised over the picks here")
    BUILT.append(c)
    return ModelConfig(
        name=f"{c['model_type']}-{c['num_hidden_layers']}l",
        arch_type="moe",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        vocab_size=c["vocab_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=flops.head_dim(c),
        qk_norm=True,
        rope_theta=float(c["rope_theta"]),
        d_ff=c["moe_intermediate_size"],
        num_experts=router_width(c),
        experts_per_token=c["num_experts_per_tok"],
        moe_held=held(c),
        dtype=c["torch_dtype"],
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
    )


def shapes(c: dict) -> dict:
    """Leaf name -> shape, per-layer weights stacked on a leading axis."""
    n, dm, f, v = (c["num_hidden_layers"], c["hidden_size"],
                   c["moe_intermediate_size"], c["vocab_size"])
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 flops.head_dim(c))
    e = c["num_experts"]
    return {
        "embed": (v, dm),
        "lm_head": (dm, v),
        "layers": {
            "ln1": {"scale": (n, dm)},
            "attn": {"wq": (n, dm, h * hd), "wk": (n, dm, kv * hd),
                     "wv": (n, dm, kv * hd), "wo": (n, h * hd, dm),
                     "q_norm": {"scale": (n, hd)},
                     "k_norm": {"scale": (n, hd)}},
            "ln2": {"scale": (n, dm)},
            "moe": {"router": (n, dm, router_width(c)),
                    "w_gate": (n, e, dm, f), "w_up": (n, e, dm, f),
                    "w_down": (n, e, f, dm)}},
        "ln_f": {"scale": (dm,)},
    }


def layer_matmul_params(c: dict) -> int:
    """Attention's projections, the router, and the expected expert
    weights a token meets here: it picks ``num_experts_per_tok`` of the
    router's experts, and under uniform routing the held share of them,
    ``k * held / router width`` experts' three matrices (1 for 8 of 128
    picks with 16 held)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    experts = c["num_experts_per_tok"] * c["num_experts"] / router_width(c)
    return int(flops.attention_params(c) + d * router_width(c)
               + experts * 3 * d * f)


def parameters(c: dict) -> int:
    """Every weight this chip holds: per layer attention's projections, two
    norm scales of ``hidden_size`` and two of ``head_dim``, the router and
    the held experts' three matrices; the final norm, the embedding and
    the output head (untied)."""
    d, f, v = c["hidden_size"], c["moe_intermediate_size"], c["vocab_size"]
    per_layer = (flops.attention_params(c) + 2 * d + 2 * flops.head_dim(c)
                 + d * router_width(c) + c["num_experts"] * 3 * d * f)
    return per_layer * c["num_hidden_layers"] + d + 2 * v * d


WEIGHT_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def gmm_cost(c: dict, counters: dict) -> tuple:
    """(FLOPs, bytes) the grouped matmuls need for the expert work that the
    engine's ``serve.moe.*`` counters record: ``rows`` routed to held
    experts, each through the three matrices (2 x 3 x rows x d x f FLOPs);
    each live row read once and its output written once by each of the
    three; each held expert that got a row (``experts``, summed over
    expert-layer applications) has its three matrices read once; all in
    the configuration's ``torch_dtype``.  Padding rows are not counted."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    weight_bytes = WEIGHT_BYTES[c["torch_dtype"]]
    rows, experts = counters["serve.moe.rows"], counters["serve.moe.experts"]
    flops_ = 2.0 * 3 * rows * d * f
    bytes_ = weight_bytes * (experts * 3 * d * f + rows * 3 * (d + f))
    return flops_, float(bytes_)
