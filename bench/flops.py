"""Model FLOPs and bytes, counted from shapes.

Conventions (the same for every cell):

- A matrix product of (m, k) by (k, n) is 2*m*k*n FLOPs.  Norms, RoPE,
  biases, softmax and the embedding lookup are not counted.
- Attention counts QK^T and PV: 4 * heads * head_dim FLOPs for each
  (query, key) pair a causal mask keeps.
- Only real tokens count: pads are left out, and recomputation (remat) is
  not counted.  The output head is counted only at the positions whose
  logits the algorithm uses (the response tokens of a sample, the token a
  serving step samples).
"""
from __future__ import annotations

from dataclasses import dataclass

import harness


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    layer_matmul_params: int       # matrix weights a token meets in a layer
    weight_bytes: int = 2          # bf16

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """From a Hugging Face style ``config.json`` dictionary; the layer's
        matrices are counted by the configuration's model family."""
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
                   vocab=c["vocab_size"],
                   layer_matmul_params=harness.family(c).layer_matmul_params(c))

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab


def head_dim(c: dict) -> int:
    return c.get("head_dim", c["hidden_size"] // c["num_attention_heads"])


def attention_params(c: dict) -> int:
    """Weights of attention's q, k, v and output projections."""
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    hd = head_dim(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def layer_flops(dims: Dims, tokens: int, attn_pairs: int) -> float:
    """Forward FLOPs of the layer stack over ``tokens`` tokens whose
    attention keeps ``attn_pairs`` (query, key) pairs in all."""
    return (2.0 * dims.layer_matmul_params * dims.layers * tokens
            + 4.0 * dims.heads * dims.head_dim * dims.layers * attn_pairs)


def head_flops(dims: Dims, positions: int) -> float:
    return 2.0 * dims.head_params * positions


def causal_pairs(n: int) -> int:
    """(query, key) pairs of a causal mask over ``n`` tokens."""
    return n * (n + 1) // 2


def sequence_forward_flops(dims: Dims, prompt: int, response: int) -> float:
    """One forward over a sample of ``prompt`` real prompt tokens and
    ``response`` response tokens, the head at the response positions."""
    n = prompt + response
    return layer_flops(dims, n, causal_pairs(n)) + head_flops(dims, response)


def grpo_sample_flops(dims: Dims, prompt: int, response: int) -> float:
    """Model FLOPs one GRPO sample costs in an iteration: generation (one
    forward), the old and reference log-probabilities (two forwards) and
    the update (forward and backward, three forwards)."""
    return 6.0 * sequence_forward_flops(dims, prompt, response)


def decode_token_flops(dims: Dims, context: int) -> float:
    """One decoded token that attends to ``context`` keys (itself
    included), with the head applied."""
    return layer_flops(dims, 1, context) + head_flops(dims, 1)


def paged_attention_cost(dims: Dims, contexts, kv_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) the decode attention of one step needs over all
    layers, for slots whose contexts (keys attended, the new token
    included) are ``contexts``: every live K and V row read once, each
    query read and each output written once."""
    rows = sum(contexts)
    flops = 4.0 * dims.heads * dims.head_dim * dims.layers * rows
    kv = 2 * dims.kv_heads * dims.head_dim * kv_bytes * dims.layers * rows
    qo = 2 * dims.heads * dims.head_dim * kv_bytes * dims.layers * len(contexts)
    return flops, float(kv + qo)


def parameters(dims: Dims, qkv_bias: bool = False) -> int:
    """Every weight: the layer matrices, two norm scales a layer, the
    final norm, the embedding and the output head (untied)."""
    per_layer = dims.layer_matmul_params + 2 * dims.d_model
    if qkv_bias:
        per_layer += (dims.heads + 2 * dims.kv_heads) * dims.head_dim
    return (per_layer * dims.layers + dims.d_model
            + 2 * dims.vocab * dims.d_model)
