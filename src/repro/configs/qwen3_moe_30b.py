"""qwen3-moe-30b (Qwen3-30B-A3B) — the paper's own MoE evaluation model
[arXiv:2505.09388].

48L d_model=2048 32H (kv=4, head_dim=128) with per-head q/k RMSNorm, 128
experts top-8 (softmax, renormalised over the 8), expert d_ff=768, no
shared expert, vocab=151936, rms_norm_eps=1e-6.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b",
    arch_type="moe",
    num_layers=48,
    d_model=2048,
    vocab_size=151_936,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    qk_norm=True,
    d_ff=768,
    num_experts=128,
    experts_per_token=8,
    rope_theta=1e6,
    norm_eps=1e-6,
)


def smoke() -> ModelConfig:
    return CONFIG.replace(
        name="qwen3-moe-smoke",
        num_layers=2,
        d_model=256,
        vocab_size=512,
        num_heads=8,
        num_kv_heads=2,
        head_dim=32,
        d_ff=128,
        num_experts=4,
        experts_per_token=2,
    )
