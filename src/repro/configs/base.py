"""Config system for MindSpeed-RL-on-JAX.

Three config families:
  * ModelConfig   — architecture hyperparameters (one per assigned arch).
  * ShapeConfig   — the four assigned input shapes (train/prefill/decode/long).
  * RLConfig      — GRPO/PPO algorithm + dataflow (transfer dock, resharding).

Everything is a frozen dataclass so configs hash and can key jit caches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    vocab_size: int
    # --- attention (0 heads => attention-free family) ---
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    qkv_bias: bool = False
    qk_norm: bool = False            # RMSNorm over head_dim on q and k, before
    #                                  RoPE, one scale a layer each (qwen3)
    rope_theta: float = 10_000.0
    sliding_window: int = 0          # 0 => full causal attention
    mrope_sections: Tuple[int, ...] = ()   # M-RoPE (qwen2-vl): head_dim split t/h/w
    # --- mlp ---
    d_ff: int = 0
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_impl: str = "dispatch"       # training's expert path: dispatch
    #                                  (capacity einsum) | gmm (dropless);
    #                                  serving is always dropless (gmm)
    moe_held: Tuple[int, ...] = ()   # (first, count): the experts this
    #                                  layer holds of the router's
    #                                  num_experts (expert parallelism's
    #                                  share); () holds them all
    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_ngroups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128             # SSD chunk length for training/prefill
    # --- hybrid (zamba2): shared attention block applied every k layers ---
    hybrid_attn_period: int = 0      # 0 => not hybrid
    # --- enc-dec (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0             # fixed #frame embeddings from the stub frontend
    # --- vlm ---
    vision_tokens: int = 0           # #patch embeddings provided by the stub frontend
    # --- numerics / training ---
    dtype: str = "bfloat16"
    remat: bool = True
    norm_eps: float = 1e-5
    norm_type: str = "rmsnorm"       # rmsnorm | layernorm
    mlp_type: str = "swiglu"         # swiglu | gelu
    tie_embeddings: bool = False

    @property
    def is_attention_free(self) -> bool:
        return self.num_heads == 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(first expert id, count) of the experts this layer holds."""
        return tuple(self.moe_held) or (0, self.num_experts)

    @property
    def d_inner(self) -> int:        # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input shape.  kind selects which program is lowered."""
    name: str
    kind: str                        # train | prefill | decode
    seq_len: int
    global_batch: int

    def replace(self, **kw) -> "ShapeConfig":
        return dataclasses.replace(self, **kw)


INPUT_SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    "train",   4_096,   256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768,  32),
    "decode_32k":  ShapeConfig("decode_32k",  "decode",  32_768,  128),
    "long_500k":   ShapeConfig("long_500k",   "decode",  524_288, 1),
}


@dataclass(frozen=True)
class RLConfig:
    """GRPO/PPO algorithm + MindSpeed-RL dataflow knobs."""
    algorithm: str = "grpo"          # grpo | ppo | dapo
    num_generations: int = 8         # N responses per prompt (GRPO group size)
    clip_eps: float = 0.2
    clip_eps_high: float = 0.28      # DAPO decoupled upper clip
    kl_coef: float = 0.001
    entropy_coef: float = 0.0
    gamma: float = 1.0
    gae_lambda: float = 0.95
    temperature: float = 1.0
    greedy: bool = False             # argmax decoding (bit-reproducible runs)
    max_prompt_len: int = 64
    max_response_len: int = 64
    # --- optimizer ---
    lr: float = 1e-5
    betas: Tuple[float, float] = (0.9, 0.95)
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    zero_optimizer: bool = False     # ZeRO-shard optimizer moments over data axis
    # --- generation engine ---
    rollout_engine: str = "sync"     # sync (batch RolloutEngine) | serving
    serve_max_slots: int = 8         # continuous-batching slot count
    serve_block_size: int = 16       # paged KV-cache block size (tokens)
    serve_prefix_cache: bool = True  # ref-counted prompt-head block sharing
    serve_prefill_chunk: int = 0     # chunked prefill: max prefill tokens
    #                                  per engine step (0 = whole-prompt
    #                                  admission prefill, the classic path)
    serve_host_tier_blocks: int = 0  # host-RAM KV tier capacity in blocks
    #                                  (0 = off): reclaimed-but-indexed
    #                                  blocks spill to host and preempted/
    #                                  suspended requests swap their KV
    #                                  back in instead of re-prefilling
    serve_sampling_seed: int = 0     # run key for counter-based per-request
    #                                  sampling streams: request `seed`
    #                                  samples token t with
    #                                  fold_in(fold_in(PRNGKey(this), seed),
    #                                  t) — replayable, schedule-independent
    serve_top_p: float = 1.0         # nucleus sampling mass (1.0 = off);
    #                                  fused into the jitted decode step
    serve_top_k: int = 0             # top-k truncation (0 = off); both
    #                                  knobs apply to sync AND serving
    #                                  engines (the sampled bit-identity
    #                                  contract requires shared parameters)
    # --- dataflow (the paper's contribution) ---
    use_transfer_dock: bool = True   # False => centralized replay buffer baseline
    num_warehouses: int = 4          # S, usually = #nodes
    use_allgather_swap: bool = True  # False => naive resharding baseline
    overlap_h2d: bool = True         # prefetch H2D swap during inference stage
    partial_rollout: bool = False
    stage_fusion: bool = True        # overlap ref-inference with reward scoring
    # --- bandwidth model for dispatch accounting (paper: 300 MB/s inter-server,
    #     50 GB/s H2D/D2H) ---
    internode_bw: float = 300e6
    h2d_bw: float = 50e9
    # --- observability (repro.obs) ---
    trace_path: Optional[str] = None  # write a Chrome-trace/Perfetto JSON
    #                                  here (train.py --trace); None disables
    #                                  tracing (registry metrics stay on)

    def replace(self, **kw) -> "RLConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# TPU v5e hardware constants used by the roofline analysis (targets, since the
# container executes on CPU).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class HardwareConfig:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # bytes/s per chip
    ici_bw: float = 50e9             # bytes/s per link
    hbm_bytes: float = 16e9          # HBM capacity per chip


TPU_V5E = HardwareConfig()
