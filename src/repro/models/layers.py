"""Shared transformer building blocks (pure-JAX pytrees).

Params are plain dicts; per-layer params are stacked along a leading L axis
and consumed by ``jax.lax.scan``.  All blocks compute in ``cfg.dtype``
(bf16 by default) with fp32 accumulation inside the fused ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, width: int, layers: int | None = None) -> dict:
    shape = (width,) if layers is None else (layers, width)
    p = {"scale": jnp.ones(shape, cdtype(cfg))}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros(shape, cdtype(cfg))
    return p


def norm_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm_type == "layernorm":
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
        return y.astype(x.dtype)
    return ops.rmsnorm(x, p["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# attention block (GQA + RoPE/M-RoPE + causal/SWA; used by dense/moe/vlm/
# hybrid-shared-block and whisper self/cross attention)
# ---------------------------------------------------------------------------

def attn_init(cfg: ModelConfig, key, layers: int | None = None) -> dict:
    h, kv, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    lead = () if layers is None else (layers,)
    sc_in = 1.0 / np.sqrt(d)
    sc_out = 1.0 / np.sqrt(h * hd)
    p = {
        "wq": _normal(ks[0], lead + (d, h * hd), sc_in, cdtype(cfg)),
        "wk": _normal(ks[1], lead + (d, kv * hd), sc_in, cdtype(cfg)),
        "wv": _normal(ks[2], lead + (d, kv * hd), sc_in, cdtype(cfg)),
        "wo": _normal(ks[3], lead + (h * hd, d), sc_out, cdtype(cfg)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(lead + (h * hd,), cdtype(cfg))
        p["bk"] = jnp.zeros(lead + (kv * hd,), cdtype(cfg))
        p["bv"] = jnp.zeros(lead + (kv * hd,), cdtype(cfg))
    return p


def _qkv(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"])
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"])
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return (q.reshape(b, s, h, hd), k.reshape(b, s, kv, hd),
            v.reshape(b, s, kv, hd))


def attn_project_out(p: dict, y: jnp.ndarray) -> jnp.ndarray:
    b, s, h, hd = y.shape
    return jnp.einsum("bsk,kd->bsd", y.reshape(b, s, h * hd), p["wo"])


def attn_train(p: dict, cfg: ModelConfig, x: jnp.ndarray, cos, sin,
               window: int | None = None, causal: bool = True) -> jnp.ndarray:
    """Full-sequence self-attention (training / prefill compute)."""
    q, k, v = _qkv(p, cfg, x)
    if cos is not None:
        q = ops.apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
        k = ops.apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])
    w = cfg.sliding_window if window is None else window
    y = ops.attention(q, k, v, causal=causal, window=w)
    return attn_project_out(p, y)


def attn_prefill(p: dict, cfg: ModelConfig, x: jnp.ndarray, cos, sin,
                 window: int | None = None):
    """Like attn_train but also returns (k, v) for cache insertion."""
    q, k, v = _qkv(p, cfg, x)
    if cos is not None:
        q = ops.apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
        k = ops.apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])
    w = cfg.sliding_window if window is None else window
    y = ops.attention(q, k, v, causal=True, window=w)
    return attn_project_out(p, y), k, v


def attn_decode(p: dict, cfg: ModelConfig, x1: jnp.ndarray, cos1, sin1,
                k_cache, v_cache, slot: jnp.ndarray, valid: jnp.ndarray):
    """One-token decode.  x1: (B, 1, d); k_cache/v_cache: (B, S, KV, hd);
    slot: () int32 — the cache slot to write (ring-buffered by the caller) —
    or (B,) int32 for per-sequence slots (continuous-batching serving, where
    every sequence sits at its own depth);
    valid: (B, S) bool — live cache slots AFTER insertion."""
    q, k, v = _qkv(p, cfg, x1)
    if cos1 is not None:
        q = ops.apply_rope(q, cos1[:, :, None, :], sin1[:, :, None, :])
        k = ops.apply_rope(k, cos1[:, :, None, :], sin1[:, :, None, :])
    if jnp.ndim(slot) == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, axis=1)
    else:
        rows = jnp.arange(k_cache.shape[0])
        k_cache = k_cache.at[rows, slot].set(k[:, 0])
        v_cache = v_cache.at[rows, slot].set(v[:, 0])
    y = ops.decode_attention(q, k_cache, v_cache, valid)
    return attn_project_out(p, y), k_cache, v_cache


def attn_decode_paged(p: dict, cfg: ModelConfig, x1: jnp.ndarray, cos1, sin1,
                      pool_k, pool_v, layer, tables, pos, block_size: int,
                      window: int):
    """One-token decode against the PAGED pool (continuous-batching serving).

    pool_k/pool_v: the stacked row pools of all layers (n, R, KV, hd) —
    read-only here; ``layer`` () int32 picks this layer's; no dense
    per-slot cache view is ever built.  tables: (S, MB) int32 block table;
    pos: (S,) int32 cached rows per slot.  Returns the attention output plus
    this token's (k, v) rows (S, KV, hd) for the engine to scatter into the
    pool after the step."""
    q, k, v = _qkv(p, cfg, x1)
    if cos1 is not None:
        q = ops.apply_rope(q, cos1[:, :, None, :], sin1[:, :, None, :])
        k = ops.apply_rope(k, cos1[:, :, None, :], sin1[:, :, None, :])
    y = ops.paged_decode_attention(q, k[:, 0], v[:, 0], pool_k, pool_v, layer,
                                   tables, pos, block_size=block_size,
                                   window=window)
    return attn_project_out(p, y), k[:, 0], v[:, 0]


def attn_prefill_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray, cos, sin,
                       pool_k, pool_v, table, start, block_size: int,
                       window: int | None = None):
    """Continuation prefill of one CHUNK for ONE slot against the paged pool
    (chunked prefill / prefix-shared admission).  x: (1, C, d) chunk hidden
    states at global positions ``start + i``; pool_k/pool_v: (R, KV, hd) one
    layer's row pool (read-only here); table: (MB,) int32 the slot's block
    row; start: () int32 rows already resident.  Returns (out, k, v) like
    ``attn_prefill`` — the caller scatters k/v into the pool afterwards."""
    q, k, v = _qkv(p, cfg, x)
    if cos is not None:
        q = ops.apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
        k = ops.apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])
    w = cfg.sliding_window if window is None else window
    y = ops.chunk_prefill_attention(q, k, v, pool_k, pool_v, table, start,
                                    block_size=block_size, window=w)
    return attn_project_out(p, y), k, v


def cross_attn_decode(p: dict, cfg: ModelConfig, x1: jnp.ndarray,
                      k_cache, v_cache):
    """Cross-attention decode against a static (encoder) cache."""
    b, _, _ = x1.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dk->bsk", x1, p["wq"]).reshape(b, 1, h, hd)
    valid = jnp.ones(k_cache.shape[:2], bool)
    y = ops.decode_attention(q, k_cache, v_cache, valid)
    return attn_project_out(p, y)


def cross_attn_train(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                     enc_k, enc_v) -> jnp.ndarray:
    """Full-sequence cross attention (no mask — encoder is fully visible)."""
    b, s, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"]).reshape(b, s, h, hd)
    y = ops.attention(q, enc_k, enc_v, causal=False, window=0)
    return attn_project_out(p, y)


def cross_kv(p: dict, cfg: ModelConfig, enc_out: jnp.ndarray):
    b, s, _ = enc_out.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,dk->bsk", enc_out, p["wk"]).reshape(b, s, kv, hd)
    v = jnp.einsum("bsd,dk->bsk", enc_out, p["wv"]).reshape(b, s, kv, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(cfg: ModelConfig, key, layers: int | None = None,
             d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = cfg.d_ff if d_ff is None else d_ff
    lead = () if layers is None else (layers,)
    ks = jax.random.split(key, 3)
    p = {
        "w_up": _normal(ks[1], lead + (d, f), 1 / np.sqrt(d), cdtype(cfg)),
        "w_down": _normal(ks[2], lead + (f, d), 1 / np.sqrt(f), cdtype(cfg)),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = _normal(ks[0], lead + (d, f), 1 / np.sqrt(d), cdtype(cfg))
    return p


def mlp_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if cfg.mlp_type == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        hidden = ops.swiglu(gate, up)
    else:
        hidden = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", hidden, p["w_down"])


# ---------------------------------------------------------------------------
# embeddings / positions
# ---------------------------------------------------------------------------

def embed_init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 2)
    p = {"embed": _normal(ks[0], (cfg.vocab_size, cfg.d_model), 1.0,
                          cdtype(cfg))}
    if not cfg.tie_embeddings:
        p["lm_head"] = _normal(
            ks[1], (cfg.d_model, cfg.vocab_size),
            1 / np.sqrt(cfg.d_model), cdtype(cfg))
    return p


def constrain_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Pin the leading (batch) dim of an activation to the data axes.

    Without this XLA's sharding propagation can settle on batch-REPLICATED
    activations (measured: qwen1.5-110b train kept the full global batch on
    every device — §Perf log); one constraint at the embedding anchors the
    whole layer stack."""
    from jax.sharding import PartitionSpec as P

    mesh = ops.ambient_mesh()
    if mesh is None:
        return x
    sizes = dict(mesh.shape)
    axes = tuple(a for a in ("pod", "data") if a in sizes)
    n = 1
    for a in axes:
        n *= sizes[a]
    if n > 1 and x.shape[0] % n == 0:
        spec = P(axes if len(axes) > 1 else axes[0],
                 *([None] * (x.ndim - 1)))
        return ops._maybe_constrain(x, spec)
    return x


def embed_tokens(p: dict, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    return constrain_batch(jnp.take(p["embed"], tokens, axis=0))


def unembed(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head)


def sinusoid_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """Whisper-style sinusoidal embeddings.  positions: (...,) int32."""
    half = d_model // 2
    freqs = jnp.exp(-np.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / (half - 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rope_for(cfg: ModelConfig, positions: jnp.ndarray):
    """cos/sin for standard RoPE, or None for non-RoPE models."""
    if cfg.rope_theta <= 0:
        return None, None
    return ops.rope_tables(positions, cfg.head_dim, cfg.rope_theta)


def mrope_for(cfg: ModelConfig, positions3: jnp.ndarray):
    return ops.mrope_tables(positions3, cfg.head_dim, cfg.rope_theta,
                            cfg.mrope_sections)
