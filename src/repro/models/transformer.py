"""Dense decoder-only transformer (families: dense, vlm).

Layers are scanned (stacked params) so 80-layer configs lower to O(1) HLO.
Supports GQA, RoPE / M-RoPE (vlm), QKV bias, sliding-window attention, and a
ring-buffered KV cache for long-context decode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 6)
    n = cfg.num_layers
    return {
        **L.embed_init(cfg, ks[0]),
        "layers": {
            "ln1": L.norm_init(cfg, cfg.d_model, n),
            "attn": L.attn_init(cfg, ks[1], n),
            "ln2": L.norm_init(cfg, cfg.d_model, n),
            "mlp": L.mlp_init(cfg, ks[2], n),
        },
        "ln_f": L.norm_init(cfg, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def _positions(cfg: ModelConfig, b: int, s: int, offset=0):
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.arch_type == "vlm":
        # M-RoPE: vision prefix laid out on a (t=0, h, w) grid, text sequential.
        p = cfg.vision_tokens
        side = max(int(p ** 0.5), 1)
        idx = jnp.arange(s, dtype=jnp.int32)
        is_vis = idx < p
        t = jnp.where(is_vis, 0, idx)
        h = jnp.where(is_vis, idx // side, idx)
        w = jnp.where(is_vis, idx % side, idx)
        pos3 = jnp.stack([t, h, w])[:, None, :] + offset
        return jnp.broadcast_to(pos3, (3, b, s))
    return pos


def _rope(cfg: ModelConfig, positions):
    if cfg.arch_type == "vlm":
        return L.mrope_for(cfg, positions)
    return L.rope_for(cfg, positions)


def _decode_pos_valid(cfg: ModelConfig, pos, b: int, cap: int):
    """Normalize a decode position — () shared by the batch (synchronized
    rollout) or (B,) per-sequence (continuous-batching serving) — into
    (offset for _positions, write slot, (B, cap) validity mask)."""
    pos = jnp.asarray(pos, jnp.int32)
    offset = pos if pos.ndim == 0 else pos[:, None]
    slot = jax.lax.rem(pos, cap)
    ar = jnp.arange(cap)
    pcol = pos if pos.ndim == 0 else pos[:, None]
    valid = ar <= pcol  # ring overwrite keeps this exact for cap == window
    if cfg.sliding_window > 0 and cap > cfg.sliding_window:
        valid &= ar > pcol - cfg.sliding_window
    valid = jnp.broadcast_to(valid if pos.ndim else valid[None], (b, cap))
    return offset, slot, valid


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------

def _layer_train(cfg, lp, x, cos, sin):
    x = x + L.attn_train(lp["attn"], cfg, L.norm_apply(lp["ln1"], cfg, x),
                         cos, sin)
    x = x + L.mlp_apply(lp["mlp"], cfg, L.norm_apply(lp["ln2"], cfg, x))
    return x


def _embed_in(params, cfg, batch):
    x = L.embed_tokens(params, cfg, batch["tokens"])
    if cfg.arch_type == "vlm" and "vision_embeds" in batch:
        p = batch["vision_embeds"].shape[1]
        x = jnp.concatenate(
            [batch["vision_embeds"].astype(x.dtype), x[:, p:]], axis=1)
        x = L.constrain_batch(x)   # re-anchor: concat drops the constraint
    return x


# ---------------------------------------------------------------------------
# train / prefill / decode
# ---------------------------------------------------------------------------

def forward_hidden(params: dict, cfg: ModelConfig, batch: dict) -> jnp.ndarray:
    """Final hidden states (B, S, d) — used by the PPO critic value head."""
    x = _embed_in(params, cfg, batch)
    b, s, _ = x.shape
    cos, sin = _rope(cfg, _positions(cfg, b, s))

    def body(h, lp):
        return _layer_train(cfg, lp, h, cos, sin), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return L.norm_apply(params["ln_f"], cfg, x)


def forward(params: dict, cfg: ModelConfig, batch: dict) -> jnp.ndarray:
    x = forward_hidden(params, cfg, batch)
    # logits stay in the compute dtype: an f32 cast here would seed f32
    # cotangents through the WHOLE backward residual chain (§Perf log).
    return L.unembed(params, cfg, x)


def init_cache(cfg: ModelConfig, batch: int, capacity: int) -> dict:
    kv, hd, n = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    dt = L.cdtype(cfg)
    return {
        "k": jnp.zeros((n, batch, capacity, kv, hd), dt),
        "v": jnp.zeros((n, batch, capacity, kv, hd), dt),
    }


def prefill(params: dict, cfg: ModelConfig, batch: dict, cache: dict,
            last=None):
    """Ingest the prompt; returns (last-token logits, filled cache).

    ``last`` (traced () int32, optional) selects which position's logits to
    return instead of the final one — the serving engine's bucketed admission
    prefill right-pads prompts to a power-of-2 length and needs the logits of
    the last REAL token (causality keeps rows < ``last`` + their KV
    bit-identical to an unpadded prefill)."""
    x = _embed_in(params, cfg, batch)
    b, s, _ = x.shape
    cap = cache["k"].shape[2]
    cos, sin = _rope(cfg, _positions(cfg, b, s))

    def body(h, lp):
        y, k, v = L.attn_prefill(lp["attn"], cfg,
                                 L.norm_apply(lp["ln1"], cfg, h), cos, sin)
        h = h + y
        h = h + L.mlp_apply(lp["mlp"], cfg, L.norm_apply(lp["ln2"], cfg, h))
        # store last `cap` positions (ring semantics when cap < s)
        k = k[:, -cap:] if s >= cap else jnp.pad(
            k, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
        v = v[:, -cap:] if s >= cap else jnp.pad(
            v, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
        return h, (k, v)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    xl = x[:, -1:] if last is None else jax.lax.dynamic_slice_in_dim(
        x, last, 1, axis=1)
    x = L.norm_apply(params["ln_f"], cfg, xl)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, {"k": ks, "v": vs}


def prefill_paged(params: dict, cfg: ModelConfig, pool_k: jnp.ndarray,
                  pool_v: jnp.ndarray, table: jnp.ndarray,
                  tokens: jnp.ndarray, start, *, block_size: int, last):
    """Continuation prefill of one CHUNK for one serving slot.

    tokens: (1, C) the chunk (right-padded to a bucket); start: () int32 —
    KV rows already resident for this slot (prefix-shared blocks and/or
    earlier chunks); table: (MB,) int32 the slot's block-table row; ``last``:
    () int32 — index WITHIN the chunk whose logits to return (the engine
    only consumes them on the final chunk, to sample the first token).

    Returns (logits (1, V) f32, k_rows (n, C, kv, hd), v_rows) — the caller
    scatters the chunk's KV rows into the pool, exactly like ``decode_paged``
    returns one token's rows.  Row content is bitwise identical to the same
    rows of a whole-prompt ``prefill`` on the jnp attention path (see
    ``kernels.ops.chunk_prefill_attention``), which is what lets prefix
    sharing + chunked prefill preserve the serving engine's greedy
    bit-compatibility with ``RolloutEngine``."""
    x = _embed_in(params, cfg, {"tokens": tokens})
    b, c, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    cos, sin = _rope(cfg, _positions(cfg, b, c, offset=start))

    def body(h, xs):
        lp, pk, pv = xs
        y, k1, v1 = L.attn_prefill_paged(lp["attn"], cfg,
                                         L.norm_apply(lp["ln1"], cfg, h),
                                         cos, sin, pk, pv, table, start,
                                         block_size)
        h = h + y
        h = h + L.mlp_apply(lp["mlp"], cfg, L.norm_apply(lp["ln2"], cfg, h))
        return h, (k1, v1)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], pool_k, pool_v))
    xl = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    x = L.norm_apply(params["ln_f"], cfg, xl)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, ks[:, 0], vs[:, 0]


def paged_window(cfg: ModelConfig, cap: int) -> int:
    """Effective sliding window for a paged decode over a logical capacity of
    ``cap`` rows — mirrors ``_decode_pos_valid``'s static gate, which only
    applies the window once the cache could outlive it."""
    return (cfg.sliding_window
            if cfg.sliding_window > 0 and cap > cfg.sliding_window else 0)


def decode_paged(params: dict, cfg: ModelConfig, pool_k: jnp.ndarray,
                 pool_v: jnp.ndarray, tables: jnp.ndarray,
                 tokens: jnp.ndarray, pos: jnp.ndarray, *, block_size: int):
    """One decode step against the PAGED KV pool (continuous-batching
    serving).  tokens: (S, 1); pos: (S,) int32 per-slot cached rows;
    pool_k/pool_v: (n, R, kv, hd) row pools; tables: (S, MB) int32.  The
    layer scan carries a layer index, not pool slices: each layer's
    attention takes the stacked pools whole and reads its own pages.

    Returns (logits, new_k, new_v) where new_k/new_v (n, S, kv, hd) are this
    token's KV rows for the engine to scatter into the pool — the model
    never materializes a dense per-slot cache view (contrast ``decode``,
    which consumes one; that path remains for the synchronized rollout
    engine and as the serving bit-compatibility oracle)."""
    x = L.embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    window = paged_window(cfg, tables.shape[1] * block_size)
    cos, sin = _rope(cfg, _positions(cfg, b, 1, offset=pos[:, None]))

    def body(h, xs):
        lp, layer = xs
        y, k1, v1 = L.attn_decode_paged(lp["attn"], cfg,
                                        L.norm_apply(lp["ln1"], cfg, h),
                                        cos, sin, pool_k, pool_v, layer,
                                        tables, pos, block_size, window)
        h = h + y
        h = h + L.mlp_apply(lp["mlp"], cfg, L.norm_apply(lp["ln2"], cfg, h))
        return h, (k1, v1)

    layers = jnp.arange(pool_k.shape[0], dtype=jnp.int32)
    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], layers))
    x = L.norm_apply(params["ln_f"], cfg, x)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, ks, vs


def decode(params: dict, cfg: ModelConfig, cache: dict, tokens: jnp.ndarray,
           pos: jnp.ndarray):
    """One decode step.  tokens: (B, 1); pos: () int32 — absolute position of
    the incoming token (same for the whole batch; synchronized RL rollout) —
    or (B,) int32 per-sequence positions (continuous-batching serving).
    """
    x = L.embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    cap = cache["k"].shape[2]
    offset, slot, valid = _decode_pos_valid(cfg, pos, b, cap)
    cos, sin = _rope(cfg, _positions(cfg, b, 1, offset=offset))

    def body(h, xs):
        lp, kc, vc = xs
        y, kc, vc = L.attn_decode(lp["attn"], cfg,
                                  L.norm_apply(lp["ln1"], cfg, h),
                                  cos, sin, kc, vc, slot, valid)
        h = h + y
        h = h + L.mlp_apply(lp["mlp"], cfg, L.norm_apply(lp["ln2"], cfg, h))
        return h, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                         cache["v"]))
    x = L.norm_apply(params["ln_f"], cfg, x)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, {"k": ks, "v": vs}
