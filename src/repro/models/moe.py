"""Mixture-of-Experts decoder (mixtral-8x7b, llama4-maverick, qwen3-moe).

Routing is capacity-based dispatch (the TPU-idiomatic dense-einsum form used
by t5x/MaxText "dropping" MoE): tokens are split into groups of
``_MOE_GROUP`` along the sequence, each group computes a top-k one-hot
dispatch tensor of shape (group, E, capacity) and the expert FFN runs as an
einsum over (E, capacity) token slots — so compiled FLOPs scale with ACTIVE
tokens (× capacity_factor), not with E.  Expert dims shard over the mesh
"model" axis (EP); XLA emits the all-to-all-equivalent resharding collectives.

Small-batch decode (b·k << E, e.g. long_500k top-1) switches to a
weight-gather path: reading k experts' weights per token is the true
memory-bound cost; the dense dispatch form would overcount FLOPs by E/(b·k).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T

_MOE_GROUP = 256


def moe_init(cfg: ModelConfig, key, layers: int) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": L._normal(ks[0], (layers, d, e), 1 / np.sqrt(d), jnp.float32),
        "w_gate": L._normal(ks[1], (layers, e, d, f), 1 / np.sqrt(d),
                            L.cdtype(cfg)),
        "w_up": L._normal(ks[2], (layers, e, d, f), 1 / np.sqrt(d),
                          L.cdtype(cfg)),
        "w_down": L._normal(ks[3], (layers, e, f, d), 1 / np.sqrt(f),
                            L.cdtype(cfg)),
    }


def _route(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """x: (B, S, d) -> (gates (B,S,k), idx (B,S,k), probs (B,S,E))."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates.astype(x.dtype), idx, probs


def _aux_loss(cfg: ModelConfig, probs: jnp.ndarray, idx: jnp.ndarray):
    """Switch-style load-balance loss."""
    e = cfg.num_experts
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)      # (B,S,k,E)
    ce = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))     # fraction routed
    return e * jnp.sum(me * ce)


def moe_apply_gmm(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Dropless expert FFN via grouped matmul (the paper's GMM kernel).

    Tokens are replicated per selected expert, sorted by expert id with
    group boundaries padded to the GMM tile, run through three grouped
    matmuls, then un-permuted and gate-combined.  No capacity drops — exact
    routing — at the cost of data-dependent padding (<= E*tile rows)."""
    from repro.kernels import ops
    from repro.kernels.gmm import pad_groups

    b, s, d = x.shape
    e, k, f = cfg.num_experts, cfg.experts_per_token, cfg.d_ff
    gates, idx, probs = _route(p, cfg, x)
    aux = _aux_loss(cfg, probs, idx)
    t = b * s
    xt = x.reshape(t, d)
    xk = jnp.repeat(xt, k, axis=0)                       # (T*k, d)
    gid = idx.reshape(t * k)
    # NOTE: single-layer weights here — callers pass per-layer slices
    tile = 64
    xs, sizes, order, dest = pad_groups(xk, gid, e, tile_t=tile)
    gate = ops.gmm(xs, p["w_gate"], sizes, tile_t=tile)
    up = ops.gmm(xs, p["w_up"], sizes, tile_t=tile)
    h = ops.swiglu(gate, up)
    ys = ops.gmm(h, p["w_down"], sizes, tile_t=tile)
    yk = jnp.zeros((t * k, d), ys.dtype).at[order].set(ys[dest])
    y = jnp.einsum("tkd,tk->td", yk.reshape(t, k, d),
                   gates.reshape(t, k).astype(ys.dtype))
    return y.reshape(b, s, d), aux


def moe_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Expert FFN.  x: (B, S, d) -> (y, aux_loss).  Dispatch-form (capacity
    einsum) by default; ``cfg.moe_impl == "gmm"`` selects the dropless
    grouped-matmul path."""
    if cfg.moe_impl == "gmm":
        return moe_apply_gmm(p, cfg, x)
    b, s, d = x.shape
    e, k, f = cfg.num_experts, cfg.experts_per_token, cfg.d_ff
    g = min(_MOE_GROUP, s)
    while s % g:
        g //= 2
    ng = s // g
    cap = max(int(np.ceil(k * g * cfg.moe_capacity_factor / e)), 1)

    gates, idx, probs = _route(p, cfg, x)
    aux = _aux_loss(cfg, probs, idx)

    xg = x.reshape(b * ng, g, d)
    gates = gates.reshape(b * ng, g, k)
    idx = idx.reshape(b * ng, g, k)

    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)        # (n, g, k, E)
    pos = jnp.cumsum(onehot.reshape(b * ng, g * k, e), axis=1).reshape(
        b * ng, g, k, e) * onehot - 1                       # slot per (tok,k)
    keep = (pos >= 0) & (pos < cap)
    dispatch = (jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                               dtype=x.dtype)[..., :cap]
                * onehot[..., None].astype(x.dtype))        # (n,g,k,E,C)
    combine = dispatch * gates[..., None, None]
    dispatch = jnp.sum(dispatch, axis=2)                    # (n,g,E,C)
    combine = jnp.sum(combine, axis=2)

    xe = jnp.einsum("ngec,ngd->necd", dispatch, xg)         # (n,E,C,d)
    gate = jnp.einsum("necd,edf->necf", xe, p["w_gate"])
    up = jnp.einsum("necd,edf->necf", xe, p["w_up"])
    h = L.ops.swiglu(gate.reshape(-1, f), up.reshape(-1, f)).reshape(gate.shape)
    ye = jnp.einsum("necf,efd->necd", h, p["w_down"])
    y = jnp.einsum("ngec,necd->ngd", combine, ye)
    return y.reshape(b, s, d), aux


def moe_decode_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """One-token expert FFN.  x: (B, 1, d)."""
    b, _, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    gates, idx, _ = _route(p, cfg, x)
    if b * k * 4 <= e:
        # weight-gather path: read only the selected experts' weights
        idxf = idx.reshape(b, k)
        wg = jnp.take(p["w_gate"], idxf, axis=0)            # (b,k,d,f)
        wu = jnp.take(p["w_up"], idxf, axis=0)
        wd = jnp.take(p["w_down"], idxf, axis=0)
        xt = x[:, 0]                                        # (b,d)
        gate = jnp.einsum("bd,bkdf->bkf", xt, wg)
        up = jnp.einsum("bd,bkdf->bkf", xt, wu)
        h = L.ops.swiglu(gate.reshape(b * k, -1),
                         up.reshape(b * k, -1)).reshape(gate.shape)
        yk = jnp.einsum("bkf,bkfd->bkd", h, wd)
        y = jnp.einsum("bkd,bk->bd", yk, gates[:, 0].astype(yk.dtype))
        return y[:, None]
    # dispatch path: group along the BATCH (one group of b tokens), so the
    # expert einsum costs E*C ~= b*k*cf token-slots, not b*E.
    y, _ = moe_apply(p, cfg, x.reshape(1, b, d))
    return y.reshape(b, 1, d)


# ---------------------------------------------------------------------------
# model API (reuses the dense skeleton, swapping the MLP for MoE)
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
            "moe": moe_init(cfg, ks[2], n),
        },
        "ln_f": L.norm_init(cfg, cfg.d_model),
    }


def forward(params: dict, cfg: ModelConfig, batch: dict):
    x = L.embed_tokens(params, cfg, batch["tokens"])
    b, s, _ = x.shape
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, s))

    def body(carry, lp):
        h, aux = carry
        h = h + L.attn_train(lp["attn"], cfg,
                             L.norm_apply(lp["ln1"], cfg, h), cos, sin)
        y, a = moe_apply(lp["moe"], cfg, L.norm_apply(lp["ln2"], cfg, h))
        return (h + y, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, 0.0), params["layers"])
    x = L.norm_apply(params["ln_f"], cfg, x)
    # compute-dtype logits: see transformer.forward (§Perf log)
    logits = L.unembed(params, cfg, x)
    return logits, aux / cfg.num_layers


init_cache = T.init_cache


def prefill(params: dict, cfg: ModelConfig, batch: dict, cache: dict,
            last=None):
    x = L.embed_tokens(params, cfg, batch["tokens"])
    b, s, _ = x.shape
    cap = cache["k"].shape[2]
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, s))

    def body(h, lp):
        y, kk, vv = L.attn_prefill(lp["attn"], cfg,
                                   L.norm_apply(lp["ln1"], cfg, h), cos, sin)
        h = h + y
        y, _ = moe_apply(lp["moe"], cfg, L.norm_apply(lp["ln2"], cfg, h))
        h = h + y
        kk = kk[:, -cap:] if s >= cap else jnp.pad(
            kk, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
        vv = vv[:, -cap:] if s >= cap else jnp.pad(
            vv, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
        return h, (kk, vv)

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
    """Continuation prefill of one chunk — the MoE twin of
    ``transformer.prefill_paged`` (expert FFN instead of the dense MLP).

    Caveat the dense twin does not have: capacity-based routing groups over
    the CHUNK length, so per-token expert outputs match a whole-prompt
    prefill exactly only while no token is capacity-dropped in either
    grouping (generous ``moe_capacity_factor``, as at smoke scale); routing
    itself is per-token and unaffected by chunking."""
    x = L.embed_tokens(params, cfg, tokens)
    b, c, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, c, offset=start))

    def body(h, xs):
        lp, pk, pv = xs
        y, k1, v1 = L.attn_prefill_paged(lp["attn"], cfg,
                                         L.norm_apply(lp["ln1"], cfg, h),
                                         cos, sin, pk, pv, table, start,
                                         block_size)
        h = h + y
        y2, _ = moe_apply(lp["moe"], cfg, L.norm_apply(lp["ln2"], cfg, h))
        h = h + y2
        return h, (k1, v1)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], pool_k, pool_v))
    xl = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    x = L.norm_apply(params["ln_f"], cfg, xl)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, ks[:, 0], vs[:, 0]


def decode_paged(params: dict, cfg: ModelConfig, pool_k: jnp.ndarray,
                 pool_v: jnp.ndarray, tables: jnp.ndarray,
                 tokens: jnp.ndarray, pos: jnp.ndarray, *, block_size: int):
    """One decode step against the paged KV pool — the MoE twin of
    ``transformer.decode_paged`` (expert FFN instead of the dense MLP).
    Returns (logits, new_k, new_v); no dense cache view is materialized."""
    x = L.embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    window = T.paged_window(cfg, tables.shape[1] * block_size)
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, 1, offset=pos[:, None]))

    def body(h, xs):
        lp, layer = xs
        y, k1, v1 = L.attn_decode_paged(lp["attn"], cfg,
                                        L.norm_apply(lp["ln1"], cfg, h),
                                        cos, sin, pool_k, pool_v, layer,
                                        tables, pos, block_size, window)
        h = h + y
        h = h + moe_decode_apply(lp["moe"], cfg,
                                 L.norm_apply(lp["ln2"], cfg, h))
        return h, (k1, v1)

    layers = jnp.arange(pool_k.shape[0], dtype=jnp.int32)
    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], layers))
    x = L.norm_apply(params["ln_f"], cfg, x)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, ks, vs


def decode(params: dict, cfg: ModelConfig, cache: dict, tokens: jnp.ndarray,
           pos: jnp.ndarray):
    x = L.embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    cap = cache["k"].shape[2]
    offset, slot, valid = T._decode_pos_valid(cfg, pos, b, cap)
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, 1, offset=offset))

    def body(h, xs):
        lp, kc, vc = xs
        y, kc, vc = L.attn_decode(lp["attn"], cfg,
                                  L.norm_apply(lp["ln1"], cfg, h),
                                  cos, sin, kc, vc, slot, valid)
        h = h + y
        h = h + moe_decode_apply(lp["moe"], cfg,
                                 L.norm_apply(lp["ln2"], cfg, h))
        return h, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                         cache["v"]))
    x = L.norm_apply(params["ln_f"], cfg, x)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, {"k": ks, "v": vs}
