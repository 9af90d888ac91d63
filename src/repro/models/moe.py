"""Mixture-of-Experts decoder (mixtral-8x7b, llama4-maverick, qwen3-moe).

Two expert paths:

* Training (``forward``) runs ``moe_apply``: capacity-based dispatch by
  default (the TPU-idiomatic dense-einsum form used by t5x/MaxText
  "dropping" MoE): tokens are split into groups of ``_MOE_GROUP`` along the
  sequence, each group computes a top-k one-hot dispatch tensor of shape
  (group, E, capacity) and the expert FFN runs as an einsum over
  (E, capacity) token slots — compiled FLOPs scale with ACTIVE tokens
  (× capacity_factor), not with E.  Expert dims shard over the mesh
  "model" axis (EP); XLA emits the all-to-all-equivalent resharding
  collectives.  ``cfg.moe_impl == "gmm"`` selects the dropless path instead.
* Serving (``prefill``, ``prefill_paged``, ``decode_paged``, ``decode``) is
  always dropless: every routed row goes through the grouped matmul
  (``kernels/gmm.py``), whatever ``moe_impl`` says, so prefill, chunked
  prefill and decode compute each token's expert output alike.

A layer may hold a share of the experts (``cfg.moe_held``, expert
parallelism's share on one chip): the router keeps its width
(``num_experts``) and its experts per token; the expert tables hold
``count`` experts, and the layer adds only the terms of the picks it
holds.  What the absent experts would add is left out, and that partial
sum goes on to the next layer.  Picks of experts not held are never
computed.  ``prefill_paged`` and ``decode_paged`` (and ``prefill`` with
``moe_stats=True``) return, last, the expert layers' work as int32 counts in
``STATS`` order, summed over layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T

_MOE_GROUP = 256
# the counts the dropless path returns: rows routed to experts held here,
# rows the grouped matmul computes (padding included), expert-layer
# applications, and held experts with a row (whose weights are read)
STATS = ("rows", "padded_rows", "calls", "experts")
_GMM_WEIGHT_BLOCK = 4 * 1024 * 1024   # bytes of one expert's weight block
# the expert tables, which the serving paths' layer scans leave stacked: the
# grouped matmul reads a layer's table in place, where the scan's slice
# would copy the whole table every layer (a slice cannot fuse into a kernel)
TABLES = ("w_gate", "w_up", "w_down")


def moe_init(cfg: ModelConfig, key, layers: int) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    _, n = cfg.experts_held
    ks = jax.random.split(key, 4)
    return {
        "router": L._normal(ks[0], (layers, d, e), 1 / np.sqrt(d), jnp.float32),
        "w_gate": L._normal(ks[1], (layers, n, d, f), 1 / np.sqrt(d),
                            L.cdtype(cfg)),
        "w_up": L._normal(ks[2], (layers, n, d, f), 1 / np.sqrt(d),
                          L.cdtype(cfg)),
        "w_down": L._normal(ks[3], (layers, n, f, d), 1 / np.sqrt(f),
                            L.cdtype(cfg)),
    }


def _route(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """x: (B, S, d) -> (gates (B,S,k) f32, idx (B,S,k), probs (B,S,E)):
    softmax over every expert, top-k, gates renormalised over the k."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx, probs


def _aux_loss(cfg: ModelConfig, probs: jnp.ndarray, idx: jnp.ndarray):
    """Switch-style load-balance loss."""
    e = cfg.num_experts
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)      # (B,S,k,E)
    ce = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))     # fraction routed
    return e * jnp.sum(me * ce)


def _local(cfg: ModelConfig, idx: jnp.ndarray) -> jnp.ndarray:
    """Expert ids relative to the first expert held here."""
    first, _ = cfg.experts_held
    return idx - first if first else idx


def _gmm_tile(picks: int, experts: int) -> int:
    """Row tile of the grouped matmul: twice the mean rows an expert gets,
    a power of two in [16, 128] (16: bfloat16's sublane tile)."""
    mean = max(picks // max(experts, 1), 1)
    return int(min(128, max(16, 2 ** int(np.ceil(np.log2(2 * mean))))))


def _gmm_block_f(cfg: ModelConfig, rows_in: int, cols: int) -> int:
    """Output columns a grouped-matmul step takes: all of them where one
    expert's (rows_in, cols) weights fit ``_GMM_WEIGHT_BLOCK``, else 512."""
    fits = rows_in * cols * L.cdtype(cfg).itemsize <= _GMM_WEIGHT_BLOCK
    return cols if fits else 512


def _dropless(p: dict, cfg: ModelConfig, xt: jnp.ndarray, gates, idx,
              layer=0):
    """Dropless expert FFN over the experts held here (the paper's GMM).

    xt: (t, d); gates, idx: (t, k) routing over all ``num_experts``; the
    expert tables in ``p`` are one layer's, or the layer stack read at
    ``layer``.  The
    picks of held experts are sorted by expert into a buffer sized for the
    static worst case (every pick held, each group padded to the tile),
    run through three grouped matmuls that compute only the tiles the
    groups fill, and combined with their gates in float32; picks of
    experts not held add nothing.  Returns (y (t, d), counts in STATS
    order (4,) int32)."""
    from repro.kernels import ops

    t, d = xt.shape
    k, f = cfg.experts_per_token, cfg.d_ff
    _, n = cfg.experts_held
    tile = _gmm_tile(t * k, cfg.num_experts)
    cap = -(-(t * k + n * (tile - 1)) // tile) * tile       # static rows
    local = _local(cfg, idx).reshape(t * k)
    held = (local >= 0) & (local < n)
    gid = jnp.where(held, local, n)                         # n: not here
    sizes = jnp.zeros((n + 1,), jnp.int32).at[gid].add(1)[:n]
    padded = -(-sizes // tile) * tile
    order = jnp.argsort(gid, stable=True)
    sgid = gid[order]
    starts = jnp.cumsum(padded) - padded                    # padded offsets
    firsts = jnp.cumsum(sizes) - sizes                      # unpadded
    g = jnp.minimum(sgid, n - 1)
    row = jnp.where(sgid < n, starts[g] + jnp.arange(t * k) - firsts[g], cap)
    xs = jnp.zeros((cap, d), xt.dtype).at[row].set(xt[order // k],
                                                   mode="drop")
    bf_in = _gmm_block_f(cfg, d, f)
    gate = ops.gmm(xs, p["w_gate"], padded, layer, tile_t=tile,
                   block_f=bf_in)
    up = ops.gmm(xs, p["w_up"], padded, layer, tile_t=tile, block_f=bf_in)
    h = ops.swiglu(gate, up)
    ys = ops.gmm(h, p["w_down"], padded, layer, tile_t=tile,
                 block_f=_gmm_block_f(cfg, f, d))
    dest = jnp.zeros((t * k,), jnp.int32).at[order].set(row)
    # rows past the filled tiles are never read: a pick not held reads
    # the fill value
    yk = ys.at[dest].get(mode="fill", fill_value=0).astype(jnp.float32)
    y = jnp.einsum("tkd,tk->td", yk.reshape(t, k, d), gates.reshape(t, k),
                   precision=jax.lax.Precision.HIGHEST)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(padded), jnp.int32(1),
                       jnp.sum(sizes > 0)]).astype(jnp.int32)
    return y.astype(xt.dtype), stats


def expert_ffn(p: dict, cfg: ModelConfig, x: jnp.ndarray, layer=0):
    """The serving paths' expert FFN, dropless.  x: (B, S, d) -> (y, counts
    in STATS order); ``p``'s tables as ``_dropless`` takes them."""
    b, s, d = x.shape
    gates, idx, _ = _route(p, cfg, x)
    y, stats = _dropless(p, cfg, x.reshape(b * s, d),
                         gates.reshape(b * s, -1), idx.reshape(b * s, -1),
                         layer)
    return y.reshape(b, s, d), stats


def moe_apply_gmm(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Dropless expert FFN (``moe_impl == "gmm"``).  x: (B, S, d) ->
    (y, aux_loss)."""
    b, s, d = x.shape
    gates, idx, probs = _route(p, cfg, x)
    y, _ = _dropless(p, cfg, x.reshape(b * s, d), gates.reshape(b * s, -1),
                     idx.reshape(b * s, -1))
    return y.reshape(b, s, d), _aux_loss(cfg, probs, idx)


def moe_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Training's expert FFN.  x: (B, S, d) -> (y, aux_loss).  Dispatch-form
    (capacity einsum) by default; ``cfg.moe_impl == "gmm"`` selects the
    dropless grouped-matmul path.  Picks of experts not held here, and picks
    over an expert's capacity, are dropped."""
    if cfg.moe_impl == "gmm":
        return moe_apply_gmm(p, cfg, x)
    b, s, d = x.shape
    e, k, f = cfg.num_experts, cfg.experts_per_token, cfg.d_ff
    _, n = cfg.experts_held
    g = min(_MOE_GROUP, s)
    while s % g:
        g //= 2
    ng = s // g
    cap = max(int(np.ceil(k * g * cfg.moe_capacity_factor / e)), 1)

    gates, idx, probs = _route(p, cfg, x)
    aux = _aux_loss(cfg, probs, idx)
    gates = gates.astype(x.dtype)

    xg = x.reshape(b * ng, g, d)
    gates = gates.reshape(b * ng, g, k)
    idx = _local(cfg, idx).reshape(b * ng, g, k)

    onehot = jax.nn.one_hot(idx, n, dtype=jnp.int32)        # (n, g, k, E)
    pos = jnp.cumsum(onehot.reshape(b * ng, g * k, n), axis=1).reshape(
        b * ng, g, k, n) * onehot - 1                       # slot per (tok,k)
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


def _no_stats():
    return jnp.zeros((len(STATS),), jnp.int32)


def _scanned(params: dict):
    """What a serving path's layer scan slices: the layer tree without the
    expert tables, and each layer's index; and the stacked tables."""
    layers = dict(params["layers"])
    moe = dict(layers["moe"])
    tables = {name: moe.pop(name) for name in TABLES}
    n = moe["router"].shape[0]
    return ({**layers, "moe": moe}, jnp.arange(n, dtype=jnp.int32)), tables


def _ffn(lp: dict, cfg: ModelConfig, h, stats, tables: dict, layer):
    """The residual add of a serving layer's expert FFN, reading layer
    ``layer`` of the stacked ``tables``, and its counts added to
    ``stats``."""
    y, st = expert_ffn({**lp["moe"], **tables}, cfg,
                       L.norm_apply(lp["ln2"], cfg, h), layer)
    return h + y, stats + st


def prefill(params: dict, cfg: ModelConfig, batch: dict, cache: dict,
            last=None, *, moe_stats: bool = False):
    x = L.embed_tokens(params, cfg, batch["tokens"])
    b, s, _ = x.shape
    cap = cache["k"].shape[2]
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, s))

    scanned, tables = _scanned(params)

    def body(carry, xs):
        h, st = carry
        lp, layer = xs
        y, kk, vv = L.attn_prefill(lp["attn"], cfg,
                                   L.norm_apply(lp["ln1"], cfg, h), cos, sin)
        h, st = _ffn(lp, cfg, h + y, st, tables, layer)
        kk = kk[:, -cap:] if s >= cap else jnp.pad(
            kk, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
        vv = vv[:, -cap:] if s >= cap else jnp.pad(
            vv, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
        return (h, st), (kk, vv)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, st), (ks, vs) = jax.lax.scan(body, (x, _no_stats()), scanned)
    xl = x[:, -1:] if last is None else jax.lax.dynamic_slice_in_dim(
        x, last, 1, axis=1)
    x = L.norm_apply(params["ln_f"], cfg, xl)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    out = (logits, {"k": ks, "v": vs})
    return (*out, st) if moe_stats else out


def prefill_paged(params: dict, cfg: ModelConfig, pool_k: jnp.ndarray,
                  pool_v: jnp.ndarray, table: jnp.ndarray,
                  tokens: jnp.ndarray, start, *, block_size: int, last):
    """Continuation prefill of one chunk — the MoE twin of
    ``transformer.prefill_paged`` (expert FFN instead of the dense MLP).
    The expert FFN is dropless and per token, so a chunk computes each
    token's expert output as a whole-prompt prefill does.  Returns
    (logits, ks, vs, expert counts)."""
    x = L.embed_tokens(params, cfg, tokens)
    b, c, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, c, offset=start))

    (layers, layer_ids), tables = _scanned(params)

    def body(carry, xs):
        h, st = carry
        lp, layer, pk, pv = xs
        y, k1, v1 = L.attn_prefill_paged(lp["attn"], cfg,
                                         L.norm_apply(lp["ln1"], cfg, h),
                                         cos, sin, pk, pv, table, start,
                                         block_size)
        return _ffn(lp, cfg, h + y, st, tables, layer), (k1, v1)

    (x, st), (ks, vs) = jax.lax.scan(body, (x, _no_stats()),
                                     (layers, layer_ids, pool_k, pool_v))
    xl = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    x = L.norm_apply(params["ln_f"], cfg, xl)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, ks[:, 0], vs[:, 0], st


def decode_paged(params: dict, cfg: ModelConfig, pool_k: jnp.ndarray,
                 pool_v: jnp.ndarray, tables: jnp.ndarray,
                 tokens: jnp.ndarray, pos: jnp.ndarray, *, block_size: int):
    """One decode step against the paged KV pool — the MoE twin of
    ``transformer.decode_paged`` (expert FFN instead of the dense MLP).
    Returns (logits, new_k, new_v, expert counts); no dense cache view is
    materialized."""
    x = L.embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    window = T.paged_window(cfg, tables.shape[1] * block_size)
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, 1, offset=pos[:, None]))

    scanned, experts = _scanned(params)

    def body(carry, xs):
        h, st = carry
        lp, layer = xs
        y, k1, v1 = L.attn_decode_paged(lp["attn"], cfg,
                                        L.norm_apply(lp["ln1"], cfg, h),
                                        cos, sin, pool_k, pool_v, layer,
                                        tables, pos, block_size, window)
        return _ffn(lp, cfg, h + y, st, experts, layer), (k1, v1)

    (x, st), (ks, vs) = jax.lax.scan(body, (x, _no_stats()), scanned)
    x = L.norm_apply(params["ln_f"], cfg, x)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, ks, vs, st


def decode(params: dict, cfg: ModelConfig, cache: dict, tokens: jnp.ndarray,
           pos: jnp.ndarray):
    x = L.embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    cap = cache["k"].shape[2]
    offset, slot, valid = T._decode_pos_valid(cfg, pos, b, cap)
    cos, sin = L.rope_for(cfg, T._positions(cfg, b, 1, offset=offset))

    (layers, layer_ids), tables = _scanned(params)

    def body(carry, xs):
        h, st = carry
        lp, layer, kc, vc = xs
        y, kc, vc = L.attn_decode(lp["attn"], cfg,
                                  L.norm_apply(lp["ln1"], cfg, h),
                                  cos, sin, kc, vc, slot, valid)
        return _ffn(lp, cfg, h + y, st, tables, layer), (kc, vc)

    (x, _), (ks, vs) = jax.lax.scan(body, (x, _no_stats()),
                                    (layers, layer_ids, cache["k"],
                                     cache["v"]))
    x = L.norm_apply(params["ln_f"], cfg, x)
    logits = L.unembed(params, cfg, x)[:, 0].astype(jnp.float32)
    return logits, {"k": ks, "v": vs}
