"""Sharding rules: param-path -> PartitionSpec, per stage.

Two stages mirror the paper's resharding flow:

  * ``train`` (update stage)   — FSDP over "data" + TP/EP over "model";
    optimizer moments inherit the param spec (ZeRO is subsumed by FSDP).
  * ``gen`` (generation stage) — selectable layout:
      - "2d"  : same 2-D layout as train (weight-gathered decode; baseline)
      - "tp"  : TP over "model" only, replicated over "data" (no per-step
                weight allgather — for models that fit HBM)

The pair (train, gen) layouts being DIFFERENT is exactly what creates the
paper's resharding flow; ``core/resharding.py`` moves weights between them.

Rules are name-based over the param pytree paths; stacked (scanned) layers
are detected by rank (base rank + 1 leading layer axis).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def data_axes(mesh) -> tuple:
    """Batch-sharding axes: ("pod","data") on the multi-pod mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _mdl(mesh) -> int:
    return mesh.shape["model"]


# base (unstacked) dim specs per leaf name; "D"=fsdp axis, "M"=model axis.
# Resolved to mesh axes per stage.
_TABLE = {
    # embeddings
    "embed": ("M", "D"),
    "lm_head": ("D", "M"),
    # attention
    "wq": ("D", "M"), "wk": ("D", "M"), "wv": ("D", "M"),
    "bq": ("M",), "bk": ("M",), "bv": ("M",),
    "wo": ("M", "D"),
    # dense mlp
    "w_gate": ("D", "M"), "w_up": ("D", "M"), "w_down": ("M", "D"),
    # norms
    "scale": (None,), "bias": (None,),
    # mamba2
    "wz": ("D", "M"), "wx": ("D", "M"),
    "wB": ("D", None), "wC": ("D", None), "wdt": ("D", None),
    "dt_bias": (None,), "A_log": (None,), "D": (None,),
    "conv_wx": (None, "M"), "conv_bx": ("M",),
    "conv_wB": (None, None), "conv_bB": (None,),
    "conv_wC": (None, None), "conv_bC": (None,),
    "out_proj": ("M", "D"),
}

# MoE expert tables (under a "moe" parent). EP when E divides the model axis.
# FSDP ("D") must shard the NON-contracting dim of each expert matmul: putting
# it on the contraction dim forces an all-reduce of every expert output over
# the data axis (measured 17.9 TB/device on llama4 train_4k — §Perf log).
_TABLE_MOE_EP = {
    "router": ("D", None),
    "w_gate": ("M", None, "D"), "w_up": ("M", None, "D"),
    "w_down": ("M", "D", None),
}
_TABLE_MOE_TP = {
    "router": ("D", None),
    "w_gate": (None, "D", "M"), "w_up": (None, "D", "M"),
    "w_down": (None, "M", "D"),
}


def _resolve(dims, stage: str, mesh) -> P:
    """Map the symbolic ("D"/"M"/None) dims to mesh axes for a stage."""
    out = []
    for d in dims:
        if d == "M":
            out.append("model")
        elif d == "D":
            if stage == "train":
                out.append(data_axes(mesh) if len(data_axes(mesh)) > 1
                           else "data")
            else:  # gen "2d" keeps fsdp; "tp" replicates over data
                out.append("data" if stage == "gen2d" else None)
        else:
            out.append(None)
    return P(*out)


def _leaf_spec(path, leaf, cfg: ModelConfig, stage: str, mesh) -> P:
    keys = [getattr(k, "key", str(k)) for k in path]
    name = keys[-1]
    parent = keys[-2] if len(keys) > 1 else ""
    in_moe = "moe" in keys
    if in_moe:
        ep = cfg.experts_held[1] % _mdl(mesh) == 0   # expert tables
        table = _TABLE_MOE_EP if ep else _TABLE_MOE_TP
        dims = table.get(name)
    else:
        dims = _TABLE.get(name)
        if dims is None and parent in ("norm", "ln", "ln1", "ln2", "lnx",
                                       "ln_f", "enc_ln"):
            dims = (None,)
    if dims is None:
        dims = (None,) * leaf.ndim  # replicate unknown leaves
    ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
    extra = ndim - len(dims)
    if extra > 0:        # stacked layers / group dims -> leading None axes
        dims = (None,) * extra + tuple(dims)
    elif extra < 0:
        dims = tuple(dims)[-ndim:] if ndim else ()
    spec = _resolve(dims, stage, mesh)
    # never shard a dim the mesh axis cannot divide AND that is tiny
    fixed = []
    shape = getattr(leaf, "shape", ())
    for i, ax in enumerate(spec):
        if ax is None:
            fixed.append(None)
            continue
        size = mesh.shape[ax] if isinstance(ax, str) else 1
        if isinstance(ax, tuple):
            size = 1
            for a in ax:
                size *= mesh.shape[a]
        if shape and shape[i] % size != 0:
            fixed.append(None)   # jit arg shardings must divide evenly
        else:
            fixed.append(ax)
    return P(*fixed)


def param_specs(cfg: ModelConfig, params, mesh, stage: str = "train",
                gen_mode: str = "2d"):
    """Tree of PartitionSpec matching ``params`` (arrays or ShapeDtypeStruct).

    stage: "train" | "gen"; gen_mode: "2d" | "tp".
    """
    tag = "train" if stage == "train" else ("gen2d" if gen_mode == "2d"
                                            else "gen")
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path, leaf, cfg, tag, mesh), params)


def to_named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_partition(mesh, global_batch: int) -> P | None:
    """Spec for the leading batch dim (None when batch < axis size)."""
    axes = data_axes(mesh)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if global_batch % size == 0:
        return axes if len(axes) > 1 else axes[0]
    if global_batch % mesh.shape["data"] == 0:
        return "data"
    return None


def cache_specs(cfg: ModelConfig, cache, mesh):
    """Specs for a decode cache pytree (leaves have a leading layer axis and
    a batch axis second)."""
    def spec(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        name = keys[-1]
        b = leaf.shape[1]
        bax = batch_partition(mesh, b) if b > 1 else None
        mdl = mesh.shape["model"]
        if name in ("k", "v", "attn_k", "attn_v", "xk", "xv"):
            # (L, B, S, KV, hd): shard kv heads when they divide the model
            # axis; otherwise shard head_dim (contraction-sharded attention,
            # small all-reduce) rather than replicating the whole cache.
            if leaf.shape[3] % mdl == 0:
                return P(None, bax, None, "model", None)
            if leaf.shape[4] % mdl == 0:
                return P(None, bax, None, None, "model")
            return P(None, bax, None, None, None)
        if name == "ssm":
            # (L, B, H, P, N)
            hax = "model" if leaf.shape[2] % mdl == 0 else None
            return P(None, bax, hax, None, None)
        if name in ("x", "B", "C"):
            # conv states (L, B, k-1, D)
            dax = "model" if leaf.shape[3] % mdl == 0 else None
            return P(None, bax, None, dax)
        return P(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(spec, cache)
