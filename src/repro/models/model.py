"""Uniform model API over all architecture families.

    m = build_model(cfg)
    params             = m.init(cfg, key)
    logits, aux        = m.forward(params, cfg, batch)     # train (teacher forcing)
    cache              = m.init_cache(cfg, batch_size, capacity)
    logits, cache      = m.prefill(params, cfg, batch, cache)
    logits, cache      = m.decode(params, cfg, cache, tokens, pos)
    logits, k1, v1     = m.decode_paged(params, cfg, pool_k, pool_v, tables,
                                        tokens, pos, block_size=bs)  # serving
    logits, ks, vs     = m.prefill_paged(params, cfg, pool_k, pool_v, table,
                                         tokens, start, block_size=bs,
                                         last=n)  # serving chunked prefill

MoE's ``decode_paged`` and ``prefill_paged`` return the expert layers'
counts after these (``moe.STATS``); its ``prefill`` does with
``moe_stats=True``.

``batch`` is a dict: tokens (B, S) int32, plus family extras —
vision_embeds (B, P, d) for vlm, frames (B, enc_seq, d) for audio.
``aux`` is the MoE load-balance loss (0.0 elsewhere).
"""
from __future__ import annotations

import types

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, mamba2, moe, transformer


def _family(cfg: ModelConfig):
    return {
        "dense": transformer,
        "vlm": transformer,
        "moe": moe,
        "ssm": mamba2,
        "hybrid": hybrid,
        "audio": encdec,
    }[cfg.arch_type]


def build_model(cfg: ModelConfig) -> types.SimpleNamespace:
    fam = _family(cfg)

    def forward(params, cfg, batch):
        out = fam.forward(params, cfg, batch)
        if isinstance(out, tuple):
            return out
        return out, jnp.float32(0.0)

    return types.SimpleNamespace(
        init=fam.init,
        forward=forward,
        init_cache=fam.init_cache,
        prefill=fam.prefill,
        decode=fam.decode,
        # paged-pool entry points (serving) — transformer/moe only; other
        # families cache recurrent state and never page.  decode_paged is
        # the hot loop; prefill_paged is the chunk-continuation prefill
        # behind chunked prefill and prefix-shared admission
        decode_paged=getattr(fam, "decode_paged", None),
        prefill_paged=getattr(fam, "prefill_paged", None),
        family=fam,
    )


def init_params(cfg: ModelConfig, key):
    return build_model(cfg).init(cfg, key)


def init_cache(cfg: ModelConfig, batch: int, capacity: int):
    return build_model(cfg).init_cache(cfg, batch, capacity)
