"""Fused rotate-half RoPE application Pallas TPU kernel.

x: (B, S, H, d) with cos/sin (B, S, d//2); the rotation is applied in one
VMEM pass per (batch, seq-block) tile across all heads.

Backward: ``jax.custom_vjp`` whose backward is the VJP of the same f32 math
in jnp (``_rope_math``), recomputed from the saved inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Deployment envelope for the VMEM budget check (tools/analyze kernel-shapes):
# up to 64 heads of head_dim 128 in the config zoo.
VMEM_BOUNDS = {"h": 64, "d": 128}


def _rope_math(x, cos, sin):
    """The kernel's arithmetic: x (B, S, H, d), cos/sin (B, S, d//2), f32
    throughout with one cast at the end."""
    x = x.astype(jnp.float32)
    c = cos.astype(jnp.float32)[:, :, None, :]  # broadcast over heads
    s = sin.astype(jnp.float32)[:, :, None, :]
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    o_ref[...] = _rope_math(x_ref[...], cos_ref[...],
                            sin_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, *,
               block_s: int = 128, interpret: bool = False) -> jnp.ndarray:
    """x: (B, S, H, d); cos/sin: (B, S, d//2) (or broadcastable (1, S, d//2)).

    d must be even (rotate-half splits the feature dim in two)."""
    b, s, h, d = x.shape
    assert d % 2 == 0, f"rotate-half RoPE needs an even head dim, got {d}"
    cos = jnp.broadcast_to(cos, (b, s, d // 2))
    sin = jnp.broadcast_to(sin, (b, s, d // 2))
    return _rope(x, cos, sin, block_s, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rope(x, cos, sin, block_s, interpret):
    return _rope_fwd_call(x, cos, sin, block_s=block_s, interpret=interpret)


def _rope_fwd(x, cos, sin, block_s, interpret):
    return _rope(x, cos, sin, block_s, interpret), (x, cos, sin)


def _rope_bwd(block_s, interpret, res, dout):
    x, cos, sin = res
    _, vjp = jax.vjp(_rope_math, x, cos, sin)
    dx, dc, ds = vjp(dout.astype(jnp.float32))
    return dx.astype(x.dtype), dc.astype(cos.dtype), ds.astype(sin.dtype)


_rope.defvjp(_rope_fwd, _rope_bwd)


def _rope_fwd_call(x, cos, sin, *, block_s: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    b, s, h, d = x.shape
    block_s = min(block_s, s)
    while s % block_s:
        block_s //= 2
    block_s = max(block_s, 1)
    grid = (b, s // block_s)
    return pl.pallas_call(
        _rope_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, d // 2), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_s, d // 2), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_s, h, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, d), x.dtype),
        interpret=interpret,
    )(x, cos, sin)
