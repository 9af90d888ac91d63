"""Fused RMSNorm Pallas TPU kernel.

Tiles rows into VMEM blocks of (block_rows, d); each program computes the
mean-square and scales in one pass (one HBM read, one HBM write — the fusion
the paper's Ascend kernel provides).

Backward: ``jax.custom_vjp`` whose backward is the VJP of the same f32 math
in jnp (``_rmsnorm_math``), recomputed from the saved inputs — so the update
step differentiates through the Pallas forward on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Deployment envelope for the VMEM budget check (tools/analyze kernel-shapes):
# widest config-zoo d_model is 8192 (qwen1.5-110b).
VMEM_BOUNDS = {"d": 8192}


def _rmsnorm_math(x, w, eps: float):
    """The kernel's arithmetic (f32 throughout, one cast at the end)."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    o_ref[...] = _rmsnorm_math(x_ref[...], w_ref[...], eps).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-5,
            block_rows: int = 128, interpret: bool = False) -> jnp.ndarray:
    """x: (rows, d), w: (d,).  d should be a multiple of 128 on real TPU."""
    return _rmsnorm(x, w, eps, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x, w, eps, block_rows, interpret):
    return _rmsnorm_fwd_call(x, w, eps=eps, block_rows=block_rows,
                             interpret=interpret)


def _rmsnorm_fwd(x, w, eps, block_rows, interpret):
    return _rmsnorm(x, w, eps, block_rows, interpret), (x, w)


def _rmsnorm_bwd(eps, block_rows, interpret, res, dout):
    x, w = res
    _, vjp = jax.vjp(lambda x, w: _rmsnorm_math(x, w, eps), x, w)
    dx, dw = vjp(dout.astype(jnp.float32))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def _rmsnorm_fwd_call(x, w, *, eps: float = 1e-5, block_rows: int = 128,
                      interpret: bool = False) -> jnp.ndarray:
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows //= 2
    block_rows = max(block_rows, 1)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, w)
