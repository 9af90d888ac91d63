"""Fused SwiGLU Pallas TPU kernel: out = silu(gate) * up.

Avoids materializing silu(gate) in HBM (the fusion the paper integrates).

Backward: ``jax.custom_vjp`` whose backward is the VJP of the same f32 math
in jnp (``_swiglu_math``), recomputed from the saved inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _swiglu_math(g, u):
    """The kernel's arithmetic (f32 throughout, one cast at the end)."""
    g = g.astype(jnp.float32)
    return g * jax.nn.sigmoid(g) * u.astype(jnp.float32)


def _swiglu_kernel(g_ref, u_ref, o_ref):
    o_ref[...] = _swiglu_math(g_ref[...], u_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_f", "interpret"))
def swiglu(gate: jnp.ndarray, up: jnp.ndarray, *, block_rows: int = 256,
           block_f: int = 512, interpret: bool = False) -> jnp.ndarray:
    """gate, up: (rows, f)."""
    return _swiglu(gate, up, block_rows, block_f, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _swiglu(gate, up, block_rows, block_f, interpret):
    return _swiglu_fwd_call(gate, up, block_rows=block_rows, block_f=block_f,
                            interpret=interpret)


def _swiglu_fwd(gate, up, block_rows, block_f, interpret):
    return _swiglu(gate, up, block_rows, block_f, interpret), (gate, up)


def _swiglu_bwd(block_rows, block_f, interpret, res, dout):
    gate, up = res
    _, vjp = jax.vjp(_swiglu_math, gate, up)
    dg, du = vjp(dout.astype(jnp.float32))
    return dg.astype(gate.dtype), du.astype(up.dtype)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _swiglu_fwd_call(gate, up, *, block_rows: int = 256, block_f: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    rows, f = gate.shape
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows //= 2
    block_rows = max(block_rows, 1)
    block_f = min(block_f, f)
    while f % block_f:
        block_f //= 2
    block_f = max(block_f, 1)
    grid = (rows // block_rows, f // block_f)
    spec = pl.BlockSpec((block_rows, block_f), lambda i, j: (i, j))
    return pl.pallas_call(
        _swiglu_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, f), gate.dtype),
        interpret=interpret,
    )(gate, up)
