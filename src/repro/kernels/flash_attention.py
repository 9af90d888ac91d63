"""FlashAttention Pallas TPU kernel (causal + sliding-window, GQA).

Grid: (batch, kv_head, q_block).  Each program holds one q tile
(block_q, group*d) in VMEM and streams k/v blocks with an online-softmax
accumulator.  Tile sizes are MXU-aligned (multiples of 128 at full scale).

The q/k block loop bound is static; causal and sliding-window masking skip
out-of-range blocks by zero-masking (interpret-mode friendly; on real TPU the
``when`` predication prunes them).

Backward: ``jax.custom_vjp`` whose backward is the jnp flash path's VJP
(``ops._flash``: chunked online-softmax forward for the log-sum-exp, then
the chunked recompute backward), recomputed from the saved q, k, v — O(S)
memory, no score matrix materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Deployment envelope for the VMEM budget check (tools/analyze kernel-shapes):
# largest config-zoo model has head_dim 128, 8 KV heads under 64 query heads
# (group 8), and serve contexts up to 4k.  Worst case ~5 MiB/program.
VMEM_BOUNDS = {"g": 8, "d": 128, "sk": 4096}


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  window: int, scale: float, sq: int, sk: int):
    # q_ref: (1, 1, block_q, g, d); k_ref/v_ref: (1, 1, sk, d)
    q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, g, d)
    bq, g, d = q.shape
    qi = pl.program_id(2)
    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq) + (sk - sq)
    nb = sk // block_k

    def body(i, carry):
        acc, m, l = carry
        kblk = k_ref[0, 0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, 0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        k_pos = i * block_k + jax.lax.iota(jnp.int32, block_k)
        s = jax.lax.dot_general(
            q.reshape(bq * g, d), kblk,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(bq, g, block_k)
        mask = jnp.ones((bq, block_k), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[:, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.reshape(bq * g, block_k), vblk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(bq, g, d)
        acc_new = acc * corr[..., None] + pv
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, g, d), jnp.float32)
    m0 = jnp.full((bq, g), -1e30, jnp.float32)
    l0 = jnp.zeros((bq, g), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nb, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / l[..., None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Sq, H, d); k, v: (B, Sk, KV, d).  Returns (B, Sq, H, d)."""
    scale = scale if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
    return _flash(q, k, v, causal, window, scale, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, block_q, block_k, interpret):
    return _flash_fwd_call(q, k, v, causal=causal, window=window, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    return (_flash(q, k, v, causal, window, scale, block_q, block_k,
                   interpret), (q, k, v))


def _flash_bwd(causal, window, scale, block_q, block_k, interpret, res, dout):
    from repro.kernels import ops

    _, vjp = jax.vjp(lambda q, k, v: ops._flash(q, k, v, causal, window,
                                                scale), *res)
    return vjp(dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_fwd_call(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    assert h % kv == 0, f"query heads {h} must group evenly over {kv} KV heads"
    g = h // kv
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    block_q = min(block_q, sq)
    while sq % block_q:
        block_q //= 2
    block_q = max(block_q, 1)
    block_k = min(block_k, sk)
    while sk % block_k:
        block_k //= 2
    block_k = max(block_k, 1)

    qg = q.reshape(b, sq, kv, g, d).transpose(0, 2, 1, 3, 4)  # (B,KV,Sq,g,d)
    kt = k.transpose(0, 2, 1, 3)                              # (B,KV,Sk,d)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, kv, sq // block_q)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_k=block_k, causal=causal, window=window,
            scale=scale, sq=sq, sk=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, g, d), lambda i, j, n: (i, j, n, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, n: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, g, d), lambda i, j, n: (i, j, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, sq // block_q * block_q, g, d),
                                       q.dtype),
        interpret=interpret,
    )(qg, kt, vt)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, sq, h, d)
