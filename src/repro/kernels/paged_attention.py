"""Paged decode-attention Pallas TPU kernel (flash-decoding over block tables).

The serving hot loop decodes one token per slot per step against KV that
lives in the paged pool (serve/paged_cache.py).  Before this kernel, the
engine materialized a dense ``(layers, slots, max_blocks*block_size, kv, hd)``
copy of the pool every step (``gather_kv``) and ran dense attention on it —
decode cost scaled with pool *capacity*, not live tokens.  Here attention
reads the block table directly:

  grid = (layer, slot, kv_block)

The block table and per-slot positions ride in as SCALAR-PREFETCH operands
(the same trick as ``gather_pool_pallas``): the pool BlockSpec's index map
looks up ``tbl[slot, block]`` so each program DMAs exactly the pool block its
table entry names.  The innermost grid dimension walks a slot's blocks
sequentially; VMEM scratch carries the flash-decoding online-softmax partials
``(acc, m, l)`` across blocks, initialized at block 0 and finalized at the
last block, where the in-flight token's (k, v) — not yet scattered into the
pool — is folded in as the final softmax element before normalization.

Masking: rows at logical position ``>= pos[slot]`` (null-block rows,
beyond-length rows, idle slots) are masked to -1e30 so they contribute
nothing; blocks that start at or beyond ``pos`` skip their update entirely
via ``pl.when`` (their table entries all name the null block, so the dead
DMAs at least all hit one hot block).  A fully-masked first block can leak
``exp(0)`` garbage into the partials while ``m == -1e30``; the next real
(or final-token) rescale multiplies it by ``exp(-1e30 - m_new) == 0``, so
the result is still exact — the standard flash-decoding identity.

Numerics: online softmax is mathematically identical to dense softmax but
not bitwise (rescaling rounds differently); the engine's bit-compatibility
oracle is the jnp reference (kernels/ref.py), which is two-pass and bitwise
equal to the dense-gather path.  Greedy decode is identical across all
three (tested in tests/test_paged_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Deployment envelope for the VMEM budget check (tools/analyze kernel-shapes):
# up to 64 query heads over 8 KV heads of head_dim 128, pool blocks of at
# most 64 rows.  Worst case well under 1 MiB/program.
VMEM_BOUNDS = {"h": 64, "hd": 128, "kv": 8, "block_size": 64}


def _paged_decode_kernel(tbl_ref, pos_ref, q_ref, kn_ref, vn_ref, kb_ref,
                         vb_ref, o_ref, acc_ref, m_ref, l_ref, *,
                         block_size: int, nb: int, kv: int, g: int, hd: int,
                         window: int, scale: float):
    i = pl.program_id(1)      # slot
    j = pl.program_id(2)      # kv block (innermost: sequential per slot)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    p = pos_ref[i]
    start = j * block_size

    @pl.when(start < p)       # block holds at least one cached row (< pos)
    def _block():
        q = q_ref[0, 0, 0].reshape(kv, g, hd).astype(jnp.float32) * scale
        kblk = kb_ref[0, 0].reshape(block_size, kv, hd).astype(jnp.float32)
        vblk = vb_ref[0, 0].reshape(block_size, kv, hd).astype(jnp.float32)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (block_size, 1),
                                                0)[:, 0]
        s = jnp.einsum("kgd,skd->kgs", q, kblk,
                       preferred_element_type=jnp.float32)
        valid = kpos < p
        if window > 0:
            valid &= kpos > p - window
        s = jnp.where(valid[None, None, :], s, -1e30)
        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        pexp = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(pexp, axis=-1)
        acc_ref[...] = acc_prev * corr[..., None] + jnp.einsum(
            "kgs,skd->kgd", pexp, vblk, preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)     # fold the in-flight token, then normalize
    def _final():
        q = q_ref[0, 0, 0].reshape(kv, g, hd).astype(jnp.float32) * scale
        kn = kn_ref[0, 0, 0].reshape(kv, hd).astype(jnp.float32)
        vn = vn_ref[0, 0, 0].reshape(kv, hd).astype(jnp.float32)
        s1 = jnp.einsum("kgd,kd->kg", q, kn,
                        preferred_element_type=jnp.float32)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s1)
        corr = jnp.exp(m_prev - m_new)
        p1 = jnp.exp(s1 - m_new)
        l = l_ref[...] * corr + p1
        acc = acc_ref[...] * corr[..., None] + p1[..., None] * vn[:, None]
        o_ref[0, 0, 0] = (acc / l[..., None]).reshape(kv * g * hd).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "window", "scale",
                                             "interpret"))
def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, tables, pos, *,
                           block_size: int, window: int = 0,
                           scale: float | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """One-token attention straight off the paged pool.

    q:             (n, S, H, hd)   per-slot decode queries
    k_new / v_new: (n, S, KV, hd)  the in-flight token's KV (not in the pool)
    pool_k/pool_v: (n, R, KV, hd)  row pools, R = (num_blocks + 1) * block_size
    tables:        (S, MB) int32   block table (scalar prefetch)
    pos:           (S,) int32      cached rows per slot (write position)

    Returns (n, S, H, hd).  The model's layer scan calls this with n == 1;
    the kernel is written for the general (layer, slot, kv_block) grid.

    Pool rows R must be a multiple of block_size and H a multiple of KV.
    """
    from jax.experimental.pallas import tpu as pltpu

    n, s, h, hd = q.shape
    kv = pool_k.shape[2]
    assert pool_k.shape[1] % block_size == 0, \
        f"pool rows {pool_k.shape[1]} must be a multiple of {block_size}"
    assert h % kv == 0, f"query heads {h} must group evenly over {kv} KV heads"
    g = h // kv
    _, mb = tables.shape
    scale = scale if scale is not None else 1.0 / float(np.sqrt(hd))
    poolk4 = pool_k.reshape(n, -1, block_size, kv * hd)
    poolv4 = pool_v.reshape(n, -1, block_size, kv * hd)
    # per-slot operands carry a unit axis so each block's last two dims
    # (1, width) equal the array's: the chip's tiling rule (last two block
    # dims divisible by (8, 128) or whole) refuses a (1, width) block over
    # an (S, width) array once S > 1
    q4 = q.reshape(n, s, 1, h * hd)
    kn4 = k_new.reshape(n, s, 1, kv * hd)
    vn4 = v_new.reshape(n, s, 1, kv * hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, s, mb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, h * hd),
                         lambda l, i, j, tbl, ps: (l, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, kv * hd),
                         lambda l, i, j, tbl, ps: (l, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, kv * hd),
                         lambda l, i, j, tbl, ps: (l, i, 0, 0)),
            pl.BlockSpec((1, 1, block_size, kv * hd),
                         lambda l, i, j, tbl, ps: (l, tbl[i, j], 0, 0)),
            pl.BlockSpec((1, 1, block_size, kv * hd),
                         lambda l, i, j, tbl, ps: (l, tbl[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, h * hd),
                               lambda l, i, j, tbl, ps: (l, i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g, hd), jnp.float32),   # acc
            pltpu.VMEM((kv, g), jnp.float32),       # m (running max)
            pltpu.VMEM((kv, g), jnp.float32),       # l (running denom)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, block_size=block_size, nb=mb,
                          kv=kv, g=g, hd=hd, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, s, 1, h * hd), q.dtype),
        interpret=interpret,
    )(tables, pos, q4, kn4, vn4, poolk4, poolv4)
    return out.reshape(n, s, h, hd)
