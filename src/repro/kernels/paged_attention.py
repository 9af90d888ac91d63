"""Paged decode-attention Pallas TPU kernel (flash-decoding over block tables).

The serving hot loop decodes one token per slot per step against KV that
lives in the paged pool (serve/paged_cache.py).  Attention reads the block
table directly; no dense per-slot copy of the cache is ever built.

Operands.  The pools go in whole, as the stacked ``(layers, R, KV, hd)``
arrays they are stored as, in ``memory_space=ANY`` (HBM): no reshape, no
per-layer slice.  The layer index, the flattened block table and the
per-slot positions ride in as SCALAR-PREFETCH operands (SMEM), so the
kernel computes every page address itself.

Grid and walk.  The grid is one program per slot, run in order
("arbitrary"), each holding all of the slot's KV heads: the query block is
``(H, hd)`` and a compute block ``(rows * KV, hd)``, and a head mask keeps
each query head to its own KV head, so any grouping ``g = H // KV`` works
(7 for Qwen2.5-7B, 1 for MHA) without a ``(KV, g, hd)`` split.  A slot
attends to the table entries ``first .. ceil(pos / block_size) - 1``
(``page_span``): ``first`` is 0, or with a sliding window the first page
that holds a row inside it.  Only those pages are copied; a slot with
``pos == 0`` (idle, or still prefilling) copies nothing and attends to its
in-flight token alone.  The live pages are walked in compute blocks of
``pages_per_block`` pages (about 128 rows): each page is one
``make_async_copy`` of ``block_size`` contiguous pool rows into a
double-buffered VMEM block, so the next block's pages load while this one
computes.  The last block of a slot starts the first block of the next
slot, so the copies run on across program boundaries (two SMEM words carry
which buffer that block landed in).  A block's page positions past the
slot's live pages are left uncopied and masked.

Masking: rows at logical position ``>= pos`` (the rest of the last page, the
uncopied tail of a partial block) and, with a window, rows ``<= pos -
window`` are masked to -1e30 and their values to 0, so stale VMEM never
reaches the sums.  The online-softmax partials ``(acc, m, l)`` stay in
float32 across blocks, and the in-flight token's (k, v), not yet scattered
into the pool, is folded in last, before normalization.

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
# up to 64 query heads over 8 KV heads of head_dim 128, compute blocks of up
# to 128 pool rows (``rows``).  One program (one slot) holds the slot's q,
# in-flight k/v and output and two buffers each of K and V blocks, whatever
# the slot count: about 2.1 MiB at f32 accounting, 0.5 MiB as stored
# (bfloat16); the pools stay in HBM.
VMEM_BOUNDS = {"h": 64, "kv": 8, "hd": 128, "rows": 128}

_BLOCK_ROWS = 128      # rows of one compute block (several pages)


def page_span(pos, block_size: int, window: int = 0):
    """Table entries ``[first, end)`` that hold a row a query at ``pos``
    attends to (the in-flight row at ``pos`` itself is not in the pool yet).
    Works on host ints and arrays as on traced scalars."""
    end = (pos + block_size - 1) // block_size
    if window <= 0:
        return 0 * end, end
    lo = pos - window + 1
    return (lo * (lo > 0)) // block_size, end


def pages_per_block(block_size: int, table_width: int) -> int:
    """Pages of one compute block: about ``_BLOCK_ROWS`` rows, at most the
    table's width."""
    return max(1, min(table_width, _BLOCK_ROWS // block_size))


def _paged_decode_kernel(layer_ref, tbl_ref, pos_ref, q_ref, kn_ref, vn_ref,
                         pool_k, pool_v, o_ref, state_ref, kbuf, vbuf, sems, *,
                         block_size: int, mb: int, ppb: int, window: int,
                         scale: float):
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    ns = pl.num_programs(0)
    rows = ppb * block_size
    layer = layer_ref[0]

    def span(slot):
        first, end = page_span(pos_ref[slot], block_size, window)
        end = jnp.minimum(end, mb)
        return first, end, (end - first + ppb - 1) // ppb

    def copies(slot, first, end, b, buf):
        """(page present, K copy, V copy) for each page of block ``b``."""
        out = []
        for j in range(ppb):
            e = first + b * ppb + j
            live = e < end
            page = tbl_ref[slot * mb + jnp.minimum(e, mb - 1)]
            src = pl.ds(page * block_size, block_size)
            dst = pl.ds(j * block_size, block_size)
            out.append((live,
                        pltpu.make_async_copy(pool_k.at[layer, src],
                                              kbuf.at[buf, dst], sems.at[0, buf]),
                        pltpu.make_async_copy(pool_v.at[layer, src],
                                              vbuf.at[buf, dst], sems.at[1, buf])))
        return out

    def start(slot, first, end, b, buf):
        for live, ck, cv in copies(slot, first, end, b, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(slot, first, end, b, buf):
        for live, ck, cv in copies(slot, first, end, b, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(i == 0)
    def _():
        state_ref[0] = 0       # buffer of this slot's first block
        state_ref[1] = 0       # 1: the previous program already started it

    first, end, nblk = span(i)
    base = state_ref[0]
    nxt = jnp.minimum(i + 1, ns - 1)
    n_first, n_end, n_nblk = span(nxt)
    chain = (i + 1 < ns) & (n_nblk > 0)     # start the next slot's block 0

    @pl.when((nblk > 0) & (state_ref[1] == 0))
    def _():
        start(i, first, end, 0, base)

    p = pos_ref[i]
    q = q_ref[0].astype(jnp.float32) * scale                  # (h, hd)
    h, hd = q.shape
    kv = kn_ref.shape[1]
    g = h // kv
    # query head r reads KV head r // g; in the flattened (rows * kv, hd)
    # block, column c holds KV head c % kv of row c // kv
    qhead = jax.lax.broadcasted_iota(jnp.int32, (h, rows * kv), 0) // g
    chead = jax.lax.broadcasted_iota(jnp.int32, (h, rows * kv), 1) % kv
    same = qhead == chead
    nt = (((1,), (1,)), ((), ()))

    def block(b, carry):
        m_prev, l_prev, acc_prev = carry
        buf = (base + b) % 2

        @pl.when(b + 1 < nblk)
        def _():
            start(i, first, end, b + 1, 1 - buf)

        @pl.when((b + 1 == nblk) & chain)
        def _():
            start(nxt, n_first, n_end, 0, 1 - buf)

        wait(i, first, end, b, buf)
        row0 = (first + b * ppb) * block_size

        def live(kpos):
            ok = kpos < p
            return ok & (kpos > p - window) if window > 0 else ok

        valid = live(row0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows * kv), 1) // kv)
        vvalid = live(row0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows * kv, 1), 0) // kv)
        kblk = kbuf[buf].reshape(rows * kv, hd).astype(jnp.float32)
        vblk = vbuf[buf].reshape(rows * kv, hd).astype(jnp.float32)
        vblk = jnp.where(vvalid, vblk, 0.0)
        s = jax.lax.dot_general(q, kblk, nt,
                                preferred_element_type=jnp.float32)
        s = jnp.where(same & valid, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        acc = acc_prev * corr + jnp.dot(pexp, vblk,
                                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((h, 1), -1e30, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, hd), jnp.float32))
    m_prev, l_prev, acc = jax.lax.fori_loop(0, nblk, block, init)

    chained = (nblk > 0) & chain
    state_ref[0] = jnp.where(chained, (base + nblk) % 2, 0)
    state_ref[1] = chained.astype(jnp.int32)

    # fold the in-flight token last, then normalize
    kn = kn_ref[0].astype(jnp.float32)                        # (kv, hd)
    vn = vn_ref[0].astype(jnp.float32)
    own = (jax.lax.broadcasted_iota(jnp.int32, (h, kv), 0) // g
           == jax.lax.broadcasted_iota(jnp.int32, (h, kv), 1))
    s1 = jnp.sum(jnp.where(own, jax.lax.dot_general(
        q, kn, nt, preferred_element_type=jnp.float32), 0.0),
        axis=-1, keepdims=True)                               # (h, 1)
    m_new = jnp.maximum(m_prev, s1)
    corr = jnp.exp(m_prev - m_new)
    p1 = jnp.exp(s1 - m_new)
    l = l_prev * corr + p1
    acc = acc * corr + jnp.dot(jnp.where(own, p1, 0.0), vn,
                               preferred_element_type=jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "window", "scale",
                                             "interpret"))
def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, layer, tables,
                           pos, *, block_size: int, window: int = 0,
                           scale: float | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """One-token attention straight off the paged pool, for one layer.

    q:             (S, H, hd)      per-slot decode queries
    k_new / v_new: (S, KV, hd)     the in-flight token's KV (not in the pool)
    pool_k/pool_v: (n, R, KV, hd)  the stacked row pools, as stored;
                   R = (num_blocks + 1) * block_size
    layer:         () int32        which of the n layers to read
    tables:        (S, MB) int32   block table
    pos:           (S,) int32      cached rows per slot (write position)

    Returns (S, H, hd).  H must be a multiple of KV and the pool rows a
    multiple of block_size.  ``interpret`` runs the kernel in TPU interpret
    mode (its DMAs and semaphores simulated on the CPU).
    """
    from jax.experimental.pallas import tpu as pltpu

    s, h, hd = q.shape
    kv = pool_k.shape[2]
    assert pool_k.shape[1] % block_size == 0, \
        f"pool rows {pool_k.shape[1]} must be a multiple of {block_size}"
    assert h % kv == 0, f"query heads {h} must group evenly over {kv} KV heads"
    mb = tables.shape[1]
    ppb = pages_per_block(block_size, mb)
    rows = ppb * block_size
    scale = scale if scale is not None else 1.0 / float(np.sqrt(hd))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda i, ly, tbl, ps: (i, 0, 0)),
            pl.BlockSpec((1, kv, hd), lambda i, ly, tbl, ps: (i, 0, 0)),
            pl.BlockSpec((1, kv, hd), lambda i, ly, tbl, ps: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, ly, tbl, ps: (i, 0, 0)),
        scratch_shapes=[
            pltpu.SMEM((2,), jnp.int32),                     # chain state
            pltpu.VMEM((2, rows, kv, hd), pool_k.dtype),     # K blocks
            pltpu.VMEM((2, rows, kv, hd), pool_v.dtype),     # V blocks
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, block_size=block_size, mb=mb,
                          ppb=ppb, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode_attention",
    )(layer, tables.reshape(-1), pos, q, k_new, v_new, pool_k, pool_v)
    return out
