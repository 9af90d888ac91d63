"""Grouped matmul (GMM) Pallas TPU kernel — the MoE expert-FFN hot spot.

x (T, d) holds tokens sorted by expert with every group boundary aligned to
``tile_t`` (the caller pads each group); w (E, d, f).  The expert id of each
row tile is data-dependent, so it is passed through scalar prefetch
(PrefetchScalarGridSpec) and consumed by the weight BlockSpec index_map —
exactly the megablocks-on-TPU adaptation: contiguous MXU tiles instead of
GPU gather-scatter.

T is a static worst case (every routed row plus each group's padding); the
rows actually routed fill only its first ``sum(group_sizes) / tile_t``
tiles, a count also passed through scalar prefetch.  Tiles past it map
every block to the last live tile's, so the pipeline fetches no new x or
weight block and writes no output back for them, and the kernel skips
their MXU work: device time follows the rows routed, not T.  Their output
rows are left unwritten and must not be read.  Operands enter the MXU in
their own type (bfloat16 stays bfloat16) with float32 accumulation.

The weights may be a layer stack ``(L, E, d, f)`` with the layer's index
(scalar prefetch too): the kernel then reads that layer's blocks in place,
where a slice of the stack before the call would copy the layer's whole
table (XLA cannot fuse a slice into a custom call).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Deployment envelope for the VMEM budget check (tools/analyze kernel-shapes):
# widest MoE d_model in the config zoo is 5120 (llama4-scout class).
# Worst case: x 2.5 MiB + w 10 MiB + out 0.25 MiB per program.
VMEM_BOUNDS = {"d": 5120}


def _gmm_kernel(tile_gid_ref, live_ref, layer_ref, x_ref, w_ref, o_ref):
    del tile_gid_ref, layer_ref  # consumed by the index_maps

    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        x, w = x_ref[...], w_ref[0, 0]
        # bfloat16 products are exact in one pass; float32 takes all passes
        exact = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
        o_ref[...] = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _last_live(i, live):
    return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))


@functools.partial(jax.jit, static_argnames=("tile_t", "block_f", "interpret"))
def gmm(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
        layer=0, *, tile_t: int = 128, block_f: int = 512,
        interpret: bool = False) -> jnp.ndarray:
    """x: (T, d) group-sorted, tile-aligned; w: (E, d, f), or (L, E, d, f)
    read at ``layer``; group_sizes: (E,) multiples of ``tile_t`` summing to
    at most T."""
    if w.ndim == 3:
        w = w[None]
    t, d = x.shape
    _, e, _, f = w.shape
    assert t % tile_t == 0, (t, tile_t)
    block_f = min(block_f, f)
    while f % block_f:
        block_f //= 2
    block_f = max(block_f, 1)
    nt = t // tile_t

    # expert id per row tile, from the (traced) group sizes
    offsets = jnp.cumsum(group_sizes)                      # (E,)
    tile_start = jnp.arange(nt, dtype=jnp.int32) * tile_t
    tile_gid = jnp.clip(
        jnp.searchsorted(offsets, tile_start, side="right"), 0, e - 1
    ).astype(jnp.int32)
    live = (offsets[-1:] // tile_t).astype(jnp.int32)     # tiles in use
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    # the row tiles vary fastest, so a dead tile repeats the last live
    # tile's blocks and the pipeline skips its copies
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(f // block_f, nt),
        in_specs=[
            pl.BlockSpec((tile_t, d), lambda j, i, gid, live, layer: (
                _last_live(i, live), 0)),
            pl.BlockSpec((1, 1, d, block_f), lambda j, i, gid, live, layer: (
                layer[0], gid[_last_live(i, live)], 0, j)),
        ],
        out_specs=pl.BlockSpec(
            (tile_t, block_f),
            lambda j, i, gid, live, layer: (_last_live(i, live), j)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        interpret=interpret,
        name="moe_gmm",
    )(tile_gid, live, layer, x, w)
