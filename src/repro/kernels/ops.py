"""Public fused-op API.

Models call these; the implementation dispatches to a Pallas TPU kernel when
running on TPU (or when REPRO_PALLAS=interpret forces interpret-mode), and to
a jnp implementation otherwise.  The jnp attention path is NOT the naive
oracle: it is a chunked online-softmax implementation with a custom VJP
(flash semantics), so the compiled HLO of the CPU dry-run has the same
asymptotic memory behaviour the TPU kernel has — the roofline analysis stays
honest.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ref

_FORCE_INTERPRET = os.environ.get("REPRO_PALLAS", "") == "interpret"


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu" or _FORCE_INTERPRET


# ---------------------------------------------------------------------------
# elementwise fusions
# ---------------------------------------------------------------------------

def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _shard_axes(names, dim: int):
    """The ambient mesh's axes among ``names`` to split a dim of size
    ``dim`` over, or None when there is no mesh or they do not divide it
    (the dim is then replicated)."""
    mesh = ambient_mesh()
    if mesh is None:
        return None
    axes = tuple(a for a in names if a in mesh.axis_names)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if not axes or n == 1 or dim % n:
        return None
    return axes if len(axes) > 1 else axes[0]


def _per_shard(kernel, args, in_specs, out_spec):
    """Call a Pallas kernel on each device's block of ``args``.

    XLA cannot partition a Pallas (Mosaic) kernel, so under a multi-device
    ambient mesh (``jax.set_mesh``) the kernel runs inside ``shard_map``
    with the given specs; one device calls it directly.  The specs split
    only dims the kernel treats independently (rows, batch, heads)."""
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(*args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)(*args)


_BATCH_AXES = ("pod", "data")


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    if _use_pallas() and x.ndim >= 2:
        from repro.kernels import rmsnorm as _k

        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        spec = P(_shard_axes(_BATCH_AXES, x2.shape[0]), None)
        out = _per_shard(
            lambda x, w: _k.rmsnorm(x, w, eps=eps, interpret=_interpret()),
            (x2, w), (spec, P(None)), spec)
        return out.reshape(shape)
    return ref.rmsnorm(x, w, eps)


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    if _use_pallas() and gate.ndim >= 2:
        from repro.kernels import swiglu as _k

        shape = gate.shape
        g2, u2 = gate.reshape(-1, shape[-1]), up.reshape(-1, shape[-1])
        spec = P(_shard_axes(_BATCH_AXES, g2.shape[0]),
                 _shard_axes(("model",), g2.shape[1]))
        out = _per_shard(
            lambda g, u: _k.swiglu(g, u, interpret=_interpret()),
            (g2, u2), (spec, spec), spec)
        return out.reshape(shape)
    return ref.swiglu(gate, up)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., d), cos/sin broadcastable (..., d//2)."""
    if _use_pallas() and x.ndim == 4:
        from repro.kernels import rope as _k

        c, s = cos, sin
        if c.ndim == 4:            # callers pass a broadcast head axis
            c, s = c[:, :, 0], s[:, :, 0]
        b, sq, h, d = x.shape
        c = jnp.broadcast_to(c, (b, sq, d // 2))
        s = jnp.broadcast_to(s, (b, sq, d // 2))
        bax, hax = _shard_axes(_BATCH_AXES, b), _shard_axes(("model",), h)
        return _per_shard(
            lambda x, c, s: _k.apply_rope(x, c, s, interpret=_interpret()),
            (x, c, s), (P(bax, None, hax, None), P(bax, None, None),
                        P(bax, None, None)), P(bax, None, hax, None))
    return ref.rope(x, cos, sin)


def rope_tables(positions: jnp.ndarray, head_dim: int, theta: float):
    """cos/sin tables for rotate-half RoPE.  positions: (...,) int32.

    Returns cos, sin of shape positions.shape + (head_dim//2,).
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_tables(positions: jnp.ndarray, head_dim: int, theta: float,
                 sections: tuple):
    """M-RoPE (qwen2-vl): positions (3, ...) for (t, h, w); the half-dim is
    split into ``sections`` (summing to head_dim//2), each section rotated by
    its own position stream."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (3, ..., half)
    idx = np.concatenate(
        [np.full((s,), i) for i, s in enumerate(sections)]
    )  # (half,) which position stream each channel uses
    onehot = jax.nn.one_hot(jnp.asarray(idx), 3, dtype=jnp.float32)  # (half, 3)
    ang = jnp.einsum("s...h,hs->...h", ang, onehot)
    return jnp.cos(ang), jnp.sin(ang)


# ---------------------------------------------------------------------------
# attention — flash semantics
# ---------------------------------------------------------------------------

_DEF_BLOCK = int(os.environ.get("REPRO_ATTN_BLOCK", "512"))
# jnp-path flash block size trade-off: the (acc, m, l) carry is re-read and
# re-written every kv block, so HBM carry traffic ∝ nb = Sk/block, while the
# per-block score tile traffic is ~constant in nb.  Larger blocks cut carry
# traffic linearly until the score tile dominates (§Perf log).  The Pallas
# TPU kernel keeps the carry in VMEM and has no such trade-off.


def _pick_block(s: int, target: int = 0) -> int:
    target = target or _DEF_BLOCK
    if s <= target:
        return s
    b = target
    while s % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal: bool, window: int, scale: float):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, scale)
    return out


def _flash_fwd_impl(q, k, v, causal, window, scale, q_pos=None):
    """Chunked online-softmax forward.  q:(B,Sq,H,d) k,v:(B,Sk,KV,d).

    ``q_pos`` ((Sq,) int32, optional) gives the queries' GLOBAL positions
    for the causal/window masks; the default keeps the standard convention
    (q rows are the last Sq of the Sk context).  Chunked prefill passes the
    chunk's absolute offsets — extra keys this masks out contribute exact
    zeros to every row's reductions, so a chunk's rows stay bitwise equal
    to a whole-prompt prefill whenever both contexts fit one kv block
    (``_pick_block``) AND both Sk are powers of two: XLA reduces a pow2 key
    length with the same real-element grouping at any pow2 size, but a
    non-pow2 Sk regroups the reduction value-dependently and breaks row
    bitwise-equality once a row attends past the regroup boundary (which
    is why ``chunk_prefill_attention`` pow2-pads its capacity window).
    Beyond one kv block the online-softmax rescan order differs and
    equality degrades to allclose."""
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    blk = _pick_block(sk)
    nb = sk // blk
    qg = (q.reshape(b, sq, kv, g, d) * scale).astype(jnp.float32)
    if q_pos is None:
        q_pos = jnp.arange(sq) + (sk - sq)

    kb = k.reshape(b, nb, blk, kv, d).swapaxes(0, 1).astype(jnp.float32)
    vb = v.reshape(b, nb, blk, kv, d).swapaxes(0, 1).astype(jnp.float32)

    def step(carry, xs):
        acc, m, l = carry
        kblk, vblk, i = xs
        k_pos = i * blk + jnp.arange(blk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kblk)
        mask = jnp.ones((sq, blk), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vblk)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, kv, g, sq, d), jnp.float32)
    m0 = jnp.full((b, kv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (kb, vb, jnp.arange(nb))
    )
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    lse = (m + jnp.log(l))  # (B, KV, G, Sq)
    return out.astype(q.dtype), lse


def _flash_fwd(q, k, v, causal, window, scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, scale, res, dout):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    blk = _pick_block(sk)
    nb = sk // blk
    qg = q.reshape(b, sq, kv, g, d).astype(jnp.float32)
    dog = dout.reshape(b, sq, kv, g, d).astype(jnp.float32)
    og = out.reshape(b, sq, kv, g, d).astype(jnp.float32)
    delta = jnp.sum(dog * og, axis=-1).transpose(0, 2, 3, 1)  # (B,KV,G,Sq)
    q_pos = jnp.arange(sq) + (sk - sq)
    kb = k.reshape(b, nb, blk, kv, d).swapaxes(0, 1).astype(jnp.float32)
    vb = v.reshape(b, nb, blk, kv, d).swapaxes(0, 1).astype(jnp.float32)

    def step(dq, xs):
        kblk, vblk, i = xs
        k_pos = i * blk + jnp.arange(blk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kblk) * scale
        mask = jnp.ones((sq, blk), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        p = jnp.where(mask[None, None, None], jnp.exp(s - lse[..., None]), 0.0)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dog, vblk)
        ds = p * (dp - delta[..., None]) * scale
        dv_blk = jnp.einsum("bkgqs,bqkgd->bskd", p, dog)
        dk_blk = jnp.einsum("bkgqs,bqkgd->bskd", ds, qg)
        dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds, kblk)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, kv, g, d), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (kb, vb, jnp.arange(nb)))
    dk = dk_b.swapaxes(0, 1).reshape(b, sk, kv, d).astype(k.dtype)
    dv = dv_b.swapaxes(0, 1).reshape(b, sk, kv, d).astype(v.dtype)
    return dq.reshape(b, sq, h, d).astype(q.dtype), dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _attention_batch_spec(b: int, h: int, sq: int = 0):
    """When the head count cannot divide the "model" axis, GQA attention
    cannot be head-sharded — XLA then contraction-shards the score einsums
    and all-reduces score-sized tensors every block (measured 16.5 TB/device
    on llama4 train_4k — §Perf log).  Two escapes, in preference order:

    1. batch divides the WHOLE mesh -> shard attention purely over batch
       (fully local, collectives only at entry/exit);
    2. otherwise, Ulysses-style sequence parallelism for prefill: shard the
       q SEQUENCE over "model" (k/v stay model-replicated, which for GQA is
       cheap) — per-device score compute drops by the model-axis size.

    Returns (q_spec, kv_spec) or None."""
    mesh = ambient_mesh()
    if mesh is None:
        return None
    sizes = dict(mesh.shape)
    mdl = sizes.get("model", 1)
    if mdl <= 1 or h % mdl == 0:
        return None                       # head sharding works; leave to XLA
    axes = tuple(a for a in ("pod", "data", "model") if a in sizes)
    total = 1
    for a in axes:
        total *= sizes[a]
    if total > 1 and b % total == 0:
        spec = P(axes, None, None, None)
        return spec, spec
    dp = tuple(a for a in ("pod", "data") if a in sizes)
    ndp = 1
    for a in dp:
        ndp *= sizes[a]
    bax = (dp if len(dp) > 1 else dp[0]) if ndp > 1 and b % ndp == 0 else None
    if sq > 1 and sq % mdl == 0:
        return (P(bax, "model", None, None), P(bax, None, None, None))
    return None


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: float | None = None) -> jnp.ndarray:
    """GQA attention with flash semantics (chunked, O(S) memory, recompute
    backward).  q: (B,Sq,H,d); k,v: (B,Sk,KV,d)."""
    scale = scale if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
    spec = _attention_batch_spec(q.shape[0], q.shape[2], q.shape[1])
    if spec is not None:
        qs, kvs = spec
        q = _maybe_constrain(q, qs)
        k = _maybe_constrain(k, kvs)
        v = _maybe_constrain(v, kvs)
    if _use_pallas():
        from repro.kernels import flash_attention as _k

        # heads split only where both q and kv heads divide: contiguous head
        # blocks then keep every GQA group on one device
        blk = P(_shard_axes(_BATCH_AXES, q.shape[0]), None,
                _shard_axes(("model",), math.gcd(q.shape[2], k.shape[2])),
                None)
        out = _per_shard(
            lambda q, k, v: _k.flash_attention(
                q, k, v, causal=causal, window=window, scale=scale,
                interpret=_interpret()),
            (q, k, v), (blk, blk, blk), blk)
    else:
        out = _flash(q, k, v, causal, window, scale)
    if spec is not None:
        # re-anchor: the Ulysses q-sequence sharding must NOT leak past the
        # attention — downstream MoE layers need the "model" axis for EP
        # (leaked S-sharding measured: full-expert f32 all-gathers on llama4
        # multi-pod prefill — §Perf log)
        out = _maybe_constrain(out, spec[1])
    return out


def ambient_mesh():
    """The mesh set for tracing (``jax.set_mesh``), as an AbstractMesh.
    None when no mesh is set (single-device tests / examples)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _maybe_constrain(x, spec):
    """with_sharding_constraint when the ambient mesh provides the axes;
    no-op otherwise (single-device tests / examples)."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    flat = []
    for ax in spec:
        flat.extend(ax if isinstance(ax, tuple) else [ax])
    if any(ax is not None and ax not in mesh.axis_names for ax in flat):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def decode_attention(q, k_cache, v_cache, valid_mask, *,
                     scale: float | None = None) -> jnp.ndarray:
    """One-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, d); k_cache/v_cache: (B, S, KV, d);
    valid_mask: (B, S) bool — True for live cache slots.
    Memory-bound; a plain einsum is roofline-optimal here.

    Sharding: when KV heads cannot divide the "model" axis the cache is
    head_dim-sharded (see sharding/rules.py); we pin q to the same layout so
    the contraction is local and only the (tiny) score partial-sums are
    all-reduced — instead of XLA re-gathering the whole cache per step.
    """
    b, _, h, d = q.shape
    _, s, kv, _ = k_cache.shape
    g = h // kv
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    # keep the cache in its storage dtype (bf16) and accumulate in f32 via
    # preferred_element_type — upcasting the cache would materialize (and,
    # under SPMD, re-gather) a full-precision copy of the whole cache.
    qg = (q.reshape(b, kv, g, d) * scale).astype(k_cache.dtype)
    mesh = ambient_mesh()
    mdl = mesh.shape.get("model", 1) if mesh is not None else 1
    if mdl > 1 and kv % mdl and d % mdl == 0:
        # hd-sharded-cache regime (see sharding/rules.py)
        qg = _maybe_constrain(qg, P(None, None, None, "model"))
    sc = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                    preferred_element_type=jnp.float32)
    sc = jnp.where(valid_mask[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, d).astype(q.dtype)


def chunk_prefill_attention(q, k_new, v_new, pool_k, pool_v, table, start, *,
                            block_size: int, window: int = 0,
                            scale: float | None = None) -> jnp.ndarray:
    """Prefill-CONTINUATION attention for ONE slot over the paged pool —
    the compute behind chunked prefill and prefix-shared admission.

    q, k_new, v_new: (1, C, H|KV, d) — the chunk's fresh projections, global
    positions ``start + i`` (pad rows allowed past the real tail; they are
    causally invisible to real rows and their outputs are discarded);
    pool_k/pool_v: (R, KV, d) one layer's row pool; table: (MB,) int32 the
    slot's block-table row; start: () int32 rows already resident (shared
    prefix blocks and/or earlier chunks).

    Gathers the slot's capacity window (static MB*block_size rows — unlike
    decode this is NOT the hot loop; admission cost amortizes over the whole
    sequence), substitutes the chunk's fresh KV at its own rows, and runs
    the SAME chunked online-softmax forward full prefill uses
    (``_flash_fwd_impl``) with explicit global q positions.  Keys at
    logical positions > q_pos (stale rows, null-block rows, chunk pads) are
    causally masked and contribute exact zeros, which is what keeps a
    chunk's rows bitwise equal to the whole-prompt prefill on the jnp path
    (see ``_flash_fwd_impl``; the TPU whole-prefill path runs the Pallas
    flash kernel instead, where the contract is allclose, not bitwise).
    """
    scale = scale if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
    c = q.shape[1]
    bs = block_size
    flat = (table[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
    # pad the window to the next POWER OF TWO (extra rows read the pool's
    # last null-block row; their positions exceed every real q_pos, so the
    # causal mask kills them).  The whole-prompt path always runs flash at
    # a pow2 key length (admission buckets), and pow2 lengths reduce with
    # identical real-element grouping — appended masked keys contribute
    # exact zeros.  A NON-pow2 capacity window (e.g. 48 rows) makes the
    # backend regroup the reduction value-dependently, which broke the
    # chunk==dense bit contract once a row attended past the regroup
    # boundary; the pad closes that hole.
    w = flat.shape[0]
    p2 = 1
    while p2 < w:
        p2 *= 2
    if p2 != w:
        flat = jnp.concatenate(
            [flat, jnp.full((p2 - w,), pool_k.shape[0] - 1, flat.dtype)])
    kw = pool_k[flat]                       # (pow2 >= MB*bs, KV, d)
    vw = pool_v[flat]
    idx = start + jnp.arange(c)
    # pad rows past the window clamp onto nothing ("drop"): they are masked
    # for every real query anyway
    kw = kw.at[idx].set(k_new[0], mode="drop")
    vw = vw.at[idx].set(v_new[0], mode="drop")
    out, _ = _flash_fwd_impl(q, kw[None], vw[None], True, window, scale,
                             q_pos=idx)
    return out


def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, layer, tables,
                           pos, *, block_size: int, window: int = 0,
                           scale: float | None = None) -> jnp.ndarray:
    """One-token attention straight off the paged KV pool — the serving hot
    loop's attention (no dense per-slot gather is ever materialized).

    q: (S, 1, H, d) decode queries; k_new/v_new: (S, KV, d) the in-flight
    token's KV (scattered into the pool by the caller AFTER this);
    pool_k/pool_v: (n, R, KV, d) the stacked row pools of all layers, as
    stored; layer: () int32 the layer to read; tables: (S, MB) int32;
    pos: (S,) int32 cached rows per slot.

    Dispatch: flash-decoding Pallas kernel on TPU (or REPRO_PALLAS=interpret),
    which reads the layer's live pages out of the stacked pool itself; else
    the chunked two-pass jnp reference on ``pool[layer]`` — which is BITWISE
    equal to ``decode_attention`` over the dense-gathered view, preserving
    the serving engine's bit-compatibility with the synchronized rollout
    engine.
    """
    if _use_pallas():
        from repro.kernels import paged_attention as _k

        out = _k.paged_decode_attention(
            q[:, 0], k_new, v_new, pool_k, pool_v, layer, tables, pos,
            block_size=block_size, window=window, scale=scale,
            interpret=_interpret())
        return out[:, None]
    return ref.paged_decode_attention(q, k_new, v_new, pool_k[layer],
                                      pool_v[layer], tables, pos,
                                      block_size=block_size, window=window,
                                      scale=scale)


# ---------------------------------------------------------------------------
# grouped matmul (MoE)
# ---------------------------------------------------------------------------

def gmm(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
        layer=0, tile_t: int = 128, block_f: int = 512) -> jnp.ndarray:
    """Grouped matmul: x (T,d) sorted by group, w (E,d,f) or a layer stack
    (L,E,d,f) read at ``layer``, group_sizes (E,) summing to at most T; rows
    past the groups are not computed (TPU: left unwritten, CPU: zero).

    TPU path: Pallas kernel with MXU-aligned tiles (caller must align group
    boundaries to ``tile_t``), reading a stack's layer in place.  CPU path:
    each row tile times its group's weights, gathered per tile (tiles past
    the groups give zero).
    """
    if _use_pallas():
        from repro.kernels import gmm as _k

        return _k.gmm(x, w, group_sizes, layer, tile_t=tile_t,
                      block_f=block_f, interpret=_interpret())
    if w.ndim == 4:
        w = w[layer]
    t, d = x.shape
    e = w.shape[0]
    nt = t // tile_t
    bounds = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(bounds, jnp.arange(nt) * tile_t, side="right")
    wt = w[jnp.minimum(gid, e - 1)].astype(x.dtype)          # (nt, d, f)
    y = jnp.einsum("ntd,ndf->ntf", x.reshape(nt, tile_t, d), wt)
    return jnp.where((gid < e)[:, None, None], y, 0).reshape(t, -1)
