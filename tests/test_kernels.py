"""Per-kernel correctness: Pallas (interpret mode) vs the pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, gmm, ops, ref, rmsnorm, rope, swiglu

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("rows,d", [(8, 64), (64, 256), (33, 128), (128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(rows, d, dtype, rng):
    x = jax.random.normal(rng, (rows, d), dtype)
    w = jax.random.normal(jax.random.fold_in(rng, 1), (d,), dtype)
    out = rmsnorm.rmsnorm(x, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.rmsnorm(x, w), np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("rows,f", [(16, 64), (64, 512), (100, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu(rows, f, dtype, rng):
    g = jax.random.normal(rng, (rows, f), dtype)
    u = jax.random.normal(jax.random.fold_in(rng, 1), (rows, f), dtype)
    out = swiglu.swiglu(g, u, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.swiglu(g, u), np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("b,s,h,d", [(2, 16, 4, 32), (1, 64, 8, 64),
                                     (3, 24, 2, 128)])
def test_rope(b, s, h, d, rng):
    x = jax.random.normal(rng, (b, s, h, d))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    cos, sin = ops.rope_tables(pos, d, 10_000.0)
    out = rope.apply_rope(x, cos, sin, interpret=True)
    want = ref.rope(x, cos[:, :, None, :], sin[:, :, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,sq,h,kv,d", [
    (2, 32, 8, 2, 16), (1, 64, 4, 4, 32), (2, 128, 8, 1, 64)])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, sq, h, kv, d, window, dtype, rng):
    q = jax.random.normal(rng, (b, sq, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, sq, kv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, sq, kv, d), dtype)
    out = flash_attention.flash_attention(
        q, k, v, causal=True, window=window, interpret=True,
        block_q=16, block_k=16)
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_flash_noncausal(rng):
    q = jax.random.normal(rng, (2, 32, 4, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 32, 4, 16))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 32, 4, 16))
    out = flash_attention.flash_attention(q, k, v, causal=False,
                                          interpret=True, block_q=16)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t,d,f,e,tile", [(256, 32, 64, 4, 64),
                                          (128, 64, 128, 2, 32),
                                          (512, 16, 32, 8, 64)])
def test_gmm(t, d, f, e, tile, rng):
    # group sizes: tile-aligned (the kernel contract), incl. an empty group
    sizes = np.zeros(e, np.int32)
    remaining = t
    for i in range(e - 1):
        take = min(remaining, tile * (i % 3))
        sizes[i] = take
        remaining -= take
    sizes[-1] = remaining
    gs = jnp.asarray(sizes)
    x = jax.random.normal(rng, (t, d))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (e, d, f))
    out = gmm.gmm(x, w, gs, tile_t=tile, interpret=True)
    want = ref.gmm(x, w, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("stacked", [False, True], ids=["table", "stack"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gmm_rows_past_the_groups(dtype, stacked, rng):
    """A buffer sized for the worst case, filled only in part (the serving
    path's expert FFN): the rows of the groups match the oracle; the tiles
    past them are not computed (zero on the jnp path).  ``stack``: the
    weights are layer 1 of a 3-layer stack, read in place."""
    t, d, f, e, tile = 256, 32, 256, 4, 16
    sizes = jnp.asarray([32, 0, 16, 48], jnp.int32)       # 6 of 16 tiles
    x = jax.random.normal(rng, (t, d)).astype(dtype)
    ws = jax.random.normal(jax.random.fold_in(rng, 1),
                           (3, e, d, f)).astype(dtype)
    w, layer = (ws, 1) if stacked else (ws[1], 0)
    live = int(jnp.sum(sizes))
    want = ref.gmm(x[:live], ws[1], sizes)
    tol = 1e-4 if dtype == "float32" else 2e-2
    out = gmm.gmm(x, w, sizes, layer, tile_t=tile, block_f=128,
                  interpret=True)
    np.testing.assert_allclose(np.asarray(out[:live], np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)
    cpu = ops.gmm(x, w, sizes, layer, tile_t=tile)
    np.testing.assert_allclose(np.asarray(cpu[:live], np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)
    assert not np.any(np.asarray(cpu[live:], np.float32))


def test_ops_attention_grads_match_naive(rng):
    """custom-VJP flash backward == autodiff through the naive oracle."""
    q = jax.random.normal(rng, (2, 32, 8, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 32, 2, 16))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 32, 2, 16))

    def f_ops(q, k, v):
        return (ops.attention(q, k, v, causal=True, window=8) ** 2).sum()

    def f_ref(q, k, v):
        return (ref.attention(q, k, v, causal=True, window=8) ** 2).sum()

    g1 = jax.grad(f_ops, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_decode_attention_matches_last_position(rng):
    q = jax.random.normal(rng, (2, 1, 8, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 32, 2, 16))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 32, 2, 16))
    valid = jnp.ones((2, 32), bool)
    out = ops.decode_attention(q, k, v, valid)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def _grads(fn, args, argnums):
    return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=argnums)(
        *args)


@pytest.mark.parametrize("kernel", ["rmsnorm", "swiglu", "rope", "flash"])
def test_kernel_vjp_matches_reference_grad(kernel, rng):
    """Each Pallas forward's custom VJP (interpret mode) == jax.grad of the
    jnp reference, for every differentiable input."""
    ks = [jax.random.fold_in(rng, i) for i in range(3)]
    if kernel == "rmsnorm":
        args = (jax.random.normal(ks[0], (24, 128)),
                jax.random.normal(ks[1], (128,)))
        pallas = lambda x, w: rmsnorm.rmsnorm(x, w, interpret=True)  # noqa: E731
        want = ref.rmsnorm
    elif kernel == "swiglu":
        args = (jax.random.normal(ks[0], (24, 256)),
                jax.random.normal(ks[1], (24, 256)))
        pallas = lambda g, u: swiglu.swiglu(g, u, interpret=True)  # noqa: E731
        want = ref.swiglu
    elif kernel == "rope":
        pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
        cos, sin = ops.rope_tables(pos, 32, 10_000.0)
        args = (jax.random.normal(ks[0], (2, 16, 4, 32)), cos, sin)
        pallas = lambda x, c, s: rope.apply_rope(x, c, s, interpret=True)  # noqa: E731
        want = lambda x, c, s: ref.rope(x, c[:, :, None], s[:, :, None])  # noqa: E731
    else:
        args = (jax.random.normal(ks[0], (2, 32, 8, 16)),
                jax.random.normal(ks[1], (2, 32, 2, 16)),
                jax.random.normal(ks[2], (2, 32, 2, 16)))
        pallas = lambda q, k, v: flash_attention.flash_attention(  # noqa: E731
            q, k, v, causal=True, window=8, interpret=True, block_q=16,
            block_k=16)
        want = lambda q, k, v: ref.attention(q, k, v, causal=True, window=8)  # noqa: E731
    argnums = tuple(range(len(args)))
    for got, exp in zip(_grads(pallas, args, argnums),
                        _grads(want, args, argnums)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-4, atol=1e-5)
