"""MoE implementation paths: the capacity-dispatch einsum and the GMM
dropless path must agree when capacity admits every token."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import moe


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "qwen3-moe-30b",
                                  "llama4-maverick-400b-a17b"])
def test_gmm_path_matches_dispatch(arch, rng):
    cfg = get_smoke_config(arch).replace(
        dtype="float32", remat=False, moe_capacity_factor=8.0)
    p = moe.moe_init(cfg, rng, 1)
    lp = jax.tree.map(lambda v: v[0], p)
    x = jax.random.normal(jax.random.fold_in(rng, 1),
                          (2, 16, cfg.d_model)) * 0.5
    y_disp, aux1 = moe.moe_apply(lp, cfg, x)
    y_gmm, aux2 = moe.moe_apply(lp, cfg.replace(moe_impl="gmm"), x)
    np.testing.assert_allclose(np.asarray(y_disp), np.asarray(y_gmm),
                               rtol=1e-4, atol=1e-5)
    assert float(aux1) == pytest.approx(float(aux2), rel=1e-5)


def test_dispatch_drops_when_capacity_low(rng):
    """With tiny capacity the dispatch path drops tokens (outputs differ
    from the dropless GMM path) — the documented trade-off."""
    cfg = get_smoke_config("mixtral-8x7b").replace(
        dtype="float32", remat=False, moe_capacity_factor=0.25)
    p = moe.moe_init(cfg, rng, 1)
    lp = jax.tree.map(lambda v: v[0], p)
    x = jax.random.normal(jax.random.fold_in(rng, 2),
                          (2, 32, cfg.d_model)) * 0.5
    y_disp, _ = moe.moe_apply(lp, cfg, x)
    y_gmm, _ = moe.moe_apply(lp, cfg.replace(moe_impl="gmm"), x)
    assert np.max(np.abs(np.asarray(y_disp) - np.asarray(y_gmm))) > 1e-3


def test_moe_forward_with_gmm_impl(rng):
    cfg = get_smoke_config("qwen3-moe-30b").replace(
        dtype="float32", remat=False, moe_impl="gmm")
    from repro.models.model import build_model
    m = build_model(cfg)
    params = m.init(cfg, rng)
    batch = {"tokens": jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)}
    logits, aux = m.forward(params, cfg, batch)
    assert not np.any(np.isnan(np.asarray(logits)))


def test_decode_gather_path_matches_dispatch(rng):
    """Small-batch decode (b*k*4 <= E) goes through the dropless grouped
    matmul, which reads only the picked experts' weights; it must match
    the dispatch-form result."""
    cfg = get_smoke_config("llama4-maverick-400b-a17b").replace(
        dtype="float32", remat=False, moe_capacity_factor=8.0)
    assert cfg.num_experts == 4 and cfg.experts_per_token == 1
    p = moe.moe_init(cfg, rng, 1)
    lp = jax.tree.map(lambda v: v[0], p)
    x = jax.random.normal(jax.random.fold_in(rng, 3),
                          (1, 1, cfg.d_model)) * 0.5   # b*k*4 = 4 <= E
    y_gather, _ = moe.expert_ffn(lp, cfg, x)
    y_disp, _ = moe.moe_apply(lp, cfg, x.reshape(1, 1, -1))
    np.testing.assert_allclose(np.asarray(y_gather), np.asarray(y_disp),
                               rtol=1e-4, atol=1e-5)


def _cut(p: dict, first: int, n: int) -> dict:
    """One chip's share of an expert layer: the router whole, the expert
    tables' ``first``..``first + n - 1``."""
    return {k: v if k == "router" else v[first:first + n]
            for k, v in p.items()}


@pytest.mark.parametrize("path", ["serve", "dispatch"])
def test_expert_shares_add_up(path, rng):
    """Eight chips of an expert-parallel layer, each told which 2 of the 16
    experts it holds: their partial outputs sum to the uncut layer's output
    (the router keeps its 16 outputs and 4 picks in every share)."""
    cfg = get_smoke_config("qwen3-moe-30b").replace(
        dtype="float32", remat=False, num_experts=16, experts_per_token=4,
        moe_capacity_factor=16.0)
    p = jax.tree.map(lambda v: v[0], moe.moe_init(cfg, rng, 1))
    x = jax.random.normal(jax.random.fold_in(rng, 4),
                          (2, 12, cfg.d_model)) * 0.5

    def layer(p, cfg):
        if path == "serve":
            return moe.expert_ffn(p, cfg, x)[0]
        return moe.moe_apply(p, cfg, x)[0]

    whole = layer(p, cfg)
    parts = [layer(_cut(p, 2 * i, 2), cfg.replace(moe_held=(2 * i, 2)))
             for i in range(8)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # a share alone is a part, not the whole
    assert np.max(np.abs(np.asarray(parts[0] - whole))) > 1e-2


def test_dropless_counts_rows_held(rng):
    """The serving path's counts: rows routed to the held experts, rows the
    grouped matmul computes (whole tiles), one call, held experts with a
    row."""
    cfg = get_smoke_config("qwen3-moe-30b").replace(
        dtype="float32", remat=False, num_experts=16, experts_per_token=4,
        moe_held=(4, 4))
    p = jax.tree.map(lambda v: v[0], moe.moe_init(cfg, rng, 1))
    x = jax.random.normal(jax.random.fold_in(rng, 5), (3, 8, cfg.d_model))
    _, st = moe.expert_ffn(p, cfg, x)
    rows, padded, calls, experts = (int(v) for v in st)
    _, idx, _ = moe._route(p, cfg, x)
    local = np.asarray(idx).reshape(-1) - 4
    mine = local[(local >= 0) & (local < 4)]
    tile = moe._gmm_tile(24 * 4, 16)
    assert rows == len(mine) and calls == 1
    assert experts == len(set(mine.tolist()))
    assert padded == sum(-(-np.sum(mine == e) // tile) * tile
                         for e in range(4))
