"""Compile the main path's Pallas kernels for a described TPU v5e chip, at
real widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that is
described, not attached, and refuses what the chip would refuse (block
shapes off the (8, 128) tiling, more VMEM than a kernel may use, a kernel
that autodiff cannot pass) — faults interpret mode never shows.  Widths are
yi-6b's (d_model 4096, 32 query heads over 4 KV heads of 128, d_ff 11008)
and, for the MoE grouped matmul and the MoE serving cell's decode step and
chunk prefill, qwen3-moe-30b's (d_model 2048, 128 experts of d_ff 768, 16
held).

The topology is described inside a module-scoped fixture, so importing this
file loads no TPU library; under several pytest workers only the worker
that runs these tests loads it.  The persistent compilation cache is off
while they run: a compile for a described chip cannot be read back.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (flash_attention, gmm, paged_attention, rmsnorm,
                           rope, swiglu)

D, H, KV, HD, FF = 4096, 32, 4, 128, 11008      # yi-6b
B, S = 8, 128                                    # sequences, tokens each


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fwd_and_grad(fn, args, argnums):
    """Compile the kernel's forward, and value_and_grad through it (the
    value keeps the forward kernel in the program)."""
    _compile(fn, *args)

    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    _compile(jax.value_and_grad(loss, argnums=argnums), *args)


def _paged_decode(sh, *, slots, entries, block, layers, h, kv, hd=HD):
    """Compile the paged decode kernel over a stacked ``layers``-layer pool
    sized for ``slots`` sequences of ``entries`` table entries."""
    rows = (slots * entries + 1) * block               # + the null block
    return _compile(
        lambda q, kn, vn, pk, pv, ly, t, p:
        paged_attention.paged_decode_attention(
            q, kn, vn, pk, pv, ly, t, p, block_size=block, interpret=False),
        sh((slots, h, hd)), sh((slots, kv, hd)), sh((slots, kv, hd)),
        sh((layers, rows, kv, hd)), sh((layers, rows, kv, hd)),
        sh((), jnp.int32), sh((slots, entries), jnp.int32),
        sh((slots,), jnp.int32))


def test_paged_decode_attention_8_slots(one_chip):
    sh = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)  # noqa: E731
    compiled = _paged_decode(sh, slots=8, entries=4, block=16, layers=1,
                             h=H, kv=KV)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("h,kv", [(H, KV), (28, 4)],
                         ids=["yi6b_g8", "qwen25_7b_g7"])
def test_paged_decode_attention_serving_shapes(one_chip, h, kv):
    """The serving cell's shapes: 128 slots, 64 table entries of 16 rows,
    the stacked 8-layer pool read in place (no copy of it feeds the
    kernel); and Qwen2.5-7B's 7 query heads per KV head."""
    sh = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)  # noqa: E731
    compiled = _paged_decode(sh, slots=128, entries=64, block=16, layers=8,
                             h=h, kv=kv)
    text = compiled.as_text()
    pool = "bf16[8,131088,4,128]"
    made = [ln for ln in text.splitlines()
            if f"= {pool}" in ln and " parameter(" not in ln]
    assert not made, made


def test_decode_layer_scan_reads_pool_in_place(one_chip, monkeypatch):
    """An 8-layer ``decode_paged`` at yi-6b widths over the serving pool:
    no op inside the layer scan's while body makes a pool-sized array —
    neither a per-layer slice (``[..., 131088, 4, 128]``) nor the kernel's
    old page relayout (``[..., 8193, 16, 512]``); the stacked pool only
    passes through the loop to the kernel."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.model import build_model

    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("yi-6b").replace(num_layers=8)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                          shapes)
    slots, entries, block = 128, 64, 16
    pool = _shape(one_chip, (8, (slots * entries + 1) * block, KV, HD))
    compiled = _compile(
        lambda p, pk, pv, t, tok, pos: model.decode_paged(
            p, cfg, pk, pv, t, tok, pos, block_size=block),
        params, pool, pool, _shape(one_chip, (slots, entries), jnp.int32),
        _shape(one_chip, (slots, 1), jnp.int32),
        _shape(one_chip, (slots,), jnp.int32))
    text = compiled.as_text()
    _assert_not_made_in_scan(text, r"(131088,4,128|8193,16,512)")
    assert "paged_decode_attention" in text


def test_flash_attention_fwd_and_grad(one_chip):
    args = (_shape(one_chip, (B, S, H, HD)), _shape(one_chip, (B, S, KV, HD)),
            _shape(one_chip, (B, S, KV, HD)))
    _fwd_and_grad(lambda q, k, v: flash_attention.flash_attention(
        q, k, v, causal=True, interpret=False), args, (0, 1, 2))


def test_rmsnorm_fwd_and_grad(one_chip):
    args = (_shape(one_chip, (B * S, D)), _shape(one_chip, (D,)))
    _fwd_and_grad(lambda x, w: rmsnorm.rmsnorm(x, w, interpret=False),
                  args, (0, 1))


def test_swiglu_fwd_and_grad(one_chip):
    args = (_shape(one_chip, (B * S, FF)), _shape(one_chip, (B * S, FF)))
    _fwd_and_grad(lambda g, u: swiglu.swiglu(g, u, interpret=False),
                  args, (0, 1))


def _assert_not_made_in_scan(text, dims):
    """No op inside a layer scan's while body of the compiled program
    ``text`` makes a bfloat16 array whose trailing dims match ``dims`` (a
    regex): such an array only passes through the loop."""
    import re

    bodies = set(re.findall(r"\bwhile\(.*?body=%?([\w.-]+)", text))
    assert bodies, "no layer scan in the program"
    made = re.compile(r"=\s*bf16\[(?:\d+,)*" + dims + r"\]")
    seen = 0
    for comp in re.split(r"\n(?=\S)", text):
        name = re.match(r"%?([\w.-]+)", comp)
        if not name or name.group(1) not in bodies:
            continue
        seen += 1
        for ln in comp.splitlines()[1:]:
            if made.search(ln) and "get-tuple-element(" not in ln \
                    and " parameter(" not in ln:
                raise AssertionError(f"op in the layer scan makes it: "
                                     f"{ln.strip()[:200]}")
    assert seen == len(bodies)


def test_rope_fwd_and_grad(one_chip):
    args = (_shape(one_chip, (B, S, H, HD)),
            _shape(one_chip, (B, S, HD // 2), jnp.float32),
            _shape(one_chip, (B, S, HD // 2), jnp.float32))
    _fwd_and_grad(lambda x, c, s: rope.apply_rope(x, c, s, interpret=False),
                  args, (0,))


@pytest.mark.parametrize("d,f", [(2048, 768), (768, 2048)],
                         ids=["gate_up", "down"])
def test_gmm_qwen3_moe_widths(one_chip, d, f):
    experts, tile = 128, 128
    _compile(lambda x, w, g: gmm.gmm(x, w, g, tile_t=tile, interpret=False),
             _shape(one_chip, (experts * tile, d)),
             _shape(one_chip, (experts, d, f)),
             _shape(one_chip, (experts,), jnp.int32))


def _qwen3_moe_cell(one_chip, monkeypatch):
    """The ``qwen3moe-12l.rollout`` cell's model on the described chip:
    Qwen3-30B-A3B's widths, 12 layers, 16 of 128 experts held; its
    parameters' shapes, and ``ops`` steered to the Pallas kernels."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.model import build_model

    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("qwen3-moe-30b").replace(num_layers=12, moe_held=(0, 16))
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                          shapes)
    return cfg, model, params


QWEN3_SLOTS, QWEN3_ENTRIES, QWEN3_BLOCK = 256, 64, 16
# one layer's table of the 16 held experts: the grouped matmul reads it in
# place from the stacked tables, where a per-layer slice would copy 50 MB
QWEN3_TABLE = r"(16,2048,768|16,768,2048)"


def _qwen3_pool(one_chip):
    rows = (QWEN3_SLOTS * QWEN3_ENTRIES + 1) * QWEN3_BLOCK
    return _shape(one_chip, (12, rows, 4, HD))


def test_qwen3_moe_decode_step(one_chip, monkeypatch):
    """The MoE cell's decode step: 256 slots over the 12-layer pool, the
    router over 128 experts, the dropless expert FFN through the grouped
    matmul over the 16 held, and its counts returned."""
    cfg, model, params = _qwen3_moe_cell(one_chip, monkeypatch)
    pool, slots = _qwen3_pool(one_chip), QWEN3_SLOTS
    compiled = _compile(
        lambda p, pk, pv, t, tok, pos: model.decode_paged(
            p, cfg, pk, pv, t, tok, pos, block_size=QWEN3_BLOCK),
        params, pool, pool,
        _shape(one_chip, (slots, QWEN3_ENTRIES), jnp.int32),
        _shape(one_chip, (slots, 1), jnp.int32),
        _shape(one_chip, (slots,), jnp.int32))
    text = compiled.as_text()
    assert "moe_gmm" in text and "paged_decode_attention" in text
    _assert_not_made_in_scan(text, QWEN3_TABLE)


@pytest.mark.parametrize("chunk", [16, 512])
def test_qwen3_moe_chunk_prefill(one_chip, monkeypatch, chunk):
    """The MoE cell's continuation prefill of one chunk against the pool, at
    the shortest tail bucket and the longest prompt bucket."""
    cfg, model, params = _qwen3_moe_cell(one_chip, monkeypatch)
    pool = _qwen3_pool(one_chip)
    compiled = _compile(
        lambda p, pk, pv, t, tok, st, last: model.prefill_paged(
            p, cfg, pk, pv, t, tok, st, block_size=QWEN3_BLOCK, last=last),
        params, pool, pool, _shape(one_chip, (QWEN3_ENTRIES,), jnp.int32),
        _shape(one_chip, (1, chunk), jnp.int32),
        _shape(one_chip, (), jnp.int32), _shape(one_chip, (), jnp.int32))
    text = compiled.as_text()
    assert "moe_gmm" in text
    _assert_not_made_in_scan(text, QWEN3_TABLE)


def test_sharded_train_step_runs_kernels_per_shard(topo, monkeypatch):
    """The GRPO update step on a 2x2 ("data", "model") mesh, at smoke
    widths, with ``ops`` steered to the Pallas kernels as on a TPU: XLA
    cannot partition a Pallas kernel, so this compiles only because ``ops``
    runs each kernel per shard under the ambient mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.configs.base import RLConfig
    from repro.core import grpo
    from repro.kernels import ops
    from repro.models.model import build_model
    from repro.optim.adamw import AdamWState
    from repro.sharding import param_specs

    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    cfg = get_smoke_config("yi-6b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, param_specs(cfg, shapes, mesh, stage="train"))
    mu = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
        p.shape, jnp.float32, sharding=p.sharding), params)
    opt = AdamWState(step=jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=NamedSharding(mesh, P())), mu=mu, nu=mu)
    b, s = 8, 64
    rep = NamedSharding(mesh, P())
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rep),
             "response_mask": jax.ShapeDtypeStruct((b, s), jnp.float32,
                                                   sharding=rep),
             "old_logp": jax.ShapeDtypeStruct((b, s - 1), jnp.float32,
                                              sharding=rep),
             "ref_logp": jax.ShapeDtypeStruct((b, s - 1), jnp.float32,
                                              sharding=rep),
             "advantages": jax.ShapeDtypeStruct((b,), jnp.float32,
                                                sharding=rep)}
    step = jax.jit(grpo.make_train_step(cfg, RLConfig()),
                   donate_argnums=(0, 1))
    with jax.set_mesh(mesh):
        _compile(step, params, opt, batch)
