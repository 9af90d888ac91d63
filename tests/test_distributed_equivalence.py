"""Distributed correctness: forward/train-step on a multi-device mesh must
match the single-device result — this validates every sharding rule and
with_sharding_constraint added by the perf work.  Runs in a subprocess (the
8-device XLA flag must precede jax init)."""
import json
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, numpy as np
import jax.numpy as jnp
jax.config.update("jax_default_matmul_precision", "highest")
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.configs.base import RLConfig
from repro.launch.mesh import make_mesh
from repro.core import grpo
from repro.models.model import build_model
from repro.optim import adamw_init
from repro.sharding import param_specs, batch_partition

out = {}
for arch in ("yi-6b", "mixtral-8x7b", "mamba2-1.3b"):
    cfg = get_smoke_config(arch).replace(dtype="float32", remat=False)
    m = build_model(cfg)
    params = m.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                          cfg.vocab_size)}
    # single device
    logits1, _ = jax.jit(lambda p, b: m.forward(p, cfg, b))(params, batch)

    # 8-device mesh (2 data x 4 model), full sharding rules + constraints
    mesh = make_mesh((2, 4), ("data", "model"))
    specs = param_specs(cfg, params, mesh, stage="train")
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    pd = jax.device_put(params, shardings)
    bd = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    with jax.set_mesh(mesh):
        logits8, _ = jax.jit(lambda p, b: m.forward(p, cfg, b))(pd, bd)
    err = float(np.max(np.abs(np.asarray(logits1) - np.asarray(logits8))))
    scale = float(np.max(np.abs(np.asarray(logits1))))
    out[arch] = {"err": err, "scale": scale}
print(json.dumps(out))
"""


def test_mesh_forward_matches_single_device():
    res = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, cwd="/root/repo",
                         timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for arch, r in out.items():
        assert r["err"] <= 1e-3 * max(r["scale"], 1.0), (arch, r)
