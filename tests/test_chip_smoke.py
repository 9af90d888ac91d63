"""chip_smoke.py's phases at smoke widths on the CPU, so a broken bring-up
script shows here before chip time is spent on it.  The chip run itself
(``python chip_smoke.py``) refuses any platform but TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_smoke_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu_before_any_work(smoke, capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""                       # no observation, no result
    assert "needs a TPU" in out.err


def test_one_chip_phases_at_smoke_widths(smoke):
    cfg = get_smoke_config("yi-6b")            # bf16 + remat, as on the chip
    trainer = smoke.make_trainer(cfg, seed=0)
    smoke.train_phase(trainer, on_chip=False)
    smoke.serve_phase(cfg, trainer.params, seed=0, on_chip=False)


def test_logp_gap_rejects_a_shifted_context(smoke):
    import numpy as np

    a = np.linspace(-12.0, -9.0, 64)
    smoke.logp_gap("aligned", [(a, a + 1e-3)])
    with pytest.raises(smoke.SmokeFailure):
        smoke.logp_gap("shifted", [(a[1:], a[:-1] + 1.0)])


def test_compile_cache_dir_env_or_checkout(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed, git-ignored .jax_cache of the checkout."""
    import jax

    from repro.launch.cache import use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_four_chip_phase_on_four_cpu_devices():
    """The --four-chips phase on 4 virtual CPU devices with the Pallas
    kernels in interpret mode: the per-shard kernel calls, the placement,
    the relayout and host-swap bit checks and the sharded forward."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke as cs\n"
        "from repro.configs import get_smoke_config\n"
        "cfg = get_smoke_config('yi-6b').replace(num_layers=4)\n"
        "cs.four_chip_phase(cfg, seed=0, on_chip=False)\n"
        "print('{\"ok\": true}')\n" % str(ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS="interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == {"ok": True}
