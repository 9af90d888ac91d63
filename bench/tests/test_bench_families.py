"""A configuration finds its model family and reference by name: the
weights each existing configuration makes from a seed are those it made
before the lookup existed, and a new family comes in as new files only."""
import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

import harness
import smoke
import weights

TOY = os.path.join(smoke.BENCH, "tests", "data", "toy_family")
SEED = 2**31 + 12345

# (leaf path, shape, dtype, first 16 hex digits of the sha256 of its
# bytes) of the weights made from SEED at the smoke widths, taken when
# the tree was laid out by bench/weights.py itself
LLAMA = [
    ("['embed']", (8192, 1024), "bfloat16", "8ea6081bc6bf284e"),
    ("['layers']['attn']['wk']", (2, 1024, 128), "bfloat16", "fc95bc457ff851f7"),
    ("['layers']['attn']['wo']", (2, 1024, 1024), "bfloat16", "5c3dc130cc0c39af"),
    ("['layers']['attn']['wq']", (2, 1024, 1024), "bfloat16", "7962b23cd5db847f"),
    ("['layers']['attn']['wv']", (2, 1024, 128), "bfloat16", "b9ee6dfc52d99677"),
    ("['layers']['ln1']['scale']", (2, 1024), "bfloat16", "045ad01d502fc9de"),
    ("['layers']['ln2']['scale']", (2, 1024), "bfloat16", "045ad01d502fc9de"),
    ("['layers']['mlp']['w_down']", (2, 2048, 1024), "bfloat16", "3daf7228fb78890f"),
    ("['layers']['mlp']['w_gate']", (2, 1024, 2048), "bfloat16", "8b0b8640355ebd6b"),
    ("['layers']['mlp']['w_up']", (2, 1024, 2048), "bfloat16", "0cf5a0c9d78f0168"),
    ("['lm_head']", (1024, 8192), "bfloat16", "57a813c08e15d351"),
    ("['ln_f']['scale']", (1024,), "bfloat16", "95fbbe9f3324b7d9"),
]
QWEN2 = [
    ("['embed']", (8192, 1024), "bfloat16", "8ea6081bc6bf284e"),
    ("['layers']['attn']['bk']", (2, 128), "bfloat16", "3629742e0476e746"),
    ("['layers']['attn']['bq']", (2, 1024), "bfloat16", "97f9e8928fb5b51c"),
    ("['layers']['attn']['bv']", (2, 128), "bfloat16", "58af7d22278e3479"),
    ("['layers']['attn']['wk']", (2, 1024, 128), "bfloat16", "b9ee6dfc52d99677"),
    ("['layers']['attn']['wo']", (2, 1024, 1024), "bfloat16", "57b0b4a9e21d83f1"),
    ("['layers']['attn']['wq']", (2, 1024, 1024), "bfloat16", "1035b3cd5924a845"),
    ("['layers']['attn']['wv']", (2, 1024, 128), "bfloat16", "94646a8b8bf72e95"),
    ("['layers']['ln1']['scale']", (2, 1024), "bfloat16", "045ad01d502fc9de"),
    ("['layers']['ln2']['scale']", (2, 1024), "bfloat16", "045ad01d502fc9de"),
    ("['layers']['mlp']['w_down']", (2, 2048, 1024), "bfloat16", "9ad37dd9a685bca3"),
    ("['layers']['mlp']['w_gate']", (2, 1024, 2048), "bfloat16", "e8d6f50667cd8d91"),
    ("['layers']['mlp']['w_up']", (2, 1024, 2048), "bfloat16", "a688d01bad723698"),
    ("['lm_head']", (1024, 8192), "bfloat16", "ba35e116e738893d"),
    ("['ln_f']['scale']", (1024,), "bfloat16", "95fbbe9f3324b7d9"),
]


@pytest.mark.parametrize("name,want", [("yi-6b.8l", LLAMA),
                                       ("yi-6b.2l", LLAMA),
                                       ("qwen2.5-7b.7l", QWEN2)])
def test_weights_from_a_seed_are_unchanged(name, want):
    c = {**harness.load_json("configs", f"{name}.json"), **smoke.SMOKE_CONFIG}
    w = jax.jit(lambda k: weights.init(c, k))(weights.key(SEED))
    got = [(jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype),
            hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16])
           for p, x in jax.tree_util.tree_flatten_with_path(w)[0]]
    assert got == want


def _files(root):
    out = {}
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", ".jax_cache")]
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_family_is_added_as_new_files_only(tmp_path):
    """A copy of the benchmark takes a family whose FFN and norms differ
    from Llama's (GELU over two matrices, LayerNorm with a bias) by its
    family, reference, configuration and limits files, and runs a rollout
    cell on it through the harness, correct; no file of the copy that was
    there before is touched."""
    copy = str(tmp_path / "bench")
    shutil.copytree(smoke.BENCH, copy, ignore=shutil.ignore_patterns(
        "__pycache__", ".jax_cache", "tests"))
    before = _files(copy)
    for d, _, names in os.walk(TOY):
        for n in names:
            rel = os.path.relpath(os.path.join(d, n), TOY)
            assert rel not in before, rel
            os.makedirs(os.path.dirname(os.path.join(copy, rel)),
                        exist_ok=True)
            shutil.copy(os.path.join(TOY, rel), os.path.join(copy, rel))
    with open(os.path.join(smoke.BENCH, os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "toy-gelu.rollout"
    bench["workloads"] = [{"name": cell, "config": "toy-gelu",
                           "traffic": "rollout.groups8", "chips": 1,
                           "why": "a toy family"}]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = [cell]
    rc, out, run = smoke.run_cell(cell, "rollout", bench_dir=copy,
                                  bench=bench)
    assert rc == 0
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "rollout_tokens_per_s"}
    assert run.readings["tokens"] > 0
    after = _files(copy)
    assert {k: after[k] for k in before} == before
