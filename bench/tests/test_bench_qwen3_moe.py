"""The Qwen3-MoE configuration (``qwen3-30b-a3b.12l-e16``) and its cell
``qwen3moe-12l.rollout``: the family's tree and counts, the serving path
against the plain reference at a small size, and the cell's whole run on
the CPU."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import program
import smoke
import weights

CELL = "qwen3moe-12l.rollout"
CONFIG = "qwen3-30b-a3b.12l-e16"
SEED = 2**31 + 16016
# the per-layer metrics whose reader takes a TPU kernel's device time: on
# the CPU no kernel runs, and they read nothing
TPU_ONLY = {"paged_attention_roofline.rollout", "gmm_roofline.moe"}


def _config(**kw) -> dict:
    return {**harness.load_json("configs", f"{CONFIG}.json"), **kw}


@pytest.fixture
def cell_spread(monkeypatch):
    """The smoke widths with the initialisation scaled so that the logits
    spread as at this configuration's width (std 0.02 x sqrt(2048), about
    0.9, not the smoke default's 1.3): the check's gaps then land near the
    chip's (PERF.md section 2)."""
    c, d = _config(), smoke.SMOKE_CONFIG["hidden_size"]
    monkeypatch.setitem(smoke.SMOKE_CONFIG, "initializer_range",
                        c["initializer_range"] * (c["hidden_size"] / d) ** 0.5)


def test_family_tree_and_parameters():
    """The family's weights tree is the program's, leaf for leaf, and the
    parameters this chip holds match the closed form: per layer attention
    (2048 x 4096 q, 2 x 2048 x 512 k and v, 4096 x 2048 o), norms (2 x
    2048 + 2 x 128), router (2048 x 128) and 16 experts of 3 x 2048 x 768;
    12 layers, the final norm, an untied 151,936-row embedding and head."""
    c = _config()
    fam = harness.family(c)
    cfg = program.model_config(c)
    assert cfg.num_experts == 128 and cfg.experts_held == (0, 16)
    assert cfg.experts_per_token == 8 and cfg.qk_norm
    from repro.models.model import build_model

    tree = jax.eval_shape(lambda k: build_model(cfg).init(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda s: tuple(s.shape), tree)
    assert got == jax.tree.map(tuple, fam.shapes(c),
                               is_leaf=lambda x: isinstance(x, tuple))
    per_layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 2048
                 + 2 * 128 + 2048 * 128 + 16 * 3 * 2048 * 768)
    closed = 12 * per_layer + 2048 + 2 * 151936 * 2048
    assert closed == 1_757_991_936
    assert fam.parameters(c) == closed
    assert sum(int(np.prod(s)) for s in jax.tree.leaves(
        fam.shapes(c), is_leaf=lambda x: isinstance(x, tuple))) == closed
    # a token meets attention, the router and, under uniform routing,
    # 8 x 16 / 128 = 1 held expert
    assert fam.layer_matmul_params(c) == (2048 * 4096 * 2 + 2 * 2048 * 512
                                          + 2048 * 128 + 3 * 2048 * 768)


def test_gmm_cost_convention():
    c = _config()
    fam = harness.family(c)
    f, b = fam.gmm_cost(c, {"serve.moe.rows": 10, "serve.moe.experts": 3})
    assert f == 2 * 3 * 10 * 2048 * 768
    assert b == 2 * (3 * 3 * 2048 * 768 + 10 * 3 * (2048 + 768))


# -- the serving path against the reference, in process -------------------

def _small():
    """The smoke widths with q/k norm scales drawn away from 1 (a trained
    checkpoint's are learned; at 1 the norm barely moves random q and k,
    whose rows already have an RMS near 1)."""
    c = _config(**smoke.SMOKE_CONFIG)
    w = jax.jit(lambda k: weights.init(c, k))(weights.key(SEED))
    attn = w["layers"]["attn"]
    for i, name in enumerate(("q_norm", "k_norm")):
        u = jax.random.uniform(jax.random.PRNGKey(i), attn[name][
            "scale"].shape, minval=0.25, maxval=2.5)
        attn[name] = {"scale": u.astype(attn[name]["scale"].dtype)}
    return c, w


def _served(c, w):
    """Two prompts of one group and one other, served greedily by the
    engine: whole prefill, prefix-shared chunk prefill, paged decode."""
    from repro.serve.engine import ServingEngine

    rng = np.random.default_rng(7)
    head = rng.integers(0, c["vocab_size"], 40, dtype=np.int32)
    other = rng.integers(0, c["vocab_size"], 27, dtype=np.int32)
    eng = ServingEngine(program.model_config(c), max_new=12, eos_id=-1,
                        pad_id=c["eos_token_id"], greedy=True, max_slots=4,
                        block_size=16, max_seq_len=64)
    for p in (head, np.concatenate([head[:32], other[:5]]), other):
        eng.submit(p)
    outs = eng.drain(w)
    counts = eng.metrics.snapshot()["counters"]
    return outs, counts


def _gap(c, w, outs, ref):
    """Widest gap between a served token's logp and the reference's."""
    gap = 0.0
    for o in outs:
        seq = jnp.asarray(np.concatenate([o.prompt, o.gen])[None])
        with jax.default_matmul_precision("highest"):
            lp = np.asarray(ref.token_logp(w, c, seq))[0]
        p = len(o.prompt)
        gap = max(gap, float(np.max(np.abs(
            lp[p - 1:p - 1 + len(o.gen)] - o.gen_logp))))
    return gap


# the bfloat16 program against the float32 reference reads 0.146 here
# (3.8e-6 with the program in float32: the gap is bfloat16's rounding and
# the routing flips it causes); the reference without the gates'
# renormalisation reads 0.70, without the q/k norm 3.6
SERVE_TOL = 0.35


@pytest.fixture(scope="module")
def served():
    c, w = _small()
    outs, counts = _served(c, w)
    return c, w, outs, counts


def test_serving_matches_reference(served):
    """Prefill, prefix-shared chunk prefill and paged decode on the engine
    give each served token the reference's log-probability, within
    ``SERVE_TOL``; the first token's whole logit row agrees too.  The
    engine's expert counters saw the work."""
    c, w, outs, counts = served
    ref = harness.reference(c)
    assert sum(len(o.gen) for o in outs) == 36
    assert _gap(c, w, outs, ref) < SERVE_TOL
    from repro.models import moe

    cfg = program.model_config(c)
    toks = jnp.asarray(outs[0].prompt[None])
    cache = moe.init_cache(cfg, 1, toks.shape[1])
    logits, _ = moe.prefill(w, cfg, {"tokens": toks}, cache)
    with jax.default_matmul_precision("highest"):
        x = ref.hidden(w, c, toks)[:, -1]
        want = x @ w["lm_head"].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(jax.nn.log_softmax(logits)),
                               np.asarray(jax.nn.log_softmax(want)),
                               atol=SERVE_TOL)
    assert counts["serve.moe.calls"] > 0
    assert 0 < counts["serve.moe.rows"] <= counts["serve.moe.padded_rows"]


@pytest.mark.parametrize("mutation", ["no_qk_norm", "no_renorm"])
def test_reference_mutation_fails(served, monkeypatch, mutation):
    """The check has the power to see the mechanisms: the reference without
    the q/k norm, or without renormalising the top-k gates, is farther
    from the served tokens than ``SERVE_TOL``."""
    c, w, outs, _ = served
    ref = harness.reference(c)
    if mutation == "no_qk_norm":
        monkeypatch.setattr(ref, "qk_norm", lambda x, scale, eps: x)
    else:
        monkeypatch.setattr(ref, "gates", lambda probs, k: jax.lax.top_k(
            probs, k))
    assert _gap(c, w, outs, ref) > SERVE_TOL


# -- the cell through the harness -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct(trace, cell_spread):
    """``qwen3moe-12l.rollout`` at smoke widths through ``harness.main``:
    correct, and every metric declared for the cell is reported but the
    TPU kernels' rooflines; the window holds the expert counters."""
    rc, out, run = smoke.run_cell(CELL, "rollout", trace=trace)
    assert rc == 0
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    bench = harness.benchmark()
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in harness.metrics_for(bench, CELL, kind)}
    assert set(out["metrics"]) == want - TPU_ONLY
    c = run.counters
    assert c["serve.moe.calls"] > 0
    assert 0 < c["serve.moe.rows"] <= c["serve.moe.padded_rows"]
    assert 0 < c["serve.moe.experts"] <= 16 * c["serve.moe.calls"]


def test_limits_file_states_its_origin():
    """The cell's limit lies between its two chip readings, the program's
    widest gap and the float8 control's least, with more room above the
    program's, and those readings are at least 2x apart; the traffic file
    the cell names is there."""
    lim = harness.load_json("limits", f"{CELL}.json")
    lo, hi, limit = (lim["lower"]["logp"], lim["upper"]["logp"],
                     lim["limits"]["logp"])
    assert hi >= 2 * lo
    assert lo < limit < hi and limit / lo > hi / limit
    assert os.path.exists(os.path.join(smoke.BENCH, "traffic",
                                       "rollout.groups8.s256.json"))
