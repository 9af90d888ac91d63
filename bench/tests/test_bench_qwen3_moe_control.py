"""The float8 control (the plain reference in float8 e4m3, one step below
the configuration's bfloat16, in the program's place) must fail
``qwen3moe-12l.rollout``'s limit, and read farther from the reference
than the program on the same tokens.

Smoke widths, with the logits' spread of the cell's width.  The engine
serves a fixed set of requests to their full length, so the number of
tokens compared does not depend on how fast the machine runs, as it does
in a timed window; they are scored by the cell's own scorer and check.
The program itself is not held to the limit here: on these 256 tokens at
two layers it reads 0.107, where the cell on the chip, at twelve layers
and full widths, read at most 0.033 (PERF.md section 2)."""
import jax
import numpy as np

import check
import harness
import program
import smoke
import weights
from test_bench_qwen3_moe import CELL, CONFIG, SEED, cell_spread  # noqa: F401

REQUESTS, MAX_NEW = 8, 32


def test_control_is_not_correct(cell_spread):  # noqa: F811
    from repro.serve.engine import ServingEngine

    c = {**harness.load_json("configs", f"{CONFIG}.json"),
         **smoke.SMOKE_CONFIG}
    w = jax.jit(lambda k: weights.init(c, k))(weights.key(SEED))
    lo, hi = smoke.SMOKE_TRAFFIC["rollout"]["prompt_len"]
    rng = np.random.default_rng(SEED)
    eng = ServingEngine(program.model_config(c), max_new=MAX_NEW, eos_id=-1,
                        pad_id=c["eos_token_id"], temperature=1.0,
                        max_slots=4, block_size=16,
                        max_seq_len=hi + MAX_NEW, seed=SEED)
    for _ in range(REQUESTS):
        eng.submit(rng.integers(0, c["vocab_size"], rng.integers(lo, hi + 1),
                                dtype=np.int32))
    outs = eng.drain(w)
    assert sum(len(o.gen) for o in outs) == REQUESTS * MAX_NEW

    scorer = harness.generator("rollout")._scorer
    ref = scorer(c, w, hi + MAX_NEW, None)
    ctl = scorer(c, w, hi + MAX_NEW, "fp8")
    lps = [ref(o) for o in outs]
    limits = harness.load_json("limits", f"{CELL}.json")["limits"]
    gap = check.widest_gap([(o.gen_logp, lp) for o, lp in zip(outs, lps)])
    control = check.widest_gap([(ctl(o), lp) for o, lp in zip(outs, lps)])
    held = check.held({"logp": control}, limits)
    assert not all(h["ok"] for h in held), held
    assert gap < control
