"""Paged decode attention: Pallas kernel vs jnp ref vs dense-gather oracle.

Three implementations, one contract:

  * ``kernels/ref.paged_decode_attention`` (CPU path) must be BITWISE equal
    to ``ops.decode_attention`` over the dense-gathered view — the serving
    engine's bit-compatibility with ``RolloutEngine`` rides on it.
  * the Pallas kernel (interpret mode here) is online-softmax — numerically
    close, and greedy decode lands on identical tokens (subprocess test).
  * the jitted serving step must materialize NO dense (n, S, MB*bs, kv, hd)
    cache view: checked against the optimized HLO and the compiled step's
    temp-buffer footprint as ``max_blocks_per_seq`` grows.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.rollout import RolloutEngine
from repro.data.tokenizer import ByteTokenizer
from repro.kernels import ops, ref
from repro.kernels.paged_attention import (page_span, pages_per_block,
                                           paged_decode_attention as pallas_pda)
from repro.models.model import build_model
from repro.serve.engine import ServingEngine, prefill_bucket
from repro.serve.paged_cache import gather_pool_ref

TOK = ByteTokenizer()


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    m = build_model(cfg)
    params = m.init(cfg, jax.random.PRNGKey(0))
    return cfg, m, params


def _rand_case(seed, s=4, kv=2, g=4, hd=32, bs=4, mb=5, nblk=24):
    """Random pool/tables/pos + the dense-gathered oracle inputs."""
    rng = np.random.RandomState(seed)
    h = kv * g
    nblk = max(nblk, s * mb)
    r = (nblk + 1) * bs
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (s, 1, h, hd), jnp.float32)
    pool_k = jax.random.normal(ks[1], (r, kv, hd), jnp.float32)
    pool_v = jax.random.normal(ks[2], (r, kv, hd), jnp.float32)
    k_new = jax.random.normal(ks[3], (s, kv, hd), jnp.float32)
    v_new = jax.random.normal(ks[4], (s, kv, hd), jnp.float32)
    # each slot owns disjoint random blocks (like a real allocation)
    perm = rng.permutation(nblk)[:s * mb].reshape(s, mb)
    tables = jnp.asarray(perm, jnp.int32)
    # ragged: corner positions (empty slot, full slot) + random interior
    pos = np.array([0, mb * bs - 1] + list(rng.randint(0, mb * bs, s - 2)),
                   np.int32)[:s]
    return q, k_new, v_new, pool_k, pool_v, tables, jnp.asarray(pos), bs


def _oracle(q, k_new, v_new, pool_k, pool_v, tables, pos, bs, window=0):
    """gather_kv + insert-at-pos + dense decode_attention (the old path)."""
    kc = gather_pool_ref(pool_k[None], tables, bs)[0]
    vc = gather_pool_ref(pool_v[None], tables, bs)[0]
    rows = jnp.arange(q.shape[0])
    kc = kc.at[rows, pos].set(k_new)
    vc = vc.at[rows, pos].set(v_new)
    cap = tables.shape[1] * bs
    valid = jnp.arange(cap)[None, :] <= pos[:, None]
    if window > 0:
        valid &= jnp.arange(cap)[None, :] > pos[:, None] - window
    return ops.decode_attention(q, kc, vc, valid)


def _pallas(q, k_new, v_new, pool_k, pool_v, tables, pos, bs, window=0):
    """The Pallas kernel (TPU interpret mode) on one layer's pool, as
    layer 0 of a one-layer stack; returns the oracle's (S, 1, H, d)."""
    out = pallas_pda(q[:, 0], k_new, v_new, pool_k[None], pool_v[None],
                     jnp.int32(0), tables, pos, block_size=bs, window=window,
                     interpret=True)
    return out[:, None]


def test_ref_bitwise_matches_dense_oracle():
    args = _rand_case(0)
    want = np.asarray(jax.jit(_oracle, static_argnums=(7,))(*args))
    got = np.asarray(jax.jit(
        ref.paged_decode_attention,
        static_argnames=("block_size",))(*args[:-1], block_size=args[-1]))
    np.testing.assert_array_equal(want, got)


def test_pallas_interpret_close_to_oracle():
    q, k_new, v_new, pool_k, pool_v, tables, pos, bs = _rand_case(1)
    want = np.asarray(jax.jit(_oracle, static_argnums=(7,))(
        q, k_new, v_new, pool_k, pool_v, tables, pos, bs))
    got = _pallas(q, k_new, v_new, pool_k, pool_v, tables, pos, bs)
    np.testing.assert_allclose(want, np.asarray(got), rtol=2e-5, atol=2e-5)


def test_property_random_tables_ragged_pos():
    """Property sweep: random block tables, ragged pos (incl. empty and full
    slots), varied GQA shapes — ref stays bitwise-exact, Pallas stays close."""
    for seed in range(8):
        kv, g = [(1, 4), (2, 2), (2, 4), (4, 1)][seed % 4]
        case = _rand_case(seed + 10, s=3 + seed % 3, kv=kv, g=g,
                          hd=16, bs=2 + 2 * (seed % 2), mb=3 + seed % 4)
        q, k_new, v_new, pool_k, pool_v, tables, pos, bs = case
        want = np.asarray(jax.jit(_oracle, static_argnums=(7,))(*case))
        got = np.asarray(jax.jit(
            ref.paged_decode_attention,
            static_argnames=("block_size",))(*case[:-1], block_size=bs))
        np.testing.assert_array_equal(want, got, err_msg=f"seed {seed}")
        pk = _pallas(q, k_new, v_new, pool_k, pool_v, tables, pos, bs)
        np.testing.assert_allclose(want, np.asarray(pk),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"seed {seed}")


def test_ref_sliding_window_matches_oracle():
    q, k_new, v_new, pool_k, pool_v, tables, pos, bs = _rand_case(2)
    cap = tables.shape[1] * bs
    w = 6
    valid = jnp.arange(cap)[None, :] <= pos[:, None]
    valid &= jnp.arange(cap)[None, :] > pos[:, None] - w
    kc = gather_pool_ref(pool_k[None], tables, bs)[0]
    vc = gather_pool_ref(pool_v[None], tables, bs)[0]
    rows = jnp.arange(q.shape[0])
    kc = kc.at[rows, pos].set(k_new)
    vc = vc.at[rows, pos].set(v_new)
    want = np.asarray(jax.jit(ops.decode_attention)(q, kc, vc, valid))
    got = np.asarray(jax.jit(
        ref.paged_decode_attention, static_argnames=("block_size", "window"))(
        q, k_new, v_new, pool_k, pool_v, tables, pos, block_size=bs, window=w))
    np.testing.assert_array_equal(want, got)
    pk = _pallas(q, k_new, v_new, pool_k, pool_v, tables, pos, bs, window=w)
    np.testing.assert_allclose(want, np.asarray(pk), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the kernel's page walk (TPU interpret mode: DMAs and semaphores simulated)
# ---------------------------------------------------------------------------

# (kv, g, block_size, table width, layers, layer read, window).  At
# block_size 4 a compute block is 32 pages, so a 40-entry table ends in a
# partial block; windows of 9 and 13 rows skip whole pages.
WALK_CASES = {
    "g1_mha": (4, 1, 4, 6, 1, 0, 0),
    "g4": (2, 4, 4, 5, 1, 0, 0),
    "g7": (2, 7, 4, 6, 1, 0, 0),
    "g8": (1, 8, 8, 4, 1, 0, 0),
    "partial_last_block": (1, 4, 4, 40, 1, 0, 0),
    "window_skips_pages": (2, 4, 4, 12, 1, 0, 9),
    "window_partial_block": (1, 2, 2, 40, 1, 0, 13),
    "layer_2_of_3": (2, 4, 4, 6, 3, 2, 0),
}


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_pallas_page_walk_matches_oracle(case):
    """Property sweep of the Pallas page walk against the dense oracle:
    random disjoint tables and random positions, with the corners forced
    in — ``pos`` 0 on a live table, 0 on an idle slot whose table names
    only the null block, a page boundary, and a full table."""
    kv, g, bs, mb, layers, layer, window = WALK_CASES[case]
    seed = sorted(WALK_CASES).index(case)
    s = 6
    rng = np.random.RandomState(100 + seed)
    q, k_new, v_new, pool_k, pool_v, tables, _, _ = _rand_case(
        100 + seed, s=s, kv=kv, g=g, hd=16, bs=bs, mb=mb)
    null = pool_k.shape[0] // bs - 1
    tables = tables.at[1].set(null)                  # idle slot
    pos = np.array([0, 0, 2 * bs, mb * bs - 1]
                   + list(rng.randint(1, mb * bs, s - 4)), np.int32)
    pos = jnp.asarray(pos)
    want = np.asarray(jax.jit(_oracle, static_argnums=(7, 8))(
        q, k_new, v_new, pool_k, pool_v, tables, pos, bs, window))
    # the layer read sits among others that differ from it
    stack_k = jnp.stack([pool_k + (i - layer) for i in range(layers)])
    stack_v = jnp.stack([pool_v - 2 * (i - layer) for i in range(layers)])
    got = pallas_pda(q[:, 0], k_new, v_new, stack_k, stack_v,
                     jnp.int32(layer), tables, pos, block_size=bs,
                     window=window, interpret=True)
    np.testing.assert_allclose(want, np.asarray(got[:, None]),
                               rtol=2e-5, atol=2e-5)


def test_page_span_counts_live_and_windowed_pages():
    """``page_span`` is what the kernel walks and what the engine counts:
    [0, ceil(pos / bs)) without a window; with one, from the first page
    holding a row the query still sees."""
    pos = np.array([0, 1, 16, 17, 100])
    first, end = page_span(pos, 16)
    np.testing.assert_array_equal(first, [0, 0, 0, 0, 0])
    np.testing.assert_array_equal(end, [0, 1, 1, 2, 7])
    first, end = page_span(pos, 16, window=20)
    # rows > pos - 20 are seen: pos 100 sees rows 81.. -> pages 5, 6
    np.testing.assert_array_equal(first, [0, 0, 0, 0, 5])
    np.testing.assert_array_equal(end - first, [0, 1, 1, 2, 2])
    assert pages_per_block(16, 64) == 8 and pages_per_block(4, 5) == 5
    assert pages_per_block(256, 8) == 1


# ---------------------------------------------------------------------------
# engine integration: preemption refill + budgeted resume on the paged path
# ---------------------------------------------------------------------------

def test_preemption_refill_then_budget_resume_matches_rollout(dense_setup):
    """One run exercising BOTH re-prefill paths over the paged decode step:
    a starved pool forces recompute preemption mid-drain, then budget
    suspension + mid-sequence resubmission finishes the requests — greedy
    tokens must equal the synchronized engine's."""
    cfg, _, params = dense_setup
    b, pl, mn = 4, 8, 12
    prompts = np.random.RandomState(21).randint(0, 250, (b, pl)).astype(
        np.int32)
    sync = RolloutEngine(cfg, max_new=mn, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                         greedy=True)
    ref_out = sync.generate(params, prompts, jax.random.PRNGKey(5))
    cont = ServingEngine(cfg, max_new=mn, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                         greedy=True, max_slots=3, block_size=4,
                         num_blocks=11, max_seq_len=pl + mn)
    pending = {cont.submit(prompts[i], budget=6): i for i in range(b)}
    done, rounds = {}, 0
    preempts = 0
    while pending:
        outs, resum = cont.run_to_budget(params)
        for o in outs:
            done[pending.pop(o.rid)] = o
            preempts += o.preemptions
        nxt = {}
        for req in resum:
            i = pending.pop(req.rid)
            preempts += req.preemptions   # resubmission starts a fresh count
            nxt[cont.submit(req.prompt, generated=req.generated,
                            max_new=mn - len(req.generated), budget=6)] = i
        pending = nxt
        rounds += 1
        assert rounds <= 5
    assert preempts > 0, "pool was never starved — shrink num_blocks"
    assert rounds > 1, "budget suspension never fired"
    for i, o in done.items():
        n = len(o.gen)
        assert n == ref_out.lengths[i]
        np.testing.assert_array_equal(np.asarray(o.gen),
                                      ref_out.tokens[i, pl:pl + n])
    cont.sched.check_invariants()


# ---------------------------------------------------------------------------
# footprint: the jitted step must not materialize the dense cache view
# ---------------------------------------------------------------------------

def _lowered_step(cfg, params, *, block_size, max_seq):
    eng = ServingEngine(cfg, max_new=4, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                        greedy=True, max_slots=4, block_size=block_size,
                        max_seq_len=max_seq)
    s = eng.max_slots
    tok = jnp.zeros((s, 1), jnp.int32)
    pos = jnp.zeros((s,), jnp.int32)
    done = jnp.ones((s,), bool)
    compiled = eng._step.lower(
        params, eng.cache.pool_k, eng.cache.pool_v,
        jnp.asarray(eng.sched.tables), tok, pos, done).compile()
    return eng, compiled


def test_step_materializes_no_dense_cache_view(dense_setup):
    """The acceptance property: no (n, S, MB*bs, kv, hd) buffer exists in
    the compiled step (gather_kv is gone from the decode path), and the
    step's temp footprint stays ~flat when max_blocks_per_seq grows 4x —
    the dense gather alone would grow it by 2*n*S*cap*kv*hd*4 bytes."""
    cfg, _, params = dense_setup
    bs = 8
    eng1, c1 = _lowered_step(cfg, params, block_size=bs, max_seq=4 * bs)
    eng2, c2 = _lowered_step(cfg, params, block_size=bs, max_seq=16 * bs)
    n, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    for eng, comp in ((eng1, c1), (eng2, c2)):
        cap = eng.cache.max_blocks_per_seq * bs
        dense_shape = f"f32[{n},{eng.max_slots},{cap},{kv},{hd}]"
        assert dense_shape not in comp.as_text(), \
            f"dense cache view {dense_shape} materialized in the jitted step"
    # temp growth far below one dense gather of the larger engine
    cap2 = eng2.cache.max_blocks_per_seq * bs
    dense_bytes = 2 * n * eng2.max_slots * cap2 * kv * hd * 4
    t1 = c1.memory_analysis().temp_size_in_bytes
    t2 = c2.memory_analysis().temp_size_in_bytes
    assert t2 - t1 < dense_bytes // 2, (t1, t2, dense_bytes)


# ---------------------------------------------------------------------------
# bucketed admission prefill
# ---------------------------------------------------------------------------

def test_prefill_bucket_shape():
    assert [prefill_bucket(n) for n in (1, 8, 9, 16, 17, 33)] == \
        [8, 8, 16, 16, 32, 64]


def test_bucketed_admission_bounds_compiles_and_matches_sync(dense_setup):
    """Varied-length online submits must compile one prefill per power-of-2
    BUCKET (not per length), and bucket padding must not change greedy
    outputs vs the synchronized engine fed the same (unpadded) prompts."""
    cfg, _, params = dense_setup
    lengths = [3, 5, 6, 7, 9, 11, 12, 13]
    mn = 6
    cont = ServingEngine(cfg, max_new=mn, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                         greedy=True, max_slots=2, block_size=4,
                         max_seq_len=max(lengths) + mn)
    sync = RolloutEngine(cfg, max_new=mn, eos_id=TOK.eos_id, pad_id=TOK.pad_id,
                         greedy=True)
    rng = np.random.RandomState(3)
    rid2prompt = {}
    for ln in lengths:
        prompt = rng.randint(0, 250, (ln,)).astype(np.int32)
        rid2prompt[cont.submit(prompt)] = prompt
    outs = cont.drain(params)
    assert sorted(o.rid for o in outs) == sorted(rid2prompt)
    buckets = {prefill_bucket(n) for n in lengths}
    n_prefill = cont._prefill._cache_size()
    assert n_prefill <= len(buckets), \
        f"{n_prefill} prefill compiles for buckets {sorted(buckets)}"
    # greedy outputs unchanged by the bucket padding (subset: one prompt per
    # bucket — each sync comparison compiles its own prefill/decode shapes)
    checked = {}
    for o in outs:
        checked.setdefault(prefill_bucket(len(rid2prompt[o.rid])), o)
    for o in checked.values():
        p = rid2prompt[o.rid]
        want = sync.generate(params, p[None], jax.random.PRNGKey(5))
        n = int(want.lengths[0])
        assert len(o.gen) == n
        np.testing.assert_array_equal(np.asarray(o.gen),
                                      want.tokens[0, len(p):len(p) + n])


# ---------------------------------------------------------------------------
# Pallas path end-to-end (subprocess — REPRO_PALLAS read at import)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os, sys, json
import jax, numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
sys.path.insert(0, "src")
from repro.configs import get_smoke_config
from repro.core.rollout import RolloutEngine
from repro.data.tokenizer import ByteTokenizer
from repro.models.model import build_model
from repro.serve.engine import ServingEngine

tok = ByteTokenizer()
cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
m = build_model(cfg)
params = m.init(cfg, jax.random.PRNGKey(0))
prompts = np.random.RandomState(0).randint(0, 250, (2, 8)).astype(np.int32)
sync = RolloutEngine(cfg, max_new=6, eos_id=tok.eos_id, pad_id=tok.pad_id,
                     greedy=True)
cont = ServingEngine(cfg, max_new=6, eos_id=tok.eos_id, pad_id=tok.pad_id,
                     greedy=True, max_slots=2, block_size=4)
a = sync.generate(params, prompts, jax.random.PRNGKey(5))
b = cont.generate(params, prompts, jax.random.PRNGKey(5))
print(json.dumps({"match": bool(np.array_equal(a.tokens, b.tokens)),
                  "lengths": a.lengths.tolist()}))
"""


def test_pallas_engine_greedy_bit_identity_subprocess():
    """Under REPRO_PALLAS=interpret the serving step runs the Pallas paged
    kernel (online softmax — logits differ in ulps from the dense path);
    greedy TOKEN sequences must still be identical to RolloutEngine."""
    import os
    env = dict(os.environ, REPRO_PALLAS="interpret")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, cwd="/root/repo",
                         timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["match"], "pallas paged decode diverged from sync greedy"
