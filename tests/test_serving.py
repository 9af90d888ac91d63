"""Continuous-batching serving subsystem (repro.serve)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.rollout import RolloutEngine
from repro.data.tokenizer import ByteTokenizer
from repro.models.model import build_model
from repro.serve.engine import ServingEngine
from repro.serve.paged_cache import (PagedKVCache, blocks_for,
                                     gather_pool_pallas, gather_pool_ref)
from repro.serve.scheduler import Request, Scheduler

TOK = ByteTokenizer()


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    m = build_model(cfg)
    params = m.init(cfg, jax.random.PRNGKey(0))
    return cfg, m, params


def _prompts(b, pl, seed=0):
    return np.random.RandomState(seed).randint(0, 250, (b, pl)).astype(np.int32)


def _engines(cfg, max_new, **kw):
    sync = RolloutEngine(cfg, max_new=max_new, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True)
    cont = ServingEngine(cfg, max_new=max_new, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True, **kw)
    return sync, cont


# ---------------------------------------------------------------------------
# paged cache
# ---------------------------------------------------------------------------

def test_pallas_gather_matches_ref(rng):
    pool = jax.random.normal(rng, (2, 40, 2, 16), jnp.float32)  # 4 blks + null
    tables = jnp.asarray(np.array([[2, 0, 4], [1, 3, 4]], np.int32))
    a = gather_pool_ref(pool, tables, 8)
    b = gather_pool_pallas(pool, tables, 8, interpret=True)
    assert a.shape == (2, 2, 24, 2, 16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_view_matches_dense_prefill(dense_setup):
    """Prefill KV scattered into blocks, then gathered back, must reproduce
    the dense cache row content bit-for-bit."""
    cfg, m, params = dense_setup
    b, pl, bs = 3, 8, 4
    prompts = _prompts(b, pl)
    cache = m.init_cache(cfg, b, pl)
    _, cache = m.prefill(params, cfg, {"tokens": jnp.asarray(prompts)}, cache)

    pc = PagedKVCache(cfg, num_blocks=12, block_size=bs, max_blocks_per_seq=4)
    tables = np.full((b, 4), pc.null_block, np.int32)
    j = np.arange(pl)
    for i in range(b):
        blocks = [pc.alloc() for _ in range(blocks_for(pl, bs))]
        tables[i, :len(blocks)] = blocks
        flat = jnp.asarray(tables[i][j // bs] * bs + j % bs)
        pc.pool_k = pc.pool_k.at[:, flat].set(cache["k"][:, i])
        pc.pool_v = pc.pool_v.at[:, flat].set(cache["v"][:, i])
    view = pc.dense_view(tables)
    np.testing.assert_array_equal(np.asarray(view["k"][:, :, :pl]),
                                  np.asarray(cache["k"]))
    np.testing.assert_array_equal(np.asarray(view["v"][:, :, :pl]),
                                  np.asarray(cache["v"]))
    # decode over the paged view == decode over the dense cache
    tok = _prompts(b, 1, seed=9)
    padded = {
        "k": jnp.pad(cache["k"], ((0, 0), (0, 0), (0, 8), (0, 0), (0, 0))),
        "v": jnp.pad(cache["v"], ((0, 0), (0, 0), (0, 8), (0, 0), (0, 0))),
    }
    pos = jnp.full((b,), pl, jnp.int32)
    l_dense, _ = m.decode(params, cfg, padded, jnp.asarray(tok), pos)
    l_paged, _ = m.decode(params, cfg, view, jnp.asarray(tok), pos)
    np.testing.assert_array_equal(np.asarray(l_dense), np.asarray(l_paged))


def test_vector_pos_decode_matches_scalar(dense_setup):
    cfg, m, params = dense_setup
    b, pl = 3, 6
    cache = m.init_cache(cfg, b, 12)
    _, cache = m.prefill(params, cfg,
                         {"tokens": jnp.asarray(_prompts(b, pl))}, cache)
    tok = jnp.asarray(_prompts(b, 1, seed=2))
    l1, c1 = m.decode(params, cfg, cache, tok, jnp.int32(pl))
    l2, c2 = m.decode(params, cfg, cache, tok, jnp.full((b,), pl, jnp.int32))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(c1["k"]), np.asarray(c2["k"]))


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _sched(cfg, num_blocks=8, bs=4, mb=4, slots=2):
    cache = PagedKVCache(cfg, num_blocks=num_blocks, block_size=bs,
                         max_blocks_per_seq=mb)
    return Scheduler(cache, max_slots=slots), cache


def test_scheduler_admission_refill_eviction(dense_setup):
    cfg, _, _ = dense_setup
    sched, cache = _sched(cfg)
    reqs = [Request(rid=i, prompt=np.zeros((5,), np.int32), max_new=3)
            for i in range(4)]
    for r in reqs:
        sched.submit(r)
    admitted = sched.admit()
    # FIFO: rids 0, 1 fill both slots; each holds ceil(6/4)=2 blocks
    assert [r.rid for r in admitted] == [0, 1]
    assert cache.num_free == 4
    sched.check_invariants()
    # nothing admittable: no free slot
    assert sched.admit() == []
    # eviction frees blocks + slot; refill picks the FIFO head
    done = sched.finish(admitted[0].slot)
    assert done.rid == 0 and cache.num_free == 6
    sched.check_invariants()
    nxt = sched.admit()
    assert [r.rid for r in nxt] == [2]
    sched.check_invariants()


def test_scheduler_growth_and_preemption(dense_setup):
    cfg, _, _ = dense_setup
    sched, cache = _sched(cfg, num_blocks=5, bs=4, mb=4, slots=2)
    a = Request(rid=0, prompt=np.zeros((7,), np.int32), max_new=8)
    b = Request(rid=1, prompt=np.zeros((7,), np.int32), max_new=8)
    sched.submit(a)
    sched.submit(b)
    assert len(sched.admit()) == 2        # 2 blocks each, 1 left
    for r in (a, b):
        r.cache_len = 7
    assert sched.ensure_capacity() == []  # 8th token still fits block 2
    sched.check_invariants()
    a.cache_len = b.cache_len = 8         # both need a 3rd block; 1 free
    pre = sched.ensure_capacity()
    # oldest (rid 0) grabs the last block; youngest (rid 1) is preempted
    assert [r.rid for r in pre] == [1]
    assert b.slot == -1 and b.cache_len == 0 and b.preemptions == 1
    assert sched.waiting[0] is b          # re-queued at the FRONT
    sched.check_invariants()
    # rid 0 finishing frees enough for rid 1 to come back
    sched.finish(a.slot)
    assert [r.rid for r in sched.admit()] == [1]
    sched.check_invariants()


def test_scheduler_rejects_unschedulable(dense_setup):
    cfg, _, _ = dense_setup
    sched, _ = _sched(cfg, num_blocks=4, bs=4, mb=4, slots=1)
    with pytest.raises(ValueError):       # needs 5 blocks > max_blocks_per_seq
        sched.submit(Request(rid=0, prompt=np.zeros((10,), np.int32),
                             max_new=8))


# ---------------------------------------------------------------------------
# engine vs RolloutEngine
# ---------------------------------------------------------------------------

def test_generate_bitcompat_with_rollout(dense_setup):
    """S == B and block-aligned capacity: every jitted shape matches the
    synchronized engine, so greedy outputs are BIT-identical."""
    cfg, _, params = dense_setup
    b, pl, mn = 4, 8, 12
    prompts = _prompts(b, pl)
    sync, cont = _engines(cfg, mn, max_slots=b, block_size=4)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    r2 = cont.generate(params, prompts, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    np.testing.assert_array_equal(r1.response_mask, r2.response_mask)
    np.testing.assert_array_equal(r1.lengths, r2.lengths)
    np.testing.assert_array_equal(r1.gen_logp, r2.gen_logp)


def test_generate_refill_matches_rollout(dense_setup):
    """More requests than slots: waves of admission + refill must not change
    greedy outputs."""
    cfg, _, params = dense_setup
    b, pl, mn = 6, 8, 10
    prompts = _prompts(b, pl, seed=3)
    sync, cont = _engines(cfg, mn, max_slots=2, block_size=4)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    r2 = cont.generate(params, prompts, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    np.testing.assert_array_equal(r1.lengths, r2.lengths)


def test_generate_with_preemption_matches_rollout(dense_setup):
    """A starved block pool forces recompute-preemption mid-generation; the
    re-prefilled continuation must land on the same greedy tokens."""
    cfg, _, params = dense_setup
    b, pl, mn = 4, 8, 12
    prompts = _prompts(b, pl, seed=4)
    sync, cont = _engines(cfg, mn, max_slots=3, block_size=4,
                          num_blocks=11, max_seq_len=pl + mn)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    r2 = cont.generate(params, prompts, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r1.tokens, r2.tokens)


def test_moe_serving_matches_rollout():
    cfg = get_smoke_config("mixtral-8x7b").replace(dtype="float32",
                                                   remat=False)
    m = build_model(cfg)
    params = m.init(cfg, jax.random.PRNGKey(1))
    prompts = _prompts(3, 6, seed=6)
    sync, cont = _engines(cfg, 8, max_slots=3, block_size=2)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    r2 = cont.generate(params, prompts, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r1.tokens, r2.tokens)


def test_unsupported_arch_raises():
    cfg = get_smoke_config("mamba2-1.3b")
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, max_new=4, eos_id=TOK.eos_id, pad_id=TOK.pad_id)


# ---------------------------------------------------------------------------
# budgeted / mid-sequence requests (partial-rollout backend)
# ---------------------------------------------------------------------------

def test_run_to_budget_splits_finished_and_resumable(dense_setup):
    """Requests that exhaust their per-run budget come back resumable with
    their slots and blocks freed; EOS/cap finishes are reported normally."""
    cfg, _, params = dense_setup
    pl = 8
    prompts = _prompts(3, pl, seed=12)
    _, cont = _engines(cfg, 16, max_slots=3, block_size=4)
    r_short = cont.submit(prompts[0], max_new=3, budget=8)   # cap < budget
    r_a = cont.submit(prompts[1], max_new=16, budget=5)
    r_b = cont.submit(prompts[2], max_new=16, budget=5)
    outs, resum = cont.run_to_budget(params)
    assert [o.rid for o in outs] == [r_short]
    assert len(outs[0].gen) == 3
    assert sorted(r.rid for r in resum) == sorted([r_a, r_b])
    for req in resum:
        assert req.num_new == 5 and req.slot == -1
    assert cont.sched.idle and cont.cache.num_free == cont.cache.num_blocks
    cont.sched.check_invariants()


def test_mid_sequence_resume_matches_uninterrupted(dense_setup):
    """Greedy decode chopped into budget-4 installments (suspend, resubmit
    mid-sequence with the generated seed) lands on the same tokens as one
    uninterrupted run — resume is a re-prefill, the same path a recompute
    preemption takes."""
    cfg, _, params = dense_setup
    b, pl, mn = 3, 8, 12
    prompts = _prompts(b, pl, seed=7)
    sync, cont = _engines(cfg, mn, max_slots=b, block_size=4)
    ref = sync.generate(params, prompts, jax.random.PRNGKey(5))
    pending = {cont.submit(prompts[i], max_new=mn, budget=4): i
               for i in range(b)}
    done, rounds = {}, 0
    while pending:
        outs, resum = cont.run_to_budget(params)
        for o in outs:
            done[pending.pop(o.rid)] = o
        nxt = {}
        for req in resum:
            i = pending.pop(req.rid)
            nxt[cont.submit(req.prompt, generated=req.generated,
                            max_new=mn - len(req.generated), budget=4)] = i
        pending = nxt
        rounds += 1
        assert rounds <= 4
    assert sorted(done) == list(range(b))
    for i, o in done.items():
        n = len(o.gen)
        assert n == ref.lengths[i]
        np.testing.assert_array_equal(np.asarray(o.gen),
                                      ref.tokens[i, pl:pl + n])


def test_on_finish_never_fires_for_suspensions(dense_setup):
    cfg, _, params = dense_setup
    prompts = _prompts(2, 8, seed=13)
    _, cont = _engines(cfg, 16, max_slots=2, block_size=4)
    cont.submit(prompts[0], max_new=2, budget=6)
    cont.submit(prompts[1], max_new=16, budget=6)
    seen = []
    outs, resum = cont.run_to_budget(params, on_finish=seen.append)
    assert [o.rid for o in seen] == [o.rid for o in outs] == [0]
    assert [r.rid for r in resum] == [1]
    assert cont._on_finish is None       # restored after the run


def test_submit_rejects_bad_budget(dense_setup):
    cfg, _, _ = dense_setup
    _, cont = _engines(cfg, 8, max_slots=2, block_size=4)
    with pytest.raises(ValueError, match="budget"):
        cont.submit(np.zeros((4,), np.int32), budget=0)


def test_drain_refuses_budgeted_requests(dense_setup):
    """drain() returns finished outputs only — letting it run budgeted
    requests would strand their suspensions, so it refuses up front."""
    cfg, _, params = dense_setup
    _, cont = _engines(cfg, 8, max_slots=2, block_size=4)
    cont.submit(_prompts(1, 4, seed=14)[0], budget=2)
    with pytest.raises(RuntimeError, match="run_to_budget"):
        cont.drain(params)


# ---------------------------------------------------------------------------
# scheduler pressure: tiny pool, preemption firing, invariants every step
# ---------------------------------------------------------------------------

def test_scheduler_pressure_invariants_and_outputs(dense_setup):
    """Drive submit/step against a deliberately starved block pool: the
    recompute preemption must fire, Scheduler.check_invariants() must hold
    after EVERY step, and every request must eventually finish with the
    synchronized engine's greedy outputs."""
    cfg, _, params = dense_setup
    b, pl, mn = 6, 8, 12
    prompts = _prompts(b, pl, seed=11)
    sync, cont = _engines(cfg, mn, max_slots=4, block_size=4,
                          num_blocks=13, max_seq_len=pl + mn)
    ref = sync.generate(params, prompts, jax.random.PRNGKey(5))
    for i in range(b):
        cont.submit(prompts[i])
    outs, steps = [], 0
    while not cont.sched.idle:
        outs.extend(cont.step(params))
        cont.sched.check_invariants()
        steps += 1
        assert steps < 1000, "scheduler stopped making progress"
    assert sorted(o.rid for o in outs) == list(range(b))
    assert sum(o.preemptions for o in outs) > 0, "pool was never starved"
    for o in outs:
        n = len(o.gen)
        assert n == ref.lengths[o.rid]
        np.testing.assert_array_equal(np.asarray(o.gen),
                                      ref.tokens[o.rid, pl:pl + n])
    assert cont.cache.num_free == cont.cache.num_blocks


# ---------------------------------------------------------------------------
# online API + streaming
# ---------------------------------------------------------------------------

def test_online_budgets_and_latency(dense_setup):
    cfg, _, params = dense_setup
    _, cont = _engines(cfg, 16, max_slots=2, block_size=4, max_seq_len=24)
    budgets = [2, 7, 3, 5]
    for i, bud in enumerate(budgets):
        cont.submit(_prompts(1, 6, seed=i)[0], max_new=bud)
    outs = cont.drain(params)
    assert sorted(o.rid for o in outs) == [0, 1, 2, 3]
    by_rid = {o.rid: o for o in outs}
    for i, bud in enumerate(budgets):
        assert len(by_rid[i].gen) <= bud
        assert by_rid[i].latency_s > 0 and by_rid[i].ttft_s >= 0
    assert cont.sched.idle


@pytest.mark.parametrize("window", [0, 5])
def test_decode_kv_pages_counts_the_kernels_walk(dense_setup, window):
    """``serve.decode.kv_pages`` adds, each decode step, the table entries
    the paged decode kernel walks for one layer: ceil(pos / bs) per
    decoding slot, less the pages a sliding window has left behind."""
    cfg, _, params = dense_setup
    cfg = cfg.replace(sliding_window=window)
    bs, pl = 4, 6
    _, cont = _engines(cfg, 9, max_slots=2, block_size=bs, max_seq_len=16)
    for i, mn in enumerate([9, 3, 6]):
        cont.submit(_prompts(1, pl, seed=i)[0], max_new=mn)
    outs = cont.drain(params)
    want = 0
    for o in outs:             # decode step j of a request runs at pos pl + j
        for pos in range(pl, pl + len(o.gen) - 1):
            first = max(pos - window + 1, 0) // bs if window else 0
            want += -(-pos // bs) - first
    assert want > 0
    assert cont.stats()["decode_kv_pages"] == want
    assert cont.metrics.value("serve.decode.kv_pages") == want


def test_on_finish_streams_each_sample(dense_setup):
    """generate() must deliver every finished row the moment it completes,
    in dock-ready (cap-width) format matching the final RolloutResult."""
    cfg, _, params = dense_setup
    b, pl, mn = 4, 8, 6
    prompts = _prompts(b, pl, seed=8)
    seen = {}

    def on_finish(i, row, mask, n):
        seen[i] = (row.copy(), mask.copy(), n)

    _, cont = _engines(cfg, mn, max_slots=2, block_size=2)
    res = cont.generate(params, prompts, jax.random.PRNGKey(5),
                        on_finish=on_finish)
    assert sorted(seen) == list(range(b))
    for i in range(b):
        np.testing.assert_array_equal(seen[i][0], res.tokens[i])
        np.testing.assert_array_equal(seen[i][1], res.response_mask[i])
        assert seen[i][2] == res.lengths[i]


def test_trainer_serving_streams_into_dock():
    from repro.configs.base import RLConfig
    from repro.core.trainer import GRPOTrainer
    from repro.data.prompts import PromptDataset, pattern_task

    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    rl = RLConfig(num_generations=2, max_prompt_len=12, max_response_len=8,
                  rollout_engine="serving", serve_max_slots=2,
                  serve_block_size=4)
    ds = PromptDataset(pattern_task(), max_prompt_len=rl.max_prompt_len,
                       seed=0)
    tr = GRPOTrainer(cfg, rl, ds, num_nodes=2, seed=0)
    stats = tr.iteration(2)
    for v in (stats.loss, stats.kl, stats.reward_mean):
        assert np.isfinite(v)
    assert isinstance(tr.actor.engine, ServingEngine)


# ---------------------------------------------------------------------------
# transfer dock error message (satellite)
# ---------------------------------------------------------------------------

def test_transfer_dock_get_names_missing_field():
    from repro.core.transfer_dock import DispatchLedger, TransferDock

    dock = TransferDock(2, {"reward": 0}, DispatchLedger())
    dock.put("tokens", [0], np.zeros((1, 4), np.float32), src_node=0)
    with pytest.raises(KeyError) as ei:
        dock.get("reward", "advantages", [0], dst_node=0)
    msg = str(ei.value)
    assert "advantages" in msg and "sample 0" in msg and "reward" in msg