"""Prefix-cache block sharing + chunked prefill (repro.serve).

The contracts under test:

  * allocator — blocks are ref-counted; freeing only decrements; a freed
    block keeps its content + index entry until ``alloc()`` reclaims it
    (least-recently-freed first), at which point the entry dies;
  * sharing — N requests with the same prompt head prefill it ONCE
    (asserted via the engine's admitted-prefill token counter), including
    members admitted in the same wave, and partial-rollout resume re-matches
    its own suspended blocks;
  * bit-identity — greedy outputs (tokens AND gen_logp) are bitwise
    invariant to prefix sharing and to any prefill chunk size, and
    ``generate()`` keeps its bitwise contract with ``RolloutEngine``;
  * safety — a params change flushes the index (stale-weights KV is never
    matched), and scheduler/cache invariants hold under a randomized
    admit/evict/resume sweep.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.rollout import RolloutEngine
from repro.data.tokenizer import ByteTokenizer
from repro.models.model import build_model
from repro.serve.engine import ServingEngine
from repro.serve.paged_cache import PagedKVCache, prefix_key
from repro.serve.scheduler import OutOfBlocksError

TOK = ByteTokenizer()


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    m = build_model(cfg)
    params = m.init(cfg, jax.random.PRNGKey(0))
    return cfg, m, params


def _prompts(b, pl, seed=0):
    return np.random.RandomState(seed).randint(0, 250, (b, pl)).astype(np.int32)


def _engine(cfg, max_new, **kw):
    return ServingEngine(cfg, max_new=max_new, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True, **kw)


# ---------------------------------------------------------------------------
# allocator: refcounts, revival, eviction ordering
# ---------------------------------------------------------------------------

def test_refcount_share_free_revive_evict(dense_setup):
    cfg, _, _ = dense_setup
    pc = PagedKVCache(cfg, num_blocks=3, block_size=4, max_blocks_per_seq=3)
    toks = np.arange(8, dtype=np.int32)
    key = prefix_key(b"", toks[:4])
    # chained keys identify the WHOLE prefix: same block tokens under a
    # different parent produce a different key
    assert key != prefix_key(prefix_key(b"", toks[4:]), toks[:4])
    a = pc.alloc()
    pc.register(key, a)
    assert pc.lookup(key) == a
    pc.share(a)
    assert pc.refcount(a) == 2
    pc.free([a])                       # one ref down: still resident
    assert pc.refcount(a) == 1 and pc.num_free == 2
    assert pc.lookup(key) == a
    pc.free([a])                       # now reclaimable, STILL indexed
    assert pc.refcount(a) == 0 and pc.num_free == 3
    assert pc.lookup(key) == a
    pc.share(a)                        # revival out of the free structure
    assert pc.refcount(a) == 1 and pc.num_free == 2
    pc.free([a])
    # eviction order is least-recently-freed first: the two never-used
    # blocks go before the freshly freed cached one
    b1, b2 = pc.alloc(), pc.alloc()
    assert a not in (b1, b2)
    assert pc.lookup(key) == a      # content still intact
    c = pc.alloc()                     # reclaims a -> index entry dies
    assert c == a
    assert pc.lookup(key) is None
    with pytest.raises(OutOfBlocksError):
        pc.alloc()
    pc.flush_index()
    assert pc._index == {} and pc._block_key == {}


def test_eviction_order_exact_under_revive_churn(dense_setup):
    """A freed block that gets revived and freed again must be evicted at
    its NEW position (most recently freed), not at its stale first-free
    deque slot — the epoch stamp invalidates the old entry."""
    cfg, _, _ = dense_setup
    pc = PagedKVCache(cfg, num_blocks=2, block_size=4, max_blocks_per_seq=2)
    b0, b1 = pc.alloc(), pc.alloc()
    pc.free([b0])                      # t1: b0 freed first
    pc.share(b0)                       # revived (stale deque entry remains)
    pc.free([b1])                      # t2
    pc.free([b0])                      # t3: b0 now MOST recently freed
    assert pc.alloc() == b1, "evicted the hotter block first"
    assert pc.alloc() == b0


def test_double_free_asserts(dense_setup):
    cfg, _, _ = dense_setup
    pc = PagedKVCache(cfg, num_blocks=2, block_size=4, max_blocks_per_seq=2)
    b = pc.alloc()
    pc.free([b])
    with pytest.raises(AssertionError):
        pc.free([b])


# ---------------------------------------------------------------------------
# group sharing: N samples per prompt prefill the head once
# ---------------------------------------------------------------------------

def test_group_prefills_shared_head_once(dense_setup):
    """8 requests for one prompt: the block-aligned head is prefilled by the
    first member only; every other member prefills just the divergent tail
    (the final partial block), whether admitted in the same wave or later."""
    cfg, _, params = dense_setup
    pl, mn, bs, n = 19, 8, 8, 8
    prompt = _prompts(1, pl, seed=1)[0]
    cont = _engine(cfg, mn, max_slots=4, block_size=bs, max_seq_len=pl + mn)
    for _ in range(n):
        cont.submit(prompt)
    outs = cont.drain(params)
    cont.sched.check_invariants()
    head = (pl - 1) // bs * bs                       # 16 shareable rows
    tail = pl - head                                 # 3-token tail each
    assert cont.prefill_tokens == pl + (n - 1) * tail
    assert cont.shared_prefill_tokens == (n - 1) * head
    # every member decodes the identical greedy stream
    gens = [np.asarray(o.gen) for o in sorted(outs, key=lambda o: o.rid)]
    lps = [o.gen_logp for o in sorted(outs, key=lambda o: o.rid)]
    for g, lp in zip(gens[1:], lps[1:]):
        np.testing.assert_array_equal(g, gens[0])
        np.testing.assert_array_equal(lp, lps[0])
    assert cont.cache.num_free == cont.cache.num_blocks


def test_block_aligned_prompt_keeps_one_tail_token(dense_setup):
    """A prompt that is an exact block multiple may not be matched whole:
    at least one token stays in the tail so admission has last-token logits
    to sample the first response token from."""
    cfg, _, params = dense_setup
    pl, mn, bs = 16, 6, 8
    prompt = _prompts(1, pl, seed=2)[0]
    cont = _engine(cfg, mn, max_slots=2, block_size=bs, max_seq_len=pl + mn)
    cont.submit(prompt)
    cont.submit(prompt)
    cont.drain(params)
    cont.sched.check_invariants()
    # member 2 re-prefills the whole LAST block (8 tokens), shares the first
    assert cont.prefill_tokens == pl + bs
    assert cont.shared_prefill_tokens == pl - bs


# ---------------------------------------------------------------------------
# bit-identity across sharing / chunking configurations
# ---------------------------------------------------------------------------

def test_sharing_and_chunking_bitwise_invariant(dense_setup):
    """Greedy gen AND gen_logp are bitwise identical across: no prefix
    cache, prefix cache, chunked prefill, and both combined — for a mixed
    workload of duplicate and distinct prompts."""
    cfg, _, params = dense_setup
    pl, mn, bs = 19, 10, 8
    ps = _prompts(2, pl, seed=3)
    subs = [ps[0], ps[0], ps[1], ps[0], ps[1]]

    def run(**kw):
        e = _engine(cfg, mn, max_slots=3, block_size=bs,
                    max_seq_len=pl + mn, **kw)
        for p in subs:
            e.submit(p)
        outs = {o.rid: o for o in e.drain(params)}
        e.sched.check_invariants()
        return e, outs

    base_e, base = run(prefix_cache=False)
    assert base_e.shared_prefill_tokens == 0
    for kw in (dict(prefix_cache=True),
               dict(prefix_cache=False, prefill_chunk=4),
               dict(prefix_cache=True, prefill_chunk=4),
               dict(prefix_cache=True, prefill_chunk=1)):
        e, outs = run(**kw)
        for rid in base:
            np.testing.assert_array_equal(np.asarray(base[rid].gen),
                                          np.asarray(outs[rid].gen))
            np.testing.assert_array_equal(base[rid].gen_logp,
                                          outs[rid].gen_logp)
        if kw.get("prefix_cache"):
            # 3 duplicate admissions x 16-row head — same-wave members
            # included (the rematch-before-first-chunk upgrade)
            assert e.shared_prefill_tokens == 3 * 16
        if kw.get("prefill_chunk"):
            assert e.max_step_prefill <= kw["prefill_chunk"]


def test_generate_bitcompat_with_sharing_and_chunking(dense_setup):
    """The PR-1 contract survives the new allocator: ``generate()`` over
    GRPO-style duplicated prompts, with prefix sharing AND chunked prefill
    enabled, stays BIT-identical (incl. gen_logp) to ``RolloutEngine``."""
    cfg, _, params = dense_setup
    pl, mn, n = 8, 12, 3
    prompts = np.repeat(_prompts(2, pl, seed=4), n, axis=0)   # 2 groups of 3
    sync = RolloutEngine(cfg, max_new=mn, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True)
    cont = _engine(cfg, mn, max_slots=len(prompts), block_size=4,
                   prefill_chunk=4)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    r2 = cont.generate(params, prompts, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    np.testing.assert_array_equal(r1.response_mask, r2.response_mask)
    np.testing.assert_array_equal(r1.gen_logp, r2.gen_logp)
    # group members 2..N shared the head blocks (8 rows each at bs=4)
    assert cont.shared_prefill_tokens == 2 * (n - 1) * 4


def test_generate_preemption_with_sharing_chunking_matches_rollout(
        dense_setup):
    """Starved pool: recompute-preemption refills run through the
    prefix-matched chunked path and still land on the sync engine's greedy
    tokens."""
    cfg, _, params = dense_setup
    b, pl, mn = 4, 8, 12
    prompts = _prompts(b, pl, seed=4)
    sync = RolloutEngine(cfg, max_new=mn, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True)
    cont = _engine(cfg, mn, max_slots=3, block_size=4, num_blocks=11,
                   max_seq_len=pl + mn, prefill_chunk=6)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    r2 = cont.generate(params, prompts, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    cont.sched.check_invariants()


# ---------------------------------------------------------------------------
# chunked prefill: per-step budget + decode interleaving
# ---------------------------------------------------------------------------

def test_chunk_budget_and_interleaving_mid_decode(dense_setup):
    """A max-length prompt admitted while another request decodes: no step
    spends more than ``prefill_chunk`` prefill tokens, and the running
    request keeps producing tokens while the long prompt chunks in."""
    cfg, _, params = dense_setup
    bs, chunk, mn = 4, 4, 8
    long_pl = 36                       # max-length prompt: 9 chunks of 4
    cont = _engine(cfg, mn, max_slots=2, block_size=bs,
                   max_seq_len=long_pl + mn, prefill_chunk=chunk)
    short = _prompts(1, 8, seed=5)[0]
    cont.submit(short)
    cont.step(params)
    short_req = cont.sched.running[0]  # single slot in use so far
    cont.step(params)                  # short request is mid-decode
    long_prompt = _prompts(1, long_pl, seed=6)[0]
    rid_long = cont.submit(long_prompt)
    before = len(short_req.generated)
    outs = cont.step(params)           # admits the long prompt: first chunk
    cont.sched.check_invariants()
    long_req = cont.sched.running[1]
    assert long_req.rid == rid_long and cont._prefilling(long_req)
    while cont._prefilling(long_req):
        outs.extend(cont.step(params))
        cont.sched.check_invariants()
    progressed = len(short_req.generated) - before
    assert cont.max_step_prefill <= chunk
    assert progressed > 0, "decode stalled while the long prompt prefilled"
    outs.extend(cont.drain(params))
    # chunked long-prompt outputs == sync engine outputs
    sync = RolloutEngine(cfg, max_new=mn, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True)
    ref = sync.generate(params, long_prompt[None], jax.random.PRNGKey(5))
    o = next(o for o in outs if o.rid == rid_long)
    n = len(o.gen)
    assert n == ref.lengths[0]
    np.testing.assert_array_equal(np.asarray(o.gen),
                                  ref.tokens[0, long_pl:long_pl + n])


# ---------------------------------------------------------------------------
# partial-rollout resume hits the prefix cache; params change flushes it
# ---------------------------------------------------------------------------

def test_resume_hits_prefix_cache(dense_setup):
    """Budget-suspended requests leave their blocks indexed: the next-run
    resume re-matches every full block of prompt+generated (including
    blocks completed DURING decode) and only prefills the ragged tail."""
    cfg, _, params = dense_setup
    pl, mn, bs = 16, 16, 4
    prompt = _prompts(1, pl, seed=7)[0]
    cont = _engine(cfg, mn, max_slots=2, block_size=bs, max_seq_len=pl + mn)
    cont.submit(prompt, max_new=mn, budget=6)
    _, resum = cont.run_to_budget(params)
    req = resum[0]
    assert cont.shared_prefill_tokens == 0 and cont.prefill_tokens == pl
    cont.submit(req.prompt, generated=req.generated,
                max_new=mn - len(req.generated), budget=6)
    _, resum = cont.run_to_budget(params)
    # stream at resume: 16 prompt + 6 generated = 22 rows; full blocks
    # cover 20 (prompt blocks from admission + one block filled mid-decode)
    assert cont.shared_prefill_tokens == 20
    assert cont.prefill_tokens == pl + 2
    cont.sched.check_invariants()


def test_params_change_flushes_prefix_index(dense_setup):
    """KV cached under old weights must never satisfy a match under new
    weights — a fresh params object flushes the index."""
    cfg, _, params = dense_setup
    pl, mn = 16, 4
    prompt = _prompts(1, pl, seed=8)[0]
    cont = _engine(cfg, mn, max_slots=2, block_size=4, max_seq_len=pl + mn)
    cont.submit(prompt)
    cont.drain(params)
    params2 = jax.tree_util.tree_map(lambda a: a + 0, params)
    cont.submit(prompt)
    cont.drain(params2)
    assert cont.shared_prefill_tokens == 0
    assert cont.prefill_tokens == 2 * pl
    # same object again: the index rebuilt under params2 is matchable
    cont.submit(prompt)
    cont.drain(params2)
    assert cont.shared_prefill_tokens == 12
    cont.sched.check_invariants()


# ---------------------------------------------------------------------------
# randomized admit / evict / resume sweep — invariants after every step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("host_blocks", [0, 24])
def test_randomized_admit_evict_resume_sweep(dense_setup, host_blocks):
    """Duplicate-heavy traffic against a starved pool, submissions arriving
    mid-flight, budget suspends and mid-sequence resumes: refcount/index
    invariants hold after EVERY engine step and every request finishes with
    the sync engine's greedy tokens.  Runs tier-less and with the host KV
    tier attached — the host variant additionally exercises the tiered
    index exclusivity + host slot invariants (``check_invariants`` covers
    both tiers when ``cache.host`` is set)."""
    cfg, _, params = dense_setup
    pl, mn = 12, 10
    rng = np.random.RandomState(11)
    pool = [p for p in _prompts(3, pl, seed=11)]
    sync = RolloutEngine(cfg, max_new=mn, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True)
    ref = sync.generate(params, np.stack(pool), jax.random.PRNGKey(5))
    cont = _engine(cfg, mn, max_slots=3, block_size=4, num_blocks=14,
                   max_seq_len=pl + mn, prefill_chunk=5,
                   host_tier_blocks=host_blocks)

    # phase 1: staggered arrivals, stepped by hand, invariants every step
    arrivals = [int(rng.randint(0, 8)) for _ in range(8)]
    rid2prompt, outs, steps = {}, [], 0
    while arrivals or not cont.sched.idle:
        for t in list(arrivals):
            if t <= steps:
                arrivals.remove(t)
                pi = int(rng.randint(0, 3))
                rid2prompt[cont.submit(pool[pi])] = pi
        outs.extend(cont.step(params))
        cont.sched.check_invariants()
        steps += 1
        assert steps < 500, "engine stopped making progress"
    preempted = sum(o.preemptions for o in outs)

    # phase 2: budgeted rounds with mid-sequence resume
    pending = {}
    for i in range(6):
        pi = int(rng.randint(0, 3))
        rid = cont.submit(pool[pi], max_new=mn, budget=int(rng.randint(2, 6)))
        pending[rid] = pi
    rounds = 0
    while pending:
        finished, resum = cont.run_to_budget(params)
        cont.sched.check_invariants()
        for o in finished:
            rid2prompt[o.rid] = pending.pop(o.rid)
            outs.append(o)
        for req in resum:
            pi = pending.pop(req.rid)
            new_rid = cont.submit(req.prompt, generated=req.generated,
                                  max_new=mn - len(req.generated),
                                  budget=int(rng.randint(2, 6)))
            pending[new_rid] = pi
        rounds += 1
        assert rounds <= 16

    for o in outs:
        pi = rid2prompt[o.rid]
        n = len(o.gen)
        assert n == ref.lengths[pi]
        np.testing.assert_array_equal(np.asarray(o.gen),
                                      ref.tokens[pi, pl:pl + n])
    assert cont.cache.num_free == cont.cache.num_blocks
    assert cont.shared_prefill_tokens > 0, "sweep never hit the prefix cache"
    assert preempted > 0, "pool was never starved"
    if host_blocks:
        assert cont.stats()["swap_out_blocks"] > 0, "sweep never spilled"
        cont.close()


# ---------------------------------------------------------------------------
# MoE + trainer integration
# ---------------------------------------------------------------------------

def test_moe_shared_chunked_matches_sync():
    """Serving's expert FFN is dropless and per token, so chunked prefill
    with prefix sharing generates what the sync engine's whole-prompt
    prefill does, at the default capacity factor (which serving ignores)."""
    cfg = get_smoke_config("mixtral-8x7b").replace(dtype="float32",
                                                   remat=False)
    m = build_model(cfg)
    params = m.init(cfg, jax.random.PRNGKey(1))
    prompt = _prompts(1, 6, seed=6)[0]
    prompts = np.stack([prompt] * 3)
    sync = RolloutEngine(cfg, max_new=8, eos_id=TOK.eos_id,
                         pad_id=TOK.pad_id, greedy=True)
    cont = _engine(cfg, 8, max_slots=3, block_size=2, max_seq_len=14,
                   prefill_chunk=3)
    r1 = sync.generate(params, prompts, jax.random.PRNGKey(5))
    for _ in range(3):
        cont.submit(prompt)
    outs = cont.drain(params)
    cont.sched.check_invariants()
    for o in outs:
        n = len(o.gen)
        assert n == r1.lengths[o.rid]
        np.testing.assert_array_equal(np.asarray(o.gen),
                                      r1.tokens[o.rid, 6:6 + n])
    assert cont.shared_prefill_tokens > 0


def test_trainer_group_generation_shares_heads():
    """GRPO with the serving engine: the trainer's N-per-prompt generation
    batch hits the prefix cache for every group member after the first."""
    from repro.configs.base import RLConfig
    from repro.core.trainer import GRPOTrainer
    from repro.data.prompts import PromptDataset, pattern_task

    cfg = get_smoke_config("yi-6b").replace(dtype="float32", remat=False)
    rl = RLConfig(num_generations=2, max_prompt_len=12, max_response_len=8,
                  rollout_engine="serving", serve_max_slots=4,
                  serve_block_size=4)
    ds = PromptDataset(pattern_task(), max_prompt_len=rl.max_prompt_len,
                       seed=0)
    tr = GRPOTrainer(cfg, rl, ds, num_nodes=2, seed=0)
    tr.iteration(2)
    eng = tr.actor.engine
    assert isinstance(eng, ServingEngine)
    assert eng.shared_prefill_tokens > 0
