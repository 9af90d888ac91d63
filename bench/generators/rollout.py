"""A closed-loop stream of GRPO groups into the program's ``ServingEngine``.

Traffic file keys: ``slots``, ``block_size``, ``group_size`` (N),
``groups_in_flight``, ``prompt_len`` [lo, hi], ``max_new`` {median, sigma,
min, max} (lognormal, clipped), ``temperature``, ``fill_groups``,
``check_requests``.  A group is one prompt submitted N times, each with
its own ``max_new``; a new group goes in when a whole group has finished,
as GRPO's advantages wait for whole groups.  Sizes come in blocks of
groups that each hold the distributions' quantiles (``Stream``); the seed
orders each block and draws the token ids, so every seed offers the same
work.

Set-up makes the weights from the seed (one compiled call), warms every
program the stream uses (whole-prompt prefill at each length bucket, the
continuation prefill of a shared prefix at each tail bucket, the decode
step over all slots), then steps the stream until ``fill_groups`` groups
have finished, which brings it to its steady state: every slot busy, the
queue near its stationary depth, groups finishing at a steady rate.  The
window steps the engine until ``--seconds`` have passed, and records each
step's live slots, queue depth and groups submitted, and the window's
increase of every counter in the engine's registry, under the registry's
names (``serve.steps``, ``serve.launches``, ...).  A traced run gives the
engine an enabled tracer, whose spans (``serve.*``) land in the profiler's
trace beside the benchmark's.  After it, a
sample of the finished requests drawn from the seed, the longest among
them, is scored by the plain reference: the check is the widest gap
between the engine's log-probability of each served token and the
reference's.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import flops
import harness
import hostload
import program
import weights

BLOCK_GROUPS = 16


class Stream:
    """Groups in blocks of ``BLOCK_GROUPS``: every block holds the same
    sizes, the quantiles of the prompt-length and ``max_new``
    distributions, in an order the seed draws anew for each block.  A run
    uses some ten blocks, so every seed offers the same work, and so does
    every stretch of a few blocks."""

    def __init__(self, t: dict, seed: int, vocab: int):
        from statistics import NormalDist

        n, b = t["group_size"], BLOCK_GROUPS
        lo, hi = t["prompt_len"]
        self.prompt_lens = np.rint(
            lo + (hi - lo) * (np.arange(b) + 0.5) / b).astype(int)
        mn = t["max_new"]
        z = np.asarray([NormalDist().inv_cdf((k + 0.5) / (b * n))
                        for k in range(b * n)])
        self.max_news = np.clip(np.rint(mn["median"] * np.exp(mn["sigma"] * z)),
                                mn["min"], mn["max"]).astype(int)
        self.rng = np.random.default_rng(seed)
        self.n, self.vocab, self.i = n, vocab, 0

    def next(self):
        n, k = self.n, self.i % BLOCK_GROUPS
        if k == 0:
            self.lens = self.rng.permutation(self.prompt_lens)
            self.news = self.rng.permutation(self.max_news)
        self.i += 1
        prompt = self.rng.integers(0, self.vocab, self.lens[k], dtype=np.int32)
        return prompt, self.news[k * n:(k + 1) * n]


def warm(eng, params, t: dict, vocab: int, seed: int) -> None:
    """Compile every program the stream can reach: whole-prompt prefill at
    each length bucket the prompts fall in, continuation prefill of a
    shared prefix at every tail bucket up to the longest prompt, the
    decode step over all slots and the first-token draw."""
    from repro.serve.engine import prefill_bucket

    lo, hi = t["prompt_len"]
    bs = t["block_size"]
    rng = np.random.default_rng(seed ^ 0x5EED)
    base = rng.integers(0, vocab, hi, dtype=np.int32)
    eng.submit(base, max_new=64)
    b = prefill_bucket(lo)
    while b <= prefill_bucket(hi):
        n = min(max(b, lo), hi)
        eng.submit(rng.integers(0, vocab, n, dtype=np.int32), max_new=2)
        b *= 2
    eng.step(params)
    b = 8
    while b <= prefill_bucket(hi):
        take = min(b, hi - bs)                  # tail rows to prefill
        shared = max(bs, (hi - take) // bs * bs)
        take = min(take, hi - shared)
        tail = rng.integers(0, vocab, take, dtype=np.int32)
        eng.submit(np.concatenate([base[:shared], tail]), max_new=2)
        b *= 2
    eng.drain(params)


def drive(run, devices) -> dict:
    from repro.obs import Tracer
    from repro.serve.engine import ServingEngine

    c, t = run.config, run.traffic
    dims = flops.Dims.from_config(c)
    cfg = program.model_config(c)
    vocab = c["vocab_size"]
    lo, hi = t["prompt_len"]
    params = jax.jit(lambda k: weights.init(c, k))(weights.key(run.seed))
    eng = ServingEngine(cfg, max_new=t["max_new"]["max"],
                        eos_id=c["eos_token_id"], pad_id=c["eos_token_id"],
                        temperature=t["temperature"], max_slots=t["slots"],
                        block_size=t["block_size"],
                        max_seq_len=hi + t["max_new"]["max"], seed=run.seed,
                        tracer=Tracer(enabled=True) if run.trace else None)
    warm(eng, params, t, vocab, run.seed)

    stream = Stream(t, run.seed, vocab)
    pending: dict[int, int] = {}        # group -> requests not finished
    group_of: dict[int, int] = {}
    finished: dict[int, object] = {}
    count = {"requests": 0, "groups": 0, "done": 0}

    def submit_group():
        prompt, news = stream.next()
        g = stream.i
        pending[g] = len(news)
        for mn in news:
            rid = eng.submit(prompt, max_new=int(mn))
            group_of[rid] = g
            count["requests"] += 1
        count["groups"] += 1

    def step(keep: bool):
        with run.span("engine.step"):
            outs = eng.step(params)
        for o in outs:
            g = group_of.pop(o.rid)
            if keep:
                if "token" in run.faults and len(o.gen) > 1:
                    o.gen[1] = (o.gen[1] + 1) % vocab
                finished[o.rid] = o
            pending[g] -= 1
            if pending[g] == 0:
                del pending[g]
                count["done"] += 1
                with run.span("submit_group"):
                    submit_group()
        return outs

    for _ in range(t["groups_in_flight"]):
        submit_group()
    fill_steps = 0
    while count["done"] < t["fill_groups"]:
        step(keep=False)
        fill_steps += 1

    s0, r0 = eng.stats(), eng.metrics.snapshot()["counters"]
    c0 = dict(count)
    log = []                    # per step: end time, live, queue, groups in
    steps_ctx = []
    host = hostload.HostLoad()
    h0 = host.mark()
    run.start_window()
    with run.traced():
        while True:
            groups = count["groups"]
            outs = step(keep=True)
            now = time.perf_counter()
            log.append((now - run.window_t0,
                        eng.sched.num_running + len(outs),
                        eng.sched.num_pending, count["groups"] - groups))
            if run.trace:
                ctx = [r.cache_len for r in eng.sched.running.values()]
                ctx += [len(o.prompt) + len(o.gen) - 1 for o in outs]
                steps_ctx.append(ctx)
            if now - run.window_t0 >= run.seconds:
                break
    window_s = run.window_t1 - run.window_t0
    compiles = run.compiles_in_window()
    host_load = host.since(h0)
    host.close()
    s1, r1 = eng.stats(), eng.metrics.snapshot()["counters"]
    delta = {k: s1[k] - s0[k] for k in ("steps", "prefill_tokens",
                                        "shared_prefill_tokens",
                                        "decode_tokens", "sampled_tokens",
                                        "submitted", "finished")}
    # every counter of the engine's registry, under its own name
    delta.update({k: v - r0.get(k, 0) for k, v in r1.items()})
    window = {
        "kind": "rollout", "window_s": window_s, "counters": delta,
        "block_table": list(eng.sched.tables.shape),
        "attempted": count["requests"], "failed": 0,
        "window_compiles": compiles, "host": host_load,
        "stream": stream_summary(log, fill_steps, c0, count),
        "memory_peak_bytes": program.memory_peak_bytes(devices),
    }
    if run.trace:
        window["flops"] = window_flops(dims, delta, steps_ctx, lo, hi)
        window["paged_attention"] = [flops.paged_attention_cost(dims, ctx)
                                     for ctx in steps_ctx]
    window["trace"] = run.reduce_trace(run.chips) if run.trace else None
    del eng, params
    program.release()
    window["checks"] = compare(c, t, run, list(finished.values()))
    return window


def stream_summary(log, fill_steps, c0, c1, parts=4) -> dict:
    """The stream over the window, whole and in ``parts`` equal stretches
    of steps: live slots and queue depth per step, groups submitted, and
    the milliseconds a step took; flat stretches show a steady state."""
    ends = np.asarray([r[0] for r in log])
    live, queue, groups = (np.asarray([r[k] for r in log]) for k in (1, 2, 3))
    dur = np.diff(np.concatenate([[0.0], ends]))
    quarters = []
    for idx in np.array_split(np.arange(len(log)), parts):
        if len(idx):
            quarters.append({
                "steps": int(len(idx)),
                "step_ms": float(1e3 * dur[idx].mean()),
                "live_slots": float(live[idx].mean()),
                "queue": float(queue[idx].mean()),
                "groups_submitted": int(groups[idx].sum())})
    return {"fill_steps": fill_steps, "fill_groups_done": c0["done"],
            "steps": len(log),
            "groups_submitted": c1["groups"] - c0["groups"],
            "groups_done": c1["done"] - c0["done"],
            "live_slots_mean": float(live.mean()),
            "live_slots_min": int(live.min()),
            "queue_mean": float(queue.mean()),
            "queue_min": int(queue.min()),
            "parts": quarters}


def window_flops(dims, delta, steps_ctx, lo, hi) -> float:
    """Decode: every decoded token with its context; prefill: the tokens
    computed (shared rows are not), each counted as attending to half of
    the mean prompt (an undercount for tails of shared prompts); one head
    application per generated token."""
    decode = sum(flops.layer_flops(dims, len(ctx), sum(ctx))
                 for ctx in steps_ctx)
    pre = delta["prefill_tokens"]
    prefill = flops.layer_flops(dims, pre, int(pre * (lo + hi) / 4))
    return decode + prefill + flops.head_flops(dims, delta["sampled_tokens"])


def sample(outs: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not outs:
        return []
    outs = sorted(outs, key=lambda o: o.rid)
    longest = max(outs, key=lambda o: len(o.prompt) + len(o.gen))
    rest = [o for o in outs if o is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(c, t, run, outs) -> list:
    """Widest gap between a served token's engine log-probability and the
    reference's, over the sample; no finished request is a failure."""
    picked = sample(outs, t["check_requests"], run.seed)
    if not picked:
        run.readings = {"logp": float("inf"), "tokens": 0}
        return check.held(run.readings, run.limits)
    width = t["prompt_len"][1] + t["max_new"]["max"]
    w = jax.jit(lambda k: weights.init(c, k))(weights.key(run.seed))
    ref = _scorer(c, w, width, None)
    ctl = _scorer(c, w, width, "fp8") if run.control else None
    pairs, ctl_pairs = [], []
    for o in picked:
        lp = ref(o)
        pairs.append((o.gen_logp, lp))
        if ctl is not None:
            ctl_pairs.append((ctl(o), lp))
    run.readings = {"logp": check.widest_gap(pairs),
                    "tokens": int(sum(len(o.gen) for o in picked))}
    if ctl is not None:
        # the control: the reference in the program's place, in float8
        run.control_readings = {"logp": check.widest_gap(ctl_pairs)}
    return check.held(run.readings, run.limits)


def _scorer(c, w, width, quant):
    """Log-probabilities of a request's served tokens under the reference
    (``quant=None``) or the control, every request padded to one width so
    one program serves all."""
    ref = harness.reference(c)
    fn = jax.jit(lambda w, x: ref.token_logp(w, c, x, quant))

    def score(o):
        toks = np.full((1, width), c["eos_token_id"], np.int32)
        seq = np.concatenate([o.prompt, o.gen])
        toks[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            lp = np.asarray(fn(w, jnp.asarray(toks)))[0]
        p = len(o.prompt)
        return lp[p - 1:p - 1 + len(o.gen)]

    return score
