"""ServingEngine — request-level continuous batching over the paged KV cache.

Two APIs over one machinery:

  * online  — ``submit(prompt)`` / ``step(params)`` / ``drain(params)``: a
    request loop.  Each ``step`` admits whatever fits (prefill + KV inject),
    runs ONE fused decode step over the whole slot batch, and evicts finished
    sequences immediately — freed slots refill next step, so short requests
    never wait for long ones.  A request may be submitted MID-SEQUENCE
    (``generated=`` carries tokens from earlier runs; admission re-prefills
    prompt+seed exactly like a recompute-preemption refill) and carry a
    per-run ``budget``; ``run_to_budget(params)`` drains the queue and
    returns budget-exhausted requests as RESUMABLE — this is what backs
    cross-iteration partial rollout (core/partial.py).
  * batch   — ``generate(params, prompts, key)``: drop-in for
    ``core.rollout.RolloutEngine.generate``.  All prompts are prefilled in a
    single jitted call (bit-identical to the synchronized engine) and their
    KV rows injected at admission; with ``max_slots >= B`` and a block-aligned
    capacity the outputs are BIT-compatible with ``RolloutEngine`` under
    greedy decoding (tested).  ``on_finish`` streams each sample out the
    moment it completes — the trainer uses it to push finished rollouts into
    the transfer dock before the batch barrier.

The decode batch is always the full ``(max_slots,)`` slot vector: idle slots
carry the pad token, position 0, and a block table pointing at the null
block, so jitted shapes never change and no recompilation happens as
sequences come and go.  Per-slot depths ride the model zoo's paged decode
path (``decode_paged`` in models/transformer.py, models/moe.py), whose
attention reads the block tables DIRECTLY (kernels/paged_attention.py on
TPU, the chunked jnp reference elsewhere) — no dense per-slot cache view is
gathered, so decode-step cost scales with live tokens, not pool capacity.

Admission is PREFIX-CACHED and (optionally) CHUNKED:

  * ``prefix_cache=True`` (default) — the scheduler matches each request's
    block-aligned prompt head against resident ref-counted blocks
    (serve/paged_cache.py) and only the divergent tail is prefilled via the
    model zoo's ``prefill_paged`` continuation entry; GRPO's N-per-prompt
    groups prefill the prompt once, and preemption/partial-rollout resumes
    re-match their own still-indexed blocks.  A NEW params object flushes
    the index — stale-weights KV is never matched.
  * ``prefill_chunk=C`` — admission prefill is split into <=C-token chunks
    interleaved with decode steps: each ``step()`` spends at most C prefill
    tokens total (``max_step_prefill`` tracks the observed maximum), so a
    max-length prompt admitted mid-decode never monopolizes a step.
    Mid-prefill slots ride the fused decode step as idle (tables masked to
    the null block) until their first token is sampled.
  * ``host_tier_blocks=N`` — attaches a host-RAM KV tier beneath the
    device pool (serve/host_tier.py): reclaiming an indexed
    prefill-provenance block SPILLS it to host instead of dropping it, the
    scheduler matches host-resident prefixes at admission, and re-admission
    streams them back (swap preemption instead of recompute preemption).
    Requires ``prefix_cache=True`` — the tier is the index's second level.

Bit-identity scope (stated precisely, because the suite enforces it):
``generate()``'s batch path keeps its bitwise contract with
``RolloutEngine`` (incl. gen_logp) at ANY capacity — stash admissions
inject the one batched prefill's rows, and a prefix match only elides
writing identical bits.  The ONLINE path (submit/step, and generate()'s
preemption refills) is bitwise invariant to sharing, chunk size, and the
host tier being on or off, while the pow2-padded slot capacity fits one
flash kv-block (``REPRO_ATTN_BLOCK``, 512 rows — every test/smoke
config); past that the continuation chunk's online-softmax block
partition differs from whole-prompt prefill's, logits agree to allclose
rather than bitwise, and greedy equality is token-level in practice — the
same caveat the PR-4 bucketed admission prefill already carried versus
the sync engine.

SAMPLED decoding carries the same contract, because sampling is
COUNTER-BASED per request: ``submit`` derives each request's stream root
``fold_in(run_key, seed)`` (seed defaults to the rid) and token ``t`` is
drawn with ``fold_in(stream, t)`` — never from an engine-wide key chain —
so a request's sampled tokens are a pure function of (params, prompt,
stream, t), bitwise invariant to admission order, pool size, chunking,
preemption/refill, budget suspend/resume and the host tier, and equal to
the sync ``RolloutEngine`` wherever the logits themselves are bit-equal
(the flash kv-block scope above).  ``docs/serving.md`` § "Deterministic
sampling" states the full replay contract.

The tier-on/off leg additionally rests on three rules:
only prefill-provenance blocks spill (``PagedKVCache.mark_decode_write``),
a match chain never continues through device blocks after a host hit
(``Scheduler._match``), and swap-in registration lands at admission like
a whole-tail recompute's — so prefer unchunked admission when exact
tier-on/off logp equality matters.  See docs/serving.md.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.rollout import (RolloutResult, request_stream, sample_tokens,
                                sampled_drawer)
from repro.kernels.paged_attention import page_span
from repro.models.model import build_model
from repro.models.moe import STATS as MOE_STATS
from repro.models.transformer import paged_window
from repro.obs import NULL_SPAN, MetricsRegistry, get_tracer
from repro.serve.host_tier import HostKVTier, SwapWorkerError
from repro.serve.paged_cache import (PagedKVCache, blocks_for,
                                     scatter_prefill, scatter_token)
from repro.serve.scheduler import Request, Scheduler


def prefill_bucket(n: int) -> int:
    """Admission-prefill length bucket: next power of two (>= 8).  Online
    ``submit()`` sees arbitrary prompt+seed lengths; bucketing bounds the
    number of prefill/scatter jit specializations at O(log max_len) instead
    of one per distinct length."""
    b = 8
    while b < n:
        b *= 2
    return b


def _counted(fn):
    """``fn``, whose outputs end with an MoE model's expert counts, taking
    the counts' accumulator as its last argument and returning it, in the
    counts' place, with them added (one program, no extra launch)."""
    def run(*args):
        *out, counts = fn(*args[:-1])
        return (*out, args[-1] + counts)
    return run


@dataclass
class RequestOutput:
    rid: int
    prompt: np.ndarray       # (P,)  int32
    gen: np.ndarray          # (n,)  int32 — generated tokens, EOS inclusive
    gen_logp: np.ndarray     # (n,)  float32 — engine-side logp per token
    latency_s: float         # submit -> finish
    ttft_s: float            # submit -> first token (prefill)
    preemptions: int

    @property
    def tokens(self) -> np.ndarray:
        return np.concatenate([self.prompt, self.gen])


class ServingEngine:
    """Continuous-batching generation engine (the vLLM-Ascend analogue)."""

    def __init__(self, cfg: ModelConfig, *, max_new: int, eos_id: int,
                 pad_id: int, temperature: float = 1.0, greedy: bool = False,
                 top_p: float = 1.0, top_k: int = 0,
                 max_slots: int = 8, block_size: int = 16,
                 max_seq_len: int | None = None, num_blocks: int | None = None,
                 prefix_cache: bool = True, prefill_chunk: int | None = None,
                 host_tier_blocks: int = 0, seed: int = 0, tracer=None,
                 faults=None):
        if cfg.arch_type not in ("dense", "moe"):
            # ssm/hybrid cache recurrent state (nothing to page); vlm would
            # need per-request vision_embeds carried through preemption
            # refills (ROADMAP) — silently re-prefilling without them would
            # corrupt the vision-prefix KV, so refuse up front.
            raise ValueError(
                f"serving needs the paged {{k,v}} attention cache; arch "
                f"{cfg.name!r} ({cfg.arch_type}) is not servable — "
                f"use the synchronized RolloutEngine for it")
        self.cfg = cfg
        self.model = build_model(cfg)
        self.max_new = max_new
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.temperature = temperature
        self.greedy = greedy
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        self.top_p = top_p
        self.top_k = top_k
        self.max_slots = max_slots
        self.block_size = block_size
        self.prefix_cache = prefix_cache
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        if host_tier_blocks and not prefix_cache:
            raise ValueError(
                "host_tier_blocks requires prefix_cache=True: the host tier "
                "is the prefix index's second level — without the index "
                "there is nothing to spill under or match against")
        self.host_tier_blocks = host_tier_blocks
        self._num_blocks_req = num_blocks
        self.cache: PagedKVCache | None = None
        self.sched: Scheduler | None = None
        # run key for counter-based per-request sampling streams: NEVER
        # split/advanced (that was the old engine-wide key chain, whose
        # sequencing leaked scheduling into every request's samples) — each
        # request derives fold_in(run_key, seed) at submit and owns its
        # stream from then on
        self._run_key = jax.random.PRNGKey(seed)
        self._next_rid = 0
        self._on_finish = None
        self._resumable: list[Request] = []  # budget-exhausted, slot freed
        self._seen_params = None            # weights-era token: a new params
        #                                     object flushes the prefix index
        # telemetry (repro.obs): the registry is ALWAYS on (aggregate
        # counters/histograms — engine.stats() and the bench artifacts read
        # it); the tracer defaults to the disabled process tracer, whose
        # calls are no-ops in the hot loop.  Counter catalog (exact names
        # documented in docs/observability.md):
        #   serve.prefill_tokens = real tokens run through prefill COMPUTE
        #   (bucket pads excluded; the batch generate() path counts its full
        #   batched prefill — a hit there elides pool writes/blocks, not
        #   FLOPs); serve.shared_prefill_tokens = rows satisfied by a prefix
        #   match instead of a fresh prefill (compute savings on the online
        #   path, block/memory savings on the batch path); serve.launches =
        #   calls of the engine's jitted programs and of request_stream,
        #   serve.host_reads = device values read to the host, both counted
        #   at their call sites; serve.decode.kv_pages = pool pages the
        #   paged decode kernel walks for ONE layer, summed over decode
        #   steps (from the host positions, no device read);
        #   serve.moe.{rows,padded_rows,calls,experts} = the expert layers'
        #   work (models/moe.py STATS), accumulated on the device by the
        #   programs that run them and read when the registry is
        #   snapshotted (that read is not in serve.host_reads)
        self.tracer = tracer if tracer is not None else get_tracer()
        self.metrics = MetricsRegistry()
        self._moe_acc = None
        if cfg.is_moe:
            self._moe_acc = jnp.zeros((len(MOE_STATS),), jnp.int32)
            self.metrics.add_source(
                tuple(f"serve.moe.{n}" for n in MOE_STATS),
                lambda: np.asarray(self._moe_acc))
        # the host tier outlives pool regrows (_ensure_state rebuilds the
        # cache; host entries are content-addressed by prefix key, so they
        # stay valid against any device pool shape)
        self.host_tier = (
            HostKVTier(cfg, num_blocks=host_tier_blocks,
                       block_size=block_size, metrics=self.metrics,
                       tracer=self.tracer, faults=faults)
            if host_tier_blocks else None)
        self._host_degraded = False       # swap worker failed: tier dropped,
        #                                   recompute-preemption mode
        self._step_prefill = 0
        if max_seq_len is not None:
            self._ensure_state(max_seq_len)
        counted = _counted if cfg.is_moe else (lambda fn: fn)
        self._prefill = jax.jit(counted(self._prefill_impl))
        self._chunk = jax.jit(counted(self._chunk_impl))
        self._sample = jax.jit(self._sample_impl)
        self._step = jax.jit(counted(self._step_impl), donate_argnums=(1, 2))
        self._write = jax.jit(scatter_prefill, donate_argnums=(0,))
        # sampled draws go through the PROCESS-SHARED drawer (one compiled
        # function per sampling config, the same object RolloutEngine uses)
        # — engine-local jits could fuse the log_softmax differently and
        # drift logp by ulps, breaking the cross-engine bitwise contract
        self._draw = (None if greedy else
                      sampled_drawer(temperature, top_p, top_k, pad_id))

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------
    def _ensure_state(self, max_seq: int) -> None:
        mb = blocks_for(max_seq, self.block_size)
        if self.cache is not None:
            if self.cache.max_blocks_per_seq >= mb:
                return
            if self.sched.running:
                # running sequences have KV rows in the pool — regrowing
                # would orphan them; queued-only is safe (blocks are only
                # allocated at admission)
                raise RuntimeError(
                    f"request needs {mb} blocks/seq but the pool was sized "
                    f"for {self.cache.max_blocks_per_seq} and sequences are "
                    f"mid-decode; construct the engine with max_seq_len>= "
                    f"{max_seq} for mixed loads")
        waiting = self.sched.waiting if self.sched is not None else ()
        if (self.cache is not None and self.host_tier is not None
                and not self._host_degraded):
            # regrow drops the old pool; any in-flight swap-in targeted its
            # rows, so retire those (the owning requests were preempted —
            # they re-prefill; host entries themselves are content-addressed
            # and survive the regrow)
            try:
                self.host_tier.swap.drain()
                self.host_tier.swap.pop_ready()
            except SwapWorkerError:
                # the old pool is being dropped anyway, so no garbage rows
                # can survive — just flip to recompute-preemption mode
                self.host_tier.disable()
                self.metrics.inc("serve.swap.degraded")
                self._host_degraded = True
        num_blocks = self._num_blocks_req or self.max_slots * mb
        self.cache = PagedKVCache(self.cfg, num_blocks=num_blocks,
                                  block_size=self.block_size,
                                  max_blocks_per_seq=mb,
                                  host=(None if self._host_degraded
                                        else self.host_tier))
        self.sched = Scheduler(self.cache, self.max_slots,
                               prefix_cache=self.prefix_cache,
                               tracer=self.tracer, metrics=self.metrics)
        self.sched.waiting.extend(waiting)

    # ------------------------------------------------------------------
    # telemetry views (registry-backed; names in docs/observability.md)
    # ------------------------------------------------------------------
    @property
    def steps(self) -> int:
        """Fused decode steps run."""
        return self.metrics.value("serve.steps")

    @property
    def prefill_tokens(self) -> int:
        return self.metrics.value("serve.prefill_tokens")

    @property
    def shared_prefill_tokens(self) -> int:
        return self.metrics.value("serve.shared_prefill_tokens")

    @property
    def max_step_prefill(self) -> int:
        """Most prefill tokens any single step spent (chunk-budget bound)."""
        return int(self.metrics.value("serve.max_step_prefill"))

    def stats(self) -> dict:
        """Aggregate serving summary: request counts, token counters, and
        nearest-rank percentile summaries of per-request TTFT (submit ->
        first token) and e2e latency (submit -> finish), both derived from
        the ``Request`` ``submitted_at``/``first_token_at``/``finished_at``
        perf-counter stamps at finish time.  THE latency summary — consumers
        (examples/serve.py, bench artifacts) read this instead of computing
        their own percentiles."""
        m = self.metrics
        return {
            "submitted": m.value("serve.submitted"),
            "finished": m.value("serve.finished"),
            "suspended": m.value("serve.suspended"),
            "preemptions": m.value("serve.preemptions"),
            "preempt_swap": m.value("serve.preempt.swap"),
            "preempt_recompute": m.value("serve.preempt.recompute"),
            "steps": m.value("serve.steps"),
            "prefill_tokens": m.value("serve.prefill_tokens"),
            "shared_prefill_tokens": m.value("serve.shared_prefill_tokens"),
            "readmit_prefill_tokens": m.value("serve.readmit_prefill_tokens"),
            "decode_tokens": m.value("serve.decode_tokens"),
            "decode_kv_pages": m.value("serve.decode.kv_pages"),
            "launches": m.value("serve.launches"),
            "host_reads": m.value("serve.host_reads"),
            "sampled_requests": m.value("serve.sampled.requests"),
            "sampled_tokens": m.value("serve.sampled.tokens"),
            "priority_bypass": m.value("serve.priority.bypass"),
            "max_step_prefill": int(m.value("serve.max_step_prefill")),
            "swap_out_blocks": m.value("serve.swap.out_blocks"),
            "swap_out_bytes": m.value("serve.swap.out_bytes"),
            "swap_in_blocks": m.value("serve.swap.in_blocks"),
            "swap_in_bytes": m.value("serve.swap.in_bytes"),
            "swap_host_evictions": m.value("serve.swap.host_evictions"),
            "swap_degraded": m.value("serve.swap.degraded"),
            "host_tier_blocks": self.host_tier_blocks,
            "host_resident_blocks": (len(self.host_tier)
                                     if self.host_tier else 0),
            "ttft_s": m.summarize("serve.ttft_s"),
            "latency_s": m.summarize("serve.latency_s"),
        }

    # ------------------------------------------------------------------
    # jitted pieces
    # ------------------------------------------------------------------
    def _run(self, fn, *args):
        """Call one of the jitted programs below; an MoE engine hands it
        the expert counts' accumulator and keeps the one it returns."""
        if self._moe_acc is None:
            return fn(*args)
        *out, self._moe_acc = fn(*args, self._moe_acc)
        return tuple(out)

    def _prefill_impl(self, params, batch, last=None):
        """``last`` (traced () int32) selects the logits position for
        bucket-padded admission prefills; None (the batch generate() path)
        keeps the final position, bit-identical to RolloutEngine.  An MoE
        model's expert counts come last."""
        b, s = batch["tokens"].shape
        cache = self.model.init_cache(self.cfg, b, s)
        counts = {"moe_stats": True} if self.cfg.is_moe else {}
        return self.model.prefill(params, self.cfg, batch, cache, last=last,
                                  **counts)

    def _sample_impl(self, logits):
        """GREEDY first-token sampling (argmax consumes no key; the graph is
        the pre-streams one, keeping greedy bit-contracts untouched).
        Sampled engines draw first tokens through ``self._draw`` instead."""
        return sample_tokens(logits, None, temperature=self.temperature,
                             greedy=True)

    def _chunk_impl(self, params, pool_k, pool_v, table, chunk, start, last):
        """One continuation-prefill chunk for one slot (see
        ``models.*.prefill_paged``).  Compiles once per chunk BUCKET
        (``prefill_bucket``), like the whole-prompt admission path."""
        return self.model.prefill_paged(params, self.cfg, pool_k, pool_v,
                                        table, chunk, start,
                                        block_size=self.block_size, last=last)

    def _step_impl(self, params, pool_k, pool_v, tables, tok, pos, done):
        """One continuous-batching decode step over the full slot batch.

        tables: (S, MB) int32; tok: (S, 1); pos: (S,) — per-slot write
        position (= current cache length); done: (S,) True on idle slots.
        GREEDY engines sample fused in this graph (argmax — the pre-streams
        graph, so greedy bit-contracts are untouched) and return
        ``(pool_k, pool_v, nxt, lp)``.  SAMPLED engines return
        ``(pool_k, pool_v, logits)``; an MoE model's expert counts come
        last.  The draw happens in the
        process-shared ``sampled_drawer`` with each slot's stream root and
        token count, so slot s's token depends only on its OWN stream and
        logits, never on which other requests share the step — and the
        draw compiles identically to the sync engine's.

        TRUE paged decode: attention reads the block tables directly
        (kernels/paged_attention.py + kernels/ref.py) and the model returns
        only this token's per-layer KV rows, which are scattered into the
        pool — no dense ``(n, S, MB*bs, kv, hd)`` cache view is ever
        materialized and nothing is re-extracted from one, so step cost
        scales with LIVE tokens, not pool capacity.  ``gather_kv`` survives
        only behind ``PagedKVCache.dense_view`` for debugging/oracle use."""
        logits, new_k, new_v, *counts = self.model.decode_paged(
            params, self.cfg, pool_k, pool_v, tables, tok, pos,
            block_size=self.block_size)
        s = tables.shape[0]
        rows = jnp.arange(s)
        flat = (tables[rows, pos // self.block_size] * self.block_size
                + pos % self.block_size)            # (S,) — idle -> null block
        pool_k = scatter_token(pool_k, new_k, flat)
        pool_v = scatter_token(pool_v, new_v, flat)
        if self.greedy:
            nxt, lp = sample_tokens(logits, None,
                                    temperature=self.temperature,
                                    greedy=True, done=done,
                                    pad_id=self.pad_id)
            return pool_k, pool_v, nxt, lp, *counts
        return pool_k, pool_v, logits, *counts

    # ------------------------------------------------------------------
    # online API
    # ------------------------------------------------------------------
    def submit(self, prompt, *, max_new: int | None = None,
               budget: int | None = None, generated=None,
               seed: int | None = None, priority: int = 0) -> int:
        """Queue one request.  Returns its engine-assigned request id.

        ``max_new`` caps the NEW tokens this submission may emit (defaults to
        the engine-wide cap — never mutated per request).  ``generated``
        seeds the request mid-sequence with tokens from earlier runs; the
        admission prefill then covers prompt+seed, the same re-prefill the
        recompute preemption does.  ``budget`` (≤ max_new to matter) makes
        the request SUSPEND resumable after that many new tokens — collect
        it from ``run_to_budget``.

        ``seed`` names the request's SAMPLING STREAM: token ``t`` is drawn
        with ``fold_in(fold_in(run_key, seed), t)`` where ``t`` counts all
        generated tokens including the mid-sequence seed, so resubmitting a
        suspension with the SAME ``seed`` continues its stream exactly.
        Defaults to the request id — distinct per submission, replayable on
        a fresh engine built with the same engine ``seed`` because rids are
        assigned in submission order.  ``priority`` picks the admission/
        preemption class (higher runs first, evicted last; FIFO within a
        class, starvation-bounded — see serve/scheduler.AdmissionQueue);
        it never changes what any request GENERATES, only when.

        Admission prefill is BUCKETED: prompts are right-padded to the next
        power-of-2 length (causally inert) so varied-length online traffic
        compiles O(log max_len) prefill specializations, not one per
        distinct length."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        max_new = self.max_new if max_new is None else max_new
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if budget is not None and budget < 1:
            raise ValueError(f"budget must be >= 1, got {budget}")
        gen = [int(t) for t in generated] if generated is not None else []
        self._ensure_state(len(prompt) + len(gen) + max_new)
        rid = self._next_rid
        self._next_rid += 1
        if seed is None:
            seed = rid
        # greedy decoding never consumes a key — skip the stream derivation
        # so the greedy hot path stays dispatch-free at submit
        stream = None
        if not self.greedy:
            with self.tracer.span("serve.submit", cat="serve"):
                stream = np.asarray(request_stream(self._run_key, seed),
                                    np.uint32)
            self.metrics.inc("serve.launches")
            self.metrics.inc("serve.host_reads")
        # seeded tokens carry no engine-side logp (they were sampled in an
        # earlier run, possibly under different weights) — pad with zeros to
        # keep generated/gen_logp aligned
        self.sched.submit(Request(rid=rid, prompt=prompt, max_new=max_new,
                                  budget=budget, priority=priority,
                                  seed=seed, stream=stream, generated=gen,
                                  gen_logp=[0.0] * len(gen),
                                  resume_base=len(gen)))
        self.metrics.inc("serve.submitted")
        if not self.greedy:
            self.metrics.inc("serve.sampled.requests")
        return rid

    def _first_sample(self, logits, req: Request) -> tuple[int, float]:
        """Draw ``req``'s next token from its (1, V) admission-prefill
        logits.  Sampled requests go through the process-shared drawer with
        key ``fold_in(stream, t)``, ``t`` = tokens already generated
        (mid-sequence seed included) — bitwise the draw the decode step
        would make for this request at the same logits, so admission-time
        first-token sampling and decode sampling are one stream arithmetic.
        Greedy requests use the engine's fused greedy sampler.  The reads
        wait on the admission prefill: the host blocks here."""
        with self.tracer.span("serve.first_token", cat="serve"):
            if req.stream is None:
                t0, l0 = self._sample(logits)
            else:
                t0, l0 = self._draw(
                    logits, jnp.asarray(req.stream)[None],
                    jnp.full((1,), len(req.generated), jnp.int32),
                    jnp.zeros((1,), bool))
            self.metrics.inc("serve.launches")
            self.metrics.inc("serve.host_reads", 2)
            return int(t0[0]), float(l0[0])

    def _prefill_span(self, req: Request):
        """The ``serve.prefill`` span of one admission prefill or chunk."""
        tr = self.tracer
        return (tr.span("serve.prefill", cat="serve", args={"rid": req.rid})
                if tr.enabled else NULL_SPAN)

    def flush_prefix(self) -> None:
        """Drop every cached prefix now — BOTH tiers (the host tier flushes
        through ``PagedKVCache.flush_index``).  ``step()`` does this
        automatically when it sees a NEW params object; call it explicitly
        if you update weights by mutating the params container in place
        (object identity cannot see that)."""
        if self.sched is not None:
            self.sched.flush_prefix()
        self._seen_params = None

    def close(self) -> None:
        """Stop the host tier's swap worker (no-op without a tier).  The
        worker is a daemon thread, so this is for tidy tests and long-lived
        drivers that churn engines, not a correctness requirement."""
        if self.host_tier is not None:
            self.host_tier.close()

    @staticmethod
    def _prefilling(req: Request) -> bool:
        """True while an admitted request still owes tail-prefill rows (its
        first token is not sampled yet, so it cannot join the decode batch)."""
        return req.cache_len < req.prefill_len

    def step(self, params) -> list[RequestOutput]:
        """Admit what fits, advance chunked prefills within the per-step
        token budget, run one fused decode step over the decodable slots,
        evict what finished.  Mid-prefill slots ride along as idle (their
        table rows are masked to the null block for the decode write), so a
        long prompt never monopolizes a step.

        When the tracer is enabled, every step emits one ``serve.step`` span
        (its phases are child spans: ``serve.admit``, ``serve.decode.*``,
        ``serve.retire``) plus ``serve.tokens`` / ``serve.slots`` counter
        samples; disabled, this wrapper is a single predicate check on top
        of the hot loop and each phase span is the null span."""
        tr = self.tracer
        if not tr.enabled:
            return self._step_once(params)
        m = self.metrics
        with tr.span("serve.step", cat="serve", args=(args := {})):
            finished = self._step_once(params)
            args.update({
                "step": m.value("serve.steps"),
                "live_slots": self.sched.num_running if self.sched else 0,
                "waiting": self.sched.num_pending if self.sched else 0,
                "prefill_tokens": self._step_prefill,
                "finished": len(finished)})
        tr.counter("serve.tokens",
                   {"prefill": m.value("serve.prefill_tokens"),
                    "shared_prefill": m.value("serve.shared_prefill_tokens"),
                    "decode": m.value("serve.decode_tokens")}, cat="serve")
        tr.counter("serve.slots",
                   {"running": self.sched.num_running if self.sched else 0,
                    "waiting": self.sched.num_pending if self.sched else 0,
                    "preemptions": m.value("serve.preemptions")},
                   cat="serve")
        if self.host_tier is not None:
            tr.counter("serve.swap",
                       {"out_bytes": m.value("serve.swap.out_bytes"),
                        "in_bytes": m.value("serve.swap.in_bytes"),
                        "host_resident": len(self.host_tier)}, cat="serve")
        return finished

    def _step_once(self, params) -> list[RequestOutput]:
        finished: list[RequestOutput] = []
        if self.sched is None:
            return finished
        if params is not self._seen_params:
            # new weights: cached prefixes are stale — never match them.
            # Weights-era detection is OBJECT IDENTITY on the params pytree:
            # the trainers pass one stable object per era (jit updates
            # produce a fresh pytree), so this is exact for every in-repo
            # caller.  A driver that mutates the params container IN PLACE
            # must call flush_prefix() itself; one that rebuilds an equal
            # pytree every step merely flushes the cache into a no-op.
            if self._seen_params is not None:
                self.sched.flush_prefix()
            self._seen_params = params
        tr = self.tracer
        self._step_prefill = 0
        with tr.span("serve.admit", cat="serve"):
            self._admit(params, finished)
        self._advance_prefills(params, finished)
        self.metrics.set_max("serve.max_step_prefill", self._step_prefill)
        preempted = self.sched.ensure_capacity()
        if preempted:
            self.metrics.inc("serve.preemptions", len(preempted))
        if self.host_tier is not None and not self._host_degraded:
            # force the swap drain barrier now — after all of this step's
            # swap traffic was scheduled, BEFORE decode reads the pools: a
            # worker failure degrades the tier here, and the victims are
            # preempted before any garbage swap-in row can reach compute
            _ = self.cache.pool_k
            if self.cache.degraded:
                self._handle_degradation()
        with tr.span("serve.decode.prep", cat="serve"):
            decodable = [slot for slot, req in self.sched.running.items()
                         if not self._prefilling(req)]
            if not decodable:
                return finished
            s = self.max_slots
            tok = np.full((s, 1), self.pad_id, np.int32)
            pos = np.zeros((s,), np.int32)
            done = np.ones((s,), bool)
            streams = np.zeros((s, 2), np.uint32)  # idle/greedy: zero key
            tcount = np.zeros((s,), np.int32)
            tables = self.sched.tables
            for slot, req in self.sched.running.items():
                if self._prefilling(req):
                    # not decoding this step: route its KV write to the null
                    # block (a real table row would let the pad-token write
                    # clobber row 0 — possibly a SHARED prefix block)
                    tables = tables.copy() if tables is self.sched.tables \
                        else tables
                    tables[slot, :] = self.cache.null_block
                    continue
                tok[slot, 0] = req.generated[-1]
                pos[slot] = req.cache_len
                done[slot] = False
                if req.stream is not None:
                    streams[slot] = req.stream
                    tcount[slot] = len(req.generated)
            first, end = page_span(
                pos, self.block_size,
                paged_window(self.cfg, tables.shape[1] * self.block_size))
            self.metrics.inc("serve.decode.kv_pages", int((end - first).sum()))
        with tr.span("serve.decode.launch", cat="serve"):
            out = self._run(
                self._step, params, self.cache.pool_k, self.cache.pool_v,
                jnp.asarray(tables), jnp.asarray(tok),
                jnp.asarray(pos), jnp.asarray(done))
            if self.greedy:
                pool_k, pool_v, nxt, lp = out
            else:
                pool_k, pool_v, logits = out
                nxt, lp = self._draw(logits, jnp.asarray(streams),
                                     jnp.asarray(tcount), jnp.asarray(done))
        self.metrics.inc("serve.launches", 1 if self.greedy else 2)
        self.cache.pool_k, self.cache.pool_v = pool_k, pool_v
        self.metrics.inc("serve.steps")
        self.metrics.inc("serve.decode_tokens", len(decodable))
        if not self.greedy:
            self.metrics.inc("serve.sampled.tokens", len(decodable))
        with tr.span("serve.decode.wait", cat="serve"):
            nxt = np.asarray(nxt)
            lp = np.asarray(lp)
        self.metrics.inc("serve.host_reads", 2)
        with tr.span("serve.retire", cat="serve"):
            for slot in decodable:
                req = self.sched.running[slot]
                # the row just written lives in this block: taint it against
                # host spill (decode bytes are not prefill-reproducible)
                self.cache.mark_decode_write(int(self.sched.tables[
                    slot, req.cache_len // self.block_size]))
                req.cache_len += 1
                req.generated.append(int(nxt[slot]))
                req.gen_logp.append(float(lp[slot]))
                if req.cache_len % self.block_size == 0:
                    # a decode-filled block just completed: index it so a
                    # budget-suspended resume (or identical sampled prefix)
                    # re-matches instead of re-prefilling
                    self.sched.register_prefix(req)
                self._retire(req, finished)
        return finished

    def _handle_degradation(self) -> None:
        """The swap worker failed and the cache detached the tier
        (``PagedKVCache._degrade_host``) — finish the flip to plain
        recompute-preemption mode.  Every running request owning a block
        whose swap-in never landed is preempted (youngest first, matching
        ``ensure_capacity``'s victim order): its rows are garbage, and
        recompute re-prefills them bit-identically, so greedy outputs stay
        bitwise equal to a fault-free (or tier-off) run."""
        self._host_degraded = True
        bad = self.cache.take_degraded()
        victims = []
        if bad:
            for slot in reversed(self.sched._admit_order):
                blocks = self.sched._blocks.get(slot)
                if blocks is not None and bad.intersection(blocks) \
                        and self.sched.running.get(slot) is not None:
                    victims.append(slot)
            for slot in victims:
                self.sched._preempt(slot)
            if victims:
                self.metrics.inc("serve.preemptions", len(victims))
        if self.tracer.enabled:
            self.tracer.instant("serve.swap.degraded", cat="serve", args={
                "bad_blocks": sorted(int(b) for b in bad),
                "preempted": len(victims)})

    def drain(self, params) -> list[RequestOutput]:
        """Run steps until every queued request has finished.  Budgeted
        requests are refused here: their suspensions would be silently
        stranded (this returns finished outputs only) — use
        ``run_to_budget``, which collects them."""
        if self.sched is not None and any(
                r.budget is not None
                for r in (*self.sched.waiting, *self.sched.running.values())):
            raise RuntimeError(
                "drain() would drop budget-suspended requests on the floor; "
                "collect them with run_to_budget()")
        return self._drain(params)

    def _drain(self, params) -> list[RequestOutput]:
        outs: list[RequestOutput] = []
        while self.sched is not None and not self.sched.idle:
            outs.extend(self.step(params))
        return outs

    def run_to_budget(self, params, on_finish=None
                      ) -> tuple[list[RequestOutput], list[Request]]:
        """Drain the queue, retiring every request either FINISHED (EOS, or
        ``max_new`` new tokens emitted) or RESUMABLE (its per-run ``budget``
        exhausted first).  Returns ``(finished, resumable)``.

        Resumable requests' slots and KV blocks are already freed; continue
        one next run with ``submit(req.prompt, generated=req.generated,
        max_new=remaining, budget=...)`` — the re-prefill then happens under
        whatever weights that run passes, which is exactly the mildly
        off-policy resume partial rollout accepts by design.

        ``on_finish(out: RequestOutput)`` fires per request the moment it
        truly finishes (never for suspensions) — the partial-rollout trainer
        streams rows into the transfer dock from it mid-drain."""
        if on_finish is not None:
            self._on_finish = on_finish
        try:
            outs = self._drain(params)
        finally:
            if on_finish is not None:
                self._on_finish = None
            # hand over (or, on an aborted drain, discard) this run's
            # suspensions — stale entries must never leak into a later run
            resumable, self._resumable = self._resumable, []
        return outs, resumable

    # ------------------------------------------------------------------
    # admission / eviction
    # ------------------------------------------------------------------
    def _admit(self, params, finished: list) -> None:
        """Admit queued requests ONE at a time, prefilling (or scheduling
        the chunked prefill of) each before the next is matched — that
        ordering is what lets the 2nd..Nth member of a GRPO group admitted
        in the same step share the 1st member's freshly registered head."""
        while True:
            admitted = self.sched.admit(limit=1)
            if not admitted:
                return
            req = admitted[0]
            matched = req.cache_len            # rows the prefix match covers
            self.metrics.inc("serve.shared_prefill_tokens", matched)
            if req.stash is not None:
                # batch generate() path: rows come from the one batched
                # prefill; matched rows are already resident (bitwise the
                # same values) so their writes sink into the null block.
                # The batched prefill computed ALL p tokens regardless of
                # the match, so the full p counts as prefill compute — on
                # this path a hit saves blocks (memory), not FLOPs.
                krows, vrows, tok0, lp0 = req.stash
                req.stash = None
                p = krows.shape[1]
                self.metrics.inc("serve.prefill_tokens", p)
                with self._prefill_span(req):
                    flat = self._write_rows(req.slot, 0, matched, p, p)
                    self.cache.pool_k = self._write(self.cache.pool_k, krows,
                                                    flat)
                    self.cache.pool_v = self._write(self.cache.pool_v, vrows,
                                                    flat)
                self.metrics.inc("serve.launches", 2)
                req.cache_len = p
                self.sched.register_prefix(req)
                self._first_token(req, tok0, lp0, finished)
            elif matched == 0 and self.prefill_chunk is None:
                # whole-prompt bucketed masked prefill: right-pad to the next
                # power-of-2 length (pads are causally inert — rows < p and
                # their KV are bit-identical to an unpadded prefill) and read
                # the logits at the last REAL position; pad rows scatter into
                # the null block (the write sink), so the whole admission
                # path compiles once per BUCKET, not once per prompt length.
                toks = req.refill_tokens
                p = len(toks)
                pb = prefill_bucket(p)
                padded = np.full((pb,), self.pad_id, np.int32)
                padded[:p] = toks
                with self._prefill_span(req):
                    logits, cache = self._run(
                        self._prefill, params,
                        {"tokens": jnp.asarray(padded[None])},
                        jnp.int32(p - 1))
                    krows, vrows = cache["k"][:, 0], cache["v"][:, 0]
                    flat = self._write_rows(req.slot, 0, 0, p, pb)
                    self.cache.pool_k = self._write(self.cache.pool_k, krows,
                                                    flat)
                    self.cache.pool_v = self._write(self.cache.pool_v, vrows,
                                                    flat)
                self.metrics.inc("serve.launches", 3)
                self.metrics.inc("serve.prefill_tokens", p)
                if req.preemptions:
                    # re-admission prefill: with a host tier most of these
                    # rows would have been swapped in instead — THE
                    # machine-readable recompute-vs-swap A/B quantity
                    self.metrics.inc("serve.readmit_prefill_tokens", p)
                self._step_prefill += p
                req.cache_len = p
                self.sched.register_prefix(req)
                t0, l0 = self._first_sample(logits, req)
                self._first_token(req, t0, l0, finished)
            elif self.prefill_chunk is None:
                # prefix hit, unchunked: one continuation chunk covers the
                # whole divergent tail (>= 1 token by the match cap)
                self._run_chunk(params, req, req.prefill_len - matched,
                                finished)
            # else: chunked mode — _advance_prefills drives the tail (and,
            # for a fresh prompt, the whole prefill) under the per-step
            # token budget; the request sits admitted but not decodable

    def _advance_prefills(self, params, finished: list) -> None:
        """Chunked-prefill scheduler half-step: spend at most
        ``prefill_chunk`` prefill tokens across the mid-prefill slots
        (admission order), so prefill work per engine step is bounded and
        decode latency for running sequences stays flat."""
        if self.prefill_chunk is None:
            return
        budget = self.prefill_chunk
        for slot in list(self.sched._admit_order):
            if budget <= 0:
                return
            req = self.sched.running.get(slot)
            if req is None or not self._prefilling(req):
                continue
            take = min(budget, req.prefill_len - req.cache_len)
            budget -= self._run_chunk(params, req, take, finished)

    def _run_chunk(self, params, req: Request, take: int, finished: list
                   ) -> int:
        """One continuation-prefill call: rows [cache_len, cache_len+take)
        of ``req``'s stream, attending to everything already resident
        (shared prefix blocks and earlier chunks).  Completing the prefill
        samples the first token from the final chunk's logits.  Returns the
        prefill tokens actually spent (rematch may shrink the tail)."""
        self.metrics.inc("serve.shared_prefill_tokens",
                         self.sched.rematch(req))
        # pool reads are the swap-failure barrier: take them BEFORE building
        # the chunk, and if the tier degraded under them, resolve victims
        # first — this request itself may own a garbage swap-in block, in
        # which case it was just preempted and must not compute this chunk
        pool_k, pool_v = self.cache.pool_k, self.cache.pool_v
        if (self.host_tier is not None and not self._host_degraded
                and self.cache.degraded):
            self._handle_degradation()
            if self.sched.running.get(req.slot) is not req:
                return 0              # preempted: re-admitted via recompute
        take = min(take, req.prefill_len - req.cache_len)
        toks = req.refill_tokens
        start = req.cache_len
        cb = prefill_bucket(take)
        chunk = np.full((cb,), self.pad_id, np.int32)
        chunk[:take] = toks[start:start + take]
        with self._prefill_span(req):
            logits, krows, vrows = self._run(
                self._chunk, params, pool_k, pool_v,
                jnp.asarray(self.sched.tables[req.slot]),
                jnp.asarray(chunk[None]), jnp.int32(start),
                jnp.int32(take - 1))
            flat = self._write_rows(req.slot, start, 0, take, cb)
            self.cache.pool_k = self._write(self.cache.pool_k, krows, flat)
            self.cache.pool_v = self._write(self.cache.pool_v, vrows, flat)
        self.metrics.inc("serve.launches", 3)
        req.cache_len = start + take
        self.metrics.inc("serve.prefill_tokens", take)
        if req.preemptions:
            self.metrics.inc("serve.readmit_prefill_tokens", take)
        self._step_prefill += take
        self.sched.register_prefix(req)
        if not self._prefilling(req):
            t0, l0 = self._first_sample(logits, req)
            self._first_token(req, t0, l0, finished)
        return take

    def _first_token(self, req: Request, tok0: int, lp0: float,
                     finished: list) -> None:
        if req.first_token_at < 0:
            req.first_token_at = time.perf_counter()
        req.generated.append(tok0)
        req.gen_logp.append(lp0)
        if not self.greedy:
            self.metrics.inc("serve.sampled.tokens")
        self._retire(req, finished)

    def _write_rows(self, slot: int, base: int, skip: int, take: int,
                    padded: int) -> jnp.ndarray:
        """Flat pool rows for a (bucket-padded) prefill write whose row j
        holds GLOBAL position base+j: rows skip <= j < take land at their
        table-mapped position; everything else — already-resident
        prefix-matched rows (j < skip) and bucket pads (j >= take) — sinks
        into the null block, whose reads are always masked.  One mapping
        for all three admission writes: whole-prompt (base=0, skip=0),
        stash (base=0, skip=matched), chunk (base=start, skip=0)."""
        tbl = self.sched.tables[slot]
        j = np.arange(padded)
        g = base + np.minimum(j, take - 1)
        real = tbl[g // self.block_size] * self.block_size \
            + g % self.block_size
        sink = self.cache.null_block * self.block_size + j % self.block_size
        return jnp.asarray(np.where((j >= skip) & (j < take), real, sink))

    def _retire(self, req: Request, finished: list) -> None:
        """Evict the request if its last token ended it: EOS or ``max_new``
        new tokens => finished; per-run ``budget`` reached => suspended
        (resumable).  ``max_new`` is checked first, so a budget larger than
        the remaining cap clamps itself."""
        if (req.generated[-1] == self.eos_id
                or req.num_new >= req.max_new):
            self._finish(req.slot, finished)
        elif req.budget is not None and req.num_new >= req.budget:
            self._resumable.append(self.sched.suspend(req.slot))
            self.metrics.inc("serve.suspended")

    def _finish(self, slot: int, finished: list) -> None:
        req = self.sched.finish(slot)
        out = RequestOutput(
            rid=req.rid, prompt=req.prompt,
            gen=np.asarray(req.generated, np.int32),
            gen_logp=np.asarray(req.gen_logp, np.float32),
            latency_s=req.finished_at - req.submitted_at,
            ttft_s=max(req.first_token_at - req.submitted_at, 0.0),
            preemptions=req.preemptions)
        self.metrics.inc("serve.finished")
        self.metrics.observe("serve.ttft_s", out.ttft_s)
        self.metrics.observe("serve.latency_s", out.latency_s)
        finished.append(out)
        if self._on_finish is not None:
            self._on_finish(out)

    # ------------------------------------------------------------------
    # batch API — drop-in for RolloutEngine.generate
    # ------------------------------------------------------------------
    def generate(self, params, prompts: np.ndarray, key, extras=None,
                 on_finish=None) -> RolloutResult:
        """prompts: (B, PL) int32 padded.  Continuous-batching decode; each
        finished sample is streamed to ``on_finish(i, tokens_row, mask_row,
        length)`` the moment it completes (cap-width rows, dock-ready).

        ``key`` is consumed as this CALL's run key only — row ``i`` samples
        token ``t`` with ``fold_in(fold_in(key, i), t)``, exactly
        ``RolloutEngine.generate``'s derivation, and NO engine state is
        mutated by it: the same (params, prompts, key) replays bitwise on
        this engine or a fresh one, and interleaved ``generate()`` calls
        never cross-contaminate."""
        b, pl = prompts.shape
        cap = pl + self.max_new
        self._ensure_state(cap)
        if not self.sched.idle:
            raise RuntimeError("generate() needs an idle engine")
        batch = {"tokens": jnp.asarray(prompts)}
        if extras:
            batch.update(extras)
        # ONE batched prefill for the whole wave — bit-identical numerics to
        # RolloutEngine's prefill; rows are injected into the pool per slot
        # at admission time, so refills never recompile.
        logits, cache = self._run(self._prefill, params, batch, None)
        streams = np.asarray(
            jax.vmap(lambda i: request_stream(key, i))(jnp.arange(b)),
            np.uint32)
        if self.greedy:
            tok0, lp0 = self._sample(logits)
        else:
            tok0, lp0 = self._draw(logits, jnp.asarray(streams),
                                   jnp.zeros((b,), jnp.int32),
                                   jnp.zeros((b,), bool))
        tok0, lp0 = np.asarray(tok0), np.asarray(lp0)
        self.metrics.inc("serve.launches", 3)    # prefill, streams, draw
        self.metrics.inc("serve.host_reads", 3)  # streams, tok0, lp0

        rows: dict[int, tuple] = {}

        def sink(out: RequestOutput):
            trow, mrow, n = self.assemble_row(out, pl, cap)
            rows[out.rid] = (trow, mrow, n, out)
            if on_finish is not None:
                on_finish(out.rid, trow, mrow, n)

        self._on_finish = sink
        try:
            for i in range(b):
                req = Request(rid=i, prompt=np.asarray(prompts[i], np.int32),
                              max_new=self.max_new, seed=i,
                              stream=None if self.greedy else streams[i])
                req.stash = (cache["k"][:, i], cache["v"][:, i],
                             int(tok0[i]), float(lp0[i]))
                self.sched.submit(req)
            self.drain(params)
        finally:
            self._on_finish = None

        t = max(r[2] for r in rows.values())
        tokens = np.stack([rows[i][0] for i in range(b)])
        mask = np.stack([rows[i][1] for i in range(b)])
        lengths = np.asarray([rows[i][2] for i in range(b)], np.int32)
        gen_logp = np.zeros((b, t), np.float32)
        for i in range(b):
            out = rows[i][3]
            gen_logp[i, :len(out.gen_logp)] = out.gen_logp
        return RolloutResult(tokens=tokens, response_mask=mask,
                             gen_logp=gen_logp, lengths=lengths)

    def assemble_row(self, out: RequestOutput, pl: int, cap: int):
        """RolloutEngine-format row: prompt + gen, PAD after EOS.  THE
        dock-ready row format — every consumer (generate()'s on_finish and
        the partial-rollout trainer's sink) assembles through here."""
        row = np.full((cap,), self.pad_id, np.int32)
        row[:pl] = out.prompt[:pl]
        n = len(out.gen)
        row[pl:pl + n] = out.gen
        mask = np.zeros((cap,), np.float32)
        mask[pl:pl + n] = 1.0
        return row, mask, n
