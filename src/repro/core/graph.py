"""First-class dataflow-graph API — RL algorithms as declared graphs.

The paper (Fig. 1) describes RL training as a graph whose NODES are worker
states and whose EDGES are sample dataflow through the transfer dock plus
the weight resharding flow.  This module makes that graph a first-class
object instead of hand-written stage sequencing inside each trainer:

  * ``StageNode``     — one worker state: its cluster node id, the dock
    fields it consumes/produces, the callable that does the work, and an
    optional weight-layout requirement ("generation" | "update") which IS
    the resharding-flow edge.
  * ``RLGraph``       — a validated collection of stage nodes (unique
    names, acyclic field dependencies, every input produced by some node
    or declared external).
  * ``GraphExecutor`` — the readiness-driven scheduler: it runs any node
    whose input fields are ready per the TDController metadata, performs
    the resharding transitions the layout edges demand, and — when the
    config enables stage fusion — dispatches independent ready nodes
    CONCURRENTLY (the paper's Table 2 fusion becomes a scheduling
    property, not trainer code).

Mapping of paper Fig. 1 onto a GRPO declaration::

                       +------------------+
        prompt ------> | actor_generation |   layout: generation
                       +------------------+
                         | tokens, response_mask
          +--------------+---------------+----------------+
          v                              v                v
    [actor_inference]            [ref_inference]      [reward]     (all three
      | old_logp                   | ref_logp           | rewards   fuse)
          +--------------+---------------+        +-----+
                         v                        v
                         |                  [advantages]  (group barrier)
                         |                        | advantages
                         +-----------+------------+
                                     v
                              [actor_update]          layout: update

With the serving engine, generation streams each finished sample into the
dock the moment its sequence completes; the executor polls the metadata
plane while generation drains and starts stream-capable downstream nodes
(ref_inference, reward) at SAMPLE granularity — before the generation
barrier.

Execution semantics
-------------------
``GraphExecutor.run(graph, ctx, expected=N)`` schedules in rounds.  In each
round every node not yet finished asks its controller which samples have
all declared input fields ready; a node with work is dispatched when

  * it is a STREAM node (``stream=True``) — any non-empty subset runs, or
  * it is a BARRIER node — the full expected batch must be ready
    (``expected`` is the per-iteration sample count; ``expected=None``
    makes every node greedy, which is what partial rollout needs).

All runnable nodes of one round that agree on a weight layout are
dispatched together — concurrently when ``rl.stage_fusion`` is set.  The
executor owns the resharding flow: before dispatching a round it moves the
actor weights to the layout the round requires via
``ctx.resharder.to_generation()`` / ``to_update()`` and restores the update
layout when the run drains.  Node callables never call the resharder.

``ctx`` is the algorithm object (a trainer).  The executor reads/writes
``ctx.params`` (update-layout weights) and ``ctx.gen_params``
(generation-layout weights, only non-None while the generation layout is
live) and reads ``ctx.resharder``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax

from repro.core.resharding import ReshardLedger
from repro.obs import MetricsRegistry, get_tracer
from repro.resilience import RetryPolicy, TransientError

LAYOUTS = ("generation", "update")
TIMINGS = ("gen", "infer", "update")


@dataclass
class StageNode:
    """One worker state of the RL dataflow graph.

    ``fn(ctx, io)`` does the stage's work: ``io.ins`` holds the fetched
    input fields (stacked arrays over ``io.idxs``), and the return value is
    a dict ``{field: rows}`` aligned with ``io.idxs`` that the executor
    puts back into the dock (return None to opt out — e.g. when the stage
    streamed its outputs through ``io.put`` itself).  Setting
    ``io.consumed`` to a subset of ``io.idxs`` marks only those samples
    consumed (partial rollout finishes a prefix of its batch per round).
    """
    name: str                         # worker-state name (one TDController)
    node: int                         # cluster node id (dock ledger routing)
    inputs: tuple                     # dock fields consumed
    outputs: tuple                    # dock fields produced
    fn: Callable                      # fn(ctx, io) -> dict | None
    layout: Optional[str] = None      # "generation" | "update" | None (any)
    stream: bool = False              # may run on partial sample subsets
    gate: Optional[Callable] = None   # gate(ctx, idxs) -> dispatchable idxs
    timing: str = "infer"             # stats bucket: gen | infer | update
    max_retries: Optional[int] = None  # transient-failure retry budget for
    #                                    this node (None = executor default)

    def __post_init__(self):
        if self.layout is not None and self.layout not in LAYOUTS:
            raise ValueError(f"node {self.name!r}: layout must be one of "
                             f"{LAYOUTS}, got {self.layout!r}")
        if self.timing not in TIMINGS:
            raise ValueError(f"node {self.name!r}: timing must be one of "
                             f"{TIMINGS}, got {self.timing!r}")
        self.inputs = tuple(self.inputs)
        self.outputs = tuple(self.outputs)


class StageIO:
    """Per-dispatch view handed to a node callable."""

    def __init__(self, node: StageNode, idxs: list, ins: dict,
                 executor: "GraphExecutor"):
        self.node = node
        self.idxs = list(idxs)
        self.ins = ins
        self.consumed = list(idxs)    # fn may shrink (partial rollout)
        self._ex = executor

    def put(self, fld: str, idxs, rows) -> None:
        """Thread-safe dock put attributed to this stage's cluster node —
        used by streaming stages (serving on_finish) to emit per-sample
        outputs before the stage returns."""
        self._ex.put(self.node, fld, idxs, rows)


class RLGraph:
    """A validated dataflow graph: stage nodes + field edges."""

    def __init__(self, name: str, nodes: Sequence[StageNode],
                 external: Sequence[str] = ("prompt",)):
        self.name = name
        self.nodes = list(nodes)
        self.external = tuple(external)
        self._validate()

    # -- validation ---------------------------------------------------------
    def _validate(self) -> None:
        names = [n.name for n in self.nodes]
        if len(set(names)) != len(names):
            raise ValueError(f"graph {self.name!r}: duplicate node names in "
                             f"{names}")
        producers: dict[str, str] = {}
        for n in self.nodes:
            for f in n.outputs:
                if f in producers:
                    raise ValueError(
                        f"graph {self.name!r}: field {f!r} produced by both "
                        f"{producers[f]!r} and {n.name!r}")
                producers[f] = n.name
        for n in self.nodes:
            for f in n.inputs:
                if f not in producers and f not in self.external:
                    raise ValueError(
                        f"graph {self.name!r}: node {n.name!r} consumes "
                        f"{f!r} which no node produces and which is not "
                        f"declared external {self.external}")
        self.toposort()   # raises on cycles

    def toposort(self) -> list:
        """Topological order over field dependencies (Kahn).  Raises on
        cycles.  The declared order is preserved among ties — it is the
        deterministic dispatch order of the executor."""
        producers = {f: n.name for n in self.nodes for f in n.outputs}
        deps = {n.name: {producers[f] for f in n.inputs if f in producers
                         and producers[f] != n.name}
                for n in self.nodes}
        order, placed = [], set()
        nodes = list(self.nodes)
        while nodes:
            ready = [n for n in nodes if deps[n.name] <= placed]
            if not ready:
                cyc = sorted(n.name for n in nodes)
                raise ValueError(f"graph {self.name!r}: dependency cycle "
                                 f"among {cyc}")
            for n in ready:
                order.append(n)
                placed.add(n.name)
            nodes = [n for n in nodes if n.name not in placed]
        return order

    # -- derived views ------------------------------------------------------
    def states(self) -> dict:
        """worker-state name -> cluster node id (the TransferDock ctor arg)."""
        return {n.name: n.node for n in self.nodes}

    def edges(self) -> list:
        """(producer, field, consumer) triples, external producers as '·'."""
        producers = {f: n.name for n in self.nodes for f in n.outputs}
        out = []
        for n in self.nodes:
            for f in n.inputs:
                out.append((producers.get(f, "·"), f, n.name))
        return out

    def describe(self) -> str:
        """Human-readable declaration — what `--print-graph` shows."""
        lines = [f"RLGraph {self.name!r} "
                 f"(external fields: {', '.join(self.external)})"]
        for n in self.toposort():
            tags = []
            if n.layout:
                tags.append(f"layout={n.layout}")
            if n.stream:
                tags.append("stream")
            if n.gate is not None:
                tags.append("gated")
            tag = f"  [{', '.join(tags)}]" if tags else ""
            lines.append(f"  {n.name} @node{n.node}{tag}")
            lines.append(f"      in : {', '.join(n.inputs) or '—'}")
            lines.append(f"      out: {', '.join(n.outputs) or '—'}")
        return "\n".join(lines)


@dataclass
class GraphRun:
    """Result record of one GraphExecutor.run."""
    trace: list = field(default_factory=list)        # (node, idxs) dispatches
    stage_times: dict = field(default_factory=lambda: dict.fromkeys(
        TIMINGS, 0.0))
    counts: dict = field(default_factory=dict)       # node -> samples consumed
    rounds: int = 0
    reshard: ReshardLedger = field(default_factory=ReshardLedger)
    retries: dict = field(default_factory=dict)      # node -> retry count
    quarantined: dict = field(default_factory=dict)  # node -> dropped idxs
    quarantined_idxs: set = field(default_factory=set)  # union over nodes


class GraphExecutor:
    """Readiness-driven scheduler over one transfer dock.

    One executor instance serves ANY RLGraph over its dock — GRPO, PPO and
    partial rollout are three declarations over the same engine.
    """

    def __init__(self, dock, rl, tracer=None, faults=None, retry=None,
                 metrics=None):
        self.dock = dock  # guarded-by: lock
        self.rl = rl
        self.lock = threading.RLock()
        # every dispatch emits one `stage.<node>` span (cat "graph") carrying
        # node id, sample idxs and fused-round membership — the rich form of
        # the (node, idxs) tuples GraphRun.trace keeps for bit-identity tests
        self.tracer = tracer if tracer is not None else get_tracer()
        self.faults = faults              # FaultPlan | None (chaos hook)
        self.retry = retry if retry is not None else RetryPolicy()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._mesh_stage = threading.Lock()   # see _mesh_scope

    # -- thread-safe dock access -------------------------------------------
    def put(self, node: StageNode, fld: str, idxs, rows) -> None:
        # dock.put injects its fault at entry, before any row lands, so a
        # retried put is exactly idempotent (same rows land once)
        for attempt in range(self.retry.max_retries + 1):
            try:
                with self.lock:
                    self.dock.put(fld, idxs, rows, src_node=node.node)
                return
            except TransientError as err:
                if attempt >= self.retry.max_retries:
                    raise
                self._note_retry(node, attempt, err)
                time.sleep(self.retry.backoff(attempt))

    def _available(self, node: StageNode, ctx) -> list:
        with self.lock:
            idxs = self.dock.request_metadata(node.name, node.inputs)
        if node.gate is not None:
            idxs = list(node.gate(ctx, idxs))
        return idxs

    def _peek(self, node: StageNode, ctx) -> list:
        """Readiness check WITHOUT a ledger-counted metadata request — the
        streaming busy-poll uses this so the dispatch ledger keeps modeling
        algorithmic traffic, not poll frequency (a real deployment is
        notified by the warehouse broadcast, not by polling)."""
        with self.lock:
            idxs = self.dock.controllers[node.name].available(node.inputs)
        if node.gate is not None:
            idxs = list(node.gate(ctx, idxs))
        return idxs

    def _fetch(self, node: StageNode, idxs) -> dict:
        with self.lock:
            return {f: self.dock.get(node.name, f, idxs, node.node)
                    for f in node.inputs}

    # -- layout (resharding-flow) edges -------------------------------------
    def _ensure_layout(self, ctx, want: str) -> None:
        if want == self._layout:
            return
        if not self.tracer.enabled:   # disabled tracer: no span-name f-string
            return self._do_reshard(ctx, want)
        with self.tracer.span(f"reshard.to_{want}", cat="reshard"):
            self._do_reshard(ctx, want)

    def _do_reshard(self, ctx, want: str) -> None:
        if want == "generation":
            gen, stash, led = ctx.resharder.to_generation(ctx.params)
            ctx.params = None     # paper semantics: update buffers off-device
            ctx.gen_params = gen
            self._stash = stash
            # accumulate across round trips so multi-transition runs report
            # total reshard traffic, not just the last trip
            prev = self._run.reshard
            led.events = prev.events + led.events
            led.d2h_bytes += prev.d2h_bytes
            led.h2d_bytes += prev.h2d_bytes
            led.gathered_bytes += prev.gathered_bytes
            led.wall_s += prev.wall_s
            self._run.reshard = led
        else:
            ctx.gen_params = None
            ctx.params, self._run.reshard = ctx.resharder.to_update(
                self._stash, self._run.reshard)
            self._stash = None
        self._layout = want

    @contextlib.contextmanager
    def _mesh_scope(self, ctx):
        """A stage's device context when the trainer's mesh spans several
        devices: ``jax.set_mesh`` (thread-local, so per stage thread) lets
        ``kernels.ops`` run each Pallas kernel per shard, since XLA cannot
        partition one.  Such stages run one at a time: two threads launching
        programs with collectives on the same devices may enqueue them in a
        different order on each device and deadlock.  On one device stages
        keep their concurrency and their programs stay unchanged."""
        mesh = getattr(getattr(ctx, "resharder", None), "mesh", None)
        if mesh is None or mesh.size == 1:
            yield
            return
        with self._mesh_stage, jax.set_mesh(mesh):
            yield

    # -- dispatch -----------------------------------------------------------
    def _dispatch(self, node: StageNode, idxs, ctx, *, round_: int = 0,
                  fused: bool = False, stream: bool = False) -> None:
        """One stage dispatch.  ``round_`` is the executor round that
        scheduled it, ``fused`` whether it shared the round with other
        nodes (concurrent dispatch), ``stream`` whether it was started by
        the streaming poll while a generation stage drained — together the
        span records the fused-round membership the bare trace tuple
        cannot express."""
        if not self.tracer.enabled:   # disabled tracer: no span-arg dict,
            return self._run_stage(node, idxs, ctx)   # no f-string name
        span_args = {"node": node.name, "cluster_node": node.node,
                     "samples": len(idxs),
                     "idxs": [int(i) for i in idxs],
                     "round": round_, "fused": fused, "stream": stream}
        with self.tracer.span(f"stage.{node.name}", cat="graph",
                              args=span_args):
            self._run_stage(node, idxs, ctx)

    def _run_stage(self, node: StageNode, idxs, ctx) -> None:
        budget = (node.max_retries if node.max_retries is not None
                  else self.retry.max_retries)
        for attempt in range(budget + 1):
            try:
                # fault site at stage ENTRY — a retried attempt re-runs the
                # whole stage from the fetch, so retry is idempotent and the
                # outputs of a recovered run are bit-identical to fault-free
                if self.faults is not None:
                    self.faults.check("stage." + node.name)
                io = self._attempt_stage(node, idxs, ctx)
                break
            except TransientError as err:
                if attempt >= budget:
                    self._quarantine(node, idxs, err)
                    return
                self._note_retry(node, attempt, err)
                time.sleep(self.retry.backoff(attempt))
        with self.lock:
            if io.consumed:
                self.dock.mark_consumed(node.name, io.consumed)
            run = self._run
            run.counts[node.name] = (run.counts.get(node.name, 0)
                                     + len(io.consumed))

    def _attempt_stage(self, node: StageNode, idxs, ctx) -> StageIO:
        ins = self._fetch(node, idxs)
        io = StageIO(node, idxs, ins, self)
        with self._mesh_scope(ctx):
            out = node.fn(ctx, io)
        if out:
            for fld, rows in out.items():
                self.put(node, fld, io.idxs, rows)
        return io

    def _note_retry(self, node: StageNode, attempt: int, err) -> None:
        self.metrics.inc("graph.retry")
        with self.lock:
            run = getattr(self, "_run", None)
            if run is not None:
                run.retries[node.name] = run.retries.get(node.name, 0) + 1
        if self.tracer.enabled:
            self.tracer.instant("graph.retry", cat="graph",
                                args={"node": node.name, "attempt": attempt,
                                      "error": str(err)})

    def _quarantine(self, node: StageNode, idxs, err) -> None:
        """Retry budget exhausted: drop this dispatch's samples instead of
        poisoning the batch.  The idxs are marked consumed for the failing
        node (so the run quiesces) and recorded on the GraphRun; downstream
        barriers shrink by the quarantined count (``_effective``), so
        surviving samples still flow end to end."""
        dropped = [int(i) for i in idxs]
        with self.lock:
            self.dock.mark_consumed(node.name, idxs)
            run = self._run
            run.quarantined.setdefault(node.name, []).extend(dropped)
            run.quarantined_idxs.update(dropped)
            # NOT added to run.counts: ``_effective`` already shrinks every
            # node's target by the quarantined idxs, and counting them as
            # consumed too would double-subtract — the failing node would
            # stop before processing the samples that were still healthy
        self.metrics.inc("graph.quarantined", len(dropped))
        if self.tracer.enabled:
            self.tracer.instant("graph.quarantine", cat="graph",
                                args={"node": node.name, "idxs": dropped,
                                      "error": str(err)})

    def _effective(self, expected: int | None) -> int | None:
        """Barrier target net of quarantined samples — a dropped sample can
        never arrive, so downstream barriers must not wait for it."""
        if expected is None:
            return None
        return expected - len(self._run.quarantined_idxs)

    def _streaming(self, ctx, graph: RLGraph) -> bool:
        actor = getattr(ctx, "actor", None)
        return (self.rl.stage_fusion
                and actor is not None
                and getattr(actor, "engine_kind", "sync") == "serving"
                and any(n.stream for n in graph.nodes))

    def _poll_stream(self, graph, ctx, expected, seen) -> bool:
        """Dispatch stream nodes on whatever samples became ready while a
        generation-layout stage is draining.  Returns True on progress.
        Stream work dispatched here overlaps the generation stage, so it is
        NOT added to the stage timing buckets."""
        progressed = False
        for node in graph.nodes:
            if not node.stream or node.layout is not None:
                continue
            eff = self._effective(expected)
            if (eff is not None
                    and self._run.counts.get(node.name, 0) >= eff):
                continue
            if not self._peek(node, ctx):
                continue
            idxs = self._available(node, ctx)   # the real, counted request
            key = (node.name, frozenset(idxs))
            if not idxs or key in seen:
                continue
            seen.add(key)
            self._run.trace.append((node.name, tuple(idxs)))
            self._dispatch(node, idxs, ctx, round_=self._run.rounds,
                           fused=True, stream=True)
            progressed = True
        return progressed

    # -- main loop ----------------------------------------------------------
    def run(self, graph: RLGraph, ctx, *, expected: int | None = None
            ) -> GraphRun:
        """Execute ``graph`` until quiescent.

        ``expected``: samples each stage must consume this iteration (barrier
        semantics for non-stream nodes); None makes every node greedy — it
        fires on whatever is ready, but a greedy NON-stream node dispatches
        at most once per run (one quantum per iteration: partial rollout's
        generation node must not re-run on the samples it left unfinished).
        """
        from concurrent.futures import ThreadPoolExecutor

        with self.lock:
            missing = [s for s in graph.states()
                       if s not in self.dock.controllers]
        if missing:
            raise ValueError(f"dock has no controllers for graph states "
                             f"{missing} — build the dock from graph.states()")
        self._run = run = GraphRun()
        run.counts = {n.name: 0 for n in graph.nodes}
        producers = {f: n.name for n in graph.nodes for f in n.outputs}
        self._layout = "update"
        self._stash = None
        seen: set = set()
        dispatched: set = set()       # nodes that ran at least once this run
        try:
            while True:
                runnable = []
                eff = self._effective(expected)
                for node in graph.nodes:
                    if eff is not None and run.counts[node.name] >= eff:
                        continue
                    if (expected is None and not node.stream
                            and node.name in dispatched):
                        continue      # greedy quantum: once per run
                    idxs = self._available(node, ctx)
                    if not idxs:
                        continue
                    key = (node.name, frozenset(idxs))
                    if key in seen:
                        continue      # no progress since last identical try
                    if (eff is not None and not node.stream
                            and run.counts[node.name] + len(idxs) < eff):
                        continue      # barrier: wait for the full batch
                    runnable.append((node, idxs))
                if not runnable:
                    break
                # producer deferral: a node whose input-producer is also
                # runnable this round would fire on a partial view of the
                # producer's output (greedy non-stream nodes fire only once
                # per run, so samples the producer emits later would strand
                # until next iteration — and WHICH samples would depend on
                # streaming poll timing).  Defer the consumer; it fires next
                # round once the producer quiesces.  A topologically minimal
                # runnable node is never deferred, so progress is guaranteed;
                # barrier (expected) rounds are unaffected — a consumer only
                # becomes runnable there after its producer fully ran.
                ready_names = {n.name for n, _ in runnable}
                runnable = [(n, i) for n, i in runnable
                            if not any(producers.get(f) in ready_names
                                       and producers[f] != n.name
                                       for f in n.inputs)]
                run.rounds += 1
                # nodes that agree on a layout dispatch together; the first
                # declared layout requirement picks the round's layout
                want = next((n.layout for n, _ in runnable if n.layout), None)
                batch = ([(n, i) for n, i in runnable
                          if n.layout in (None, want)]
                         if want else runnable)
                if want is not None:
                    self._ensure_layout(ctx, want)
                for node, idxs in batch:
                    seen.add((node.name, frozenset(idxs)))
                    dispatched.add(node.name)
                    run.trace.append((node.name, tuple(idxs)))
                # stage timing is the round's WALL time (fused stages
                # overlap, so their round costs max, not sum — that is the
                # Table 2 speedup Eq. 5 throughput should see), attributed
                # to the round's leading timing bucket
                t0 = time.perf_counter()
                fused = len(batch) > 1
                if (want == "generation" and self._streaming(ctx, graph)):
                    # generation drains in a worker thread; the scheduler
                    # thread polls the metadata plane and starts stream
                    # nodes at sample granularity as on_finish puts land
                    with ThreadPoolExecutor(max_workers=len(batch)) as ex:
                        futs = [ex.submit(self._dispatch, n, i, ctx,
                                          round_=run.rounds, fused=True)
                                for n, i in batch]
                        while not all(f.done() for f in futs):
                            if not self._poll_stream(graph, ctx, expected,
                                                     seen):
                                time.sleep(0.001)
                        for f in futs:
                            f.result()
                elif fused and self.rl.stage_fusion:
                    # stage fusion as a scheduling property: independent
                    # ready nodes run concurrently (paper Table 2)
                    with ThreadPoolExecutor(max_workers=len(batch)) as ex:
                        futs = [ex.submit(self._dispatch, n, i, ctx,
                                          round_=run.rounds, fused=True)
                                for n, i in batch]
                        for f in futs:
                            f.result()
                else:
                    for node, idxs in batch:
                        self._dispatch(node, idxs, ctx, round_=run.rounds,
                                       fused=fused)
                run.stage_times[batch[0][0].timing] += (
                    time.perf_counter() - t0)
        finally:
            # the run always hands the update-layout weights back
            self._ensure_layout(ctx, "update")
        return run


# ---------------------------------------------------------------------------
# group gating helper shared by GRPO-family graphs
# ---------------------------------------------------------------------------

def complete_groups(idxs, group_size: int) -> list:
    """Keep only samples whose FULL group (idx // group_size) is present —
    the readiness gate that lets partial rollout update on complete GRPO
    groups while the rest wait in the warehouses."""
    by_group: dict[int, list] = {}
    for i in idxs:
        by_group.setdefault(int(i) // group_size, []).append(int(i))
    out: list[int] = []
    for gid in sorted(by_group):
        members = by_group[gid]
        if len(members) == group_size:
            out.extend(sorted(members))
    return out


def derive_nodes(base: RLGraph, overrides: dict) -> list:
    """Copy a graph's nodes with per-node field overrides — algorithm
    variants re-declare only what differs instead of duplicating the whole
    topology (PPO and partial rollout are edits of the GRPO graph)."""
    unknown = set(overrides) - {n.name for n in base.nodes}
    if unknown:
        raise ValueError(f"derive_nodes: {sorted(unknown)} not in graph "
                         f"{base.name!r}")
    return [dataclasses.replace(n, **overrides.get(n.name, {}))
            for n in base.nodes]
