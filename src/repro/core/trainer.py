"""GRPO trainer — the MindSpeed-RL iteration as a declared dataflow graph:

  generation stage  -> inference stage -> update stage
        ^                                     |
        +---- resharding flow (allgather-swap) ----+

The algorithm is DECLARED in ``build_grpo_graph`` as stage nodes over dock
fields; the shared ``GraphExecutor`` (core/graph.py) schedules any node
whose inputs are ready per the transfer-dock metadata, handles the
update<->generation weight-layout transitions that the graph's layout
edges demand, and fuses independent ready stages (ref-inference ∥ reward ∥
actor-inference) by dispatching them concurrently.  Runs for real on CPU at
smoke scale (the end-to-end examples) and is the template the launch layer
lowers at production scale.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RLConfig
from repro.core import grpo
from repro.core.graph import GraphExecutor, RLGraph, StageNode
from repro.core.resharding import Resharder
from repro.core.transfer_dock import (CentralReplayBuffer, DispatchLedger,
                                      TransferDock)
from repro.core.workers import ActorWorker, ReferenceWorker, RewardWorker
from repro.resilience import call_with_retry
from repro.data.prompts import PromptDataset
from repro.data.tokenizer import ByteTokenizer
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model
from repro.obs import Tracer, get_tracer
from repro.optim import adamw_init
from repro.optim.adamw import AdamWState
from repro.sharding import param_specs


@dataclass
class IterationStats:
    reward_mean: float
    reward_std: float
    loss: float
    kl: float
    gen_time: float
    infer_time: float
    update_time: float
    reshard: dict = field(default_factory=dict)
    dispatch: dict = field(default_factory=dict)
    trace: list = field(default_factory=list)   # executor (node, idxs) log


# ---------------------------------------------------------------------------
# graph declaration — the paper's Fig. 1 nodes/edges for GRPO/DAPO
# ---------------------------------------------------------------------------

def build_grpo_graph(actor_node: int = 0, ref_node: int = 1,
                     reward_node: int = 2) -> RLGraph:
    """GRPO as an RLGraph: generation fans out to three independent
    consumers (actor/ref inference + reward — the fusion set), rewards
    gather into group advantages, and everything joins at the update."""
    T = GRPOTrainer
    return RLGraph("grpo", [
        StageNode("actor_generation", actor_node,
                  inputs=("prompt",),
                  outputs=("tokens", "response_mask"),
                  fn=T._stage_generate, layout="generation", timing="gen"),
        StageNode("actor_inference", actor_node,
                  inputs=("tokens",), outputs=("old_logp",),
                  fn=T._stage_old_logp, layout="update"),
        StageNode("ref_inference", ref_node,
                  inputs=("tokens",), outputs=("ref_logp",),
                  fn=T._stage_ref_logp, stream=True),
        StageNode("reward", reward_node,
                  inputs=("tokens",), outputs=("rewards",),
                  fn=T._stage_reward, stream=True),
        StageNode("advantages", reward_node,
                  inputs=("rewards",), outputs=("advantages",),
                  fn=T._stage_advantages),
        StageNode("actor_update", actor_node,
                  inputs=("tokens", "response_mask", "old_logp", "ref_logp",
                          "advantages"),
                  outputs=(),
                  fn=T._stage_update, layout="update", timing="update"),
    ])


class GRPOTrainer:
    """Owns model/optimizer state and the workers; the iteration itself is
    ``self.graph`` executed by the shared ``GraphExecutor``."""

    clear_dock_each_iteration = True
    # subclasses may pin the actor's generation engine (None => honor
    # rl.rollout_engine); partial rollout pins "serving" — budgeted resume
    # is an engine capability, not a trainer loop
    actor_engine_kind: str | None = None

    def __init__(self, cfg: ModelConfig, rl: RLConfig, dataset: PromptDataset,
                 *, num_nodes: int = 4, microbatch: int = 0, seed: int = 0,
                 mesh=None, tracer=None, faults=None):
        assert cfg.vocab_size >= ByteTokenizer.vocab_size
        if rl.partial_rollout and self.clear_dock_each_iteration:
            # the flag is honored by the PartialRolloutTrainer graph (which
            # keeps dock indices across iterations); silently running plain
            # GRPO/PPO against it would be a no-op the user cannot see
            raise ValueError(
                "rl.partial_rollout=True needs PartialRolloutTrainer "
                "(core/partial.py), not " + type(self).__name__)
        self.cfg = cfg
        self.rl = rl
        self.dataset = dataset
        self.key = jax.random.PRNGKey(seed)
        self.tok = dataset.tok
        self.microbatch = microbatch
        # one tracer serves every instrumented layer (executor spans, dock
        # counter events, serving-engine steps): injected > rl.trace_path
        # (fresh enabled tracer) > the disabled process default
        self.tracer = tracer if tracer is not None else (
            Tracer(enabled=True) if rl.trace_path else get_tracer())
        self.faults = faults     # FaultPlan | None — chaos hooks everywhere
        self._iters_run = 0

        # --- model / optimizer state, in the update layout ---------------
        model = build_model(cfg)
        self.key, k = jax.random.split(self.key)
        self.mesh = mesh or make_local_mesh()
        self._init_state(lambda k: model.init(cfg, k), k)
        # genuine copy: train_step donates self.params' buffers, so the
        # frozen reference policy must own distinct ones
        self.ref_params = jax.tree.map(jnp.copy, self.params)
        self.train_step = jax.jit(grpo.make_train_step(cfg, rl),
                                  donate_argnums=(0, 1))
        self.gen_params = None   # generation-layout weights (executor-owned)

        # --- workers + graph + dock --------------------------------------
        self.actor = ActorWorker(cfg, rl, eos_id=self.tok.eos_id,
                                 pad_id=self.tok.pad_id, node=0,
                                 engine=self.actor_engine_kind,
                                 tracer=self.tracer, faults=faults)
        self.ref = ReferenceWorker(cfg, self.ref_params, node=1 % num_nodes)
        self.reward = RewardWorker(dataset, node=2 % num_nodes)
        self.graph = self._build_graph()
        ledger = DispatchLedger(internode_bw=rl.internode_bw,
                                tracer=self.tracer)
        if rl.use_transfer_dock:
            self.dock = TransferDock(min(rl.num_warehouses, num_nodes),
                                     self.graph.states(), ledger,
                                     faults=faults)
        else:
            self.dock = CentralReplayBuffer(self.graph.states(), ledger,
                                            faults=faults)
        self.executor = GraphExecutor(self.dock, rl, tracer=self.tracer,
                                      faults=faults)
        self.last_run = None

    def _init_state(self, init, *args) -> None:
        """Make the weights with ``init(*args)`` as one compiled program,
        directly in the update-stage (train) layout; build the resharder
        between the two stage layouts; create the optimizer state in the
        same layout.  On a mesh nothing is first gathered onto one device."""
        shapes = jax.eval_shape(init, *args)
        tspecs = param_specs(self.cfg, shapes, self.mesh, stage="train")
        gspecs = param_specs(self.cfg, shapes, self.mesh, stage="gen",
                             gen_mode="tp")
        self.resharder = Resharder(self.mesh, tspecs, gspecs,
                                   use_swap=self.rl.use_allgather_swap)
        shardings = self.resharder.train_shardings
        self.params = jax.jit(init, out_shardings=shardings)(*args)
        self.opt_state = jax.jit(adamw_init, out_shardings=AdamWState(
            step=NamedSharding(self.mesh, P()), mu=shardings,
            nu=shardings))(self.params)

    def _build_graph(self) -> RLGraph:
        return build_grpo_graph(self.actor.node, self.ref.node,
                                self.reward.node)

    # ------------------------------------------------------------------
    # per-iteration prompt enqueue (the graph's external field)
    # ------------------------------------------------------------------
    def _enqueue(self, global_batch: int) -> int | None:
        """Put this iteration's prompts into the dock; returns the expected
        per-stage sample count (None => greedy scheduling)."""
        G, N = global_batch, self.rl.num_generations
        total = G * N
        prompts, plens, metas = self.dataset.sample(G)
        self._plen = prompts.shape[1]
        prompts_rep = np.repeat(prompts, N, axis=0)
        self._metas = {i: metas[i // N] for i in range(total)}
        # the dock.put fault site fires at entry, before any row lands, so a
        # retried put is exactly once-effective (same rows, same idxs)
        call_with_retry(
            lambda: self.dock.put("prompt", list(range(total)), prompts_rep,
                                  src_node=self.actor.node),
            self.executor.retry)
        return total

    # ------------------------------------------------------------------
    # stage callables (the graph nodes' fns)
    # ------------------------------------------------------------------
    def _stage_generate(self, io):
        self.key, k = jax.random.split(self.key)
        pbatch = io.ins["prompt"]
        if self.actor.engine_kind == "serving":
            # continuous batching: each finished sample flows into the dock
            # the MOMENT its sequence completes, not at the batch barrier —
            # the executor sees per-sample readiness and starts stream
            # stages (ref_inference, reward) before generation drains.
            idxs = io.idxs

            def _stream(i, tokens_row, mask_row, length):
                io.put("tokens", [idxs[i]], tokens_row[None])
                io.put("response_mask", [idxs[i]], mask_row[None])

            self.actor.generate(self.gen_params, pbatch, k,
                                on_finish=_stream)
            return None
        roll = self.actor.generate(self.gen_params, pbatch, k)
        return {"tokens": roll.tokens, "response_mask": roll.response_mask}

    def _stage_old_logp(self, io):
        return {"old_logp": self.actor.old_logprobs(self.params,
                                                    io.ins["tokens"])}

    def _stage_ref_logp(self, io):
        return {"ref_logp": self.ref.logprobs(io.ins["tokens"])}

    def _stage_reward(self, io):
        rw = self.reward.score([self._metas[i] for i in io.idxs],
                               io.ins["tokens"], self._plen)
        for idx, r in zip(io.idxs, rw):
            self._it["reward_by_idx"][idx] = float(r)
        return {"rewards": np.asarray(rw)[:, None]}

    def _stage_advantages(self, io):
        N = self.rl.num_generations
        rw = io.ins["rewards"][:, 0]
        self._it["rewards_arr"] = rw
        adv = np.asarray(
            grpo.group_advantages(jnp.asarray(rw.reshape(-1, N)))
        ).reshape(-1)
        return {"advantages": adv[:, None]}

    def _stage_update(self, io):
        ins = io.ins
        n = len(io.idxs)
        mb = self.microbatch or n
        for lo in range(0, n, mb):
            sl = slice(lo, lo + mb)
            batch = {
                "tokens": jnp.asarray(ins["tokens"][sl]),
                "response_mask": jnp.asarray(ins["response_mask"][sl]),
                "old_logp": jnp.asarray(ins["old_logp"][sl]),
                "ref_logp": jnp.asarray(ins["ref_logp"][sl]),
                "advantages": jnp.asarray(ins["advantages"][sl])[:, 0],
            }
            self.params, self.opt_state, metrics = self.train_step(
                self.params, self.opt_state, batch)
            self._it["losses"].append(float(metrics["loss"]))
            self._it["kls"].append(float(metrics["kl"]))
        return None

    # ------------------------------------------------------------------
    def iteration(self, global_batch: int) -> IterationStats:
        """One RL iteration: enqueue prompts, run the graph to quiescence."""
        if self.clear_dock_each_iteration:
            self.dock.clear()
        expected = self._enqueue(global_batch)
        self._it = {"losses": [], "kls": [], "reward_by_idx": {}}
        with self.tracer.span("iteration", cat="train",
                              args={"iteration": self._iters_run,
                                    "global_batch": global_batch}):
            run = self.executor.run(self.graph, self, expected=expected)
        self._iters_run += 1
        self.last_run = run
        return self._stats(run)

    def export_trace(self, path: str | None = None) -> str:
        """Dump the tracer's Chrome-trace JSON (openable in Perfetto)."""
        path = path or self.rl.trace_path
        if path is None:
            raise ValueError("no trace path: pass one or set rl.trace_path")
        return self.tracer.export(path)

    def _stats(self, run) -> IterationStats:
        it = self._it
        rw = it.get("rewards_arr")
        if rw is None and it["reward_by_idx"]:
            rw = np.asarray([it["reward_by_idx"][i]
                             for i in sorted(it["reward_by_idx"])])
        losses, kls = it["losses"], it["kls"]
        return IterationStats(
            reward_mean=float(np.mean(rw)) if rw is not None and len(rw)
            else 0.0,
            reward_std=float(np.std(rw)) if rw is not None and len(rw)
            else 0.0,
            loss=float(np.mean(losses)) if losses else 0.0,
            kl=it.get("kl_stat",
                      float(np.mean(kls)) if kls else 0.0),
            gen_time=run.stage_times["gen"],
            infer_time=run.stage_times["infer"],
            update_time=run.stage_times["update"],
            reshard=run.reshard.snapshot(),
            dispatch=self.dock.ledger.snapshot(),
            trace=list(run.trace),
        )

    def throughput(self, stats: IterationStats, global_batch: int,
                   num_devices: int = 1) -> float:
        """Paper Eq. (5): T = G*N*(PL+SL) / ND / ETE."""
        ete = stats.gen_time + stats.infer_time + stats.update_time
        toks = (global_batch * self.rl.num_generations
                * (self.rl.max_prompt_len + self.rl.max_response_len))
        return toks / max(num_devices, 1) / max(ete, 1e-9)
