"""PPO trainer — actor-critic RLHF declared over the same dataflow graph.

Differences from GRPO (`trainer.py`) are pure graph edits: the inference
node also emits critic values, and the advantage node is token-level GAE
over KL-shaped rewards (plus the PF-PPO rank filtration) instead of group
z-scores.  The executor, dock and resharder are untouched — the dataflow
layer is algorithm-agnostic, which is the point of the paper's
architecture (Fig. 6): a new algorithm is a new ``RLGraph``, not a new
trainer loop.  All sample movement routes through the dock's metadata
plane (``request_metadata``/``mark_consumed``), so the dispatch ledger
sees PPO traffic exactly like GRPO traffic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RLConfig
from repro.core import ppo
from repro.core.graph import RLGraph, derive_nodes
from repro.core.trainer import GRPOTrainer, build_grpo_graph


def build_ppo_graph(actor_node: int = 0, ref_node: int = 1,
                    reward_node: int = 2) -> RLGraph:
    """PPO as a graph EDIT of GRPO: the inference node also emits critic
    values, the advantage node is GAE shaping, the update is the PPO step —
    generation/ref/reward and the topology are inherited."""
    T = PPOTrainer
    base = build_grpo_graph(actor_node, ref_node, reward_node)
    return RLGraph("ppo", derive_nodes(base, {
        "actor_inference": dict(outputs=("old_logp", "values"),
                                fn=T._stage_infer_values),
        "advantages": dict(node=actor_node,
                           inputs=("response_mask", "old_logp", "ref_logp",
                                   "values", "rewards"),
                           outputs=("advantages_tok", "returns",
                                    "values_pad"),
                           fn=T._stage_gae),
        "actor_update": dict(inputs=("tokens", "response_mask", "old_logp",
                                     "values_pad", "advantages_tok",
                                     "returns"),
                             fn=T._stage_ppo_update),
    }))


class PPOTrainer(GRPOTrainer):
    def __init__(self, cfg: ModelConfig, rl: RLConfig, dataset, *,
                 pf_filter: bool = False, **kw):
        rl = rl.replace(algorithm="ppo")
        self.pf = pf_filter
        super().__init__(cfg, rl, dataset, **kw)
        key = jax.random.PRNGKey(kw.get("seed", 0) + 17)
        # the optimizer state and the resharder must carry the value head
        self._init_state(lambda p, k: ppo.add_value_head(p, cfg, k),
                         self.params, key)
        self.train_step = jax.jit(ppo.make_train_step(cfg, rl),
                                  donate_argnums=(0, 1))
        self._values = jax.jit(self._values_impl)

    def _build_graph(self) -> RLGraph:
        return build_ppo_graph(self.actor.node, self.ref.node,
                               self.reward.node)

    def _values_impl(self, params, batch):
        return ppo.value_forward(params, self.cfg, batch)

    # -- PPO samples one response per prompt (no group repeat) ------------
    def _enqueue(self, global_batch: int) -> int:
        G = global_batch
        prompts, plens, metas = self.dataset.sample(G)
        self._plen = prompts.shape[1]
        self._metas = dict(enumerate(metas))
        self.dock.put("prompt", list(range(G)), prompts,
                      src_node=self.actor.node)
        return G

    # -- stage callables ---------------------------------------------------
    def _stage_infer_values(self, io):
        toks = io.ins["tokens"]
        old_logp = self.actor.old_logprobs(self.params, toks)
        values = np.asarray(
            self._values(self.params, {"tokens": jnp.asarray(toks)}),
            np.float32)
        return {"old_logp": old_logp, "values": values}

    def _stage_gae(self, io):
        """Token-level shaped rewards (-kl per token + terminal task reward)
        -> GAE advantages/returns, optionally PF-PPO filtered."""
        rl = self.rl
        G = len(io.idxs)
        mask = io.ins["response_mask"]
        old_logp = io.ins["old_logp"]
        ref_logp = io.ins["ref_logp"]
        values = io.ins["values"]
        rewards = io.ins["rewards"][:, 0]
        self._it["rewards_arr"] = rewards

        kl = old_logp - ref_logp                           # (G, S-1)
        tok_rewards = -rl.kl_coef * kl
        m = mask[:, 1:]
        last = np.maximum(m.cumsum(1).argmax(1), 0)
        tok_rewards[np.arange(G), last] += rewards
        adv, ret = ppo.gae(jnp.asarray(tok_rewards),
                           jnp.asarray(values[:, 1:] * m),
                           jnp.asarray(m), rl.gamma, rl.gae_lambda)
        adv = np.asarray(adv)
        if self.pf:
            w = np.asarray(ppo.pf_filter(jnp.asarray(rewards)))
            adv = adv * w[:, None]
        pad = lambda a: np.concatenate(                    # noqa: E731
            [np.zeros((G, 1), np.float32), a], axis=1)
        self._it["kl_stat"] = float(np.mean(np.abs(kl * m)))
        return {"advantages_tok": pad(adv),
                "returns": pad(np.asarray(ret)),
                "values_pad": pad(np.asarray(values[:, 1:]))}

    def _stage_ppo_update(self, io):
        ins = io.ins
        tb = {
            "tokens": jnp.asarray(ins["tokens"]),
            "response_mask": jnp.asarray(ins["response_mask"]),
            "old_logp": jnp.asarray(ins["old_logp"]),
            "values": jnp.asarray(ins["values_pad"]),
            "old_values": jnp.asarray(ins["values_pad"]),
            "advantages_tok": jnp.asarray(ins["advantages_tok"]),
            "returns": jnp.asarray(ins["returns"]),
        }
        self.params, self.opt_state, metrics = self.train_step(
            self.params, self.opt_state, tb)
        self._it["losses"].append(float(metrics["loss"]))
        return None
