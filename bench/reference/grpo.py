"""Plain reference of the GRPO update as the cell states it: the same
objective and optimizer, followed step by step over the samples the
program generated, in float32 at ``Precision.HIGHEST``.

Per iteration: rewards from the benchmark's task, group-relative
advantages ``(r - mean) / (std + 1e-6)`` over each prompt's N samples;
log-probabilities of the response tokens under the current weights (old)
and the initial ones (reference); the loss

    mean over response tokens of  -min(ratio * A, clip(ratio) * A)
                                  + kl_coef * (exp(d) - d - 1),
    ratio = exp(logp - old),  d = ref - logp,

and one AdamW step.  The weights are kept in the configuration's type
(bfloat16) between steps, as the configuration states: the gradient with
respect to them is rounded to that type, optionally clipped by its global
norm, and Adam's moments are float32.  Adam's moments are recomputed from
the kept gradients at each step (the same float32 arithmetic in the same
order), so the memory held is the weights and at most three gradients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import harness


def advantages(rewards: np.ndarray, n: int) -> np.ndarray:
    r = np.asarray(rewards, np.float32).reshape(-1, n)
    mean = r.mean(axis=1, keepdims=True)
    std = r.std(axis=1, keepdims=True)
    return ((r - mean) / (std + np.float32(1e-6))).reshape(-1)


def response_mask(tokens: np.ndarray, prompt_width: int, eos: int):
    """1 on the generated tokens up to and including the first EOS."""
    b, s = tokens.shape
    mask = np.zeros((b, s), np.float32)
    for i in range(b):
        resp = tokens[i, prompt_width:]
        stop = np.nonzero(resp == eos)[0]
        n = stop[0] + 1 if len(stop) else len(resp)
        mask[i, prompt_width:prompt_width + n] = 1.0
    return mask


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


def named(tree) -> dict:
    """Leaf path -> float, for a tree of scalars on the device."""
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def leaf_norms(tree) -> dict:
    """Leaf name -> float32 L2 norm."""
    return named(_norms(tree))


def leaf_diff_norms(a, b) -> dict:
    """Leaf name -> L2 norm of a - b, in float32."""
    return named(_diff_norms(a, b))


class GRPOReference:
    def __init__(self, c: dict, rl: dict, quant=None, shard=None):
        """``rl``: kl_coef, clip_eps, lr, betas, eps, weight_decay,
        grad_clip.  ``shard(tree)`` places arrays (several chips)."""
        self.c, self.rl, self.quant = c, rl, quant
        self.model = harness.reference(c)
        self.shard = shard or (lambda t: t)
        self._logp = jax.jit(lambda w, t: self.model.token_logp(w, c, t,
                                                                quant))
        self._grad = jax.jit(jax.value_and_grad(self._loss))
        self._adam = {}

    def logp(self, w, tokens):
        with jax.default_matmul_precision("highest"):
            return self._logp(w, self.shard(jnp.asarray(tokens)))

    def _loss(self, w, tokens, mask, adv, old, ref):
        rl = self.rl
        logp = self.model.token_logp(w, self.c, tokens, self.quant)
        m = mask[:, 1:]
        ratio = jnp.exp(logp - old)
        a = adv[:, None]
        pg = -jnp.minimum(ratio * a, jnp.clip(ratio, 1 - rl["clip_eps"],
                                               1 + rl["clip_eps"]) * a)
        d = ref - logp
        kl = jnp.exp(d) - d - 1.0
        per_tok = pg + rl["kl_coef"] * kl
        return jnp.sum(per_tok * m) / jnp.maximum(jnp.sum(m), 1.0)

    def grad(self, w, tokens, mask, adv, old, ref):
        args = [self.shard(jnp.asarray(x)) for x in (tokens, mask, adv)]
        with jax.default_matmul_precision("highest"):
            loss, g = self._grad(w, *args, old, ref)
        return float(loss), g

    def _clip_scale(self, g):
        clip = self.rl["grad_clip"]
        if not clip:
            return None
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                          for x in jax.tree.leaves(g)))
        return jnp.minimum(1.0, clip / (gn + 1e-9))

    def adam(self, w, hist, scales, step: int):
        """Weights after ``step`` AdamW steps' last one, the moments
        recomputed from the kept gradients ``hist`` (bf16) and their clip
        scales."""
        b1, b2 = self.rl["betas"]
        eps, lr, wd = self.rl["eps"], self.rl["lr"], self.rl["weight_decay"]

        def upd(p, *gs):
            m = jnp.zeros(p.shape, jnp.float32)
            v = jnp.zeros(p.shape, jnp.float32)
            for g, sc in zip(gs, scales):
                g32 = g.astype(jnp.float32) if sc is None else g * sc
                m = b1 * m + (1 - b1) * g32
                v = b2 * v + (1 - b2) * g32 * g32
            t = jnp.float32(step)
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            delta = mhat / (jnp.sqrt(vhat) + eps)
            if wd:
                delta = delta + wd * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype)

        fn = jax.jit(lambda w, *hs: jax.tree.map(upd, w, *hs),
                     donate_argnums=(0,))
        return fn(w, *hist)

    def run(self, w0_fn, iterations: list[dict]) -> dict:
        """Follow the program's first iterations.  ``w0_fn()`` makes the
        initial weights (on the device); each iteration gives ``tokens``
        (B, S), ``mask`` (B, S) and ``rewards`` (B,) with ``n`` samples a
        prompt.  Returns the readings the check compares."""
        w = w0_fn()
        refs = [self.logp(w, it["tokens"]) for it in iterations]
        out = {"loss": [], "old_logp": [], "ref_logp": [],
               "grad_norms": None, "delta_norms": None}
        hist, scales = [], []
        for k, (it, ref) in enumerate(zip(iterations, refs), start=1):
            old = self.logp(w, it["tokens"])
            adv = advantages(it["rewards"], it["n"])
            loss, g = self.grad(w, it["tokens"], it["mask"], adv, old, ref)
            sc = self._clip_scale(g)
            if k == 1:
                out["grad_norms"] = leaf_norms(
                    g if sc is None else jax.tree.map(lambda x: x * sc, g))
            out["loss"].append(loss)
            out["old_logp"].append(np.asarray(old))
            out["ref_logp"].append(np.asarray(ref))
            hist.append(g)
            scales.append(sc)
            del g
            w = self.adam(w, hist, scales, k)
        del hist, refs
        out["delta_norms"] = leaf_diff_norms(w, w0_fn())
        return out
