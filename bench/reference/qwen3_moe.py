"""Plain reference of the Qwen3-MoE decoder (Qwen3-30B-A3B).

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, with no kernel,
cache, batching trick, sort or sharding rule of the program under test; it
imports nothing of it.  It follows the published layer
(``Qwen3MoeDecoderLayer``):

    x = embed[tokens]
    for each layer:
        h = rmsnorm(x) * ln1;  q, k, v = h Wq, h Wk, h Wv
        q, k = rmsnorm over head_dim of each head * q_norm, * k_norm
        rotate-half RoPE on q and k at positions 0..S-1 (theta from config)
        causal grouped-query softmax attention, scale 1/sqrt(head_dim)
        x = x + attn Wo
        h = rmsnorm(x) * ln2
        p = softmax(h Wrouter) over the router's experts; the top k of p,
            their gates renormalised to sum to 1
        x = x + sum over the picks e held here of
                gate_e * (silu(h Wgate_e) * (h Wup_e)) Wdown_e
    logits = (rmsnorm(x) * ln_f) lm_head

Departures from the published model, both the deployment's cut (the
configuration's ``reduced``): 12 of 48 layers, and of each layer's 128
experts the 16 this chip holds, from ``experts_held_from``.  The router
keeps its 128 outputs and 8 picks; a pick of an expert not held adds
nothing here (its chip adds it), as in the program.  Every held expert is
computed for every token, and the picks select among them.

Weights are stored in the configuration's type (bfloat16) and widened to
float32 where they are used.  ``quant="fp8"`` is the control: every matrix
product, the router's and attention's included, takes both operands
through float8 e4m3 with one scale per tensor, the precision step below
bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import llama as base


def qk_norm(x, scale, eps):
    """RMSNorm over the last axis (head_dim) of each head."""
    return base._rmsnorm(x, scale, eps)


def gates(probs, k: int):
    """The top ``k`` probabilities, renormalised to sum to 1, and their
    expert ids."""
    top, idx = jax.lax.top_k(probs, k)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


def experts(h, m, c, quant):
    """The held experts' share of the expert FFN at ``h`` (B, S, d)."""
    first, n = c.get("experts_held_from", 0), c["num_experts"]
    logits = base._mm("bsd,de->bse", h, m["router"], quant)
    g, idx = gates(jax.nn.softmax(logits, axis=-1), c["num_experts_per_tok"])
    # weight of each held expert at each token: its gate where picked
    held = jnp.arange(first, first + n)
    wt = jnp.sum(jnp.where(idx[..., None] == held, g[..., None], 0.0),
                 axis=-2)                                   # (B, S, n)
    gate = base._mm("bsd,edf->bsef", h, m["w_gate"], quant)
    up = base._mm("bsd,edf->bsef", h, m["w_up"], quant)
    out = base._mm("bsef,efd->bsed", jax.nn.silu(gate) * up, m["w_down"],
                   quant)
    return jnp.einsum("bsed,bse->bsd", out, wt,
                      precision=jax.lax.Precision.HIGHEST)


def hidden(w: dict, c: dict, tokens, quant=None):
    """Final normed hidden states (B, S, d_model), float32."""
    heads, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    b, s = tokens.shape
    x = w["embed"][tokens].astype(jnp.float32)
    lw = w["layers"]
    for i in range(c["num_hidden_layers"]):
        lay = jax.tree.map(lambda t: t[i], lw)
        a = lay["attn"]
        h = base._rmsnorm(x, lay["ln1"]["scale"], eps)
        q = base._mm("bsd,dk->bsk", h, a["wq"], quant)
        k = base._mm("bsd,dk->bsk", h, a["wk"], quant)
        v = base._mm("bsd,dk->bsk", h, a["wv"], quant)
        q = qk_norm(q.reshape(b, s, heads, hd), a["q_norm"]["scale"], eps)
        k = qk_norm(k.reshape(b, s, kvh, hd), a["k_norm"]["scale"], eps)
        q, k = base._rope(q, theta), base._rope(k, theta)
        y = base._attention(q, k, v.reshape(b, s, kvh, hd), quant)
        x = x + base._mm("bsk,kd->bsd", y.reshape(b, s, heads * hd), a["wo"],
                         quant)
        x = x + experts(base._rmsnorm(x, lay["ln2"]["scale"], eps),
                        lay["moe"], c, quant)
    return base._rmsnorm(x, w["ln_f"]["scale"], eps)


def token_logp(w: dict, c: dict, tokens, quant=None):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for t < S-1: (B, S-1)."""
    x = hidden(w, c, tokens, quant)[:, :-1]
    logits = base._mm("bsd,dv->bsv", x, w["lm_head"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return tgt - lse
