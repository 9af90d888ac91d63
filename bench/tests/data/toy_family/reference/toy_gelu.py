"""Plain reference of the toy family (``families/toy_gelu.py``): the Llama
reference's attention, RoPE and matrix products, under LayerNorm with a
bias and a GELU FFN of two matrices (the tanh form)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import llama as base


def _layernorm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def hidden(w: dict, c: dict, tokens, quant=None):
    heads, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim", c["hidden_size"] // heads)
    eps, theta = c["norm_epsilon"], c["rope_theta"]
    b, s = tokens.shape
    x = w["embed"][tokens].astype(jnp.float32)
    lw = w["layers"]
    for i in range(c["num_hidden_layers"]):
        lay = jax.tree.map(lambda t: t[i], lw)
        a, m = lay["attn"], lay["mlp"]
        h = _layernorm(x, lay["ln1"], eps)
        q = base._mm("bsd,dk->bsk", h, a["wq"], quant)
        k = base._mm("bsd,dk->bsk", h, a["wk"], quant)
        v = base._mm("bsd,dk->bsk", h, a["wv"], quant)
        q = base._rope(q.reshape(b, s, heads, hd), theta)
        k = base._rope(k.reshape(b, s, kvh, hd), theta)
        y = base._attention(q, k, v.reshape(b, s, kvh, hd), quant)
        x = x + base._mm("bsk,kd->bsd", y.reshape(b, s, heads * hd), a["wo"],
                         quant)
        h = _layernorm(x, lay["ln2"], eps)
        up = base._mm("bsd,df->bsf", h, m["w_up"], quant)
        x = x + base._mm("bsf,fd->bsd", jax.nn.gelu(up, approximate=True),
                         m["w_down"], quant)
    return _layernorm(x, w["ln_f"], eps)


def token_logp(w: dict, c: dict, tokens, quant=None):
    x = hidden(w, c, tokens, quant)[:, :-1]
    logits = base._mm("bsd,dv->bsv", x, w["lm_head"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return tgt - lse
