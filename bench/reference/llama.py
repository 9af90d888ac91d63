"""Plain reference of the Llama/Qwen2 decoder (Yi-6B, Qwen2.5-7B).

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, with no kernel,
cache, batching trick or sharding rule of the program under test; it
imports nothing of it.  It follows the published architecture:

    x = embed[tokens]
    for each layer:
        h = rmsnorm(x) * ln1;  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
        rotate-half RoPE on q and k at positions 0..S-1 (theta from config)
        causal grouped-query softmax attention, scale 1/sqrt(head_dim)
        x = x + attn Wo
        h = rmsnorm(x) * ln2;  x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * ln_f) lm_head

Weights are stored in the configuration's type (bfloat16) and widened to
float32 where they are used.  ``quant="fp8"`` is the control: every matrix
product, attention's included, takes both operands through float8 e4m3
with one scale per tensor, the precision step below bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@jax.custom_vjp
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


# the backward passes the cotangent through unrounded (straight-through):
# the control computes its products from float8 operands, forward and
# backward, while its gradients are carried in float32 as the program's are
_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def _q(x, quant):
    x = x.astype(jnp.float32)
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown precision {quant!r}")
    return _fp8(x)


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, H, D); rotate-half convention."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant):
    """q: (B, S, H, D); k, v: (B, S, KV, D); causal."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k, quant) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return _mm("bhqk,bkhd->bqhd", p, v, quant)


def hidden(w: dict, c: dict, tokens, quant=None):
    """Final normed hidden states (B, S, d_model), float32."""
    heads, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim", c["hidden_size"] // heads)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    b, s = tokens.shape
    x = w["embed"][tokens].astype(jnp.float32)
    lw = w["layers"]
    for i in range(c["num_hidden_layers"]):
        a = jax.tree.map(lambda t: t[i], lw["attn"])
        m = jax.tree.map(lambda t: t[i], lw["mlp"])
        h = _rmsnorm(x, lw["ln1"]["scale"][i], eps)
        q = _mm("bsd,dk->bsk", h, a["wq"], quant)
        k = _mm("bsd,dk->bsk", h, a["wk"], quant)
        v = _mm("bsd,dk->bsk", h, a["wv"], quant)
        if "bq" in a:
            q = q + a["bq"].astype(jnp.float32)
            k = k + a["bk"].astype(jnp.float32)
            v = v + a["bv"].astype(jnp.float32)
        q = _rope(q.reshape(b, s, heads, hd), theta)
        k = _rope(k.reshape(b, s, kvh, hd), theta)
        v = v.reshape(b, s, kvh, hd)
        y = _attention(q, k, v, quant).reshape(b, s, heads * hd)
        x = x + _mm("bsk,kd->bsd", y, a["wo"], quant)
        h = _rmsnorm(x, lw["ln2"]["scale"][i], eps)
        gate = _mm("bsd,df->bsf", h, m["w_gate"], quant)
        up = _mm("bsd,df->bsf", h, m["w_up"], quant)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["w_down"], quant)
    return _rmsnorm(x, w["ln_f"]["scale"], eps)


def token_logp(w: dict, c: dict, tokens, quant=None):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for t < S-1: (B, S-1)."""
    x = hidden(w, c, tokens, quant)[:, :-1]
    logits = _mm("bsd,dv->bsv", x, w["lm_head"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return tgt - lse
