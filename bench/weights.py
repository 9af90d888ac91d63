"""Weights made from the seed, on the device, in one compiled call.

The tree has the layout the model code under test consumes (per-layer
weights stacked on a leading axis) and holds what a Hugging Face
checkpoint of the configuration holds: every matrix is drawn from a normal
of standard deviation ``initializer_range``, the attention biases too where
the architecture has them, and the norm scales are 1.  The benchmark makes
them from the seed for the program under test and again, after the
window, for the reference: the reference never reads weights the program
made or changed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import harness


def shapes(c: dict) -> dict:
    """Leaf name -> shape for the configuration ``c``, as its model family
    lays the tree out."""
    return harness.family(c).shapes(c)


def key(seed: int):
    """The weights' PRNG key: an argument of the compiled program, so that
    one program (compiled once, then loaded from the cache) serves every
    seed."""
    return jax.random.PRNGKey(seed)


def init(c: dict, key) -> dict:
    """The weights, traced: wrap in ``jax.jit`` (with the shardings the
    caller needs) so they are made on the device in one program."""
    dtype = jnp.dtype(c["torch_dtype"])
    std = c["initializer_range"]
    tree = shapes(c)
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    out = []
    for i, (path, shape) in enumerate(zip(paths, leaves)):
        if "scale" in path:
            out.append(jnp.ones(shape, dtype))
        else:
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, shape, jnp.float32) * std)
                       .astype(dtype))
    return jax.tree.unflatten(treedef, out)
