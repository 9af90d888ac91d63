"""Persistent compilation cache for the entry points.

A run finds only the entries written to the directory it reads, so the
directory must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads the variable itself and nothing here overrides it), and
otherwise the fixed ``.jax_cache`` directory at the root of the checkout
(listed in ``.gitignore``).  Entry points call ``use_compile_cache()`` from
their ``main``, before the first compile; importing this module sets
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
