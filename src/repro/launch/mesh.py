"""Production meshes.

Single pod:  (16, 16)    over ("data", "model")        — 256 chips.
Multi-pod:   (2, 16, 16) over ("pod", "data", "model") — 512 chips.

FUNCTIONS (not module constants) so importing this module never touches
jax device state — only ``dryrun.py`` sets the 512-host-device XLA flag.

Every mesh here has Auto axes: XLA propagates shardings from the
``sharding/rules.py`` specs and the ``ops`` constraints, the mode those
rules are written for (``jax.make_mesh`` now defaults to Explicit axes).
"""
from __future__ import annotations

import math

import jax
from jax.experimental import mesh_utils
from jax.sharding import AbstractMesh, Mesh


def make_mesh(shape, axes):
    """A device mesh of ``shape`` over the first ``prod(shape)`` devices,
    laid out for the physical topology."""
    devices = jax.devices()[:math.prod(shape)]
    return Mesh(mesh_utils.create_device_mesh(shape, devices), axes)


def make_abstract_mesh(shape, axes):
    """A device-free mesh for sharding rules (dry runs, spec tests)."""
    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (CPU examples/tests)."""
    return make_mesh((1, 1), ("data", "model"))
