"""Resharding flow: update-stage layout <-> generation-stage layout.

Implements the paper's two strategies:

  * ``naive_reshard``   — Figure 3 baseline: materialize the generation-layout
    weights while the update-layout weights are still resident, leaving the
    update buffers on device for the whole generation stage (redundant
    memory R of Eq. 3 == the entire per-device update partition).

  * ``allgather_swap``  — Figure 5: (1) temp-buffer allgather of the update
    weights, (2) slice-select the generation shard, (3) swap the update
    weights D2H into ``pinned_host`` memory (fully releasing device memory
    for the KV cache), (4) free the temp buffer.  Before the next update the
    weights are swapped H2D (overlappable with the inference stage).

The D2H/H2D path is the native ``memory_kind="pinned_host"``, which both the
TPU and the CPU backends expose, so the identical code runs everywhere.
Every step is recorded in a ``ReshardLedger`` (per-device bytes + modeled
durations with the paper's 50 GB/s H2D bandwidth), which benchmarks use to
reproduce Figure 10.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# size accounting
# ---------------------------------------------------------------------------

def _axis_size(mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def per_device_bytes(leaf, spec: P, mesh) -> int:
    """Bytes of one device's shard (ceil for uneven sharding)."""
    shape = list(leaf.shape)
    for i, ax in enumerate(spec):
        n = _axis_size(mesh, ax)
        shape[i] = -(-shape[i] // n)
    n = int(np.prod(shape)) if shape else 1
    return n * jnp.dtype(leaf.dtype).itemsize


def tree_device_bytes(tree, specs, mesh) -> int:
    total = 0
    leaves = jax.tree.leaves(tree)
    specl = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(leaves, specl):
        total += per_device_bytes(leaf, spec, mesh)
    return total


def tree_global_bytes(tree) -> int:
    return sum(l.size * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------

@dataclass
class ReshardLedger:
    """Per-device memory timeline + modeled durations of one reshard."""
    events: list = field(default_factory=list)   # (label, device_bytes_delta)
    d2h_bytes: int = 0
    h2d_bytes: int = 0
    gathered_bytes: int = 0
    h2d_bw: float = 50e9
    wall_s: float = 0.0

    def log(self, label: str, delta: int):
        self.events.append((label, int(delta)))

    def timeline(self) -> list:
        """(label, cumulative per-device bytes) after each event."""
        out, cur = [], 0
        for label, d in self.events:
            cur += d
            out.append((label, cur))
        return out

    @property
    def peak_bytes(self) -> int:
        return max((b for _, b in self.timeline()), default=0)

    @property
    def swap_time_s(self) -> float:
        return (self.d2h_bytes + self.h2d_bytes) / self.h2d_bw

    def snapshot(self) -> dict:
        return {
            "timeline": self.timeline(),
            "peak_device_bytes": self.peak_bytes,
            "d2h_bytes": self.d2h_bytes,
            "h2d_bytes": self.h2d_bytes,
            "modeled_swap_time_s": self.swap_time_s,
            "wall_s": self.wall_s,
        }


# ---------------------------------------------------------------------------
# resharder
# ---------------------------------------------------------------------------

def _host_sharding(sh: NamedSharding) -> NamedSharding:
    return NamedSharding(sh.mesh, sh.spec, memory_kind="pinned_host")


class Resharder:
    """Moves the actor weights between the two stage layouts."""

    def __init__(self, mesh, train_specs, gen_specs, *,
                 use_swap: bool = True, paper_two_step: bool = False):
        self.mesh = mesh
        self.train_specs = train_specs
        self.gen_specs = gen_specs
        self.train_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), train_specs,
            is_leaf=lambda x: isinstance(x, P))
        self.gen_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), gen_specs,
            is_leaf=lambda x: isinstance(x, P))
        self.use_swap = use_swap
        self.paper_two_step = paper_two_step

    # -- generation direction -------------------------------------------------
    def to_generation(self, params):
        """Returns (gen_params, stash, ledger).  ``stash`` holds the update
        weights off the device (``pinned_host`` memory) and is consumed by
        ``to_update``."""
        led = ReshardLedger()
        t0 = time.perf_counter()
        mesh = self.mesh
        upd_dev = tree_device_bytes(params, self.train_specs, mesh)
        led.log("update weights resident", upd_dev)

        if self.paper_two_step:
            # Figure 5 steps 1-2 literally: full allgather temp, then select.
            repl = jax.tree.map(
                lambda l: jax.device_put(l, NamedSharding(
                    mesh, P(*([None] * l.ndim)))), params)
            temp = tree_device_bytes(repl, jax.tree.map(
                lambda l: P(*([None] * l.ndim)), params,
                is_leaf=lambda x: hasattr(x, "ndim")), mesh)
            led.log("temp allgather buffer", temp)
            led.gathered_bytes = temp
            gen = jax.device_put(repl, self.gen_shardings)
            led.log("generation slices selected",
                    tree_device_bytes(gen, self.gen_specs, mesh))
            del repl
            led.log("temp buffer freed", -temp)
        else:
            # fused gather+select (XLA emits the minimal collective)
            gen = jax.device_put(params, self.gen_shardings)
            gb = tree_device_bytes(gen, self.gen_specs, mesh)
            led.gathered_bytes = gb
            led.log("generation layout materialized", gb)

        if self.use_swap:
            host = jax.tree.map(
                lambda l, sh: jax.device_put(l, _host_sharding(sh)),
                params, self.train_shardings)
            led.d2h_bytes = tree_device_bytes(params, self.train_specs, mesh)
            jax.block_until_ready(jax.tree.leaves(gen))
            led.log("update weights swapped D2H", -upd_dev)
            stash = ("host", host)
        else:
            # naive: update weights stay resident for the whole generation
            stash = ("device", params)
        led.wall_s = time.perf_counter() - t0
        return gen, stash, led

    # -- update direction ------------------------------------------------------
    def to_update(self, stash, ledger: ReshardLedger | None = None):
        """H2D swap back (overlap with inference by calling early — JAX
        dispatch is async)."""
        kind, host = stash
        led = ledger or ReshardLedger()
        t0 = time.perf_counter()
        if kind == "device":
            return host, led
        params = jax.tree.map(
            lambda l, sh: jax.device_put(l, sh), host, self.train_shardings)
        led.h2d_bytes = tree_device_bytes(params, self.train_specs, self.mesh)
        led.log("update weights swapped H2D",
                tree_device_bytes(params, self.train_specs, self.mesh))
        led.wall_s += time.perf_counter() - t0
        return params, led

    # -- analytics -------------------------------------------------------------
    def redundancy_bytes(self, params) -> int:
        """Eq. (3): device bytes the NAIVE flow wastes during generation —
        the whole per-device update partition that allgather-swap releases."""
        return tree_device_bytes(params, self.train_specs, self.mesh)


def naive_reshard(mesh, params, gen_specs):
    """Baseline: reshard keeping update weights resident."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), gen_specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(params, shardings)
