"""The comparisons that decide ``correct``: each number is a gap between
what the timed path produced and the plain reference, held to its limit."""
from __future__ import annotations

import numpy as np

# leaves whose reference gradient is under this share of the median
# leaf's are left out of the change comparison: Adam moves them by
# round-off alone
NEGLIGIBLE_GRAD = 1e-3


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> float:
    """max over leaves of |prog - ref| / max(ref, median leaf of ref)."""
    names = sorted(ref if leaves is None else leaves)
    med = float(np.median([ref[n] for n in ref]))
    gaps = [abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in names]
    return float(max(gaps)) if gaps else 0.0


def moving_leaves(ref_grad: dict) -> list:
    med = float(np.median(list(ref_grad.values())))
    return [n for n, v in ref_grad.items() if v >= NEGLIGIBLE_GRAD * med]


def widest_gap(pairs) -> float:
    """pairs of arrays (program, reference); the widest |difference|."""
    w = 0.0
    for a, b in pairs:
        d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
        if d.size:
            if not np.all(np.isfinite(d)):
                return float("inf")
            w = max(w, float(d.max()))
    return w


def held(readings: dict, limits: dict) -> list[dict]:
    """One entry per limited number, in the limits' order."""
    out = []
    for name, limit in limits.items():
        v = float(readings[name])
        out.append({"name": name, "value": v, "limit": float(limit),
                    "ok": bool(np.isfinite(v) and v <= limit)})
    return out
