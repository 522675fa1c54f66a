"""The numbers that decide ``correct``: gaps between what the timed path
produced and what a plain reference computes for the same inputs.

Every reading is a non-negative gap; a run is correct when each reading is
at or under its limit (the cell's workload file states the limits, and
``PERF.md`` the readings each was set from).
"""
from __future__ import annotations

import math

import numpy as np


def rel_gap(a: float, b: float) -> float:
    """|a - b| / |b|; a non-finite ``a`` reads as infinitely far."""
    a, b = float(a), float(b)
    if not math.isfinite(a):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)


def max_rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return math.inf
    if not np.isfinite(a).all():
        return math.inf
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def leaf_norm_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """The worst leaf's gap between two sets of per-leaf norms: the gap of
    the norms (not the norm of the difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. ``keep`` optionally names the leaves that count. Returns
    ``(gap, leaf)``."""
    names = [k for k in ref if keep is None or k in keep]
    if set(prog) != set(ref):
        return math.inf, "leaf set differs"
    med = float(np.median([ref[k] for k in names])) if names else 0.0
    worst, where = 0.0, ""
    for k in names:
        p = float(prog[k])
        if not math.isfinite(p):
            return math.inf, k
        g = abs(p - float(ref[k])) / max(float(ref[k]), med, 1e-30)
        if g > worst:
            worst, where = g, k
    return worst, where


def moved_leaves(grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is above ``share`` of the median
    leaf's: the others move by round-off alone and are left out of the
    change after several steps."""
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if float(v) >= share * med}


def verdict(readings: dict, limits: dict) -> list:
    """[(name, value, limit)] for every limit, in the limits' order; a
    reading that is missing counts as infinitely far."""
    return [(k, float(readings.get(k, math.inf)), float(lim))
            for k, lim in limits.items()]
