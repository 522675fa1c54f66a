"""Step-size schedules: one resolver for every driver entry point.

Historically ``sassmm.run`` took a callable ``t -> gamma_t`` (1-indexed)
while ``fedmm.run`` took either a callable or a sequence indexed from 0 —
so the same experiment written against the two entry points could silently
run different schedules. ``resolve_schedule`` is the single normalization
point: every run loop (and every shim kept for the legacy modules) accepts
a callable, a sequence/array, or a scalar, and materializes the same
float32 array ``gammas[t] = gamma_{t+1}`` for rounds t = 0..n_rounds-1.
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

Schedule = Union[callable, Sequence, float]


def resolve_schedule(gammas: Schedule, n_rounds: int) -> jnp.ndarray:
    """Materialize a step-size schedule as a float32 array of shape
    ``(n_rounds,)`` — one SCALAR gamma per round, validated eagerly.

    * callable: gamma_t at t = 1..n_rounds (the paper's 1-indexed
      convention, matching the legacy ``gammas(t + 1)`` call sites). It is
      first called ONCE, eagerly, on a host NumPy vector of the round
      indices ``np.arange(1, n_rounds + 1)``, so ``beta + t`` stays float64
      host arithmetic exactly as with a Python int ``t``, and each
      primitive runs unfused as in a scalar call. That vector is kept only
      if it has shape ``(n_rounds,)`` and scalar calls at ``t = 1`` and
      ``t = n_rounds`` return 0-d values with the same float32 bits as its
      ends. Otherwise (the vector call raised, e.g. a Python branch on
      ``t`` or ``math.sqrt``; the shape or an end differs) the callable is
      evaluated per round, one scalar call each. Records the
      ``jax.monitoring`` event ``/fedmm/schedule/vectorized`` or
      ``/fedmm/schedule/per_round``. The probes see only the ends: a
      callable whose vector and scalar values agree there and differ only
      inside is taken at its vector values;
    * sequence/array: the first ``n_rounds`` entries (must be long enough);
    * python scalar: a constant schedule.

    Every consumer indexes the resolved array by a (possibly traced) round
    counter — ``gammas[t]`` under jit CLAMPS out-of-range indices to the
    last entry instead of raising, so a short or wrongly-shaped schedule
    would silently replay its last gamma (or broadcast a vector gamma into
    the server update). Both are rejected HERE, at resolution time, where
    the shapes are still static and the error can name the problem.
    """
    if callable(gammas):
        vals = _vectorized(gammas, n_rounds)
        if vals is not None:
            jax.monitoring.record_event("/fedmm/schedule/vectorized")
            return vals
        jax.monitoring.record_event("/fedmm/schedule/per_round")
        vals = [jnp.asarray(gammas(t + 1), jnp.float32)
                for t in range(n_rounds)]
        bad = [v.shape for v in vals if v.ndim != 0]
        if bad:
            raise ValueError(
                f"callable schedule must return a scalar gamma per round, "
                f"got array shape(s) {sorted(set(bad))} — a non-scalar "
                f"gamma would silently broadcast into the server update")
        return jnp.stack(vals) if vals else jnp.zeros((0,), jnp.float32)
    arr = jnp.asarray(gammas, jnp.float32)
    if arr.ndim == 0:
        return jnp.full((n_rounds,), arr)
    if arr.ndim > 1:
        raise ValueError(
            f"schedule must be a 1-D array of per-round scalar gammas, got "
            f"shape {tuple(arr.shape)} — a {arr.ndim}-D schedule would "
            f"silently broadcast vector gammas into the server update")
    if arr.shape[0] < n_rounds:
        raise ValueError(
            f"schedule has {arr.shape[0]} entries < n_rounds={n_rounds} — "
            f"indexing it by round under jit would silently clamp to the "
            f"last entry instead of raising")
    return arr[:n_rounds]


def _vectorized(gamma, n_rounds: int) -> jnp.ndarray | None:
    """``gamma`` at rounds 1..n_rounds from one call on a host vector, or
    None where that call raises or disagrees with scalar calls at the
    ends (``resolve_schedule``'s contract)."""
    if n_rounds < 1:
        return None
    try:
        vals = jnp.asarray(gamma(np.arange(1, n_rounds + 1)), jnp.float32)
        if vals.shape != (n_rounds,):
            return None
        ends = [jnp.asarray(gamma(t), jnp.float32) for t in (1, n_rounds)]
    except Exception:  # the callable is not vectorisable; go per round
        return None
    if any(e.ndim != 0 for e in ends):
        return None
    host, first, last = jax.device_get((vals, *ends))
    got = host[[0, -1]].view(np.uint32)
    want = np.array([first, last], np.float32).view(np.uint32)
    return vals if np.array_equal(got, want) else None


def decaying_stepsize(beta: float):
    """gamma_t = beta / sqrt(beta + t) — the schedule used in Section 6.
    (Canonical home; ``core.sassmm.decaying_stepsize`` is an alias.)"""
    def gamma(t):
        return beta / jnp.sqrt(beta + t)
    return gamma


def schedule_length(gammas: Schedule) -> int | None:
    """Length of an array schedule, or None for callables/scalars (used to
    infer ``n_rounds`` when the caller omits it)."""
    if callable(gammas):
        return None
    try:
        return len(gammas)
    except TypeError:
        return None
