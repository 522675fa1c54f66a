"""``resolve_schedule``'s one vectorised call of a callable schedule.

Contracts pinned here:
  * a callable that takes a host vector of round indices gives the
    per-round loop's float32 array bit for bit, from one vector call, and
    records ``/fedmm/schedule/vectorized`` once;
  * a callable that raises on a vector, or whose vector disagrees with
    scalar calls in shape or at the ends, is evaluated per round and
    records ``/fedmm/schedule/per_round`` once; interrupts and exits
    raised by the vector call are not swallowed;
  * non-scalar gammas are rejected with the per-round messages, the
    ones the vector's shape check would pass included.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api

VECTORIZED = "/fedmm/schedule/vectorized"
PER_ROUND = "/fedmm/schedule/per_round"
BETA = 0.02

VECTORISABLE = {
    "decaying": api.decaying_stepsize(0.02),
    "bench-lambda": lambda t: BETA / jnp.sqrt(BETA + t),
    "inv-sqrt": lambda t: 0.5 / jnp.sqrt(t),
    "f32-linear": lambda t: jnp.float32(0.1) * t,
    "numpy-only": lambda t: 0.3 / np.sqrt(1.0 + t),
    "power": lambda t: 0.9 ** (t + 1),
    "jitted": jax.jit(lambda t: BETA / jnp.sqrt(BETA + t)),
}

FALLBACK = {
    "python-branch": lambda t: 0.1 if t < 5 else 0.01,
    "math-sqrt": lambda t: 0.5 / math.sqrt(t),
    # a vector of the right shape whose ends differ from the scalar calls
    "vector-ends-differ": lambda t: (np.full(np.shape(t), 0.1)
                                     if np.ndim(t) else 0.2),
}


class _Events:
    """Counts the ``/fedmm/schedule/*`` events recorded while active."""

    def __init__(self):
        self.count = {}

    def _event(self, name, **_):
        if name.startswith("/fedmm/schedule/"):
            self.count[name] = self.count.get(name, 0) + 1

    def __enter__(self):
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._event)


def _per_round(gamma, n_rounds):
    """The loop the vector call replaces: one scalar call a round."""
    return jnp.stack([jnp.asarray(gamma(t + 1), jnp.float32)
                      for t in range(n_rounds)])


def _assert_bits_equal(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    assert got.weak_type == want.weak_type
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("n_rounds", [1, 7, 300])
@pytest.mark.parametrize("name", sorted(VECTORISABLE))
def test_vectorised_call_equals_per_round_loop(name, n_rounds):
    gamma = VECTORISABLE[name]
    with _Events() as ev:
        got = api.resolve_schedule(gamma, n_rounds)
    assert ev.count == {VECTORIZED: 1}
    _assert_bits_equal(got, _per_round(gamma, n_rounds))


@pytest.mark.parametrize("n_rounds", [1, 7, 300])
@pytest.mark.parametrize("name", sorted(FALLBACK))
def test_unvectorisable_callable_goes_per_round(name, n_rounds):
    gamma = FALLBACK[name]
    with _Events() as ev:
        got = api.resolve_schedule(gamma, n_rounds)
    assert ev.count == {PER_ROUND: 1}
    _assert_bits_equal(got, _per_round(gamma, n_rounds))


def test_probes_see_only_the_ends():
    """The documented limit: a vector that agrees with the scalar calls
    at both ends and differs inside is kept at its vector values."""
    def gamma(t):
        if np.ndim(t):
            return np.where((t == 1) | (t == 5), 0.1, 0.3)
        return 0.1
    with _Events() as ev:
        got = api.resolve_schedule(gamma, 5)
    assert ev.count == {VECTORIZED: 1}
    np.testing.assert_array_equal(
        np.asarray(got), np.array([0.1, 0.3, 0.3, 0.3, 0.1], np.float32))


def test_no_rounds_gives_an_empty_schedule():
    with _Events() as ev:
        got = api.resolve_schedule(api.decaying_stepsize(0.02), 0)
    assert ev.count == {PER_ROUND: 1}
    assert got.shape == (0,) and got.dtype == jnp.float32


def test_exit_raised_by_the_vector_call_propagates():
    def gamma(t):
        if np.ndim(t):
            raise SystemExit(3)
        return 0.1
    with pytest.raises(SystemExit):
        api.resolve_schedule(gamma, 4)


@pytest.mark.parametrize("gamma,n_rounds", [
    (lambda t: jnp.full((3,), 0.1), 4),
    (lambda t: jnp.full((4,), 0.1), 4),
    (lambda t: jnp.full((7,), 0.1), 7),
    (lambda t: jnp.full((1,), 0.1), 1),
], ids=["3-of-4", "n-of-4", "n-of-7", "1-of-1"])
def test_non_scalar_gammas_rejected_per_round(gamma, n_rounds):
    """A gamma of shape ``(n_rounds,)`` passes the vector call's shape
    check; the scalar probes catch it, and the per-round loop rejects it
    with its message."""
    with _Events() as ev, pytest.raises(ValueError,
                                        match="scalar gamma per round"):
        api.resolve_schedule(gamma, n_rounds)
    assert ev.count == {PER_ROUND: 1}
