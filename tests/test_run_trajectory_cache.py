"""The federated ``api.run(scan=True)`` keeps its compiled trajectory.

Contracts pinned here:
  * a repeat call with the same problem, spec and shapes dispatches the
    kept program: no ``/fedmm/run/trajectory/trace`` and no JAX jaxpr
    trace, and a bit-identical trajectory;
  * calls that differ only in data (key, x0, eval batch, static batch
    values) return exactly what a fresh program returns: nothing is
    frozen from the first call;
  * anything that decides the program (``n_rounds``, ``client_mode``,
    spec, problem, ``sanitize``) retraces;
  * an unhashable spec (``mu`` set) caches by identity;
  * ``sanitize=True`` hits too, and still raises on a planted NaN;
  * the cache holds at most ``_TRAJECTORIES_MAX`` programs, and
    ``clear_trajectory_cache`` empties it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api.driver as drv
from repro import api
from repro.core import compression as C
from repro.core.variational import DictLearnSpec, make_dictlearn

KEY = jax.random.PRNGKey(0)
N, P, K, BATCH = 4, 12, 3, 8
TRACE = "/fedmm/run/trajectory/trace"
CALL = "/fedmm/run/trajectory/call"
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"

ZS = jax.random.normal(KEY, (N, BATCH, P))
S0 = {"s1": jnp.eye(K), "s2": jax.random.normal(jax.random.PRNGKey(1),
                                                (P, K))}
SUR = make_dictlearn(DictLearnSpec(p=P, K=K, ista_iters=3))
PROBLEM = api.as_problem(SUR)
SPEC = api.FederationSpec(n_clients=N, participation=0.5, alpha=0.1,
                          compressor=C.block_quant(8, 16))


class _Events:
    """Counts the ``jax.monitoring`` events and durations recorded while
    active."""

    def __init__(self):
        self.count = {}

    def _event(self, name, **_):
        self.count[name] = self.count.get(name, 0) + 1

    def _duration(self, name, secs, **_):
        self._event(name)

    def __enter__(self):
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


@pytest.fixture(autouse=True)
def _fresh_cache():
    api.clear_trajectory_cache()
    yield
    api.clear_trajectory_cache()


def _run(problem=PROBLEM, spec=SPEC, x0=S0, data=None, key=KEY, n_rounds=3,
         eval_batch=ZS[0], **kw):
    data = (lambda t, k: ZS) if data is None else data
    st, hist = api.run(problem, x0, data, 0.3, spec=spec, key=key,
                       n_rounds=n_rounds, eval_batch=eval_batch, **kw)
    return jax.device_get((st, hist))


def _assert_bit_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _differs(a, b):
    return any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("static", [False, True], ids=["callable", "static"])
def test_repeat_call_dispatches_without_tracing(static):
    data = ZS if static else None
    first = _run(data=data)
    with _Events() as ev:
        again = _run(data=data)
    assert ev.count.get(CALL) == 1
    assert TRACE not in ev.count and JAXPR_TRACE not in ev.count, ev.count
    _assert_bit_identical(first, again)
    assert len(drv._TRAJECTORIES) == 1


def test_rebuilt_equal_problem_hits():
    _run()
    with _Events() as ev:
        _run(problem=api.as_problem(SUR))
    assert TRACE not in ev.count


DATA_CHANGES = {
    "key": dict(key=jax.random.PRNGKey(7)),
    "x0": dict(x0={"s1": 2.0 * jnp.eye(K), "s2": S0["s2"] + 0.5}),
    "eval_batch": dict(eval_batch=ZS[1]),
    "static_data": dict(data=ZS * 1.5),
}


@pytest.mark.parametrize("change", sorted(DATA_CHANGES))
def test_data_changes_are_never_stale(change):
    kw = DATA_CHANGES[change]
    base_kw = dict(data=ZS) if change == "static_data" else {}
    base = _run(**base_kw)
    with _Events() as ev:
        cached = _run(**kw)
    assert TRACE not in ev.count, "the changed call should reuse the program"
    api.clear_trajectory_cache()
    fresh = _run(**kw)
    _assert_bit_identical(cached, fresh)
    assert _differs(base, cached)


PROGRAM_CHANGES = {
    "n_rounds": dict(n_rounds=4),
    "client_mode": dict(client_mode="scan"),
    "spec": dict(spec=api.FederationSpec(n_clients=N, participation=1.0,
                                         alpha=0.1,
                                         compressor=SPEC.compressor)),
    "problem": dict(problem=api.as_problem(make_dictlearn(
        DictLearnSpec(p=P, K=K, ista_iters=3)))),
    "sanitize": dict(sanitize=True),
}


@pytest.mark.parametrize("change", sorted(PROGRAM_CHANGES))
def test_program_changes_retrace(change):
    _run()
    with _Events() as ev:
        _run(**PROGRAM_CHANGES[change])
    assert ev.count.get(TRACE) == 1
    assert len(drv._TRAJECTORIES) == 2


def test_spec_with_mu_caches_by_identity():
    spec = api.FederationSpec(n_clients=N, participation=0.5, alpha=0.1,
                              mu=jnp.arange(1.0, N + 1) / (N * (N + 1) / 2),
                              compressor=SPEC.compressor)
    with pytest.raises(TypeError):
        hash(spec)
    first = _run(spec=spec)
    with _Events() as ev:
        again = _run(spec=spec)
    assert TRACE not in ev.count
    _assert_bit_identical(first, again)


def test_sanitize_hits_and_still_raises_on_planted_nan():
    clean = _run(data=ZS, sanitize=True)
    with _Events() as ev:
        again = _run(data=ZS, sanitize=True)
    assert TRACE not in ev.count
    _assert_bit_identical(clean, again)
    with _Events() as ev:
        with pytest.raises(Exception, match="nan"):
            _run(data=ZS.at[0, 0, 0].set(jnp.nan), sanitize=True)
    assert TRACE not in ev.count


def test_cache_stays_within_its_bound(monkeypatch):
    monkeypatch.setattr(drv, "_TRAJECTORIES_MAX", 2)
    for n_rounds in (1, 2, 3):
        _run(n_rounds=n_rounds)
        assert len(drv._TRAJECTORIES) <= 2
    with _Events() as ev:
        _run(n_rounds=3)           # most recent: kept
    assert TRACE not in ev.count
    with _Events() as ev:
        _run(n_rounds=1)           # least recently used: dropped
    assert ev.count.get(TRACE) == 1
    assert len(drv._TRAJECTORIES) == 2


def test_clear_trajectory_cache_empties_it():
    _run()
    assert len(drv._TRAJECTORIES) == 1
    api.clear_trajectory_cache()
    assert not drv._TRAJECTORIES
    with _Events() as ev:
        _run()
    assert ev.count.get(TRACE) == 1
