"""The driver's host spans and device scopes (``repro.api.spans``).

Contracts pinned here:
  * a federated ``api.run`` records each ``/fedmm/run/*`` duration exactly
    once per call through ``jax.monitoring``, also under the key-trace
    audit, and its five child phases add up to no more than the call;
  * a span whose body raises still records its duration;
  * a federated ``api.run`` given a callable schedule resolves it in one
    vector call (``/fedmm/schedule/vectorized``), and runs the trajectory
    it runs on the array the per-round resolution gives, bit for bit;
  * the round's device phases carry their ``fedmm.*`` named scopes into
    the lowered program's op metadata, for both client modes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api.spans import span
from repro.core import compression as C
from repro.core.variational import DictLearnSpec, make_dictlearn

KEY = jax.random.PRNGKey(0)
N, P, K, BATCH = 4, 12, 3, 8
PHASES = ("keys", "schedule", "batches", "stack", "scan")


def _problem():
    """The paper's dictionary learning at a tiny size: every phase of a
    round (the mirror map T included) has operations to scope."""
    zs = jax.random.normal(KEY, (N, BATCH, P))
    s0 = {"s1": jnp.eye(K), "s2": jax.random.normal(jax.random.PRNGKey(1),
                                                    (P, K))}
    problem = api.as_problem(make_dictlearn(DictLearnSpec(p=P, K=K,
                                                          ista_iters=3)))
    return zs, s0, problem


def _spec():
    return api.FederationSpec(n_clients=N, participation=0.5, alpha=0.1,
                              compressor=C.block_quant(8, 16))


class _Durations:
    """Collects the ``/fedmm/`` durations recorded while active."""

    def __init__(self):
        self.seen = {}

    def __call__(self, name, secs, **_):
        if name.startswith("/fedmm/"):
            self.seen.setdefault(name, []).append(secs)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


@pytest.mark.parametrize("audit_keys", [False, True])
def test_run_records_each_span_once_per_call(audit_keys):
    zs, s0, problem = _problem()
    with _Durations() as d:
        for _ in range(2):
            st, _ = api.run(problem, s0, lambda t, k: zs, 0.3, spec=_spec(),
                            key=KEY, n_rounds=3, eval_batch=zs[0],
                            audit_keys=audit_keys)
    jax.block_until_ready(st)
    names = ["/fedmm/run"] + [f"/fedmm/run/{p}" for p in PHASES]
    assert sorted(d.seen) == sorted(names)
    assert all(len(d.seen[n]) == 2 for n in names), d.seen
    for call in range(2):
        children = sum(d.seen[f"/fedmm/run/{p}"][call] for p in PHASES)
        assert 0.0 < children <= d.seen["/fedmm/run"][call]


class _Events:
    """Collects the ``/fedmm/schedule/*`` events recorded while active."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **_):
        if name.startswith("/fedmm/schedule/"):
            self.seen.append(name)

    def __enter__(self):
        jax.monitoring.register_event_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self)


def test_run_resolves_callable_schedule_in_one_vector_call():
    zs, s0, problem = _problem()
    gamma = api.decaying_stepsize(0.3)

    def run(schedule):
        st, hist = api.run(problem, s0, lambda t, k: zs, schedule,
                           spec=_spec(), key=KEY, n_rounds=3,
                           eval_batch=zs[0])
        return jax.device_get((st, hist))

    with _Events() as ev:
        got = run(gamma)
        # ``int`` of a vector raises: the same gammas, resolved per round
        per_round = api.resolve_schedule(lambda t: gamma(int(t)), 3)
    assert ev.seen == ["/fedmm/schedule/vectorized",
                       "/fedmm/schedule/per_round"]
    want = run(per_round)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_span_records_when_its_body_raises():
    with _Durations() as d:
        with pytest.raises(RuntimeError):
            with span("test.raises", step=3):
                raise RuntimeError("boom")
    assert len(d.seen["/fedmm/test/raises"]) == 1
    assert d.seen["/fedmm/test/raises"][0] >= 0.0


ROUND_SCOPES = ("fedmm.view", "fedmm.participation", "fedmm.client_oracle",
                "fedmm.wire_encode", "fedmm.wire_decode", "fedmm.aggregate",
                "fedmm.server")


@pytest.mark.parametrize("client_mode", ["vmap", "scan"])
def test_round_phases_carry_named_scopes(client_mode):
    zs, s0, problem = _problem()
    spec = _spec()
    state = api.init(problem, s0, spec)

    def one_round(st, batch, key):
        return api.step(problem, spec, st, batch, 0.3, key,
                        client_mode=client_mode)

    text = jax.jit(one_round).lower(state, zs, KEY).as_text(
        debug_info=True)
    missing = [s for s in ROUND_SCOPES if s not in text]
    assert not missing, missing

    def trajectory(key):
        return api.run(problem, s0, zs, 0.3, spec=spec, key=key,
                       n_rounds=2, eval_batch=zs[0], client_mode=client_mode)

    text = jax.jit(trajectory).lower(KEY).as_text(debug_info=True)
    missing = [s for s in ROUND_SCOPES + ("fedmm.eval",) if s not in text]
    assert not missing, missing
