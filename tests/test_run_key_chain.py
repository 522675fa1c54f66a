"""``api.run``'s per-round key chain, built in one compiled scan.

Contracts pinned here:
  * ``_key_chain`` (one jitted ``lax.scan`` of ``split(key, 3)``) gives
    the eager loop's ``round_keys`` and every ``batch_keys[t]`` bit for
    bit, for a raw ``PRNGKey``, a ``fold_in``'d key and a typed
    ``jax.random.key``;
  * ``run`` records ``/fedmm/run/keys/compiled`` once per call with no
    key audit, and ``/fedmm/run/keys/eager`` once under
    ``audit_keys=True`` or a ``KeyAudit`` activated by hand;
  * trajectories with the audit on (eager chain) and off (compiled
    chain) are bit-identical, on the scan and the per-round loop, with
    per-round and static data.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api.driver as drv
from repro import api
from repro.analysis.keytrace import KeyAudit
from repro.core import compression as C
from repro.core.variational import DictLearnSpec, make_dictlearn

COMPILED = "/fedmm/run/keys/compiled"
EAGER = "/fedmm/run/keys/eager"

N, P, K, BATCH = 4, 12, 3, 8
KEY = jax.random.PRNGKey(0)
ZS = jax.random.normal(KEY, (N, BATCH, P))
S0 = {"s1": jnp.eye(K), "s2": jax.random.normal(jax.random.PRNGKey(1),
                                                (P, K))}
PROBLEM = api.as_problem(make_dictlearn(DictLearnSpec(p=P, K=K,
                                                      ista_iters=3)))
SPEC = api.FederationSpec(n_clients=N, participation=0.5, alpha=0.1,
                          compressor=C.block_quant(8, 16))

KEY_KINDS = {
    "prngkey": lambda: jax.random.PRNGKey(2024),
    "fold_in": lambda: jax.random.fold_in(jax.random.PRNGKey(7),
                                          2**31 + 12345),
    "typed": lambda: jax.random.key(3),
}


def _bits(key):
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key)


def _eager_chain(key, n_rounds):
    round_keys, batch_keys = [], []
    for _ in range(n_rounds):
        key, k_round, k_batch = jax.random.split(key, 3)
        round_keys.append(k_round)
        batch_keys.append(k_batch)
    return jnp.stack(round_keys), batch_keys


@pytest.mark.parametrize("n_rounds", [1, 7, 300])
@pytest.mark.parametrize("kind", sorted(KEY_KINDS))
def test_compiled_chain_equals_eager_loop(kind, n_rounds):
    key = KEY_KINDS[kind]()
    ref_round, ref_batch = _eager_chain(key, n_rounds)
    round_keys, batch_keys = drv._round_keys(key, n_rounds)
    assert round_keys.shape == ref_round.shape
    assert round_keys.dtype == ref_round.dtype
    np.testing.assert_array_equal(_bits(round_keys), _bits(ref_round))
    assert len(batch_keys) == n_rounds
    for t, (got, want) in enumerate(zip(batch_keys, ref_batch)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(_bits(got), _bits(want),
                                      err_msg=f"batch_keys[{t}]")


class _Events:
    """Counts the ``jax.monitoring`` events recorded while active."""

    def __init__(self):
        self.count = {}

    def _event(self, name, **_):
        self.count[name] = self.count.get(name, 0) + 1

    def __enter__(self):
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._event)


def _noisy(t, k):
    """Per-round data that consumes the round's batch key."""
    return ZS + 0.1 * jax.random.normal(k, ZS.shape)


def _run(data=_noisy, **kw):
    st, hist = api.run(PROBLEM, S0, data, 0.3, spec=SPEC, key=KEY,
                       n_rounds=4, eval_batch=ZS[0], **kw)
    return jax.device_get((st, hist))


@pytest.mark.parametrize("scan", [True, False])
def test_run_counts_compiled_chain_once_per_call(scan):
    with _Events() as ev:
        _run(scan=scan)
        _run(scan=scan)
    assert ev.count.get(COMPILED) == 2
    assert EAGER not in ev.count


def test_run_counts_eager_chain_under_audit():
    with _Events() as ev:
        _run(audit_keys=True)
    assert ev.count.get(EAGER) == 1
    assert COMPILED not in ev.count
    # a KeyAudit a caller activates by hand is observed the same way
    with _Events() as ev, KeyAudit().activate():
        _run()
    assert ev.count.get(EAGER) == 1
    assert COMPILED not in ev.count


@pytest.mark.parametrize("data,scan", [(_noisy, True), (_noisy, False),
                                       (ZS, True)],
                         ids=["per-round-scan", "per-round-loop",
                              "static-scan"])
def test_audit_on_off_trajectories_bit_identical(data, scan):
    st_ref, hist_ref = _run(data=data, scan=scan)
    audit = KeyAudit()
    st, hist = _run(data=data, scan=scan, audit_keys=audit)
    for a, b in zip(jax.tree.leaves((st_ref, hist_ref)),
                    jax.tree.leaves((st, hist))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sum(e.kind == "split" for e in audit.report.events) >= 4
    assert audit.reuse_events == []
