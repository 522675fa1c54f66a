"""The checkify runtime sanitizer (repro.analysis Layer 3,
``api.run/step(..., sanitize=True)``).

Contracts pinned here:
  * **golden bit-identity** — sanitize=True returns the SAME trajectory
    bit-for-bit (state and every stacked metric) as sanitize=False on the
    scan path, the python fallback, the eager step, and the shard_mapped
    mesh path: checkify only adds error outputs, it never perturbs the
    primal math;
  * an injected NaN / division-by-zero inside the client oracle is
    caught and raised with its origin (JaxRuntimeError), on both run
    paths;
  * the ``eval_every`` cadence's deliberate NaN fill value does NOT trip
    the sanitizer (constants are not checked computations);
  * the comm-bytes audit: a compressor whose analytic ``payload_fn``
    disagrees with its actual encoded buffers fails fast under
    sanitize=True and stays permissive (metrics lie, nothing raises)
    when off;
  * centralized runs reject sanitize=True with a clear error.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import api
from repro.core import compression as C
from repro.core.quadratic import quadratic_for_objective

KEY = jax.random.PRNGKey(0)


def _quad_problem(n_clients=4, het=3.0, dim=6):
    ks = jax.random.split(KEY, n_clients)
    Xs = jnp.stack([jax.random.normal(k, (32, dim)) for k in ks])
    w_i = jnp.stack([jnp.linspace(-1, 1, dim) + het * i
                     for i in range(n_clients)])
    ys = jnp.einsum("nbp,np->nb", Xs, w_i)

    def loss(batch, theta):
        xb, yb = batch
        return 0.5 * jnp.mean((xb @ theta - yb) ** 2)

    return (Xs, ys), quadratic_for_objective(loss, rho=0.05)


def _assert_bit_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _spec(**kw):
    kw.setdefault("compressor", C.block_quant(4, 64))
    return api.FederationSpec(n_clients=4, participation=0.5, alpha=0.1,
                              **kw)


# ---------------------------------------------------------------------------
# golden bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scan", [True, False], ids=["scan", "python"])
def test_run_bit_identical_under_sanitize(scan):
    (Xs, ys), sur = _quad_problem()
    problem = api.as_problem(sur)
    kwargs = dict(spec=_spec(), key=KEY, n_rounds=8, scan=scan,
                  eval_batch=(Xs.reshape(-1, 6), ys.reshape(-1)))
    st0, h0 = api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3,
                      **kwargs)
    st1, h1 = api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3,
                      sanitize=True, **kwargs)
    _assert_bit_identical(st0.x, st1.x)
    _assert_bit_identical(st0.v_i, st1.v_i)
    assert set(h0) == set(h1)
    for k in h0:
        np.testing.assert_array_equal(np.asarray(h0[k]), np.asarray(h1[k]),
                                      err_msg=k)


def test_step_bit_identical_under_sanitize():
    (Xs, ys), sur = _quad_problem()
    problem = api.as_problem(sur)
    spec = _spec()
    state0 = api.init(problem, jnp.zeros(6), spec)
    k = jax.random.PRNGKey(7)
    s0, m0 = api.step(problem, spec, state0, (Xs, ys), 0.3, k)
    s1, m1 = api.step(problem, spec, state0, (Xs, ys), 0.3, k,
                      sanitize=True)
    _assert_bit_identical(s0.x, s1.x)
    _assert_bit_identical(s0.v_i, s1.v_i)
    for key in m0:
        np.testing.assert_array_equal(np.asarray(m0[key]),
                                      np.asarray(m1[key]), err_msg=key)


def test_mesh_run_bit_identical_under_sanitize():
    """checkify threads through the shard_mapped client stage + code-space
    collective (works on a 1-device mesh and on the CI 8-fake-device
    run alike)."""
    (Xs, ys), sur = _quad_problem(n_clients=8, dim=64)
    problem = api.as_problem(sur)
    spec = api.FederationSpec(n_clients=8, participation=1.0, alpha=0.1,
                              compressor=C.block_quant(4, 64))
    mesh = Mesh(np.asarray(jax.devices()), ("clients",))
    kwargs = dict(spec=spec, key=KEY, n_rounds=4, mesh=mesh)
    st0, _ = api.run(problem, jnp.zeros(64), lambda t, k: (Xs, ys), 0.3,
                     **kwargs)
    st1, _ = api.run(problem, jnp.zeros(64), lambda t, k: (Xs, ys), 0.3,
                     sanitize=True, **kwargs)
    _assert_bit_identical(st0.x, st1.x)
    # the fused reduce uplink threads checkify through psum too
    st2, _ = api.run(problem, jnp.zeros(64), lambda t, k: (Xs, ys), 0.3,
                     uplink="reduce", sanitize=True, **kwargs)
    st3, _ = api.run(problem, jnp.zeros(64), lambda t, k: (Xs, ys), 0.3,
                     uplink="reduce", **kwargs)
    _assert_bit_identical(st2.x, st3.x)


def test_eval_every_nan_cadence_not_flagged():
    """Skipped eval rounds record a deliberate NaN constant — a fill
    value, not a computed NaN — and must not trip nan_checks."""
    (Xs, ys), sur = _quad_problem()
    problem = api.as_problem(sur)
    kwargs = dict(spec=_spec(), key=KEY, n_rounds=6, eval_every=3,
                  eval_batch=(Xs.reshape(-1, 6), ys.reshape(-1)))
    st, hist = api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3,
                       sanitize=True, **kwargs)
    loss = np.asarray(hist["loss"])
    assert np.isnan(loss[0]) and np.isfinite(loss[2])


# ---------------------------------------------------------------------------
# real poison is caught
# ---------------------------------------------------------------------------

def _poisoned_problem(sur):
    """0/0 inside the client oracle -> NaN in round 0."""
    bad = dataclasses.replace(
        sur, s_bar=lambda b, th: jax.tree.map(
            lambda x: x + (x - x) / (x - x), sur.s_bar(b, th)))
    return api.as_problem(bad)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "python"])
def test_injected_nan_is_flagged(scan):
    (Xs, ys), sur = _quad_problem()
    problem = _poisoned_problem(sur)
    kwargs = dict(spec=_spec(), key=KEY, n_rounds=3, scan=scan)
    # without the sanitizer the poison is LAUNDERED, not propagated: the
    # block quantizer's `scale = where(amax > 0, ...)` guard sees
    # NaN > 0 == False, quantizes the NaN client update to all-zero
    # codes, and the trajectory quietly loses those clients — the state
    # stays finite and nothing ever says "NaN". This is exactly the
    # silent-corruption mode the sanitizer exists to expose.
    st, _ = api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3,
                    **kwargs)
    assert np.isfinite(np.asarray(st.x)).all()
    with pytest.raises(Exception, match="division by zero|nan"):
        api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3,
                sanitize=True, **kwargs)


def test_injected_nan_is_flagged_in_eager_step():
    (Xs, ys), sur = _quad_problem()
    problem = _poisoned_problem(sur)
    spec = _spec()
    state0 = api.init(problem, jnp.zeros(6), spec)
    with pytest.raises(Exception, match="division by zero|nan"):
        api.step(problem, spec, state0, (Xs, ys), 0.3, KEY, sanitize=True)


def test_collapse_failure_degrades_to_upstream_rule(monkeypatch):
    """The device-axis collapse pokes at jax._src.checkify.Error internals
    — if a jax upgrade reshuffles that layout, the patched shard_map rule
    must degrade to the upstream rule's error, not crash the trace with
    the collapse's own exception."""
    from jax.sharding import PartitionSpec

    from repro.analysis import runtime

    def boom(error):
        raise RuntimeError("checkify Error layout changed")

    monkeypatch.setattr(runtime, "_collapse_error_device_axis", boom)
    mesh = Mesh(np.asarray(jax.devices()), ("clients",))
    x = jnp.ones((len(jax.devices()), 4))

    def f(a):
        return jax.shard_map(lambda xl: jnp.log(xl),
                             mesh=mesh, in_specs=(PartitionSpec("clients"),),
                             out_specs=PartitionSpec("clients"))(a)

    err, out = runtime.checkified(f)(x)  # must not raise the RuntimeError
    err.throw()  # log(1) trips nothing
    np.testing.assert_array_equal(np.asarray(out), 0.0)


# ---------------------------------------------------------------------------
# the comm-bytes audit
# ---------------------------------------------------------------------------

def test_comm_audit_catches_lying_payload_model():
    (Xs, ys), sur = _quad_problem()
    problem = api.as_problem(sur)
    lying = dataclasses.replace(
        C.block_quant(4, 64), payload_fn=lambda shape, itemsize: 1.0)
    kwargs = dict(spec=_spec(compressor=lying), key=KEY, n_rounds=2)
    # off: permissive (the metric lies, nothing raises)
    api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3, **kwargs)
    # on: trace-time failure naming the compressor and both byte counts
    with pytest.raises(ValueError, match="comm-bytes audit failed"):
        api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3,
                sanitize=True, **kwargs)


def test_honest_model_passes_audit_on_scan_client_mode():
    (Xs, ys), sur = _quad_problem()
    problem = api.as_problem(sur)
    st, _ = api.run(problem, jnp.zeros(6), lambda t, k: (Xs, ys), 0.3,
                    spec=_spec(), key=KEY, n_rounds=2, client_mode="scan",
                    sanitize=True)
    assert np.isfinite(np.asarray(st.x)).all()


def test_centralized_rejects_sanitize():
    (Xs, ys), sur = _quad_problem()
    with pytest.raises(ValueError, match="sanitize=True"):
        api.run(api.as_problem(sur), jnp.zeros(6),
                [(Xs[0], ys[0])] * 3, 0.3, sanitize=True)
