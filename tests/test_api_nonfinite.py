"""``api.step`` reports how many participating clients' oracle outputs held
a NaN or an inf (``n_nonfinite``): the wire's zero-scale guard would
otherwise turn each such group into zeros and the round would go on as if
nothing had happened."""
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core import compression as C
from repro.core.quadratic import quadratic_for_objective

KEY = jax.random.PRNGKey(0)


def _problem():
    def loss(batch, theta):
        xb, yb = batch
        return 0.5 * jnp.mean((xb @ theta - yb) ** 2)

    return api.as_problem(quadratic_for_objective(loss, rho=0.05))


def _batches(poison):
    """Three clients; client 1's targets hold a NaN when ``poison``."""
    xs = jax.random.normal(KEY, (3, 8, 4))
    ys = jnp.ones((3, 8))
    if poison:
        ys = ys.at[1, 0].set(jnp.nan)
    return xs, ys


@pytest.mark.parametrize("client_mode", ["vmap", "scan"])
@pytest.mark.parametrize("poison,active,expect", [
    (False, (1, 1, 1), 0),
    (True, (1, 1, 1), 1),
    (True, (1, 0, 1), 0),      # the poisoned client sat the round out
])
def test_step_counts_participating_nonfinite_clients(client_mode, poison,
                                                     active, expect):
    problem = _problem()
    spec = api.FederationSpec(n_clients=3, participation=1.0, alpha=0.1,
                              compressor=C.block_quant(8, 4))
    state = api.init(problem, jnp.zeros(4), spec)
    new, m = api.step(problem, spec, state, _batches(poison), 0.3, KEY,
                      active=jnp.asarray(active, bool),
                      client_mode=client_mode)
    assert int(m["n_nonfinite"]) == expect
    if poison and expect:
        # the wire zeroed the poisoned groups: the state alone cannot tell
        assert bool(jnp.all(jnp.isfinite(new.x)))
