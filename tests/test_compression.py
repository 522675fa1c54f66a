"""A4 compression operators: unbiasedness + relative variance bound, and
Lemma 1 (partial participation == extra compression). Property-based with
hypothesis where the invariant is distributional."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import compression as C


def _mc_moments(comp, x, n=400, seed=0, chunk=50_000):
    """Sample mean of ``comp.apply(k, x)`` and of its squared error over
    ``n`` keys, drawn ``chunk`` at a time so a large ``n`` stays small in
    memory."""
    chunk = min(n, chunk)
    assert n % chunk == 0
    keys = jax.random.split(jax.random.PRNGKey(seed), n)

    def sums(ks):
        outs = jax.vmap(lambda k: comp.apply(k, x))(ks)
        return jnp.sum(outs, axis=0), jnp.sum((outs - x[None]) ** 2)

    total, sq_err = jax.lax.map(sums, keys.reshape(n // chunk, chunk,
                                                   *keys.shape[1:]))
    return jnp.sum(total, axis=0) / n, jnp.sum(sq_err) / n


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=64),
       st.sampled_from([4, 8]),
       st.integers(min_value=0, max_value=10**6))
def test_block_quant_unbiased_and_bounded(dim, bits, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (dim,)) * 3.0
    comp = C.block_quant(bits=bits, block=32)
    mean, var = _mc_moments(comp, x, n=600, seed=seed)
    sq = float(jnp.sum(x ** 2))
    # unbiasedness: |E Q(x) - x| small relative to the MC std
    tol = 4.0 * np.sqrt(comp.omega * sq / 600 + 1e-12) + 1e-5
    assert float(jnp.max(jnp.abs(mean - x))) < max(tol, 0.05 * np.sqrt(sq) + 1e-5)
    # A4 variance bound E||Q(x)-x||^2 <= omega ||x||^2 (with MC slack)
    assert float(var) <= comp.omega * sq * 1.5 + 1e-8


@settings(max_examples=10, deadline=None)
@given(st.floats(min_value=0.1, max_value=1.0),
       st.integers(min_value=0, max_value=10**6))
def test_rand_k_unbiased_and_bounded(frac, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (48,))
    comp = C.rand_k(frac)
    # 10**6 draws: at fraction 0.99999, which hypothesis draws often, a
    # coordinate drops once in 10**5 draws; the squared error's estimate
    # sits inside 1.4 omega ||x||^2 only with hundreds of drops (about
    # 480 here; 8,000 draws gave 4 and failed 29 seeds in 100)
    mean, var = _mc_moments(comp, x, n=10**6, seed=seed)
    sq = float(jnp.sum(x ** 2))
    assert float(jnp.max(jnp.abs(mean - x))) < 0.3 * float(jnp.max(jnp.abs(x))) + 1e-4
    assert float(var) <= comp.omega * sq * 1.4 + 1e-8


def test_identity_exact():
    comp = C.identity()
    x = {"a": jnp.arange(5.0), "b": jnp.ones((2, 2))}
    out = comp.apply(jax.random.PRNGKey(0), x)
    assert jax.tree.all(jax.tree.map(lambda u, v: bool(jnp.all(u == v)), x, out))
    assert comp.omega == 0.0


@settings(max_examples=20, deadline=None)
@given(st.floats(min_value=0.05, max_value=1.0),
       st.floats(min_value=0.0, max_value=4.0))
def test_lemma1_omega_formula(p, omega):
    """omega_p = omega + (1+omega)(1-p)/p; p=1 leaves omega unchanged."""
    w = C.effective_omega(omega, p)
    assert w == pytest.approx(omega + (1 + omega) * (1 - p) / p)
    assert C.effective_omega(omega, 1.0) == pytest.approx(omega)


def test_lemma1_composition_moments():
    """Monte-Carlo check that Quant-tilde = (U/p) Quant satisfies A4(omega_p):
    unbiased and variance <= omega_p ||x||^2 (Appendix D.2)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (32,))
    base = C.rand_k(0.5)
    comp = C.with_participation(base, p=0.5)
    mean, var = _mc_moments(comp, x, n=4000, seed=2)
    sq = float(jnp.sum(x ** 2))
    assert float(jnp.max(jnp.abs(mean - x))) < 0.25 * float(jnp.linalg.norm(x))
    assert float(var) <= comp.omega * sq * 1.3
    # and the variance is strictly larger than the base compressor's
    _, var_base = _mc_moments(base, x, n=4000, seed=3)
    assert float(var) > float(var_base)


def test_block_quant_preserves_pytree_and_dtype():
    comp = C.block_quant(8, 64)
    tree = {"w": jnp.ones((3, 7), jnp.float32), "b": jnp.zeros((5,), jnp.float32)}
    out = comp.apply(jax.random.PRNGKey(0), tree)
    assert out["w"].shape == (3, 7) and out["w"].dtype == jnp.float32
    # zero maps to zero exactly (scale-0 block guard)
    assert bool(jnp.all(out["b"] == 0.0))


def test_block_quant_exact_on_two_level_blocks():
    """Blocks whose entries sit exactly on quantization levels are preserved."""
    comp = C.block_quant(bits=8, block=4)
    levels = 2.0 ** 7 - 1.0
    x = jnp.array([1.0, -1.0, 64.0 / levels, 0.0])
    out = comp.apply(jax.random.PRNGKey(0), x)
    assert jnp.allclose(out, x, atol=1e-6)
