"""Traffic and data generators, driven by a cell's workload file and seed.

These are the benchmark's own copies of the repo's synthetic generators
(``repro.data.synthetic.token_stream``, ``client_minibatch_fn``,
``repro.data.movielens.movielens_like``), so that a change to ``src/``
cannot move the yardstick. Everything is made on the device from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# token streams for the federated LM cells
# ---------------------------------------------------------------------------

def client_token_cdf(n_clients: int, vocab: int, skew: float):
    """Per-client unigram CDFs (n, vocab): a Zipf-like base sharpened
    towards a client-specific band of the vocabulary (non-IID text), the
    distribution of ``repro.data.synthetic.token_stream``."""
    v = jnp.arange(vocab, dtype=jnp.float32)
    base = 1.0 / (v + 10.0)
    center = (jnp.arange(n_clients, dtype=jnp.float32)[:, None] + 0.5) \
        / n_clients * vocab
    width = vocab / n_clients / (1.0 - skew + 1e-3)
    boost = jnp.exp(-0.5 * ((v[None] - center) / width) ** 2)
    prob = base[None] + skew * boost
    cdf = jnp.cumsum(prob, axis=-1)
    return cdf / cdf[:, -1:]


def lm_round_batch(key, cdf, n_clients: int, local_batch: int, seq_len: int,
                   n_frames: int, d_model: int, frames_scale: float,
                   frames_dtype):
    """One round's client batches: tokens/labels (n, b, S) drawn by
    inverse CDF from each client's unigram law, and stub encoder frames
    (n, b, F, d) in the dtype the model is served in."""
    kt, kf = jax.random.split(key)
    u = jax.random.uniform(kt, (n_clients, local_batch * (seq_len + 1)))
    toks = jax.vmap(lambda c, uu: jnp.searchsorted(c, uu, side="right"))(
        cdf, u)
    toks = jnp.minimum(toks, cdf.shape[-1] - 1).astype(jnp.int32)
    toks = toks.reshape(n_clients, local_batch, seq_len + 1)
    frames = (jax.random.normal(kf, (n_clients, local_batch, n_frames,
                                     d_model), jnp.float32)
              * frames_scale).astype(frames_dtype)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:],
            "frames": frames}


# ---------------------------------------------------------------------------
# the MovieLens-like dictionary-learning data
# ---------------------------------------------------------------------------

def movielens_like(key, n_users: int, n_movies: int, rank: int,
                   noise: float = 0.3, density: float = 0.08):
    """(n_users, n_movies) rating vectors, zeros where unobserved: a
    low-rank user x movie matrix with rating levels 0.5..5 in half steps."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    u = jax.random.normal(k1, (n_users, rank)) / jnp.sqrt(rank)
    v = jax.random.normal(k2, (n_movies, rank))
    raw = 3.5 + 1.2 * jnp.matmul(u, v.T, precision="highest") \
        + noise * jax.random.normal(k3, (n_users, n_movies))
    ratings = jnp.clip(jnp.round(raw * 2.0) / 2.0, 0.5, 5.0)
    observed = jax.random.bernoulli(k4, density, (n_users, n_movies))
    return jnp.where(observed, ratings, 0.0).astype(jnp.float32)


def heterogeneous_split(key, z, n_clients: int, power_iters: int = 30):
    """Cut the users into ``n_clients`` equal shards along the data's
    leading principal direction, so each client holds one band of it
    (heterogeneous clients; a device-side stand-in for the paper's
    constrained k-means). Returns (n_clients, n // n_clients, p)."""
    n = (z.shape[0] // n_clients) * n_clients
    z = z[:n]
    zc = z - jnp.mean(z, axis=0)
    v = jax.random.normal(key, (z.shape[1],))

    def it(_, v):
        w = jnp.matmul(zc.T, jnp.matmul(zc, v, precision="highest"),
                       precision="highest")
        return w / jnp.linalg.norm(w)

    v = jax.lax.fori_loop(0, power_iters, it, v)
    order = jnp.argsort(jnp.matmul(zc, v, precision="highest"))
    return z[order].reshape(n_clients, n // n_clients, z.shape[1])


def client_minibatch_fn(client_data, batch_size: int, spans=None):
    """``f(t, key) -> (n_clients, b, p)``: each client's minibatch drawn
    uniformly with replacement from its own shard (the Section 6 oracle:
    50 examples sampled at random among the local examples). Runs eagerly,
    as a user's data callable does; timed under the span ``data``."""
    n_clients, n_local = client_data.shape[0], client_data.shape[1]

    def fn(t, key):
        if spans is None:
            return _draw(key)
        with spans("data"):
            return _draw(key)

    def _draw(key):
        idx = jax.random.randint(key, (n_clients, batch_size), 0, n_local)
        return jnp.take_along_axis(client_data, idx[..., None], axis=1)

    return fn
