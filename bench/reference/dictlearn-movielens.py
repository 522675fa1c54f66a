"""Plain float32 reference of the paper's federated dictionary learning
(arXiv:2507.17534, Section 6; eqs. 14-18 and 28), written from the
configuration alone.

Mirror parameter s = (s1, s2): s1 = E[h h^T] (K x K, kept PSD), s2 =
E[z h^T] (p x K), with h = argmin 0.5 ||z - theta h||^2 + lam ||h||_1
solved by ISTA (step 1 / ||theta^T theta||_2, ``ista_iters`` iterations
from 0). T(s) = s2 (s1 + eta I)^-1. The objective is the mean over the
evaluation samples of 0.5 ||z - theta h||^2 + lam ||h||_1, plus
eta ||theta||^2.

One round of Algorithm 2: the view theta = T(s); every client's oracle on
its minibatch; the drift d_i = S_i - s - V_i; the wire (the flattened leaf
cut into blocks of ``quant_block``, max-abs scale, stochastic rounding to
``quant_bits``-bit codes with uniform draws); the mu-weighted sum of the
participating clients' payloads; s <- proj(s + gamma (V + agg / p)) with s1
projected onto the PSD cone; V += alpha/p agg; V_i += alpha/p q_i.

The randomness follows the documented key chain of ``api.run`` from the
run's key: per round ``key, k_round, k_batch = split(key, 3)``; the
minibatch from ``k_batch``; ``k_part, k_quant = split(k_round)``; the
participation draw Bernoulli(p) from ``k_part``; the client keys
``split(k_quant, n)``, and each leaf's draws from ``split(client_key,
n_leaves)`` in the leaves' sorted order. So the reference sees the same
minibatches, participants and dither as the program, and the comparison
measures the arithmetic alone.

Every product is computed in float32 at ``highest`` precision, or one step
lower for a control (``bench/precision.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic


class Reference:
    def __init__(self, config: dict, workload: dict, mm,
                 precision: str = "highest", half_batch: bool = False,
                 alter: bool = False):
        self.c, self.wl, self.mm = config, workload, mm
        self.prec = precision
        self.half = half_batch      # fault: each client's mean over half
        self.alter = alter          # fault: every client's s2 1% off

    # -- the problem ----------------------------------------------------------
    def lasso(self, z, theta):
        mm, lam = self.mm, self.c["lam"]
        gram = mm(theta.T, theta)
        lip = jnp.max(jnp.linalg.eigvalsh(gram)) + 1e-6
        step = 1.0 / lip
        ztd = mm(z, theta)

        def it(_, h):
            x = h - step * (mm(h, gram) - ztd)
            return jnp.sign(x) * jnp.maximum(jnp.abs(x) - step * lam, 0.0)

        return jax.lax.fori_loop(0, self.c["ista_iters"], it,
                                 jnp.zeros(ztd.shape, jnp.float32))

    def s_bar(self, z, theta):
        h = self.lasso(z, theta)
        b = z.shape[0]
        return {"s1": self.mm(h.T, h) / b, "s2": self.mm(z.T, h) / b}

    def T(self, s):
        A = s["s1"] + self.c["eta"] * jnp.eye(self.c["K"], dtype=jnp.float32)
        return jnp.linalg.solve(A.T, s["s2"].T).T

    def project(self, s):
        sym = 0.5 * (s["s1"] + s["s1"].T)
        w, v = jnp.linalg.eigh(sym)
        return {"s1": self.mm(v * jnp.maximum(w, 0.0), v.T), "s2": s["s2"]}

    def objective(self, z, theta):
        h = self.lasso(z, theta)
        r = z - self.mm(h, theta.T)
        return (0.5 * jnp.mean(jnp.sum(r * r, axis=1))
                + self.c["lam"] * jnp.mean(jnp.sum(jnp.abs(h), axis=1))
                + self.c["eta"] * jnp.sum(theta * theta))

    # -- the wire -------------------------------------------------------------
    def wire(self, key, x):
        bits, block = self.c["quant_bits"], self.c["quant_block"]
        levels = 2.0 ** (bits - 1) - 1.0
        n = x.size
        pad = (-n) % block
        flat = jnp.pad(x.reshape(-1), (0, pad)).reshape(-1, block)
        u = jax.random.uniform(key, (n + pad,), jnp.float32).reshape(-1, block)
        scale = jnp.max(jnp.abs(flat), axis=-1, keepdims=True)
        safe = jnp.where(scale > 0, scale, 1.0)
        y = flat / safe * levels
        lo = jnp.floor(y)
        q = lo + (u < (y - lo)).astype(jnp.float32)
        out = jnp.where(scale > 0, q * safe * (1.0 / levels), 0.0)
        return out.reshape(-1)[:n].reshape(x.shape)

    # -- the federated run ----------------------------------------------------
    def gammas(self, n_rounds):
        beta = np.float32(self.c["beta_stepsize"])
        t = np.arange(1, n_rounds + 1, dtype=np.float64)
        return beta / np.sqrt((self.c["beta_stepsize"] + t).astype(np.float32))

    def run(self, clients, s0, z_eval, key, n_rounds: int) -> dict:
        """The trajectory of one run: per-round objective and ||h||^2, and
        the final mirror parameter."""
        c = self.c
        n, p, alpha = c["n_clients"], c["participation"], c["alpha"]
        b = c["batch_size"]
        draw = traffic.client_minibatch_fn(clients, b)
        mu = jnp.full((n,), 1.0 / n, jnp.float32)

        def body(carry, gamma):
            key, s, v, v_i = carry
            key, k_round, k_batch = jax.random.split(key, 3)
            z = draw(0, k_batch)
            if self.half:
                z = z[:, : b // 2]
            theta = self.T(s)
            k_part, k_quant = jax.random.split(k_round)
            mask = jax.random.bernoulli(k_part, p, (n,)).astype(jnp.float32)
            qkeys = jax.random.split(k_quant, n)

            def client(zc, vc, qk):
                si = self.s_bar(zc, theta)
                if self.alter:
                    # every client's statistic s2 one part in a hundred off
                    si = dict(si, s2=si["s2"] * 1.01)
                lk = jax.random.split(qk, 2)        # leaves s1, s2
                return {name: self.wire(lk[j], si[name] - s[name] - vc[name])
                        for j, name in enumerate(("s1", "s2"))}

            q = jax.vmap(client)(z, v_i, qkeys)
            q = jax.tree.map(
                lambda x: x * mask.reshape((n,) + (1,) * (x.ndim - 1)), q)

            def wsum(x):
                acc = mu[0] * x[0]
                for i in range(1, n):
                    acc = acc + mu[i] * x[i]
                return acc

            agg = jax.tree.map(wsum, q)
            h = jax.tree.map(lambda vv, a: vv + (1.0 / p) * a, v, agg)
            s_new = self.project(jax.tree.map(lambda hh, x: gamma * hh + x,
                                              h, s))
            v = jax.tree.map(lambda vv, a: vv + (alpha / p) * a, v, agg)
            v_i = jax.tree.map(lambda vv, qq: vv + (alpha / p) * qq, v_i, q)
            out = {"loss": self.objective(z_eval, self.T(s_new)),
                   "h_norm_sq": sum(jnp.sum(x * x) for x in
                                    jax.tree.leaves(h))}
            return (key, s_new, v, v_i), out

        def go(key, s0):
            with jax.default_matmul_precision(self.prec):
                v = jax.tree.map(jnp.zeros_like, s0)
                v_i = jax.tree.map(lambda x: jnp.zeros((n,) + x.shape), s0)
                (_, s, _, _), hist = jax.lax.scan(
                    body, (key, s0, v, v_i), jnp.asarray(self.gammas(n_rounds)))
            return s, hist

        s, hist = jax.jit(go)(key, s0)
        return {"loss": np.asarray(hist["loss"]),
                "h_norm_sq": np.asarray(hist["h_norm_sq"]),
                "x": {k: np.asarray(x) for k, x in s.items()}}
