"""Example 3 — variational surrogate: dictionary learning / matrix
factorization (Section 2.3, eqs. (14)-(18); the Section 6 experiment).

Problem (eq. 28):
    argmin_theta  (1/n) sum_i E_{pi_i}[ min_h 0.5 ||Z - theta h||^2
                                        + lam ||h||_1 ] + eta ||theta||^2

Mirror parameter  s = (s1, s2) in S = M_K^+ x R^{pxK}:
    s1 = E[ h* h*^T ],    s2 = E[ Z h*^T ],    h* = M(Z, theta)  (lasso)

T(s) = argmin_theta  eta ||theta||^2 + Tr(theta^T theta s1) - 2 Tr(theta^T s2)
     = s2 (s1 + eta I)^{-1}          (ridge-regularized closed form; with the
                                      paper's eta ||theta||^2 convention,
                                      grad = 2 theta (s1 + eta I) - 2 s2 = 0)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .surrogate import Surrogate
from .prox import lasso_ista, project_psd


@dataclasses.dataclass(frozen=True)
class DictLearnSpec:
    p: int                 # observation dimension
    K: int                 # dictionary size / embedding dim
    lam: float = 0.1       # l1 penalty on codes h
    eta: float = 0.2       # l2 penalty on the dictionary theta
    ista_iters: int = 100  # inner lasso solver iterations


def sparse_code(z, theta, spec: DictLearnSpec):
    """M(Z, theta): batched lasso (eq. 16/24). z: (b, p) -> h: (b, K)."""
    return lasso_ista(z, theta, spec.lam, spec.ista_iters)


def _f32_matmuls(fn):
    """Trace ``fn`` with its matmuls, and the linear algebra built on them,
    at full float32 precision. A TPU runs f32 dots as bf16 passes unless
    told otherwise; chained through the lasso solve, the ridge solve and
    the PSD projection every round, that moved the MovieLens objective
    250x further from a float32 CPU run (4.5e-4 against 1.8e-6 relative
    after 30 rounds on a v5e), and it turns a 1-ulp reassociation of the
    client reduce into a bf16-sized step. No effect on CPU."""
    @functools.wraps(fn)
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return wrapped


def make_dictlearn(spec: DictLearnSpec) -> Surrogate:
    @_f32_matmuls
    def s_bar(batch, theta):
        z = batch["z"] if isinstance(batch, dict) else batch    # (b, p)
        h = sparse_code(z, theta, spec)                         # (b, K)
        b = z.shape[0]
        s1 = h.T @ h / b                                        # (K, K)  in M_K^+
        s2 = z.T @ h / b                                        # (p, K)
        return {"s1": s1, "s2": s2}

    @_f32_matmuls
    def T(s):
        A = s["s1"] + spec.eta * jnp.eye(spec.K, dtype=s["s1"].dtype)
        # theta = s2 A^{-1}; solve A^T X^T = s2^T for X
        return jnp.linalg.solve(A.T, s["s2"].T).T               # (p, K)

    @_f32_matmuls
    def project(s):
        # S = M_K^+ x R^{pxK}: PSD-project s1 (quantization / control-variate
        # corrections can push it off the cone — Section 5 "Challenges").
        return {"s1": project_psd(s["s1"]), "s2": s["s2"]}

    @_f32_matmuls
    def loss(batch, theta):
        z = batch["z"] if isinstance(batch, dict) else batch
        h = sparse_code(z, theta, spec)
        recon = 0.5 * jnp.mean(jnp.sum((z - h @ theta.T) ** 2, axis=1))
        l1 = spec.lam * jnp.mean(jnp.sum(jnp.abs(h), axis=1))
        return recon + l1 + spec.eta * jnp.sum(theta ** 2)

    return Surrogate(s_bar=s_bar, T=T, project=project, loss=loss)
