"""FedMM at transformer scale: Algorithm 2 with the quadratic surrogate
(Example 1) driving any model from ``repro.models``.

Mirror parameter: Shat has the parameter pytree structure; the per-client
oracle is S_i = theta - rho * grad_i(theta) on the client's batch shard;
T(s) = prox_{rho g}(s) = s / (1 + rho * wd) elementwise (g = weight decay).
Delta_i = S_i - Shat - V_i is compressed by a ``repro.core.compression.
Compressor`` (by default the unified block quantizer with the fused-hash
dither: shard-aligned groups along the last axis, elementwise jnp graph
under pjit for multi-dim leaves, Pallas-kernel dispatch for large flat
leaves) before the uplink aggregation; the server applies the SA step.
Aggregation happens in the SURROGATE space — the paper's central design —
and lowers to one weighted all-reduce over the client mesh axes.

This module owns NO quantizer of its own: ``resolve_compressor`` builds the
operator from (quant_bits, quant_block, quant_dither) or takes an explicit
``FedLMConfig.compressor``, so this trainer, ``core/fedmm.py``, and the raw
kernel produce identical dequantized payloads for identical keys.

It owns no client loop either: ``make_train_step`` adapts the model into an
``api.MMProblem`` (``make_problem``) and runs each round as one
``api.step`` call — physical silos on the driver's batched/shard_mapped
path, logical clients on its sequential-scan mode (see below).

Client topology (the layouts ``state_specs`` gives):
  physical  n = |pod| x |data| silos; V_i / grads carry a leading client dim
            sharded over ('pod','data'); inner dims sharded over 'model'.
            The uplink aggregation IS the cross-silo all-reduce.
            Memory: ~6 param-sized buffers / 16 devices -> P <~ 20B.
  logical   n in {2, 4} simulated clients; the client dim is local and inner
            dims are sharded over the whole mesh (ZeRO-style). For models
            past that size, where per-client control variates at parameter
            granularity exceed a silo's HBM (a real deployment constraint
            of FedMM with quadratic surrogates).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import api
from ..core import compression
from ..core.compression import Compressor
from ..models import sharding as shd
from ..models.model import Model


@dataclasses.dataclass(frozen=True)
class FedLMConfig:
    n_clients: int
    rho: float = 0.02              # surrogate curvature step (<= 1/L_f)
    weight_decay: float = 0.1      # g(theta) = wd/2 ||theta||^2
    p: float = 1.0                 # participation probability (A5)
    alpha: float = 0.1             # control-variate step
    quant_bits: int = 8            # 0 -> no compression
    quant_block: int = 256
    quant_dither: str = "hash"     # fused-hash dither (zero-memory at scale)
    quant_compute: str = "f32"     # "native" keeps bf16 chains in bf16
    compressor: Optional[Compressor] = None  # overrides the quant_* fields
    client_mode: str = "physical"  # physical | logical
    use_cv: bool = True            # False (alpha=0 regime): drop V/V_i
                                   # entirely — saves 2x params of state
                                   # (Theorem 1's omega_p=0 / alpha=0 case)
    server_momentum: float = 0.0   # FedAvgM heavy-ball beta on the server
    # explicit FederationSpec: overrides n_clients/p/alpha/use_cv/quant_*
    # (the same object the repro.api driver and core shims consume)
    federation: Optional[api.FederationSpec] = None

    def federation_spec(self) -> "api.FederationSpec":
        """The federation axes of this trainer as the ONE shared
        ``repro.api.FederationSpec``: this trainer, ``core/fedmm.py`` and
        the unified driver all read participation/variates/compression off
        the same object."""
        if self.federation is not None:
            return self.federation
        if self.compressor is not None:
            comp = self.compressor
        elif not self.quant_bits:
            comp = compression.identity()
        else:
            comp = compression.block_quant(
                self.quant_bits, self.quant_block, dither=self.quant_dither,
                shard_safe=True, compute=self.quant_compute)
        return api.FederationSpec(
            n_clients=self.n_clients, participation=self.p,
            alpha=self.alpha if self.use_cv else 0.0,
            variates="zero" if self.use_cv else "off", compressor=comp,
            server_momentum=self.server_momentum)


def resolve_compressor(cfg: FedLMConfig) -> Compressor:
    """The ONE uplink compressor this trainer uses — read off the shared
    ``FederationSpec`` (explicit ``cfg.compressor`` if given, else the
    unified block quantizer parameterized by the quant_* fields, identity
    when quant_bits == 0)."""
    return cfg.federation_spec().compressor


class FedLMState(NamedTuple):
    s_hat: object
    v: object
    v_i: object                    # leading client dim
    step: jnp.ndarray
    opt: object = ()               # FedAvgM momentum buffer (param-shaped
                                   # when cfg.server_momentum > 0)


def param_count(model: Model) -> int:
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(int(jnp.prod(jnp.asarray(l.shape))) if l.shape else 1
               for l in jax.tree.leaves(shapes))


def T_map(s_hat, cfg: FedLMConfig):
    """MM-2 minimizer: prox of the l2 penalty — exact and elementwise, in
    at least float32 and rounded once to each leaf's dtype (a bf16 c would
    shrink by 1 - 0.99609 where 1/(1 + 0.005) asks for 0.995)."""
    c = 1.0 / (1.0 + cfg.rho * cfg.weight_decay)
    return jax.tree.map(
        lambda x: (c * x.astype(jnp.promote_types(x.dtype, jnp.float32)))
        .astype(x.dtype), s_hat)


def init_state(model: Model, key, cfg: FedLMConfig) -> FedLMState:
    spec = cfg.federation_spec()
    params = model.init(key)
    # m_0 = 0 heavy-ball buffer when the spec carries server momentum
    opt = (jax.tree.map(jnp.zeros_like, params)
           if spec.server_momentum > 0.0 else ())
    if not spec.use_variates:
        return FedLMState(s_hat=params, v={}, v_i={}, step=jnp.asarray(0),
                          opt=opt)
    v = jax.tree.map(jnp.zeros_like, params)
    v_i = jax.tree.map(
        lambda x: jnp.zeros((spec.n_clients,) + x.shape, x.dtype), params)
    return FedLMState(s_hat=params, v=v, v_i=v_i, step=jnp.asarray(0),
                      opt=opt)


PROBE_SIZE = 4096


def grad_probe(g, size: int = PROBE_SIZE):
    """The gradient at a fixed grid of each leaf's coordinates, in float32,
    as one flat vector: along every axis of length d, every
    ``max(1, d // m)``-th index, with ``m = round(size ** (1 / ndim))``,
    so about ``size`` coordinates a leaf. A bf16 oracle output keeps
    nothing of ``rho * g`` where it is under half a step of theta; this
    view of g keeps the gradient itself, element by element, for a check
    against a reference."""
    out = []
    for x in jax.tree.leaves(g):
        m = max(1, round(size ** (1.0 / x.ndim))) if x.ndim else 1
        x = x[tuple(slice(None, None, max(1, d // m)) for d in x.shape)]
        out.append(x.astype(jnp.float32).reshape(-1))
    return jnp.concatenate(out)


def _rounded(x, dtype):
    """``x`` rounded to ``dtype`` by an explicit op: XLA, allowed excess
    precision, drops a convert whose result is converted back up, so on
    the TPU the oracle output reached the drift unrounded (carrying the
    ``rho * g`` that its bf16 value loses) wherever the two fused."""
    dtype = jnp.dtype(dtype)
    if dtype == x.dtype:
        return x
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(
        x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant).astype(dtype)


def make_problem(model: Model, cfg: FedLMConfig) -> "api.MMProblem":
    """This trainer's workload as the ONE ``api.MMProblem``: the quadratic
    surrogate (Example 1) on ``model.loss_fn`` — per-client oracle
    S_i = theta - rho * grad_i(theta) (dtype-preserving: computed in at
    least float32 and rounded once to the parameter dtype), T = the l2
    prox, projection = identity (S = R^q). ``s_bar_metrics`` surfaces the
    per-client loss from the same ``value_and_grad`` call, so the driver's
    metrics carry the trainer's ``loss`` without a second forward pass,
    the client's ``grad_probe``, and, for a model that holds a share of
    its experts (``model.loss_stats``), each client's ``expert_load``."""

    def s_bar_metrics(cb, theta):
        if model.loss_stats is None:
            loss, g = jax.value_and_grad(model.loss_fn)(theta, cb)
            stats = {}
        else:
            (loss, stats), g = jax.value_and_grad(
                model.loss_stats, has_aux=True)(theta, cb)
        def oracle(th, gg):
            acc = jnp.promote_types(th.dtype, jnp.float32)
            return _rounded(th.astype(acc) - cfg.rho * gg.astype(acc),
                            th.dtype)
        s_i = jax.tree.map(oracle, theta, g)
        return s_i, {"loss": loss, "grad_probe": grad_probe(g), **stats}

    return api.MMProblem(
        s_bar=lambda cb, theta: s_bar_metrics(cb, theta)[0],
        s_bar_metrics=s_bar_metrics,
        T=lambda s: T_map(s, cfg))


def make_train_step(model: Model, cfg: FedLMConfig, mesh=None,
                    client_axis: str = "clients", uplink: str = "gather"):
    """Returns train_step(state, batch, key, gamma) -> (state, metrics).
    batch: {"tokens": (n_clients, B_local, S), "labels": ...} (+frontend).

    The round IS one ``api.step`` call (ROADMAP follow-up (a) — no
    hand-rolled client loop left in this module): every federation axis
    comes off ``cfg.federation_spec()``, the same ``FederationSpec`` the
    reference driver consumes, and the client topology maps onto the
    driver's client modes

      * ``client_mode="physical"`` -> the batched/sharded driver path
        (``client_mode="vmap"`` + optional ``mesh=``/``client_axis=``:
        silos run concurrently, the client dim shard_mapped over the mesh
        axis and the uplink a real code-space collective — without a mesh
        the vmap stays hand-shardable by pjit exactly as before). The
        ``uplink`` knob passes straight through to ``api.step``:
        ``"gather"`` (default) all_gathers the packed payload stack onto
        every silo (bit-identical golden path), ``"reduce"`` keeps each
        silo on its own clients' payloads and psums the model-shaped
        partial aggregate (allclose; O(n/axis_size) payload memory —
        the right choice at LM scale, where the n-client stack per
        device is exactly what the silo topology cannot afford);
      * ``client_mode="logical"``  -> the driver's sequential-scan client
        mode (one client's grad/delta/quantize transients live at a time
        — the production pattern for simulated cross-silo runs on shared
        hardware).

    ``tests/test_fed_trainer.py`` golden-pins both modes against a frozen
    copy of the pre-collapse hand-rolled trainer."""

    spec = cfg.federation_spec()
    use_cv = spec.use_variates
    problem = make_problem(model, cfg)
    driver_mode = "scan" if cfg.client_mode == "logical" else "vmap"

    def train_step(state: FedLMState, batch, key, gamma):
        dstate = api.DriverState(x=state.s_hat, v=state.v, v_i=state.v_i,
                                 aux=(), opt=state.opt, step=state.step)
        new, m = api.step(problem, spec, dstate, batch, gamma, key,
                          mesh=mesh, client_axis=client_axis,
                          client_mode=driver_mode, uplink=uplink,
                          drift_metric=False)
        # legacy metric names: e_s is ||h||^2 (elementwise square+sum — the
        # driver's h_norm_sq), loss the all-client mean off s_bar_metrics
        metrics = {"loss": m["loss"], "e_s": m["h_norm_sq"],
                   "n_active": m["n_active"], "comm_bytes": m["comm_bytes"],
                   "omega_eff": m["omega_eff"],
                   "n_nonfinite": m["n_nonfinite"],
                   # the all-client mean of the oracles' gradient probes
                   "grad_probe": m["grad_probe"]}
        if "expert_load" in m:
            # the driver means per-client metrics over all n clients, each
            # of which ran its oracle: the round's assignments per held
            # expert per MoE layer are n times that mean
            metrics["expert_load"] = jnp.round(
                m["expert_load"] * spec.n_clients).astype(jnp.int32)
        if "collective_payload_bytes" in m:
            metrics["collective_payload_bytes"] = \
                m["collective_payload_bytes"]
        return FedLMState(
            s_hat=new.x,
            v=new.v if use_cv else state.v,
            v_i=new.v_i if use_cv else state.v_i,
            step=new.step, opt=new.opt), metrics

    return train_step


# ---------------------------------------------------------------------------
# sharding specs for the FedMM state + batches
# ---------------------------------------------------------------------------

def state_specs(params_shapes, cfg: FedLMConfig, fsdp, tp="model",
                fsdp_size=16, tp_size=16):
    """PartitionSpec pytrees for (s_hat, v, v_i) given the eval_shape of the
    params. physical: client dim over the fsdp axes, inner dims over tp only.
    logical: client dim unsharded, inner dims over (fsdp, tp)."""
    use_cv = cfg.federation_spec().use_variates
    if cfg.client_mode == "physical":
        pspec = shd.param_specs(params_shapes, fsdp=(), fsdp_size=10**9,
                                tp=tp, tp_size=tp_size)
        vi_spec = jax.tree.map(lambda s: P(fsdp, *s), pspec,
                               is_leaf=lambda x: isinstance(x, P))
    else:
        pspec = shd.param_specs(params_shapes, fsdp=fsdp, fsdp_size=fsdp_size,
                                tp=tp, tp_size=tp_size)
        vi_spec = jax.tree.map(lambda s: P(None, *s), pspec,
                               is_leaf=lambda x: isinstance(x, P))
    if not use_cv:
        return pspec, {}, {}
    return pspec, pspec, vi_spec


def batch_spec(cfg: FedLMConfig, fsdp):
    """tokens (n, B_local, S): physical -> client dim over the client axes;
    logical -> local-batch dim over them."""
    if cfg.client_mode == "physical":
        return P(fsdp, None, None)
    return P(None, fsdp, None)
