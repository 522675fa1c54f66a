"""The ONE MM driver: init/step/run for every algorithm in the repo.

``step`` is Algorithm 2 with every federation concern read off a
``FederationSpec``; ``centralized_step`` is Algorithm 1 (SA-SSMM, the
n=1-silo degenerate case with no federation plumbing at all); ``run`` drives
either as a single ``lax.scan``-jitted loop with stacked-pytree metrics
(one XLA computation for the whole trajectory — no per-round Python
dispatch, no per-round host sync).

The legacy entry points (``core.sassmm.run``, ``core.fedmm.run/step``,
``core.naive.run/step``, ``core.fedmm_ot.step``/``fedadam_step``) are thin
shims over this module and are trajectory-identical to their historical
implementations: the host-side key chain (``key -> k_round, k_batch`` per
round), the A5/A4 key folds, and the arithmetic order of the update all
match the old loops operation for operation —
``tests/test_api_golden.py`` pins this against frozen copies.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core.compression import (_tree_bytes, verify_payload, weighted_sum,
                                zero_invalid_rows)
from ..core.surrogate import (tree_lerp, tree_sub, tree_sq_norm,
                              tree_sq_norm_ew)
from .problem import MMProblem, as_problem
from .schedule import resolve_schedule, schedule_length
from .spans import span
from .spec import FederationSpec, participation_draw

Pytree = Any

# stacked batches above this many bytes force the python-loop fallback
# (scan would materialize the whole trajectory's data on device)
SCAN_BATCH_BYTES_MAX = 1 << 30

CLIENT_MODES = ("vmap", "scan")

UPLINKS = ("gather", "reduce")

# (round_bytes, n_rounds, budget) triples already warned about — the scan
# fallback fires the warning ONCE per distinct situation, not on every
# ``run()`` call of a long sweep. An insertion-ordered dict with an LRU
# cap, NOT a bare set: a sweep over many distinct (bytes, rounds, budget)
# situations (e.g. a growing-batch schedule) would otherwise grow the
# dedupe set without bound for the life of the process.
_SCAN_FALLBACK_WARNED: "dict" = {}
_SCAN_FALLBACK_WARNED_MAX = 128


def _lru_put(cache: dict, key, value, max_size: int):
    """Insert or refresh ``key`` as the most recently used entry of an
    insertion-ordered dict, then drop the least recently used entries
    beyond ``max_size``."""
    cache.pop(key, None)
    cache[key] = value
    while len(cache) > max_size:
        del cache[next(iter(cache))]


class DriverState(NamedTuple):
    """Unified iterate: ``x`` is Shat_t (surrogate aggregation) or theta_t
    (parameter aggregation); ``v``/``v_i`` the control variates (empty
    pytrees when ``variates='off'``); ``aux`` problem-owned server state
    (e.g. the FedMM-OT conjugate potential); ``opt`` server-optimizer state
    (e.g. FedAdam's moments, or the FedAvgM momentum buffer when
    ``spec.server_momentum > 0``)."""
    x: Pytree
    v: Pytree
    v_i: Pytree
    aux: Pytree
    opt: Pytree
    step: jnp.ndarray


class CohortSlice(NamedTuple):
    """The per-round inputs for ONE cohort of clients, gathered by a
    scheduler (``repro.sched``) from its population arena. All leading
    dimensions are the cohort size C — never the population size.

    ``mask`` is the A5 participation mask for the cohort's clients
    (0.0 also for PADDED slots of a ragged last cohort, so padding
    contributes nothing to the aggregate or to ``comm_bytes``); ``mu``
    is the matching slice of the GLOBAL client weights (NOT renormalized
    — summing cohort partials then equals the full-population weighted
    reduce, pads zeroed); ``quant_keys`` the per-client A4 keys from the
    driver's shared key fold; ``v_i`` the cohort's control-variate slice
    (``()`` when variates are off); ``valid`` an optional real-client
    indicator (1.0 real / 0.0 padded) so per-client metric sums exclude
    padding — None means every slot is real; ``corrupt`` an optional bool
    vector flagging clients whose uplink payload is damaged in flight
    (the ``FaultSpec.corrupt`` draw) — requires a checksummed wire-format
    compressor, which detects the damage and drops the client; ``edge_ids``
    the cohort's slice of the population's STABLE client -> edge assignment
    (``Topology.edge_ids`` indexed by global id) — required under a
    two-tier topology, None otherwise."""
    mask: jnp.ndarray
    mu: jnp.ndarray
    quant_keys: jnp.ndarray
    v_i: Pytree = ()
    valid: Optional[jnp.ndarray] = None
    corrupt: Optional[jnp.ndarray] = None
    edge_ids: Optional[jnp.ndarray] = None


class CohortPartial(NamedTuple):
    """What one cohort contributes to a round: the masked mu-weighted
    partial aggregate (iterate dtype — summing these across cohorts with
    weight 1.0 is bit-identical to the single full-participation reduce),
    the updated control-variate slice to scatter back into the arena,
    the realized participation count, the measured uplink bytes, the
    per-client oracle-metric SUMS over the cohort's real clients (divide
    by n_total after summing cohorts to recover ``step``'s means), and
    the actual cross-mesh collective bytes (None off-mesh).

    Under a TWO-TIER topology ``agg`` is the ``(n_edges,)``-stacked f32
    per-edge partial instead (the tier boundary is NONLINEAR when the
    compressor re-encodes, so cohorts must sum edge-wise BEFORE the
    boundary) — the scheduler finalizes it at landing via
    ``finalize_partial``; ``comm_bytes`` stays uplink-only, backbone
    bytes are billed once per landing."""
    agg: Pytree
    v_i: Pytree
    n_active: jnp.ndarray
    comm_bytes: jnp.ndarray
    metric_sums: dict
    collective_payload_bytes: Optional[float]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def variates_at_init(problem: MMProblem, x0, client_batches,
                     param_space: bool = False):
    """V_{0,i} = h_i(Shat_0) (Theorem 1's heterogeneity-robust warm start):
    one full local expectation per client. With ``param_space=True`` the
    warm start lives in Theta-space like the naive iterate:
    V_{0,i} = T(Sbar_i(theta_0)) - theta_0 (the eq.-21 local MM drift)."""
    theta0 = x0 if param_space else problem.T(x0)

    def one(batch):
        s_i = problem.s_bar(batch, theta0)
        out = problem.T(s_i) if param_space else s_i
        return tree_sub(out, x0)

    return jax.vmap(one)(client_batches)


def init(problem, x0, spec: FederationSpec, v0_i=None,
         init_batches=None) -> DriverState:
    problem = as_problem(problem)
    if spec.use_variates:
        if v0_i is None and spec.variates == "at-init":
            if init_batches is None:
                raise ValueError("variates='at-init' needs init_batches "
                                 "(an (n, ...) pytree of client data)")
            v0_i = variates_at_init(problem, x0, init_batches,
                                    spec.aggregation == "parameter")
        if v0_i is None:
            v0_i = jax.tree.map(
                lambda x: jnp.zeros((spec.n_clients,) + x.shape, x.dtype), x0)
        mu = spec.client_weights()
        v = jax.tree.map(lambda x: jnp.tensordot(mu, x, axes=1), v0_i)
    else:
        v, v0_i = (), ()
    aux = problem.init_aux() if problem.init_aux is not None else ()
    if spec.server_momentum > 0.0:
        if problem.server_opt is not None or problem.init_opt is not None:
            raise ValueError(
                "server_momentum and a custom MMProblem.server_opt/init_opt "
                "both claim the server update (and the opt state slot) — "
                "fold the momentum into your server_opt instead")
        # FedAvgM heavy-ball buffer m_0 = 0, living in the opt slot
        opt = jax.tree.map(jnp.zeros_like, x0)
    else:
        opt = problem.init_opt(x0) if problem.init_opt is not None else ()
    return DriverState(x=x0, v=v, v_i=v0_i, aux=aux, opt=opt,
                       step=jnp.asarray(0))


def centralized_init(problem, s0) -> DriverState:
    del problem
    return DriverState(x=s0, v=(), v_i=(), aux=(), opt=(),
                       step=jnp.asarray(0))


# ---------------------------------------------------------------------------
# step
# ---------------------------------------------------------------------------

def _acc(x):
    """Arithmetic dtype of a leaf: at least float32, so that a scalar
    coefficient is never rounded to a bf16 leaf's precision first."""
    return jnp.promote_types(x.dtype, jnp.float32)


def _variate_update(v, q, coef):
    """Lines 8/11/17: V <- V + coef * q, leaf-wise (coef = alpha/p),
    computed in ``_acc`` and rounded once to V's dtype. The ONE definition
    every client-stage branch shares — scan body, reduce stage and gather
    tail must apply the identical update rule."""
    return jax.tree.map(
        lambda vv, dq: (vv.astype(_acc(vv)) + coef * dq.astype(_acc(vv)))
        .astype(vv.dtype), v, q)


def _weighted_reduce(w, q):
    """The mu-weighted client reduction (line 13), dtype-preserving: the
    fixed-order sum accumulates in f32 (``weighted_sum``) and casts back
    ONCE, so bf16 leaves are not silently upcast."""
    return jax.tree.map(lambda x: weighted_sum(w, x).astype(x.dtype), q)


# a private fold_in lane for the per-round tier-boundary keys: deriving
# them off the round key consumes NOTHING from the legacy split chain, so
# flat trajectories stay bit-identical to the pre-topology driver
_EDGE_KEY_SALT = 0x45444745  # "EDGE"


def _edge_keys(key, n_edges):
    return jax.random.split(jax.random.fold_in(key, _EDGE_KEY_SALT),
                            n_edges)


def _edge_partials(q, w, edge_ids, n_edges):
    """Per-edge mu-weighted partial sums in the accumulation dtype (f32):
    the within-edge half of the two-tier reduction, grouped by the STABLE
    global client -> edge assignment. An explicit segment-sum, not a mesh
    position: it stays correct under any cohorting of the population."""
    def one(x):
        wcol = w.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1))
        return jax.ops.segment_sum(x.astype(jnp.float32) * wcol, edge_ids,
                                   num_segments=n_edges)
    return jax.tree.map(one, q)


def tier_boundary(spec: FederationSpec, edge_parts, edge_keys, x_ref):
    """Cross the edge -> root tier: optionally re-enter the wire format
    per edge (``Compressor.reencode`` with a fresh per-tier key — digests
    are RE-STAMPED, so each hop is independently verifiable and billed),
    measure the ACTUAL backbone buffers, sum over edges, and downcast
    ONCE to the iterate dtype (the PR-5 discipline applied to tier two).

    ``edge_parts`` is an ``(n_edges,)``-stacked f32 partial per leaf.
    Returns ``(agg, backbone_bytes)``; ``backbone_bytes`` is a static
    Python float (buffer shapes are static under jit)."""
    comp = spec.compressor
    if spec.topology.reencode:
        payload = jax.vmap(comp.reencode)(edge_keys, edge_parts)
        backbone_bytes = float(_tree_bytes(payload))
        edge_parts = comp.decode(payload)
    else:
        backbone_bytes = float(_tree_bytes(edge_parts))
    agg = jax.tree.map(lambda e, x: jnp.sum(e, axis=0).astype(x.dtype),
                       edge_parts, x_ref)
    return agg, backbone_bytes


def finalize_partial(spec: FederationSpec, agg, key, x_ref):
    """The scheduler's landing-time tier crossing: a two-tier cohort
    partial accumulates as the ``(n_edges,)``-stacked f32 per-edge sums
    (reencode is nonlinear — cohorts must sum BEFORE the boundary), and
    this finalizes the accumulated partial with the landing round's edge
    keys. Flat partials pass through with zero backbone bytes. Returns
    ``(agg, backbone_bytes)``."""
    topo = spec.topology
    if not topo.is_two_tier:
        return agg, 0.0
    return tier_boundary(spec, agg, _edge_keys(key, topo.n_edges), x_ref)


# the per-client flag of a non-finite oracle output, carried with the
# problem's own per-client metrics and reported by ``step`` as n_nonfinite
_NONFINITE = "_nonfinite"


def _client_stage(problem: MMProblem, spec: FederationSpec, view, x_ref,
                  client_batches, v_i, quant_keys, mask, mu, *,
                  mesh, client_axis, client_mode, uplink, corrupt=None,
                  edge_ids=None, edge_keys=None, tier_finalize=True):
    """The client half of Algorithm 2, shared by the full-population
    ``step`` and the cohort path: oracles (+ optional per-client metrics),
    drift/A4 compression, the uplink (vmap stack, sequential scan, or one
    of the two shard_map collectives), masking, V_i update, and the
    mu-weighted reduction. Operates on whatever leading client dimension
    the inputs carry — ``spec.n_clients`` in ``step``, the cohort size C
    under a scheduler — so the mesh divisibility constraint applies to
    the LOCAL count, not the population.

    Returns ``(agg, v_i_new, cmetrics, wire_bytes_client,
    collective_bytes, n_survive, backbone_bytes)``: the masked
    mu-weighted aggregate (iterate dtype), the updated variate slice,
    stacked per-client oracle metrics, the measured per-client uplink
    bytes (None for analytic compressors), the actual cross-mesh
    collective bytes (None off-mesh), the count of active clients whose
    payload SURVIVED wire verification (== ``sum(mask)`` without a
    checksummed compressor), and the measured edge -> root backbone
    bytes (None for the flat topology).

    Topology: under ``spec.topology.two_tier`` the mu-weighted reduction
    happens in two tiers — per-edge f32 partials (grouped by the stable
    ``edge_ids`` assignment, or by the ``(edge, client)`` mesh axes on
    the fused reduce path), then the ``tier_boundary`` crossing
    (optional ``Compressor.reencode`` requantization with ``edge_keys``,
    ONE cross-edge reduction, ONE downcast). ``tier_finalize=False``
    (the cohort path) returns the ``(n_edges,)``-stacked f32 per-edge
    partial instead, to be accumulated across cohorts and finalized at
    landing via ``finalize_partial``.

    Wire integrity: when the compressor was built with ``checksum=True``
    every decode path first recomputes each client's payload digest
    (``verify_payload``), ZEROES the failing clients' buffers before
    dequantize (corrupted scale bits can decode to NaN — a NaN times a
    zero weight would survive any masked reduction), and excludes them
    from ``n_survive`` — the round degrades exactly as if those clients
    had not been in the participation draw. ``corrupt`` optionally
    injects deterministic damage (the ``FaultSpec.corrupt`` draw) into
    the flagged clients' payloads between encode and verify."""
    p, alpha = spec.participation, spec.alpha
    param_space = spec.aggregation == "parameter"
    use_v = spec.use_variates
    comp = spec.compressor
    use_wire = comp.encode is not None
    verify = use_wire and comp.checksum
    if corrupt is not None and not verify:
        raise ValueError("corrupt flags need a checksummed wire-format "
                         "compressor (block_quant(..., checksum=True)) — "
                         "undetected damage would poison the aggregate")
    topo = spec.topology
    two_tier = topo.is_two_tier
    if two_tier and edge_ids is None:
        raise ValueError("a two-tier topology needs the per-client edge "
                         "assignment (edge_ids) for this client slice")
    n_local = mask.shape[0]
    if mesh is not None:
        if two_tier:
            shard = mesh.shape[client_axis] * mesh.shape[topo.edge_axis]
            if n_local % shard != 0:
                raise ValueError(
                    f"the client-stage leading dim ({n_local} clients) "
                    f"must divide evenly over the ('{topo.edge_axis}', "
                    f"'{client_axis}') mesh axes (total size {shard})")
        elif n_local % mesh.shape[client_axis] != 0:
            raise ValueError(
                f"the client-stage leading dim ({n_local} clients) must "
                f"divide evenly over the '{client_axis}' mesh axis "
                f"(size {mesh.shape[client_axis]})")

    def client_update(batch, v_c, qkey, view, x_ref):
        """One client's round: oracle (+ optional metrics), drift, wire
        encode. Returns (payload, per-client metrics dict). The server
        state comes in as arguments, never by closure: a shard_map body
        that closes over a committed, mesh-sharded array fails to
        differentiate on jax 0.9 (its zeros carry the Auto mesh into the
        Manual one)."""
        with jax.named_scope("fedmm.client_oracle"):
            if problem.s_bar_metrics is not None:
                s_i, cm = problem.s_bar_metrics(batch, view)   # line 6
            else:
                s_i, cm = problem.s_bar(batch, view), {}
            # a non-finite oracle output would reach the wire, whose
            # zero-scale guard turns each NaN group into zeros: flag it
            finite = jnp.stack([jnp.all(jnp.isfinite(x))
                                for x in jax.tree.leaves(s_i)])
            cm = dict(cm, **{_NONFINITE: jnp.logical_not(
                jnp.all(finite)).astype(jnp.float32)})
            out = problem.T(s_i) if param_space else s_i   # eq. 21 local MM
        if spec.delta == "oracle":
            d = out                                        # raw payload
        else:
            d = tree_sub(out, x_ref)                       # line 7 (drift)
            if use_v:
                d = tree_sub(d, v_c)
        with jax.named_scope("fedmm.wire_encode"):
            if use_wire:
                return comp.encode(qkey, d), cm            # line 9: wire fmt
            return comp.apply(qkey, d), cm                 # line 9 (A4)

    def upd(batch, v_c, qkey, view, x_ref):
        return client_update(batch, v_c if use_v else None, qkey, view,
                             x_ref)

    def _mask_q(x, m):
        # dtype-preserving: never let an f32 mask upcast a bf16 payload
        return x * m.astype(x.dtype)

    kind = spec.faults.corrupt_kind if spec.faults is not None else "flip"

    def _checked(payload_s, cflags):
        """Damage (optional) then verify a stacked/unbatched payload:
        returns the buffer-zeroed payload and the per-client ok flags.
        Zeroing BEFORE decode is load-bearing — corrupted scale bits
        dequantize to NaN, and NaN times a zero weight is still NaN."""
        if cflags is not None:
            from ..faults.injector import corrupt_payload
            payload_s = corrupt_payload(payload_s, cflags, kind)
        ok = verify_payload(payload_s)
        return zero_invalid_rows(payload_s, ok), ok

    collective_bytes = None
    backbone_bytes = None
    if client_mode == "scan":
        # sequential clients: one oracle/quantize transient live at a time;
        # the mu_i-weighted aggregate accumulates in the iterate's dtype
        # (flat), or edge-wise in the f32 accumulation dtype (two-tier —
        # the tier boundary does the ONE downcast)
        def body_core(agg_sum, cb, v_c, qk, mu_c, m_c, cf, e_c=None):
            payload_c, cm = upd(cb, v_c, qk, view, x_ref)
            surv_c = m_c
            with jax.named_scope("fedmm.wire_decode"):
                if verify:
                    payload_c, ok = _checked(
                        payload_c, cf if corrupt is not None else None)
                    surv_c = m_c * ok.astype(m_c.dtype)
                q_c = comp.decode(payload_c) if use_wire else payload_c
            with jax.named_scope("fedmm.aggregate"):
                q_c = jax.tree.map(lambda x: _mask_q(x, m_c), q_c)
                v_c_new = (_variate_update(v_c, q_c, alpha / p)
                           if use_v else ())
                if two_tier:
                    agg_sum = jax.tree.map(
                        lambda a, x: a.at[e_c].add(
                            mu_c * x.astype(jnp.float32)),
                        agg_sum, q_c)
                else:
                    agg_sum = jax.tree.map(
                        lambda a, x: a + (mu_c * x).astype(a.dtype),
                        agg_sum, q_c)
            return agg_sum, v_c_new, cm, surv_c
        if two_tier:
            zeros = jax.tree.map(
                lambda x: jnp.zeros((topo.n_edges,) + x.shape, jnp.float32),
                x_ref)
        else:
            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                                 x_ref)
        eids = (jnp.asarray(edge_ids, jnp.int32),) if two_tier else ()
        if verify:
            cflags = (corrupt if corrupt is not None
                      else jnp.zeros((n_local,), jnp.bool_))

            def body(carry, xs):
                agg_sum, surv = carry
                agg_sum, v_c_new, cm, surv_c = body_core(agg_sum, *xs)
                return (agg_sum, surv + surv_c), (v_c_new, cm)
            (agg, n_survive), (v_i_new, cmetrics) = jax.lax.scan(
                body, (zeros, jnp.float32(0.0)),
                (client_batches, v_i, quant_keys, mu, mask, cflags) + eids)
        else:
            def body(agg_sum, xs):
                cb, v_c, qk, mu_c, m_c, *e_c = xs
                agg_sum, v_c_new, cm, _ = body_core(
                    agg_sum, cb, v_c, qk, mu_c, m_c, None, *e_c)
                return agg_sum, (v_c_new, cm)
            agg, (v_i_new, cmetrics) = jax.lax.scan(
                body, zeros,
                (client_batches, v_i, quant_keys, mu, mask) + eids)
            n_survive = jnp.sum(mask)
        if two_tier and tier_finalize:
            with jax.named_scope("fedmm.aggregate"):
                agg, backbone_bytes = tier_boundary(spec, agg, edge_keys,
                                                    x_ref)
        # static per-client wire bytes via eval_shape (no stacked payload
        # exists on this path)
        wire_bytes_client = comp.wire_bytes(x_ref) if use_wire else None
    elif mesh is not None and uplink == "reduce":
        # the FUSED uplink: each device touches only its own clients —
        # decode + mask + mu-weighted partial-reduce run shard-locally,
        # v_i updates on the local slice, and a single psum of the
        # model-shaped partial aggregate crosses the mesh. The gathered
        # n-client payload stack of the "gather" path never exists.
        # Two-tier: the partial-reduce psum is EDGE-SCOPED (psum over the
        # client axis of the 2-D (edge, client) mesh reduces within each
        # edge group), the tier boundary optionally re-encodes each
        # edge's partial, and ONE cross-edge psum crosses the backbone.
        if two_tier and not tier_finalize:
            raise ValueError(
                "two-tier uplink='reduce' groups clients by mesh position; "
                "a streamed cohort's edge membership is data-dependent — "
                "use uplink='gather' under the scheduler")
        cspec = (PartitionSpec((topo.edge_axis, client_axis)) if two_tier
                 else PartitionSpec(client_axis))
        reenc = two_tier and topo.reencode
        ek_args = (edge_keys,) if reenc else ()
        ek_specs = (PartitionSpec(topo.edge_axis),) if reenc else ()
        measured = {}

        def stage_local(view_l, xr_l, cb, vi, qk, mu_l, m_l, cf_l):
            payload_l, cm = jax.vmap(upd, in_axes=(0, 0, 0, None, None))(
                cb, vi, qk, view_l, xr_l)
            n_l = m_l.shape[0]
            m_eff = m_l
            if verify:
                # shard-local verification: each device vets only its own
                # clients' payloads; zeroed rows reduce to exact zeros on
                # every path below, so only the survivor COUNT needs an
                # extra collective
                with jax.named_scope("fedmm.wire_decode"):
                    payload_l, ok_l = _checked(payload_l, cf_l)
                m_eff = m_l * ok_l.astype(m_l.dtype)

            def msk(x):
                return _mask_q(x, m_l.reshape((n_l,) + (1,) * (x.ndim - 1)))

            # partials stay in the ACCUMULATION dtype (f32 under f32
            # weights) until after the psum: rounding each device's
            # partial to a bf16 leaf dtype before summing axis_size of
            # them would lose bf16-epsilon per round — the gather path
            # does one f32 tensordot over all n clients and casts once,
            # and the reduce path must match that discipline
            if use_wire and comp.decode_reduce is not None and not use_v:
                # fold the mask into the weights (exact: the mask is
                # 0.0/1.0) and fuse dequantize into the accumulation via
                # the COMPRESSOR's own reduce (which carries its kernel
                # dispatch policy) — the decoded local f32 stack never
                # materializes. fused=True: this IS a per-device
                # shard_map body.
                vi_new = ()
                with jax.named_scope("fedmm.wire_decode"):
                    part = comp.decode_reduce(payload_l, mu_l * m_l,
                                              fused=True)
            else:
                # the variates need the decoded local stack anyway
                # (O(n/axis_size * model) — still never the full n);
                # wire compressors without a fused reduce decode first;
                # raw payloads reduce directly
                with jax.named_scope("fedmm.wire_decode"):
                    q_l = comp.decode(payload_l) if use_wire else payload_l
                with jax.named_scope("fedmm.aggregate"):
                    q_l = jax.tree.map(msk, q_l)
                    vi_new = (_variate_update(vi, q_l, alpha / p) if use_v
                              else ())
                    part = jax.tree.map(
                        lambda x: weighted_sum(mu_l, x), q_l)
            # the ACTUAL per-device psum operand (static under jit): the
            # model-shaped partial aggregate — what really crosses the
            # mesh, measured here rather than modeled
            measured["psum_operand_bytes"] = _tree_bytes(part)
            return part, vi_new, cm, jnp.sum(m_eff)

        cflags = (corrupt if verify and corrupt is not None
                  else jnp.zeros((n_local,), jnp.bool_))

        # the survivor count crosses every mesh axis the clients are
        # sharded over — a tuple axis name under the two-tier layout
        ns_axes = ((client_axis, topo.edge_axis) if two_tier
                   else client_axis)

        def client_stage(view_l, xr_l, cb, vi, qk, mu_l, m_l, cf_l, *ek):
            part, vi_new, cm, ns_l = stage_local(
                view_l, xr_l, cb, vi, qk, mu_l, m_l,
                cf_l if verify and corrupt is not None else None)
            with jax.named_scope("fedmm.aggregate"):
                # the within-edge (flat: cross-mesh) reduce, in the
                # accumulation dtype
                agg_l = jax.tree.map(
                    lambda x: jax.lax.psum(x, client_axis), part)
                if two_tier:
                    if reenc:
                        # tier boundary: requantize THIS edge's partial
                        # with its per-tier key (fresh digests re-stamped)
                        # and measure what actually crosses the backbone
                        # — then decode back to the f32 accumulation
                        # dtype for the cross-edge psum
                        pay_e = comp.reencode(ek[0][0], agg_l)
                        measured["backbone_edge_bytes"] = _tree_bytes(pay_e)
                        agg_l = comp.decode(pay_e)
                    else:
                        measured["backbone_edge_bytes"] = _tree_bytes(agg_l)
                    # ONE cross-edge psum crosses the backbone
                    agg_l = jax.tree.map(
                        lambda x: jax.lax.psum(x, topo.edge_axis), agg_l)
                ns = (jax.lax.psum(ns_l, ns_axes) if verify
                      else jnp.float32(0.0))
            return agg_l, vi_new, cm, ns

        agg, v_i_new, cmetrics, n_survive = jax.shard_map(
            client_stage, mesh=mesh,
            in_specs=(PartitionSpec(),) * 2 + (cspec,) * 6 + ek_specs,
            out_specs=(PartitionSpec(), cspec, cspec, PartitionSpec()),
            check_vma=False)(view, x_ref, client_batches, v_i, quant_keys,
                             mu, mask, cflags, *ek_args)
        if not verify:
            n_survive = jnp.sum(mask)
        # the ONE downcast back to the iterate dtype, AFTER the collective
        with jax.named_scope("fedmm.aggregate"):
            agg = jax.tree.map(lambda a, x: a.astype(x.dtype), agg, x_ref)
        collective_bytes = float(measured["psum_operand_bytes"])
        if two_tier:
            # total backbone traffic: every edge's tier-boundary buffer
            # enters the cross-edge collective each round
            backbone_bytes = (float(measured["backbone_edge_bytes"])
                              * topo.n_edges)
        # static per-client wire bytes via eval_shape (no stacked payload
        # survives the shard_map on this path)
        wire_bytes_client = comp.wire_bytes(x_ref) if use_wire else None
    else:
        if mesh is not None:
            # two-tier: the stacked client axis shards over BOTH mesh axes
            # edge-major (device (e, c) owns block e*C + c), so the tiled
            # gather over the tuple axis reconstructs global client order
            # — the same contiguous edge-major order Topology.edge_ids
            # assigns
            gaxes = ((topo.edge_axis, client_axis) if two_tier
                     else client_axis)
            cspec = PartitionSpec(gaxes)

            def client_stage(view_l, xr_l, cb, vi, qk):
                # each device slice runs its local clients...
                local = jax.vmap(upd, in_axes=(0, 0, 0, None, None))(
                    cb, vi, qk, view_l, xr_l)
                # ...and the uplink collective moves the ENCODED buffers:
                # packed codes + per-group scales cross the mesh boundary
                with jax.named_scope("fedmm.aggregate"):
                    return jax.tree.map(
                        lambda x: jax.lax.all_gather(x, gaxes, axis=0,
                                                     tiled=True), local)

            # check_vma=False: no varying-axis type checks in the body;
            # the tiled all_gather makes every output replicated over
            # client_axis by construction
            payload, cmetrics = jax.shard_map(
                client_stage, mesh=mesh,
                in_specs=(PartitionSpec(),) * 2 + (cspec,) * 3,
                out_specs=PartitionSpec(),
                check_vma=False)(view, x_ref, client_batches, v_i,
                                 quant_keys)
            # the gathered stack's actual buffer bytes (static under jit):
            # for wire compressors this is n * payload_bytes — asserted in
            # tests/test_sharded_driver.py, not just logged
            collective_bytes = float(_tree_bytes(payload))
        else:
            payload, cmetrics = jax.vmap(
                upd, in_axes=(0, 0, 0, None, None))(
                client_batches, v_i, quant_keys, view, x_ref)
        n_survive = jnp.sum(mask)
        if use_wire:
            # actual uplink bytes of ONE client's payload, read off the
            # stacked encoded buffers (shapes are static under jit)
            wire_bytes_client = comp.encoded_bytes(payload) / n_local
            with jax.named_scope("fedmm.wire_decode"):
                if verify:
                    # server-side verification of the (gathered) stack; a
                    # failing client degrades the round exactly like an
                    # equivalent participation draw that excluded it
                    payload, ok = _checked(payload, corrupt)
                    n_survive = jnp.sum(mask * ok.astype(mask.dtype))
                q = comp.decode(payload)   # batched; fuses into the reduce
        else:
            wire_bytes_client = None
            q = payload
        with jax.named_scope("fedmm.aggregate"):
            # non-participating clients send nothing / keep V_i
            q = jax.tree.map(
                lambda x: _mask_q(x, mask.reshape((n_local,)
                                                  + (1,) * (x.ndim - 1))),
                q)

            # client control variates (lines 8/11) + aggregation (13)
            v_i_new = _variate_update(v_i, q, alpha / p) if use_v else ()
            if two_tier:
                # within-edge tier: per-edge f32 partials by the stable
                # assignment (q is already masked; mu carries the weights)
                parts = _edge_partials(q, mu,
                                       jnp.asarray(edge_ids, jnp.int32),
                                       topo.n_edges)
                if tier_finalize:
                    agg, backbone_bytes = tier_boundary(spec, parts,
                                                        edge_keys, x_ref)
                else:
                    agg = parts
            else:
                agg = _weighted_reduce(mu, q)
    return (agg, v_i_new, cmetrics, wire_bytes_client, collective_bytes,
            n_survive, backbone_bytes)


def _server_apply(problem: MMProblem, spec: FederationSpec,
                  state: DriverState, agg, v_i_new, n_active, gamma):
    """The server half of Algorithm 2: normalization (line 13's 1/p or the
    realized n/|A_t|), the control-variate shift h = V + h, the server
    update (custom server_opt, FedAvgM heavy-ball momentum, or the plain
    SA step + projection), the server variate update (line 17), and the
    problem-owned aux update. ``agg`` is the masked mu-weighted aggregate
    over the WHOLE population — either straight from ``_client_stage`` or
    a (staleness-weighted) sum of ``CohortPartial.agg`` terms.

    Returns ``(new_state, h, aux_metrics)``."""
    with jax.named_scope("fedmm.server"):
        n, p, alpha = spec.n_clients, spec.participation, spec.alpha
        param_space = spec.aggregation == "parameter"
        use_v = spec.use_variates
        if spec.normalization == "realized":
            scale = n / jnp.maximum(n_active, 1.0)
            h = jax.tree.map(lambda a: (scale * a).astype(a.dtype), agg)
        else:
            h = jax.tree.map(
                lambda a: ((1.0 / p) * a.astype(_acc(a))).astype(a.dtype),
                agg)
        if use_v:
            h = jax.tree.map(lambda v, hh: v + hh.astype(v.dtype), state.v, h)

        # server update (lines 15-16): SA step + projection, unless the problem
        # supplies its own server optimizer (e.g. FedAdam) or the spec asks
        # for FedAvgM heavy-ball momentum on the aggregated direction
        if problem.server_opt is not None:
            if spec.server_momentum > 0.0:
                raise ValueError(
                    "server_momentum and a custom MMProblem.server_opt both "
                    "claim the server update — fold the momentum into your "
                    "server_opt instead")
            x_new, opt_new = problem.server_opt(state.x, h, gamma, state.opt)
        elif spec.server_momentum > 0.0:
            # m <- beta m + h (buffer keeps the iterate dtype),
            # x <- x + gamma m
            opt_new = jax.tree.map(
                lambda m, hh: (spec.server_momentum * m
                               + hh.astype(m.dtype)).astype(m.dtype),
                state.opt, h)
            x_new = jax.tree.map(
                lambda mm, xx: (gamma * mm.astype(xx.dtype)
                                + xx).astype(xx.dtype),
                opt_new, state.x)
            if not param_space:
                x_new = problem.project(x_new)
        else:
            x_new = jax.tree.map(
                lambda hh, xx: (gamma * hh.astype(xx.dtype)
                                + xx).astype(xx.dtype),
                h, state.x)
            if not param_space:
                x_new = problem.project(x_new)
            opt_new = state.opt

        # server control variate (line 17)
        v_new = (_variate_update(state.v, agg, alpha / p)
                 if use_v else ())

        # problem-owned server state (FedMM-OT line 16: conjugate update)
        if problem.server_step is not None:
            aux_new, aux_metrics = problem.server_step(state.aux, x_new)
        else:
            aux_new, aux_metrics = state.aux, {}
        new_state = DriverState(x=x_new, v=v_new, v_i=v_i_new, aux=aux_new,
                                opt=opt_new, step=state.step + 1)
        return new_state, h, aux_metrics


def _broadcast_view(problem: MMProblem, spec: FederationSpec,
                    state: DriverState):
    """Line 4: the view broadcast to clients — the mirror image T(Shat)
    (surrogate mode), the iterate itself (parameter mode), or the
    problem's custom view hook."""
    with jax.named_scope("fedmm.view"):
        if spec.aggregation == "parameter":
            return state.x
        if problem.view is not None:
            return problem.view(state.x, state.aux)
        return problem.T(state.x)


def centralized_step(problem: MMProblem, state: DriverState, batch, gamma):
    """Algorithm 1 (SA-SSMM): oracle, SA blend, projection."""
    theta = problem.T(state.x)
    s_oracle = problem.s_bar(batch, theta)                 # line 2
    s_new = tree_lerp(state.x, s_oracle, gamma)            # line 3
    s_new = problem.project(s_new)
    drift = tree_sub(s_new, state.x)
    metrics = {"e_s": tree_sq_norm(drift) / (gamma ** 2)}  # E^s diagnostic
    return state._replace(x=s_new, step=state.step + 1), metrics


def step(problem: MMProblem, spec: FederationSpec, state: DriverState,
         client_batches, gamma, key, active=None, *,
         mesh=None, client_axis: str = "clients",
         client_mode: str = "vmap", uplink: str = "gather",
         drift_metric: bool = True, sanitize: bool = False,
         audit_keys=False,
         cohort: Optional[CohortSlice] = None,
         _comm_audit: bool = False):
    """One federated MM round (Algorithm 2, every axis of the spec applied).
    ``client_batches`` is a pytree with a leading client axis of size n.
    ``active`` optionally overrides the A5 draw with a precomputed (n,)
    bool/0-1 mask (callers that own their participation RNG stream).

    When the spec's compressor carries a wire format (``encode`` is set —
    the packed-code path of ``core/compression.py``), clients upload
    ENCODED payloads and the server aggregates in code space: the stacked
    n-client intermediate holding every client's update is the packed
    codes + per-group scales (``bits/8 + scale_bytes/g`` bytes per
    coordinate, ~1/4 of the f32 stack at b=8 and ~1/8 at b=4) and the
    dequantization fuses into the weighted reduction — the dequantized
    n-client f32 stack never exists as a vmap-boundary buffer. The
    ``comm_bytes`` metric is computed from the ACTUAL encoded buffer
    sizes, not an analytic model. ``decode . encode`` is bit-identical to
    ``apply``, so trajectories are unchanged (tests/test_api_golden.py).

    client_mode:
      * ``"vmap"`` (default) — all clients in one batched stage (the
        historical semantics; the n-client payload stack is live at the
        vmap boundary);
      * ``"scan"`` — clients run sequentially under ``lax.scan`` so only
        ONE client's oracle/quantize transients are live at a time (the
        LM trainer's "logical" client topology; constant memory in n).
        The weighted aggregate accumulates in the iterate's dtype, so
        scan and vmap trajectories agree to rounding, not bit-for-bit.

    mesh / client_axis — the SHARDED driver path: with a ``jax.sharding
    .Mesh`` whose ``client_axis`` dimension divides n, the client stage
    runs under ``shard_map`` — each device slice owns ``n / axis_size``
    clients and computes their oracles and quantizes locally. How the
    round crosses the mesh is the ``uplink`` knob:

      * ``uplink="gather"`` (default, the bit-identical golden path) —
        the uplink is an ``all_gather`` over the mesh axis **in code
        space**: the bytes that cross the device boundary are the
        ``PackedLeaf`` codes+scales buffers (raw payloads for non-wire
        compressors), never the dequantized f32 stack. Per-client keys
        are split OUTSIDE the shard_map from the same chain, the gather
        is tiled in client order, and decode/mask/aggregation run on the
        replicated gathered stack — the trajectory is BIT-IDENTICAL to
        the single-device path (tests/test_sharded_driver.py pins this
        on 8 fake CPU devices). Every device holds the full n-client
        payload stack: O(n * payload) memory per device. The static
        ``collective_payload_bytes`` metric reports the gathered buffer
        bytes (== n * ``Compressor.payload_bytes``).
      * ``uplink="reduce"`` (the fused collective) — each device
        decodes, masks and mu-weight-reduces ONLY its own clients'
        payloads inside the shard_map (fusing dequantize into the
        accumulation via the compressor's ``decode_reduce`` hook when
        the control variates don't need the decoded stack), updates its
        slice of ``v_i`` shard-locally, and the mesh is crossed by ONE
        ``psum`` of the model-shaped partial aggregate — per-device
        memory drops from O(n * payload) to O(n/axis_size * payload +
        model). Partials cross the mesh in the ACCUMULATION dtype (f32)
        and downcast to the iterate dtype once, after the collective —
        matching the gather path's single cast, so bf16 models don't
        round per device slice. The psum's f32 reduction order differs
        from the gather path's tensordot over n clients, so ``"reduce"``
        trajectories match ``"gather"`` to allclose, not bit-for-bit
        (pinned in tests/test_sharded_driver.py).
        ``collective_payload_bytes`` reports the ACTUAL per-device psum
        operand bytes (the f32 partial aggregate).

    sanitize — the Layer-3 runtime sanitizer (``repro.analysis.runtime``):
    threads ``jax.experimental.checkify`` NaN / div-by-zero / OOB checks
    through the whole round (including vmap'd clients, the client scan and
    the shard_map body) and raises EAGERLY on the first tripped check,
    plus cross-checks the analytic ``Compressor.payload_bytes`` model
    against the bytes measured off the actual encoded buffers (the
    comm-bytes audit). checkify only ADDS error outputs — the primal
    math is untouched, so trajectories stay bit-identical (pinned in
    tests/test_sanitizer.py). Off by default and zero-cost when off.
    ``step(sanitize=True)`` throws eagerly so it must not itself be
    wrapped in ``jax.jit`` — jit your own wrapper around
    ``step(sanitize=False)``, or use ``run(..., sanitize=True)`` which
    checkifies the scanned trajectory correctly.

    cohort — the SCHEDULER path (``repro.sched``): instead of drawing
    participation and applying the server update, run the client stage on
    a provided ``CohortSlice`` (mask / mu slice / quant keys / v_i slice,
    leading dim = cohort size C, padding pre-zeroed) and return the
    ``CohortPartial`` — the masked mu-weighted partial aggregate plus its
    accounting — WITHOUT touching the iterate. The caller accumulates
    partials (optionally staleness-weighted) and lands them with
    ``apply_partial``. ``key``/``active``/``gamma`` are ignored on this
    path (the scheduler owns the key chain and the step size).

    audit_keys — the runtime key-trace audit (``repro.analysis.keytrace``):
    records every host-side ``jax.random`` call (splits, ``fold_in``
    lane derivations, consuming samplers) for the duration of the round
    and raises ``KeyReuseError`` at the second consumer if the same
    concrete key data is ever consumed twice. Pass ``True`` for the
    check alone, or a ``KeyAudit`` instance to inspect ``audit.report``
    afterwards. The wrappers delegate to the originals untouched, so
    the trajectory is BIT-IDENTICAL with the audit on (pinned in
    tests/test_keytrace.py). Off by default, zero-cost when off."""
    if audit_keys:
        from ..analysis.keytrace import resolve_audit
        audit = resolve_audit(audit_keys)
        with audit.activate():
            return step(problem, spec, state, client_batches, gamma, key,
                        active, mesh=mesh, client_axis=client_axis,
                        client_mode=client_mode, uplink=uplink,
                        drift_metric=drift_metric, sanitize=sanitize,
                        cohort=cohort, _comm_audit=_comm_audit)
    if cohort is not None:
        if sanitize:
            # checkify the cohort stage and throw EAGERLY (same contract
            # as the full-round sanitize path below: not for use inside
            # jax.jit — the scheduler wraps its own jitted closures via
            # analysis.runtime.checkified instead)
            from ..analysis.runtime import checkified

            def _plain_cohort(state, client_batches, cohort):
                return _cohort_partial(
                    problem, spec, state, client_batches, cohort,
                    mesh=mesh, client_axis=client_axis,
                    client_mode=client_mode, uplink=uplink)
            err, out = checkified(_plain_cohort)(state, client_batches,
                                                 cohort)
            err.throw()
            return out
        return _cohort_partial(problem, spec, state, client_batches, cohort,
                               mesh=mesh, client_axis=client_axis,
                               client_mode=client_mode, uplink=uplink)
    if sanitize:
        from ..analysis.runtime import checkified

        def _plain(state, client_batches, gamma, key, active):
            return step(problem, spec, state, client_batches, gamma, key,
                        active, mesh=mesh, client_axis=client_axis,
                        client_mode=client_mode, uplink=uplink,
                        drift_metric=drift_metric, _comm_audit=True)
        err, out = checkified(_plain)(state, client_batches, gamma, key,
                                      active)
        err.throw()
        return out
    n, p = spec.n_clients, spec.participation
    mu = spec.client_weights()
    param_space = spec.aggregation == "parameter"
    comp = spec.compressor
    use_wire = comp.encode is not None
    _validate_topology(mesh, client_axis, client_mode, uplink,
                       topology=spec.topology)
    edge_ids = edge_keys = None
    if spec.topology.is_two_tier:
        # the stable global assignment + per-round tier-boundary keys (a
        # private fold_in lane — the legacy key chain below is untouched)
        edge_ids = jnp.asarray(spec.topology.edge_ids(n), jnp.int32)
        edge_keys = _edge_keys(key, spec.topology.n_edges)

    view = _broadcast_view(problem, spec, state)           # line 4

    with jax.named_scope("fedmm.participation"):
        drawn, quant_keys = participation_draw(key, spec)  # A5
        if active is None:
            active = drawn
        corrupt = None
        if spec.faults is not None and spec.faults.any_injection:
            # fault-private fold_in lanes off the round key — the A5/A4
            # draws above are untouched, so a zero-probability FaultSpec
            # leaves the trajectory bit-identical to faults=None
            drop, corr = spec.faults.client_draw(key, n)
            # a dropped client's uplink never arrives: fold it into the
            # A5 mask so mu renormalizes per spec.normalization (no bytes
            # billed)
            active = jnp.logical_and(jnp.asarray(active).astype(jnp.bool_),
                                     jnp.logical_not(drop))
            corrupt = corr if spec.faults.corrupt > 0.0 else None
        mask = active.astype(jnp.float32)

    (agg, v_i_new, cmetrics, wire_bytes_client, collective_bytes,
     n_survive, backbone_bytes) \
        = _client_stage(problem, spec, view, state.x, client_batches,
                        state.v_i, quant_keys, mask, mu, mesh=mesh,
                        client_axis=client_axis, client_mode=client_mode,
                        uplink=uplink, corrupt=corrupt, edge_ids=edge_ids,
                        edge_keys=edge_keys)
    new_state, h, aux_metrics = _server_apply(
        problem, spec, state, agg, v_i_new, n_survive, gamma)
    x_new = new_state.x
    nonfinite = cmetrics.pop(_NONFINITE)

    comm = comp.round_metrics(state.x, p=p)
    per_client = (wire_bytes_client if use_wire
                  else comm["payload_bytes_per_client"])
    if _comm_audit and use_wire:
        # trace-time: wire_bytes_client is a static Python float (read off
        # the encoded buffer shapes), so a lying payload_bytes model fails
        # HERE with a diagnosable error, not downstream in a metrics plot
        from ..analysis.runtime import assert_comm_audit
        assert_comm_audit(
            comp, state.x, per_client,
            where=f"step(client_mode={client_mode!r}, uplink={uplink!r})")
    uplink_bytes = per_client * jnp.sum(mask)
    backbone = (jnp.float32(0.0) if backbone_bytes is None
                else jnp.asarray(backbone_bytes, jnp.float32))
    metrics = {
        # clients whose payload survived wire verification (== the A5
        # count without a checksummed compressor)
        "n_active": n_survive,
        # client -> edge uplink: actual encoded-buffer bytes on the wire
        # path, analytic otherwise; billed for every client that SENT —
        # a corrupt payload used the wire even though verification
        # dropped it
        "uplink_bytes": uplink_bytes,
        # edge -> root tier: actual tier-boundary buffer bytes (0 for
        # the flat topology — there is no second tier)
        "backbone_bytes": backbone,
        "comm_bytes": uplink_bytes + backbone,
        "omega_eff": jnp.asarray(comm["omega_eff"], jnp.float32),
        # participating clients whose oracle output held a NaN or an inf
        "n_nonfinite": jnp.sum(mask * nonfinite),
    }
    if drift_metric:
        # E^s (surrogate) / E^p (parameter) — the Section 6 diagnostics.
        # ``drift_metric=False`` (the LM trainer) skips the param-sized
        # drift temp + the raveling vdot, which would force replication
        # of sharded iterates.
        drift = tree_sub(x_new, state.x)
        metrics["e_p" if param_space else "e_s"] = \
            tree_sq_norm(drift) / (gamma ** 2)
    if not param_space:
        # elementwise square+sum (never ravels a sharded leaf)
        metrics["h_norm_sq"] = tree_sq_norm_ew(h)
    if collective_bytes is not None:
        metrics["collective_payload_bytes"] = jnp.asarray(collective_bytes,
                                                          jnp.float32)
    # per-client oracle metrics: mean over ALL clients (active or not).
    # Keys are static — collisions with driver metrics would silently
    # clobber the accounting, so they are an error, not an overwrite.
    dup = set(cmetrics) & set(metrics)
    if dup:
        raise ValueError(f"s_bar_metrics keys {sorted(dup)} collide with "
                         f"driver metrics — rename them in the problem")
    metrics.update({k: jnp.mean(v, axis=0) for k, v in cmetrics.items()})
    metrics.update(aux_metrics)
    return new_state, metrics


def _validate_topology(mesh, client_axis, client_mode, uplink,
                       topology=None):
    """The mesh/client-stage knob validation shared by ``step`` and the
    cohort path (the n-divisibility check lives in ``_client_stage``
    where the local client count is known)."""
    if client_mode not in CLIENT_MODES:
        raise ValueError(f"client_mode={client_mode!r} (want {CLIENT_MODES})")
    if uplink not in UPLINKS:
        raise ValueError(f"uplink={uplink!r} (want {UPLINKS})")
    if uplink == "reduce" and mesh is None:
        raise ValueError("uplink='reduce' is the cross-mesh partial-reduce "
                         "collective; it needs mesh= (without a mesh the "
                         "vmap path has no collective to fuse)")
    if mesh is not None:
        if client_mode != "vmap":
            raise ValueError("the sharded driver path shard_maps the "
                             "batched client stage; client_mode='scan' is "
                             "sequential — drop mesh= or use 'vmap'")
        if client_axis not in mesh.shape:
            raise ValueError(f"client_axis={client_axis!r} not an axis of "
                             f"the mesh (axes: {tuple(mesh.shape)})")
        if topology is not None and topology.is_two_tier:
            e_ax = topology.edge_axis
            if e_ax == client_axis:
                raise ValueError(
                    f"topology.edge_axis={e_ax!r} collides with "
                    f"client_axis — the two-tier mesh needs distinct "
                    f"(edge, client) axes")
            if e_ax not in mesh.shape:
                raise ValueError(
                    f"topology.edge_axis={e_ax!r} not an axis of the mesh "
                    f"(axes: {tuple(mesh.shape)}) — build a 2-D "
                    f"(edge, client) mesh (launch.mesh.make_edge_mesh)")
            if mesh.shape[e_ax] != topology.n_edges:
                raise ValueError(
                    f"mesh axis {e_ax!r} has size {mesh.shape[e_ax]} but "
                    f"the topology declares n_edges={topology.n_edges} — "
                    f"one mesh row per edge aggregator")


def _cohort_partial(problem: MMProblem, spec: FederationSpec,
                    state: DriverState, client_batches, cohort: CohortSlice,
                    *, mesh, client_axis, client_mode, uplink):
    """``step(..., cohort=...)``: the client stage on one cohort slice,
    returning the ``CohortPartial`` instead of applying it. The cohort's
    ``mu`` is the un-renormalized slice of the global weights, so summing
    the partial ``agg`` terms over a population's cohorts reproduces the
    full-population weighted reduce (bit-identical for a single
    full-participation cohort, reassociation-close otherwise)."""
    problem = as_problem(problem)
    _validate_topology(mesh, client_axis, client_mode, uplink,
                       topology=spec.topology)
    comp = spec.compressor
    use_wire = comp.encode is not None
    if spec.topology.is_two_tier and cohort.edge_ids is None:
        raise ValueError(
            "a two-tier topology needs CohortSlice.edge_ids — the "
            "cohort's slice of the population's stable client -> edge "
            "assignment (ClientPopulation.edge_ids)")
    mask = cohort.mask.astype(jnp.float32)
    c = mask.shape[0]
    checks = [("mu", cohort.mu), ("quant_keys", cohort.quant_keys)]
    if cohort.edge_ids is not None:
        checks.append(("edge_ids", cohort.edge_ids))
    for name, arr in checks:
        if jnp.shape(arr)[0] != c:
            raise ValueError(
                f"CohortSlice.{name} has leading dim "
                f"{jnp.shape(arr)[0]} != cohort size {c}")

    view = _broadcast_view(problem, spec, state)           # line 4
    # tier_finalize=False: a two-tier cohort returns the (n_edges,)-stacked
    # f32 per-edge partial — the tier boundary is nonlinear under reencode,
    # so cohorts sum edge-wise first and the scheduler finalizes at landing
    (agg, v_i_new, cmetrics, wire_bytes_client, collective_bytes,
     n_survive, _) \
        = _client_stage(problem, spec, view, state.x, client_batches,
                        cohort.v_i, cohort.quant_keys, mask, cohort.mu,
                        mesh=mesh, client_axis=client_axis,
                        client_mode=client_mode, uplink=uplink,
                        corrupt=cohort.corrupt, edge_ids=cohort.edge_ids,
                        tier_finalize=False)
    comm = comp.round_metrics(state.x, p=spec.participation)
    per_client = (wire_bytes_client if use_wire
                  else comm["payload_bytes_per_client"])
    nonfinite = cmetrics.pop(_NONFINITE)
    if cohort.valid is None:
        metric_sums = {k: jnp.sum(v, axis=0) for k, v in cmetrics.items()}
    else:
        # padded slots duplicate a real client's batch — their oracle
        # metrics must not count toward the population means
        valid = cohort.valid.astype(jnp.float32)
        metric_sums = {
            k: jnp.sum(v * valid.reshape((c,) + (1,) * (v.ndim - 1)),
                       axis=0)
            for k, v in cmetrics.items()}
    # a count, not a mean: the scheduler sums it over the round's cohorts
    # (padded slots carry a zero mask)
    metric_sums[_NONFINITE] = jnp.sum(mask * nonfinite)
    return CohortPartial(
        # wire-verification survivors (== sum(mask) without checksums):
        # a corrupt client is excluded from the normalization count...
        agg=agg, v_i=v_i_new, n_active=n_survive,
        # ...but BILLED — it used the wire. The mask is already 0.0 on
        # padded slots, so ragged cohorts bill exactly the real active
        # clients' uplink bytes
        comm_bytes=per_client * jnp.sum(mask),
        metric_sums=metric_sums,
        collective_payload_bytes=collective_bytes)


def apply_partial(problem: MMProblem, spec: FederationSpec,
                  state: DriverState, agg, n_active, gamma, *,
                  drift_metric: bool = True, sanitize: bool = False):
    """Land an accumulated surrogate partial: the server half of ``step``
    for a scheduler that built ``agg`` by summing (possibly
    staleness-weighted) ``CohortPartial.agg`` terms over the population.
    ``n_active`` is the total realized participation count of the
    contributing cohorts (the 'realized' normalization divides by it).
    ``state.v_i`` passes through untouched — cohort variate slices live
    in the scheduler's population arena, not in the ``DriverState``.

    ``sanitize=True`` checkifies the server update (NaN / div-by-zero /
    OOB) and throws EAGERLY — same contract as ``step(sanitize=True)``:
    don't wrap it in ``jax.jit`` yourself; the scheduler checkifies its
    jitted landing closure via ``analysis.runtime.checkified``.

    Returns ``(new_state, metrics)`` with the server-side metrics
    (``n_active``, ``omega_eff``, ``e_s``/``e_p``, ``h_norm_sq``, aux);
    the scheduler merges in the cohorts' comm accounting."""
    if sanitize:
        from ..analysis.runtime import checkified

        def _plain(state, agg, n_active, gamma):
            return apply_partial(problem, spec, state, agg, n_active,
                                 gamma, drift_metric=drift_metric)
        err, out = checkified(_plain)(state, agg, n_active, gamma)
        err.throw()
        return out
    problem = as_problem(problem)
    param_space = spec.aggregation == "parameter"
    n_active = jnp.asarray(n_active, jnp.float32)
    new_state, h, aux_metrics = _server_apply(
        problem, spec, state, agg, state.v_i, n_active, gamma)
    comm = spec.compressor.round_metrics(state.x, p=spec.participation)
    metrics = {
        "n_active": n_active,
        "omega_eff": jnp.asarray(comm["omega_eff"], jnp.float32),
    }
    if drift_metric:
        drift = tree_sub(new_state.x, state.x)
        metrics["e_p" if param_space else "e_s"] = \
            tree_sq_norm(drift) / (gamma ** 2)
    if not param_space:
        metrics["h_norm_sq"] = tree_sq_norm_ew(h)
    metrics.update(aux_metrics)
    return new_state, metrics


# ---------------------------------------------------------------------------
# run — the scan-jitted trajectory driver
# ---------------------------------------------------------------------------

def _stack_batches(batch_list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list)


@functools.partial(jax.jit, static_argnums=1)
def _key_chain(key, n_rounds):
    """The host key chain as one program: per round
    ``key, k_round, k_batch = split(key, 3)``, scanned, returning the
    stacked ``(round_keys, batch_keys)``. Threefry is integer arithmetic,
    so the bits equal the eager chain's; jit keeps one program per
    ``n_rounds`` and key aval."""
    def body(k, _):
        k, k_round, k_batch = jax.random.split(k, 3)
        return k, (k_round, k_batch)
    _, keys = jax.lax.scan(body, key, length=n_rounds)
    return keys


def _round_keys(key, n_rounds):
    """``run``'s per-round ``(round_keys, batch_keys)``: the round keys
    stacked, the batch keys a per-round list. One compiled ``_key_chain``
    call, unstacked in a few chunked dispatches; eager, one split a
    round, under an active ``KeyAudit``, whose patched ``split`` must see
    concrete keys. Records ``/fedmm/run/keys/compiled`` or
    ``/fedmm/run/keys/eager``."""
    if getattr(jax.random.split, "_repro_key_audit", False):
        jax.monitoring.record_event("/fedmm/run/keys/eager")
        round_keys, batch_keys = [], []
        for _ in range(n_rounds):
            key, k_round, k_batch = jax.random.split(key, 3)
            round_keys.append(k_round)
            batch_keys.append(k_batch)
        return jnp.stack(round_keys), batch_keys
    jax.monitoring.record_event("/fedmm/run/keys/compiled")
    round_keys, batch_keys = _key_chain(key, n_rounds)
    # iterating the array unstacks it in chunks of 100 rounds; indexing
    # it once per round would dispatch n_rounds times
    return round_keys, list(batch_keys)


class _Trajectory(NamedTuple):
    """Everything that decides the traced program of a federated
    ``run(scan=True)``: the static half of its cache key. The arrays
    (initial state, schedule, round keys, batches, eval batch) are the
    program's arguments."""
    problem: MMProblem
    spec: FederationSpec
    n_rounds: int
    mesh: Any
    client_axis: str
    client_mode: str
    uplink: str
    sanitize: bool
    track_mirror: bool      # already False under parameter aggregation
    diag: Any               # (name, fn) or None
    eval_every: int
    static: bool            # one batch pytree reused every round


class _ByIdentity:
    """Cache-key stand-in for an unhashable static argument (e.g. a
    ``FederationSpec`` with ``mu`` set): equal only to itself, and it
    holds the object so its id is not reused while the entry lives."""
    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _ByIdentity) and other.obj is self.obj


def _hashable(obj):
    try:
        hash(obj)
    except TypeError:
        return _ByIdentity(obj)
    return obj


# compiled trajectories, least recently used first: (static config,
# argument structure and avals) -> jitted function. An entry keeps its
# problem, spec and whatever their hooks close over alive; the program
# of the benchmark's MovieLens run holds 5.9 MB of code on a v5e
_TRAJECTORIES: "dict" = {}
_TRAJECTORIES_MAX = 16


def clear_trajectory_cache():
    """Drop every kept trajectory program of ``run``, and with them the
    problems, specs and closed-over arrays their keys hold."""
    _TRAJECTORIES.clear()


def _trajectory_program(cfg: _Trajectory, args):
    """The jitted trajectory for ``cfg`` and the shapes of ``args``, built
    on a miss and kept in a bounded LRU (``_TRAJECTORIES_MAX`` entries),
    so a repeated ``run`` dispatches without tracing, lowering or a
    compile-cache load. Hashable static parts key by value (a rebuilt
    equal ``as_problem(sur)`` hits), unhashable ones by identity."""
    leaves, tree = jax.tree.flatten(args)
    key = (tuple(_hashable(f) for f in cfg), tree,
           tuple(jax.typeof(x) for x in leaves))
    program = _TRAJECTORIES.get(key)
    if program is None:
        fn = functools.partial(_trajectory, cfg)
        if cfg.sanitize:
            from ..analysis.runtime import checkified
            fn = checkified(fn)
        program = jax.jit(fn)
    _lru_put(_TRAJECTORIES, key, program, _TRAJECTORIES_MAX)
    return program


def _trajectory(cfg: _Trajectory, state0, theta_prev0, diag_prev0, gammas,
                round_keys, batches, eval_batch):
    """The whole federated trajectory as one ``lax.scan``; traced only on
    a miss of ``_trajectory_program``."""
    jax.monitoring.record_event("/fedmm/run/trajectory/trace")
    problem, spec = cfg.problem, cfg.spec
    has_diag = cfg.diag is not None

    def body(carry, xs):
        state, theta_prev, diag_prev = carry
        if cfg.static:
            gamma, k, t_idx = xs
            batch = batches
        else:
            gamma, k, t_idx, batch = xs
        state, m = step(problem, spec, state, batch, gamma, k,
                        mesh=cfg.mesh, client_axis=cfg.client_axis,
                        client_mode=cfg.client_mode, uplink=cfg.uplink,
                        _comm_audit=cfg.sanitize)
        m, theta_new, diag_new = _round_metrics(cfg, eval_batch, state, m,
                                                gamma, theta_prev, diag_prev,
                                                t_idx)
        carry = (state,
                 theta_new if cfg.track_mirror else (),
                 diag_new if has_diag else ())
        return carry, m

    t_idxs = jnp.arange(cfg.n_rounds)
    xs = ((gammas, round_keys, t_idxs) if cfg.static
          else (gammas, round_keys, t_idxs, batches))
    (state, _, _), hist = jax.lax.scan(
        body, (state0, theta_prev0, diag_prev0), xs)
    return state, hist


def _round_metrics(cfg: _Trajectory, eval_batch, state, m, gamma,
                   theta_prev, diag_prev, t_idx):
    """Post-step diagnostics; returns (m, theta_new, diag_new)."""
    problem = cfg.problem
    theta_new = diag_new = None
    if cfg.track_mirror:
        theta_new = problem.T(state.x)
        m["e_p_s"] = (tree_sq_norm(tree_sub(theta_new, theta_prev))
                      / gamma ** 2)
    if cfg.diag is not None:
        diag_name, diag_fn = cfg.diag
        diag_new = diag_fn(state.x)
        m[diag_name] = (tree_sq_norm(tree_sub(diag_new, diag_prev))
                        / gamma ** 2)
    if problem.loss is not None and eval_batch is not None:
        if "loss" in m:
            raise ValueError(
                "metric key collision: the problem's s_bar_metrics "
                "already reports a per-client 'loss' and the eval hook "
                "would overwrite it — drop eval_batch or rename the "
                "client metric")
        param_space = cfg.spec.aggregation == "parameter"

        # ONE f32 code path for both cadences: the eval_every == 1
        # branch used to record problem.loss in native dtype (and
        # compute theta_eval a second time) while the lax.cond branch
        # cast to f32 — the stacked metric would silently change dtype
        # with the cadence
        def eval_loss(_):
            theta_eval = state.x if param_space else problem.T(state.x)
            return jnp.asarray(problem.loss(eval_batch, theta_eval),
                               jnp.float32)
        with jax.named_scope("fedmm.eval"):
            if cfg.eval_every > 1:
                do = (((t_idx + 1) % cfg.eval_every == 0)
                      | (t_idx == cfg.n_rounds - 1))
                m["loss"] = jax.lax.cond(
                    do, eval_loss, lambda _: jnp.float32(jnp.nan), None)
            else:
                m["loss"] = eval_loss(None)
    return m, theta_new, diag_new


def run(problem, x0, data, schedule, *, spec: Optional[FederationSpec] = None,
        key=None, n_rounds: Optional[int] = None, eval_batch=None,
        eval_every: int = 1, track_mirror: bool = False, diag=None,
        scan: bool = True, v0_i=None, init_batches=None,
        state0: Optional[DriverState] = None,
        scan_batch_bytes_max: Optional[int] = None,
        mesh=None, client_axis: str = "clients",
        client_mode: str = "vmap", uplink: str = "gather",
        sanitize: bool = False, audit_keys=False):
    """Drive ``n_rounds`` of the MM recursion; returns
    ``(final DriverState, metrics)`` where metrics is a stacked-pytree dict
    (each key an array with leading round axis). Use ``history_list`` for
    the legacy list-of-float-dicts view.

    data:
      * centralized (``spec is None``): a list of batches or a stacked
        pytree with a leading round axis;
      * federated: a callable ``(t, key) -> (n, ...) client batch pytree``
        (the legacy ``client_batch_fn``; evaluated on the host with the
        legacy per-round ``k_batch`` chain, then stacked for the scan), or
        a static ``(n, ...)`` pytree reused every round (exact local
        expectations, e.g. Figure 2).

    key: the federated key chain's root. Each round takes
    ``key, k_round, k_batch = split(key, 3)``, the legacy loops'
    derivation; the chain is built in one compiled ``lax.scan`` per
    ``n_rounds`` (the same bits as the eager loop), and with one eager
    split a round while a ``KeyAudit`` is active, so the audit sees each.

    track_mirror: record ``e_p_s`` — mirror-sequence movement
    ||T(x_{t+1}) - T(x_t)||^2 / gamma^2 (surrogate aggregation only).
    diag: optional ``(name, fn)``; records ||fn(x_{t+1}) - fn(x_t)||^2 /
    gamma^2 (e.g. the naive baseline's cross-space E^{s,p} diagnostic).
    eval_every: evaluate the ``loss`` hook only every k-th round (and the
    last); skipped rounds record NaN — use when the hook is expensive
    (e.g. the fig-3 L2-UVP evaluation) so the scan does not pay for
    values the caller discards.
    scan: jit the whole trajectory as one ``lax.scan`` (default); False
    falls back to a per-round python loop (same math, useful when stacked
    batches would not fit or for debugging). With ``scan=False`` the
    trajectory batches are never stacked OR measured — each round's batch
    is generated lazily. The federated scan is one ``jax.jit`` program
    kept across calls, keyed by what decides its trace (problem, spec,
    ``n_rounds``, mesh and client knobs, ``sanitize``, ``track_mirror``,
    ``diag``, ``eval_every``, static or per-round data) and by the
    structure and shapes of its arrays; a repeat call only dispatches it.
    At most ``_TRAJECTORIES_MAX`` (16) programs are kept, least recently
    used dropped first, each with the problem, spec and closed-over
    arrays of its key; ``clear_trajectory_cache()`` drops them. Hashable
    problems and specs key by value, unhashable ones (a spec with ``mu``
    set) by identity. A miss traces, lowers and loads the program once,
    the work an eager scan does every call; on a TPU v5e host a miss has
    read up to 0.2 s slower than the eager scan, and a process's first
    call up to 0.8 s slower (see the package README). As with any ``jax.jit``, the problem's hooks
    must be pure: Python state they read at trace time is frozen into the
    first trace of that key.
    scan_batch_bytes_max: device-byte budget for the stacked trajectory
    batches; above it the scan falls back to the lazy per-round loop
    (warning fired once per distinct situation, with the measured bytes).
    Defaults to the module-level ``SCAN_BATCH_BYTES_MAX`` (1 GiB) — raise
    it on big-memory hosts to keep the scan; any value <= 0 DISABLES the
    check entirely (no measurement, the scan always stacks); lower
    positive values force the constant-memory path.
    mesh / client_axis / client_mode / uplink: the sharded-driver knobs,
    passed through to every ``step`` — see ``step``'s docstring. With a
    mesh the per-client stage is shard_mapped over the ``client_axis``
    devices; ``uplink="gather"`` (default) crosses the mesh with a
    code-space ``all_gather`` and stays bit-identical to the
    single-device run, ``uplink="reduce"`` fuses decode/mask/weighting
    shard-locally and psums the partial aggregate (allclose to gather;
    O(n/axis_size) instead of O(n) payload memory per device).
    sanitize: thread ``jax.experimental.checkify`` NaN / div-by-zero /
    OOB-index checks through the WHOLE trajectory (one checkify around the
    ``lax.scan``; per-round on the python fallback) and run the comm-bytes
    audit every round — see ``step``'s docstring. The first tripped check
    raises ``checkify.JaxRuntimeError`` with the failing round's origin;
    with no trips the returned trajectory is BIT-IDENTICAL to
    ``sanitize=False`` (checkify only adds error outputs; pinned in
    tests/test_sanitizer.py). Off by default, zero-cost when off.
    Federated runs only (centralized ``spec=None`` rejects it).
    audit_keys: record the WHOLE host-side key chain (the per-round
    ``k_round``/``k_batch`` splits, batch-fn draws, fault/edge fold_in
    lanes) into a ``repro.analysis.keytrace.KeyTraceReport`` and raise
    ``KeyReuseError`` at the origin if the same concrete key data is
    consumed twice. ``True`` for the check alone, a ``KeyAudit``
    instance to keep the report. Trajectories are bit-identical with the
    audit on (tests/test_keytrace.py). Federated runs only.
    """
    problem = as_problem(problem)

    if spec is None:
        if sanitize:
            raise ValueError("sanitize=True is the federated driver's "
                             "runtime sanitizer; the centralized path does "
                             "not thread it — wrap centralized_step in "
                             "analysis.runtime.checkified yourself")
        if audit_keys:
            raise ValueError("audit_keys=True audits the federated driver's "
                             "host key chain; the centralized path draws "
                             "no keys — activate a keytrace.KeyAudit "
                             "yourself if your batch pipeline consumes them")
        return _run_centralized(problem, x0, data, schedule,
                                n_rounds=n_rounds, scan=scan,
                                state0=state0)

    if key is None:
        raise ValueError("federated run needs a PRNG key")
    if n_rounds is None:
        n_rounds = schedule_length(schedule)
        if n_rounds is None:
            raise ValueError("n_rounds required with a callable schedule")
    audit = contextlib.nullcontext()
    if audit_keys:
        from ..analysis.keytrace import resolve_audit
        audit = resolve_audit(audit_keys).activate()
    with audit, span("run", rounds=n_rounds, clients=spec.n_clients):
        return _run_federated(
            problem, x0, data, schedule, spec, key, n_rounds,
            eval_batch=eval_batch, eval_every=eval_every,
            track_mirror=track_mirror, diag=diag, scan=scan, v0_i=v0_i,
            init_batches=init_batches, state0=state0,
            scan_batch_bytes_max=scan_batch_bytes_max, mesh=mesh,
            client_axis=client_axis, client_mode=client_mode,
            uplink=uplink, sanitize=sanitize)


def _run_federated(problem, x0, data, schedule, spec, key, n_rounds, *,
                   eval_batch, eval_every, track_mirror, diag, scan, v0_i,
                   init_batches, state0, scan_batch_bytes_max, mesh,
                   client_axis, client_mode, uplink, sanitize):
    """``run``'s federated body, inside its ``fedmm.run`` span: the host
    phases (key chain, schedule, batch draws, stacking, the scan) each
    in a child span."""
    with span("run.schedule"):
        gammas = resolve_schedule(schedule, n_rounds)
    track_mirror = track_mirror and spec.aggregation != "parameter"

    # host-side key chain — replicates the legacy run loops exactly:
    # each round consumes (k_round, k_batch) off the same chain
    with span("run.keys"):
        round_keys, batch_keys = _round_keys(key, n_rounds)
    static = not callable(data)
    lazy = False
    budget = (SCAN_BATCH_BYTES_MAX if scan_batch_bytes_max is None
              else scan_batch_bytes_max)
    check_disabled = (scan_batch_bytes_max is not None
                      and scan_batch_bytes_max <= 0)
    if static:
        batches = data
    elif not scan:
        # explicit python loop: never stack (and never measure) the
        # trajectory — each round's batch is generated lazily below
        lazy, batches = True, None
    else:
        with span("run.batches"):
            first = data(0, batch_keys[0])
            # a disabled budget skips the measurement
            round_bytes = None if check_disabled else _tree_bytes(first)
            over = round_bytes is not None and n_rounds * round_bytes > budget
            if not over:
                batch_list = [first] + [data(t, batch_keys[t])
                                        for t in range(1, n_rounds)]
        if over:
            # do NOT materialize the trajectory: generate each round's
            # batch inside the loop, constant-memory like the legacy loops
            sig = (round_bytes, n_rounds, budget)
            warned = sig in _SCAN_FALLBACK_WARNED
            _lru_put(_SCAN_FALLBACK_WARNED, sig, True,
                     _SCAN_FALLBACK_WARNED_MAX)
            if not warned:
                warnings.warn(
                    f"stacked batches would exceed the scan budget "
                    f"({round_bytes:,} bytes/round x {n_rounds} rounds = "
                    f"{n_rounds * round_bytes:,} bytes > "
                    f"scan_batch_bytes_max={budget:,}); falling back to "
                    f"the per-round python loop — pass run(..., "
                    f"scan_batch_bytes_max=...) to raise the budget")
            scan = False
            lazy, batches, first = True, None, None
        else:
            with span("run.stack"):
                batches = _stack_batches(batch_list)
            del batch_list, first   # the stack is the only resident copy

    if state0 is None:
        state0 = init(problem, x0, spec, v0_i=v0_i,
                      init_batches=init_batches)

    cfg = _Trajectory(problem, spec, n_rounds, mesh, client_axis,
                      client_mode, uplink, sanitize, track_mirror, diag,
                      eval_every, static)
    diag_fn = diag[1] if diag is not None else None
    theta_prev0 = problem.T(state0.x) if track_mirror else ()
    diag_prev0 = diag_fn(state0.x) if diag_fn is not None else ()

    if scan:
        jax.monitoring.record_event("/fedmm/run/trajectory/call")
        args = (state0, theta_prev0, diag_prev0, gammas, round_keys,
                batches, eval_batch)
        with span("run.scan"):
            program = _trajectory_program(cfg, args)
            if sanitize:
                # the checks ride the scan body's trace, so err carries
                # the first tripped check of ANY round; thrown eagerly
                # here, after the scan
                err, (state, hist) = program(*args)
                err.throw()
            else:
                state, hist = program(*args)
        return state, hist

    # python fallback: identical math, one jitted step per round
    def _base(st, b, g, k):
        return step(problem, spec, st, b, g, k, mesh=mesh,
                    client_axis=client_axis, client_mode=client_mode,
                    uplink=uplink, _comm_audit=sanitize)
    if sanitize:
        from ..analysis.runtime import checkified
        _checked_j = jax.jit(checkified(_base))

        def step_j(st, b, g, k):
            err, out = _checked_j(st, b, g, k)
            err.throw()
            return out
    else:
        step_j = jax.jit(_base)
    state, theta_prev, diag_prev = state0, theta_prev0, diag_prev0
    hist = []
    for t in range(n_rounds):
        if static:
            batch = batches
        elif lazy:
            batch = data(t, batch_keys[t])
        else:
            batch = jax.tree.map(lambda x: x[t], batches)
        state, m = step_j(state, batch, gammas[t], round_keys[t])
        m, theta_new, diag_new = _round_metrics(cfg, eval_batch, state, m,
                                                gammas[t], theta_prev,
                                                diag_prev, jnp.asarray(t))
        if track_mirror:
            theta_prev = theta_new
        if diag_fn is not None:
            diag_prev = diag_new
        hist.append(m)
    return state, _stack_metrics(hist)


def _run_centralized(problem: MMProblem, s0, data, schedule, *,
                     n_rounds=None, scan=True, state0=None):
    if isinstance(data, (list, tuple)):
        if n_rounds is None:
            n_rounds = len(data)
        try:
            batches = _stack_batches(list(data[:n_rounds]))
        except (ValueError, TypeError):
            batches, scan = list(data[:n_rounds]), False  # ragged batches
    else:
        batches = data
        if n_rounds is None:
            n_rounds = jax.tree.leaves(data)[0].shape[0]
    gammas = resolve_schedule(schedule, n_rounds)
    if state0 is None:
        state0 = centralized_init(problem, s0)

    def with_loss(state, m, batch):
        if problem.loss is not None:
            m = dict(m, loss=problem.loss(batch, problem.T(state.x)))
        return m

    if scan:
        def body(state, xs):
            gamma, batch = xs
            state, m = centralized_step(problem, state, batch, gamma)
            return state, with_loss(state, m, batch)

        state, hist = jax.lax.scan(body, state0, (gammas, batches))
        return state, hist

    state, hist = state0, []
    for t in range(n_rounds):
        batch = (batches[t] if isinstance(batches, list)
                 else jax.tree.map(lambda x: x[t], batches))
        state, m = centralized_step(problem, state, batch, gammas[t])
        hist.append(with_loss(state, m, batch))
    return state, _stack_metrics(hist)


def mean_oracle_diag(problem, diag_batches):
    """Tbar(theta) = (1/n) sum_i Sbar_i(theta) on fixed per-client batches —
    the Section 6 cross-space E^{s,p} diagnostic for parameter-space
    aggregation. Pass as ``diag=("e_s_p", mean_oracle_diag(problem, b))``."""
    problem = as_problem(problem)

    def tbar(theta):
        return jax.tree.map(
            lambda x: jnp.mean(x, axis=0),
            jax.vmap(lambda b: problem.s_bar(b, theta))(diag_batches))

    return tbar


# ---------------------------------------------------------------------------
# metric views
# ---------------------------------------------------------------------------

def _stack_metrics(hist):
    if not hist:
        return {}
    return {k: jnp.stack([jnp.asarray(m[k]) for m in hist])
            for k in hist[0]}


def history_list(hist) -> list:
    """Stacked-pytree metrics -> the legacy list-of-float-dicts view."""
    if not hist:
        return []
    arrs = {k: jax.device_get(v) for k, v in hist.items()}
    n = len(next(iter(arrs.values())))
    return [{k: float(v[t]) for k, v in arrs.items()} for t in range(n)]
