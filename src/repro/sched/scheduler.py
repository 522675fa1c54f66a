"""CohortScheduler — stream a population through the mesh, cohort by cohort.

The driver's ``step`` runs ALL n clients as one stacked stage; the
scheduler runs the same round as ceil(n / C) cohort slices of size C (the
mesh's client capacity — ``launch.mesh.cohort_capacity``) through
``step(..., cohort=...)``, accumulates the returned ``CohortPartial``s in
a surrogate buffer, and lands the buffered aggregate with
``api.apply_partial``. Device memory is O(C * model + C * payload) —
independent of the population size; the O(n_total) state (the variate
arena, participation counters, the round's participation/key draw) lives
on host in the ``ClientPopulation``.

Two aggregation modes:

* ``mode="sync"`` — barrier per round. The key chain, per-client key
  fold, cohort arithmetic and server update replicate ``api.run``'s
  operation for operation: with ONE full-participation cohort (C >= n)
  the trajectory and metrics are BIT-IDENTICAL to ``api.run`` (pinned in
  tests/test_scheduler.py, both uplink modes); with multiple cohorts the
  weighted reduce is re-associated cohort-by-cohort, so trajectories
  match to allclose.

* ``mode="async"`` — bounded-staleness, FedBuff-style. Cohorts are
  launched into an in-flight window of ``max_inflight`` and computed
  EAGERLY against the iterate at launch time; a landing order (FIFO,
  reordered by ``delay_fn``) drains them into the buffer with weight
  ``spec.staleness_weight(tau)`` where tau = server updates since
  launch; after ``buffer_cohorts`` landings the buffer applies one
  server update. ``spec.max_staleness`` forces every over-bound in-flight
  cohort to land before the next update (the bounded-staleness drain).
  With the defaults (window = one population pass, ``delay_fn=None``,
  ``staleness_weight(0) == 1``) every cohort lands fresh and the
  trajectory is bit-identical to ``mode="sync"`` — the property pinned
  in tests/test_scheduler.py.

Fault tolerance (``spec.faults`` — a ``repro.faults.FaultSpec``):

* Client dropout folds into the wave's A5 participation mask at the
  ``_draw_wave`` host pull, so the cohort arithmetic renormalizes the
  surviving ``mu`` mass per ``spec.normalization`` with NO new jitted
  code — a zero-probability ``FaultSpec`` is bit-identical to
  ``faults=None`` (the draws ride fault-private ``fold_in`` lanes and
  never consume splits from the participation/quantization chain).
* Payload corruption flags flow into ``CohortSlice.corrupt`` (requires a
  checksummed wire-format compressor; the driver detects and drops the
  damaged client at decode). The corrupt-aware jitted closure is built
  ONLY when ``faults.corrupt > 0`` — no-fault runs keep the original
  traced program.
* Cohort failure walks a PRE-DRAWN retry ladder (``fail_u`` uniforms) at
  uplink time: each failed attempt bills its bytes (the wire was used)
  and counts in the ``fault_retries`` metric; in async mode the failed
  cohort re-enters the window with its staleness clock intact and
  ``retry_backoff`` extra landing delay, and a cohort force-drained by
  ``max_staleness`` walks its remaining ladder in place (the staleness
  bound holds even under retry). A ladder exhausted after
  ``max_retries`` abandons the cohort (``fault_abandoned``) — billed,
  never aggregated.
* ``straggle`` adds ``straggle_delay`` landing priority on top of
  ``delay_fn`` (async), composing with the force-drain.
* ``kill_round`` raises ``ServerKilled`` immediately before that
  update lands — the crash point for kill-and-resume tests.

Crash-consistent checkpointing: ``run(..., checkpoint_dir=...)`` publishes
one atomic ``round_NNNNNN.snap`` snapshot after each server update — the
DriverState leaves, the population arena, the host key-chain cursor, the
metric rows, and (async) the full in-flight window with each entry's
partial, retry state and wave context. ``resume()`` restores the latest
snapshot and reproduces the uninterrupted trajectory bit-for-bit (the
kill point is disabled on resume).

Incremental-MM reading (Mairal 2014): each client's surrogate block is
updated when its cohort lands while the other blocks stay frozen —
bounded staleness bounds how frozen, and ``staleness_weight`` shrinks a
stale block's move toward its fresh value.
"""
from __future__ import annotations

import contextlib
import glob
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..api.driver import (_NONFINITE, CohortPartial, CohortSlice,
                          DriverState, _stack_metrics, apply_partial,
                          finalize_partial, step)
from ..analysis import hb
from ..api.problem import as_problem
from ..api.schedule import resolve_schedule, schedule_length
from ..api.spec import FederationSpec, participation_draw
from ..faults.snapshot import load_snapshot, save_snapshot
from ..faults.spec import ServerKilled
from .population import ClientPopulation

# round snapshots kept on disk (older ones are pruned after each publish)
_CKPT_KEEP = 3


def _resolve_audit(audit_keys):
    """Lazy ``keytrace.resolve_audit`` — keep the analysis import off the
    scheduler's hot path when the audit is off."""
    if not audit_keys:
        return None
    from ..analysis.keytrace import resolve_audit
    return resolve_audit(audit_keys)


class _SnapshotWriter:
    """Single-thread background publisher for round snapshots.

    The hot loop hands over a fully-COPIED host snapshot (built on the
    main thread, so it cannot alias state the next round mutates) and
    keeps driving; the worker serializes, fsyncs, atomically publishes
    (``save_snapshot``: mkstemp + fsync + os.replace) and prunes. At
    most one write is in flight — ``submit`` waits for the previous one
    — so snapshot memory is bounded at ~2x and publish order matches
    round order. Write errors surface on the next ``submit`` or at
    ``flush``; the driving loops always ``flush()`` on exit (normal,
    ``ServerKilled``, or any other exception), so when ``run`` returns
    or raises the last snapshot is durable on disk. A hard crash
    (SIGKILL) mid-write loses only that one in-flight snapshot — the
    previous published one is intact and ``resume`` still reproduces
    the uninterrupted trajectory bit-for-bit from it."""

    def __init__(self):
        self._ex = ThreadPoolExecutor(max_workers=1)
        self._fut = None
        self._last = None

    @staticmethod
    def _write(path, snap, prune_dir):
        # hb edges: the executor handoff (recv of the submit's send), the
        # snapshot-after-land ordering mark, and the completion token the
        # next submit / flush joins via Future.result()
        hb.on_recv(("snap", path))
        save_snapshot(path, snap)
        hb.on_mark("snapshot", int(snap["cursor"]),
                   after=("land", int(snap["cursor"]) - 1))
        stale = sorted(glob.glob(os.path.join(prune_dir, "round_*.snap")))
        for p in stale[:-_CKPT_KEEP]:
            try:
                os.remove(p)
            except OSError:
                pass
        hb.on_send(("snap-done", path))

    def submit(self, path, snap, prune_dir):
        if self._fut is not None:
            self._fut.result()   # backpressure + surface prior write errors
            hb.on_recv(("snap-done", self._last))
        self._fut = self._ex.submit(self._write, path, snap, prune_dir)
        self._last = path

    def flush(self):
        try:
            if self._fut is not None:
                fut, self._fut = self._fut, None
                fut.result()
                hb.on_recv(("snap-done", self._last))
        finally:
            self._ex.shutdown(wait=True)


def cohort_ids(n_total: int, cohort_size: int):
    """Static cohort assignment: contiguous slices of the population,
    the last one PADDED up to ``cohort_size`` by repeating its first id
    (every jitted cohort step sees the same (C, ...) shapes — one
    compilation). Returns a list of ``(ids, valid)`` numpy pairs; padded
    slots have valid == 0.0 and are masked out of the aggregate, the
    byte accounting and the metric sums."""
    if cohort_size < 1:
        raise ValueError(f"cohort_size must be >= 1, got {cohort_size}")
    out = []
    for lo in range(0, n_total, cohort_size):
        real = np.arange(lo, min(lo + cohort_size, n_total))
        pad = cohort_size - real.size
        ids = np.concatenate([real, np.full((pad,), real[0])]) if pad \
            else real
        valid = np.concatenate(
            [np.ones((real.size,), np.float32), np.zeros((pad,), np.float32)])
        out.append((ids.astype(np.int64), valid))
    return out


class _PartialBuffer:
    """Accumulates staleness-weighted ``CohortPartial``s between server
    updates. The first partial is adopted WITHOUT an add (and a weight of
    exactly 1.0 skips the multiply), so a single-cohort sync round feeds
    ``apply_partial`` the cohort's own ``agg`` buffers bit-for-bit."""

    def __init__(self):
        self.agg = None
        self.n_active = jnp.float32(0.0)
        self.comm_bytes = jnp.float32(0.0)
        self.collective_payload_bytes = None
        self.metric_sums = None
        self.staleness = []
        self.retries = 0        # failed cohort uplink attempts (billed)
        self.abandoned = 0      # cohorts whose retry ladder ran out

    def add(self, partial, weight: float, tau: int = 0):
        if weight == 1.0:
            agg = partial.agg
        else:
            w = float(weight)
            agg = jax.tree.map(lambda x: (w * x).astype(x.dtype),
                               partial.agg)
        self.agg = agg if self.agg is None else jax.tree.map(
            lambda a, b: a + b, self.agg, agg)
        # accounting is unweighted: these cohorts really did participate
        # and really did send those bytes, however downweighted they land
        self.n_active = self.n_active + partial.n_active
        self.comm_bytes = self.comm_bytes + partial.comm_bytes
        if partial.collective_payload_bytes is not None:
            prev = self.collective_payload_bytes
            self.collective_payload_bytes = (
                partial.collective_payload_bytes if prev is None
                else prev + partial.collective_payload_bytes)
        if self.metric_sums is None:
            self.metric_sums = dict(partial.metric_sums)
        else:
            self.metric_sums = {
                k: self.metric_sums[k] + v
                for k, v in partial.metric_sums.items()}
        self.staleness.append(int(tau))

    def bill(self, comm_bytes):
        """Count wire bytes WITHOUT aggregating — a failed attempt used
        the uplink even though its payload never landed."""
        self.comm_bytes = self.comm_bytes + comm_bytes


class _Inflight(NamedTuple):
    launch_updates: int     # server-update count when the cohort computed
    order: int              # global launch order (FIFO tiebreak)
    partial: object         # the CohortPartial
    wave: int               # which population pass launched it
    cohort_idx: int = -1    # index into the static cohort list
    attempt: int = 0        # next rung of the pre-drawn retry ladder
    extra: int = 0          # straggle + retry-backoff landing delay
    mask: object = None     # (C,) participation mask (deferred delivery)
    fail_row: object = None  # (max_retries + 1,) fail_u uniforms, or None


class CohortScheduler:
    """Streams cohorts of ``cohort_size`` clients through the driver's
    client stage on ``mesh`` (or single-device). ``cohort_size`` should
    divide over the mesh's client axis — ``launch.mesh.cohort_capacity``
    gives the natural choice."""

    def __init__(self, problem, spec: FederationSpec, *, cohort_size: int,
                 mesh=None, client_axis: str = "clients",
                 client_mode: str = "vmap", uplink: str = "gather",
                 drift_metric: bool = True):
        self.problem = as_problem(problem)
        self.spec = spec
        self.cohort_size = int(cohort_size)
        self.mesh = mesh
        self.client_axis = client_axis
        self.client_mode = client_mode
        self.uplink = uplink
        self.drift_metric = drift_metric
        self.n_cohorts = math.ceil(spec.n_clients / self.cohort_size)
        self._two_tier = spec.topology.is_two_tier
        if self._two_tier and uplink == "reduce":
            # fail at construction, not rounds later inside the jitted
            # cohort closure (the driver raises the same way)
            raise ValueError(
                "two-tier uplink='reduce' groups clients by mesh position; "
                "a streamed cohort's edge membership is data-dependent — "
                "use uplink='gather' under the scheduler")
        problem_ = self.problem
        spec_ = self.spec

        if self._two_tier:
            # the cohort closure grows ONE extra (C,) operand — the
            # cohort's edge-assignment slice; the flat traced program is
            # byte-for-byte the pre-topology one
            def _cohort(state, batch, mask, mu_s, qkeys, v_i, valid,
                        edge_ids):
                cohort = CohortSlice(mask=mask, mu=mu_s, quant_keys=qkeys,
                                     v_i=v_i, valid=valid,
                                     edge_ids=edge_ids)
                return step(problem_, spec_, state, batch, 0.0, None,
                            mesh=mesh, client_axis=client_axis,
                            client_mode=client_mode, uplink=uplink,
                            cohort=cohort)

            def _finalize(agg, key, x_ref):
                return finalize_partial(spec_, agg, key, x_ref)

            self._finalize_j = jax.jit(_finalize)
        else:
            def _cohort(state, batch, mask, mu_s, qkeys, v_i, valid):
                cohort = CohortSlice(mask=mask, mu=mu_s, quant_keys=qkeys,
                                     v_i=v_i, valid=valid)
                return step(problem_, spec_, state, batch, 0.0, None,
                            mesh=mesh, client_axis=client_axis,
                            client_mode=client_mode, uplink=uplink,
                            cohort=cohort)

            self._finalize_j = None

        def _apply(state, agg, n_active, gamma):
            return apply_partial(problem_, spec_, state, agg, n_active,
                                 gamma, drift_metric=drift_metric)

        self._cohort_fn = _cohort
        self._apply_fn = _apply
        self._cohort_j = jax.jit(_cohort)
        self._apply_j = jax.jit(_apply)
        if self.problem.loss is not None:
            param_space = spec.aggregation == "parameter"

            def _eval(x, batch):
                theta = x if param_space else problem_.T(x)
                return jnp.asarray(problem_.loss(batch, theta), jnp.float32)

            self._eval_j = jax.jit(_eval)
        else:
            self._eval_j = None
        # the corrupt-aware closure exists ONLY when the fault axis can
        # flag corruption: the no-fault jitted program stays untouched
        if spec_.faults is not None and spec_.faults.corrupt > 0.0:
            if self._two_tier:
                def _cohort_corrupt(state, batch, mask, mu_s, qkeys, v_i,
                                    valid, edge_ids, corrupt):
                    cohort = CohortSlice(mask=mask, mu=mu_s,
                                         quant_keys=qkeys, v_i=v_i,
                                         valid=valid, corrupt=corrupt,
                                         edge_ids=edge_ids)
                    return step(problem_, spec_, state, batch, 0.0, None,
                                mesh=mesh, client_axis=client_axis,
                                client_mode=client_mode, uplink=uplink,
                                cohort=cohort)
            else:
                def _cohort_corrupt(state, batch, mask, mu_s, qkeys, v_i,
                                    valid, corrupt):
                    cohort = CohortSlice(mask=mask, mu=mu_s,
                                         quant_keys=qkeys, v_i=v_i,
                                         valid=valid, corrupt=corrupt)
                    return step(problem_, spec_, state, batch, 0.0, None,
                                mesh=mesh, client_axis=client_axis,
                                client_mode=client_mode, uplink=uplink,
                                cohort=cohort)

            self._cohort_corrupt_fn = _cohort_corrupt
            self._cohort_corrupt_j = jax.jit(_cohort_corrupt)
        else:
            self._cohort_corrupt_fn = None
            self._cohort_corrupt_j = None
        # sanitized (checkified) twins — built lazily on first
        # run(sanitize=True); err.throw() happens eagerly at each call
        self._cohort_cj = None
        self._apply_cj = None
        self._cohort_corrupt_cj = None
        self._sanitize = False
        self._ckpt_writer = None
        # with a cohort-failure axis, client-local state (variate
        # scatter, participation counts) commits at DELIVERY — an
        # attempt that failed or was abandoned never reached the server;
        # without it, commit at COMPUTE time (the pinned async
        # semantics: the client did its round then, however stale it
        # lands)
        self._defer_delivery = (spec_.faults is not None
                                and spec_.faults.cohort_fail > 0.0)

    def _ensure_sanitized(self):
        if self._apply_cj is not None:
            return
        from ..analysis.runtime import checkified
        self._cohort_cj = jax.jit(checkified(self._cohort_fn))
        self._apply_cj = jax.jit(checkified(self._apply_fn))
        if self._cohort_corrupt_fn is not None:
            self._cohort_corrupt_cj = jax.jit(
                checkified(self._cohort_corrupt_fn))

    # -- state --------------------------------------------------------------
    def init_state(self, x0, population: ClientPopulation) -> DriverState:
        """The scheduler's ``DriverState``: like ``api.init`` but the
        per-client variates stay in the population arena — ``v_i`` is
        ``()`` and never O(n_total) on device."""
        problem, spec = self.problem, self.spec
        v = population.weighted_variate_sum() if spec.use_variates else ()
        aux = problem.init_aux() if problem.init_aux is not None else ()
        if spec.server_momentum > 0.0:
            if problem.server_opt is not None or problem.init_opt is not None:
                raise ValueError(
                    "server_momentum and a custom MMProblem.server_opt/"
                    "init_opt both claim the server update — fold the "
                    "momentum into your server_opt instead")
            opt = jax.tree.map(jnp.zeros_like, x0)
        else:
            opt = problem.init_opt(x0) if problem.init_opt is not None else ()
        return DriverState(x=x0, v=v, v_i=(), aux=aux, opt=opt,
                           step=jnp.asarray(0))

    # -- one cohort through the client stage --------------------------------
    def _run_cohort(self, state, t_wave, k_batch, ids, valid, active, qkeys,
                    pop: ClientPopulation, data_fn, fctx=None,
                    cohort_idx: int = 0):
        mask = active[ids].astype(np.float32) * valid
        mu_s = pop.mu[ids] * valid
        batch = data_fn(t_wave, k_batch, ids)
        v_i = pop.gather_variates(ids) if self.spec.use_variates else ()
        args = (state, batch, jnp.asarray(mask), jnp.asarray(mu_s),
                jnp.asarray(qkeys[ids]), v_i, jnp.asarray(valid))
        if self._two_tier:
            # the cohort's slice of the STABLE global edge assignment —
            # indexed by global id, so padded (duplicate) slots carry
            # their real client's edge and the mask zeroes them anyway
            args = args + (jnp.asarray(pop.edge_ids[ids]),)
        use_corrupt = self._cohort_corrupt_j is not None
        if use_corrupt:
            # faults.corrupt > 0 implies any_injection, so fctx and its
            # corrupt draw are always present on this path
            corr = fctx["corrupt"][ids] & (np.asarray(valid) > 0.5)
            args = args + (jnp.asarray(corr),)
        if self._sanitize:
            self._ensure_sanitized()
            fn = self._cohort_corrupt_cj if use_corrupt else self._cohort_cj
            err, partial = fn(*args)
            err.throw()
        else:
            fn = self._cohort_corrupt_j if use_corrupt else self._cohort_j
            partial = fn(*args)
        if not self._defer_delivery:
            self._deliver(pop, partial, ids, mask, valid)
        del v_i, batch
        return partial, mask

    def _deliver(self, pop: ClientPopulation, partial, ids, mask, valid):
        """Commit a cohort's client-local effects: scatter the updated
        variate slice into the arena and count realized participations.
        Without a cohort-failure axis this happens at COMPUTE time (the
        client did its round then, even if the partial lands stale
        later); with one, only at DELIVERY — a failed attempt's effects
        must not survive the failure."""
        if self.spec.use_variates:
            pop.scatter_variates(ids, partial.v_i, valid)
        pop.record_participation(ids, mask, valid)

    def _draw_wave(self, k_round):
        """One population pass's participation + quantization draw, pulled
        to HOST immediately: the (n_total,) active mask and (n_total, 2)
        key table are numpy, so no O(n_total) device array outlives the
        draw — cohorts push back only (C,)-shaped slices.

        When the spec carries an injecting ``FaultSpec``, the round's
        fault draws come off the same ``k_round`` via fault-private
        ``fold_in`` lanes: dropout folds into ``active`` right here (so
        the cohort arithmetic renormalizes the surviving ``mu`` mass with
        no new traced code) and the rest rides the returned ``fctx``."""
        active_d, qkeys_d = participation_draw(k_round, self.spec)
        faults = self.spec.faults
        fctx = None
        if faults is not None and faults.any_injection:
            drop_d, corr_d = faults.client_draw(k_round, self.spec.n_clients)
            fail_u_d, straggle_d = faults.cohort_draw(k_round, self.n_cohorts)
            active_d = jnp.logical_and(jnp.asarray(active_d, jnp.bool_),
                                       jnp.logical_not(drop_d))
            fctx = {
                "corrupt": (np.array(corr_d, copy=True)
                            if faults.corrupt > 0.0 else None),
                "fail_u": np.array(fail_u_d, copy=True),
                "straggle": np.array(straggle_d, copy=True),
            }
            del drop_d, corr_d, fail_u_d, straggle_d
        # np.array with copy=True: np.asarray of a CPU jax array can be a
        # zero-copy VIEW whose base keeps the device buffer alive — the
        # copy lets the (n_total,) draw free immediately
        active = np.array(active_d, copy=True)
        qkeys = np.array(qkeys_d, copy=True)
        del active_d, qkeys_d
        return active, qkeys, fctx

    def _land(self, state, buffer: _PartialBuffer, gamma, t_idx, n_rounds,
              eval_batch, eval_every, k_round=None):
        """Apply the buffered aggregate and assemble the round's metrics
        row (matching ``api.run``'s keys and arithmetic). Under a
        two-tier topology the buffered ``(n_edges,)``-stacked partial
        crosses the tier boundary HERE, with the landing round's
        ``k_round`` deriving the per-edge reencode keys — cohorts sum
        edge-wise before the (nonlinear) boundary, the backbone is
        billed once per landing."""
        n_total = self.spec.n_clients
        if buffer.agg is None:
            # every cohort's retry ladder ran out this update: land a
            # zero aggregate with n_active = 0 so the round index, gamma
            # schedule and metric rows stay aligned (apply_partial's
            # realized normalization guards n_active=0 with max(., 1))
            if self._two_tier:
                n_edges = self.spec.topology.n_edges
                buffer.agg = jax.tree.map(
                    lambda x: jnp.zeros((n_edges,) + jnp.shape(x),
                                        jnp.float32), state.x)
            else:
                buffer.agg = jax.tree.map(jnp.zeros_like, state.x)
        agg = buffer.agg
        backbone = jnp.float32(0.0)
        if self._two_tier:
            if k_round is None:
                raise ValueError("a two-tier landing needs the round key "
                                 "(k_round) to derive the tier-boundary "
                                 "reencode keys")
            agg, backbone_bytes = self._finalize_j(agg, k_round, state.x)
            backbone = jnp.asarray(backbone_bytes, jnp.float32)
        if self._sanitize:
            self._ensure_sanitized()
            err, (state, m) = self._apply_cj(state, agg,
                                             buffer.n_active,
                                             jnp.float32(gamma))
            err.throw()
        else:
            state, m = self._apply_j(state, agg, buffer.n_active,
                                     jnp.float32(gamma))
        m = dict(m)
        # flat: backbone == 0.0 exactly, so comm_bytes stays bitwise the
        # pre-topology value and uplink_bytes aliases it
        m["uplink_bytes"] = buffer.comm_bytes
        m["backbone_bytes"] = backbone
        m["comm_bytes"] = buffer.comm_bytes + backbone
        if buffer.collective_payload_bytes is not None:
            m["collective_payload_bytes"] = jnp.asarray(
                buffer.collective_payload_bytes, jnp.float32)
        sums = dict(buffer.metric_sums or {})
        m["n_nonfinite"] = sums.pop(_NONFINITE, jnp.float32(0.0))
        dup = set(sums) & set(m)
        if dup:
            raise ValueError(f"s_bar_metrics keys {sorted(dup)} collide "
                             f"with driver metrics — rename them in the "
                             f"problem")
        # sum / n_total == the driver's jnp.mean over the client axis
        m.update({k: v / n_total for k, v in sums.items()})
        if self._eval_j is not None and eval_batch is not None:
            if "loss" in m:
                raise ValueError(
                    "metric key collision: the problem's s_bar_metrics "
                    "already reports a per-client 'loss' and the eval hook "
                    "would overwrite it — drop eval_batch or rename the "
                    "client metric")
            if (t_idx + 1) % eval_every == 0 or t_idx == n_rounds - 1:
                m["loss"] = self._eval_j(state.x, eval_batch)
            else:
                m["loss"] = jnp.float32(jnp.nan)
        if buffer.staleness:
            stale = np.asarray(buffer.staleness, np.float32)
            m["staleness_mean"] = jnp.float32(stale.mean())
            m["staleness_max"] = jnp.float32(stale.max())
        faults = self.spec.faults
        if faults is not None and faults.any_injection:
            m["fault_retries"] = jnp.float32(buffer.retries)
            m["fault_abandoned"] = jnp.float32(buffer.abandoned)
        hb.on_mark("land", t_idx)
        return state, m

    # -- crash-consistent snapshots ------------------------------------------
    def _encode_partial(self, partial) -> dict:
        enc = {
            "agg": [np.array(l, copy=True)
                    for l in jax.tree.leaves(partial.agg)],
            "n_active": np.array(partial.n_active, copy=True),
            "comm_bytes": np.array(partial.comm_bytes, copy=True),
            "metric_sums": {k: np.array(v, copy=True)
                            for k, v in partial.metric_sums.items()},
            "collective_payload_bytes": (
                None if partial.collective_payload_bytes is None
                else float(partial.collective_payload_bytes)),
        }
        if self._defer_delivery and self.spec.use_variates:
            # deferred delivery scatters v_i at landing time, which may
            # happen after a resume — otherwise the slice was already
            # committed to the arena and need not ride the snapshot
            enc["v_i"] = [np.array(l, copy=True)
                          for l in jax.tree.leaves(partial.v_i)]
        return enc

    def _decode_partial(self, enc: dict, x_template) -> CohortPartial:
        tdef = jax.tree.structure(x_template)
        agg = jax.tree.unflatten(tdef,
                                 [jnp.asarray(l) for l in enc["agg"]])
        v_i = ()
        if enc.get("v_i") is not None:
            v_i = jax.tree.unflatten(tdef,
                                     [jnp.asarray(l) for l in enc["v_i"]])
        cpb = enc["collective_payload_bytes"]
        return CohortPartial(
            agg=agg, v_i=v_i, n_active=jnp.asarray(enc["n_active"]),
            comm_bytes=jnp.asarray(enc["comm_bytes"]),
            metric_sums={k: jnp.asarray(v)
                         for k, v in enc["metric_sums"].items()},
            collective_payload_bytes=None if cpb is None else float(cpb))

    def _encode_async_ctx(self, inflight, pending, wave, wave_ctx,
                          order) -> dict:
        if wave_ctx is None:
            wctx = None
        else:
            k_batch, active, qkeys, fctx = wave_ctx
            wctx = {
                "k_batch": np.array(k_batch, copy=True),
                "active": np.array(active, copy=True),
                "qkeys": np.array(qkeys, copy=True),
                "fctx": None if fctx is None else {
                    "corrupt": (None if fctx["corrupt"] is None
                                else np.array(fctx["corrupt"], copy=True)),
                    "fail_u": np.array(fctx["fail_u"], copy=True),
                    "straggle": np.array(fctx["straggle"], copy=True),
                },
            }
        return {
            "order": int(order),
            "wave": int(wave),
            "pending": [int(ci) for ci in pending],
            "wave_ctx": wctx,
            "inflight": [{
                "launch_updates": int(e.launch_updates),
                "order": int(e.order),
                "wave": int(e.wave),
                "cohort_idx": int(e.cohort_idx),
                "attempt": int(e.attempt),
                "extra": int(e.extra),
                "mask": np.array(e.mask, copy=True),
                "fail_row": (None if e.fail_row is None
                             else np.array(e.fail_row, copy=True)),
                "partial": self._encode_partial(e.partial),
            } for e in inflight],
        }

    def _decode_async_ctx(self, ctx: dict, x_template) -> dict:
        wctx = ctx["wave_ctx"]
        if wctx is None:
            wave_ctx = None
        else:
            fctx = wctx["fctx"]
            if fctx is not None:
                fctx = {
                    "corrupt": (None if fctx["corrupt"] is None
                                else np.asarray(fctx["corrupt"])),
                    "fail_u": np.asarray(fctx["fail_u"]),
                    "straggle": np.asarray(fctx["straggle"]),
                }
            wave_ctx = (jnp.asarray(wctx["k_batch"]),
                        np.asarray(wctx["active"]),
                        np.asarray(wctx["qkeys"]), fctx)
        inflight = [
            _Inflight(int(d["launch_updates"]), int(d["order"]),
                      self._decode_partial(d["partial"], x_template),
                      int(d["wave"]), int(d["cohort_idx"]),
                      int(d["attempt"]), int(d["extra"]),
                      np.asarray(d["mask"]),
                      (None if d["fail_row"] is None
                       else np.asarray(d["fail_row"])))
            for d in ctx["inflight"]]
        return {"inflight": inflight,
                "pending": [int(ci) for ci in ctx["pending"]],
                "wave": int(ctx["wave"]), "wave_ctx": wave_ctx,
                "order": int(ctx["order"])}

    def _save_checkpoint(self, ckpt_dir, mode, cursor, key, state, pop,
                         rows, extra=None):
        """Publish one atomic round snapshot (``faults.save_snapshot``:
        temp file + fsync + rename — a crash mid-save leaves the previous
        complete snapshot in place) and prune older ones. The host copies
        are taken HERE, synchronously; the write itself goes through the
        run's ``_SnapshotWriter`` so the round loop never blocks on
        disk."""
        os.makedirs(ckpt_dir, exist_ok=True)
        snap = {
            "mode": mode,
            "cursor": int(cursor),
            "key": np.array(key, copy=True),
            "state": {
                "treedef": str(jax.tree.structure(state)),
                "leaves": [np.array(l, copy=True)
                           for l in jax.tree.leaves(state)],
            },
            "pop": pop.snapshot(),
            "rows": [{k: np.array(v, copy=True) for k, v in r.items()}
                     for r in rows],
        }
        if extra:
            snap.update(extra)
        path = os.path.join(ckpt_dir, f"round_{cursor:06d}.snap")
        hb.on_send(("snap", path))
        if self._ckpt_writer is not None:
            # serialization + fsync + publish + prune run off the hot
            # loop; the snap above is all fresh host copies so the next
            # round cannot race the write
            self._ckpt_writer.submit(path, snap, ckpt_dir)
        else:
            _SnapshotWriter._write(path, snap, ckpt_dir)

    # -- driving loops -------------------------------------------------------
    def run(self, x0, data_fn, schedule, *, key, n_rounds: Optional[int] = None,
            population: Optional[ClientPopulation] = None,
            mode: str = "sync", eval_batch=None, eval_every: int = 1,
            max_inflight: Optional[int] = None,
            buffer_cohorts: Optional[int] = None,
            delay_fn: Optional[Callable[[int], int]] = None,
            state0: Optional[DriverState] = None,
            sanitize: bool = False, audit_keys=False,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 1):
        """Drive ``n_rounds`` server updates.

        data_fn: ``(t, key, ids) -> (len(ids), ...)`` client batch pytree
        for the GLOBAL client ids ``ids`` (padded slots repeat a real id;
        their rows are computed and discarded). ``t`` is the round index
        in sync mode and the population-pass (wave) index in async mode;
        ``key`` is the wave's ``k_batch`` off the same host chain as
        ``api.run`` — slicing the rows of ``api.run``'s per-round batch
        reproduces its data exactly.

        Async knobs (``mode="async"`` only): ``max_inflight`` cohorts in
        flight (default one population pass), ``buffer_cohorts`` landings
        per server update (default one population pass), ``delay_fn(i) ->
        int`` reorders landings (entry i becomes eligible at virtual time
        ``i + delay_fn(i)``; None/0 = FIFO = sync-equivalent).

        sanitize: checkify the jitted cohort and landing closures
        (``analysis.runtime.checkified``) and raise EAGERLY on the first
        NaN / div-by-zero / OOB check — same contract as
        ``step(sanitize=True)``; trajectories are bit-identical when no
        check trips.

        audit_keys: record the scheduler's host key chain (wave splits,
        per-wave fault/straggle ``fold_in`` lanes, batch-fn draws) into a
        ``repro.analysis.keytrace.KeyTraceReport`` and raise
        ``KeyReuseError`` at the origin on duplicate consumption —
        ``True`` for the check, a ``KeyAudit`` instance to keep the
        report. Same bit-identity contract as ``api.run``.

        checkpoint_dir / checkpoint_every: publish an atomic
        ``round_NNNNNN.snap`` snapshot every ``checkpoint_every`` server
        updates (``resume()`` continues bit-identically from the last
        one). A ``spec.faults.kill_round`` crash raises ``ServerKilled``
        BEFORE that update lands, so the last snapshot is strictly
        earlier.

        Returns ``(DriverState, ClientPopulation, metrics)`` with metrics
        a stacked-pytree dict, one leading row per server update."""
        if mode not in ("sync", "async"):
            raise ValueError(f"mode={mode!r} (want 'sync' or 'async')")
        if mode == "async" and self._two_tier:
            raise ValueError(
                "mode='async' does not support a two-tier topology: the "
                "tier boundary re-encodes with the LANDING round's keys, "
                "and the async window lands cohorts from different waves "
                "into one update — use mode='sync'")
        if n_rounds is None:
            n_rounds = schedule_length(schedule)
            if n_rounds is None:
                raise ValueError("n_rounds required with a callable "
                                 "schedule")
        if checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got "
                             f"{checkpoint_every}")
        gammas = np.asarray(resolve_schedule(schedule, n_rounds), np.float32)
        if population is None:
            population = ClientPopulation(self.spec, x0)
        if population.n_total != self.spec.n_clients:
            raise ValueError(
                f"population holds {population.n_total} clients but the "
                f"spec says {self.spec.n_clients}")
        state = state0 if state0 is not None else \
            self.init_state(x0, population)
        cohorts = cohort_ids(self.spec.n_clients, self.cohort_size)
        self._sanitize = bool(sanitize)
        self._ckpt_writer = (_SnapshotWriter() if checkpoint_dir is not None
                             else None)
        audit = _resolve_audit(audit_keys)
        try:
            with (audit.activate() if audit is not None
                  else contextlib.nullcontext()):
                if mode == "sync":
                    return self._run_sync(state, data_fn, gammas, key,
                                          n_rounds, population, cohorts,
                                          eval_batch, eval_every,
                                          checkpoint_dir, checkpoint_every)
                return self._run_async(state, data_fn, gammas, key, n_rounds,
                                       population, cohorts, eval_batch,
                                       eval_every, max_inflight,
                                       buffer_cohorts, delay_fn,
                                       checkpoint_dir, checkpoint_every)
        finally:
            if self._ckpt_writer is not None:
                w, self._ckpt_writer = self._ckpt_writer, None
                w.flush()

    def resume(self, x0, data_fn, schedule, *, checkpoint_dir: str,
               n_rounds: Optional[int] = None,
               population: Optional[ClientPopulation] = None,
               mode: str = "sync", eval_batch=None, eval_every: int = 1,
               max_inflight: Optional[int] = None,
               buffer_cohorts: Optional[int] = None,
               delay_fn: Optional[Callable[[int], int]] = None,
               sanitize: bool = False, audit_keys=False,
               checkpoint_every: int = 1):
        """Continue a crashed ``run(..., checkpoint_dir=...)`` from its
        latest atomic snapshot, reproducing the uninterrupted trajectory
        BIT-FOR-BIT: the snapshot carries the key-chain cursor, the
        DriverState leaves (treedef/shape/dtype-verified against a fresh
        template, the ``checkpoint.restore`` contract), the population
        arena, the metric rows, and (async) the in-flight window. Pass
        the same ``x0`` / ``data_fn`` / ``schedule`` / mode knobs as the
        crashed run; the ``spec.faults.kill_round`` crash point is
        DISABLED on resume (one crash per kill point — resume must make
        progress). Returns ``(DriverState, ClientPopulation, metrics)``
        covering the FULL run, restored rows included.

        audit_keys: same key-trace audit as ``run`` — an audited resume
        replays EXACTLY the uninterrupted run's trace suffix from the
        snapshot's key-chain cursor (pinned in tests/test_keytrace.py)."""
        if mode not in ("sync", "async"):
            raise ValueError(f"mode={mode!r} (want 'sync' or 'async')")
        if mode == "async" and self._two_tier:
            raise ValueError(
                "mode='async' does not support a two-tier topology: the "
                "tier boundary re-encodes with the LANDING round's keys, "
                "and the async window lands cohorts from different waves "
                "into one update — use mode='sync'")
        paths = sorted(glob.glob(os.path.join(checkpoint_dir,
                                              "round_*.snap")))
        if not paths:
            raise FileNotFoundError(
                f"no round_*.snap snapshots under {checkpoint_dir!r} — "
                f"nothing to resume")
        snap = load_snapshot(paths[-1])
        if snap["mode"] != mode:
            raise ValueError(
                f"snapshot was written by mode={snap['mode']!r} but "
                f"resume asked for mode={mode!r}")
        if n_rounds is None:
            n_rounds = schedule_length(schedule)
            if n_rounds is None:
                raise ValueError("n_rounds required with a callable "
                                 "schedule")
        gammas = np.asarray(resolve_schedule(schedule, n_rounds), np.float32)
        if population is None:
            population = ClientPopulation(self.spec, x0)
        if population.n_total != self.spec.n_clients:
            raise ValueError(
                f"population holds {population.n_total} clients but the "
                f"spec says {self.spec.n_clients}")
        population.load_snapshot(snap["pop"])
        template = self.init_state(x0, population)
        tdef = jax.tree.structure(template)
        if str(tdef) != snap["state"]["treedef"]:
            raise ValueError(
                f"snapshot DriverState treedef\n  {snap['state']['treedef']}"
                f"\ndoes not match this scheduler's\n  {tdef} — resume "
                f"needs the same problem/spec the snapshot was written "
                f"with")
        tmpl_leaves = jax.tree.leaves(template)
        stored = snap["state"]["leaves"]
        leaves = []
        for i, (tl, sl) in enumerate(zip(tmpl_leaves, stored)):
            sl = np.asarray(sl)
            tl = np.asarray(tl)
            if sl.shape != tl.shape or sl.dtype != tl.dtype:
                raise ValueError(
                    f"DriverState leaf {i}: snapshot has "
                    f"{sl.shape}/{sl.dtype}, expected {tl.shape}/{tl.dtype}")
            leaves.append(jnp.asarray(sl))
        state = jax.tree.unflatten(tdef, leaves)
        key = jnp.asarray(snap["key"])
        rows = [dict(r) for r in snap["rows"]]
        cursor = int(snap["cursor"])
        self._sanitize = bool(sanitize)
        if cursor >= n_rounds:
            return state, population, _stack_metrics(rows)
        cohorts = cohort_ids(self.spec.n_clients, self.cohort_size)
        self._ckpt_writer = _SnapshotWriter()
        audit = _resolve_audit(audit_keys)
        try:
            with (audit.activate() if audit is not None
                  else contextlib.nullcontext()):
                if mode == "sync":
                    return self._run_sync(state, data_fn, gammas, key,
                                          n_rounds, population, cohorts,
                                          eval_batch, eval_every,
                                          checkpoint_dir, checkpoint_every,
                                          kill_enabled=False,
                                          start_round=cursor, rows=rows)
                resume_ctx = self._decode_async_ctx(snap["async"], state.x)
                return self._run_async(state, data_fn, gammas, key, n_rounds,
                                       population, cohorts, eval_batch,
                                       eval_every, max_inflight,
                                       buffer_cohorts, delay_fn,
                                       checkpoint_dir, checkpoint_every,
                                       kill_enabled=False, start_round=cursor,
                                       rows=rows, resume_ctx=resume_ctx)
        finally:
            if self._ckpt_writer is not None:
                w, self._ckpt_writer = self._ckpt_writer, None
                w.flush()

    def _run_sync(self, state, data_fn, gammas, key, n_rounds, pop, cohorts,
                  eval_batch, eval_every, checkpoint_dir=None,
                  checkpoint_every=1, kill_enabled=True, start_round=0,
                  rows=None):
        faults = self.spec.faults
        rows = [] if rows is None else rows
        for t in range(start_round, n_rounds):
            # the EXACT api.run host key chain: (k_round, k_batch) per round
            key, k_round, k_batch = jax.random.split(key, 3)
            active, qkeys, fctx = self._draw_wave(k_round)
            buf = _PartialBuffer()
            for ci, (ids, valid) in enumerate(cohorts):
                partial, mask = self._run_cohort(state, t, k_batch, ids,
                                                 valid, active, qkeys, pop,
                                                 data_fn, fctx, ci)
                if self._defer_delivery:
                    # walk the cohort's pre-drawn retry ladder: each
                    # failed attempt bills its bytes; an exhausted ladder
                    # abandons the cohort (billed, never aggregated)
                    fail_row = fctx["fail_u"][ci]
                    a = 0
                    while (a < fail_row.shape[0]
                           and fail_row[a] < faults.cohort_fail):
                        buf.bill(partial.comm_bytes)
                        buf.retries += 1
                        a += 1
                    if a >= fail_row.shape[0]:
                        buf.abandoned += 1
                        continue
                    self._deliver(pop, partial, ids, mask, valid)
                buf.add(partial, 1.0)
            if (kill_enabled and faults is not None
                    and faults.kill_round == t):
                raise ServerKilled(t)
            pop.rounds_seen += 1
            state, m = self._land(state, buf, gammas[t], t, n_rounds,
                                  eval_batch, eval_every, k_round=k_round)
            rows.append(m)
            if checkpoint_dir is not None and (
                    (t + 1) % checkpoint_every == 0 or t == n_rounds - 1):
                self._save_checkpoint(checkpoint_dir, "sync", t + 1, key,
                                      state, pop, rows)
        return state, pop, _stack_metrics(rows)

    def _run_async(self, state, data_fn, gammas, key, n_rounds, pop, cohorts,
                   eval_batch, eval_every, max_inflight, buffer_cohorts,
                   delay_fn, checkpoint_dir=None, checkpoint_every=1,
                   kill_enabled=True, start_round=0, rows=None,
                   resume_ctx=None):
        spec = self.spec
        faults = spec.faults
        k_cohorts = len(cohorts)
        if max_inflight is None:
            max_inflight = k_cohorts
        if buffer_cohorts is None:
            buffer_cohorts = k_cohorts
        if max_inflight < 1 or buffer_cohorts < 1:
            raise ValueError("max_inflight and buffer_cohorts must be >= 1")
        if buffer_cohorts > max_inflight:
            raise ValueError(
                f"buffer_cohorts={buffer_cohorts} > max_inflight="
                f"{max_inflight} can never fill the buffer — the window "
                f"admits at most max_inflight unapplied cohorts")
        weight_fn = spec.staleness_weight or (lambda tau: 1.0)
        rows = [] if rows is None else rows
        updates = start_round
        if resume_ctx is None:
            inflight: list[_Inflight] = []
            pending = []        # cohort indices of the wave not yet launched
            wave = -1
            wave_ctx = None     # (k_batch, active, qkeys, fctx) of the wave
            order = 0
        else:
            inflight = resume_ctx["inflight"]
            pending = resume_ctx["pending"]
            wave = resume_ctx["wave"]
            wave_ctx = resume_ctx["wave_ctx"]
            order = resume_ctx["order"]
        landed = 0
        buf = _PartialBuffer()

        def prio(e: _Inflight) -> int:
            return (e.order + (delay_fn(e.order) if delay_fn else 0)
                    + e.extra)

        def uplink(e: _Inflight, must_land: bool):
            """Walk the entry's pre-drawn failure ladder at landing time.
            Returns the entry when its uplink succeeds; None when it
            re-entered the window (retry with ``retry_backoff`` extra
            landing delay, staleness clock INTACT) or its ladder ran
            out. ``must_land`` (force-drain) walks the remaining ladder
            in place so the staleness bound holds even under retry."""
            if e.fail_row is None:
                return e
            a = e.attempt
            n_att = len(e.fail_row)
            while a < n_att:
                if e.fail_row[a] >= faults.cohort_fail:
                    return e._replace(attempt=a)
                # this attempt failed AFTER using the wire
                buf.bill(e.partial.comm_bytes)
                buf.retries += 1
                a += 1
                if a < n_att and not must_land:
                    inflight.append(e._replace(
                        attempt=a, extra=e.extra + faults.retry_backoff))
                    return None
            buf.abandoned += 1
            return None

        while updates < n_rounds:
            # 1. keep the in-flight window full: compute cohorts EAGERLY
            #    against the CURRENT iterate (their staleness accrues as
            #    later updates land before they do). The window counts
            #    every cohort computed since the last APPLIED update
            #    (launched + buffered), so max_inflight = one population
            #    pass means no cross-update pipelining (the sync-exact
            #    default) and 2x a pass keeps one wave pre-computing
            #    against the stale iterate while the current wave lands.
            while len(inflight) + landed < max_inflight:
                if not pending:
                    key, k_round, k_batch_w = jax.random.split(key, 3)
                    wave += 1
                    wave_ctx = (k_batch_w,) + self._draw_wave(k_round)
                    pending = list(range(k_cohorts))
                ci = pending.pop(0)
                ids, valid = cohorts[ci]
                k_batch, active, qkeys, fctx = wave_ctx
                partial, mask = self._run_cohort(state, wave, k_batch, ids,
                                                 valid, active, qkeys, pop,
                                                 data_fn, fctx, ci)
                extra = 0
                fail_row = None
                if fctx is not None:
                    if bool(fctx["straggle"][ci]):
                        extra = faults.straggle_delay
                    if faults.cohort_fail > 0.0:
                        fail_row = np.array(fctx["fail_u"][ci], copy=True)
                inflight.append(_Inflight(updates, order, partial, wave,
                                          ci, 0, extra, mask, fail_row))
                order += 1
            # 2. land one cohort: anything over the staleness bound first
            #    (forced drain), else the delay-ordered head of the window
            if spec.max_staleness is not None:
                forced = [e for e in inflight
                          if updates - e.launch_updates >= spec.max_staleness]
            else:
                forced = []
            e = (min(forced, key=lambda e: e.order) if forced
                 else min(inflight, key=prio))
            inflight.remove(e)
            e = uplink(e, bool(forced))
            if e is None:
                continue
            tau = updates - e.launch_updates
            buf.add(e.partial, weight_fn(tau), tau)
            if self._defer_delivery:
                ids, valid = cohorts[e.cohort_idx]
                self._deliver(pop, e.partial, ids, e.mask, valid)
            landed += 1
            # 3. a full buffer triggers the server update — after draining
            #    every remaining over-bound cohort (bounded staleness: no
            #    in-flight cohort may outlive max_staleness updates)
            if landed >= buffer_cohorts:
                if spec.max_staleness is not None:
                    over = sorted(
                        (e2 for e2 in inflight
                         if updates - e2.launch_updates >= spec.max_staleness),
                        key=lambda e2: e2.order)
                    for e2 in over:
                        inflight.remove(e2)
                        e2 = uplink(e2, True)
                        if e2 is None:
                            continue
                        tau2 = updates - e2.launch_updates
                        buf.add(e2.partial, weight_fn(tau2), tau2)
                        if self._defer_delivery:
                            ids2, valid2 = cohorts[e2.cohort_idx]
                            self._deliver(pop, e2.partial, ids2, e2.mask,
                                          valid2)
                if (kill_enabled and faults is not None
                        and faults.kill_round == updates):
                    raise ServerKilled(updates)
                state, m = self._land(state, buf, gammas[updates], updates,
                                      n_rounds, eval_batch, eval_every)
                rows.append(m)
                updates += 1
                pop.rounds_seen += 1
                landed = 0
                buf = _PartialBuffer()
                if checkpoint_dir is not None and (
                        updates % checkpoint_every == 0
                        or updates == n_rounds):
                    self._save_checkpoint(
                        checkpoint_dir, "async", updates, key, state, pop,
                        rows, extra={"async": self._encode_async_ctx(
                            inflight, pending, wave, wave_ctx, order)})
        return state, pop, _stack_metrics(rows)
