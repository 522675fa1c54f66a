"""Whole federated experiments through ``repro.api.run``, back to back:
each call a fresh run of ``rounds_per_call`` rounds keyed from the seed and
the call's index, on client data and an initial statistic made once from
the seed. A call is what a user of the paper's experiments runs in a
sweep: the host key chain and batch draws, tracing and lowering of the
scanned rounds, the compiled program (from the compile cache after the
first call), and the rounds on the device.

Workload keys: ``rounds_per_call``, ``client_mode``, ``check_calls`` (how
many of the window's calls the reference re-runs, drawn from the seed),
``trace_seconds``, ``limits``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, precision, traffic


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.c, self.wl, self.seed = ctx.config, ctx.workload, ctx.seed

    def _reference(self, mm_name="highest", half_batch=False, alter=False):
        mod = self.ctx.load_reference(self.c["name"])
        return mod.Reference(self.c, self.wl, precision.MATMULS[mm_name],
                             precision=mm_name, half_batch=half_batch,
                             alter=alter)

    def prepare(self):
        """The inputs alone: client data, evaluation users and the initial
        statistic, from the seed."""
        c = self.c
        key = traffic.seed_key(self.seed)
        k_data, k_split, k_theta, self.k_runs = jax.random.split(key, 4)

        @jax.jit
        def make_data(k_data, k_split, k_theta):
            z = traffic.movielens_like(k_data, c["n_samples"], c["p"], c["K"])
            clients = traffic.heterogeneous_split(k_split, z, c["n_clients"])
            theta0 = jax.random.normal(k_theta, (c["p"], c["K"])) * 0.1
            return z, clients, theta0

        z, self.clients, theta0 = make_data(k_data, k_split, k_theta)
        self.z_eval = z[:c["eval_samples"]]
        ref = self._reference()
        with jax.default_matmul_precision("highest"):
            self.s0 = jax.jit(ref.s_bar)(z[:c["init_samples"]], theta0)
        del z

    def setup(self):
        from repro import api
        from repro.core import compression
        from repro.core.variational import DictLearnSpec, make_dictlearn

        c = self.c
        self.prepare()
        self.problem = api.as_problem(make_dictlearn(DictLearnSpec(
            p=c["p"], K=c["K"], lam=c["lam"], eta=c["eta"],
            ista_iters=c["ista_iters"])))
        self.spec = api.FederationSpec(
            n_clients=c["n_clients"], participation=c["participation"],
            alpha=c["alpha"],
            compressor=compression.block_quant(c["quant_bits"],
                                               c["quant_block"]))
        self.data_fn = traffic.client_minibatch_fn(
            self.clients, c["batch_size"], spans=self.ctx.spans)
        beta = c["beta_stepsize"]
        self.gamma = lambda t: beta / jnp.sqrt(beta + t)
        self.calls = {}
        self.call(0)                      # the cell's one program, warmed
        self.calls.clear()
        self.i = 1

    def run_key(self, i: int):
        return jax.random.fold_in(self.k_runs, i)

    def call(self, i: int):
        from repro import api
        st, hist = api.run(
            self.problem, self.s0, self.data_fn, self.gamma, spec=self.spec,
            key=self.run_key(i), n_rounds=self.wl["rounds_per_call"],
            eval_batch=self.z_eval, client_mode=self.wl["client_mode"])
        loss, hsq, x = jax.device_get((hist["loss"], hist["h_norm_sq"],
                                       st.x))
        self.calls[i] = {"loss": np.asarray(loss),
                         "h_norm_sq": np.asarray(hsq),
                         "x": {k: np.asarray(v) for k, v in x.items()}}
        return self.calls[i]

    def window(self, seconds: float) -> dict:
        spans = self.ctx.spans
        calls = failed = 0
        t0 = time.perf_counter()
        while True:
            with spans("api_run"):
                out = self.call(self.i)
            if not np.isfinite(out["loss"]).all():
                failed += 1
            self.i += 1
            calls += 1
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
        elapsed = now - t0
        rounds = calls * self.wl["rounds_per_call"]
        return {"elapsed": elapsed, "attempted": calls, "failed": failed,
                "units": rounds, "calls": calls,
                "e2e": {"rounds_per_s": rounds / elapsed}}

    def release(self):
        pass

    def reference_run(self, i: int, mm_name="highest", half_batch=False,
                      alter=False):
        ref = self._reference(mm_name, half_batch, alter)
        return ref.run(self.clients, self.s0, self.z_eval, self.run_key(i),
                       self.wl["rounds_per_call"])

    def readings(self, out: dict, ref: dict) -> dict:
        state = max(
            float(np.linalg.norm(out["x"][k].astype(np.float64)
                                 - ref["x"][k])
                  / max(np.linalg.norm(ref["x"][k]), 1e-30))
            if np.isfinite(out["x"][k]).all() else np.inf
            for k in ref["x"])
        return {"objective_gap": compare.max_rel_gap(out["loss"],
                                                     ref["loss"]),
                "state_gap": state,
                "first_h_gap": compare.rel_gap(out["h_norm_sq"][0],
                                               ref["h_norm_sq"][0])}

    def check(self) -> dict:
        """The reference re-runs a sample of the window's calls, drawn
        from the seed; each reading is the worst over the sample."""
        ids = sorted(self.calls)
        rng = np.random.default_rng(self.seed % (2 ** 63))
        pick = rng.choice(ids, size=min(self.wl["check_calls"], len(ids)),
                          replace=False)
        worst = {}
        for i in sorted(int(j) for j in pick):
            r = self.readings(self.calls[i], self.reference_run(i))
            for k, v in r.items():
                worst[k] = max(worst.get(k, 0.0), v)
        return worst

    def control_readings(self, i: int = 1) -> dict:
        """The readings of the control (the reference at ``high``, three
        bf16 passes, in the program's place) and of the faults planted in
        the reference, each against the reference, for run ``i``."""
        ref = self.reference_run(i)
        rf = self._reference()
        with jax.default_matmul_precision("highest"):
            l0 = float(jax.jit(lambda s: rf.objective(self.z_eval, rf.T(s)))(
                self.s0))
        unchanged = {"loss": np.full_like(ref["loss"], l0),
                     "h_norm_sq": ref["h_norm_sq"],
                     "x": {k: np.asarray(v) for k, v in self.s0.items()}}
        return {
            "control_high": self.readings(self.reference_run(i, "high"),
                                          ref),
            "fault_half_batch": self.readings(
                self.reference_run(i, half_batch=True), ref),
            "fault_answer_altered": self.readings(
                self.reference_run(i, alter=True), ref),
            "fault_state_unchanged": self.readings(unchanged, ref)}
