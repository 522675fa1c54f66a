"""Federated LM rounds through ``repro.fed.trainer.make_train_step``, one
round per host step as ``repro.launch.train`` drives it: make the round's
client batches on the device, run the compiled step, read the loss.

Workload keys: ``client_mode`` (logical: clients scanned in turn;
physical: clients vmapped together), ``uplink`` (gather | reduce: one
silo per chip on a ``("clients",)`` mesh of the cell's chips; absent: no
mesh), ``n_clients``, ``participation``, ``local_batch``, ``seq_len``,
``gamma`` (the step size
gamma_t = gamma / sqrt(1 + t)), ``token_skew``, ``frames_scale``,
``check_steps`` (the rounds that set-up drives and the reference follows),
``trace_seconds``, ``limits``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, traffic

ARCH_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab",
             "head_dim", "n_encoder_layers", "n_frontend_tokens", "dtype",
             "rope_theta")


def arch_config(config: dict):
    """The repo's architecture config with every size the configuration
    file states."""
    import repro.configs as RC
    return dataclasses.replace(RC.get(config["arch"]),
                               **{k: config[k] for k in ARCH_KEYS
                                  if k in config})


def init_params(shapes, key):
    """Seeded weights in the served dtype, made on the device in one
    jitted call: norm scales at 1, embedding and head rows N(0, 0.02^2),
    every other matrix N(0, 1/fan_in) with fan_in its second-to-last
    axis."""
    leaves, tdef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if name.endswith("['scale']"):
                x = jnp.ones(s.shape, jnp.float32)
            elif "embedding" in name:
                x = jax.random.normal(k, s.shape) * 0.02
            else:
                x = jax.random.normal(k, s.shape) / np.sqrt(s.shape[-2])
            out.append(x.astype(s.dtype))
        return jax.tree.unflatten(tdef, out)

    return jax.jit(make)(key)


def _tree_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.wl = ctx.config, ctx.workload
        self.seed = ctx.seed

    # -- inputs ---------------------------------------------------------------
    def _inputs(self):
        """The jitted per-round feed: round index -> (client batches, step
        key), all from the seed; and gamma_t on the host."""
        wl, cfg = self.wl, self.arch
        key = traffic.seed_key(self.seed)
        self.k_weights, k_data = jax.random.split(key)
        cdf = jax.jit(traffic.client_token_cdf, static_argnums=(0, 1, 2))(
            wl["n_clients"], cfg.vocab, wl["token_skew"])
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

        @jax.jit
        def feed(r):
            k = jax.random.fold_in(k_data, r)
            kb, ks = jax.random.split(k)
            batch = traffic.lm_round_batch(
                kb, cdf, wl["n_clients"], wl["local_batch"], wl["seq_len"],
                cfg.n_frontend_tokens, cfg.d_model, wl["frames_scale"], dt)
            return batch, ks

        self.feed = feed
        self.gamma = lambda r: np.float32(wl["gamma"] / np.sqrt(1.0 + r))

    def batch_at(self, r):
        batch, k = self.feed(r)
        return batch, k, self.gamma(r)

    # -- set-up ---------------------------------------------------------------
    def prepare(self):
        """The inputs alone: the model's shapes and the seeded feed."""
        from repro.models.model import build_model
        self.arch = arch_config(self.config)
        self.model = build_model(self.arch)
        self._inputs()

    def setup(self):
        from repro.fed import trainer as FT

        wl, config = self.wl, self.config
        self.prepare()
        f = config["fedmm"]
        fcfg = FT.FedLMConfig(
            n_clients=wl["n_clients"], rho=f["rho"],
            weight_decay=f["weight_decay"], p=wl["participation"],
            alpha=f["alpha"], quant_bits=f["quant_bits"],
            quant_block=f["quant_block"], quant_dither=f["quant_dither"],
            use_cv=f["use_cv"], client_mode=wl["client_mode"])
        mesh_kw = {}
        if wl.get("uplink"):
            from jax.sharding import Mesh
            mesh_kw = dict(mesh=Mesh(np.asarray(self.ctx.devices),
                                     ("clients",)), uplink=wl["uplink"])
        self.step = jax.jit(FT.make_train_step(self.model, fcfg, **mesh_kw))
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        params = init_params(shapes, self.k_weights)
        n = wl["n_clients"]
        state = FT.FedLMState(
            s_hat=params, v=jax.tree.map(jnp.zeros_like, params),
            v_i=jax.tree.map(lambda x: jnp.zeros((n,) + x.shape, x.dtype),
                             params),
            step=jnp.asarray(0))
        self.names = [jax.tree_util.keystr(p) for p, _ in
                      jax.tree_util.tree_leaves_with_path(params)]
        norms = jax.jit(_tree_norms)
        diff_norms = jax.jit(lambda a, b: _tree_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
        # the first rounds, through the window's own step and feed: the
        # reference follows exactly these
        losses = []
        for r in range(wl["check_steps"]):
            batch, k, g = self.batch_at(r)
            state, m = self.step(state, batch, k, g)
            losses.append(float(m["loss"]))
            if r == 0:
                agg = np.asarray(norms(state.v)) * (wl["participation"]
                                                    / f["alpha"])
        change = np.asarray(diff_norms(state.s_hat, params))
        del params
        self.capture = {"loss": losses,
                        "agg_norms": dict(zip(self.names, map(float, agg))),
                        "change_norms": dict(zip(self.names,
                                                 map(float, change)))}
        self.state, self.r = state, wl["check_steps"]
        self.tokens_per_client = wl["local_batch"] * wl["seq_len"]

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float) -> dict:
        spans = self.ctx.spans
        state, r = self.state, self.r
        rounds = tokens = failed = 0
        t0 = time.perf_counter()
        while True:
            with spans("batch"):
                batch, k = self.feed(r)
            with spans("dispatch"):
                state, m = self.step(state, batch, k, self.gamma(r))
            with spans("loss_read"):
                loss, n_active = jax.device_get((m["loss"], m["n_active"]))
            now = time.perf_counter()
            rounds += 1
            r += 1
            if not np.isfinite(loss):
                failed += 1
            tokens += int(round(float(n_active))) * self.tokens_per_client
            if now - t0 >= seconds:
                break
        elapsed = now - t0
        self.state, self.r = state, r
        return {"elapsed": elapsed, "attempted": rounds, "failed": failed,
                "units": rounds, "tokens": tokens,
                "e2e": {"tokens_per_s": tokens / elapsed}}

    def release(self):
        self.state = None

    # -- correctness ----------------------------------------------------------
    def reference_capture(self, mm_name="highest", half_batch=False) -> dict:
        from bench import precision
        ref = self.ctx.load_reference(self.config["name"]).Reference(
            self.config, self.wl, precision.MATMULS[mm_name],
            half_batch=half_batch)
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        params0 = init_params(shapes, self.k_weights)
        return ref.capture(params0, self.batch_at, self.wl["check_steps"])

    def readings(self, capture: dict, ref: dict) -> dict:
        loss = compare.max_rel_gap(capture["loss"], ref["loss"])
        grad, grad_leaf = compare.leaf_norm_gap(capture["agg_norms"],
                                                ref["agg_norms"])
        keep = compare.moved_leaves(ref["agg_norms"])
        change, change_leaf = compare.leaf_norm_gap(
            capture["change_norms"], ref["change_norms"], keep)
        self.notes = [
            f"losses {capture['loss']} reference {ref['loss']}",
            f"grad_gap worst leaf {grad_leaf}: "
            f"{capture['agg_norms'].get(grad_leaf)} reference "
            f"{ref['agg_norms'].get(grad_leaf)}",
            f"change_gap worst leaf {change_leaf}: "
            f"{capture['change_norms'].get(change_leaf)} reference "
            f"{ref['change_norms'].get(change_leaf)}"]
        return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}

    def check(self) -> dict:
        return self.readings(self.capture, self.reference_capture())

    def control_readings(self) -> dict:
        """The readings of the control (the reference one precision step
        below the configuration's, in the program's place) and of the
        faults planted in the reference, each against the reference."""
        ref = self.reference_capture()
        zeros = {k: 0.0 for k in ref["agg_norms"]}
        unchanged = {"loss": [ref["loss"][0]] * len(ref["loss"]),
                     "agg_norms": zeros, "change_norms": zeros}
        return {
            "control_fp8": self.readings(self.reference_capture("fp8"), ref),
            "fault_half_batch": self.readings(
                self.reference_capture(half_batch=True), ref),
            "fault_state_unchanged": self.readings(unchanged, ref)}
